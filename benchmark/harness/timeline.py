"""One timeline: the device ops of a ``jax.profiler`` trace joined to the
names the PROGRAM gave them, and the program's own host spans beside them.

``harness/trace.py`` labels a device op from outside (HLO opcode + result
shape).  Since PR 25 the program names its work itself: every Pallas kernel
carries ``pl.pallas_call(name=...)``, every instruction the halo exchange
adds sits under an ``exchange.<axis>`` named scope, and ``telemetry.span``
writes the program's host spans into the profiler's own trace.  All three
end up in the ``*.xplane.pb``:

* a device op on the "XLA Ops" line is named by its whole HLO line; the
  ``%name`` that starts it finds the instruction in the module's ``Hlo Proto``
  (a stat of the ``/host:metadata`` plane; the module is the "XLA Modules"
  event that covers the op), whose ``metadata.op_name`` is the scope path the
  program traced it under: ``jit(exchange)/shard_map/exchange.z/blend_slab/
  pallas_call``.  A Pallas kernel's name is the component before
  ``pallas_call``.
* instructions the compiler adds itself (layout copies, async slices,
  ``ConcatBitcast``) carry no ``op_name``.  They INHERIT one from the first
  named instruction that uses their result (searching on through unnamed
  users), else from the first named producer of an operand: a layout copy
  between ``exchange.z/slice`` and ``exchange.z/reshape`` is exchange work.
  Inherited ops are flagged, and ``summary()`` says how much device time was
  attributed that way.
* host spans are the events of ``/host:*`` planes whose names are in
  ``harness/program_names.json`` (the program's span registry, kept equal to
  it by a tier-1 test; this file imports nothing of ``stencil_tpu``).

A timeline is plain data (what ``harness/fixture_timeline.json`` records the
raw form of):

    {"workload": "<cell>" or None, "named": bool,
     "devices": {"<plane>": [[label, start_ns, dur_ns, kernel | None,
                              scope_path, inherited], ...]},
     "host": [[name, start_ns, dur_ns, {arg: value}], ...]}

``label`` is ``trace.op_label``'s (so the old and the new metrics select from
the same ops); ``named`` says whether any Pallas op carries a registered
kernel name -- on a program from before PR 25 none does, and every reducer
over names then reads nothing.

Heavy imports (``google.protobuf``, the 19-95 MB parse) happen inside
``load()`` only, which reducers call after the window has closed; the result
is cached per process.
"""

from __future__ import annotations

import functools
import glob
import json
import os
import re

from benchmark.harness import trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TRACE_ROOT = os.path.join(ROOT, ".bench_out", "trace")
MODULES_LINE = "XLA Modules"
HLO_STAT = "Hlo Proto"
PALLAS_LABEL = re.compile(r"^custom-call(-inplace)?_")
PALLAS_TAIL = "pallas_call"


@functools.lru_cache(maxsize=None)
def program_names() -> dict:
    """The program's registry as the benchmark knows it (a data file)."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "program_names.json")) as f:
        return json.load(f)


# --- reading the xplane ----------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _schema():
    """(XSpace, HloProto) message classes for the handful of fields read
    here, built from descriptors so nothing but ``google.protobuf`` is
    needed (the tensorflow ``_pb2`` modules may be absent, and importing
    tensorflow beside a live TPU client is not worth the risk).  Field
    numbers are those of tsl/profiler/protobuf/xplane.proto and
    xla/service/hlo.proto; unknown fields are skipped by the parser."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    pkg = "bench_timeline"
    fd = descriptor_pb2.FileDescriptorProto(name="bench_timeline.proto", package=pkg, syntax="proto3")

    def message(name, *fields):
        m = fd.message_type.add(name=name)
        for fname, number, ftype, repeated in fields:
            f = m.field.add(name=fname, number=number,
                            label=F.LABEL_REPEATED if repeated else F.LABEL_OPTIONAL)
            if isinstance(ftype, str):
                f.type, f.type_name = F.TYPE_MESSAGE, f".{pkg}.{ftype}"
            else:
                f.type = ftype

    message("XStat", ("metadata_id", 1, F.TYPE_INT64, 0), ("double_value", 2, F.TYPE_DOUBLE, 0),
            ("uint64_value", 3, F.TYPE_UINT64, 0), ("int64_value", 4, F.TYPE_INT64, 0),
            ("str_value", 5, F.TYPE_STRING, 0), ("bytes_value", 6, F.TYPE_BYTES, 0),
            ("ref_value", 7, F.TYPE_UINT64, 0))
    message("XEvent", ("metadata_id", 1, F.TYPE_INT64, 0), ("offset_ps", 2, F.TYPE_INT64, 0),
            ("duration_ps", 3, F.TYPE_INT64, 0), ("stats", 4, "XStat", 1))
    message("XLine", ("name", 2, F.TYPE_STRING, 0), ("timestamp_ns", 3, F.TYPE_INT64, 0),
            ("events", 4, "XEvent", 1))
    message("XEventMetadata", ("id", 1, F.TYPE_INT64, 0), ("name", 2, F.TYPE_STRING, 0),
            ("stats", 5, "XStat", 1))
    message("XStatMetadata", ("id", 1, F.TYPE_INT64, 0), ("name", 2, F.TYPE_STRING, 0))
    # map<int64, X> fields are repeated {key = 1, value = 2} entries on the wire
    message("EventMetadataEntry", ("key", 1, F.TYPE_INT64, 0), ("value", 2, "XEventMetadata", 0))
    message("StatMetadataEntry", ("key", 1, F.TYPE_INT64, 0), ("value", 2, "XStatMetadata", 0))
    message("XPlane", ("name", 2, F.TYPE_STRING, 0), ("lines", 3, "XLine", 1),
            ("event_metadata", 4, "EventMetadataEntry", 1),
            ("stat_metadata", 5, "StatMetadataEntry", 1))
    message("XSpace", ("planes", 1, "XPlane", 1))
    message("OpMetadata", ("op_name", 2, F.TYPE_STRING, 0))
    message("HloInstructionProto", ("name", 1, F.TYPE_STRING, 0), ("opcode", 2, F.TYPE_STRING, 0),
            ("metadata", 7, "OpMetadata", 0), ("id", 35, F.TYPE_INT64, 0),
            ("operand_ids", 36, F.TYPE_INT64, 1))
    message("HloComputationProto", ("name", 1, F.TYPE_STRING, 0),
            ("instructions", 2, "HloInstructionProto", 1))
    message("HloModuleProto", ("name", 1, F.TYPE_STRING, 0),
            ("computations", 3, "HloComputationProto", 1))
    message("HloProto", ("hlo_module", 1, "HloModuleProto", 0))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    get = lambda n: message_factory.GetMessageClass(pool.FindMessageTypeByName(f"{pkg}.{n}"))  # noqa: E731
    return get("XSpace"), get("HloProto")


def _stat_value(stat, stat_names):
    if stat.str_value:
        return stat.str_value
    if stat.ref_value:
        return stat_names.get(stat.ref_value, stat.ref_value)
    for field in ("int64_value", "uint64_value", "double_value"):
        v = getattr(stat, field)
        if v:
            return v
    return 0


def _hlo_module(blob: bytes) -> dict:
    """{instruction name: {"opcode", "op_name", "operands": [names]}} of one
    ``Hlo Proto`` (every computation: while bodies hold most of the ops)."""
    _, HloProto = _schema()
    proto = HloProto.FromString(blob)
    by_id, out = {}, {}
    for comp in proto.hlo_module.computations:
        for inst in comp.instructions:
            by_id[inst.id] = inst.name
    for comp in proto.hlo_module.computations:
        for inst in comp.instructions:
            out[inst.name] = {
                "opcode": inst.opcode, "op_name": inst.metadata.op_name,
                "operands": [by_id[i] for i in inst.operand_ids if i in by_id],
            }
    return out


def read_xplane(path: str) -> dict:
    """The raw form of a timeline (what the fixture records): per device
    plane its "XLA Ops" and "XLA Modules" events, every module's HLO name
    map, and the host events whose names the registry knows."""
    XSpace, _ = _schema()
    with open(path, "rb") as f:
        space = XSpace.FromString(f.read())
    names = program_names()
    keep = set(names["spans"]) | set(names["jax_compile_events"])
    raw = {"ops": {}, "modules": {}, "hlo": {}, "host": []}
    for plane in space.planes:
        meta = {e.key: e.value for e in plane.event_metadata}
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        if plane.name == "/host:metadata":
            for m in meta.values():
                for stat in m.stats:
                    if stat_names.get(stat.metadata_id) == HLO_STAT and stat.bytes_value:
                        raw["hlo"][m.name] = _hlo_module(stat.bytes_value)
        elif trace.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name not in (trace.OPS_LINE, MODULES_LINE):
                    continue
                events = [
                    [meta[e.metadata_id].name, line.timestamp_ns + e.offset_ps / 1e3, e.duration_ps / 1e3]
                    for e in line.events
                ]
                raw["ops" if line.name == trace.OPS_LINE else "modules"][plane.name] = events
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    name = meta[e.metadata_id].name
                    if name in keep or name.startswith(trace.HOST_PREFIX):
                        args = {stat_names.get(s.metadata_id, str(s.metadata_id)): _stat_value(s, stat_names)
                                for s in e.stats}
                        raw["host"].append(
                            [name, line.timestamp_ns + e.offset_ps / 1e3, e.duration_ps / 1e3, args])
    return raw


# --- the join ---------------------------------------------------------------------


def _resolve(module: dict) -> dict:
    """{instruction: (op_name, inherited)} with compiler-added instructions
    (no ``op_name``) inheriting from their nearest named user, else their
    nearest named producer."""
    users = {}
    for name, inst in module.items():
        for operand in inst["operands"]:
            users.setdefault(operand, []).append(name)

    def nearest(start, edges):
        seen, frontier = {start}, [start]
        while frontier:
            nxt = []
            for node in frontier:
                for other in edges(node):
                    if other in seen or other not in module:
                        continue
                    if module[other]["op_name"]:
                        return module[other]["op_name"]
                    seen.add(other)
                    nxt.append(other)
            frontier = nxt
        return ""

    out = {}
    for name, inst in module.items():
        if inst["op_name"]:
            out[name] = (inst["op_name"], False)
            continue
        found = nearest(name, lambda n: users.get(n, ())) or nearest(
            name, lambda n: module[n]["operands"])
        out[name] = (found, bool(found))
    return out


def _module_for(modules: dict, full: str):
    """An "XLA Modules" event is named ``<module>(<program id>)``, and so is
    the metadata plane's entry; fall back on the bare module name."""
    if full in modules:
        return modules[full]
    base = full.split("(")[0]
    same = [m for name, m in modules.items() if name.split("(")[0] == base]
    return same[0] if len(same) == 1 else None


def build(raw: dict, workload=None) -> dict:
    """Raw xplane contents -> a timeline (see the module docstring)."""
    import bisect

    resolved = {name: _resolve(module) for name, module in raw["hlo"].items()}
    kernels = set(program_names()["kernels"])
    devices, named = {}, False
    for plane, ops in raw["ops"].items():
        spans = sorted(raw["modules"].get(plane, ()), key=lambda m: m[1])
        starts = [m[1] for m in spans]
        rows = []
        for text, start, dur in ops:
            label = trace.op_label(text)
            k = bisect.bisect_right(starts, start) - 1
            module = None
            if k >= 0 and start < spans[k][1] + spans[k][2]:
                module = _module_for(resolved, spans[k][0])
            inst = text.partition(" = ")[0].strip().lstrip("%")
            if module is None:  # no module line (or an op outside every module): by instruction name
                holders = [m for m in resolved.values() if inst in m]
                module = holders[0] if len(holders) == 1 else None
            scope, inherited = (module or {}).get(inst, ("", False))
            kernel = None
            if PALLAS_LABEL.match(label) and not inherited:
                parts = scope.split("/")
                if len(parts) >= 2 and parts[-1] == PALLAS_TAIL:
                    kernel = parts[-2]
                    named = named or kernel in kernels
            rows.append([label, start, dur, kernel, scope, inherited])
        devices[plane] = rows
    return {"workload": workload, "named": named, "devices": devices,
            "host": sorted(raw["host"], key=lambda h: h[1])}


def newest_xplane():
    """(path, workload) of the newest ``*.xplane.pb`` under ``.bench_out/trace/``
    -- the harness empties a cell's trace directory before it traces, so the
    newest file is the running cell's; the cell is the directory's name."""
    files = glob.glob(os.path.join(TRACE_ROOT, "**", "*.xplane.pb"), recursive=True)
    if not files:
        return None, None
    path = max(files, key=os.path.getmtime)
    return path, os.path.relpath(path, TRACE_ROOT).split(os.sep)[0]


@functools.lru_cache(maxsize=2)
def _load(path: str, mtime: float, workload) -> dict:
    del mtime  # part of the key: a re-traced cell is read anew
    return build(read_xplane(path), workload)


def load():
    """The timeline of the newest trace, parsed once per process; None where
    there is no trace."""
    path, workload = newest_xplane()
    if path is None:
        return None
    return _load(path, os.path.getmtime(path), workload)


# --- what the reducers share ----------------------------------------------------


def select(ops, label=(), not_label=(), kernel=(), scope=(), not_scope=(), inherit=True):
    """The leaf ops that pass every given test: ``label`` / ``not_label``
    regexes on the op's label, ``kernel`` on its kernel name (an op without
    one fails), ``scope`` / ``not_scope`` on its scope path (an inherited
    path counts unless ``inherit`` is false)."""
    comp = lambda ps: [re.compile(p) for p in ps]  # noqa: E731
    label, not_label, kernel, scope, not_scope = map(comp, (label, not_label, kernel, scope, not_scope))
    out = []
    for op in trace.leaf_ops(ops):
        path = op[4] if (inherit or not op[5]) else ""
        if label and not any(p.search(op[0]) for p in label):
            continue
        if any(p.search(op[0]) for p in not_label):
            continue
        if kernel and not (op[3] and any(p.search(op[3]) for p in kernel)):
            continue
        if scope and not any(p.search(path) for p in scope):
            continue
        if any(p.search(path) for p in not_scope):
            continue
        out.append(op)
    return out


def host_spans(tl: dict, name: str) -> list:
    return [h for h in tl["host"] if h[0] == name]


def summary(tl: dict) -> dict:
    """What one traced run did, for the info line: the program's dispatches
    as its own spans count them against the harness's ``bench.enqueue``s,
    the rate over the traced stretch, and how much device time was named
    by inheritance."""
    enq = host_spans(tl, trace.HOST_PREFIX + "enqueue")
    blk = host_spans(tl, trace.HOST_PREFIX + "block")
    steps = host_spans(tl, "domain.step")
    exchanges = host_spans(tl, "domain.exchange")
    out = {
        "workload": tl["workload"], "named": tl["named"],
        "bench_enqueue_spans": len(enq), "bench_block_spans": len(blk),
        "domain_step_spans": len(steps),
        "domain_step_steps_sum": sum(int(h[3].get("steps", 0)) for h in steps),
        "domain_exchange_spans": len(exchanges),
        "domain_exchange_count_sum": sum(int(h[3].get("count", 0)) for h in exchanges),
    }
    if enq and blk:
        out["traced_elapsed_s"] = (max(h[1] + h[2] for h in blk) - min(h[1] for h in enq)) / 1e9
    busy = inherited = unnamed = 0.0
    for ops in tl["devices"].values():
        leaves = trace.leaf_ops(ops)
        busy += trace.busy_ns(leaves)
        inherited += trace.busy_ns([o for o in leaves if o[5]])
        unnamed += trace.busy_ns([o for o in leaves if not o[4]])
    if busy:
        out["inherited_scope_pct"] = 100.0 * inherited / busy
        out["no_scope_pct"] = 100.0 * unnamed / busy
    return out


def for_ctx(ctx: dict):
    """The timeline a reducer reads: the one a test put into ``ctx``, else
    the newest trace's (the harness's ``ctx`` carries none)."""
    return ctx["timeline"] if "timeline" in ctx else load()


def say_summary(tl: dict) -> None:
    """The ``{"bench": "timeline", ...}`` info line of a traced run (one
    ``span_percentile`` metric applies per cell, so it prints once): the
    program's own count of its work against the harness's (``domain.step``
    spans' ``steps`` summed, or ``domain.exchange`` calls, against
    ``bench.enqueue`` spans x the configuration's dispatch size) and the rate
    over the traced stretch alone (the cost of tracing on, PERF.md)."""
    out = summary(tl)
    try:
        from benchmark.harness import window

        cell, config, traffic = window.find_cell(tl["workload"])
        n = config["dispatch"][cell["traffic"]]
        did = (out["domain_step_steps_sum"] if traffic["work"] == "cell_updates"
               else out["domain_exchange_count_sum"])
        out["dispatch_size"] = n
        out["program_counted"] = did
        out["harness_counted"] = out["bench_enqueue_spans"] * n
        out["counts_agree"] = did == out["harness_counted"]
        if out.get("traced_elapsed_s") and tl["devices"]:  # a rate is a device number: never from a CPU rehearsal
            e2e = traffic["end_to_end"]
            rate = window.work_per_dispatch(config, traffic, n) * out["bench_block_spans"] / out["traced_elapsed_s"]
            out["traced_rate"] = {"name": e2e["name"], "value": rate * e2e["scale"], "unit": e2e["unit"]}
    except (SystemExit, KeyError, TypeError, OSError) as e:  # a fixture's cell, a rehearsal's override
        out["dispatch_size"] = f"unknown ({type(e).__name__})"
    print(json.dumps({"bench": "timeline", **out}, default=str), flush=True)
