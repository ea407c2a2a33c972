"""The wires of a traced run: every ``collective-permute`` of a device plane as
an interval of its own, from its start to its done, joined to the scope the
PROGRAM traced it under, beside the compute the chip ran meanwhile -- the
exchange's own and everybody else's.

On the "XLA Ops" line a wire is two ops, ``collective-permute-start`` (the
issue, a microsecond or two) and ``collective-permute-done`` (the WAIT for
what has not arrived).  The flight is the start op's begin to the done op's
end, the two paired through the operand the done's HLO line names.  The
profiler draws the same interval on a third line, "Async XLA Ops" -- on a
four-chip v5e host on ``/device:TPU:0`` alone, and there begin for begin and
end for end what the pair gives (my chip runs, PR 49, call 178: the five
shares agreed to the last digit) -- so the pair is the one source.  The
interval ends when the WAIT ends: an upper bound on the transfer, so a share
taken over it can only read low.

Nothing is parsed twice over: the ops, their scopes and the host spans are
``harness/timeline.py``'s (``read_xplane`` for the HLO lines, ``build`` for
the scope every op inherits), cut down by ``compact`` to what the shares need
-- the raw form ``harness/fixture_wires.json`` records from a real four-chip
trace:

    {"ops": {"<plane>": [[name, start_ns, dur_ns, scope], ...]},
     "modules": {"<plane>": [[module, start_ns, dur_ns], ...]},
     "host": [[name, start_ns, dur_ns, {arg: value}], ...]}

``name`` is the whole HLO line of a ``collective-permute*`` and
``trace.op_label``'s label of every other op.  ``build`` makes of it a wires
timeline:

    {"workload": "<cell>" or None,
     "devices": {"<plane>": {
         "flights": [{"inst", "start", "end", "bytes", "scope", "hop", "stage",
                      "self", "run"}, ...],
         "busy": [[start, end], ...],     # union of every op, merged
         "kernel": [[start, end], ...],   # leaf compute OUTSIDE every exchange.* scope
         "own": [[start, end], ...],      # leaf compute UNDER one: the exchange's pack, unpack, blend
         "runs": [[module, start, end], ...]}},   # the "XLA Modules" events
     "host": [...]}                       # domain.step / domain.exchange

``hop`` is ``x.low`` ... from the ``exchange.<axis>.low|high`` scope around
the ``ppermute`` (None under no such scope), ``stage`` the enclosing
``step.stage.<k>``; ``bytes`` the result the done op hands on (what the chip
RECEIVED, from its shape); ``self`` a permute whose every pair sends a shard
to itself (an unsplit mesh axis: no wire, left out of every share); ``run``
the index of the module execution that holds the flight's start.  Compute is
a leaf op that is no collective (no ``-start``, ``-done``, ``send``, ``recv``
...): under the ``kernel`` part of a flight the step's own work hides the
wire, under the ``own`` part the exchange is still what the chip does.

Imports nothing of ``stencil_tpu``; heavy imports happen inside ``load()``.
"""

from __future__ import annotations

import bisect
import functools
import os
import re

from benchmark.harness import timeline, trace

WIRE = re.compile(r"^collective-permute")
#: ops that are no compute: while one runs the chip waits for, or issues, a transfer
COLLECTIVE = re.compile(r"^(collective-permute|all-to-all|all-gather|all-reduce|reduce-scatter|send|recv)")
EXCHANGE = re.compile(r"(?:^|/)exchange\.")
DIRECTION = re.compile(r"(?:^|/)exchange\.([xyz])\.(low|high)(?:/|$)")
STAGE = re.compile(r"(?:^|/)step\.stage\.(\d+)(?:/|$)")
SPANS = ("domain.step", "domain.exchange")
_ARRAY = re.compile(r"\b(pred|[suf]\d+|bf16|c64|c128)\[([\d,]*)\]")
_PAIRS = re.compile(r"source_target_pairs=\{(.*?)\}\}")
_STARTED_BY = re.compile(r" collective-permute-done\(.*?%([\w.\-]+)\)")
_BYTES = {"pred": 1, "bf16": 2, "c64": 8, "c128": 16}


# --- from the timeline's raw form ----------------------------------------------------


def _instruction(text: str) -> str:
    return text.partition(" = ")[0].strip().lstrip("%")


def _opcode(text: str) -> str:
    """The HLO opcode of a whole HLO line; "" of a label."""
    m = trace._HLO.match(trace._LAYOUT.sub("", text.partition(" = ")[2]))
    return m.group(2) if m else ""


def compact(raw: dict, rows: dict = None) -> dict:
    """``timeline.read_xplane``'s raw contents -> the wires' raw form (module
    docstring).  ``rows`` are ``timeline.build(raw)["devices"]``, op for op
    beside ``raw["ops"]`` (``load`` hands over the cached ones)."""
    rows = timeline.build(raw)["devices"] if rows is None else rows
    ops = {
        plane: [
            [text if WIRE.match(_opcode(text)) else row[0], start, dur, row[4]]
            for (text, start, dur), row in zip(events, rows[plane])
        ]
        for plane, events in raw["ops"].items()
    }
    return {"ops": ops, "modules": raw["modules"], "host": [h for h in raw["host"] if h[0] in SPANS]}


# --- intervals --------------------------------------------------------------------


def merged(intervals) -> list:
    """Union of ``[start, end]`` pairs as sorted, disjoint pairs."""
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def overlap(a, b) -> list:
    """Intersection of two merged lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            out.append([lo, hi])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def span_ns(intervals) -> float:
    return sum(end - start for start, end in intervals)


def covered_ns(intervals, ends, lo: float, hi: float) -> float:
    """How much of ``[lo, hi]`` a merged list covers; ``ends`` its intervals'
    ends (sorted as they are), so that one flight costs a bisection and the
    few intervals under it, not the whole list."""
    total, k = 0.0, bisect.bisect_right(ends, lo)
    while k < len(intervals) and intervals[k][0] < hi:
        total += min(intervals[k][1], hi) - max(intervals[k][0], lo)
        k += 1
    return total


# --- the join ---------------------------------------------------------------------


def result_bytes(text: str) -> int:
    """Bytes of the arrays a ``collective-permute-done`` hands on, from the
    result shape of its HLO line."""
    result = trace._LAYOUT.sub("", text.partition(" = ")[2])
    result = result[: result.index(" collective-permute")] if " collective-permute" in result else result
    total = 0
    for dtype, dims in _ARRAY.findall(result):
        cells = 1
        for d in dims.split(",") if dims else ():
            cells *= int(d)
        total += cells * (_BYTES.get(dtype) or int(re.sub(r"\D", "", dtype)) // 8)
    return total


def sends_to_itself(text: str) -> bool:
    m = _PAIRS.search(text)
    if not m:
        return False
    pairs = re.findall(r"(\d+),(\d+)", m.group(1))
    return bool(pairs) and all(src == dst for src, dst in pairs)


def build(raw: dict, workload=None) -> dict:
    """The wires' raw form -> a wires timeline (module docstring).  A done
    whose start the trace missed, and a start whose done it missed, are no
    flight."""
    devices = {}
    for plane, ops in raw["ops"].items():
        runs = sorted(raw["modules"].get(plane, ()), key=lambda m: m[1])
        run_starts = [m[1] for m in runs]

        def run_of(t):
            k = bisect.bisect_right(run_starts, t) - 1
            return k if k >= 0 and t < runs[k][1] + runs[k][2] else None

        flights, pending = [], {}
        for text, start, dur, scope in sorted((o for o in ops if " = " in o[0]), key=lambda o: o[1]):
            opcode = _opcode(text)
            if opcode.endswith("-start"):
                pending[_instruction(text)] = (text, start, scope)
            elif opcode.endswith("-done"):
                named = _STARTED_BY.search(text)
                begun = pending.pop(named.group(1), None) if named else None
                if begun is None:
                    continue
                hop, stage = DIRECTION.search(begun[2]), STAGE.search(begun[2])
                flights.append({
                    "inst": _instruction(begun[0]), "start": begun[1], "end": start + dur,
                    "bytes": result_bytes(text), "scope": begun[2],
                    "hop": f"{hop.group(1)}.{hop.group(2)}" if hop else None,
                    "stage": int(stage.group(1)) if stage else None,
                    "self": sends_to_itself(begun[0]), "run": run_of(begun[1]),
                })
        compute = [o for o in trace.leaf_ops(ops)
                   if not COLLECTIVE.match(_opcode(o[0]) if " = " in o[0] else o[0])]
        devices[plane] = {
            "flights": flights,
            "busy": merged([o[1], o[1] + o[2]] for o in ops),
            "kernel": merged([o[1], o[1] + o[2]] for o in compute if not EXCHANGE.search(o[3])),
            "own": merged([o[1], o[1] + o[2]] for o in compute if EXCHANGE.search(o[3])),
            "runs": [[m[0], m[1], m[1] + m[2]] for m in runs],
        }
    return {"workload": workload, "devices": devices, "host": sorted(raw["host"], key=lambda h: h[1])}


@functools.lru_cache(maxsize=2)
def _load(path: str, mtime: float, workload) -> dict:
    del mtime  # part of the key: a re-traced cell is read anew
    return build(compact(timeline.read_xplane(path), timeline.load()["devices"]), workload)


def load():
    """The wires timeline of the newest trace, parsed once per process; None
    where there is no trace."""
    path, workload = timeline.newest_xplane()
    if path is None:
        return None
    return _load(path, os.path.getmtime(path), workload)


def for_ctx(ctx: dict):
    """The wires timeline a reducer reads: the one a test put into ``ctx``,
    else the newest trace's."""
    return ctx["wires"] if "wires" in ctx else load()


# --- what the reducers share ----------------------------------------------------


def wires_of(device: dict) -> list:
    """The flights that cross to another chip."""
    return [f for f in device["flights"] if not f["self"]]


def said_bytes(tl: dict):
    """``(bytes one shard receives over wires per dispatch, raw steps or
    exchanges per dispatch)`` as the program's spans say them: ``wire_bytes``
    x ``steps`` of a ``domain.step`` span, x ``count`` of a ``domain.
    exchange`` span -- the commonest value among the traced spans of the kind
    the cell runs (steps where it runs any).  None where no span says
    ``wire_bytes`` (a program from before PR 49, outside the plane route)."""
    for name, per in (("domain.step", "steps"), ("domain.exchange", "count")):
        said = [
            (int(h[3]["wire_bytes"]) * int(h[3].get(per, 1)), int(h[3].get(per, 1)))
            for h in tl["host"] if h[0] == name and "wire_bytes" in h[3]
        ]
        if said:
            return max(set(said), key=said.count)
    return None


def whole_runs(device: dict) -> dict:
    """{run index: its wires} for the module executions the trace holds
    whole: those with as many wires as the fullest execution of that module
    (the trace's edges cut the first and the last)."""
    by_run = {}
    for f in wires_of(device):
        if f["run"] is not None:
            by_run.setdefault(f["run"], []).append(f)
    fullest = {}
    for k, flights in by_run.items():
        name = device["runs"][k][0]
        fullest[name] = max(fullest.get(name, 0), len(flights))
    return {k: fl for k, fl in by_run.items() if len(fl) == fullest[device["runs"][k][0]]}


def table(tl: dict) -> dict:
    """The ``{"bench": "wires"}`` info line: per hop and, for a staged step,
    per stage -- wires and received bytes a step (from the device's shapes; the
    program's ``wire_bytes`` beside their sum), microseconds a step in flight
    and, of those, ``hidden`` under the step's kernels, under the exchange's
    ``own`` compute, and ``exposed`` (neither: the chip issues or waits), and
    GB/s over the flight -- over the whole module executions of every chip.
    Every interval is a start/done pair of "XLA Ops"."""
    said = said_bytes(tl)
    parts = ("inflight", "hidden", "own")
    rows, totals, steps = {}, dict.fromkeys(parts, 0.0), 0
    for device in tl["devices"].values():
        runs = whole_runs(device)
        steps += len(runs) * (said[1] if said else 1)
        everything = [f for flights in runs.values() for f in flights]
        union = merged([f["start"], f["end"]] for f in everything)
        under = {"hidden": device["kernel"], "own": device["own"]}
        totals["inflight"] += span_ns(union) / 1e3
        for part, compute in under.items():
            totals[part] += span_ns(overlap(union, compute)) / 1e3
        ends = {part: [end for _, end in compute] for part, compute in under.items()}
        for f in everything:
            row = rows.setdefault(
                (f["stage"], f["hop"] or f["inst"]), {"wires": 0, "bytes": 0, **dict.fromkeys(parts, 0.0)})
            row["wires"] += 1
            row["bytes"] += f["bytes"]
            row["inflight"] += (f["end"] - f["start"]) / 1e3
            for part, compute in under.items():
                row[part] += covered_ns(compute, ends[part], f["start"], f["end"]) / 1e3

    def a_step(row):
        return {
            "inflight_us_a_step": row["inflight"] / (steps or 1),
            "hidden_us_a_step": row["hidden"] / (steps or 1),
            "own_us_a_step": row["own"] / (steps or 1),
            "exposed_us_a_step": (row["inflight"] - row["hidden"] - row["own"]) / (steps or 1),
        }

    return {
        "workload": tl["workload"], "steps_counted": steps,
        "program_wire_bytes_a_step": said[0] / said[1] if said else None,
        "device_wire_bytes_a_step": sum(r["bytes"] for r in rows.values()) / (steps or 1),
        **a_step(totals),
        "self_permutes": sum(1 for d in tl["devices"].values() for f in d["flights"] if f["self"]),
        "hops": [
            {"stage": stage, "hop": hop, "wires_a_step": row["wires"] / (steps or 1),
             "bytes_a_step": row["bytes"] / (steps or 1), **a_step(row),
             "gbps_over_flight": row["bytes"] / row["inflight"] / 1e3 if row["inflight"] else None}
            for (stage, hop), row in sorted(
                rows.items(), key=lambda kv: (kv[0][0] is None, kv[0][0] or 0, kv[0][1]))
        ],
    }
