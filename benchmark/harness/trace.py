"""From a ``jax.profiler`` trace (``*.xplane.pb``) to an op table, and the
arithmetic every reducer shares: busy union, traced window, idle gaps.

An op table is plain data (what ``harness/fixture_trace.json`` records):

    {"devices": {"<plane>": [[label, start_ns, dur_ns], ...]},
     "host": [[name, start_ns, dur_ns], ...]}

``devices`` holds the "XLA Ops" line of every ``/device:TPU:<n>`` plane;
``host`` the benchmark's own ``bench.*`` annotations.  A label is the op's
HLO opcode plus its result shape as the trace printed it, e.g.
``custom-call_f32_512_512_512_``: Pallas kernels carry no name of their own
in this tree (PERF.md, Open questions), so the shape is what tells two of
them apart.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."
_LAYOUT = re.compile(r"\{[^{}]*\}")
_HLO = re.compile(r"^(\([^()]*\)|\S+)\s+([a-z][\w\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
PALLAS_TARGET = "tpu_custom_call"


def op_label(name: str) -> str:
    """The trace names a device op by its whole HLO line:

        %closed_call.4 = f32[512,512,512]{2,1,0:T(8,128)} custom-call(...), custom_call_target="tpu_custom_call", ...

    The label is the opcode and the result shape, ``custom-call_f32_512_512_512_``
    (a tuple result keeps every member).  A Pallas kernel whose result
    aliases an operand is ``custom-call-inplace_<shape>``: it may touch a
    sliver of that shape (the blend kernels do), so its bytes cannot be read
    from it.  A custom call that is not a Pallas kernel keeps its target:
    ``custom-call.<target>_<shape>``."""
    _, eq, rhs = name.partition(" = ")
    if not eq:
        return re.sub(r"[^A-Za-z0-9.\-]", "_", name.lstrip("%"))[:120]
    m = _HLO.match(_LAYOUT.sub("", rhs))
    if not m:
        return re.sub(r"[^A-Za-z0-9.\-]", "_", name.lstrip("%"))[:120]
    shape, opcode = m.groups()
    if opcode == "custom-call":
        target = _TARGET.search(rhs)
        if target and target.group(1) != PALLAS_TARGET:
            opcode += "." + target.group(1)
        elif "output_to_operand_aliasing" in rhs:
            opcode += "-inplace"
    return opcode + "_" + re.sub(r"[^A-Za-z0-9]", "_", shape)


def find_xplane(trace_dir: str) -> str:
    files = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime,
    )
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return files[-1]


def load(trace_dir: str) -> dict:
    """The op table of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(trace_dir))
    devices, host = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                devices[plane.name] = [
                    [op_label(e.name), float(e.start_ns), float(e.duration_ns)]
                    for e in line.events
                ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append([e.name, float(e.start_ns), float(e.duration_ns)])
    return {"devices": devices, "host": sorted(host, key=lambda h: h[1])}


def describe(trace_dir: str, top: int = 12) -> dict:
    """What a trace holds, for reading one by hand: every plane, every line,
    its event count, its commonest names and one event's stats."""
    from collections import Counter

    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(trace_dir))
    out = {}
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            events = list(line.events)
            names = Counter(e.name for e in events)
            sample = {}
            if events:
                sample = {k: str(v)[:300] for k, v in dict(events[len(events) // 2].stats).items()}
            lines[line.name] = {
                "events": len(events), "top": names.most_common(top), "sample_stats": sample,
            }
            if DEVICE_PLANE.match(plane.name) and "XLA Ops" in line.name:
                labels = {}  # label -> [count, total ns, shortest, longest]
                for e in events:
                    row = labels.setdefault(op_label(e.name), [0, 0.0, float("inf"), 0.0])
                    d = float(e.duration_ns)
                    row[:] = [row[0] + 1, row[1] + d, min(row[2], d), max(row[3], d)]
                lines[line.name]["labels"] = dict(sorted(labels.items(), key=lambda kv: -kv[1][1])[:40])
        out[plane.name] = lines
    return out


def merged(ops) -> list:
    """Union of the ops' intervals as sorted, disjoint [start, end] pairs
    (ops may nest -- a ``while`` holds its body -- or overlap)."""
    out = []
    for start, dur in sorted((o[1], o[2]) for o in ops):
        end = start + dur
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def busy_ns(ops) -> float:
    return sum(e - s for s, e in merged(ops))


def window_ns(table: dict) -> tuple:
    """The traced window on the devices' clock: first op start to last op
    end over all chips (every chip is measured against the same window)."""
    ops = [o for dev in table["devices"].values() for o in dev]
    if not ops:
        raise ValueError("the trace holds no device op: nothing ran on the device")
    return min(o[1] for o in ops), max(o[1] + o[2] for o in ops)


def leaf_ops(ops) -> list:
    """Ops that hold no other op: control flow (``while``, ``conditional``)
    spans its body, and counting both would count the body twice."""
    ordered = sorted(ops, key=lambda o: (o[1], -o[2]))
    leaves, stack = [], []  # stack of [op, has_child]
    for op in ordered:
        while stack and op[1] >= stack[-1][0][1] + stack[-1][0][2]:
            done, has_child = stack.pop()
            if not has_child:
                leaves.append(done)
        if stack and op[1] + op[2] <= stack[-1][0][1] + stack[-1][0][2]:
            stack[-1][1] = True  # held whole: an op that merely overlaps is no child
        stack.append([op, False])
    leaves.extend(op for op, has_child in stack if not has_child)
    return leaves


def busy_and_window_s(table: dict) -> tuple:
    """(busy seconds, mean over chips; traced window seconds)."""
    t0, t1 = window_ns(table)
    busy = sum(busy_ns(ops) for ops in table["devices"].values()) / len(table["devices"])
    return busy / 1e9, (t1 - t0) / 1e9


def device_summary(table: dict) -> dict:
    """busy_s (mean over chips), window_s, the top ops and the idle gaps by
    what the host was doing (the ``bench.*`` annotation that covers the
    gap's midpoint; ``untagged`` where none does)."""
    busy_s, window_s = busy_and_window_s(table)
    chips = len(table["devices"])
    per_op, gaps = {}, {}
    host = table["host"]  # sorted by start; the benchmark's spans do not nest
    starts = [h[1] for h in host]
    for ops in table["devices"].values():
        for label, _, dur in leaf_ops(ops):
            per_op[label] = per_op.get(label, 0.0) + dur / chips
        spans = merged(ops)
        for (_, end), (start, _) in zip(spans, spans[1:]):
            mid = (end + start) / 2
            k = bisect.bisect_right(starts, mid) - 1
            covered = k >= 0 and mid < host[k][1] + host[k][2]
            tag = host[k][0][len(HOST_PREFIX):] if covered else "untagged"
            gaps[tag] = gaps.get(tag, 0.0) + (start - end) / chips
    top = lambda d: [[k, v / 1e9] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "busy_s": busy_s, "window_s": window_s,
        "device_ops": top(per_op), "idle_gaps": top(gaps),
    }
