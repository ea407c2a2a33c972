"""Share of the device's idle-gap time that falls inside one of the
program's own host spans, and the per-span table behind it."""

import json
import os

from benchmark.harness import timeline, trace


def reduce(ctx):
    """Every gap between a chip's busy intervals goes to the innermost
    program span (``harness/program_names.json``) covering its midpoint,
    else to ``untagged``; returns 100 x (gap time under a program span) /
    (all gap time), over all chips.  The table (seconds per span, mean over
    chips) is written to ``.bench_out/idle_by_span.<cell>.json``.  No device
    op, no gap or no program span at all reads as nothing."""
    tl = timeline.for_ctx(ctx)
    if tl is None or not tl["devices"]:
        return None
    program = set(timeline.program_names()["spans"])
    spans = [h for h in tl["host"] if h[0] in program]
    if not spans:
        return None
    table, total = {}, 0.0
    for ops in tl["devices"].values():
        busy = trace.merged(ops)
        for (_, end), (start, _) in zip(busy, busy[1:]):
            mid = (end + start) / 2
            cover = [h for h in spans if h[1] <= mid < h[1] + h[2]]
            tag = max(cover, key=lambda h: h[1])[0] if cover else "untagged"  # innermost: the latest to open
            table[tag] = table.get(tag, 0.0) + (start - end)
            total += start - end
    if not total:
        return None
    chips = len(tl["devices"])
    out = os.path.join(timeline.ROOT, ".bench_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"idle_by_span.{tl['workload']}.json"), "w") as f:
        json.dump({k: v / 1e9 / chips for k, v in sorted(table.items(), key=lambda kv: -kv[1])}, f, indent=1)
    return 100.0 * (total - table.get("untagged", 0.0)) / total
