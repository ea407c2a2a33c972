"""Shares of the wires' own in-flight intervals (``harness/timeline_wires.py``):
how long transfers fly, how much of that the step's own kernels hide, how fast
the bytes the PROGRAM says it sends arrive against the ICI peak, and whether
the program's count and its names are true.  Mean over chips; every interval
arithmetic is a union on one chip's clock."""

import json

from benchmark.harness import timeline_wires as tw


def _per_chip(tl, ctx, what):
    said = tw.said_bytes(tl)
    for device in tl["devices"].values():
        wires = tw.wires_of(device)
        if not wires:
            continue
        flying = tw.merged([f["start"], f["end"]] for f in wires)
        if what == "inflight":
            busy = tw.span_ns(device["busy"])
            if busy:
                yield 100.0 * tw.span_ns(tw.overlap(flying, device["busy"])) / busy
        elif what == "hidden":
            # compute ops lie inside busy, so the part under them is inside the clipped flight
            clipped = tw.span_ns(tw.overlap(flying, device["busy"]))
            if clipped:
                yield 100.0 * tw.span_ns(tw.overlap(flying, device["kernel"])) / clipped
        elif what == "named":
            named = tw.merged([f["start"], f["end"]] for f in wires if f["hop"])
            yield 100.0 * tw.span_ns(named) / tw.span_ns(flying)
        else:  # against the program's own count: over the module executions the trace holds whole
            runs = tw.whole_runs(device)
            if said is None or not runs:
                continue
            if what == "counted":
                arrived = sum(f["bytes"] for flights in runs.values() for f in flights) / len(runs)
                if max(said[0], arrived):
                    yield 100.0 * min(said[0], arrived) / max(said[0], arrived)
            elif what == "ici" and ctx.get("peaks"):
                flown = tw.span_ns(tw.merged(
                    [f["start"], f["end"]] for flights in runs.values() for f in flights)) / 1e9
                if flown:
                    yield 100.0 * (len(runs) * said[0] / flown) / ctx["peaks"]["ici_bytes_per_s"]


def reduce(ctx, what):
    """``what``:

    * ``inflight`` -- 100 x (union of the wires' in-flight intervals, clipped
      to busy) / busy: a wait lies inside its flight, so this reads at or
      above the cell's ``collective_pct.*``.  Also prints the run's
      ``{"bench": "wires"}`` info line.
    * ``hidden`` -- of that in-flight time, 100 x the part during which the
      chip ran a leaf op that is no collective and lies OUTSIDE every
      ``exchange.*`` scope: the step's kernels hiding the wire.  The
      exchange's own pack / unpack / blend under a flight is exchange time
      still (the info line's ``own_us_a_step``), and counts with the waits
      in 100 minus this.
    * ``ici`` -- 100 x (bytes one chip receives over wires in the module
      executions the trace holds whole, by the program's ``wire_bytes`` x the
      steps or exchanges a dispatch / the union of those executions'
      flights) / ``peaks["ici_bytes_per_s"]``: bytes received at most at the
      chip's whole ICI rate over at least the time they flew cannot pass 100
      unless the program says too many.
    * ``counted`` -- 100 x min / max of those said bytes a dispatch and the
      result bytes of the ``collective-permute`` ops one chip ran a dispatch.
    * ``named`` -- 100 x the in-flight time of wires under an
      ``exchange.<axis>.low|high`` scope over all wires' in-flight time.

    Nothing to read -- no trace, no device plane (a CPU rehearsal), no wire on
    any chip (a one-chip cell), for ``ici`` / ``counted`` no span that says
    ``wire_bytes`` (a program from before PR 49 where the step is not the
    plane route's) -- is None, never 0."""
    tl = tw.for_ctx(ctx)
    if tl is None or not tl["devices"]:
        return None
    if what == "inflight" and any(tw.wires_of(d) for d in tl["devices"].values()):
        print(json.dumps({"bench": "wires", **tw.table(tl)}, default=str), flush=True)
    shares = list(_per_chip(tl, ctx, what))
    return sum(shares) / len(shares) if shares else None
