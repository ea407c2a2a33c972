#!/usr/bin/env python
"""Per-phase roofline report from a telemetry directory.

The table the PERF_NOTES break-even models (VPU wall, split-step overlap,
zpack) previously required a human to assemble: measured device time per
phase joined with the analytic counters into achieved GB/s / GFLOP/s and
the fraction of the chip roofline, per phase.

Inputs, all from one telemetry dir (a run with ``STENCIL_TELEMETRY_DIR``
set and — for device truth — ``--profile-dir`` pointing inside it):

* ``metrics_<rank>.json`` (written by ``telemetry.write_artifacts``) or an
  explicit ``--metrics`` snapshot: the analytic counters.
* ``jax.profiler`` trace dumps (``*.trace.json[.gz]``, searched
  recursively; ``--profile-dir`` narrows the search): device rows.
* ``trace_<rank>.json`` (the host Chrome trace): the HOST-span fallback
  when no device trace exists (CPU dryrun containers) — the report is
  tagged ``"source": "host"`` because async dispatch wall-clock is not
  device truth; and with ``--merge``, the file the device rows are merged
  into so Perfetto shows both on one timeline.

The report also grows a COMMS dimension when the trace carries device rows:
collective-permute device time attributed per registered
``exchange.<axis>.<side>`` scope, joined with the analytic
``exchange.hop.*.bytes`` counters into achieved per-link GB/s — and, with
``--fabric`` pointing at a probe artifact (``python -m stencil_tpu.fabric
--out``), compared against the PROBED link bandwidth per mesh axis per
direction, bottleneck axis named.  ``--json PATH`` writes that comms
roofline as its own ``{"bench": "comms_roofline", ...}`` artifact —
the shape ``perf_ledger.py`` ingests as ``exchange_hop:*`` series.

Outputs: ``roofline.json`` + ``roofline.md`` in the telemetry dir (or
``--out-json`` / ``--out-md``).

    python scripts/perf_report.py /tmp/telem --chip "TPU v5 lite" --merge \\
        --fabric fabric.json --json comms_roofline.json
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

# runnable as `python scripts/perf_report.py` from anywhere: the telemetry
# parsers are jax-free stencil_tpu modules imported from the repo root
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        "perf_report",
        description="per-phase roofline from a telemetry dir (see module docstring)",
    )
    p.add_argument("dir", help="telemetry directory (metrics + traces)")
    p.add_argument(
        "--metrics",
        default=None,
        metavar="PATH",
        help="metrics snapshot JSON (default: newest metrics_*.json in DIR)",
    )
    p.add_argument(
        "--profile-dir",
        default=None,
        metavar="DIR",
        help="where to look for jax.profiler trace dumps (default: DIR, searched recursively)",
    )
    p.add_argument(
        "--chip",
        default=None,
        help="device kind for the peak table (e.g. 'TPU v5 lite'; default: "
        "the snapshot carries no chip — achieved rates only)",
    )
    p.add_argument(
        "--hbm-gbps",
        type=float,
        default=None,
        help="measured copy bandwidth to use as the HBM roofline "
        "(bench.py's chip_copy_gbps)",
    )
    p.add_argument(
        "--merge",
        action="store_true",
        help="also merge the device rows into DIR's host Chrome trace "
        "(trace_*.json) so Perfetto shows one timeline",
    )
    p.add_argument(
        "--fabric",
        default=None,
        metavar="PATH",
        help="fabric probe artifact (telemetry/fabric.py; `python -m "
        "stencil_tpu.fabric --out`) — joins probed per-link ceilings into "
        "the comms roofline",
    )
    p.add_argument(
        "--json",
        default=None,
        metavar="PATH",
        dest="comms_json",
        help="also write the machine-readable comms-roofline report "
        '({"bench": "comms_roofline", ...}) to PATH — the shape '
        "perf_ledger.py ingests as exchange_hop:* series",
    )
    p.add_argument("--out-json", default=None, metavar="PATH")
    p.add_argument("--out-md", default=None, metavar="PATH")
    return p


def _load_metrics(args) -> dict:
    path = args.metrics
    if path is None:
        cands = sorted(
            glob.glob(os.path.join(args.dir, "metrics_*.json")),
            key=os.path.getmtime,
        )
        path = cands[-1] if cands else None
    if path is None:
        print("no metrics snapshot found (counters will be absent)", file=sys.stderr)
        return {}
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def _host_attribution(host_trace: str) -> dict:
    """Host-span fallback: sum span durations per name from the Chrome
    trace — same shape as the device attribution, tagged by the caller."""
    from stencil_tpu.telemetry.device import attribute_device_time, load_trace_events

    return attribute_device_time(load_trace_events(host_trace))


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    from stencil_tpu.telemetry.device import (
        attribute_device_time,
        attribute_exchange_directions,
        find_trace_files,
        load_trace_events,
        merge_device_rows,
    )
    from stencil_tpu.telemetry.roofline import (
        comms_roofline,
        render_markdown,
        roofline_report,
    )
    from stencil_tpu.utils.artifact import atomic_write_json, atomic_write_text

    snapshot = _load_metrics(args)
    profile_dir = args.profile_dir or args.dir
    host_traces = sorted(glob.glob(os.path.join(args.dir, "trace_*.json")))
    # the host chrome trace is not a profiler dump — exclude it from the
    # device-trace search (find_trace_files only matches *.trace.json[.gz],
    # so the patterns are already disjoint; this is belt and braces)
    device_traces = [t for t in find_trace_files(profile_dir) if t not in host_traces]

    attribution, source, directions = None, "device", None
    if device_traces:
        events = load_trace_events(device_traces[0])
        if events:
            attribution = attribute_device_time(events)
            if attribution["_total"]["events"] == 0:
                # a dump with no device process (CPU backend: host Python
                # frames only) is not device truth — fall through to host
                attribution = None
        if attribution is not None:
            # per-direction exchange attribution (device rows only: a
            # host-only dump attributes zero, never wall-clock garbage)
            directions = attribute_exchange_directions(events)
            if args.merge and host_traces:
                with open(host_traces[0], encoding="utf-8") as f:
                    doc = json.load(f)
                doc["traceEvents"] = merge_device_rows(
                    doc.get("traceEvents", []), events
                )
                atomic_write_json(host_traces[0], doc, indent=None)
                print(f"merged device rows into {host_traces[0]}", file=sys.stderr)
    if attribution is None and host_traces:
        attribution, source = _host_attribution(host_traces[0]), "host"
        print(
            "no device trace found — falling back to HOST spans "
            "(async dispatch wall-clock, not device truth)",
            file=sys.stderr,
        )
    if attribution is None:
        print(f"no trace found under {profile_dir}", file=sys.stderr)
        return 1

    report = roofline_report(
        snapshot,
        attribution,
        chip=args.chip,
        measured_hbm_gbps=args.hbm_gbps,
        source=source,
    )

    fabric_model = None
    if args.fabric:
        from stencil_tpu.telemetry.fabric import link_model

        with open(args.fabric, encoding="utf-8") as f:
            fabric_model = link_model(json.load(f))
    comms = comms_roofline(directions, snapshot, fabric_model)
    if comms is not None:
        report["comms"] = comms
    if args.comms_json:
        atomic_write_json(
            args.comms_json,
            {
                "bench": "comms_roofline",
                "chip": args.chip,
                "source": source,
                **(comms or {"coverage": None, "hops": {},
                             "bottleneck": None, "bottleneck_axis": None}),
            },
        )
        print(f"wrote comms roofline to {args.comms_json}", file=sys.stderr)

    out_json = args.out_json or os.path.join(args.dir, "roofline.json")
    out_md = args.out_md or os.path.join(args.dir, "roofline.md")
    atomic_write_json(out_json, report)
    atomic_write_text(out_md, render_markdown(report))
    print(render_markdown(report))
    print(f"wrote {out_json} and {out_md}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
