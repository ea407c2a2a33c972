"""Roofline reports: join measured device time per phase with the analytic
counters the tree already records.

The PERF_NOTES break-even models (split-step overlap, zpack) all end in the
same table a human currently assembles by hand: achieved GB/s per phase vs
the chip's peak.  This module builds that table from
two inputs this repo already produces —

* a **metrics snapshot** (``telemetry.snapshot()`` /
  ``metrics_<rank>.json``): the analytic counters ``domain.exchange.bytes``,
  ``exchange.packed.bytes``;
* a **device-time attribution** (``telemetry/device.py``): measured device
  microseconds per phase from a ``jax.profiler`` capture, or — when no
  profiler backend exists — host span durations as a degraded stand-in
  (tagged ``"source": "host"``; host wall-clock of an async dispatch is an
  upper bound on nothing, so the tag matters).

The portable-stencil framework survey (arxiv 2309.04671) ranks kernels by
achieved-vs-roofline; ``scripts/perf_report.py`` renders this module's
JSON as that table, and ``bench.py`` embeds it when profiling is on.

jax-free: reports are built offline, often from a dead run's artifacts.
"""

from __future__ import annotations

from typing import Dict, Optional

from stencil_tpu.telemetry import names

#: published per-chip peaks keyed by the ``device_kind`` string the chip
#: reports (``jax.devices()[0].device_kind`` — the label
#: ``tune.key.chip_kind`` persists; a v5e says "TPU v5 lite", chip run of
#: PR 21), matched by prefix.  Sources: Google Cloud TPU documentation,
#: "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM), "TPU v5p" (459 TFLOP/s bf16,
#: 2765 GB/s), "TPU v4" (275 TFLOP/s bf16, 1200 GB/s).  No f32 matrix peak
#: is published, and the MXU rounds f32 operands to bf16 at default
#: precision, so bf16 is the one matrix ceiling.  Unknown chips (and CPU
#: dryruns) carry None peaks — the report then shows achieved rates with a
#: null roofline fraction instead of inventing a ceiling.
PEAKS: Dict[str, dict] = {
    "TPU v5 lite": {"hbm_gbps": 819.0, "mxu_gflops_bf16": 197_000.0},
    "TPU v5p": {"hbm_gbps": 2765.0, "mxu_gflops_bf16": 459_000.0},
    "TPU v4": {"hbm_gbps": 1200.0, "mxu_gflops_bf16": 275_000.0},
}

#: phase -> the analytic counter carrying its traffic/work (the join key)
PHASE_BYTES_COUNTERS = {
    "exchange": names.EXCHANGE_BYTES,
    "pack": names.EXCHANGE_PACKED_BYTES,
}

#: the phases whose counter is HBM traffic, read against the HBM roofline.
#: ``exchange``'s is bytes over WIRES (the sum of the ``exchange.hop.*``
#: counters, 0 on one chip): its ceiling is the link, which the comms
#: table's ``% of link`` reads it against
HBM_PHASES = ("pack",)


def peaks_for(chip: Optional[str],
              measured_hbm_gbps: Optional[float] = None) -> dict:
    """The peak table for ``chip`` (prefix match over ``PEAKS``), with the
    MEASURED copy bandwidth substituted for the nominal HBM number when
    available — a throttled chip's honest ceiling is what it measured, not
    the datasheet (the ``chip_copy_gbps`` rule bench.py
    already applies to its headline)."""
    out = {"chip": chip, "hbm_gbps": None, "mxu_gflops_bf16": None,
           "hbm_source": None}
    if chip:
        for prefix, vals in PEAKS.items():
            if chip.startswith(prefix):
                out.update(vals)
                out["hbm_source"] = "nominal"
                break
    if measured_hbm_gbps:
        out["hbm_gbps"] = float(measured_hbm_gbps)
        out["hbm_source"] = "measured"
    return out


def _counters(snapshot: Optional[dict]) -> dict:
    return (snapshot or {}).get("counters", {}) or {}


def roofline_report(
    snapshot: Optional[dict],
    attribution: Optional[dict],
    chip: Optional[str] = None,
    measured_hbm_gbps: Optional[float] = None,
    source: str = "device",
    counters_scope: str = "run",
) -> dict:
    """The per-phase roofline join.

    ``attribution`` is ``{phase: {"device_us": ..., "events": ...}}``
    (``telemetry.device.attribute_device_time``; a host-span fallback uses
    the same shape with ``source="host"``).  Phases carrying an analytic
    bytes counter report achieved GB/s and, where the bytes are HBM traffic
    (``HBM_PHASES``), their fraction of the HBM roofline; scope phases with no counter (interior/exterior) report time
    and their share of total device time — the overlap-efficiency inputs.

    ``counters_scope`` records what window the counters cover, because the
    join is only honest when numerator and denominator cover the SAME
    window: ``"capture"`` = the counter deltas of the profiled window
    (``ProfileCapture.counters_snapshot`` — what the drivers pass);
    ``"run"`` = whole-run cumulative counters (offline ``perf_report``
    over ``metrics_*.json``), where achieved rates overstate by
    (run work / captured work) unless the run captured its whole measured
    loop.
    """
    counters = _counters(snapshot)
    attribution = attribution or {}
    peaks = peaks_for(chip, measured_hbm_gbps)
    total_us = attribution.get("_total", {}).get("device_us", 0.0)
    phases = {}
    for phase, row in attribution.items():
        if phase.startswith("_"):
            continue
        us = float(row.get("device_us", 0.0))
        s = us / 1e6
        entry = {
            "device_ms": round(us / 1e3, 6),
            "events": int(row.get("events", 0)),
            "share_of_device": round(us / total_us, 4) if total_us else None,
            "bytes": None,
            "gbps": None,
            "frac_of_roofline": None,
        }
        bc = PHASE_BYTES_COUNTERS.get(phase)
        if bc is not None:
            b = counters.get(bc)
            if b:
                entry["bytes"] = int(b)
                if s > 0:
                    entry["gbps"] = round(b / s / 1e9, 3)
                    if peaks["hbm_gbps"] and phase in HBM_PHASES:
                        entry["frac_of_roofline"] = round(
                            entry["gbps"] / peaks["hbm_gbps"], 4
                        )
        phases[phase] = entry
    return {
        "source": source,
        "counters_scope": counters_scope,
        "peaks": peaks,
        "total_device_ms": round(total_us / 1e3, 6) if total_us else None,
        "unattributed_device_ms": round(
            attribution.get("_unattributed", {}).get("device_us", 0.0) / 1e3,
            6,
        ),
        "phases": phases,
    }


def capture_report(
    capture,
    chip: Optional[str] = None,
    measured_hbm_gbps: Optional[float] = None,
) -> Optional[dict]:
    """``roofline_report`` for a ``ProfileCapture``'s newest window: the
    dump's attribution joined with the capture-window counter deltas
    (whole-run snapshot fallback, tagged in ``counters_scope``).  Returns
    None when the capture produced no device rows (backend without a
    device profiler) — THE shared finalize for ``bench.py`` (embeds the
    report) and ``bin/_common.profile_finalize`` (writes it)."""
    attribution = capture.attribution()
    if attribution is None or attribution["_total"]["events"] == 0:
        return None
    deltas = capture.counters_snapshot()
    from stencil_tpu import telemetry

    return roofline_report(
        deltas if deltas is not None else telemetry.snapshot(),
        attribution,
        chip=chip,
        measured_hbm_gbps=measured_hbm_gbps,
        counters_scope="capture" if deltas is not None else "run",
    )


def comms_roofline(
    direction_attribution: Optional[dict],
    snapshot: Optional[dict],
    fabric_model: Optional[dict] = None,
) -> Optional[dict]:
    """The communication dimension of the roofline: achieved per-link GB/s
    per mesh axis per direction, vs the PROBED link bandwidth when a fabric
    matrix is joined in.

    Three inputs, all artifacts this repo already produces:

    * ``direction_attribution`` — ``device.attribute_exchange_directions``
      over a profiler trace: collective-permute device time per registered
      ``exchange.<axis>.<side>`` scope, plus the coverage fraction of the
      whole exchange family;
    * ``snapshot`` — the ``exchange.hop.<axis>.<side>.bytes`` counters
      (the wires of the message plans that ran; ``domain.exchange.bytes``
      is their sum);
    * ``fabric_model`` — ``telemetry.fabric.link_model`` output (optional:
      without it, achieved rates report with null probed ceilings).

    The bottleneck is the direction with the most device time — the hop a
    topology/placement change must shrink first.  Returns None when there
    is no attribution at all (no trace).
    """
    if not direction_attribution:
        return None
    counters = _counters(snapshot)
    axes_model = (fabric_model or {}).get("axes", {})
    span_to_hop = {
        span: hop for hop, span in names.EXCHANGE_DIRECTION_SPANS.items()
    }
    hops = {}
    bottleneck = None
    for span, row in (direction_attribution.get("directions") or {}).items():
        axis, side = span_to_hop[span]
        us = float(row.get("device_us", 0.0))
        s = us / 1e6
        b = counters.get(names.EXCHANGE_HOP_BYTES[(axis, side)])
        probed = (axes_model.get(axis, {}).get(side) or {}).get("gbps_med")
        entry = {
            "axis": axis,
            "direction": side,
            "device_ms": round(us / 1e3, 6),
            "events": int(row.get("events", 0)),
            "bytes": int(b) if b else None,
            "gbps": round(b / s / 1e9, 3) if (b and s > 0) else None,
            "probed_gbps": probed,
            "frac_of_link": None,
        }
        if entry["gbps"] is not None and probed:
            entry["frac_of_link"] = round(entry["gbps"] / probed, 4)
        hops[span] = entry
        if us > 0 and (bottleneck is None or us > bottleneck["_us"]):
            bottleneck = {"span": span, "_us": us, **entry}
    if bottleneck is not None:
        bottleneck.pop("_us")
    return {
        "coverage": direction_attribution.get("coverage"),
        "exchange_device_ms": round(
            float(direction_attribution.get("exchange_device_us") or 0.0) / 1e3, 6
        ),
        "attributed_ms": round(
            float(direction_attribution.get("attributed_us") or 0.0) / 1e3, 6
        ),
        "hops": hops,
        "bottleneck": bottleneck,
        "bottleneck_axis": bottleneck["axis"] if bottleneck else None,
        "fabric": "probed" if fabric_model else None,
    }


def render_markdown(report: dict) -> str:
    """The report as the PERF_NOTES-style markdown table."""
    peaks = report.get("peaks", {})
    lines = [
        "# Per-phase roofline",
        "",
        f"- chip: `{peaks.get('chip')}`  "
        f"(HBM peak {peaks.get('hbm_gbps')} GB/s "
        f"[{peaks.get('hbm_source') or 'unknown'}], "
        f"MXU bf16 peak {peaks.get('mxu_gflops_bf16')} GFLOP/s)",
        f"- timing source: **{report.get('source')}** "
        + ("(device truth)" if report.get("source") == "device"
           else "(host spans — async dispatch upper bound only)"),
        f"- counters scope: **{report.get('counters_scope')}** "
        + ("(capture-window deltas — rates are honest)"
           if report.get("counters_scope") == "capture"
           else "(whole-run cumulative — rates overstate unless the "
           "capture covered the whole measured loop)"),
        f"- total device time: {report.get('total_device_ms')} ms "
        f"(unattributed {report.get('unattributed_device_ms')} ms)",
        "",
        "| phase | device ms | events | share | GB/s | % of roofline |",
        "|---|---|---|---|---|---|",
    ]
    for phase in sorted(report.get("phases", {})):
        e = report["phases"][phase]
        frac = e.get("frac_of_roofline")
        lines.append(
            f"| `{phase}` | {e['device_ms']} | {e['events']} | "
            f"{e.get('share_of_device')} | {e.get('gbps') or ''} | "
            f"{f'{100 * frac:.1f}%' if frac is not None else ''} |"
        )
    lines.append("")
    comms = report.get("comms")
    if comms:
        cov = comms.get("coverage")
        lines += [
            "## Comms roofline (per mesh hop)",
            "",
            f"- exchange device time: {comms.get('exchange_device_ms')} ms, "
            f"direction coverage "
            + (f"{100 * cov:.1f}%" if cov is not None else "n/a")
            + (
                ""
                if comms.get("fabric")
                else " (no fabric probe joined — probed ceilings null; run "
                "`python -m stencil_tpu.fabric`)"
            ),
            "",
            "| hop | device ms | events | bytes | GB/s | probed GB/s | % of link |",
            "|---|---|---|---|---|---|---|",
        ]
        for span in sorted(comms.get("hops", {})):
            e = comms["hops"][span]
            frac = e.get("frac_of_link")
            lines.append(
                f"| `{span}` | {e['device_ms']} | {e['events']} | "
                f"{e.get('bytes') or ''} | {e.get('gbps') or ''} | "
                f"{e.get('probed_gbps') or ''} | "
                f"{f'{100 * frac:.1f}%' if frac is not None else ''} |"
            )
        bn = comms.get("bottleneck")
        if bn:
            lines += [
                "",
                f"**Bottleneck: mesh axis `{bn['axis']}`** "
                f"(`{bn.get('span')}`, {bn['device_ms']} ms of exchange "
                "device time — the hop a topology/placement change must "
                "shrink first).",
            ]
        lines.append("")
    return "\n".join(lines)
