"""strong — exchange-only strong-scaling benchmark (+ overlap A/B).

Parity target: reference bin/strong.cu: identical to weak.cu but the global
size is NOT scaled by the device count (strong.cu:30-48; defaults 512^3).
Same CSV row layout (the reference even prints "weak," for the strong binary,
strong.cu:181 — we emit "strong," so rows are distinguishable).

``--overlap`` runs the same stream-engine split-vs-off A/B as weak.py, at
the FIXED global size (rounded to the forced/derived mesh) — the
strong-scaling rows of the overlap story.  ``--tune`` wires both drivers
into the autotuner's exchange-route and stream-plan searches (bin/weak.py).
"""

from __future__ import annotations

import sys

import jax

from stencil_tpu.bin import _common
from stencil_tpu.bin.weak import build_parser, emit_overlap, run, run_overlap
from stencil_tpu.core.radius import Radius


def main(argv=None) -> int:
    args = build_parser("strong").parse_args(argv)
    _common.require_platform("strong")
    args.trivial = args.naive
    _common.telemetry_begin(args)
    _common.tune_begin(args)
    try:
        if args.overlap:
            emit_overlap(
                run_overlap(args, name="strong", weak_scale=False), args
            )
            _common.telemetry_end(args)
            return 0
        x, y, z = _common.fit_to_mesh(args.x, args.y, args.z, Radius.constant(3))
        row = run(x, y, z, args.n_iters, args, name="strong")
        if jax.process_index() == 0:
            print(row)
        _common.telemetry_end(args)
        return 0
    finally:
        _common.tune_end(args)


if __name__ == "__main__":
    sys.exit(main())
