"""``python -m stencil_tpu.fabric`` — probe the realized mesh fabric.

Runs the point-to-point ``ppermute`` sweep (``telemetry/fabric.py``) over
every neighbor hop of the device mesh, prints the per-axis link model and
slowest-link callout, and persists the stamped matrix artifact under the
fabric cache (``STENCIL_FABRIC_CACHE``) so later runs — the comms
roofline in ``scripts/perf_report.py``, placement/tuner consumers — load
it without device work.

The mesh defaults to the repo's canonical factorization of all visible
devices (``parallel/mesh.make_mesh``); ``--grid X Y Z`` forces one.
"""

from __future__ import annotations

import argparse
import json
import sys


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        "stencil_tpu.fabric",
        description="measure per-link fabric bandwidth over the realized "
        "device mesh (see docs/observability.md 'Fabric observatory')",
    )
    p.add_argument(
        "--grid", type=int, nargs=3, metavar=("X", "Y", "Z"), default=None,
        help="force the mesh grid (must multiply to the device count)",
    )
    p.add_argument(
        "--nbytes", type=int, default=None,
        help="bandwidth payload per shard in bytes (default: 8 MiB)",
    )
    p.add_argument(
        "--lat-nbytes", type=int, default=None, metavar="N",
        help="run a second small-payload sweep and report per-edge latency",
    )
    p.add_argument("--reps", type=int, default=3, help="timed rounds per edge")
    p.add_argument(
        "--inner", type=int, default=1, help="chained dispatches per timed round"
    )
    p.add_argument(
        "--cache", default=None, metavar="DIR",
        help="fabric cache directory (default: STENCIL_FABRIC_CACHE or "
        "~/.cache/stencil_tpu/fabric)",
    )
    p.add_argument(
        "--force", action="store_true",
        help="re-probe even when a matching cached matrix exists",
    )
    p.add_argument(
        "--out", default=None, metavar="PATH",
        help="also write the probe artifact to PATH (atomic)",
    )
    p.add_argument("--json", action="store_true", help="print the raw artifact")
    args = p.parse_args(argv)

    import numpy as np

    import jax
    from stencil_tpu.core.radius import Radius
    from stencil_tpu.parallel.mesh import make_mesh, mesh_from_grid
    from stencil_tpu.bin._common import require_platform
    from stencil_tpu.telemetry import fabric

    require_platform("stencil_tpu.fabric")
    if args.cache is not None:
        fabric.set_dir_override(args.cache)
    devices = jax.devices()
    if args.grid is not None:
        nx, ny, nz = args.grid
        if nx * ny * nz != len(devices):
            p.error(
                f"--grid {nx}x{ny}x{nz} needs {nx * ny * nz} devices, "
                f"have {len(devices)}"
            )
        mesh = mesh_from_grid(np.array(devices).reshape(nx, ny, nz))
    else:
        # a dummy cubic domain: the probe only cares about the device grid,
        # and this is the factorization real runs get by default
        mesh, _ = make_mesh((128, 128, 128), Radius.constant(1), devices)

    kwargs = dict(
        lat_nbytes=args.lat_nbytes, reps=args.reps, inner=args.inner
    )
    if args.nbytes is not None:
        kwargs["nbytes"] = args.nbytes
    doc = fabric.ensure(mesh, force=args.force, **kwargs)

    if args.out:
        from stencil_tpu.utils.artifact import atomic_write_json

        atomic_write_json(args.out, doc)
    if args.json:
        print(json.dumps(doc, indent=2, sort_keys=True))
        return 0

    model = fabric.link_model(doc)
    topo = "x".join(str(v) for v in doc["topology"])
    print(
        f"fabric probe: topology {topo} on {doc['chip']} "
        f"({doc['protocol']['edges']} unique edges, {doc['seconds']:.3g}s, "
        f"nbytes {doc['nbytes']})"
    )
    for axis, sides in sorted(model["axes"].items()):
        for side in ("low", "high"):
            if side in sides:
                s = sides[side]
                print(
                    f"  {axis}.{side}: med {s['gbps_med']:.3g} GB/s, "
                    f"min {s['gbps_min']:.3g} GB/s over {s['links']} link(s)"
                )
    slow = model["slowest"]
    if slow:
        print(
            f"  slowest link: {slow['axis']}.{slow['side']} "
            f"{slow['src']}->{slow['dst']} at {slow['gbps']:.3g} GB/s"
        )
    else:
        print("  no fabric links (single-device mesh)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
