"""The benchmark's one command.

    python3 benchmark/run.py --workload <config>.<traffic> --seed <n> --seconds <s> --trace <0|1>

Runs one cell on the machine it is started on and prints, as the last line
of its standard output, one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, and with ``--trace 1`` ``breakdown``).
Anything but a ``tpu`` backend with the cell's chips exits non-zero and
prints no result.  ``--rehearse N`` (CPU, N^3 per chip, interpreted kernels)
drives the same control flow for rehearsals: no metric it prints is a device
number and its ``correct`` is always false.
"""

import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--lower-precision", action="store_true",
                   help="the control: the program's bf16 storage axis; must come out not correct")
    p.add_argument("--also-verify", type=lambda s: [int(v) for v in s.split(",") if v], default=[],
                   metavar="SEED,SEED", help="after the window, also compare these seeds' first dispatches "
                   "with the reference, in the same process (a cell whose set-up is long)")
    p.add_argument("--describe-trace", action="store_true",
                   help="with --trace 1: also write what the trace holds to .bench_out/")
    p.add_argument("--rehearse", type=int, default=0, metavar="N",
                   help="CPU rehearsal at N^3 per chip (interpreted kernels, never a measurement)")
    p.add_argument("--dispatch-size", type=int, default=0,
                   help="rehearsal only: steps (or exchanges) per dispatch")
    return p.parse_args(argv)


def main(argv=None) -> int:
    opts = parse(argv)
    # stay off jax until here: a parent that touched it would hold the chip
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    # one compile cache, at a fixed path inside the checkout (the path is
    # part of the key); the program takes the same variable
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from benchmark.harness import window

    return window.run(opts, T_START)


if __name__ == "__main__":
    sys.exit(main())
