#!/usr/bin/env python3
"""Tier-1's test-seconds, read from the junit XML of a run.

    python scripts/tier1_times.py /tmp/_t1.xml [--files N]

Prints test-seconds per file (a file is one worker's under ``--dist
loadfile``), their sum, the sum over the driver's six workers (the wall a
perfect packing would take) and the twenty longest cases; exits 1 when the
sum, a file or a case is over its budget below (ROADMAP D13).  What the
seconds buy is Python-side tracing and lowering of interpreted
``pallas_call``s (PERF.md section 7): they scale as programs traced x calls a
program x weight of the kernel body, so a mechanism is tested on the light
kernels of ``tests/test_plane_stencil.py`` and a model once a mechanism.

Standard library only; not collected by pytest (no ``test_`` in its name).
"""

from __future__ import annotations

import argparse
import sys
import xml.etree.ElementTree as ET
from collections import defaultdict

SUM_BUDGET_S = 5000.0
FILE_BUDGET_S = 400.0
CASE_BUDGET_S = 60.0
WORKERS = 6


def read(path):
    """``[(file, case, seconds)]`` of every test case of a junit file."""
    cases = []
    for tc in ET.parse(path).getroot().iter("testcase"):
        file = tc.get("classname", "").split(".")
        # classname is the dotted module (and a class behind it): the module
        # is the last part that starts "test_"
        name = next((p for p in reversed(file) if p.startswith("test_")), file[-1])
        cases.append((name + ".py", tc.get("name", ""), float(tc.get("time", 0.0))))
    return cases


def report(cases, files_shown=None, out=sys.stdout):
    per_file = defaultdict(lambda: [0.0, 0])
    for file, _, s in cases:
        per_file[file][0] += s
        per_file[file][1] += 1
    total = sum(s for _, _, s in cases)
    ranked = sorted(per_file.items(), key=lambda kv: -kv[1][0])
    print(f"{'file':44s} {'s':>8s} {'cases':>6s}", file=out)
    for file, (s, n) in ranked[:files_shown]:
        print(f"{file:44s} {s:8.1f} {n:6d}", file=out)
    if files_shown is not None and len(ranked) > files_shown:
        rest = ranked[files_shown:]
        print(
            f"{'(%d other files)' % len(rest):44s} "
            f"{sum(v[0] for _, v in rest):8.1f} {sum(v[1] for _, v in rest):6d}",
            file=out,
        )
    print(f"{'sum':44s} {total:8.1f} {len(cases):6d}", file=out)
    print(f"{'sum / %d workers' % WORKERS:44s} {total / WORKERS:8.1f}", file=out)
    print("\nthe twenty longest cases", file=out)
    for file, case, s in sorted(cases, key=lambda c: -c[2])[:20]:
        print(f"{s:8.1f}  {file}::{case}", file=out)

    over = []
    if total > SUM_BUDGET_S:
        over.append(f"sum {total:.1f} s > {SUM_BUDGET_S:.0f}")
    over += [f"{f} {s:.1f} s > {FILE_BUDGET_S:.0f}" for f, (s, _) in ranked if s > FILE_BUDGET_S]
    over += [f"{f}::{c} {s:.1f} s > {CASE_BUDGET_S:.0f}" for f, c, s in cases if s > CASE_BUDGET_S]
    for line in over:
        print("OVER BUDGET: " + line, file=out)
    return 1 if over else 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("junit_xml")
    ap.add_argument("--files", type=int, default=None, help="show the N heaviest files only")
    args = ap.parse_args(argv)
    return report(read(args.junit_xml), args.files)


if __name__ == "__main__":
    sys.exit(main())
