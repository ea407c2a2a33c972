"""elastic driver — isotropic elastic wave propagation, space order 8.

Parity target: Devito's ``benchmarks/user/benchmark.py -P elastic -so 8 -d X
Y Z`` (``examples/seismic/elastic``: ``ForwardOperator``, the velocity-stress
scheme on a staggered grid, a ``--nbl``-point damping layer) — the elastic
forward propagator of land seismic modelling, RTM and FWI.  ``x y z`` are the
PHYSICAL extents, as Devito's ``-d``; the run adds the sponge and the 4-cell
zero frame on every side (docs/elastic.md).  No source and no receivers: a
seeded velocity packet inside the physical region stands for the shot.  One
CSV row, like the other drivers, plus Devito's own figure of merit (GPts/s
over the whole padded grid, one point per cell and time step):

    elastic,ranks,devCount,x,y,z,nbl,min(s),trimean(s),gpts_per_s
"""

from __future__ import annotations

import sys

from stencil_tpu.bin.acoustic import run
from stencil_tpu.models.elastic import ElasticWave


def main(argv=None) -> int:
    # the acoustic driver's body: same grid, frame, sponge and command line
    return run(argv, "elastic", ElasticWave, steps=4)


if __name__ == "__main__":
    sys.exit(main())
