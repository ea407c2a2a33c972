"""Model families: the reference's driver applications as reusable models.

* ``jacobi`` — 7-point Jacobi heat stencil with hot/cold sphere forcing
  (reference bin/jacobi3d.cu), the flagship app.
* ``astaroth`` — radius-3 multi-quantity MHD proxy (reference
  bin/astaroth_sim.cu).
* ``acoustic`` — isotropic acoustic wave propagation at space order 8
  (Devito ``examples/seismic/acoustic``; Minimod), the 25-point radius-4
  star, beside its plain reference ``acoustic_reference``.
* ``elastic`` — isotropic elastic wave propagation at space order 8 (Devito
  ``examples/seismic/elastic``; Minimod), velocity-stress on a staggered
  grid: thirteen quantities, two stages a time step, beside its plain
  reference ``elastic_reference``.
* ``lbm`` -- D3Q19 BGK lattice Boltzmann on a periodic box (FluidX3D's
  benchmark), beside its plain reference ``lbm_reference``.
* ``astaroth_mhd`` -- Astaroth's real MHD step (``astaroth`` is its proxy):
  eight fields at sixth order, three Runge-Kutta substeps a step, beside its
  plain reference ``astaroth_mhd_reference``.
"""

from stencil_tpu.models.jacobi import Jacobi3D
from stencil_tpu.models.astaroth import AstarothSim
from stencil_tpu.models.acoustic import AcousticWave
from stencil_tpu.models.elastic import ElasticWave

__all__ = ["Jacobi3D", "AstarothSim", "AcousticWave", "ElasticWave"]
