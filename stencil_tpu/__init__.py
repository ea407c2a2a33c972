"""stencil_tpu — a TPU-native 3D stencil halo-exchange framework.

Built from scratch in JAX/XLA/Pallas with the capabilities of the reference
MPI+CUDA library (``/root/reference``, mengshanfeng/stencil-2).  The reference's
five hand-rolled transports collapse into ``lax.ppermute`` collectives over a
3D device mesh; its CUDA pack/unpack kernels become Pallas kernels; its
double-buffered device allocations become donated, shell-carrying sharded
``jax.Array`` s.

Public API (mirrors reference ``include/stencil/stencil.hpp``):

    from stencil_tpu import DistributedDomain, Radius, Dim3, MethodFlags
"""

from stencil_tpu.core.dim3 import Dim3, Rect3
from stencil_tpu.core.direction_map import DirectionMap, DIRECTIONS_26
from stencil_tpu.core.radius import Radius
from stencil_tpu.core.geometry import LocalSpec
from stencil_tpu.utils.config import (
    MethodFlags,
    PlacementStrategy,
    apply_compile_cache,
)

# Persistent XLA compilation cache (JAX_COMPILATION_CACHE_DIR, else the
# fixed <checkout>/.jax_cache): applied at package import so it lands before
# the first backend compile whichever entry point the process came through
# (models, drivers, bench.py, chip_smoke.py).
apply_compile_cache()

__version__ = "0.1.0"

__all__ = [
    "Dim3",
    "Rect3",
    "DirectionMap",
    "DIRECTIONS_26",
    "Radius",
    "LocalSpec",
    "MethodFlags",
    "PlacementStrategy",
    "DistributedDomain",
    "save_checkpoint",
    "restore_checkpoint",
    "write_paraview",
]

_LAZY = {
    # these pull in jax; keep the geometry core importable without it
    "DistributedDomain": ("stencil_tpu.domain", "DistributedDomain"),
    "save_checkpoint": ("stencil_tpu.io.checkpoint", "save_checkpoint"),
    "restore_checkpoint": ("stencil_tpu.io.checkpoint", "restore_checkpoint"),
    "write_paraview": ("stencil_tpu.io.paraview", "write_paraview"),
}


def __getattr__(name):
    if name in _LAZY:
        import importlib

        mod, attr = _LAZY[name]
        return getattr(importlib.import_module(mod), attr)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
