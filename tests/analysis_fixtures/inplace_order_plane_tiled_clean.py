# analysis-fixture: contract=inplace-order expect=clean
"""The real ``stream_plane_pass_tiled`` (ISSUE 51) at ``x_radius = 1``, in
place: the pipeline moves ``(1, 8, Z)`` y tiles of the raw planes on the grid
``(X + 2, NT + 1)`` -- ``u`` ringed and fetched at plane ``min(i, X - 1)``, ``c``
read along y alone and fetched lagged at ``clip(i - 1, 0, X - 1)``, both outputs
held at plane ``clip(i - 2, 0, X - 1)``.  Within an x step the in maps run the
block's tail rows first and the out map last, so the pairs the contract judges
keep their order BY PLANES: plane ``j`` is fetched at x step ``j`` or ``j + 1``
and flushed at x step ``j + 2``.  Where a plane index is clamped the maps stand
still -- the out map through x steps ``0 .. 1`` (nothing is flushed onto plane 0
before its own tiles come), the in maps past plane ``X - 1`` (nothing is
refetched from a plane the pass has begun to overwrite) -- which is what the
contract would catch a tiled map without.  ``build(split_y=True)`` is the same
pass beside a split y (ISSUE 53: the fills are the z self-wrap alone, y tiles of
16 rows -- a y tile holds the ``lo.y + hi.y`` tiles its margins are cut from):
the maps are the same functions, and the contract judges them the same."""

import jax
import jax.numpy as jnp

from stencil_tpu import analysis
from stencil_tpu.core.dim3 import Dim3
from stencil_tpu.ops.stream_pass import stream_plane_pass_tiled

R = 1
N = (6, 32, 128)


def _kernel(views, info):
    u, c = views["u"], views["c"]
    return {
        "u": 0.5 * u.center() + 0.25 * (u.sh(1, 1, 0) + u.sh(-1, 0, -1)) + c.sh(0, -1, 0),
        "c": c.center() - u.sh(0, 1, 1),
    }


def build(split_y: bool = False):
    fills = tuple(
        (axis, d, s, R) for axis in ((2,) if split_y else (1, 2))
        for d, s in ((0, N[axis]), (R + N[axis], R))
    )
    rows = 16 if split_y else 8

    def step(origin, u, c):
        return stream_plane_pass_tiled(
            _kernel, ["u", "c"], [u, c], Dim3(R, R, R), Dim3(R, R, R), R,
            origin, Dim3(*N), tile_rows=rows, strip=8, alias=True, interpret=True,
            halo_readers=("u", "c"), writers=("u", "c"), rings=("u",), wrap_fills=fills,
        )

    blk = jax.ShapeDtypeStruct(tuple(n + 2 * R for n in N), jnp.float32)
    origin = jax.ShapeDtypeStruct((3,), jnp.int32)
    return analysis.trace_artifact(
        step, origin, blk, blk, label="fixture:inplace-order-plane-tiled", kind="fn"
    )
