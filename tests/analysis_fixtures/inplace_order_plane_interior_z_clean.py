# analysis-fixture: contract=inplace-order expect=clean
"""The real ``stream_plane_pass`` at ``x_radius = 3`` on the aligned window
beside a SPLIT y (ISSUE 48: ``window="interior-z"``, the strip form): the
four-chip MHD step's shape in small -- ``u`` ringed and read on the y-z, x-y
and x-z diagonals, ``c`` differenced along y alone and ``p`` read at the centre
(both fetched lagged), ``renames=(("p", "u"),)``, in place, handed the z fills
alone.  The window changes what the pass does with a block once it is in VMEM
-- the low z halo filled over every row, the aligned 64 x 128 corner taken as
tiles and the block's six tail rows into the last sublane of six margin tiles
behind them, the strips over tiles ``[3, 11)``, the stored plane's tail rows
and z shell written behind its corner -- and nothing of what moves between HBM
and VMEM: the blocks are the raw ``(1, Y, Z)`` planes, the maps the raw
window's, and the pair the contract judges (raw ``p``, fetched at the output
plane ``clip(i - 3, 0, X-1)``; ``u``'s output, held at the same plane) keeps
its order: plane ``j`` is fetched before grid step ``j + 3`` and flushed after
it."""

import jax
import jax.numpy as jnp

from stencil_tpu import analysis
from stencil_tpu.core.dim3 import Dim3
from stencil_tpu.ops.stream_pass import stream_plane_pass

R = 3
N = (8, 64, 128)


def _kernel(views, info):
    u, c = views["u"], views["c"]
    mixed = u.sh(0, R, R) - u.sh(0, -R, R) + u.sh(R, -R, 0) - u.sh(-R, 0, R)
    return {"u": 0.5 * u.center() + 0.125 * mixed + c.sh(0, 1, 0) - views["p"].center()}


def build():
    fills = ((2, 0, N[2], R), (2, R + N[2], R, R))  # the z self-wrap alone: the mesh splits y

    def step(origin, u, c, p):
        return stream_plane_pass(
            _kernel, ["u", "c", "p"], [u, c, p], Dim3(R, R, R), Dim3(R, R, R), R,
            origin, Dim3(N[0], 2 * N[1], N[2]), alias=True, interpret=True,
            halo_readers=("u", "c"), writers=("u",), rings=("u",), wrap_fills=fills,
            renames=(("p", "u"),), window="interior-z", strip=32,
            prerotated=(("u", 0, R), ("u", -R, R)),
        )

    blk = jax.ShapeDtypeStruct(tuple(n + 2 * R for n in N), jnp.float32)
    origin = jax.ShapeDtypeStruct((3,), jnp.int32)
    return analysis.trace_artifact(
        step, origin, blk, blk, blk, label="fixture:inplace-order-plane-interior-z", kind="fn"
    )
