# analysis-fixture: contract=inplace-order expect=clean
"""The real ``stream_plane_pass`` at ``x_radius = 4`` with a RENAME (ISSUE
36): acoustic's shape, ``u`` ringed and read off-centre, ``u_prev`` and a
coefficient ``m`` fetched lagged, ``renames=(("u_prev", "u"),)``.  The pass
has ONE output, ``u``'s new value, and it aliases ANOTHER quantity's operand:
raw ``u_prev`` (operand ``1 + 1``; operand 0 is ``origin``), not raw ``u``.
The pair the contract judges is (raw ``u_prev``, fetched at the output plane
``clip(i - 4, 0, X-1)``; the output, held at the same plane): plane ``j`` is
fetched before grid step ``j + 4`` and flushed after it, and no later fetch
goes back.  Raw ``u`` is an input only: nothing is flushed over it."""

import jax
import jax.numpy as jnp

from stencil_tpu import analysis
from stencil_tpu.core.dim3 import Dim3
from stencil_tpu.ops.stream_pass import stream_plane_pass

R = 4


def _kernel(views, info):
    u = views["u"]
    lap = u.sh(R, 0, 0) + u.sh(-R, 0, 0) + u.sh(0, R, 0) + u.sh(0, 0, -R)
    return {"u": 2.0 * u.center() - views["u_prev"].center() + views["m"].center() * lap}


def build():
    n = 8 + 2 * R

    def step(origin, u, u_prev, m):
        return stream_plane_pass(
            _kernel, ["u", "u_prev", "m"], [u, u_prev, m], Dim3(R, R, R), Dim3(R, R, R),
            R, origin, Dim3(8, 8, 8), alias=True, interpret=True,
            halo_readers=("u",), writers=("u",), rings=("u",), renames=(("u_prev", "u"),),
        )

    blk = jax.ShapeDtypeStruct((n, n, n), jnp.float32)
    origin = jax.ShapeDtypeStruct((3,), jnp.int32)
    return analysis.trace_artifact(
        step, origin, blk, blk, blk,
        label="fixture:inplace-order-plane-renamed", kind="fn",
    )
