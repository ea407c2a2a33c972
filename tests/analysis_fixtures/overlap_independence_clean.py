# analysis-fixture: contract=overlap-independence expect=clean
"""The sanctioned split shape: the interior pallas call reads only
pre-exchange values; the exterior band pass consumes the exchanged data."""

import jax
import jax.experimental.pallas as pl
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from stencil_tpu import analysis
from jax import shard_map


def _copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def _pcopy(x):
    return pl.pallas_call(
        _copy_kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=True,
    )(x)


def build():
    devs = jax.devices()[:8]
    mesh = Mesh(np.array(devs), ("x",))
    perm = [(i, (i + 1) % 8) for i in range(8)]

    def body(x):
        recv = lax.ppermute(x, "x", perm)
        with jax.named_scope("step.overlap.interior"):
            a = _pcopy(x)  # pre-exchange only: ppermute-free by dataflow
        with jax.named_scope("step.overlap.exterior"):
            b = _pcopy(recv)  # the boundary fix-up reads fresh halos
        return a + b

    fn = shard_map(body, mesh=mesh, in_specs=P("x"), out_specs=P("x"),
                   check_vma=False)
    x = jnp.zeros((8, 16), jnp.float32)
    return analysis.trace_artifact(
        fn,
        x,
        label="fixture:overlap-independence-clean",
        kind="fn",
        axes={"overlap": "split", "exchange_route": "direct"},
        n_devices=8,
    )
