"""Tier-2: generic plane-streaming kernel matches the jnp path for the
Astaroth proxy (radius-3 shell, distance-1 reads), even and uneven sizes."""

import numpy as np
import pytest

from ulp import assert_reassociation_close

from stencil_tpu.models.astaroth import AstarothSim


@pytest.mark.parametrize("size", [(28, 28, 28), (15, 14, 13)])
def test_astaroth_pallas_matches_jnp(size):
    a = AstarothSim(*size, num_quantities=2)
    a.realize()
    b = AstarothSim(*size, num_quantities=2, kernel_impl="pallas", interpret=True)
    b.realize()
    # the default schedule upgrades to the temporal wavefront everywhere:
    # even sizes on the z-slab variant, padded sizes on the plain variant
    assert b._wavefront_m == 3
    a.step(3)
    b.step(3)
    for i in range(2):
        # summation-order rounding differs between the two formulations
        np.testing.assert_allclose(a.field(i), b.field(i), rtol=1e-6, atol=1e-6)


def test_astaroth_wavefront_schedule_matches_per_step():
    """The opt-in wavefront schedule (exchange every m<=3 steps, m-level
    kernel over the radius-3 shell) reproduces the per-step pallas schedule:
    a level-s shell cell computed in-kernel uses the same arithmetic the
    neighbor applies to the same level-(s-1) values, so skipping the
    intermediate exchanges changes nothing — up to the LAST ULP, which XLA
    may perturb by fusing the m levels into one graph (excess-precision /
    reassociation across the division); hence the analytic reassociation
    bound from tests/ulp.py, not array_equal (a depth-1 macro IS bitwise,
    see below): ≤ 6 roundings per level may land in a different order /
    excess precision, each contributing at most a half-ulp at the six-sum's
    magnitude (≤ 6·|field|)."""
    a = AstarothSim(28, 28, 28, num_quantities=2, kernel_impl="pallas", interpret=True,
                    schedule="per-step")
    a.realize()
    b = AstarothSim(28, 28, 28, num_quantities=2, kernel_impl="pallas", interpret=True,
                    schedule="wavefront")
    b.realize()
    assert b._wavefront_m >= 2
    a.step(5)
    b.step(5)  # macros + a shallower remainder dispatch
    for i in range(2):
        assert_reassociation_close(
            b.field(i), a.field(i), rounds=6 * 5, scale=6.0,
            context=f"fused wavefront q{i}",
        )

    # one step = a depth-1 remainder dispatch = the same exchange cadence:
    # near-identical (the engine's plane and wavefront passes evaluate the
    # same kernel arithmetic; only the shell handling differs)
    a1 = AstarothSim(28, 28, 28, kernel_impl="pallas", interpret=True,
                     schedule="per-step")
    a1.realize(); a1.step(1)
    b1 = AstarothSim(28, 28, 28, kernel_impl="pallas", interpret=True,
                     schedule="wavefront")
    b1.realize(); b1.step(1)
    np.testing.assert_array_equal(a1.field(0), b1.field(0))


def test_astaroth_halo_multiplier_deepens_wavefront():
    """A halo multiplier widens the radius-3 shell, letting the engine
    wavefront deeper than 3 levels per exchange — same field values."""
    a = AstarothSim(32, 32, 32, kernel_impl="pallas", interpret=True,
                    schedule="per-step")
    a.realize()
    b = AstarothSim(32, 32, 32, kernel_impl="pallas", interpret=True)
    b.dd.set_halo_multiplier(2)  # shell 6 -> m up to 6
    b.realize()
    assert b._wavefront_m == 6, b._wavefront_m
    a.step(7)
    b.step(7)  # one macro + a shallower remainder
    np.testing.assert_allclose(a.field(), b.field(), rtol=1e-6, atol=1e-6)

    with pytest.raises(ValueError, match="per-step"):
        c = AstarothSim(32, 32, 32, kernel_impl="pallas", interpret=True,
                        schedule="per-step")
        c.dd.set_halo_multiplier(2)
        c.realize()


def test_astaroth_wavefront_uneven_and_jnp_guard():
    # uneven sizes run the wavefront's PLAIN variant at full depth now
    m = AstarothSim(15, 14, 13, kernel_impl="pallas", interpret=True,
                    schedule="wavefront")
    m.realize()
    assert m._wavefront_m == 3
    # the temporal schedule needs the streaming engine
    with pytest.raises(ValueError, match="pallas"):
        AstarothSim(16, 16, 16, schedule="wavefront").realize()


def test_mean6_kernel_axes_variants():
    """The bespoke mean6 kernels' storage-dtype variants (ISSUE 7): nothing
    in the shipped models calls these two directly (the astaroth wavefront
    rides ops/stream.py), so pin the f32-accumulate forms HERE against
    their native siblings or they rot as the shared helper
    (_level_sum) evolves."""
    import jax.numpy as jnp

    from ulp import assert_bf16_storage_close

    from stencil_tpu.core.dim3 import Dim3
    from stencil_tpu.ops.plane_stencil import (
        mean6_plane_step,
        mean6_shell_wavefront_step,
    )

    rng = np.random.default_rng(11)
    src = rng.random((16, 16, 16)).astype(np.float32)
    # the wavefront kernel ALIASES its input (input_output_aliases={0: 0}),
    # so every call gets its own device buffer
    fresh = lambda dt=jnp.float32: jnp.asarray(src, dt)
    raw = fresh()

    # temporal wavefront: bf16 one downcast per pass.
    # Only the interior is valid at level m (the shell carries garbage by
    # the validity contract), so compare inside the shell_width=3 ring.
    core = (slice(3, 13),) * 3
    v = mean6_shell_wavefront_step(fresh(), m=2, shell_width=3, interpret=True)
    b = mean6_shell_wavefront_step(fresh(jnp.bfloat16), m=2,
                                   shell_width=3, interpret=True,
                                   f32_accumulate=True)
    assert b.dtype == jnp.bfloat16
    assert_bf16_storage_close(np.asarray(b)[core], np.asarray(v)[core],
                              passes=1, scale=1.0,
                              context="mean6 wavefront bf16")

    # single-level plane pass: same contract (interior window only — the
    # pass-through shell keeps its input bytes in every variant)
    one = Dim3(1, 1, 1)
    pv = mean6_plane_step(raw, one, one, interpret=True)
    pb = mean6_plane_step(raw.astype(jnp.bfloat16), one, one, interpret=True,
                          f32_accumulate=True)
    assert_bf16_storage_close(pb, pv, passes=1, scale=1.0,
                              context="mean6 plane bf16")
