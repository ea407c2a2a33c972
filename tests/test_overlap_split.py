"""Tier-1: the stream engine's split-step overlap schedule (ops/stream.py).

The tentpole claims, in-process on the fake 8-chip CPU mesh (interpret-mode
pallas): ``overlap=split`` is BITWISE identical to ``overlap=off`` across
stream routes (plane/wavefront), exchange routes (direct/zpack_xla),
radii {1,2}, halo multipliers, uneven shards, and f32/f64 fused messages;
resolution follows explicit > env > tuned > static-off with structural
degradation (wrap has no exchange to hide, the z-slab wavefront re-plans to
the plain form or degrades); the ladder steps split→off before any depth
descent; the ``overlap`` tuner axis searches, persists, and is consulted —
with pre-overlap (v2-era) cache entries still valid and garbage values
degrading to the static plan; and the split schedule's telemetry
(``step.overlap`` event, ``step.overlap.exterior_cells`` counter) fires.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from stencil_tpu import telemetry, tune
from stencil_tpu.core.radius import Radius
from stencil_tpu.domain import DistributedDomain
from stencil_tpu.ops import stream as sm
from stencil_tpu.ops import stream_plan as sp
from stencil_tpu.telemetry import names as tm
from stencil_tpu.tune import space as tune_space
from stencil_tpu.tune.runners import autotune_stream

TOL = dict(rtol=1e-6, atol=1e-6)


@pytest.fixture
def tune_dir(tmp_path, monkeypatch):
    """Hermetic tuned-config cache (the exchange-routes suite's pattern)."""
    monkeypatch.setenv("STENCIL_TUNE_CACHE", str(tmp_path))
    monkeypatch.delenv("STENCIL_TUNE", raising=False)
    tune.reset_memo()
    yield tmp_path
    tune.reset_memo()


def _mk(size=(16, 16, 16), radius=1, mult=1, dtypes=(jnp.float32,), route=None):
    # 16^3 over the 8-chip mesh (shard 8, shell up to 3) keeps interpret-mode
    # pallas cheap while exercising every band/corner case — tier-1 budget
    dd = DistributedDomain(*size)
    dd.set_radius(Radius.constant(radius))
    dd.set_devices(jax.devices()[:8])
    if route is not None:
        dd.set_exchange_route(route)
    if mult > 1:
        dd.set_halo_multiplier(mult)
    hs = [dd.add_data(f"q{i}", dtype=t) for i, t in enumerate(dtypes)]
    dd.realize()
    for i, h in enumerate(hs):
        dd.init_by_coords(
            h, lambda x, y, z, i=i: jnp.sin(0.13 * (x + 2 * y + 3 * z) + i)
        )
    return dd, hs


def mean6_kernel(views, info):
    out = {}
    for name, src in views.items():
        out[name] = (
            src.sh(-1, 0, 0) + src.sh(1, 0, 0)
            + src.sh(0, -1, 0) + src.sh(0, 1, 0)
            + src.sh(0, 0, -1) + src.sh(0, 0, 1)
        ) / 6.0
    return out


def wide_kernel(views, info):
    """Distance-2 reads — the radius-2 plane-route case of the matrix."""
    out = {}
    for name, src in views.items():
        out[name] = (
            src.sh(-2, 0, 0) + src.sh(2, 0, 0)
            + src.sh(0, -2, 0) + src.sh(0, 2, 0)
            + src.sh(0, 0, -2) + src.sh(0, 0, 2)
            + 2.0 * src.center()
        ) / 8.0
    return out


def _assert_split_bitwise(steps, kernel=mean6_kernel, expect_route=None,
                          **mk_kwargs):
    """Build off and split steps over twin domains, run, compare interiors
    EXACTLY (np.testing.assert_array_equal — bitwise, not allclose)."""
    step_kwargs = mk_kwargs.pop("step_kwargs", {})
    dd_a, hs_a = _mk(**mk_kwargs)
    dd_b, hs_b = _mk(**mk_kwargs)
    sa = dd_a.make_step(kernel, engine="stream", interpret=True,
                        stream_overlap="off", **step_kwargs)
    sb = dd_b.make_step(kernel, engine="stream", interpret=True,
                        stream_overlap="split", **step_kwargs)
    assert sb._stream_plan["overlap"] == "split", sb._stream_plan
    if expect_route is not None:
        assert sb._stream_plan["route"] == expect_route, sb._stream_plan
    dd_a.run_step(sa, steps)
    dd_b.run_step(sb, steps)
    for ha, hb in zip(hs_a, hs_b):
        np.testing.assert_array_equal(
            dd_a.quantity_to_host(ha), dd_b.quantity_to_host(hb)
        )
    return sa, sb


# --- bitwise equivalence -----------------------------------------------------


def test_split_bitwise_wavefront():
    """The headline: the m-level wavefront under the split schedule (a
    z-slab static plan re-planned to the plain form) — 2 macros + remainder."""
    _, sb = _assert_split_bitwise(7, mult=3, expect_route="wavefront")
    assert sb._stream_plan["m"] == 3 and not sb._stream_plan["z_slabs"]


@pytest.mark.parametrize("route", ["direct", "zpack_xla"])
def test_split_bitwise_exchange_routes(route):
    """The packed shell ppermutes ride unchanged under split: both exchange
    routes produce bitwise-identical split steps."""
    _assert_split_bitwise(4, mult=2, route=route, expect_route="wavefront")


def test_split_bitwise_plane_radius1():
    _assert_split_bitwise(
        3, expect_route="plane", step_kwargs={"stream_path": "plane"}
    )


def test_split_bitwise_plane_radius2():
    """Radius-2 reads force the plane route with a width-2 band."""
    _assert_split_bitwise(
        3, kernel=wide_kernel, radius=2,
        expect_route="plane", step_kwargs={"x_radius": 2},
    )


def test_split_bitwise_uneven_shards():
    """Padded shards: the high-side band offsets ride the same traced
    n_valid arithmetic as the exchange's dynamic halo blends."""
    _assert_split_bitwise(3, size=(15, 13, 15), expect_route="plane")
    _assert_split_bitwise(
        5, size=(15, 15, 15), mult=2,
        expect_route="wavefront", step_kwargs={"stream_path": "wavefront"},
    )


def test_split_bitwise_f32_f64_fused():
    """Mixed f32/f64 quantities fuse into one message per direction and come
    back bit-exact under the split schedule too."""
    _assert_split_bitwise(
        3, dtypes=(jnp.float32, jnp.float64),
        expect_route="plane", step_kwargs={"stream_path": "plane"},
    )
    _assert_split_bitwise(4, mult=2, dtypes=(jnp.float64,),
                          expect_route="wavefront")


def test_split_matches_xla_ground_truth():
    """Split is not just self-consistent: it matches the XLA engine's
    per-step ground truth at the stream engine's usual tolerance."""
    dd_ref, hs_ref = _mk()
    dd_b, hs_b = _mk(mult=2)
    ref = dd_ref.make_step(mean6_kernel, overlap=False)
    sb = dd_b.make_step(mean6_kernel, engine="stream", interpret=True,
                        stream_overlap="split")
    dd_ref.run_step(ref, 4)
    dd_b.run_step(sb, 4)
    np.testing.assert_allclose(
        dd_ref.quantity_to_host(hs_ref[0]), dd_b.quantity_to_host(hs_b[0]),
        **TOL,
    )


# --- resolution --------------------------------------------------------------


def test_overlap_resolution_precedence(tune_dir, monkeypatch):
    # static fallback: no request, no env, cold cache -> off
    dd, _ = _mk(mult=2)
    step = dd.make_step(mean6_kernel, engine="stream", interpret=True)
    assert step._stream_plan["overlap"] == "off"
    # env beats static
    monkeypatch.setenv("STENCIL_STREAM_OVERLAP", "split")
    dd, _ = _mk(mult=2)
    step = dd.make_step(mean6_kernel, engine="stream", interpret=True)
    assert step._stream_plan["overlap"] == "split"
    # explicit beats env
    dd, _ = _mk(mult=2)
    step = dd.make_step(mean6_kernel, engine="stream", interpret=True,
                        stream_overlap="off")
    assert step._stream_plan["overlap"] == "off"


def test_overlap_env_invalid_rejected(monkeypatch):
    monkeypatch.setenv("STENCIL_STREAM_OVERLAP", "sideways")
    dd, _ = _mk(mult=2)
    with pytest.raises(ValueError, match="STENCIL_STREAM_OVERLAP"):
        dd.make_step(mean6_kernel, engine="stream", interpret=True)


def test_overlap_unknown_request_rejected():
    dd, _ = _mk(mult=2)
    with pytest.raises(ValueError, match="unknown stream overlap"):
        dd.make_step(mean6_kernel, engine="stream", interpret=True,
                     stream_overlap="bogus")


def test_split_degrades_on_wrap_route():
    """A single subdomain plans the wrap route — no exchange to hide, so an
    explicit split degrades to off with a warning instead of crashing."""
    dd = DistributedDomain(16, 16, 16)
    dd.set_radius(Radius.constant(1))
    dd.set_devices(jax.devices()[:1])
    h = dd.add_data("q")
    dd.realize()
    dd.init_by_coords(h, lambda x, y, z: jnp.sin(0.1 * (x + y + z)))
    step = dd.make_step(mean6_kernel, engine="stream", interpret=True,
                        stream_overlap="split")
    assert step._stream_plan["route"] == "wrap"
    assert step._stream_plan["overlap"] == "off"


def test_split_structural_guard_on_zslab_plan():
    """The last-resort guard: a z-slab plan that reaches resolution with a
    split request degrades to off (make_stream_step normally re-plans the
    plain form first — plain_wavefront_plan)."""
    plan = {"route": "wavefront", "m": 2, "z_slabs": True, "grouping": "joint",
            "overlap": "split", "overlap_forced": True}
    val, source = sp._resolve_stream_overlap(plan)
    assert val == "off" and source == "explicit/degraded"


def test_split_replans_zslab_to_plain_form():
    """An explicit split against the z-slab static pick re-plans the PLAIN
    wavefront at a VMEM-fitting depth (split needs z halos in the big array
    for the exchange it overlaps)."""
    dd, _ = _mk(mult=2)
    with tune.disabled():
        static = sp.plan_stream(dd, 1, "auto", False)
    assert static["route"] == "wavefront" and static["z_slabs"]
    step = dd.make_step(mean6_kernel, engine="stream", interpret=True,
                        stream_overlap="split")
    assert step._stream_plan["route"] == "wavefront"
    assert not step._stream_plan["z_slabs"]
    assert step._stream_plan["overlap"] == "split"


# --- resilience ladder -------------------------------------------------------


def test_ladder_steps_split_down_to_off(monkeypatch):
    """A runtime VMEM_OOM on a split rung first drops the SCHEDULE at the
    same depth (split -> off), and only later descends depth — and the
    stepped-down off rung still matches the ground truth."""
    real_build = sm._build_stream_step
    calls = []

    def fake_build(dd, kernel, r, plan, interp, donate=True, **kw):
        calls.append(dict(plan))
        step = real_build(dd, kernel, r, plan, interp, donate, **kw)
        if len(calls) == 1:

            def boom(curr, steps=1):
                raise RuntimeError(
                    "Ran out of memory in memory space vmem ... "
                    "exceeded scoped vmem limit by 8.59M"
                )

            return boom
        return step

    monkeypatch.setattr(sm, "_build_stream_step", fake_build)
    dd, hs = _mk(mult=2)
    step = dd.make_step(mean6_kernel, engine="stream", interpret=True,
                        stream_overlap="split")
    assert step._stream_plan["overlap"] == "split"
    dd.run_step(step, 4)  # fake OOM -> rebuild with overlap=off -> runs
    assert step._stream_plan["overlap"] == "off"
    assert step._stream_plan["m"] == calls[0]["m"]  # same depth
    assert len(calls) == 2 and calls[1]["overlap"] == "off"
    assert [d[0] for d in step._resilience.descents] == [
        f"wavefront[m={calls[0]['m']},split]"
    ]
    ref_dd, ref_hs = _mk()
    ref = ref_dd.make_step(mean6_kernel, overlap=False)
    ref_dd.run_step(ref, 4)
    np.testing.assert_allclose(
        ref_dd.quantity_to_host(ref_hs[0]), dd.quantity_to_host(hs[0]), **TOL
    )


# --- tuner axis + cache compatibility ---------------------------------------


def test_stream_space_grows_split_candidates(tune_dir):
    dd, _ = _mk(mult=2)
    with tune.disabled():
        static = sp.plan_stream(dd, 1, "auto", False)
    cands, _ = tune_space.stream_space(dd, 1, False, static)
    assert all("overlap" in c for c in cands)
    split_cands = [c for c in cands if c["overlap"] == "split"]
    assert split_cands, cands
    # the split twin of a z-slab static pick is the PLAIN form
    assert all(not c["z_slabs"] for c in split_cands)


def test_autotune_persists_overlap_and_consult(tune_dir):
    dd, _ = _mk(mult=2)
    report = autotune_stream(dd, mean6_kernel, x_radius=1, interpret=True,
                             reps=1, rt=0.0)
    assert report.source == "search"
    assert "overlap" in report.config
    # pin a split winner and verify the next auto-mode build consults it
    key = dd.tune_key("stream")
    tune.record_config(key, dict(report.config, overlap="split"))
    tune.reset_memo()
    dd2, _ = _mk(mult=2)
    step = dd2.make_step(mean6_kernel, engine="stream", interpret=True)
    assert step._stream_plan["overlap"] == "split"


def test_v2_era_cache_entry_without_overlap_still_hits(tune_dir):
    """Pre-overlap entries (no ``overlap`` field) stay consultable — the
    axis joined the vocabulary WITHOUT a schema bump; absent = static off."""
    dd, _ = _mk(mult=2)
    key = dd.tune_key("stream")
    tune.record_config(
        key,
        {"route": "wavefront", "m": 2, "z_slabs": False, "grouping": "joint",
         "alias": False, "halo_multiplier": 2},
    )
    tune.reset_memo()
    dd2, _ = _mk(mult=2)
    step = dd2.make_step(mean6_kernel, engine="stream", interpret=True)
    assert step._stream_plan["m"] == 2 and not step._stream_plan["z_slabs"]
    assert step._stream_plan["overlap"] == "off"


def test_garbage_overlap_cache_entry_degrades_to_static(tune_dir):
    """A hand-edited/garbage overlap value invalidates the tuned plan to the
    static pick (warn, never crash) — the never-crash pin for the axis."""
    dd, _ = _mk(mult=2)
    key = dd.tune_key("stream")
    tune.record_config(
        key,
        {"route": "wavefront", "m": 2, "z_slabs": False, "grouping": "joint",
         "overlap": "banana", "halo_multiplier": 2},
    )
    tune.reset_memo()
    dd2, _ = _mk(mult=2)
    step = dd2.make_step(mean6_kernel, engine="stream", interpret=True)
    # the static plan applies (z-slab wavefront) and the run proceeds
    assert step._stream_plan["z_slabs"]
    assert step._stream_plan["overlap"] == "off"
    dd2.run_step(step, 2)


# --- telemetry ---------------------------------------------------------------


def test_split_event_and_exterior_cells_counter(tmp_path):
    telemetry.enable(dir=str(tmp_path))
    telemetry.reset()
    try:
        dd, _ = _mk(mult=2)
        step = dd.make_step(mean6_kernel, engine="stream", interpret=True,
                            stream_overlap="split")
        before = telemetry.snapshot()["counters"][tm.STEP_OVERLAP_EXTERIOR_CELLS]
        dd.run_step(step, 4)
        after = telemetry.snapshot()["counters"][tm.STEP_OVERLAP_EXTERIOR_CELLS]
        raw = dd.local_spec().raw_size()
        # 6 bands x width-per-level x steps, all shards (one field)
        want = 2 * (raw.y * raw.z + raw.x * raw.z + raw.x * raw.y) * 4 * 8
        assert after - before == want
        import json

        events = [
            json.loads(line) for line in open(telemetry.event_log_path())
        ]
        ov = [e for e in events if e["event"] == tm.EVENT_STEP_OVERLAP]
        assert ov and ov[-1]["overlap"] == "split"
        assert ov[-1]["source"] == "explicit"
    finally:
        telemetry.disable()
    # off steps move nothing through the counter
    c0 = telemetry.snapshot()["counters"][tm.STEP_OVERLAP_EXTERIOR_CELLS]
    dd, _ = _mk(mult=2)
    step = dd.make_step(mean6_kernel, engine="stream", interpret=True)
    dd.run_step(step, 2)
    assert telemetry.snapshot()["counters"][tm.STEP_OVERLAP_EXTERIOR_CELLS] == c0
