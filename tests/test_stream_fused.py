"""Tier-1: the stream engine's fused unpack→blend mode (ops/stream_plan.py
``STREAM_HALO``; docs/tuning.md "Fused halo consumption").

The tentpole claims, in-process on the fake 8-chip CPU mesh (interpret-mode
pallas): ``halo="fused"`` is BITWISE identical to ``halo="array"`` across
stream routes (plane / plain wavefront), both yzpack exchange routes,
multi-dtype fused domains, and macro remainders; resolution follows
explicit > env > tuned > static-array with structural degradation (wrap,
split schedule, non-yzpack routes, uneven shards; a z-slab static plan
re-plans to the plain form); the ladder steps fused→array at the same
depth before any depth descent; the ``halo`` tuner axis searches, persists,
and is consulted — with pre-halo cache entries still warm and garbage
values degrading to the static plan; the ``fused-halo`` program contract
proves the big array sees NO halo write in the fused program (and fires on
an unfused program claiming fused); and the ``step.halo`` telemetry event
records every resolution.
"""

import json

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from stencil_tpu import analysis, telemetry, tune
from stencil_tpu.analysis.framework import step_artifact
from stencil_tpu.analysis.programs import tpu_shaped_trace
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


def _mk(size=(16, 16, 16), radius=1, mult=1, dtypes=(jnp.float32,),
        route="yzpack_xla"):
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


def _assert_fused_bitwise(steps, expect_route=None, **mk_kwargs):
    """Build array and fused steps over twin domains, run, compare the RAW
    blocks EXACTLY — the fused level-0 planes equal the post-exchange
    planes byte for byte, so even shell cells of the outputs agree."""
    step_kwargs = mk_kwargs.pop("step_kwargs", {})
    dd_a, hs_a = _mk(**mk_kwargs)
    dd_b, hs_b = _mk(**mk_kwargs)
    sa = dd_a.make_step(mean6_kernel, engine="stream", interpret=True,
                        stream_halo="array", **step_kwargs)
    sb = dd_b.make_step(mean6_kernel, engine="stream", interpret=True,
                        stream_halo="fused", **step_kwargs)
    assert sb._stream_plan["halo"] == "fused", sb._stream_plan
    assert not sb._stream_plan.get("z_slabs"), sb._stream_plan
    if expect_route is not None:
        assert sb._stream_plan["route"] == expect_route, sb._stream_plan
    dd_a.run_step(sa, steps)
    dd_b.run_step(sb, steps)
    for ha, hb in zip(hs_a, hs_b):
        np.testing.assert_array_equal(
            dd_a.raw_to_host(ha), dd_b.raw_to_host(hb)
        )
    return sa, sb


# --- bitwise equivalence -----------------------------------------------------


def test_fused_bitwise_wavefront():
    """The headline: the m-level plain wavefront with every axis's shell
    landing in VMEM (a z-slab static plan re-planned) — 2 macros +
    remainder."""
    _, sb = _assert_fused_bitwise(7, mult=3, expect_route="wavefront")
    assert sb._stream_plan["m"] == 3


def test_fused_bitwise_plane():
    _assert_fused_bitwise(
        3, expect_route="plane", step_kwargs={"stream_path": "plane"}
    )


def test_fused_bitwise_plane_wide_shell():
    """Halo-multiplier shell on the plane route: the fused patch covers the
    FULL shell widths (wider than the kernel's read radius)."""
    _assert_fused_bitwise(
        3, mult=2, expect_route="plane", step_kwargs={"stream_path": "plane"}
    )


def test_fused_bitwise_multi_dtype():
    """f32 + f64 quantities: each dtype's y/z messages pack per quantity,
    fuse per direction, and land in the right VMEM planes."""
    _assert_fused_bitwise(
        4, mult=2, dtypes=(jnp.float32, jnp.float64),
        expect_route="wavefront",
    )


def test_fused_bitwise_pallas_route():
    """The tile-local pack/unpack pipeline feeding the fused consumer."""
    _assert_fused_bitwise(
        4, mult=2, route="yzpack_pallas", expect_route="wavefront"
    )


def test_fused_matches_xla_ground_truth():
    """Fused is not just self-consistent: it matches the XLA engine's
    per-step ground truth at the stream engine's usual tolerance."""
    dd_ref, hs_ref = _mk(route=None)
    dd_b, hs_b = _mk(mult=2)
    ref = dd_ref.make_step(mean6_kernel, overlap=False)
    sb = dd_b.make_step(mean6_kernel, engine="stream", interpret=True,
                        stream_halo="fused")
    dd_ref.run_step(ref, 4)
    dd_b.run_step(sb, 4)
    np.testing.assert_allclose(
        dd_ref.quantity_to_host(hs_ref[0]), dd_b.quantity_to_host(hs_b[0]),
        **TOL,
    )


# --- resolution --------------------------------------------------------------


def test_halo_resolution_precedence(tune_dir, monkeypatch):
    # static fallback: no request, no env, cold cache -> array
    dd, _ = _mk(mult=2)
    step = dd.make_step(mean6_kernel, engine="stream", interpret=True)
    assert step._stream_plan["halo"] == "array"
    # env beats static
    monkeypatch.setenv("STENCIL_STREAM_HALO", "fused")
    dd, _ = _mk(mult=2)
    step = dd.make_step(mean6_kernel, engine="stream", interpret=True)
    assert step._stream_plan["halo"] == "fused"
    # explicit beats env
    dd, _ = _mk(mult=2)
    step = dd.make_step(mean6_kernel, engine="stream", interpret=True,
                        stream_halo="array")
    assert step._stream_plan["halo"] == "array"


def test_halo_env_invalid_rejected(monkeypatch):
    monkeypatch.setenv("STENCIL_STREAM_HALO", "sideways")
    dd, _ = _mk(mult=2)
    with pytest.raises(ValueError, match="STENCIL_STREAM_HALO"):
        dd.make_step(mean6_kernel, engine="stream", interpret=True)


def test_halo_unknown_request_rejected():
    dd, _ = _mk(mult=2)
    with pytest.raises(ValueError, match="unknown stream halo"):
        dd.make_step(mean6_kernel, engine="stream", interpret=True,
                     stream_halo="bogus")


def test_fused_degrades_without_ypack_route():
    """A fused request against a z-only (or direct) exchange route degrades
    to array with a warning — the fused exchange needs the y message."""
    for route in (None, "zpack_xla"):
        dd, _ = _mk(mult=2, route=route)
        step = dd.make_step(mean6_kernel, engine="stream", interpret=True,
                            stream_halo="fused")
        assert step._stream_plan["halo"] == "array", (route, step._stream_plan)
        dd.run_step(step, 2)


def test_fused_degrades_under_split():
    """fused and split are structurally exclusive (the exterior band passes
    read exchanged BLOCKS): requesting both keeps split and degrades the
    halo mode."""
    dd, _ = _mk(mult=2)
    step = dd.make_step(mean6_kernel, engine="stream", interpret=True,
                        stream_overlap="split", stream_halo="fused")
    assert step._stream_plan["overlap"] == "split"
    assert step._stream_plan["halo"] == "array"


def test_fused_degrades_on_wrap_route():
    dd = DistributedDomain(16, 16, 16)
    dd.set_radius(Radius.constant(1))
    dd.set_devices(jax.devices()[:1])
    h = dd.add_data("q")
    dd.realize()
    dd.init_by_coords(h, lambda x, y, z: jnp.sin(0.1 * (x + y + z)))
    step = dd.make_step(mean6_kernel, engine="stream", interpret=True,
                        stream_halo="fused")
    assert step._stream_plan["route"] == "wrap"
    assert step._stream_plan["halo"] == "array"


def test_fused_degrades_on_uneven_shards():
    """Padded shards: the fused pack cuts at static offsets, so fused
    degrades to array (which supports them) instead of crashing."""
    dd, hs = _mk(size=(15, 15, 15), route=None)
    step = dd.make_step(mean6_kernel, engine="stream", interpret=True,
                        stream_halo="fused")
    assert step._stream_plan["halo"] == "array"
    dd.run_step(step, 2)


def test_fused_replans_zslab_to_plain_form():
    """A fused request against the z-slab static pick re-plans the PLAIN
    wavefront (the fused buffers are the level-0 patch of a plain pass) —
    the split path's rule, shared."""
    dd, _ = _mk(mult=2)
    with tune.disabled():
        static = sp.plan_stream(dd, 1, "auto", False)
    assert static["route"] == "wavefront" and static["z_slabs"]
    step = dd.make_step(mean6_kernel, engine="stream", interpret=True,
                        stream_halo="fused")
    assert step._stream_plan["route"] == "wavefront"
    assert not step._stream_plan["z_slabs"]
    assert step._stream_plan["halo"] == "fused"


# --- resilience ladder -------------------------------------------------------


def test_ladder_steps_fused_down_to_array(monkeypatch):
    """A runtime VMEM_OOM on a fused rung first drops the HALO MODE at the
    same depth (fused -> array), and only later descends depth — and the
    stepped-down array rung still matches the ground truth."""
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
                        stream_halo="fused")
    assert step._stream_plan["halo"] == "fused"
    dd.run_step(step, 4)  # fake OOM -> rebuild with halo=array -> runs
    assert step._stream_plan["halo"] == "array"
    assert step._stream_plan["m"] == calls[0]["m"]  # same depth
    assert len(calls) == 2 and calls[1]["halo"] == "array"
    assert [d[0] for d in step._resilience.descents] == [
        f"wavefront[m={calls[0]['m']},fused]"
    ]
    ref_dd, ref_hs = _mk(route=None)
    ref = ref_dd.make_step(mean6_kernel, overlap=False)
    ref_dd.run_step(ref, 4)
    np.testing.assert_allclose(
        ref_dd.quantity_to_host(ref_hs[0]), dd.quantity_to_host(hs[0]), **TOL
    )


# --- tuner axis + cache compatibility ---------------------------------------


def test_stream_space_grows_fused_twin_only_with_ypack_route(tune_dir):
    dd, _ = _mk(mult=2)
    with tune.disabled():
        static = sp.plan_stream(dd, 1, "auto", False)
    cands, _ = tune_space.stream_space(dd, 1, False, static)
    assert all("halo" in c for c in cands)
    fused_cands = [c for c in cands if c["halo"] == "fused"]
    assert fused_cands and all(not c["z_slabs"] for c in fused_cands)
    # a z-only exchange route cannot feed the fused consumer: prefiltered
    dd2, _ = _mk(mult=2, route="zpack_xla")
    with tune.disabled():
        static2 = sp.plan_stream(dd2, 1, "auto", False)
    cands2, pre2 = tune_space.stream_space(dd2, 1, False, static2)
    assert not [c for c in cands2 if c["halo"] == "fused"]
    assert pre2 >= 1


def test_autotune_persists_halo_and_consult(tune_dir):
    dd, _ = _mk(mult=2)
    report = autotune_stream(dd, mean6_kernel, x_radius=1, interpret=True,
                             reps=1, rt=0.0)
    assert report.source == "search"
    assert "halo" in report.config
    # pin a fused winner and verify the next auto-mode build consults it
    # (pin the FULL wavefront shape — the search winner may be the plane
    # route, whose m=1 would make a bare route override structurally
    # invalid and silently fall back to static — and overlap=off: the
    # winner of a timing search on the CPU is as often the split twin,
    # under which fused structurally degrades)
    key = dd.tune_key("stream")
    win = dict(report.config, halo="fused", overlap="off", route="wavefront",
               m=2, z_slabs=False, grouping="joint")
    tune.record_config(key, win)
    tune.reset_memo()
    dd2, _ = _mk(mult=2)
    step = dd2.make_step(mean6_kernel, engine="stream", interpret=True)
    assert step._stream_plan["halo"] == "fused"


def test_pre_halo_cache_entry_without_halo_still_hits(tune_dir):
    """Pre-halo entries (no ``halo`` field) stay consultable — the axis
    joined the vocabulary WITHOUT a schema bump; absent = static array."""
    dd, _ = _mk(mult=2)
    key = dd.tune_key("stream")
    tune.record_config(
        key,
        {"route": "wavefront", "m": 2, "z_slabs": False, "grouping": "joint",
         "alias": False, "overlap": "off", "halo_multiplier": 2},
    )
    tune.reset_memo()
    dd2, _ = _mk(mult=2)
    step = dd2.make_step(mean6_kernel, engine="stream", interpret=True)
    assert step._stream_plan["m"] == 2 and not step._stream_plan["z_slabs"]
    assert step._stream_plan["halo"] == "array"


def test_garbage_halo_cache_entry_degrades_to_static(tune_dir):
    """A hand-edited/garbage halo value invalidates the tuned plan to the
    static pick (warn, never crash) — the never-crash pin for the axis."""
    dd, _ = _mk(mult=2)
    key = dd.tune_key("stream")
    tune.record_config(
        key,
        {"route": "wavefront", "m": 2, "z_slabs": False, "grouping": "joint",
         "halo": "banana", "halo_multiplier": 2},
    )
    tune.reset_memo()
    dd2, _ = _mk(mult=2)
    step = dd2.make_step(mean6_kernel, engine="stream", interpret=True)
    # the static plan applies (z-slab wavefront) and the run proceeds
    assert step._stream_plan["z_slabs"]
    assert step._stream_plan["halo"] == "array"
    dd2.run_step(step, 2)


# --- the no-big-array-halo-write proof ---------------------------------------


def _step_art(halo, route="yzpack_xla", claim=None, **step_kwargs):
    """Trace a built stream step under the TPU-shaped knobs and wrap it
    with the halo axis it CLAIMS (``claim`` overrides the real mode — the
    fire case below)."""
    with tpu_shaped_trace():
        dd, _ = _mk(mult=2, route=route)
        step = dd.make_step(mean6_kernel, engine="stream", interpret=True,
                            stream_halo=halo, **step_kwargs)
        axes = {"halo": claim if claim is not None else halo,
                "overlap": "off", "exchange_route": route}
        return step_artifact(dd, step, label=f"fused-proof:{halo}", axes=axes)


def test_fused_program_has_no_big_array_halo_write():
    """The acceptance pin: the traced fused step contains NO halo-region
    write to the big array — no partial-window DUS/scatter on a raw-shaped
    array, no blend/unpack kernel — machine-checked by the ``fused-halo``
    contract, plus a direct jaxpr walk for the DUS half."""
    art = _step_art("fused")
    assert art.plan["halo"] == "fused"
    assert analysis.check(art, contract="fused-halo") == []
    # belt and braces: walk the jaxpr ourselves for raw-shaped window writes
    from stencil_tpu.analysis import jaxpr as jx

    raw = art.dd.local_spec().raw_size()
    for e in jx.iter_eqns(art.closed):
        if e.primitive.name in ("dynamic_update_slice", "scatter"):
            shape = tuple(getattr(e.invars[0].aval, "shape", ()))
            assert shape[-3:] != (raw.x, raw.y, raw.z), (
                f"{e.primitive.name} writes the big array in the fused "
                f"program: {shape}"
            )


def test_unfused_program_claiming_fused_fires():
    """The contract is a real discriminator: the same workload built with
    halo=array on the plane route — whose exchange blends every received
    shell into the raw blocks — fires when its axes claim fused.  (The
    z-slab wavefront would not: its blends land on lane-padded blocks and
    its z halos already avoid the big array; the plane route is the form
    whose raw-block blends the fused mode exists to remove.)"""
    art = _step_art("array", claim="fused", stream_path="plane")
    findings = analysis.check(art, contract="fused-halo")
    assert findings, "array-mode program passed the fused-halo contract"


# --- telemetry ---------------------------------------------------------------


def test_halo_event(tmp_path):
    telemetry.enable(dir=str(tmp_path))
    telemetry.reset()
    try:
        dd, _ = _mk(mult=2)
        dd.make_step(mean6_kernel, engine="stream", interpret=True,
                     stream_halo="fused")
        events = [
            json.loads(line) for line in open(telemetry.event_log_path())
        ]
        ev = [e for e in events if e["event"] == tm.EVENT_STEP_HALO]
        assert ev and ev[-1]["halo"] == "fused"
        assert ev[-1]["source"] == "explicit"
        assert ev[-1]["exchange_route"] == "yzpack_xla"
        # a degraded resolution records the provenance tag
        dd2, _ = _mk(mult=2, route="zpack_xla")
        dd2.make_step(mean6_kernel, engine="stream", interpret=True,
                      stream_halo="fused")
        events = [
            json.loads(line) for line in open(telemetry.event_log_path())
        ]
        ev = [e for e in events if e["event"] == tm.EVENT_STEP_HALO]
        assert ev[-1]["halo"] == "array"
        assert ev[-1]["source"] == "explicit/degraded"
    finally:
        telemetry.disable()
