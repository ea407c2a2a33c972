"""Autotuner tests (stencil_tpu/tune/): cache round-trips (corrupt/stale
files included), burst-aware trial protocol, resilience-classified pruning,
planner consultation, fallback-to-static when disabled, the compile-cache
knob, and the no-raw-env-read lint.

All tier-1 tests run in-process on CPU (interpret-mode pallas, tiny
domains); the bench subprocess acceptance test is tier-2 (slow) — tier-1
sits at ~96% of its wall budget (ROADMAP).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from stencil_tpu import telemetry, tune  # noqa: E402
from stencil_tpu.telemetry import names as tm  # noqa: E402
from stencil_tpu.tune import cache as tune_cache  # noqa: E402
from stencil_tpu.tune.key import WorkloadKey  # noqa: E402
from stencil_tpu.tune.trial import measure_alternating, search  # noqa: E402


def _key(route="jacobi-wrap", domain=(16, 16, 16)):
    return WorkloadKey(
        chip="testchip", domain=domain, dtype="float32", n_fields=1,
        mesh=(1, 1, 1), radius=1, route=route,
    )


@pytest.fixture
def tune_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("STENCIL_TUNE_CACHE", str(tmp_path))
    monkeypatch.delenv("STENCIL_TUNE", raising=False)
    tune.reset_memo()
    yield tmp_path
    tune.reset_memo()


def _counter(name):
    return telemetry.snapshot()["counters"][name]


# --- key + cache -------------------------------------------------------------


def test_workload_key_roundtrip_and_digest():
    k = _key()
    assert WorkloadKey.from_dict(k.to_dict()) == k
    assert k.digest() == _key().digest()
    # any axis change re-keys (a tuned config must never cross workloads)
    assert k.digest() != _key(domain=(32, 16, 16)).digest()
    assert k.digest() != _key(route="stream").digest()
    assert "jacobi-wrap" in k.label()


def test_cache_roundtrip(tune_dir):
    k = _key()
    assert tune_cache.load(k) is None
    path = tune_cache.store(k, {"k": 12}, meta={"trials": 3})
    assert os.path.dirname(path) == str(tune_dir)
    cfg, meta = tune_cache.load(k)
    assert cfg == {"k": 12} and meta["trials"] == 3


def test_cache_corrupt_file_is_a_miss(tune_dir):
    k = _key()
    tune_cache.store(k, {"k": 12})
    with open(tune_cache.path_for(k), "w") as f:
        f.write("{ not json")
    assert tune_cache.load(k) is None  # warn, never crash


def test_cache_stale_toolchain_is_a_miss(tune_dir):
    k = _key()
    p = tune_cache.store(k, {"k": 12})
    doc = json.load(open(p))
    doc["jax"] = "0.0.0-other"
    json.dump(doc, open(p, "w"))
    assert tune_cache.load(k) is None  # re-qualify on a new toolchain
    doc = json.load(open(p))
    assert doc["config"] == {"k": 12}  # the file itself is intact


def test_best_config_counts_hits_and_misses(tune_dir):
    k = _key()
    h0, m0 = _counter(tm.TUNE_CACHE_HIT), _counter(tm.TUNE_CACHE_MISS)
    assert tune.best_config(k) is None
    assert _counter(tm.TUNE_CACHE_MISS) == m0 + 1
    tune.record_config(k, {"k": 9})
    assert tune.best_config(k) == {"k": 9}
    assert _counter(tm.TUNE_CACHE_HIT) == h0 + 1


def test_best_config_disabled_falls_back_to_static(tune_dir, monkeypatch):
    k = _key()
    tune.record_config(k, {"k": 9})
    monkeypatch.setenv("STENCIL_TUNE", "0")
    assert tune.best_config(k) is None  # static picks, no consult
    monkeypatch.setenv("STENCIL_TUNE", "1")
    assert tune.best_config(k) == {"k": 9}
    with tune.disabled():
        assert tune.best_config(k) is None


# --- trial protocol ----------------------------------------------------------


def test_measure_alternating_drops_rep0_and_alternates():
    calls = []
    clock = [0.0]

    def timer():
        return clock[0]

    def make_run(name, cost):
        def run(n):
            calls.append(name)
            clock[0] += cost * n
        return run

    samples = measure_alternating(
        [make_run("a", 1.0), make_run("b", 3.0)], 2, 0.0, reps=2, timer=timer
    )
    # 3 rounds (rep0 + 2), strictly alternating within each round
    assert calls == ["a", "b"] * 3
    # rep 0 discarded; per-iteration figures are exact under the fake clock
    assert samples == [[1.0, 1.0], [3.0, 3.0]]


def test_measure_alternating_per_run_inner():
    clock = [0.0]
    run = lambda n: clock.__setitem__(0, clock[0] + 2.0 * n)
    samples = measure_alternating(
        [run, run], [1, 4], 0.0, reps=1, timer=lambda: clock[0]
    )
    assert samples == [[2.0], [2.0]]


def test_search_selects_fastest_and_reports_static():
    import time as _time

    key = _key(route="synthetic")
    candidates = [{"k": 1}, {"k": 2}]
    costs = {1: 0.003, 2: 0.0005}

    def build_run(cand):
        def run(n):
            _time.sleep(costs[cand["k"]] * n)
        return run

    report = search(key, candidates, build_run, depth_key="k", reps=2, rt=0.0)
    assert report.config == {"k": 2}
    assert report.trials == 2
    r = report.result_for({"k": 1})
    assert r.seconds_per_iter > report.result_for({"k": 2}).seconds_per_iter


def test_search_prunes_injected_vmem_oom_and_deeper_neighbors(tune_dir):
    from stencil_tpu.resilience import inject

    key = _key(route="synthetic")
    candidates = [{"k": 1}, {"k": 4}, {"k": 8}]
    built = []

    def build_run(cand):
        built.append(cand["k"])
        return lambda n: None

    p0 = _counter(tm.TUNE_PRUNED)
    inject.set_plan("compile:vmem_oom:tune:synthetic:k=4")
    try:
        report = search(key, candidates, build_run, depth_key="k", reps=1, rt=0.0)
    finally:
        inject.set_plan(None)
    # k=4 OOMed -> it AND its deeper neighbor k=8 are pruned, k=8 never built
    assert built == [1]
    assert report.config == {"k": 1}
    assert report.pruned == 2
    assert {r.config["k"]: r.pruned for r in report.results} == {
        1: False, 4: True, 8: True,
    }
    assert report.result_for({"k": 8}).failure_class == "vmem_oom"
    assert _counter(tm.TUNE_PRUNED) == p0 + 2  # pruning visible in telemetry


def test_deeper_neighbors_ignores_depth_derived_riders():
    """halo_multiplier mirrors the depth on the wavefront/stream candidates;
    it must not hide deeper neighbors from VMEM_OOM pruning."""
    from stencil_tpu.tune.space import deeper_neighbors, jacobi_wavefront_space

    cands, _ = jacobi_wavefront_space(
        static_m=4, depth_cap=16, z_ring_eligible=False, static_z_ring=True,
        ms=[4, 8, 12],
    )
    failing = next(c for c in cands if c["m"] == 8 and c["alias"] is False)
    deeper = deeper_neighbors(failing, cands, "m")
    assert [c["m"] for c in deeper] == [12]
    assert all(c["alias"] is False for c in deeper)


def test_search_vmem_oom_prunes_deeper_wavefront_style_candidates():
    from stencil_tpu.resilience import inject
    from stencil_tpu.tune.space import jacobi_wavefront_space

    key = _key(route="synthetic")
    cands, _ = jacobi_wavefront_space(
        static_m=2, depth_cap=16, z_ring_eligible=False, static_z_ring=True,
        ms=[2, 8, 12],
    )
    built = []

    def build_run(cand):
        built.append((cand["m"], cand["alias"]))
        return lambda n: None

    inject.set_plan(
        "compile:vmem_oom:tune:synthetic:"
        "alias=0/halo_multiplier=8/m=8"
    )
    try:
        report = search(key, cands, build_run, depth_key="m", reps=1, rt=0.0)
    finally:
        inject.set_plan(None)
    # the alias=False m=8 OOM prunes alias=False m=12 untried; the alias=True
    # family is untouched
    assert (12, False) not in built
    axes = {"storage_dtype": "native"}
    assert report.result_for(
        {"m": 12, "halo_multiplier": 12, "alias": False, "z_ring": False, **axes}
    ).pruned
    assert not report.result_for(
        {"m": 12, "halo_multiplier": 12, "alias": True, "z_ring": False, **axes}
    ).pruned


def test_stream_alias_resolution_precedence(monkeypatch):
    from stencil_tpu.ops.stream_plan import _resolve_stream_alias

    monkeypatch.delenv("STENCIL_STREAM_ALIAS", raising=False)
    # static rule: >= 4 fields alias
    assert _resolve_stream_alias({}, 1) is False
    assert _resolve_stream_alias({}, 4) is True
    # ... read from the plan's route too: the plane route always aliases (its
    # pass runs inside the step loop, ISSUE 28); every other route is as it was
    for nq in range(1, 9):
        assert _resolve_stream_alias({"route": "plane"}, nq) is True
        for route in ("wavefront", "wrap"):
            assert _resolve_stream_alias({"route": route}, nq) is (nq >= 4)
    # the tuned plan and the environment still beat it
    assert _resolve_stream_alias({"route": "plane", "alias": False}, 1) is False
    # tuned plan beats the static rule
    assert _resolve_stream_alias({"alias": True}, 1) is True
    # env beats the tuned plan
    monkeypatch.setenv("STENCIL_STREAM_ALIAS", "0")
    assert _resolve_stream_alias({"alias": True}, 1) is False
    # an autotuner CANDIDATE build beats even the env — its A/B trials must
    # compile two different kernels
    assert _resolve_stream_alias({"alias": True, "alias_forced": True}, 1) is True
    monkeypatch.setenv("STENCIL_STREAM_ALIAS", "bogus")
    with pytest.raises(ValueError, match="STENCIL_STREAM_ALIAS"):
        _resolve_stream_alias({}, 1)


def test_search_retries_transient_mid_measurement(monkeypatch):
    """A connection drop during the timed rounds (not just at build) retries
    under the PR-1 policy instead of crashing the search."""
    monkeypatch.setenv("STENCIL_RETRY_MAX", "3")
    monkeypatch.setenv("STENCIL_RETRY_BACKOFF_S", "0.0")
    key = _key(route="synthetic")
    calls = {"n": 0}

    def build_run(cand):
        def run(n):
            calls["n"] += 1
            if calls["n"] == 3:  # past build+warm: inside the timed protocol
                raise RuntimeError(
                    "UNAVAILABLE: connection reset by peer (injected)"
                )
        return run

    report = search(key, [{"k": 1}], build_run, reps=2, rt=0.0)
    assert report.config == {"k": 1} and report.trials == 1


def test_injected_execute_transient_is_retried(monkeypatch):
    """An execute-phase TRANSIENT from STENCIL_FAULT_PLAN is consumed by the
    retry policy (the hook sits inside the retried unit), not a crash."""
    from stencil_tpu.resilience import inject

    monkeypatch.setenv("STENCIL_RETRY_BACKOFF_S", "0.0")
    inject.set_plan("execute:transient:tune:synthetic")
    try:
        report = search(
            _key(route="synthetic"), [{"k": 1}],
            lambda c: (lambda n: None), reps=1, rt=0.0,
        )
    finally:
        inject.set_plan(None)
    assert report.config == {"k": 1} and report.trials == 1


def test_search_compile_reject_prunes_only_the_candidate():
    from stencil_tpu.resilience import inject

    key = _key(route="synthetic")
    candidates = [{"k": 1}, {"k": 4}, {"k": 8}]
    inject.set_plan("compile:compile_reject:tune:synthetic:k=4")
    try:
        report = search(
            key, candidates, lambda c: (lambda n: None), depth_key="k",
            reps=1, rt=0.0,
        )
    finally:
        inject.set_plan(None)
    assert report.result_for({"k": 4}).pruned
    assert not report.result_for({"k": 8}).pruned  # deeper may still compile
    assert report.trials == 2


# --- end-to-end on the real wrap kernel (interpret) --------------------------


def test_autotune_jacobi_wrap_cold_then_warm(tune_dir):
    from stencil_tpu.tune.runners import autotune_jacobi_wrap

    t0 = _counter(tm.TUNE_TRIALS)
    r1 = autotune_jacobi_wrap(16, 16, 16, interpret=True, reps=1, ks=[1, 2], rt=0.0)
    assert r1.source == "search" and r1.config is not None
    assert 1 <= r1.config["k"] <= 8
    assert _counter(tm.TUNE_TRIALS) > t0
    assert os.path.exists(r1.cache_path)
    # warm cache: ZERO trials, same config
    t1 = _counter(tm.TUNE_TRIALS)
    r2 = autotune_jacobi_wrap(16, 16, 16, interpret=True, reps=1, ks=[1, 2], rt=0.0)
    assert r2.cache_hit and r2.trials == 0 and r2.config == r1.config
    assert _counter(tm.TUNE_TRIALS) == t1


def test_forced_small_vmem_budget_prunes_deep_k(tune_dir, monkeypatch):
    """Acceptance: a forced-small VMEM budget during tuning prunes deep-k
    candidates and still returns a valid config — no crash, pruning visible
    in the telemetry counters."""
    from stencil_tpu.tune.runners import autotune_jacobi_wrap

    monkeypatch.setenv("STENCIL_VMEM_LIMIT_BYTES", str(1))
    p0 = _counter(tm.TUNE_PRUNED)
    report = autotune_jacobi_wrap(
        16, 16, 16, interpret=True, reps=1, ks=[1, 2, 4], rt=0.0
    )
    # nothing beyond the static k=1 fits a 1-byte model budget (the
    # bf16 twin is VMEM-gated too; winners carry the axis explicitly)
    assert report.config == {"k": 1, "storage_dtype": "native"}
    assert report.pruned >= 2
    assert _counter(tm.TUNE_PRUNED) >= p0 + 2


# --- planner consultation ----------------------------------------------------


def test_choose_temporal_k_consults_cache(tune_dir):
    from stencil_tpu.ops.jacobi_pallas import choose_temporal_k

    key = _key_for_wrap()
    static = choose_temporal_k((16, 16, 16), 4)
    tune.record_config(key, {"k": 3})
    assert choose_temporal_k((16, 16, 16), 4, tune_key=key) == 3
    # structurally invalid tuned depth -> static fallback, no crash
    tune.record_config(key, {"k": 99})
    assert choose_temporal_k((16, 16, 16), 4, tune_key=key) == static
    # explicit request always wins (never consults)
    assert choose_temporal_k((16, 16, 16), 4, requested=2, tune_key=key) == 2


def _key_for_wrap():
    from stencil_tpu.tune.key import chip_kind

    return WorkloadKey(
        chip=chip_kind(), domain=(16, 16, 16), dtype="float32", n_fields=1,
        mesh=(1, 1, 1), radius=1, route="jacobi-wrap",
    )


def test_jacobi_wrap_model_uses_tuned_k(tune_dir):
    from stencil_tpu.models.jacobi import Jacobi3D

    model = Jacobi3D(
        16, 16, 16, devices=[jax.devices()[0]], kernel_impl="pallas",
        interpret=True,
    )
    tune.record_config(model.dd.tune_key("jacobi-wrap"), {"k": 3})
    model.realize()
    assert model._wrap_k == 3


def test_jacobi_wavefront_plan_consults_cache(tune_dir):
    from stencil_tpu.models.jacobi import Jacobi3D

    model = Jacobi3D(
        16, 16, 16, kernel_impl="pallas", pallas_path="wavefront",
        interpret=True,
    )
    cfg = {"m": 2, "halo_multiplier": 2, "alias": True, "z_ring": False}
    tune.record_config(model.dd.tune_key("jacobi-wavefront"), cfg)
    assert model._plan_wavefront() == 2
    assert model._tuned_wavefront == cfg
    # invalid depth (exceeds shard extents) -> static plan
    model2 = Jacobi3D(
        16, 16, 16, kernel_impl="pallas", pallas_path="wavefront",
        interpret=True,
    )
    tune.record_config(
        model2.dd.tune_key("jacobi-wavefront"), {"m": 999}, meta={}
    )
    tune.reset_memo()
    assert model2._tuned_wavefront is None
    assert model2._plan_wavefront() >= 1


def test_plan_stream_consults_and_validates(tune_dir):
    from stencil_tpu.domain import DistributedDomain
    from stencil_tpu.core.radius import Radius
    from stencil_tpu.ops.stream_plan import plan_stream

    dd = DistributedDomain(16, 16, 16)
    dd.set_radius(Radius.constant(1))
    dd.set_devices([jax.devices()[0]])
    dd.add_data("q")
    dd.realize()
    static = plan_stream(dd, 1)
    tuned = {"route": "wrap", "m": 2, "z_slabs": False, "grouping": "joint"}
    tune.record_config(dd.tune_key("stream"), tuned)
    assert plan_stream(dd, 1) == tuned
    # a depth cap (user stream_depth / ladder descent) re-plans statically
    assert plan_stream(dd, 1, max_m=3)["m"] == min(3, static["m"])
    # a forced path ignores the tuned auto pick
    assert plan_stream(dd, 1, path="plane")["route"] == "plane"
    # structurally impossible persisted config degrades to the static plan
    tune.record_config(
        dd.tune_key("stream"),
        {"route": "wavefront", "m": 99, "z_slabs": False, "grouping": "joint"},
    )
    tune.reset_memo()
    assert plan_stream(dd, 1) == static


def _consult_stream(fields):
    """(what the stream planner took from a cache entry carrying ``fields``,
    the tuned plan, the static plan)."""
    from stencil_tpu.core.radius import Radius
    from stencil_tpu.domain import DistributedDomain
    from stencil_tpu.ops.stream_plan import plan_stream

    dd = DistributedDomain(16, 16, 16)
    dd.set_radius(Radius.constant(1))
    dd.set_devices([jax.devices()[0]])
    dd.add_data("q")
    dd.realize()
    static = plan_stream(dd, 1)
    tuned = {"route": "wrap", "m": 2, "z_slabs": False, "grouping": "joint"}
    assert static != tuned
    tune.record_config(dd.tune_key("stream"), dict(tuned, **fields))
    tune.reset_memo()  # consult the FILE, as the next process would
    return plan_stream(dd, 1), tuned, static


def _consult_jacobi(fields):
    """The same for the bespoke wavefront's depth (static 2, tuned 3)."""
    from stencil_tpu.models.jacobi import Jacobi3D

    def model():
        return Jacobi3D(16, 16, 16, kernel_impl="pallas",
                        pallas_path="wavefront", interpret=True)

    with tune.disabled():
        static = model()._plan_wavefront()
    assert static != 3
    probe = model()
    tune.record_config(
        probe.dd.tune_key("jacobi-wavefront"),
        dict({"m": 3, "halo_multiplier": 3, "alias": False, "z_ring": False}, **fields),
    )
    tune.reset_memo()
    return probe._plan_wavefront(), 3, static


@pytest.mark.parametrize(
    "fields,warm",
    [
        ({"compute_unit": "mxu"}, False),
        ({"compute_unit": "mxu_band"}, False),
        ({"compute_unit": "mxu_band", "mxu_input": "bf16"}, False),
        ({"compute_unit": "vpu", "mxu_input": "f32"}, True),
    ],
    ids=["mxu", "mxu_band", "mxu_band+bf16in", "vpu+f32"],
)
@pytest.mark.parametrize("consult", [_consult_stream, _consult_jacobi],
                         ids=["stream", "jacobi"])
def test_record_of_a_removed_kernel_form_is_a_logged_miss(
    tune_dir, capsys, consult, fields, warm
):
    """A cache entry written before the matrix-unit axis pair went (PR 29):
    one that names ``mxu`` / ``mxu_band`` / ``bf16`` operands holds a depth
    measured for another kernel — a logged miss, never a crash, never a warm
    hit; one that names ``vpu`` / ``f32`` is what every cache written on a
    chip holds — it stays warm and the two fields are ignored."""
    h0, m0 = _counter(tm.TUNE_CACHE_HIT), _counter(tm.TUNE_CACHE_MISS)
    got, tuned, static = consult(fields)
    logged = "no longer exists" in capsys.readouterr().err
    if warm:
        assert got == tuned and not logged  # no axis field rides the plan
        assert _counter(tm.TUNE_CACHE_HIT) > h0
    else:
        assert got == static and logged
        assert _counter(tm.TUNE_CACHE_MISS) > m0


# --- compile cache + driver flags -------------------------------------------


def test_compile_cache_rule(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set -> used untouched (nothing set in
    code); unset -> the fixed in-checkout ``.jax_cache``, exported and
    applied to the live config.  No other variable is consulted."""
    import stencil_tpu
    from stencil_tpu.utils import config

    live = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert config.apply_compile_cache() == "/elsewhere"
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == live  # left alone

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        checkout = os.path.dirname(os.path.dirname(stencil_tpu.__file__))
        fixed = os.path.join(checkout, ".jax_cache")
        assert config.compile_cache_dir() == fixed
        assert config.apply_compile_cache() == fixed
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
        # idempotent, and stable across calls (never a pid/time/temp name)
        assert config.apply_compile_cache() == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", live)
    # the rule reads jax's own variable only — no STENCIL_* name is left
    import inspect

    for fn in (config.compile_cache_dir, config.apply_compile_cache):
        assert "STENCIL_" not in inspect.getsource(fn)


def test_driver_tune_flags(tune_dir, tmp_path):
    import argparse

    from stencil_tpu.bin import _common

    p = argparse.ArgumentParser()
    _common.add_tune_flags(p)
    args = p.parse_args(["--no-tune", "--tune-cache", str(tmp_path / "c")])
    _common.tune_begin(args)
    try:
        assert not tune.enabled()
        assert tune_cache.cache_dir() == str(tmp_path / "c")
    finally:
        _common.tune_end(args)
    assert tune.enabled()  # restored for the next in-process run
    with pytest.raises(SystemExit):  # --tune and --no-tune are exclusive
        p.parse_args(["--tune", "--no-tune"])


# --- tier-2: the bench acceptance path ---------------------------------------


@pytest.mark.slow
def test_bench_warm_cache_zero_trials(tmp_path):
    """Acceptance: with a warm cache bench.py runs zero tuning trials and
    embeds the tuned config in the BENCH JSON."""
    env = dict(
        os.environ,
        STENCIL_BENCH_SIZE="16",
        STENCIL_BENCH_INTERPRET="1",
        STENCIL_TUNE_CACHE=str(tmp_path),
        STENCIL_RETRY_BACKOFF_S="0.01",
        JAX_PLATFORMS="cpu",
    )

    def run_bench():
        res = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py")],
            capture_output=True, text=True, env=env, timeout=900,
        )
        assert res.returncode == 0, res.stderr
        return json.loads(res.stdout.splitlines()[-1])

    cold = run_bench()
    assert cold["tune"]["source"] == "search" and cold["tune"]["trials"] >= 1
    assert cold["tune"]["tuned_mcells_per_s"] is not None
    warm = run_bench()
    assert warm["tune"]["cache_hit"] and warm["tune"]["trials"] == 0
    assert warm["tune"]["config"] == cold["tune"]["config"]
    assert warm["temporal_k"] == cold["tune"]["config"]["k"]
    assert warm["measurement_protocol"] == "alternating_median_drop_rep0"
