"""The benchmark's cell ``lbm-d3q19-256.bulk`` on the CPU: its rehearsal through
``harness/window.py`` at 16^3 (sound; the bf16-storage control and a frozen
dispatch each coming out not correct), the configuration's numbers against the
model's and the ISSUE's, the benchmark's copy of the update against the
program's, the byte count of ``lbm_pass_hbm_pct`` and the ``.lbm`` per-layer
metrics on the fixture timeline."""

import contextlib
import io
import json
import os
import sys
import time
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import bytes_lbm, reference_lbm as lbm  # noqa: E402

CELL = "lbm-d3q19-256.bulk"
N, DISPATCH = 16, 4  # rehearsal extent, steps per dispatch
LBM = ["stencil_kernel_pct.lbm", "exchange_dev_pct.lbm", "step_glue_pct.lbm",
       "kernel_named_pct.lbm", "enqueue_ms_p90.lbm", "compiles_in_window.lbm", "lbm_pass_hbm_pct"]


def _config():
    with open(os.path.join(ROOT, "benchmark", "configs", "lbm-d3q19-256.json")) as f:
        return json.load(f)


def _rehearse(patch=None, **flags):
    """One rehearsal in process: (last line, checks by name, plan line).  The
    runs share one built cell a storage (``rehearsal_cells``)."""
    from rehearsal_cells import shared_build

    from benchmark.harness import window

    opts = types.SimpleNamespace(
        workload=CELL, seed=2**31 + 39, seconds=0.2, trace=0, lower_precision=False,
        describe_trace=False, also_verify=[], rehearse=N, dispatch_size=DISPATCH)
    vars(opts).update(flags)
    out = io.StringIO()
    with contextlib.redirect_stdout(out), shared_build("benchmark.factories.lbm"):
        rc = window.run(opts, time.perf_counter(), patch=patch)
    assert rc == 0
    lines = [json.loads(x) for x in out.getvalue().splitlines() if x.startswith("{")]
    checks = {x["name"]: x for x in lines if x.get("bench") == "check"}
    return lines[-1], checks, next(x for x in lines if x.get("bench") == "plan")


@pytest.mark.parametrize("trace", [0, 1])
def test_the_rehearsed_cell_comes_out_sound(trace):
    line, checks, plan = _rehearse(trace=trace)
    assert line["rehearsal"]["checks_ok"] is True and line["failed"] == 0, (line, checks)
    assert line["correct"] is False and line["metrics"] == {}  # a rehearsal is never a result
    ran = plan["ran"]
    assert (ran["route"], ran["storage"], ran["descents"]) == ("wrap", "native", 0)
    assert (ran["quantities"], ran["offcentre"], ran["diagonal"], ran["read_sides"]) == (19, 18, 12, 30)
    assert (ran["macros_per_trip"], ran["aliased"], ran["exchanged_sides"]) == (2, 0, 0)
    assert checks["max_abs_err"]["value"] <= 1e-6 and checks["window_state_bad_cells"]["value"] == 0
    assert checks["mass_drift"]["value"] < checks["mass_drift"]["limit"] == 1e-5
    # a CPU trace has no device plane, and a loaded host may finish fewer than
    # the ten dispatches a p90 wants inside the short traced stretch
    absent = {"lbm_pass_hbm_pct", "stencil_kernel_pct.lbm", "exchange_dev_pct.lbm",
              "step_glue_pct.lbm", "kernel_named_pct.lbm", "enqueue_ms_p90.lbm"}
    want = {"mcells_per_s_chip", "setup_s"} if not trace else set(LBM) - absent
    assert want <= set(line["rehearsal"]["would_report"])
    if trace:  # the program's own spans, as the benchmark's reader finds them
        from benchmark.harness import timeline

        spans = [h[3] for h in timeline.host_spans(timeline.load(), "domain.step")]
        assert spans and all(
            (a["quantities"], a["diagonal"], a["read_sides"], a["steps"]) == (19, 12, 30, DISPATCH)
            for a in spans
        ), spans[:2]


@pytest.mark.parametrize("seed", [101, 2**31 + 102, 103])
def test_the_bf16_control_is_not_correct(seed):
    line, checks, plan = _rehearse(lower_precision=True, seed=seed)
    assert plan["ran"]["storage"] == "bf16"
    assert line["rehearsal"]["checks_ok"] is False
    assert "max_abs_err" in [n for n, c in checks.items() if not c["ok"]], checks
    assert checks["max_abs_err"]["value"] > 20 * checks["max_abs_err"]["limit"]


def test_a_frozen_dispatch_is_not_correct(monkeypatch):
    def freeze(cell):  # the step returns its state unchanged (on the shared cell: undone behind the test)
        monkeypatch.setattr(cell, "dispatch", lambda n: None, raising=False)

    line, checks, _ = _rehearse(patch=freeze, seed=7)
    bad = [n for n, c in checks.items() if not c["ok"]]
    assert line["rehearsal"]["checks_ok"] is False and "max_abs_err" in bad, (line, checks)


def test_configuration_states_the_issues_sizes():
    from stencil_tpu.models import lbm_reference as ref

    c = _config()
    assert c["global_extent"] == c["extent_per_chip"] == [256, 256, 256]
    assert (c["radius"], c["quantities"], c["fields"], c["chips"]) == (1, 19, 1, 1)
    assert c["reduced"] == [] and c["mesh"] == [1, 1, 1] and c["dtype"] == "float32"
    assert c["pass"] == {"reads": 19, "writes": 19}
    assert set(c["expect"]) == {"route", "depth", "storage", "quantities", "diagonal"}
    assert (c["expect"]["storage"], c["expect"]["quantities"], c["expect"]["diagonal"]) == ("native", 19, 12)
    assert c["dispatch"]["bulk"] % (2 * c["expect"]["depth"]) == 0  # whole trips of the macro loop
    s, model = lbm.setup_from(c, c["global_extent"]), ref.LbmSetup(tuple(c["global_extent"]))
    # the benchmark's copy and the model agree on every number they share
    assert (s.nu, s.u0, s.modes, s.rho0, s.max_waves) == (
        model.nu, model.u0, model.modes, model.rho0, model.max_waves)
    assert (s.rho_band, s.u_max) == (ref.RHO_BAND, ref.U_MAX) and s.omega == model.omega
    assert abs(s.omega - 5.0 / 3.0) < 1e-12 and abs(s.nu - 1 / 30) < 1e-15
    assert (lbm.C, lbm.W, lbm.NAMES) == (ref.C, ref.W, ref.NAMES)
    # the lattice: weights sum to one, first moments vanish, second are c_s^2 = 1/3
    assert abs(sum(lbm.W) - 1) < 1e-15
    for a in range(3):
        assert sum(w * cc[a] for w, cc in zip(lbm.W, lbm.C)) == 0
        for b in range(3):
            assert abs(sum(w * cc[a] * cc[b] for w, cc in zip(lbm.W, lbm.C)) - (a == b) / 3) < 1e-15
    assert all(lbm.C[i + 1] == tuple(-v for v in lbm.C[i]) for i in range(1, 19, 2))
    assert sum(1 for cc in lbm.C if sum(map(abs, cc)) == 2) == c["expect"]["diagonal"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert sum(w["chips"] == 4 for w in bench["workloads"]) * 2 <= len(bench["workloads"])
    assert CELL in next(m for m in bench["end_to_end"] if m["name"] == "mcells_per_s_chip")["workloads"]
    entry = next(x for x in bench["configs"] if x["name"] == "lbm-d3q19-256")
    assert entry["source"] == c["source"] and len(entry["source"]) <= 200 and entry["reduced"] == []
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("lbm-d3q19-256", "bulk", 1)
    assert len(cell["why"]) <= 200


def test_two_copies_of_the_update_agree():
    """``harness/reference_lbm.py`` against ``models/lbm_reference.py`` on the
    benchmark's seeded state: written apart, the same numbers."""
    import jax.numpy as jnp

    from stencil_tpu.models import lbm_reference as ref

    s = lbm.setup_from(_config(), [N] * 3)
    words = np.asarray([9, 8, 7, 6], dtype=np.uint32)
    c = (jnp.arange(N)[:, None, None], jnp.arange(N)[None, :, None], jnp.arange(N)[None, None, :])
    mine = [jnp.broadcast_to(lbm.seeded_fields(s)[q](*c, words), s.shape) for q in lbm.NAMES]
    theirs = ref.global_fields(ref.LbmSetup(s.shape), words)
    for q, a, b in zip(lbm.NAMES, mine, theirs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=q)
    got = lbm.reference(s, 5, None, words)
    want = ref.steps(ref.LbmSetup(s.shape), theirs, 5)
    assert max(float(jnp.max(jnp.abs(g - m))) for g, m in zip(got, mine)) > 1e-4  # it has moved
    for q, g, w in zip(lbm.NAMES, got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=0, atol=5e-7, err_msg=q)
    bad, mass = lbm.state_counts(s, got)
    assert bad == 0 and abs(mass - lbm.seeded_mass(s)) / lbm.seeded_mass(s) < 1e-6
    spoiled = [g for g in got]
    spoiled[3] = spoiled[3].at[1, 2, 3].set(jnp.nan).at[4, 5, 6].add(0.5)
    assert lbm.state_counts(s, spoiled)[0] == 2


def test_pass_bytes_come_from_the_configuration_alone():
    c = _config()
    assert bytes_lbm.pass_bytes(c) == 38 * 256**3 * 4 == 2_550_136_832
    c["pass"], c["extent_per_chip"], c["itemsize"] = {"reads": 3, "writes": 2}, [8, 16, 32], 2
    assert bytes_lbm.pass_bytes(c) == 5 * 8 * 16 * 32 * 2


def test_the_lbm_metrics_are_declared_for_the_cell_alone():
    import importlib

    from benchmark.harness.window import layer_metrics_for

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        declared = {m["name"]: m for m in json.load(f)["per_layer"]}
    mine = {m["name"]: m for m in layer_metrics_for(CELL, {"mcells_per_s_chip", "setup_s"})}
    assert set(LBM) <= set(mine)
    for name in LBM:
        assert declared[name]["workloads"] == [CELL] and declared[name]["moves"] == "mcells_per_s_chip"
        assert mine[name]["cells"] == [CELL]
        for key in ("unit", "better", "source", "layer"):
            assert declared[name][key] == mine[name][key], (name, key)
        assert hasattr(importlib.import_module("benchmark.reducers." + mine[name]["reducer"]), "reduce")
        for other in ("acoustic-so8-600.bulk", "astaroth-8q-512.bulk", "jacobi3d-512.bulk"):
            assert name not in {m["name"] for m in layer_metrics_for(other, {"mcells_per_s_chip", "setup_s"})}
    # ...and the other cells' named metrics stay theirs
    assert not {n for n in mine if n.endswith((".plane", ".staged", ".wired", ".bulk")) and "idle" not in n}


def test_the_lbm_shares_read_the_stream_kernels_by_name():
    """On the fixture timeline (a ``stream_wavefront_pass`` program): the
    kernel's share, the roofline share against ``bytes_lbm.pass_bytes``, and
    nothing on a program that names nothing."""
    import re

    from benchmark import selftest_timeline as st
    from benchmark.reducers import named_roofline_hbm, named_share

    def args_of(name):
        with open(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".json")) as f:
            return json.load(f)["args"]

    kernel = args_of("stencil_kernel_pct.lbm")["kernel"]
    assert kernel == [args_of("lbm_pass_hbm_pct")["kernel"]] == ["^stream_(wrap|plane|wavefront)_pass$"]
    pat = re.compile(kernel[0])
    assert all(pat.search(k) for k in ("stream_wrap_pass", "stream_plane_pass", "stream_wavefront_pass"))
    assert not any(pat.search(k) for k in ("jacobi_wrap_step", "blend_slab", "stream_wrap_pass_2"))
    c = _config()
    per_call = bytes_lbm.pass_bytes(c)
    peak = 2 * per_call / 400e-9 * 4  # so that the share is 25 %
    ctx = {"timeline": st.fixture(), "table": None, "peaks": {"hbm_bytes_per_s": peak}, "config": c}
    assert abs(named_roofline_hbm.reduce(ctx, **args_of("lbm_pass_hbm_pct")) - 25.0) < 1e-9
    parent = {**ctx, "timeline": st.fixture("parent")}
    assert named_roofline_hbm.reduce(parent, **args_of("lbm_pass_hbm_pct")) is None
    share = named_share.reduce(ctx, **args_of("stencil_kernel_pct.lbm"))
    assert 0 < share <= 100
    assert named_share.reduce(ctx, **args_of("kernel_named_pct.lbm")) is not None
    for name in ("stencil_kernel_pct.lbm", "exchange_dev_pct.lbm", "step_glue_pct.lbm", "kernel_named_pct.lbm"):
        assert named_share.reduce(parent, **args_of(name)) is None


def test_the_registry_mirror_still_equals_the_registry():
    from stencil_tpu.telemetry import names as tm

    with open(os.path.join(ROOT, "benchmark", "harness", "program_names.json")) as f:
        mirror = json.load(f)
    assert sorted(mirror["kernels"]) == sorted(tm.ALL_KERNELS)
