"""The benchmark's cell ``astaroth-mhd-256.bulk`` on the CPU: its rehearsal
through ``harness/window.py`` at 16^3 (sound; the bf16-storage control, a frozen
dispatch and a program with one term switched off each coming out not
correct), the configuration's numbers against the model's and the ISSUE's, the
benchmark's copy of the update against the program's, the byte and operation
counts of ``mhd_pass_hbm_pct`` / ``mhd_pass_flops_pct`` and the ``.mhd``
per-layer metrics on the fixture timeline."""

import contextlib
import dataclasses
import io
import json
import math
import os
import sys
import time
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import bytes_mhd, flops_mhd, reference_mhd as mhd  # noqa: E402

CELL = "astaroth-mhd-256.bulk"
N, DISPATCH = 16, 2  # rehearsal extent, time steps per dispatch (one trip of the step loop)
MHD = ["plane_pass_pct.mhd", "exchange_dev_pct.mhd", "step_glue_pct.mhd", "kernel_named_pct.mhd",
       "enqueue_ms_p90.mhd", "compiles_in_window.mhd", "mhd_pass_hbm_pct", "mhd_pass_flops_pct"]


def _config():
    with open(os.path.join(ROOT, "benchmark", "configs", "astaroth-mhd-256.json")) as f:
        return json.load(f)


def _rehearse(patch=None, own_cell=False, **flags):
    """One rehearsal in process: (last line, checks by name, plan line).  The
    runs share one built cell a storage (``rehearsal_cells``) but the one that
    asks for its ``own_cell`` to break."""
    from rehearsal_cells import shared_build

    from benchmark.harness import window

    opts = types.SimpleNamespace(
        workload=CELL, seed=2**31 + 44, seconds=0.2, trace=0, lower_precision=False,
        describe_trace=False, also_verify=[], rehearse=N, dispatch_size=DISPATCH)
    vars(opts).update(flags)
    out = io.StringIO()
    shared = contextlib.nullcontext() if own_cell else shared_build("benchmark.factories.mhd")
    with contextlib.redirect_stdout(out), shared:
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
    assert (ran["route"], ran["storage"], ran["descents"]) == ("plane", "native", 0)
    assert (ran["quantities"], ran["stages"], ran["passes"], ran["renamed"]) == (16, 3, 3, 8)
    assert (ran["renamed_by_stage"], ran["exchanged"], ran["steps_per_trip"]) == ("8/8/8", "8/8/8", 2)
    assert (ran["offcentre"], ran["diagonal"], ran["read_sides"], ran["exchanged_sides"]) == (8, 6, 48, 48)
    assert checks["max_abs_err"]["value"] <= 2e-7 and checks["window_state_bad_cells"]["value"] == 0
    # a CPU trace has no device plane, and a loaded host may finish fewer than
    # the ten dispatches a p90 wants inside the short traced stretch
    absent = {"mhd_pass_hbm_pct", "mhd_pass_flops_pct", "plane_pass_pct.mhd", "exchange_dev_pct.mhd",
              "step_glue_pct.mhd", "kernel_named_pct.mhd", "enqueue_ms_p90.mhd"}
    want = {"mcells_per_s_chip", "setup_s"} if not trace else set(MHD) - absent
    assert want <= set(line["rehearsal"]["would_report"])
    if trace:  # the program's own spans, as the benchmark's reader finds them
        from benchmark.harness import timeline

        spans = [h[3] for h in timeline.host_spans(timeline.load(), "domain.step")]
        assert spans and all(
            (a["stages"], a["renamed"], a["steps_per_trip"], a["steps"]) == (3, "8/8/8", 2, DISPATCH)
            for a in spans
        ), spans[:2]


def test_the_bf16_control_is_not_correct():
    line, checks, plan = _rehearse(lower_precision=True, seed=101)
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


def test_a_program_that_skips_a_term_is_not_correct():
    """The PROGRAM with its viscosity switched off, everything else as
    configured, through the cell's own comparison: not correct, by far."""

    def inviscid(cell):
        cell.sim.setup = dataclasses.replace(cell.sim.setup, nu=0.0)
        cell.sim.rebuild_after_reshard()  # the step, rebuilt over the changed set-up

    line, checks, _ = _rehearse(patch=inviscid, own_cell=True, seed=11)
    assert line["rehearsal"]["checks_ok"] is False
    # two steps of the cell's eight a dispatch: a quarter of the time the term has there
    assert checks["max_abs_err"]["value"] > 20 * checks["max_abs_err"]["limit"], checks


def test_configuration_states_the_issues_sizes():
    from stencil_tpu.models import astaroth_mhd_reference as ref

    c = _config()
    assert c["global_extent"] == c["extent_per_chip"] == [256, 256, 256]
    assert (c["radius"], c["quantities"], c["fields"], c["chips"]) == (3, 16, 8, 1)
    assert c["reduced"] == [] and c["mesh"] == [1, 1, 1] and c["dtype"] == "float32"
    assert c["pass"] == {"reads": 16, "writes": 8}
    assert c["expect"] == {"route": "plane", "storage": "native", "quantities": 16,
                           "stages": 3, "renamed": 8}  # and NO depth
    assert c["dispatch"]["bulk"] % 2 == 0  # whole trips of the step loop
    assert set(c["limits"]) == {"max_abs_err"} and c["limits_why"] and c["guarantees"]
    s, model = mhd.setup_from(c, c["global_extent"]), ref.MhdSetup(tuple(c["global_extent"]))
    # the benchmark's copy and the model's defaults agree on every number they share
    for key in ("nu", "eta", "chi", "zeta", "gamma", "cp", "cs0", "mu0", "lnrho0", "lnT0", "box",
                "amplitude", "modes", "max_waves"):
        assert getattr(s, key) == getattr(model, key), key
        assert key in " ".join(c["assumed"]) or key in ("box", "amplitude", "modes", "max_waves")
    assert abs(s.dt - ref.dt_of(model)) < 1e-15 and model.courant == c["setup"]["courant"] == 0.3
    assert abs(s.dt - 0.3 * (2 * math.pi / 256) / (1 + math.sqrt(3) * 0.05)) < 1e-15
    assert s.envelope == 4 * s.amplitude
    assert (mhd.FIELDS, mhd.QUANTITIES, mhd.ALPHA, mhd.BETA) == (
        ref.FIELDS, ref.QUANTITIES, ref.ALPHA, ref.BETA)
    assert all(abs(a[0] - b[0]) < 1e-15 and a[1] == b[1] for a, b in zip(mhd.RK3, ref.COEFFS))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert sum(w["chips"] == 4 for w in bench["workloads"]) * 2 <= len(bench["workloads"])
    assert CELL in next(m for m in bench["end_to_end"] if m["name"] == "mcells_per_s_chip")["workloads"]
    entry = next(x for x in bench["configs"] if x["name"] == "astaroth-mhd-256")
    assert entry["source"] == c["source"] and len(entry["source"]) <= 200 and entry["reduced"] == []
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("astaroth-mhd-256", "bulk", 1)
    # appended behind what was there: the proxy's cell and configuration stand before it
    names = [w["name"] for w in bench["workloads"]]
    assert names.index("astaroth-8q-512.bulk") < names.index("lbm-d3q19-256.bulk") < names.index(CELL)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert f"{c['dispatch']['bulk']}-step" in cell["why"]


def test_two_copies_of_the_update_agree():
    """``harness/reference_mhd.py`` against ``models/astaroth_mhd_reference.py``
    on the benchmark's seeded state: written apart and in another shape, the
    same numbers to rounding."""
    import jax.numpy as jnp

    from stencil_tpu.models import astaroth_mhd_reference as ref

    s = mhd.setup_from(_config(), [N] * 3)
    words = np.asarray([9, 8, 7, 6], dtype=np.uint32)
    c = (jnp.arange(N)[:, None, None], jnp.arange(N)[None, :, None], jnp.arange(N)[None, None, :])
    mine = {q: jnp.broadcast_to(f(*c, words), s.shape) for q, f in mhd.seeded_fields(s).items()}
    model = ref.MhdSetup(s.shape, dt=s.dt)
    theirs = ref.global_fields(model, words)
    for q in mhd.QUANTITIES:
        np.testing.assert_array_equal(np.asarray(mine[q]), np.asarray(theirs[q]), err_msg=q)
    got = dict(zip(mhd.QUANTITIES, mhd.reference(s, 3, None, words)))
    want = ref.steps(model, theirs, 3)
    for q in mhd.QUANTITIES:
        assert float(jnp.max(jnp.abs(got[q] - mine[q]))) > 1e-4, q  # it has moved
        np.testing.assert_allclose(np.asarray(got[q]), np.asarray(want[q]), rtol=0, atol=5e-7, err_msg=q)
    arrays = [got[q] for q in mhd.QUANTITIES]
    assert mhd.state_bad_cells(s, arrays) == 0
    arrays[3] = arrays[3].at[1, 2, 3].set(jnp.nan).at[4, 5, 6].add(0.5)
    assert mhd.state_bad_cells(s, arrays) == 2


def test_bytes_and_operations_come_from_the_configuration_alone():
    c = _config()
    assert bytes_mhd.pass_bytes(c) == 24 * 256**3 * 4 == 1_610_612_736
    assert flops_mhd.flops_per_cell() == 837
    assert flops_mhd.pass_flops(c) == 837 * 256**3
    # the differences are two thirds of it: 21 first, 24 second, 12 mixed
    assert sum(n * k for n, k in flops_mhd.DIFFERENCES.values()) == 21 * 8 + 24 * 10 + 12 * 14 == 576
    c["pass"], c["extent_per_chip"], c["itemsize"] = {"reads": 3, "writes": 2}, [8, 16, 32], 2
    assert bytes_mhd.pass_bytes(c) == 5 * 8 * 16 * 32 * 2
    assert flops_mhd.pass_flops(c) == 837 * 8 * 16 * 32


def test_the_count_is_the_programs_to_a_few_percent():
    """The count is of the equations, not of the program -- but a program that
    shares every read and folds the spacing into its coefficients should land
    on it: the kernels' jaxprs hold 823, 847 and 847 operations a cell."""
    import collections

    import jax
    import jax.numpy as jnp

    from stencil_tpu.models import astaroth_mhd_reference as ref

    setup = ref.MhdSetup((8, 8, 8))
    a = {q: jnp.zeros((8, 8, 8), jnp.float32) for q in ref.FIELDS}
    counts, reads = [], []
    for s in range(ref.SUBSTEPS):
        def one(cur, prev, s=s):
            taps = ref._roll_taps(cur)
            out = ref.substep(setup, taps, prev.__getitem__ if s else None, *ref.COEFFS[s])
            reads.append(len(taps))
            return out

        ops = collections.Counter(e.primitive.name for e in jax.make_jaxpr(one)(a, a).jaxpr.eqns)
        counts.append(sum(ops[k] for k in ("add", "sub", "mul", "neg", "exp", "div")))
    assert counts == [823, 847, 847] and reads == [296] * 3, (counts, reads)
    assert abs(sum(counts) / 3 - flops_mhd.flops_per_cell()) < 0.01 * flops_mhd.flops_per_cell()


def test_the_mhd_metrics_are_declared_for_the_cell_alone():
    import importlib

    from benchmark.harness.window import layer_metrics_for

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    declared = {m["name"]: m for m in per_layer}
    order = [m["name"] for m in per_layer]
    assert order[order.index(MHD[0]):][: len(MHD)] == MHD  # one block, behind the lattice cell's
    assert order.index("lbm_pass_hbm_pct") < order.index(MHD[0])
    mine = {m["name"]: m for m in layer_metrics_for(CELL, {"mcells_per_s_chip", "setup_s"})}
    assert set(MHD) <= set(mine)
    for name in MHD:
        assert declared[name]["workloads"] == [CELL] and declared[name]["moves"] == "mcells_per_s_chip"
        assert mine[name]["cells"] == [CELL]
        for key in ("unit", "better", "source", "layer"):
            assert declared[name][key] == mine[name][key], (name, key)
        assert hasattr(importlib.import_module("benchmark.reducers." + mine[name]["reducer"]), "reduce")
        for other in ("acoustic-so8-600.bulk", "astaroth-8q-512.bulk", "lbm-d3q19-256.bulk",
                      "elastic-so8-600.bulk"):
            assert name not in {m["name"] for m in layer_metrics_for(other, {"mcells_per_s_chip", "setup_s"})}
    # ...and the other cells' named metrics stay theirs
    assert not {n for n in mine if n.endswith((".plane", ".staged", ".wired", ".bulk", ".lbm")) and "idle" not in n}


def test_the_mhd_shares_read_the_plane_pass_by_name():
    """On the fixture timeline (a ``stream_wavefront_pass`` program): the two
    roofline shares against the configuration's counts when pointed at the
    kernel it holds, and nothing where there is no ``stream_plane_pass`` or no
    name at all (the parent of this PR)."""
    from benchmark import selftest_timeline as st
    from benchmark.reducers import named_roofline_flops, named_roofline_hbm, named_share

    def args_of(name):
        with open(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".json")) as f:
            return json.load(f)["args"]

    assert args_of("mhd_pass_hbm_pct")["kernel"] == args_of("mhd_pass_flops_pct")["kernel"] == "^stream_plane_pass$"
    assert args_of("plane_pass_pct.mhd")["kernel"] == ["^stream_plane_pass$"]
    assert args_of("mhd_pass_flops_pct")["peak"] == "bf16_flops_per_s"
    c = _config()
    per_call = flops_mhd.pass_flops(c)
    peak = 2 * per_call / 400e-9 * 4  # two calls in 400 ns of the fixture: 25 %
    ctx = {"timeline": st.fixture(), "table": None, "config": c,
           "peaks": {"bf16_flops_per_s": peak, "hbm_bytes_per_s": 2 * bytes_mhd.pass_bytes(c) / 400e-9 * 2}}
    here = {**args_of("mhd_pass_flops_pct"), "kernel": "^stream_wavefront_pass$"}
    assert abs(named_roofline_flops.reduce(ctx, **here) - 25.0) < 1e-9
    assert abs(named_roofline_hbm.reduce(
        ctx, **{**args_of("mhd_pass_hbm_pct"), "kernel": "^stream_wavefront_pass$"}) - 50.0) < 1e-9
    assert named_roofline_flops.reduce(ctx, **args_of("mhd_pass_flops_pct")) is None  # no plane pass here
    parent = {**ctx, "timeline": st.fixture("parent")}
    assert named_roofline_flops.reduce(parent, **here) is None
    assert named_roofline_flops.reduce({**ctx, "peaks": None}, **here) is None
    for name in ("plane_pass_pct.mhd", "exchange_dev_pct.mhd", "step_glue_pct.mhd", "kernel_named_pct.mhd"):
        assert named_share.reduce(parent, **args_of(name)) is None
    assert named_share.reduce(ctx, **args_of("kernel_named_pct.mhd")) is not None
    # the real chip's peaks are the published ones
    with open(os.path.join(ROOT, "benchmark", "harness", "peaks.json")) as f:
        assert json.load(f)["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
