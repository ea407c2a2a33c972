"""The benchmark's cell ``elastic-so8-600.bulk`` on the CPU: every part of
``benchmark/selftest_elastic.py`` (its rehearsal through ``harness/window.py``
at 24^3, the bf16-storage control and a frozen step each coming out not
correct, the slab reference against the whole-array one, the byte count of
``plane_pass_hbm_pct.staged``), the configuration's numbers against the
model's and the ISSUE's, the benchmark's copy of the update against the
program's, and the new per-layer metrics on the fixture timeline."""

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import bytes_staged, reference_elastic as wave  # noqa: E402

CELL = "elastic-so8-600.bulk"
STAGED = ["plane_pass_pct.staged", "exchange_dev_pct.staged", "step_glue_pct.staged",
          "kernel_named_pct.staged", "stage_pct.v", "stage_pct.t", "enqueue_ms_p90.staged",
          "compiles_in_window.staged", "plane_pass_hbm_pct.staged"]


def _selftest():
    spec = importlib.util.spec_from_file_location(
        "bench_selftest_elastic", os.path.join(ROOT, "benchmark", "selftest_elastic.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _config():
    with open(os.path.join(ROOT, "benchmark", "configs", "elastic-so8-600.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("part", list("abcde"))
def test_selftest_elastic(part, capsys):
    getattr(_selftest(), "part_" + part)()
    assert f"{part} " in capsys.readouterr().out


def test_configuration_states_the_issues_sizes():
    from stencil_tpu.models import elastic_reference as ref

    c = _config()
    assert c["global_extent"] == c["extent_per_chip"] == [512 + 2 * 40 + 2 * 4] * 3
    assert (c["radius"], c["space_order"], c["nbl"], c["quantities"], c["fields"]) == (4, 8, 40, 13, 1)
    assert c["reduced"] == [] and c["mesh"] == [1, 1, 1] and c["dtype"] == "float32"
    assert c["expect"] == {"route": "plane", "depth": 1, "storage": "native"}
    assert [(s["exchanged"], s["written"]) for s in c["stages"]] == [(6, 3), (3, 6)]
    assert sum(p["writes"] for p in c["passes"]) == 9 == len(ref.WAVEFIELDS)
    grid, s = ref.AcousticGrid(tuple(c["global_extent"])), wave.setup_from(c, c["global_extent"])
    # the benchmark's copy and the model agree on every number they share
    assert (s.nbl, s.frame, s.spacing, s.vp_min, s.vp_max, s.nlayers, s.modes, s.cfl) == (
        grid.nbl, ref.FRAME, grid.spacing, grid.vp_min, grid.vp_max, grid.nlayers, ref.MODES, ref.CFL)
    assert s.dt == ref.dt_of(grid) and s.physical == (512, 512, 512) and wave.COEFFS == ref.COEFFS
    assert abs(s.dt - 2.4366) < 1e-4  # what the configuration's `assumed` says
    assert wave.WAVEFIELDS == ref.WAVEFIELDS and wave.RADIUS == ref.RADIUS
    assert wave.TERMS["vx"] == ref.STAGE_V["vx"] and wave.TERMS["div"] == ref.DIVERGENCE
    assert {t: wave.TERMS[t] for t in ("txy", "txz", "tyz")} == ref.STAGE_T_SHEAR
    # 13 lane-padded arrays: what the configuration calls resident
    assert abs(13 * 608 * 608 * 640 * 4 / 1e9 - 12.30) < 0.005
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert sum(w["chips"] == 4 for w in bench["workloads"]) * 2 <= len(bench["workloads"])
    assert CELL in next(m for m in bench["end_to_end"] if m["name"] == "mcells_per_s_chip")["workloads"]
    entry = next(x for x in bench["configs"] if x["name"] == "elastic-so8-600")
    assert entry["source"] == c["source"] and len(entry["source"]) <= 200 and entry["reduced"] == []


def test_two_copies_of_the_update_agree():
    """``harness/reference_elastic.py`` (zero halo, x-slabs, 1-D profiles)
    against ``models/elastic_reference.py`` (periodic ``jnp.roll``, whole
    arrays) on the benchmark's seeded fields: written apart, they differ by
    roundings; the two sets of seeded fields are the same numbers."""
    import jax.numpy as jnp

    from stencil_tpu.models import elastic_reference as ref

    s = wave.setup_from(_config(), [24] * 3)
    assert s.nbl == 6 and s.physical == (4, 4, 4)
    words = np.asarray([9, 8, 7, 6], dtype=np.uint32)
    grid = ref.AcousticGrid(s.shape, nbl=s.nbl)
    c = (jnp.arange(24)[:, None, None], jnp.arange(24)[None, :, None], jnp.arange(24)[None, None, :])
    mine = {k: jnp.broadcast_to(fn(*c, words), s.shape).astype(jnp.float32)
            for k, fn in wave.seeded_fields(s).items()}
    theirs = ref.global_fields(grid, words)
    for q in ref.QUANTITIES:
        np.testing.assert_allclose(np.asarray(mine[q]), np.asarray(theirs[q]), rtol=2e-7, atol=0, err_msg=q)
    want = ref.steps_framed(grid, mine, 5)
    got = wave.reference_slab(s, 5, words, 0, 24, halo=0)
    assert min(float(jnp.max(jnp.abs(want[q]))) for q in ref.WAVEFIELDS) > 1e-3
    for q, g in zip(wave.WAVEFIELDS, got):
        np.testing.assert_allclose(np.asarray(g), np.asarray(want[q]), rtol=0, atol=2e-6, err_msg=q)


def test_the_staged_metrics_are_declared_for_the_cell_alone():
    from benchmark.harness.window import layer_metrics_for

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        declared = {m["name"]: m for m in json.load(f)["per_layer"]}
    mine = {m["name"] for m in layer_metrics_for(CELL, {"mcells_per_s_chip", "setup_s"})}
    assert set(STAGED) <= mine
    for name in STAGED:
        assert declared[name]["workloads"] == [CELL] and declared[name]["moves"] == "mcells_per_s_chip"
        assert name not in {m["name"] for m in layer_metrics_for("acoustic-so8-600.bulk",
                                                                {"mcells_per_s_chip", "setup_s"})}
    # ...and acoustic's five stay acoustic's
    assert not {n for n in mine if n.endswith(".plane") or n in ("plane_pass_pct", "plane_pass_hbm_pct")}


def test_stage_shares_read_the_stage_scopes():
    """``stage_pct.v`` / ``.t`` select by the scopes ``step.stage.0`` / ``.1``
    wherever they sit in an op's path; a program that has no staged step (the
    fixture's; this PR's parent) reads 0, one that names nothing reads
    nothing."""
    import re

    from benchmark import selftest_timeline as st
    from benchmark.reducers import named_share

    args = {}
    for tag in "vt":
        with open(os.path.join(ROOT, "benchmark", "layer_metrics", f"stage_pct.{tag}.json")) as f:
            args[tag] = json.load(f)["args"]
    assert args["v"]["scope"] == [r"(^|/)step\.stage\.0(/|$)"]
    assert args["t"]["scope"] == [r"(^|/)step\.stage\.1(/|$)"]
    pat = re.compile(args["t"]["scope"][0])
    assert pat.search("jit(step)/while/body/step.stage.1/step.pass/stream_plane_pass/pallas_call")
    assert pat.search("step.stage.1/exchange.z/exchange.z.wrap/blend_slab")
    assert not pat.search("jit(step)/while/body/step.stage.10/step.pass")
    assert not pat.search("jit(step)/while/body/step.stage.0/exchange.x")
    ctx = {"timeline": st.fixture(), "table": None, "peaks": None}
    assert named_share.reduce(ctx, **args["v"]) == 0.0
    assert named_share.reduce({**ctx, "timeline": st.fixture("parent")}, **args["v"]) is None


def test_staged_roofline_counts_the_mean_pass():
    from benchmark import selftest_timeline as st
    from benchmark.reducers import named_roofline_hbm

    c = _config()
    per_call = bytes_staged.plane_pass_bytes(c)
    assert per_call == 41 * 608**3 * 4 / 4
    peak = 2 * per_call / 400e-9 * 4  # so that the share is 25 %
    ctx = {"timeline": st.fixture(), "table": None, "peaks": {"hbm_bytes_per_s": peak}, "config": c}
    args = {"kernel": "^stream_wavefront_pass$", "bytes_fn": "benchmark.harness.bytes_staged:plane_pass_bytes"}
    assert abs(named_roofline_hbm.reduce(ctx, **args) - 25.0) < 1e-9
    assert named_roofline_hbm.reduce({**ctx, "timeline": st.fixture("parent")}, **args) is None
