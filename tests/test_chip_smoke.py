"""Tier-1: the seams between the program and its machine — the "CPU only
when asked for" guard every entry point shares, and ``chip_smoke.py``'s own
control flow, debugged here at 16^3 under interpret so it costs no chip
time.  (The compile-cache rule is pinned in tests/test_tune.py.)"""

import importlib.util
import json
import os

import jax
import pytest

from stencil_tpu.bin import _common

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke():
    return _load("chip_smoke")


@pytest.fixture
def unrequested(monkeypatch):
    """As if JAX_PLATFORMS had never been set (conftest sets it)."""
    monkeypatch.setattr(_common, "_requested_platforms", lambda: "")


# --- CPU only when asked for -----------------------------------------------------


def test_requested_cpu_runs_and_reports_interpret():
    # conftest asked for the CPU (env and config), so the guard passes and
    # says kernels will be interpreted
    assert _common.require_platform("test") is True


def test_unrequested_cpu_exits_naming_the_platform(unrequested):
    """A run that was not told JAX_PLATFORMS=cpu and finds itself on the
    CPU lost its accelerator: non-zero exit, platform named."""
    with pytest.raises(SystemExit) as e:
        _common.require_platform("jacobi3d")
    assert e.value.code not in (0, None)
    assert "'cpu'" in str(e.value.code) and "jacobi3d" in str(e.value.code)


def test_driver_main_refuses_unrequested_cpu(unrequested):
    from stencil_tpu.bin import jacobi3d

    with pytest.raises(SystemExit) as e:
        jacobi3d.main(["16", "16", "16", "--iters", "1"])
    assert "'cpu'" in str(e.value.code)


def test_bench_refuses_the_cpu_without_its_test_knob(monkeypatch):
    bench = _load("bench")
    monkeypatch.delenv("STENCIL_BENCH_INTERPRET", raising=False)
    with pytest.raises(SystemExit) as e:  # asked-for CPU, but no interpret knob
        bench.main([])
    assert "'cpu'" in str(e.value.code)
    monkeypatch.setenv("STENCIL_BENCH_INTERPRET", "1")
    monkeypatch.setattr(_common, "_requested_platforms", lambda: "")
    with pytest.raises(SystemExit) as e:  # knob set, but the CPU was not asked for
        bench.main([])
    assert "'cpu'" in str(e.value.code)


# --- chip_smoke.py ------------------------------------------------------------------


def test_smoke_refuses_anything_but_a_tpu(smoke, capsys):
    """Under JAX_PLATFORMS=cpu: non-zero, the platform it found named on
    stderr, and NO result line on stdout — before anything is built."""
    assert smoke.main([]) == 2
    out = capsys.readouterr()
    assert out.out == ""
    assert "'cpu'" in out.err and "nothing was built" in out.err


def test_smoke_legs_run_green_under_interpret(smoke):
    """Every leg, called the way ``main`` calls it but with the platform
    check bypassed by argument: one-chip legs on the first fake device, the
    four-chip legs on a [2,2,1] mesh of four."""
    lines = []
    legs = smoke.run_legs(jax.devices(), n=16, interpret=True, emit=lines.append)
    assert [leg["leg"] for leg in legs] == [
        "D user-mean6-4", "D jacobi3d-4", "C ripple-4",
        "A user-mean6", "B jacobi3d", "E astaroth-8q",
    ]
    for leg, line in zip(legs, lines):
        assert json.loads(line)["leg"] == leg["leg"]  # one JSON line per leg
        assert leg["ok"], leg
        assert leg["interpret"] is (leg["leg"] != "C ripple-4")
        assert leg["ran"] == leg["planned"] and not leg["descents"]
        assert leg["descent_counter"] == 0 and leg["retry_counter"] == 0
        assert leg["cache_dir"] == jax.config.jax_compilation_cache_dir
    by = {leg["leg"]: leg for leg in legs}
    assert by["D user-mean6-4"]["placement"]["mesh"] == [2, 2, 1]
    assert by["D user-mean6-4"]["ran"]["route"] == "wavefront"
    assert by["D jacobi3d-4"]["ran"]["route"] == "wavefront"
    assert by["C ripple-4"]["verdict"]["mismatches"] == [0, 0]
    assert by["A user-mean6"]["ran"]["route"] == "wrap"
    assert by["E astaroth-8q"]["ran"] == {
        "route": "wavefront", "m": 3, "grouping": "joint",
    }


def _stub_legs(smoke, monkeypatch, fail=()):
    """Replace the four leg functions with instant stand-ins that record
    (name, device count) — the driver's own control flow is what is left."""
    calls = []

    def make(default):
        def leg(devices, *a, name=default, **k):
            calls.append((name, len(devices)))
            if default in fail:
                raise RuntimeError("Mosaic failed to compile TPU kernel: injected")
            return {"leg": name, "ok": True}

        return leg

    for fn, default in (("leg_user_mean6", "A user-mean6"), ("leg_jacobi3d", "B jacobi3d"),
                        ("leg_ripple", "C ripple-4"), ("leg_astaroth", "E astaroth-8q")):
        monkeypatch.setattr(smoke, fn, make(default))
    return calls


def test_smoke_runs_every_leg_the_machine_can(smoke, monkeypatch):
    calls = _stub_legs(smoke, monkeypatch)
    smoke.run_legs(jax.devices()[:1], emit=lambda line: None)
    assert calls == [("A user-mean6", 1), ("B jacobi3d", 1), ("E astaroth-8q", 1)]
    del calls[:]
    smoke.run_legs(jax.devices()[:4], emit=lambda line: None)
    assert calls == [
        ("D user-mean6-4", 4), ("D jacobi3d-4", 4), ("C ripple-4", 4),
        ("A user-mean6", 1), ("B jacobi3d", 1), ("E astaroth-8q", 1),
    ]


def test_smoke_records_a_failing_leg_and_keeps_going(smoke, monkeypatch):
    """A leg that raises is a failed leg, not a crashed smoke: the rest
    still run and the failure decides the exit."""
    _stub_legs(smoke, monkeypatch, fail=("A user-mean6",))
    legs = smoke.run_legs(jax.devices()[:1], emit=lambda line: None)
    assert [leg["ok"] for leg in legs] == [False, True, True]
    assert legs[0]["leg"] == "A user-mean6" and "injected" in legs[0]["error"]


def test_smoke_compare_catches_a_wrong_answer(smoke):
    import numpy as np

    want = np.linspace(0.0, 1.0, 64, dtype=np.float32).reshape(4, 4, 4)
    assert smoke.compare(want.copy(), want, **smoke.TOL)["bitwise"]
    off = want.copy()
    off[1, 2, 3] += 1e-3
    v = smoke.compare(off, want, **smoke.TOL)
    assert not v["ok"] and not v["bitwise"] and v["max_abs_err"] > 9e-4
    bad = want.copy()
    bad[0, 0, 0] = np.nan
    assert not smoke.compare(bad, want, **smoke.TOL)["ok"]
