"""Tier-1: the perf ledger (stencil_tpu/telemetry/ledger.py +
scripts/perf_ledger.py) — artifact normalization over small BENCH_r*
documents of the three shapes a harness leaves (written by the fixture
below: what is under test is the ingest, not any round's numbers),
idempotent appends, and the trailing-median regression gate flagging a
synthetic regression.  The CLI subprocess run is tier-2 ``slow``."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

from stencil_tpu.telemetry import ledger

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def bench_artifacts(tmp_path):
    """A five-round BENCH_r* series in the three artifact shapes: harness
    wrappers with a ``parsed`` field (r01-r04), a failed run with
    ``parsed: null`` and no artifact line in its tail (r05), and a raw
    one-line bench document (the rerun)."""
    d = tmp_path / "artifacts"
    d.mkdir()

    def doc(value, **extra):
        return dict({"metric": "jacobi3d_mcells_per_s_per_chip",
                     "value": value, "unit": "Mcells/s",
                     "chip_copy_gbps": 500.0}, **extra)

    paths = []
    for n, value in ((1, 100.0), (2, 200.0), (3, 210.0), (4, 300.0)):
        body = doc(value)
        p = d / f"BENCH_r0{n}.json"
        p.write_text(json.dumps({
            "n": n, "cmd": "bench", "rc": 0,
            "tail": "log line\n" + json.dumps(body) + "\n", "parsed": body,
        }))
        paths.append(p)
    p = d / "BENCH_r05.json"
    p.write_text(json.dumps({
        "n": 5, "cmd": "bench", "rc": 1,
        "tail": "Traceback (most recent call last):\n  ...\nRuntimeError",
        "parsed": None,
    }))
    paths.append(p)
    p = d / "BENCH_r05_rerun.json"
    p.write_text(json.dumps(doc(
        400.0, exchange_path_mcells_per_s_per_chip=350.0,
        astaroth_8q_mupdates_per_s=80.0,
    )))
    paths.append(p)
    # distinct mtimes, in round order: the ledger's clock is the file's
    for i, p in enumerate(paths):
        os.utime(p, (1e9 + i, 1e9 + i))
    return [str(p) for p in paths]


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "scripts", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ingest_all(path, artifacts):
    entries = []
    for f in artifacts:
        entries.extend(ledger.entries_from_artifact(f))
    return ledger.append_entries(str(path), entries)


# --- artifact normalization --------------------------------------------------


class TestIngest:
    def test_bench_r_series(self, tmp_path, bench_artifacts):
        """The acceptance pin: a BENCH_r01-r05 series ingests into the
        headline series (r05 proper died pre-artifact and contributes
        nothing — its data rides the rerun), newest value the rerun's."""
        led = tmp_path / "ledger.jsonl"
        n = _ingest_all(led, bench_artifacts)
        assert n == 12  # 5 headline + 5 chip_copy + the rerun's 2 companions
        entries = ledger.read_ledger(str(led))
        headline = [
            e for e in entries if e["key"] == "jacobi3d_mcells_per_s_per_chip"
        ]
        assert [e["source"] for e in headline] == [
            "BENCH_r01.json", "BENCH_r02.json", "BENCH_r03.json",
            "BENCH_r04.json", "BENCH_r05_rerun.json",
        ]
        assert [e["value"] for e in headline] == [100.0, 200.0, 210.0, 300.0, 400.0]
        # re-ingesting is idempotent (dedupe on key+source)
        assert _ingest_all(led, bench_artifacts) == 0
        assert len(ledger.read_ledger(str(led))) == len(entries)

    def test_judge_wrapper_and_tail_fallback(self, tmp_path):
        """All three artifact shapes normalize: a raw bench doc, the judge
        wrapper's parsed field, and a failed run whose artifact line only
        survives in the tail."""
        raw = {"metric": "m", "value": 10.0, "unit": "u"}
        wrapped = {"rc": 0, "parsed": dict(raw, value=11.0), "tail": ""}
        tail_only = {
            "rc": 1,
            "parsed": None,
            "tail": "noise\n" + json.dumps(dict(raw, value=12.0)) + "\ncrash",
        }
        for i, doc in enumerate((raw, wrapped, tail_only)):
            p = tmp_path / f"a{i}.json"
            p.write_text(json.dumps(doc))
        vals = {
            ledger.entries_from_artifact(str(tmp_path / f"a{i}.json"))[0]["value"]
            for i in range(3)
        }
        assert vals == {10.0, 11.0, 12.0}

    def test_weak_scaling_summary(self, tmp_path):
        doc = {
            "bench": "weak_scaling_sweep",
            "meshes": [
                {"mesh": [2, 1, 1], "chips": 2,
                 "mcells_per_s_per_chip": {"off": 100.0, "split": 110.0}},
                {"mesh": [2, 2, 2], "chips": 8,
                 "mcells_per_s_per_chip": {"off": 90.0, "split": None}},
            ],
        }
        p = tmp_path / "weak_scaling_summary.json"
        p.write_text(json.dumps(doc))
        entries = ledger.entries_from_artifact(str(p))
        keys = {e["key"]: e["value"] for e in entries}
        assert keys == {
            "weak:2x1x1:off": 100.0, "weak:2x1x1:split": 110.0,
            "weak:2x2x2:off": 90.0,  # the None cell is dropped, not 0
        }

    def test_bench_exchange_route_ab(self, tmp_path):
        """bench_exchange's route-A/B JSON line lands as its own series:
        direct's steady-state rate plus each packed route's speedup — all
        higher-is-better, so packed-route wins are regression-gated like
        the headline numbers."""
        doc = {
            "bench": "exchange",
            "extent": [128, 128, 128],
            "quantities": 1,
            "route_ab": {
                "routes": {
                    "direct": {"ms_per_exchange": 2.0, "per_axis_ms": {}},
                    "zpack_xla": {"ms_per_exchange": 1.0, "per_axis_ms": {}},
                    "yzpack_xla": {"ms_per_exchange": 0.8, "per_axis_ms": {}},
                },
                "speedup_vs_direct": {
                    "zpack_xla": 2.0, "yzpack_xla": 2.5, "broken": None,
                },
            },
        }
        p = tmp_path / "exchange_ab.json"
        p.write_text(json.dumps(doc))
        entries = ledger.entries_from_artifact(str(p))
        keys = {e["key"]: e["value"] for e in entries}
        assert keys == {
            "exchange_ab:direct:exchanges_per_s": 500.0,
            "exchange_ab:zpack_xla:speedup": 2.0,
            "exchange_ab:yzpack_xla:speedup": 2.5,  # None speedup dropped
        }
        # and the gate consumes them like any other series
        assert ledger.append_entries(str(tmp_path / "l.jsonl"), entries) == 3

    def test_soak_summary_reshard_series(self, tmp_path):
        """The chaos soak's summary lands as LOWER-is-better series:
        recovery wall clock plus the median in-memory reshard time — and
        the gate flags a RISE there, not a drop.  A failed soak (digests
        differ) contributes nothing."""
        doc = {
            "bench": "soak_kill_resume",
            "bitwise_identical": True,
            "kills": [{"kill": 1}, {"kill": 2}],
            "reshard_seconds": [0.4, 0.2, 0.3],
            "recovery_seconds": 9.5,
        }
        p = tmp_path / "soak_summary.json"
        p.write_text(json.dumps(doc))
        entries = ledger.entries_from_artifact(str(p))
        by_key = {e["key"]: e for e in entries}
        assert by_key["soak:recovery_seconds"]["value"] == 9.5
        assert by_key["soak:recovery_seconds"]["better"] == "lower"
        assert by_key["reshard:seconds"]["value"] == 0.3  # the median
        assert by_key["reshard:seconds"]["better"] == "lower"
        # a rise flags, a drop (improvement) does not
        lpath = str(tmp_path / "l.jsonl")
        ledger.append_entries(lpath, entries)
        worse = [dict(e, ts=e["ts"] + 1, source="next.json",
                      value=e["value"] * 2) for e in entries]
        ledger.append_entries(lpath, worse)
        _, regressions = ledger.check_regressions(ledger.read_ledger(lpath))
        assert {r["key"] for r in regressions} == {
            "soak:recovery_seconds", "reshard:seconds",
        }
        improved = [dict(e, ts=e["ts"] + 2, source="best.json",
                         value=e["value"] * 0.5) for e in entries]
        ledger.append_entries(lpath, improved)
        _, regressions = ledger.check_regressions(ledger.read_ledger(lpath))
        assert not regressions
        # failed soaks are not perf points
        bad = dict(doc, bitwise_identical=False)
        p2 = tmp_path / "bad_soak.json"
        p2.write_text(json.dumps(bad))
        assert ledger.entries_from_artifact(str(p2)) == []

    def test_unknown_shapes_are_skipped(self, tmp_path):
        p = tmp_path / "x.json"
        p.write_text(json.dumps({"something": "else"}))
        assert ledger.entries_from_artifact(str(p)) == []
        assert ledger.entries_from_artifact(str(tmp_path / "absent.json")) == []

    def test_truncated_trailing_line_skipped(self, tmp_path):
        led = tmp_path / "l.jsonl"
        led.write_text(
            json.dumps({"key": "k", "value": 1.0, "source": "a", "ts": 1}) +
            '\n{"key": "k", "va'  # the crash-mid-append tail
        )
        assert len(ledger.read_ledger(str(led))) == 1


# --- the regression gate -----------------------------------------------------


class TestGate:
    def test_synthetic_regression_flagged(self, tmp_path, bench_artifacts):
        """THE acceptance pin: a rising BENCH trajectory passes the gate;
        one synthetic 40%-down headline entry flips it."""
        led = tmp_path / "ledger.jsonl"
        _ingest_all(led, bench_artifacts)
        rows, regressions = ledger.check_regressions(ledger.read_ledger(str(led)))
        assert regressions == []  # the r01->r05 trajectory only went up
        headline = next(
            r for r in rows if r["key"] == "jacobi3d_mcells_per_s_per_chip"
        )
        assert headline["ratio"] is not None and headline["n"] >= 5
        ledger.append_entries(
            str(led),
            [{"ts": 9e9, "key": "jacobi3d_mcells_per_s_per_chip",
              "value": headline["trailing_median"] * 0.6, "unit": "Mcells/s",
              "source": "BENCH_synthetic.json"}],
        )
        rows2, regressions2 = ledger.check_regressions(
            ledger.read_ledger(str(led))
        )
        assert [r["key"] for r in regressions2] == [
            "jacobi3d_mcells_per_s_per_chip"
        ]
        # the synthetic entry's trailing window now includes the r05 rerun
        # headline too — whatever the exact median, a 40% drop is far
        # outside the 10% gate
        assert regressions2[0]["ratio"] < 0.7

    def test_threshold_and_window(self):
        def e(v, i):
            return {"ts": i, "key": "k", "value": v, "unit": "", "source": str(i)}

        series = [e(100.0, i) for i in range(5)] + [e(95.0, 5)]
        _, reg = ledger.check_regressions(series, threshold=0.10)
        assert reg == []  # 5% down: inside the 10% gate
        _, reg = ledger.check_regressions(series, threshold=0.02)
        assert len(reg) == 1  # 5% down: outside a 2% gate
        # window: the median only sees the trailing entries, so a short
        # window judges against the recent plateau while a long one still
        # remembers the slow early rounds
        drift = [e(50.0, 0), e(50.0, 1), e(50.0, 2), e(100.0, 3),
                 e(100.0, 4), e(80.0, 5)]
        _, reg = ledger.check_regressions(drift, threshold=0.10, window=2)
        assert len(reg) == 1  # vs median(100,100)=100 -> 0.8
        _, reg = ledger.check_regressions(drift, threshold=0.10, window=5)
        assert reg == []  # vs median(50,50,50,100,100)=50 -> 1.6

    def test_single_entry_series_never_regresses(self):
        rows, reg = ledger.check_regressions(
            [{"ts": 1, "key": "k", "value": 5.0, "unit": "", "source": "a"}]
        )
        assert reg == [] and rows[0]["trailing_median"] is None


# --- bench.py --ledger -------------------------------------------------------


def test_entry_from_bench_result(tmp_path):
    result = {"metric": "jacobi3d_mcells_per_s_per_chip", "value": 99.5,
              "unit": "Mcells/s"}
    entry = ledger.entry_from_bench_result(result, source="live-run")
    assert entry["key"] == "jacobi3d_mcells_per_s_per_chip"
    assert entry["value"] == 99.5 and entry["source"] == "live-run"
    led = tmp_path / "l.jsonl"
    assert ledger.append_entries(str(led), [entry]) == 1


def test_repeat_source_grows_the_series(tmp_path):
    """Dedupe is per MEASUREMENT (key, source, ts), not per source: a
    second live bench run (new clock) and a regenerated artifact (new
    mtime) must append, or every repeat-source series would be capped at
    one entry and the gate would never see a new value."""
    led = str(tmp_path / "l.jsonl")
    result = {"metric": "m", "value": 100.0, "unit": "u"}
    e1 = ledger.entry_from_bench_result(result)
    assert ledger.append_entries(led, [e1]) == 1
    assert ledger.append_entries(led, [e1]) == 0  # same measurement: no-op
    e2 = ledger.entry_from_bench_result(dict(result, value=90.0))
    assert e2["ts"] > e1["ts"]
    assert ledger.append_entries(led, [e2]) == 1  # new run: appends
    # and a regenerated artifact with a fresh mtime re-ingests as new
    p = tmp_path / "weak_scaling_summary.json"
    doc = {"bench": "weak_scaling_sweep",
           "meshes": [{"mesh": [2, 1, 1], "chips": 2,
                       "mcells_per_s_per_chip": {"off": 10.0}}]}
    p.write_text(json.dumps(doc))
    assert ledger.append_entries(led, ledger.entries_from_artifact(str(p))) == 1
    assert ledger.append_entries(led, ledger.entries_from_artifact(str(p))) == 0
    doc["meshes"][0]["mcells_per_s_per_chip"]["off"] = 11.0
    p.write_text(json.dumps(doc))
    os.utime(p, (p.stat().st_atime, p.stat().st_mtime + 60))
    assert ledger.append_entries(led, ledger.entries_from_artifact(str(p))) == 1
    series = [e for e in ledger.read_ledger(led) if e["key"] == "weak:2x1x1:off"]
    assert [e["value"] for e in series] == [10.0, 11.0]


# --- the CLI (in-process) ----------------------------------------------------


class TestCLI:
    def test_ingest_then_check(self, tmp_path, capsys, bench_artifacts):
        mod = _load_script("perf_ledger")
        led = str(tmp_path / "ledger.jsonl")
        rc = mod.main(
            ["--ledger", led, "ingest",
             os.path.join(os.path.dirname(bench_artifacts[0]), "BENCH_r*.json")]
        )
        assert rc == 0
        assert mod.main(["--ledger", led, "check"]) == 0
        out = capsys.readouterr().out
        assert "jacobi3d_mcells_per_s_per_chip" in out
        # a synthetic regression flips the exit code
        ledger.append_entries(
            led,
            [{"ts": 9e9, "key": "jacobi3d_mcells_per_s_per_chip",
              "value": 1.0, "unit": "Mcells/s", "source": "synthetic"}],
        )
        assert mod.main(["--ledger", led, "check"]) == 1
        assert "REGRESSION" in capsys.readouterr().err

    def test_check_empty_ledger_is_usage_error(self, tmp_path):
        mod = _load_script("perf_ledger")
        assert mod.main(["--ledger", str(tmp_path / "nope.jsonl"), "check"]) == 2


# --- tier-2: the real CLI as the regression check would run it ---------------


@pytest.mark.slow
def test_cli_subprocess_gate(tmp_path, bench_artifacts):
    """scripts/perf_ledger.py as a subprocess — the tier-2 check shape:
    ingest a series of artifacts, run the gate, exit 0."""
    led = str(tmp_path / "ledger.jsonl")
    script = os.path.join(REPO, "scripts", "perf_ledger.py")
    ing = subprocess.run(
        [sys.executable, script, "--ledger", led, "ingest"] + bench_artifacts,
        capture_output=True, text=True, timeout=120,
    )
    assert ing.returncode == 0, ing.stderr
    chk = subprocess.run(
        [sys.executable, script, "--ledger", led, "check", "--json"],
        capture_output=True, text=True, timeout=120,
    )
    assert chk.returncode == 0, (chk.stdout, chk.stderr)
    doc = json.loads(chk.stdout)
    assert doc["regressions"] == []
