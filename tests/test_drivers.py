"""Tier-2: every bin/ driver runs end-to-end on the fake 8-device mesh and
emits its reference-parity CSV (SURVEY.md §2.4 inventory)."""

import math

import pytest


def _capture(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    assert out, "driver printed nothing"
    return out


def test_jacobi3d(capsys):
    from stencil_tpu.bin.jacobi3d import main

    assert main(["--iters", "3", "--no-weak-scale", "16", "16", "16"]) == 0
    row = _capture(capsys)[-1].split(",")
    # jacobi3d,<methods>,ranks,devCount,x,y,z,min,trimean (jacobi3d.cu:378-379)
    assert row[0] == "jacobi3d"
    assert row[4:7] == ["16", "16", "16"]
    assert float(row[7]) > 0 and float(row[8]) > 0


def test_weak(capsys):
    from stencil_tpu.bin.weak import main

    assert main(["12", "12", "12", "2"]) == 0
    row = _capture(capsys)[-1].split(",")
    assert row[0] == "weak"
    assert len(row) == 23  # weak.cu:184-188 column layout
    x, y, z, s = (int(v) for v in row[2:6])
    assert x * y * z == s
    assert int(row[6]) > 0  # exchange bytes ride the collective column
    assert float(row[21]) > 0  # accumulated exchange seconds


def test_strong(capsys):
    from stencil_tpu.bin.strong import main

    assert main(["16", "16", "16", "2"]) == 0
    row = _capture(capsys)[-1].split(",")
    assert row[0] == "strong"
    assert len(row) == 23
    assert row[2:5] == ["16", "16", "16"]  # NOT weak-scaled


def _overlap_doc(capsys, main, argv):
    import json

    assert main(argv) == 0
    return json.loads(_capture(capsys)[-1])


def test_weak_overlap_ab(capsys, tmp_path):
    """``weak --overlap``: the per-mesh overlap A/B JSON artifact (the
    weak-scaling rows scripts/run_weak_scaling.py collects) — dryrun-capable
    on the fake CPU mesh, schema pinned here."""
    import json

    from stencil_tpu.bin.weak import main

    path = tmp_path / "weak_221.json"
    doc = _overlap_doc(
        capsys,
        main,
        ["12", "12", "12", "1", "--overlap", "--mesh", "2,2,1",
         "--ab-reps", "1", "--json", str(path)],
    )
    assert doc["bench"] == "weak_overlap" and doc["dryrun"] is True
    assert doc["mesh"] == [2, 2, 1] and doc["chips"] == 4
    # per-axis weak scaling: 12^3/chip stays exact on the non-cubic mesh
    assert doc["global"] == [24, 24, 12]
    assert doc["cells_per_chip"] == 12 * 12 * 12
    assert doc["measurement_protocol"]["drop_rep0"] is True
    assert doc["measurement_protocol"]["alternating_within_process"] is True
    for ov in ("off", "split"):
        assert doc["overlap"][ov]["mcells_per_s"] > 0
        assert doc["plans"][ov]["overlap"] == ov
    assert doc["split_speedup"] > 0
    assert doc["exchange"]["ms_per_exchange"] > 0
    assert json.loads(path.read_text()) == doc


def test_strong_overlap_ab(capsys):
    from stencil_tpu.bin.strong import main

    doc = _overlap_doc(
        capsys,
        main,
        ["16", "16", "16", "1", "--overlap", "--mesh", "2,1,1", "--ab-reps", "1"],
    )
    assert doc["bench"] == "strong_overlap"
    assert doc["mesh"] == [2, 1, 1] and doc["global"] == [16, 16, 16]


@pytest.mark.slow  # tier-2: spawns one fresh interpreter per mesh shape
def test_run_weak_scaling_sweep(tmp_path):
    """scripts/run_weak_scaling.py --dryrun: one artifact per mesh plus the
    sweep summary with per-chip throughput and weak efficiency."""
    import json
    import subprocess
    import sys
    from pathlib import Path

    script = Path(__file__).resolve().parents[1] / "scripts" / "run_weak_scaling.py"
    out = tmp_path / "sweep"
    proc = subprocess.run(
        [
            sys.executable, str(script), "--dryrun", "--iters", "1",
            "--ab-reps", "1", "--out-dir", str(out),
            "--meshes", "2,1,1", "2,2,1",
        ],
        capture_output=True,
        text=True,
        timeout=540,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    summary = json.loads((out / "weak_scaling_summary.json").read_text())
    assert summary["bench"] == "weak_scaling_sweep" and summary["dryrun"]
    assert [m["mesh"] for m in summary["meshes"]] == [[2, 1, 1], [2, 2, 1]]
    for m in summary["meshes"]:
        assert m["mcells_per_s_per_chip"]["off"] > 0
        assert m["mcells_per_s_per_chip"]["split"] > 0
        assert m["exchange_ms"] > 0
        assert m["weak_efficiency"]["off"] is not None
    per_mesh = json.loads((out / "weak_2x1x1.json").read_text())
    assert per_mesh["bench"] == "weak_overlap" and per_mesh["chips"] == 2


def test_weak_exchange(capsys):
    from stencil_tpu.bin.weak_exchange import main

    assert main(["12", "12", "12", "2"]) == 0
    row = _capture(capsys)[-1].split(",")
    assert row[0] == "weak"
    assert float(row[-1]) > 0  # single wall-clock elapsed


def test_astaroth_sim(capsys):
    from stencil_tpu.bin.astaroth_sim import main

    assert main(["--x", "16", "--y", "16", "--z", "16", "--iters", "2"]) == 0
    row = _capture(capsys)[-1].split(",")
    assert row[0] == "astaroth"
    assert float(row[7]) > 0


def test_bench_exchange(capsys):
    import json

    from stencil_tpu.bin.bench_exchange import main

    assert main(
        ["--iters", "2", "--x", "12", "--y", "12", "--z", "12", "--ab-reps", "1"]
    ) == 0
    out = _capture(capsys)
    assert out[0] == (
        "name,count,trimean (S),trimean (B/s),stddev,min,avg,max,trimean (B/s swept)"
    )
    # header + 5 radius configs (bench_exchange.cu:121-195) + the JSON line
    assert len(out) == 7
    for line in out[1:6]:
        cols = line.split(",")
        assert float(cols[2]) > 0 and float(cols[3]) > 0
        # swept B/s >= modeled B/s: sweeps move full-extent slabs
        assert float(cols[8]) >= float(cols[3])
    # the machine-readable route A/B: direct-vs-packed steady-state medians
    # (alternating protocol) with the per-axis (x/y/z) ms breakdown
    doc = json.loads(out[6])
    ab = doc["route_ab"]
    assert ab["measurement_protocol"]["drop_rep0"] is True
    assert set(ab["routes"]) >= {"direct"}
    for entry in ab["routes"].values():
        assert entry["ms_per_exchange"] > 0
        assert set(entry["per_axis_ms"]) == {"x", "y", "z"}
    if ab["packed_eligible"]:
        packed = {
            "zpack_xla", "zpack_pallas", "yzpack_xla", "yzpack_pallas",
        }
        assert set(ab["routes"]) == {"direct"} | packed
        assert set(ab["speedup_vs_direct"]) == packed
        # shared-leg provenance: only the legs a route does NOT change may
        # be shared from direct — x everywhere, y only on the z-only routes
        shared = ab["measurement_protocol"]["shared_legs_with_direct"]
        assert shared == {
            "zpack_xla": ["x", "y"],
            "zpack_pallas": ["x", "y"],
            "yzpack_xla": ["x"],
            "yzpack_pallas": ["x"],
        }


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_bench_pack(capsys, backend):
    from stencil_tpu.bin.bench_pack import main

    argv = ["--iters", "1", "--size", "12", "--backend", backend]
    if backend == "pallas":
        argv.append("--interpret")
    assert main(argv) == 0
    out = _capture(capsys)
    assert len(out) == 3  # x, y, z faces (bench_pack.cu:91-107)
    for line in out:
        cols = line.split()
        assert int(cols[2]) == 12 * 12 * 3 * 4  # face slab bytes, r=3 f32
        assert float(cols[3]) > 0 and float(cols[4]) > 0


def test_bench_qap(capsys):
    from stencil_tpu.bin.bench_qap import main

    assert main(["--iters", "1", "--max-size", "6", "--exact-below", "5"]) == 0
    out = _capture(capsys)
    assert out[0] == "blkdiag"
    assert out[1] == "size CRAFT(s) cost exact(s) cost"
    # exact solve rows: heuristic cost must be >= exact cost (optimality)
    for line in out[2:4]:
        cols = line.split()
        if cols[3] != "-":
            assert float(cols[2]) >= float(cols[4]) - 1e-9


def test_pingpong(capsys):
    from stencil_tpu.bin.pingpong import main

    assert main(["--min", "2", "--max", "4", "--iters", "2"]) == 0
    out = _capture(capsys)
    for line in out:
        name, *times = line.split()
        assert "-" in name
        assert len(times) == 3
        assert all(float(t) > 0 for t in times)


def test_bench_alltoallv(capsys):
    from stencil_tpu.bin.bench_alltoallv import main

    assert main(["--iters", "1", "--scale", "0.001"]) == 0
    out = _capture(capsys)
    assert "bw" in out and "time" in out and "stencil" in out
    assert "All-to-all 8MiB" in out
    assert "Local 1GiB Remote 100M" in out
    # the contended (all-pairs-in-flight) totals accompany every matrix
    for name in ("stencil", "All-to-all 8MiB", "Local 1GiB Remote 100M"):
        i = out.index(f"{name} concurrent")
        assert float(out[i + 1]) > 0


def test_measure_buf_exchange(capsys):
    from stencil_tpu.bin.measure_buf_exchange import main

    assert main(["--iters", "2", "--sub-iters", "1", "--init-mib", "0.05"]) == 0
    out = _capture(capsys)
    assert out[0] == "x"
    assert "final x (MiB)" in out
    # each controller iteration reports the contended traversal total
    assert any(l.startswith("y_concurrent ") and float(l.split()[1]) > 0 for l in out)
    final = out[out.index("final x (MiB)") + 1 :]
    vals = [float(v) for line in final for v in line.split()]
    assert any(v > 0 for v in vals)
    assert all(not math.isnan(v) for v in vals)


def test_elastic(capsys):
    """``stencil-elastic``: Devito's ``-P elastic -so 8`` command line, the
    physical extents given and sponge + frame added, on the fake 8-device
    mesh (13 quantities, a staged step)."""
    from stencil_tpu.bin.elastic import main

    assert main(["16", "16", "16", "--nbl", "4", "--iters", "2", "--steps", "2"]) == 0
    row = _capture(capsys)[-1].split(",")
    # elastic,ranks,devCount,x,y,z,nbl,min,trimean,gpts_per_s
    assert row[0] == "elastic" and row[3:7] == ["16", "16", "16", "4"]
    assert float(row[7]) > 0 and float(row[8]) > 0 and float(row[9]) > 0
