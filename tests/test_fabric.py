"""Tier-1: the fabric observatory (stencil_tpu/telemetry/fabric.py + the
``python -m stencil_tpu.fabric`` CLI) on the fake 8-chip CPU mesh.

The probe itself is backend-agnostic (a flat-mesh single-pair ppermute per
edge), so the full sweep runs in-process here — the numbers are host
memcpys, not fabric truth, but the ARTIFACT contract is fully pinned:
complete symmetric link matrix, stamped cache with the tune-cache
corrupt/stale=miss pattern, warm loads doing zero device work, and the
derived link model / heartbeat summary shapes.  The real-hardware twin is
tier-2 ``slow``.
"""

import json
import os

import numpy as np
import pytest

import jax

from stencil_tpu import telemetry
from stencil_tpu.parallel.mesh import mesh_from_grid
from stencil_tpu.telemetry import fabric, names
from stencil_tpu.telemetry.ledger import entries_from_artifact


#: every probe's payload.  A link is ``nbytes / seconds`` rounded to three
#: decimals of a GB/s on the host's own clock: at 4 KiB one 8 ms stall of a
#: loaded worker reads 0.000 (PR 45's and PR 49's rc 1; ROADMAP D11), at 1 MiB
#: a stall of a whole second still reads 0.001.  No tier-1 probe runs smaller.
PAYLOAD = 1 << 20


def _mesh222():
    return mesh_from_grid(np.array(jax.devices()[:8]).reshape(2, 2, 2))


@pytest.fixture(autouse=True)
def _fresh(tmp_path, monkeypatch):
    monkeypatch.setenv("STENCIL_FABRIC_CACHE", str(tmp_path / "fabric"))
    telemetry.reset()
    yield
    telemetry.reset()


# --- hop enumeration (jax-free) ----------------------------------------------


class TestNeighborLinks:
    def test_2x2x2_full_torus(self):
        links = fabric.neighbor_links({"x": 2, "y": 2, "z": 2})
        # 8 ordered sends per (axis, side), 3 axes x 2 sides
        assert len(links) == 48
        # size-2 axes: low and high hop SETS coincide as ordered pairs
        assert len({(l["src"], l["dst"]) for l in links}) == 24
        # every entry names a registered direction
        for l in links:
            assert (l["axis"], l["side"]) in names.EXCHANGE_DIRECTION_SPANS

    def test_size1_axes_contribute_nothing(self):
        assert fabric.neighbor_links({"x": 1, "y": 1, "z": 1}) == []
        links = fabric.neighbor_links({"x": 1, "y": 1, "z": 4})
        assert {l["axis"] for l in links} == {"z"}
        # a ring of 4: 4 sends per side, distinct ordered pairs per side
        low = [(l["src"], l["dst"]) for l in links if l["side"] == "low"]
        assert sorted(low) == [(0, 1), (1, 2), (2, 3), (3, 0)]
        high = [(l["src"], l["dst"]) for l in links if l["side"] == "high"]
        assert sorted(high) == [(0, 3), (1, 0), (2, 1), (3, 2)]

    def test_flat_indices_are_c_order(self):
        links = fabric.neighbor_links({"x": 2, "y": 1, "z": 4})
        # x-neighbor of flat 0 (coords 0,0,0) is (1,0,0) = flat 4
        assert {(0, 4), (4, 0)} <= {(l["src"], l["dst"]) for l in links}


# --- the probe on the fake 8-chip mesh (acceptance) ---------------------------


class TestProbe:
    def test_probe_writes_complete_symmetric_matrix_and_warm_load(self):
        """THE acceptance pin: on the fake 8-chip mesh the probe writes a
        complete symmetric link-matrix artifact, and a second ensure()
        loads it warm — ZERO device work (the probe-run counter does not
        move)."""
        mesh = _mesh222()
        doc = fabric.ensure(mesh, nbytes=PAYLOAD, reps=1)
        assert doc["bench"] == "fabric_probe"
        assert doc["topology"] == [2, 2, 2] and doc["n_devices"] == 8
        assert doc["protocol"]["edges"] == 24 and len(doc["links"]) == 48
        # complete: every neighbor hop measured, positive
        assert all(l["gbps"] > 0 for l in doc["links"])
        # symmetric: the matrix's positivity pattern is its own transpose
        # (a full torus measures both directions of every physical link)
        m = doc["matrix"]
        assert len(m) == 8 and all(len(row) == 8 for row in m)
        for i in range(8):
            assert m[i][i] == 0.0
            for j in range(8):
                assert (m[i][j] > 0) == (m[j][i] > 0)
        assert sum(1 for row in m for v in row if v > 0) == 24
        json.loads(json.dumps(doc))  # stamped artifact is strict-JSON-safe

        snap = telemetry.snapshot()
        assert snap["counters"][names.FABRIC_PROBE_RUNS] == 24
        assert snap["counters"][names.FABRIC_CACHE_MISS] == 1
        assert snap["counters"][names.FABRIC_CACHE_HIT] == 0

        doc2 = fabric.ensure(mesh, nbytes=PAYLOAD, reps=1)
        assert doc2["links"] == doc["links"]
        snap = telemetry.snapshot()
        assert snap["counters"][names.FABRIC_PROBE_RUNS] == 24  # no device work
        assert snap["counters"][names.FABRIC_CACHE_HIT] == 1
        # both paths emitted the probe event, sources tagged honestly
        sources = [
            e["source"] for e in telemetry.recent_events()
            if e["event"] == names.EVENT_FABRIC_PROBE
        ]
        assert sources == ["probe", "cache"]

    def test_payload_is_part_of_the_key(self):
        mesh = _mesh222()
        fabric.ensure(mesh, nbytes=PAYLOAD, reps=1)
        fabric.ensure(mesh, nbytes=2 * PAYLOAD, reps=1)  # different fact: re-probe
        snap = telemetry.snapshot()
        assert snap["counters"][names.FABRIC_CACHE_MISS] == 2

    def test_force_reprobes(self):
        mesh = _mesh222()
        fabric.ensure(mesh, nbytes=PAYLOAD, reps=1)
        fabric.ensure(mesh, nbytes=PAYLOAD, reps=1, force=True)
        snap = telemetry.snapshot()
        assert snap["counters"][names.FABRIC_PROBE_RUNS] == 48
        assert snap["counters"][names.FABRIC_CACHE_HIT] == 0

    def test_corrupt_and_stale_cache_are_misses(self):
        """The tune-cache pattern verbatim: corrupt file -> warn + miss;
        schema/toolchain mismatch -> info + miss; never a crash."""
        mesh = _mesh222()
        doc = fabric.ensure(mesh, nbytes=PAYLOAD, reps=1)
        key = fabric.probe_key((2, 2, 2), doc["chip"], PAYLOAD, None)
        path = fabric.path_for(key)
        assert os.path.exists(path)

        with open(path, "w") as f:
            f.write('{"schema": 1, "trunc')  # corrupt
        assert fabric.load(key) is None

        stale = dict(doc, schema=fabric.SCHEMA + 1)
        with open(path, "w") as f:
            json.dump(stale, f)
        assert fabric.load(key) is None

        stale = dict(doc, jax="0.0.0-other")
        with open(path, "w") as f:
            json.dump(stale, f)
        assert fabric.load(key) is None

        with open(path, "w") as f:
            json.dump(doc, f)  # restored: hit again
        assert fabric.load(key) is not None

    def test_dir_override_beats_env(self, tmp_path):
        fabric.set_dir_override(str(tmp_path / "override"))
        try:
            assert fabric.cache_dir() == str(tmp_path / "override")
        finally:
            fabric.set_dir_override(None)


# --- derived views ------------------------------------------------------------


class TestLinkModel:
    def test_link_model_and_summary_shapes(self):
        mesh = _mesh222()
        doc = fabric.ensure(mesh, nbytes=PAYLOAD, reps=1)
        model = fabric.link_model(doc)
        assert set(model["axes"]) == {"x", "y", "z"}
        for sides in model["axes"].values():
            assert set(sides) == {"low", "high"}
            for s in sides.values():
                assert s["links"] == 8
                assert 0 < s["gbps_min"] <= s["gbps_med"]
        slow = model["slowest"]
        assert slow["gbps"] == min(l["gbps"] for l in doc["links"])
        assert names.EXCHANGE_DIRECTION_SPANS[(slow["axis"], slow["side"])]

        summ = fabric.summary(doc)
        assert summ["topology"] == [2, 2, 2]
        assert summ["slowest"] == slow
        assert summ["axes"]["z"]["low"] == model["axes"]["z"]["low"]["gbps_med"]
        json.loads(json.dumps(summ))

    def test_link_model_accepts_mesh_via_cache(self):
        """``link_model(mesh)`` — the placement/tuner entry — goes through
        ensure(): warm after one probe, zero further device work."""
        mesh = _mesh222()
        fabric.ensure(mesh, nbytes=PAYLOAD, reps=1)
        model = fabric.link_model(mesh, nbytes=PAYLOAD, reps=1)
        assert set(model["axes"]) == {"x", "y", "z"}
        snap = telemetry.snapshot()
        assert snap["counters"][names.FABRIC_PROBE_RUNS] == 24

    def test_ledger_ingests_probe_artifact(self, tmp_path):
        mesh = _mesh222()
        doc = fabric.ensure(mesh, nbytes=PAYLOAD, reps=1)
        path = tmp_path / "fabric.json"
        path.write_text(json.dumps(doc))
        entries = entries_from_artifact(str(path))
        keys = {e["key"] for e in entries}
        assert "fabric:link_gbps" in keys  # the slowest-link headline
        assert "fabric:link_gbps:z.low" in keys
        assert all(e["value"] > 0 for e in entries)


# --- the CLI ------------------------------------------------------------------


class TestCli:
    def test_cli_probe_then_warm(self, tmp_path, capsys):
        from stencil_tpu.fabric import main

        cache = str(tmp_path / "cache")
        out = str(tmp_path / "fabric.json")
        rc = main([
            "--grid", "2", "2", "2", "--nbytes", str(PAYLOAD), "--reps", "1",
            "--cache", cache, "--out", out,
        ])
        assert rc == 0
        text = capsys.readouterr().out
        assert "topology 2x2x2" in text and "slowest link" in text
        doc = json.load(open(out))
        assert doc["bench"] == "fabric_probe"
        # warm second run prints from the cache (and --json round-trips)
        rc = main([
            "--grid", "2", "2", "2", "--nbytes", str(PAYLOAD), "--reps", "1",
            "--cache", cache, "--json",
        ])
        assert rc == 0
        doc2 = json.loads(capsys.readouterr().out)
        assert doc2["links"] == doc["links"]

    def test_cli_rejects_bad_grid(self, capsys):
        from stencil_tpu.fabric import main

        with pytest.raises(SystemExit):
            main(["--grid", "3", "1", "1"])


# --- heartbeat surface --------------------------------------------------------


class TestStatusSurface:
    def test_fabric_lines_render_matrix_and_callout(self):
        mesh = _mesh222()
        doc = fabric.ensure(mesh, nbytes=PAYLOAD, reps=1)
        from stencil_tpu.status import _fabric_lines

        lines = _fabric_lines(fabric.summary(doc))
        text = "\n".join(lines)
        assert "fabric (topology 2x2x2" in text
        assert "slowest link:" in text
        assert "link matrix (GB/s):" in text
        assert len([ln for ln in lines if ln.strip()[0].isdigit() or "." in ln]) > 8
        assert _fabric_lines(None) == []  # runs without a probe: no section

    def test_flight_sticky_state_carries_fabric(self, tmp_path):
        """The heartbeat wiring: sticky FlightRecorder state lands in every
        status.json rewrite, and ``python -m stencil_tpu.status`` renders
        the fabric section from it."""
        from stencil_tpu.status import render
        from stencil_tpu.telemetry.flight import FlightRecorder, read_status

        mesh = _mesh222()
        doc = fabric.ensure(mesh, nbytes=PAYLOAD, reps=1)
        fr = FlightRecorder(str(tmp_path), label="weak-scaling")
        fr.state["fabric"] = fabric.summary(doc)
        fr.heartbeat(1, 3, stage="mesh 2x2x2")
        status = read_status(str(tmp_path))
        assert status["fabric"]["topology"] == [2, 2, 2]
        out = render(status, None)
        assert "slowest link:" in out and "link matrix" in out


# --- tier-2: the real-hardware twin ------------------------------------------


@pytest.mark.slow
def test_live_probe_on_real_mesh():
    """The same acceptance on whatever mesh this host realizes: complete
    positive matrix, symmetric positivity, warm second load.  On a real
    TPU the gbps numbers are fabric truth; a single-device host degrades
    to the no-links artifact."""
    from stencil_tpu.core.radius import Radius
    from stencil_tpu.parallel.mesh import make_mesh

    mesh, _ = make_mesh((128, 128, 128), Radius.constant(1))
    doc = fabric.ensure(mesh, nbytes=PAYLOAD, reps=2)
    n = doc["n_devices"]
    m = doc["matrix"]
    assert len(m) == n
    for i in range(n):
        for j in range(n):
            assert (m[i][j] > 0) == (m[j][i] > 0)
    if doc["protocol"]["edges"]:
        assert all(l["gbps"] > 0 for l in doc["links"])
        doc2 = fabric.ensure(mesh, nbytes=PAYLOAD, reps=2)
        assert doc2["links"] == doc["links"]
