"""Tier-1: the unified telemetry layer (stencil_tpu/telemetry/) — metrics
registry snapshots, span nesting + Chrome-trace JSON shape, the JSONL event
schema, resilience integration (fault-injected retries/descents increment
counters and log events), driver ``--metrics-out``, and the canonical-names
lint — all on CPU."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stencil_tpu import telemetry
from stencil_tpu.core.radius import Radius
from stencil_tpu.domain import DistributedDomain
from stencil_tpu.models.jacobi import Jacobi3D
from stencil_tpu.resilience import inject
from stencil_tpu.telemetry import names
from stencil_tpu.telemetry.metrics import MetricsRegistry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    """Every test starts disabled with zeroed metrics and no fault plan."""
    telemetry.disable()
    telemetry.reset()
    yield
    telemetry.disable()
    telemetry.reset()
    inject.set_plan(None)


def _events(tmp_path):
    path = tmp_path / "events_0.jsonl"
    if not path.exists():
        return []
    return [json.loads(line) for line in path.read_text().splitlines()]


def _mk_domain(names_, devices, mult=1):
    dd = DistributedDomain(24, 24, 24)
    dd.set_radius(1)
    dd.set_devices(devices)
    hs = [dd.add_data(n) for n in names_]
    if mult > 1:
        dd.set_halo_multiplier(mult)
    dd.realize()
    for h in hs:
        dd.init_by_coords(h, lambda cx, cy, cz: jnp.sin(0.3 * cx) + 0.1 * cz)
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


# --- metrics registry --------------------------------------------------------


class TestMetrics:
    def test_counters_gauges_and_seeding(self):
        r = MetricsRegistry()
        r.counter("resilience.retry.attempts").inc()
        r.counter("resilience.retry.attempts").inc(2)
        r.gauge("domain.exchange.bytes_per_exchange").set(1536)
        snap = r.snapshot(
            seed_counters=names.ALL_COUNTERS,
            seed_histograms=names.ALL_HISTOGRAMS,
        )
        assert snap["counters"]["resilience.retry.attempts"] == 3
        # seeded: every canonical counter appears even when untouched —
        # including the fabric observatory's new per-hop byte counters
        assert snap["counters"]["resilience.sentinel.trips"] == 0
        assert snap["counters"][names.EXCHANGE_HOP_Z_LOW_BYTES] == 0
        assert snap["counters"][names.FABRIC_PROBE_RUNS] == 0
        assert set(names.ALL_COUNTERS) <= set(snap["counters"])
        # seeded histograms: every canonical name appears as an EMPTY
        # distribution (count 0, None stats) so cross-round diffs of e.g.
        # fabric.link.gbps never KeyError on a fresh registry
        assert set(names.ALL_HISTOGRAMS) <= set(snap["histograms"])
        empty = snap["histograms"][names.FABRIC_LINK_GBPS]
        assert empty["count"] == 0 and empty["med"] is None
        json.loads(json.dumps(snap))  # seeded shape stays strict-JSON-safe
        assert snap["gauges"]["domain.exchange.bytes_per_exchange"] == 1536.0
        # the facade snapshot seeds both kinds the same way
        assert set(names.ALL_HISTOGRAMS) <= set(
            telemetry.snapshot()["histograms"]
        )

    def test_histogram_matches_statistics_and_json_safety(self):
        from stencil_tpu.utils.statistics import Statistics

        r = MetricsRegistry()
        h = r.histogram("domain.step.seconds")
        ref = Statistics()
        for v in (4.0, 1.0, 3.0, 2.0, 5.0):
            h.observe(v)
            ref.insert(v)
        s = h.snapshot()
        assert s["count"] == 5
        assert s["med"] == ref.med() and s["trimean"] == ref.trimean()
        assert s["stddev"] == pytest.approx(ref.stddev())
        # single-sample stddev is NaN -> None (strict-JSON-safe), and the
        # whole snapshot must round-trip through strict json
        h2 = r.histogram("domain.exchange.seconds")
        h2.observe(1.0)
        assert h2.snapshot()["stddev"] is None
        json.loads(json.dumps(r.snapshot()))

    def test_name_cannot_change_kind(self):
        r = MetricsRegistry()
        r.counter("domain.exchange.count")
        with pytest.raises(ValueError, match="different metric kind"):
            r.histogram("domain.exchange.count")

    def test_histogram_quantiles_in_snapshot(self):
        """p50/p95/p99 ride the snapshot alongside the trimean — the tail
        view cross-round diffs previously lost.  p50 must agree with med
        for both parities (linear-interpolated quantiles)."""
        r = MetricsRegistry()
        h = r.histogram("domain.step.seconds")
        for v in range(1, 101):  # 1..100
            h.observe(float(v))
        s = h.snapshot()
        assert s["p50"] == s["med"] == pytest.approx(50.5)
        assert s["p95"] == pytest.approx(95.05)
        assert s["p99"] == pytest.approx(99.01)
        assert s["p50"] <= s["p95"] <= s["p99"] <= s["max"]
        h2 = r.histogram("domain.exchange.seconds")
        for v in (3.0, 1.0, 2.0):  # odd count: p50 == the middle element
            h2.observe(v)
        s2 = h2.snapshot()
        assert s2["p50"] == s2["med"] == 2.0
        # empty histogram: NaN -> None, strict-JSON-safe
        s3 = r.histogram("domain.swap.seconds").snapshot()
        assert s3["p50"] is None and s3["p99"] is None
        json.loads(json.dumps(r.snapshot()))

    def test_quantile_validates_range(self):
        from stencil_tpu.utils.statistics import Statistics

        st = Statistics()
        st.insert(1.0)
        with pytest.raises(ValueError, match="quantile"):
            st.quantile(1.5)

    def test_counters_live_even_when_disabled(self):
        assert not telemetry.enabled()
        telemetry.inc(names.RETRY_ATTEMPTS)
        assert telemetry.snapshot()["counters"][names.RETRY_ATTEMPTS] == 1
        # histograms are NOT recorded while disabled (hot-path zero cost) —
        # the name still appears (canonical seeding), but stays empty
        telemetry.observe(names.STEP_SECONDS, 1.0)
        assert telemetry.snapshot()["histograms"][names.STEP_SECONDS]["count"] == 0


# --- spans + chrome trace ----------------------------------------------------


class TestSpans:
    def test_nesting_and_chrome_trace_shape(self, tmp_path):
        telemetry.enable(dir=str(tmp_path))
        with telemetry.span(names.SPAN_STEP, histogram=names.STEP_SECONDS):
            with telemetry.span(names.SPAN_EXCHANGE):
                pass
        path = telemetry.dump_chrome_trace()
        doc = json.loads(open(path).read())
        evs = {e["name"]: e for e in doc["traceEvents"]}
        outer, inner = evs[names.SPAN_STEP], evs[names.SPAN_EXCHANGE]
        for e in (outer, inner):
            assert e["ph"] == "X" and e["pid"] == 0
            assert e["ts"] >= 0 and e["dur"] >= 0
        # the inner span nests inside the outer on the timeline and knows
        # its parent
        assert inner["ts"] >= outer["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
        assert inner["args"]["parent"] == names.SPAN_STEP
        assert "parent" not in outer["args"]
        # the histogram= wiring observed the outer duration
        assert (
            telemetry.snapshot()["histograms"][names.STEP_SECONDS]["count"] == 1
        )

    def test_disabled_span_records_nothing(self, tmp_path):
        with telemetry.span(names.SPAN_STEP):
            pass
        assert telemetry.dump_chrome_trace(str(tmp_path / "t.json")) is None
        assert list(tmp_path.iterdir()) == []

    def test_counter_tracks_in_chrome_trace(self, tmp_path):
        """The metrics registry rides the trace as Chrome counter-track
        ("ph":"C") events sampled at span records — Perfetto shows
        cumulative exchange / packed bytes as a throughput track under
        the spans.  Identical consecutive values are deduped."""
        telemetry.enable(dir=str(tmp_path))
        telemetry.inc(names.EXCHANGE_BYTES, 1024)
        with telemetry.span(names.SPAN_EXCHANGE):
            pass
        with telemetry.span(names.SPAN_SWAP):
            pass  # bytes unchanged: no second sample
        telemetry.inc(names.EXCHANGE_BYTES, 1024)
        telemetry.inc(names.EXCHANGE_PACKED_BYTES, 500)
        with telemetry.span(names.SPAN_STEP):
            pass
        doc = json.loads(open(telemetry.dump_chrome_trace()).read())
        tracks = [e for e in doc["traceEvents"] if e["ph"] == "C"]
        bytes_track = [
            e for e in tracks if e["name"] == names.EXCHANGE_BYTES
        ]
        assert [e["args"]["value"] for e in bytes_track] == [1024, 2048]
        assert all(e["ts"] >= 0 for e in tracks)
        packed_track = [
            e for e in tracks if e["name"] == names.EXCHANGE_PACKED_BYTES
        ]
        assert [e["args"]["value"] for e in packed_track] == [0, 500]
        # spans still render as complete events alongside the tracks
        assert any(e["ph"] == "X" for e in doc["traceEvents"])


# --- JSONL event sink --------------------------------------------------------


class TestEvents:
    def test_schema_and_rank_tag(self, tmp_path):
        telemetry.enable(dir=str(tmp_path))
        telemetry.emit_event(
            names.EVENT_RETRY, label="dispatch:jacobi", attempt=1, delay_s=0.25
        )
        telemetry.emit_event(names.EVENT_DESCENT, from_rung="a", to_rung="b")
        evs = _events(tmp_path)
        assert [e["event"] for e in evs] == [
            names.EVENT_RETRY, names.EVENT_DESCENT,
        ]
        for e in evs:
            assert isinstance(e["ts"], float) and e["rank"] == 0
        assert evs[0]["label"] == "dispatch:jacobi" and evs[0]["attempt"] == 1
        assert evs[1]["from_rung"] == "a" and evs[1]["to_rung"] == "b"

    def test_disabled_emits_no_file(self, tmp_path, monkeypatch):
        monkeypatch.chdir(tmp_path)
        telemetry.emit_event(names.EVENT_RETRY, label="x")
        assert list(tmp_path.iterdir()) == []

    def test_events_without_dir_rejected(self):
        with pytest.raises(ValueError, match="directory"):
            telemetry.enable(events=True)

    def test_env_events_without_dir_rejected_even_when_off(self, monkeypatch):
        """An explicit STENCIL_TELEMETRY_EVENTS=1 with nowhere to write is a
        config error even with the master switch off — the user asked for a
        JSONL log they would silently never get."""
        monkeypatch.setenv("STENCIL_TELEMETRY_EVENTS", "1")
        monkeypatch.delenv("STENCIL_TELEMETRY_DIR", raising=False)
        monkeypatch.setenv("STENCIL_TELEMETRY", "0")
        t = telemetry._Telemetry()
        with pytest.raises(ValueError, match="STENCIL_TELEMETRY_DIR"):
            t.configure_from_env()
        monkeypatch.setenv("STENCIL_TELEMETRY_DIR", "/tmp")
        t.configure_from_env()  # with a dir it parses fine (still disabled)
        assert not t.enabled


# --- the jax.profiler trace() wrapper ----------------------------------------


class TestTraceWrapper:
    """Pins for telemetry.spans.trace() (previously unpinned): no-op on
    None, creates the dir up front, and survives a backend with no
    profiler — the graceful-degrade contract device-time attribution
    rides on (CPU dryrun containers)."""

    def test_none_is_noop(self, tmp_path, monkeypatch):
        from stencil_tpu.telemetry import trace

        monkeypatch.chdir(tmp_path)
        with trace(None):
            pass
        with trace(""):
            pass
        assert list(tmp_path.iterdir()) == []

    def test_creates_the_dir(self, tmp_path):
        from stencil_tpu.telemetry import trace

        d = tmp_path / "nested" / "prof"
        with trace(str(d)):
            pass
        assert d.is_dir()

    def test_survives_backend_without_profiler(self, tmp_path, monkeypatch):
        """A profiler that raises at capture start warns ONCE and runs the
        body unprofiled; a failed finalize cannot eat the body's result."""
        import jax

        import stencil_tpu.telemetry.spans as spans_mod

        class _NoProfiler:
            def trace(self, d):
                raise RuntimeError("profiler not supported on this backend")

        monkeypatch.setattr(jax, "profiler", _NoProfiler())
        monkeypatch.setattr(spans_mod, "_trace_unavailable_warned", False)
        ran = []
        for _ in range(2):
            with spans_mod.trace(str(tmp_path / "prof")):
                ran.append(True)
        assert ran == [True, True]
        assert spans_mod._trace_unavailable_warned  # warned (once)

        class _FailsOnExit:
            class _Ctx:
                def __enter__(self):
                    return self

                def __exit__(self, *a):
                    raise RuntimeError("finalize exploded")

            def trace(self, d):
                return self._Ctx()

        monkeypatch.setattr(jax, "profiler", _FailsOnExit())
        out = []
        with spans_mod.trace(str(tmp_path / "prof2")):
            out.append("body ran")
        assert out == ["body ran"]


# --- the in-memory event ring (the crash-report tail) ------------------------


class TestEventRing:
    def test_ring_records_even_when_disabled(self, tmp_path, monkeypatch):
        """Like the counters, the flight ring stays live with telemetry
        off — the runs whose last events matter most die unconfigured.
        No file is ever created."""
        monkeypatch.chdir(tmp_path)
        assert not telemetry.enabled()
        telemetry.emit_event(names.EVENT_RETRY, label="x", attempt=1)
        evs = telemetry.recent_events()
        assert len(evs) == 1
        assert evs[0]["event"] == names.EVENT_RETRY and evs[0]["attempt"] == 1
        assert isinstance(evs[0]["ts"], float)
        assert list(tmp_path.iterdir()) == []

    def test_ring_is_bounded_and_ordered(self):
        for i in range(telemetry.RING_SIZE + 10):
            telemetry.emit_event(names.EVENT_RETRY, attempt=i)
        evs = telemetry.recent_events()
        assert len(evs) == telemetry.RING_SIZE
        assert evs[-1]["attempt"] == telemetry.RING_SIZE + 9  # newest last
        assert evs[0]["attempt"] == 10  # oldest retained
        tail = telemetry.recent_events(5)
        assert [e["attempt"] for e in tail] == list(
            range(telemetry.RING_SIZE + 5, telemetry.RING_SIZE + 10)
        )
        telemetry.reset()
        assert telemetry.recent_events() == []


# --- rank-tagged sink output under a simulated multi-rank run ----------------


class TestMultiRankSink:
    def test_per_rank_files_and_tags(self, tmp_path, monkeypatch):
        """Each rank's sink lands in its own events_<rank>.jsonl with
        matching rank tags — pinned by simulating the rank probe, exactly
        what a multi-host run changes."""
        from stencil_tpu.telemetry import events as events_mod

        sinks = {}
        for rank in (0, 1):
            monkeypatch.setattr(events_mod, "_rank", lambda r=rank: r)
            sink = events_mod.EventSink(str(tmp_path))
            sink.emit(names.EVENT_RETRY, {"label": f"rank{rank}"})
            sink.emit(names.EVENT_DESCENT, {"from_rung": "a", "to_rung": "b"})
            sinks[rank] = sink
        for sink in sinks.values():
            sink.close()
        for rank in (0, 1):
            path = tmp_path / f"events_{rank}.jsonl"
            assert path.exists(), f"rank {rank} sink file missing"
            recs = [json.loads(l) for l in path.read_text().splitlines()]
            assert len(recs) == 2
            assert all(r["rank"] == rank for r in recs)
            assert recs[0]["label"] == f"rank{rank}"

    def test_sink_path_pinned_at_first_emit(self, tmp_path, monkeypatch):
        """The file is keyed by the rank AT FIRST EMIT and stays stable
        for the sink's lifetime even if the rank probe's answer changes
        (backend init mid-run must not fork the log)."""
        from stencil_tpu.telemetry import events as events_mod

        monkeypatch.setattr(events_mod, "_rank", lambda: 3)
        sink = events_mod.EventSink(str(tmp_path))
        sink.emit(names.EVENT_RETRY, {"attempt": 1})
        monkeypatch.setattr(events_mod, "_rank", lambda: 7)
        sink.emit(names.EVENT_RETRY, {"attempt": 2})
        sink.close()
        assert (tmp_path / "events_3.jsonl").exists()
        assert not (tmp_path / "events_7.jsonl").exists()
        recs = [
            json.loads(l)
            for l in (tmp_path / "events_3.jsonl").read_text().splitlines()
        ]
        assert len(recs) == 2


# --- the acceptance integration: fault injection -> counters + events --------


class TestResilienceIntegration:
    def test_injected_transient_increments_retry_counter(
        self, tmp_path, monkeypatch
    ):
        """The ISSUE acceptance scenario: a STENCIL_FAULT_PLAN-injected
        transient failure increments ``resilience.retry.attempts`` and the
        run still completes bit-identically."""
        monkeypatch.setenv("STENCIL_RETRY_BACKOFF_S", "0.0")
        telemetry.enable(dir=str(tmp_path))
        m = Jacobi3D(16, 16, 16, devices=jax.devices()[:1])
        m.realize()
        inject.set_plan("dispatch:transient:jacobi*2")
        m.step(3)
        snap = telemetry.snapshot()
        assert snap["counters"][names.RETRY_ATTEMPTS] == 2
        assert snap["counters"][names.FAULTS_INJECTED] == 2
        assert snap["counters"][names.STEP_DISPATCHES] == 1
        assert snap["counters"][names.STEP_ITERATIONS] == 3
        assert snap["histograms"][names.STEP_SECONDS]["count"] == 1
        retries = [e for e in _events(tmp_path) if e["event"] == names.EVENT_RETRY]
        assert len(retries) == 2
        assert retries[0]["label"] == "dispatch:jacobi"
        assert retries[0]["attempt"] == 1 and retries[1]["attempt"] == 2
        assert "connection reset" in retries[0]["error"]
        # the run completed despite the faults (bit-equality vs a clean run
        # is already pinned by test_resilience)
        assert np.isfinite(m.temperature()).all()

    def test_ladder_descent_logs_from_to_event(self, tmp_path):
        """An injected VMEM OOM walks the stream ladder one rung down; the
        descent is both a counter and an event carrying from/to rung
        labels."""
        telemetry.enable(dir=str(tmp_path))
        dd, _ = _mk_domain(["u"], jax.devices()[:8], mult=3)
        step = dd.make_step(mean6_kernel, engine="stream", interpret=True)
        inject.set_plan("execute:vmem_oom:stream*1")
        dd.run_step(step, 3)
        snap = telemetry.snapshot()
        assert snap["counters"][names.LADDER_DESCENTS] == 1
        assert snap["counters"][names.FAULTS_INJECTED] == 1
        # rung builds were timed (initial build + the post-descent rebuild)
        assert snap["histograms"][names.LADDER_BUILD_SECONDS]["count"] >= 2
        descents = [
            e for e in _events(tmp_path) if e["event"] == names.EVENT_DESCENT
        ]
        assert len(descents) == 1
        assert descents[0]["label"] == "stream"
        assert descents[0]["from_rung"] == "wavefront[m=3]"
        assert descents[0]["to_rung"] == "wavefront[m=2]"
        assert descents[0]["failure_class"] == "vmem_oom"
        compiles = [
            e for e in _events(tmp_path) if e["event"] == names.EVENT_COMPILE
        ]
        assert any(e["phase"] == "ladder" for e in compiles)
        assert any(e["phase"] == "exchange" for e in compiles)

    def test_sentinel_trip_counts_and_logs(self, tmp_path):
        telemetry.enable(dir=str(tmp_path))
        from stencil_tpu.resilience.taxonomy import DivergenceError

        m = Jacobi3D(16, 16, 16, devices=jax.devices()[:1],
                     check_divergence_every=1)
        m.realize()
        arr = m.dd._curr["temp"]
        c = tuple(s // 2 for s in arr.shape)
        m.dd._curr["temp"] = arr.at[c].set(jnp.nan)
        with pytest.raises(DivergenceError):
            m.step(1)
        assert telemetry.snapshot()["counters"][names.SENTINEL_TRIPS] == 1
        trips = [
            e for e in _events(tmp_path)
            if e["event"] == names.EVENT_DIVERGENCE
        ]
        assert trips and trips[0]["quantity"] == "temp" and trips[0]["step"] == 1

    def test_retry_exhaustion_counted(self, tmp_path, monkeypatch):
        monkeypatch.setenv("STENCIL_RETRY_BACKOFF_S", "0.0")
        monkeypatch.setenv("STENCIL_RETRY_MAX", "1")
        telemetry.enable(dir=str(tmp_path))
        m = Jacobi3D(16, 16, 16, devices=jax.devices()[:1])
        m.realize()
        inject.set_plan("dispatch:transient:jacobi*5")
        with pytest.raises(RuntimeError, match="connection reset"):
            m.step(2)
        snap = telemetry.snapshot()
        assert snap["counters"][names.RETRY_EXHAUSTED] == 1
        assert snap["counters"][names.RETRY_ATTEMPTS] == 1
        assert any(
            e["event"] == names.EVENT_RETRY_EXHAUSTED for e in _events(tmp_path)
        )


# --- domain accounting -------------------------------------------------------


class TestDomainAccounting:
    def test_exchange_bytes_and_timing_single_path(self, tmp_path):
        """``exchange()``/``swap()`` feed the reference-parity DomainStats
        AND the telemetry histograms from one timing path; the byte counter
        is the wires of the exchange's message plan (the sum of its hops, as
        for a step: ISSUE 49), the gauge the analytic ``exchange_bytes_total``."""
        telemetry.enable(dir=str(tmp_path))
        dd, _ = _mk_domain(["u", "v"], jax.devices()[:8])
        per = sum(dd.exchange_hop_bytes().values())
        dd.exchange()
        dd.swap()
        dd.exchange_many(3)
        snap = telemetry.snapshot()
        assert snap["counters"][names.EXCHANGE_COUNT] == 4
        assert snap["counters"][names.EXCHANGE_BYTES] == 4 * per > 0
        assert snap["counters"][names.EXCHANGE_BYTES] == sum(
            snap["counters"][c] for c in names.EXCHANGE_HOP_BYTES.values())
        assert snap["gauges"][names.EXCHANGE_BYTES_PER_EXCHANGE] == dd.exchange_bytes_total()
        assert snap["histograms"][names.EXCHANGE_SECONDS]["count"] == 1
        assert snap["histograms"][names.SWAP_SECONDS]["count"] == 1
        # telemetry timing populated DomainStats without enable_exchange_stats
        assert dd.stats.time_exchange > 0
        # the exchange span landed on the chrome timeline
        doc = json.loads(open(telemetry.dump_chrome_trace()).read())
        assert any(e["name"] == names.SPAN_EXCHANGE for e in doc["traceEvents"])

    def test_exchange_stats_opt_in_still_works_without_telemetry(self):
        """The reference's STENCIL_EXCHANGE_STATS opt-in must keep timing
        DomainStats when telemetry is disabled (one code path, two
        consumers)."""
        assert not telemetry.enabled()
        dd, _ = _mk_domain(["u"], jax.devices()[:8])
        dd.enable_exchange_stats(True)
        dd.exchange()
        dd.swap()
        assert dd.stats.time_exchange > 0
        # but no histogram was recorded (telemetry off) — the canonical name
        # is still seeded, empty
        assert telemetry.snapshot()["histograms"][names.EXCHANGE_SECONDS]["count"] == 0

    def test_run_step_macro_accounting(self, tmp_path):
        """Under a halo multiplier the xla engine's macro step advances mult
        raw iterations per dispatch-step and exchanges once per macro."""
        telemetry.enable(dir=str(tmp_path))
        dd, _ = _mk_domain(["u"], jax.devices()[:8], mult=2)
        step = dd.make_step(mean6_kernel, overlap=False)
        per = sum(dd.exchange_hop_bytes().values())  # the wires of one exchange (ISSUE 49)
        dd.run_step(step, 3)  # 3 macros = 6 raw iterations, 3 exchanges
        snap = telemetry.snapshot()
        assert snap["counters"][names.STEP_ITERATIONS] == 6
        assert snap["counters"][names.EXCHANGE_COUNT] == 3
        assert snap["counters"][names.EXCHANGE_BYTES] == 3 * per > 0


# --- drivers and bench -------------------------------------------------------


def test_driver_metrics_out(tmp_path):
    """``--metrics-out`` writes a full snapshot, the driver restores the
    disabled default, and sequential in-process runs start owned telemetry
    from zeroed metrics (no counter bleed into the second snapshot)."""
    from stencil_tpu.bin.jacobi3d import main

    argv = ["--iters", "2", "--no-weak-scale", "16", "16", "16"]
    a, b = tmp_path / "a.json", tmp_path / "b.json"
    assert main(argv + ["--metrics-out", str(a)]) == 0
    snap = json.loads(a.read_text())
    assert snap["counters"][names.STEP_DISPATCHES] >= 3
    assert snap["counters"][names.EXCHANGE_BYTES] > 0
    assert snap["histograms"][names.STEP_SECONDS]["count"] >= 3
    assert snap["histograms"][names.STEP_SECONDS]["trimean"] > 0
    assert not telemetry.enabled()
    assert main(argv + ["--metrics-out", str(b)]) == 0
    cb = json.loads(b.read_text())["counters"]
    assert cb[names.STEP_DISPATCHES] == snap["counters"][names.STEP_DISPATCHES]
    assert cb[names.EXCHANGE_BYTES] == snap["counters"][names.EXCHANGE_BYTES]


@pytest.mark.slow
def test_driver_crash_still_writes_metrics(tmp_path):
    """A CLI driver that dies mid-run still leaves its --metrics-out
    post-mortem snapshot (atexit path) — the failed runs are the ones whose
    retry counters matter most."""
    out = tmp_path / "crash.json"
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        STENCIL_RETRY_MAX="0",
        STENCIL_FAULT_PLAN="dispatch:transient:jacobi*9",
    )
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "stencil_tpu.bin.jacobi3d",
         "--iters", "1", "--no-weak-scale", "16", "16", "16",
         "--metrics-out", str(out)],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert proc.returncode != 0, (proc.stdout, proc.stderr)
    snap = json.loads(out.read_text())
    assert snap["counters"][names.FAULTS_INJECTED] >= 1


@pytest.mark.slow
def test_bench_json_grows_telemetry_section(tmp_path):
    """ISSUE acceptance: a CPU bench run with telemetry enabled produces a
    BENCH JSON with per-step histogram stats, exchange-bytes counters, and
    resilience counters; and writes the JSONL/trace artifacts.

    tier-2 (slow): a full bench.py subprocess.  The in-process tests above
    cover the same counters/histograms; the bench embedding itself is a
    two-line guarded block pinned by test_bench_disabled_writes_no_telemetry_key."""
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        STENCIL_BENCH_SIZE="16",
        STENCIL_BENCH_INTERPRET="1",
        STENCIL_TELEMETRY_DIR=str(tmp_path),
    )
    env.pop("XLA_FLAGS", None)  # 1 CPU device is enough and much faster
    proc = subprocess.run(
        [sys.executable, "bench.py"],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    lines = [l for l in proc.stdout.splitlines() if l.strip().startswith("{")]
    artifact = json.loads(lines[-1])
    tel = artifact["telemetry"]
    assert tel["histograms"][names.STEP_SECONDS]["count"] > 0
    assert tel["histograms"][names.STEP_SECONDS]["min"] > 0
    assert tel["counters"][names.EXCHANGE_BYTES] == 0  # one chip: a step's own account has no wire (ISSUE 49)
    assert tel["counters"][names.STEP_ITERATIONS] > 0
    # resilience counters present (zero on a clean run) — the diffable part
    assert tel["counters"][names.RETRY_ATTEMPTS] == 0
    assert tel["counters"][names.LADDER_DESCENTS] == 0
    assert (tmp_path / "events_0.jsonl").exists()  # compile events at least
    assert (tmp_path / "trace_0.json").exists()


# stencil-lint: disable=slow-marker reads bench.py's SOURCE for the guard string; never spawns it (the docstring says why)
def test_bench_disabled_writes_no_telemetry_key():
    """The disabled default: no telemetry key in the artifact and no files.
    Checked on the source, not a second full bench run (cost)."""
    src = open(os.path.join(REPO, "bench.py")).read()
    assert "telemetry.enabled()" in src  # guarded, not unconditional


# stencil-lint: disable=slow-marker the no-backend-init contract is only provable in a fresh interpreter; the child imports telemetry (jax-free) and exits in ~1s
def test_telemetry_never_initializes_backend():
    """A metrics/event call in a fresh process must not bring a jax backend
    up (the logging._rank fail-closed rule extends to telemetry)."""
    code = (
        "import sys, tempfile\n"
        "from stencil_tpu import telemetry\n"
        "from stencil_tpu.telemetry import names\n"
        "telemetry.enable(dir=tempfile.mkdtemp())\n"
        "telemetry.inc(names.RETRY_ATTEMPTS)\n"
        "telemetry.emit_event(names.EVENT_RETRY, label='x')\n"
        "with telemetry.span(names.SPAN_STEP):\n"
        "    pass\n"
        "telemetry.snapshot(); telemetry.write_artifacts()\n"
        "xb = sys.modules.get('jax._src.xla_bridge')\n"
        "assert xb is None or not getattr(xb, '_backends', None), 'backend up!'\n"
        "assert 'jax' not in sys.modules, 'telemetry imported jax'\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        env={"PATH": os.environ.get("PATH", "/usr/bin:/bin"), "PYTHONPATH": REPO},
        timeout=120,
    )
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
