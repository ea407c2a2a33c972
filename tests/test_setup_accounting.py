"""Set-up accounted from inside the program (PR 35): the always-live phase
totals behind ``telemetry.span(total=)``, jax's own trace / compile / cache
events charged to the innermost open phase, and the first dispatch of each
program as a phase of its own — on the CPU, in process, with
``STENCIL_TELEMETRY`` off and (but for the one-clock test) no profiler
session."""

import glob
import os
import time

import jax
import jax.numpy as jnp
import pytest

from stencil_tpu import DistributedDomain, Radius, telemetry
from stencil_tpu.telemetry import names as tm

TRACE_EVENT = tm.JAX_TRACE_EVENT


@pytest.fixture(autouse=True)
def _fresh_account():
    telemetry.disable()
    telemetry.reset()
    assert telemetry.watch_jax()  # jax is imported here: the listeners are on
    yield
    telemetry.disable()
    telemetry.reset()


def _counters():
    return telemetry.snapshot()["counters"]


def _series(total, phase, epoch=tm.EPOCH_SETUP):
    return tm.PHASE_SERIES[epoch, total, phase]


def _domain():
    dd = DistributedDomain(16, 16, 8)
    dd.set_radius(Radius.constant(3))
    dd.set_devices(jax.devices()[:4])
    handles = [dd.add_data(f"q{i}", dtype=jnp.float32) for i in range(2)]
    dd.realize()
    return dd, handles


def _fresh_jit(scale):
    """A program no test has compiled before (its constant is in its text)."""
    return jax.jit(lambda x: jnp.sin(x) * scale + scale)


def test_totals_are_live_with_telemetry_off_and_no_session():
    assert not telemetry.enabled()
    with telemetry.span(tm.SPAN_REALIZE, total=tm.PHASE_REALIZE):
        time.sleep(0.02)
        with telemetry.span(tm.EVENT_COMPILE, total=tm.PHASE_COMPILE, label="unit"):
            time.sleep(0.01)
    c = _counters()
    assert c[_series(tm.TOTAL_SPAN_COUNT, tm.PHASE_REALIZE)] == 1
    assert c[_series(tm.TOTAL_SPAN_COUNT, tm.PHASE_COMPILE)] == 1
    # each total is inclusive: the outer span holds the inner one's time
    assert 0.01 <= c[_series(tm.TOTAL_SPAN_SECONDS, tm.PHASE_COMPILE)] < 0.02
    assert 0.03 <= c[_series(tm.TOTAL_SPAN_SECONDS, tm.PHASE_REALIZE)] < 0.3
    assert telemetry._t.spans.events() == []  # the recorder stayed off
    assert telemetry._t.spans.phase() is None  # ...and the phase stack unwound
    # a span WITHOUT total= is what it was: no phase, no total
    with telemetry.span(tm.SPAN_SWAP):
        assert telemetry._t.spans.phase() is None


def test_jax_events_are_charged_to_the_innermost_phase_and_to_outside():
    x = jnp.ones((8, 128))
    telemetry.reset()  # making ``x`` compiled outside every phase
    with telemetry.span(tm.SPAN_REALIZE, total=tm.PHASE_REALIZE):
        with telemetry.span(tm.EVENT_COMPILE, total=tm.PHASE_COMPILE, label="unit"):
            _fresh_jit(1.25)(x)
    c = _counters()
    assert c[_series(tm.TOTAL_BACKEND_COMPILES, tm.PHASE_COMPILE)] == 1
    assert c[_series(tm.TOTAL_BACKEND_SECONDS, tm.PHASE_COMPILE)] > 0
    assert c[_series(tm.TOTAL_TRACE_SECONDS, tm.PHASE_COMPILE)] > 0
    assert c[_series(tm.TOTAL_BACKEND_COMPILES, tm.PHASE_REALIZE)] == 0  # the innermost phase took it
    assert c[_series(tm.TOTAL_BACKEND_COMPILES, tm.PHASE_OUTSIDE)] == 0
    _fresh_jit(2.25)(x)  # no program span open: a caller's own jit
    c = _counters()
    assert c[_series(tm.TOTAL_BACKEND_COMPILES, tm.PHASE_OUTSIDE)] == 1
    assert c[_series(tm.TOTAL_TRACE_SECONDS, tm.PHASE_OUTSIDE)] > 0
    assert c[_series(tm.TOTAL_BACKEND_COMPILES, tm.PHASE_COMPILE)] == 1


def test_a_trace_nested_in_a_trace_counts_once():
    """A jit traced inside a jit reports its own duration inside the outer
    one's: jax announces each start (a scalar of the event's name), and only
    the outermost duration is added."""
    telemetry._on_jax_start(TRACE_EVENT, 0.0, fun_name="outer")
    telemetry._on_jax_start(TRACE_EVENT, 0.0, fun_name="inner")
    telemetry._on_jax_duration(TRACE_EVENT, 1.0, fun_name="inner")
    telemetry._on_jax_duration(TRACE_EVENT, 3.0, fun_name="outer")
    telemetry._on_jax_duration(TRACE_EVENT, 0.5, fun_name="unannounced")  # never below zero
    assert _counters()[_series(tm.TOTAL_TRACE_SECONDS, tm.PHASE_OUTSIDE)] == 3.5
    # the real thing: the outer program's trace holds the inner one's
    inner = jax.jit(lambda x: sum((jnp.cos(x + i) for i in range(60)), x))
    outer = jax.jit(lambda x: inner(x) * 3.0)
    telemetry.reset()
    t0 = time.perf_counter()
    outer.trace(jnp.ones((8, 128)))
    wall = time.perf_counter() - t0
    traced = _counters()[_series(tm.TOTAL_TRACE_SECONDS, tm.PHASE_OUTSIDE)]
    assert 0 < traced <= wall, (traced, wall)


def _step_call(dd):
    step = dd.make_step(lambda views, info: {k: v.sh(0, 0, 0) * 1.0 for k, v in views.items()})
    return tm.SPAN_STEP, lambda: dd.run_step(step, 4, label="unit")


DISPATCHES = {
    "run_step": _step_call,
    "exchange": lambda dd: (tm.SPAN_EXCHANGE, dd.exchange),
    "exchange_many": lambda dd: (tm.SPAN_EXCHANGE, lambda: dd.exchange_many(3)),
}


@pytest.mark.parametrize("entry", sorted(DISPATCHES))
def test_first_call_is_first_dispatch_and_the_second_is_steady(entry, monkeypatch):
    dd, handles = _domain()
    span_name, call = DISPATCHES[entry](dd)
    opened = []
    real = telemetry._profiler_annotation
    monkeypatch.setattr(
        telemetry, "_profiler_annotation", lambda name, args: (opened.append((name, args)), real(name, args))[1]
    )
    first_count = _series(tm.TOTAL_SPAN_COUNT, tm.PHASE_FIRST_DISPATCH)
    assert telemetry._t.epoch == tm.EPOCH_SETUP and _counters()[first_count] == 0

    call()  # jax traces, lowers and compiles or loads in here
    c = _counters()
    assert c[first_count] == 1 and c[_series(tm.TOTAL_SPAN_SECONDS, tm.PHASE_FIRST_DISPATCH)] > 0
    assert telemetry._t.epoch == tm.EPOCH_SETUP  # a first dispatch does not end set-up
    if entry != "exchange":  # realize() compiled the exchange eagerly: its first call finds it built
        assert c[_series(tm.TOTAL_BACKEND_COMPILES, tm.PHASE_FIRST_DISPATCH)] >= 1

    call()
    call()
    c = _counters()
    assert telemetry._t.epoch == tm.EPOCH_RUN  # the first steady dispatch ended it
    assert c[first_count] == 1 and c[tm.PHASE_SERIES[tm.EPOCH_RUN, tm.TOTAL_SPAN_COUNT, tm.PHASE_FIRST_DISPATCH]] == 0
    firsts = [args.get("first") for name, args in opened if name == span_name]
    assert firsts == [1, None, None], firsts  # the steady calls' arguments are what they were
    for epoch in tm.EPOCHS:  # nothing compiled in a steady dispatch
        assert c[tm.PHASE_SERIES[epoch, tm.TOTAL_BACKEND_COMPILES, tm.PHASE_STEADY]] == 0

    # what comes after the start -- a second fill -- is the run's, not the set-up's
    before = c[_series(tm.TOTAL_SPAN_COUNT, tm.PHASE_INIT)]
    dd.init_by_coords(handles[0], lambda x, y, z: (x + y + z).astype(jnp.float32))
    c = _counters()
    assert c[_series(tm.TOTAL_SPAN_COUNT, tm.PHASE_INIT)] == before
    assert c[tm.PHASE_SERIES[tm.EPOCH_RUN, tm.TOTAL_SPAN_COUNT, tm.PHASE_INIT]] == 1


def test_an_unhashable_step_object_is_told_first_from_steady_by_identity():
    """``run_step`` takes any callable: one that is no weak-dictionary key (it
    defines ``__eq__``, so it does not hash) is known by identity."""
    dd, _ = _domain()
    built = dd.make_step(lambda views, info: {k: v.sh(0, 0, 0) * 1.0 for k, v in views.items()})

    class Step:
        def __eq__(self, other):
            return self is other

        def __call__(self, curr, steps):
            return built(curr, steps)

    step = Step()
    with pytest.raises(TypeError):
        hash(step)
    assert dd._dispatch_span_args(step, 2) == {"total": tm.PHASE_FIRST_DISPATCH, "first": 1}
    assert dd._dispatch_span_args(step, 2) == {"total": tm.PHASE_STEADY}
    assert dd._dispatch_span_args(step, 3)["first"] == 1  # other static arguments: another program
    dd.run_step(step, 2)


def test_a_recompile_inside_a_steady_dispatch_is_charged_to_steady():
    dd, _ = _domain()
    step = dd.make_step(lambda views, info: {k: v.sh(0, 0, 0) * 1.0 for k, v in views.items()})
    dd.run_step(step, 2)
    jax.clear_caches()  # the program's executable is gone: its next call compiles again
    dd.run_step(step, 2)
    assert _counters()[tm.PHASE_SERIES[tm.EPOCH_RUN, tm.TOTAL_BACKEND_COMPILES, tm.PHASE_STEADY]] >= 1


def test_a_warm_persistent_cache_reads_hits_and_no_misses(tmp_path):
    """The second start of a process against a warm cache directory, in one
    process: the in-memory executables are dropped between the two, so the
    second compile request goes to the persistent cache and is served."""
    from jax.experimental.compilation_cache import compilation_cache

    saved = {
        k: getattr(jax.config, k)
        for k in ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
                  "jax_persistent_cache_min_entry_size_bytes", "jax_enable_compilation_cache")
    }
    x = jnp.ones((8, 128))

    def start():
        telemetry.reset()
        with telemetry.span(tm.SPAN_INIT, total=tm.PHASE_INIT, quantity="unit"):
            _fresh_jit(3.75)(x).block_until_ready()
        c = _counters()
        return {t: c[_series(t, tm.PHASE_INIT)] for t in (
            tm.TOTAL_BACKEND_COMPILES, tm.TOTAL_CACHE_HITS,
            tm.TOTAL_CACHE_MISSES, tm.TOTAL_CACHE_RETRIEVAL_SECONDS)}

    try:
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        compilation_cache.reset_cache()
        cold = start()
        jax.clear_caches()
        warm = start()
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    assert cold[tm.TOTAL_BACKEND_COMPILES] == 1, cold
    assert cold[tm.TOTAL_CACHE_MISSES] == 1 and cold[tm.TOTAL_CACHE_HITS] == 0, cold
    assert warm[tm.TOTAL_BACKEND_COMPILES] == 1, warm
    assert warm[tm.TOTAL_CACHE_HITS] == 1 and warm[tm.TOTAL_CACHE_MISSES] == 0, warm
    assert warm[tm.TOTAL_CACHE_RETRIEVAL_SECONDS] > 0 == cold[tm.TOTAL_CACHE_RETRIEVAL_SECONDS]
    # what ``setup_cold_compiles`` reads: compiles the cache did not serve
    assert cold[tm.TOTAL_BACKEND_COMPILES] - cold[tm.TOTAL_CACHE_HITS] == 1
    assert warm[tm.TOTAL_BACKEND_COMPILES] - warm[tm.TOTAL_CACHE_HITS] == 0


def test_the_listener_is_registered_once_and_survives_reset():
    from jax._src import monitoring

    _domain()
    _domain()  # a second realize() registers nothing
    assert telemetry.watch_jax() is True
    assert monitoring.get_event_listeners().count(telemetry._on_jax_event) == 1
    assert monitoring.get_event_duration_listeners().count(telemetry._on_jax_duration) == 1
    assert monitoring.get_scalar_listeners().count(telemetry._on_jax_start) == 1
    x = jnp.ones((8, 128))
    telemetry.reset()
    c = _counters()
    assert all(c[name] == 0 for name in tm.PHASE_SERIES.values())
    _fresh_jit(4.75)(x)
    assert _counters()[_series(tm.TOTAL_BACKEND_COMPILES, tm.PHASE_OUTSIDE)] == 1


def test_reset_reopens_the_set_up_epoch():
    assert telemetry.dispatch_phase(first=True) == tm.PHASE_FIRST_DISPATCH
    assert telemetry._t.epoch == tm.EPOCH_SETUP
    assert telemetry.dispatch_phase(first=False) == tm.PHASE_STEADY
    assert telemetry._t.epoch == tm.EPOCH_RUN
    with telemetry.span(tm.SPAN_INIT, total=tm.PHASE_INIT):
        pass
    assert _counters()[tm.PHASE_SERIES[tm.EPOCH_RUN, tm.TOTAL_SPAN_COUNT, tm.PHASE_INIT]] == 1
    telemetry.reset()
    assert telemetry._t.epoch == tm.EPOCH_SETUP


def _host_spans(trace_dir):
    """(name, duration in seconds, args) of every host-plane event."""
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.name, e.duration_ns / 1e9, dict(e.stats)) for e in line.events)
    return out


def test_one_clock_the_profilers_spans_equal_the_totals(tmp_path):
    """A profiler session started BEFORE ``realize()`` (an operator's
    ``--profile-dir``): the host-plane durations of ``domain.realize``,
    ``domain.init`` and the ``first=1`` ``domain.step`` are the always-live
    totals, to 5 ms or 5%."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        dd, handles = _domain()
        for h in handles:
            dd.init_by_coords(h, lambda x, y, z: (x + 2 * y + 3 * z).astype(jnp.float32))
        step = dd.make_step(lambda views, info: {k: v.sh(0, 0, 0) * 1.0 for k, v in views.items()})
        dd.run_step(step, 4, label="unit")
        dd.run_step(step, 4, label="unit")
        jax.block_until_ready(dd._curr)
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path))
    c = _counters()

    def on_the_profilers_clock(name, first=False):
        return [d for n, d, args in spans if n == name and ("first" in args) == first]

    for name, phase, first, count in (
        (tm.SPAN_REALIZE, tm.PHASE_REALIZE, False, 1),
        (tm.SPAN_INIT, tm.PHASE_INIT, False, 2),
        (tm.SPAN_STEP, tm.PHASE_FIRST_DISPATCH, True, 1),
    ):
        seen = on_the_profilers_clock(name, first)
        total = c[_series(tm.TOTAL_SPAN_SECONDS, phase)]
        assert len(seen) == count == c[_series(tm.TOTAL_SPAN_COUNT, phase)], (name, seen)
        assert abs(sum(seen) - total) <= max(5e-3, 0.05 * total), (name, sum(seen), total)
    assert len(on_the_profilers_clock(tm.SPAN_STEP)) == 1  # the steady dispatch: a span, no total


def test_program_total_reads_nothing_on_a_registry_without_the_series(monkeypatch):
    from benchmark.reducers import program_total

    args = {"series": ["setup.backend_compiles"], "minus": ["setup.cache_hits"], "phases": list(tm.TIMED_PHASES)}
    assert program_total.reduce({}, **args) == 0  # this program seeds every series: 0 is a reading
    parent = {"counters": {tm.STEP_DISPATCHES: 3}, "gauges": {}, "histograms": {}}
    monkeypatch.setattr(telemetry, "snapshot", lambda: parent)  # a program from before PR 35
    assert program_total.reduce({}, **args) is None
    assert program_total.reduce({}, series=["setup.span_seconds"], phases=["realize"]) is None
