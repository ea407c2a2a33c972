"""One timeline (PR 25): the program's kernel names, exchange scopes and
host spans as a profiler trace sees them — pinned without a chip.

* every ``pallas_call`` of the canonical programs carries a registered
  kernel name, and the TPU lowering of a named call inside an exchange
  scope carries both (cross-lowered on the CPU);
* the exchange program is scoped ``exchange.<axis>`` nearly everywhere (6 of
  287 HLO instructions before this PR), a program without an exchange
  nowhere;
* ``telemetry.span`` lands on the host plane of a live ``jax.profiler``
  session with its args, and the hot path (``run_step`` / ``exchange`` /
  ``swap``) opens its spans without ever calling ``block_until_ready``;
* a registered span or kernel name that no call site uses fails (the
  reverse of the ``span-name`` / ``kernel-name`` lint rules).
"""

import ast
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest

from stencil_tpu import DistributedDomain, Radius, analysis, telemetry
from stencil_tpu.analysis import jaxpr as jx
from stencil_tpu.analysis import programs as aprog
from stencil_tpu.telemetry import names as tm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPECS = {s.label: s for s in aprog.CANONICAL_PROGRAMS}
SWEEPS = set(tm.EXCHANGE_AXIS_SPANS.values())

# one program per kernel family the matrix reaches: wrap / plane / wavefront
# stream passes, blend (static + traced offset), z and y pack + unpack
KERNEL_PROGRAMS = {
    "step:wrap/off": {tm.KERNEL_STREAM_WRAP_PASS},
    "step:plane/off/zpack_pallas": {
        tm.KERNEL_STREAM_PLANE_PASS, tm.KERNEL_PACK_ZSHELL, tm.KERNEL_UNPACK_ZSHELL,
    },
    "step:wavefront/off/direct/uneven": {
        tm.KERNEL_STREAM_WAVEFRONT_PASS, tm.KERNEL_BLEND_SLAB_DYNAMIC,
    },
    "exchange:direct": {tm.KERNEL_BLEND_PLANES, tm.KERNEL_BLEND_SLAB},
    "exchange:yzpack_pallas": {tm.KERNEL_PACK_YSHELL, tm.KERNEL_UNPACK_YSHELL},
}


@pytest.fixture(autouse=True)
def _telemetry_off():
    telemetry.disable()
    telemetry.reset()
    yield
    telemetry.disable()


def _kernel_names(art):
    return {
        e.params.get("name")
        for e in jx.iter_eqns(art.closed)
        if e.primitive.name == "pallas_call"
    }


@pytest.mark.parametrize("label", sorted(KERNEL_PROGRAMS))
def test_every_pallas_call_carries_a_registered_name(label):
    art = aprog.build_program(SPECS[label])
    got = _kernel_names(art)
    assert got and got <= tm.ALL_KERNELS, got - tm.ALL_KERNELS
    assert KERNEL_PROGRAMS[label] <= got, KERNEL_PROGRAMS[label] - got
    assert not analysis.check(art, contract="kernel-name")
    # the name is also the innermost scope of the call (its HLO op_name)
    for e in jx.iter_eqns(art.closed):
        if e.primitive.name == "pallas_call":
            assert jx.name_stack_str(e).split("/")[-1] == e.params["name"]


@pytest.mark.parametrize(
    "axis,kernel", [(0, tm.KERNEL_BLEND_PLANES), (1, tm.KERNEL_BLEND_SLAB), (2, tm.KERNEL_BLEND_SLAB)]
)
def test_tpu_lowering_carries_kernel_name_and_scope(axis, kernel):
    """Cross-lowered for the TPU on the CPU: the custom call's
    ``kernel_name`` is the registered name, and its location the scope path
    a trace reader joins on."""
    from stencil_tpu.ops import halo_blend

    scope = tm.exchange_axis_span("xyz"[axis])
    slab_shape = [16, 16, 128]
    slab_shape[axis] = 2

    def f(block, slab):
        with jax.named_scope(scope):
            return halo_blend.blend_slab(block, slab, axis, 0, interpret=False)

    block = jax.ShapeDtypeStruct((16, 16, 128), jnp.float32)
    slab = jax.ShapeDtypeStruct(tuple(slab_shape), jnp.float32)
    text = jax.jit(f).trace(block, slab).lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert "tpu_custom_call" in text
    assert f'kernel_name = "{kernel}"' in text, re.findall(r'kernel_name = "[^"]*"', text)
    assert f"{scope}/{kernel}/pallas_call" in text, re.findall(r'loc\("[^"]*pallas_call[^"]*"', text)


def _instructions(hlo_text):
    """(opcode, op_name) of every instruction of a compiled module's text
    that does work (parameters, constants and tuple plumbing carry no time)."""
    out = []
    for line in hlo_text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (?:\([^=]*?\)|\S+) ([a-z][\w\-]*)\(", line)
        if not m or m.group(1) in ("parameter", "constant", "tuple", "get-tuple-element", "bitcast"):
            continue
        name = re.search(r'op_name="([^"]*)"', line)
        out.append((m.group(1), name.group(1) if name else ""))
    return out


def _exchange_domain():
    dd = DistributedDomain(16, 16, 8)
    dd.set_radius(Radius.constant(3))
    dd.set_devices(jax.devices()[:4])
    for i in range(2):
        dd.add_data(f"q{i}", dtype=jnp.float32)
    dd.realize()
    return dd


def test_exchange_program_is_scoped_in_hlo():
    """The compiled exchange (the weak cell's program at rehearsal size):
    at least 90% of its instructions carry an ``exchange.<axis>`` sweep
    scope in ``op_name`` — the slab cuts, reshapes and blends, not just the
    six ppermutes."""
    dd = _exchange_domain()
    text = dd._exchange_fn.lower(dd._curr).compile().as_text()
    rows = _instructions(text)
    scoped = [r for r in rows if SWEEPS & set(r[1].split("/"))]
    assert len(rows) > 50 and len(scoped) >= 0.9 * len(rows), (len(scoped), len(rows))
    wires = [r for r in rows if r[0].startswith("collective-permute")]
    assert wires and all(r in scoped for r in wires)
    for art_label in ("exchange:direct", "exchange:zpack_xla", "step:wavefront/off/direct/zslab"):
        assert not analysis.check(aprog.build_program(SPECS[art_label]), contract="exchange-scope")


def test_program_without_exchange_carries_no_exchange_scope():
    """The bypass: the one-chip wrap route moves nothing between shards, so
    no instruction of its program may claim an exchange scope — the
    ``exchange_dev_pct`` of ``jacobi3d-512.bulk`` is 0 by construction."""
    from stencil_tpu.models.jacobi import Jacobi3D

    model = Jacobi3D(16, 16, 128, devices=jax.devices()[:1], kernel_impl="pallas", interpret=True)
    model.realize()
    assert model._pallas_path == "wrap"
    text = model._step.lower(model.dd._curr, 4).compile().as_text()
    rows = _instructions(text)
    assert rows and not [r for r in rows if "exchange." in r[1]]
    art = aprog.build_program(SPECS["step:wrap/off"])
    assert not analysis.check(art, contract="exchange-scope")
    assert not any("exchange." in jx.name_stack_str(e) for e in jx.iter_eqns(art.closed))


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.name, dict(e.stats)) for e in line.events)
    return out


@pytest.fixture
def profiler_session(tmp_path):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    stopped = []

    def stop():
        if not stopped:
            stopped.append(jax.profiler.stop_trace())
        return _host_events(str(tmp_path))

    yield stop
    stop()


def test_span_lands_on_the_profilers_host_plane_with_its_args(profiler_session):
    """STENCIL_TELEMETRY off: the profiler session alone is the switch."""
    assert not telemetry.enabled()
    with telemetry.span(tm.SPAN_STEP, label="unit", steps=48):
        with telemetry.span(tm.EVENT_COMPILE, label="inner"):
            pass
    events = profiler_session()
    steps = [s for n, s in events if n == tm.SPAN_STEP]
    assert len(steps) == 1 and steps[0]["label"] == "unit" and int(steps[0]["steps"]) == 48, steps
    assert [s["label"] for n, s in events if n == tm.EVENT_COMPILE] == ["inner"]
    assert telemetry.snapshot()["histograms"][tm.STEP_SECONDS]["count"] == 0  # the recorder stayed off


@pytest.mark.parametrize("session", ["no_session", "live_session"])
def test_hot_path_opens_spans_and_never_syncs(session, monkeypatch, request):
    """``run_step`` / ``exchange`` / ``swap`` with STENCIL_TELEMETRY unset
    and exchange-stats off: every call is a span, none calls
    ``block_until_ready`` (the two-deep dispatch pipeline stays two deep) and,
    steady, none reads a clock: the set-up account (PR 35) times the FIRST
    call of a program and only marks the later ones."""
    import time

    dd = _exchange_domain()
    step = dd.make_step(lambda views, info: {k: v.sh(0, 0, 0) * 1.0 for k, v in views.items()})
    dd.run_step(step, 4)  # the first dispatch of each program (compile, first=1): outside the counted stretch
    dd.exchange()
    jax.block_until_ready(dd._curr)
    stop = request.getfixturevalue("profiler_session") if session == "live_session" else None

    syncs, opened, clock_reads = [], [], []

    class CountingClock:  # ``time`` as the program's modules see it
        def __getattr__(self, name):
            if name == "perf_counter":
                clock_reads.append(name)
            return getattr(time, name)

    import stencil_tpu.domain as domain_module

    monkeypatch.setattr(telemetry, "time", CountingClock())
    monkeypatch.setattr(domain_module, "time", CountingClock())
    monkeypatch.setattr(DistributedDomain, "block_until_ready", lambda self: syncs.append("dd"))
    real_jax_sync = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready", lambda x: (syncs.append("jax"), real_jax_sync(x))[1])
    real = telemetry._profiler_annotation
    monkeypatch.setattr(
        telemetry, "_profiler_annotation", lambda name, args: (opened.append((name, args)), real(name, args))[1]
    )
    assert not telemetry.enabled() and not dd._exchange_stats
    for _ in range(3):
        dd.run_step(step, 4, label="unit")
        dd.exchange()
        dd.swap()
        dd.swap()
    monkeypatch.undo()
    assert syncs == [], syncs
    assert clock_reads == [], clock_reads
    names = [n for n, _ in opened]
    assert names.count(tm.SPAN_STEP) == 3 and names.count(tm.SPAN_EXCHANGE) == 3 and names.count(tm.SPAN_SWAP) == 6
    step_args = [a for n, a in opened if n == tm.SPAN_STEP]
    wires = {"wired": "xy", "wire_bytes": sum(dd.exchange_hop_bytes().values()) // dd.num_subdomains(),
             "joint": "xy"}  # x and y fly jointly on this mesh (ISSUE 50)
    assert all(a == {"label": "unit", "steps": 4, **wires} for a in step_args), step_args  # the xla
    # engine's own account of its wires (ISSUE 49): one exchange of every quantity a step
    exchange_args = [a for n, a in opened if n == tm.SPAN_EXCHANGE]
    assert all(
        a == {"route": "direct", "nbytes": dd.exchange_bytes_total(), "count": 1, "wrap_axes": "",
              "uneven_axes": "", "wire_bytes": wires["wire_bytes"], "joint": "xy"}
        for a in exchange_args
    ), exchange_args  # wrap_axes "": on the CPU the blend kernels are off, so z self-ppermutes
    if stop is not None:
        events = stop()
        assert sum(int(s["steps"]) for n, s in events if n == tm.SPAN_STEP) == 12
        assert len([n for n, _ in events if n == tm.SPAN_EXCHANGE]) == 3


def test_telemetry_on_still_syncs_inside_the_span():
    """The documented operator mode is unchanged: STENCIL_TELEMETRY=1 waits
    for the device inside the span (honest wall time, one sync per
    dispatch) — which is why it must stay off in a measured run."""
    dd = _exchange_domain()
    telemetry.enable()
    syncs = []
    real = DistributedDomain.block_until_ready
    try:
        DistributedDomain.block_until_ready = lambda self: (syncs.append(1), real(self))[1]
        dd.exchange()
    finally:
        DistributedDomain.block_until_ready = real
    assert syncs == [1]
    spans = [e for e in telemetry._t.spans.events() if e["name"] == tm.SPAN_EXCHANGE]
    assert len(spans) == 1 and spans[0]["args"]["route"] == "direct"
    assert telemetry.snapshot()["histograms"][tm.EXCHANGE_SECONDS]["count"] == 1


def _referenced_constants():
    """Every ``tm.X`` / ``names.X`` attribute the product tree reads, and
    every name of ``telemetry/names.py`` it calls through."""
    refs = set()
    for path in glob.glob(os.path.join(ROOT, "stencil_tpu", "**", "*.py"), recursive=True):
        if path.endswith(os.path.join("telemetry", "names.py")):
            continue
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Attribute) and isinstance(node.value, ast.Name):
                if node.value.id in ("tm", "names"):
                    refs.add(node.attr)
    return refs


@pytest.mark.parametrize("group", ["ALL_SPANS", "ALL_KERNELS"])
def test_every_registered_name_has_a_call_site(group):
    """The reverse of the lint rules: a span or kernel name nobody opens
    feeds no metric and no operator — remove it from the registry."""
    refs = _referenced_constants()
    constants = {k: v for k, v in vars(tm).items() if k.isupper() and isinstance(v, str)}
    through_helper = {}  # the keyed registries are reached through their helper
    for helper, table in (
        ("exchange_direction_span", tm.EXCHANGE_DIRECTION_SPANS),
        ("exchange_axis_span", tm.EXCHANGE_AXIS_SPANS),
        ("exchange_wrap_span", tm.EXCHANGE_WRAP_SPANS),
        ("step_stage_span", {"k": tm.SPAN_STEP_STAGE}),  # step.stage.<k>
        ("stage_pass_span", {"i": tm.SPAN_STAGE_PASS}),  # pass.<i>
    ):
        for value in table.values():
            through_helper[value] = helper
    unused = []
    for value in sorted(getattr(tm, group)):
        consts = [k for k, v in constants.items() if v == value]
        assert consts, value
        if any(k in refs for k in consts) or through_helper.get(value) in refs:
            continue
        unused.append(value)
    assert not unused, f"registered in names.{group} but opened nowhere: {unused}"
