"""Tier-2: generic plane-streaming kernel matches the jnp path for the
Astaroth proxy (radius-3 shell, distance-1 reads), even and uneven sizes."""

import numpy as np
import pytest

from ulp import assert_reassociation_close

from stencil_tpu.models.astaroth import AstarothSim
from stencil_tpu.ops import stream_plan as sp
from stencil_tpu.ops import stream_pass as spass


@pytest.mark.parametrize("size", [(28, 28, 28), (15, 14, 13)])
def test_astaroth_pallas_matches_jnp(size):
    a = AstarothSim(*size, num_quantities=2)
    a.realize()
    b = AstarothSim(*size, num_quantities=2, kernel_impl="pallas", interpret=True)
    b.realize()
    # the default schedule upgrades to the temporal wavefront everywhere:
    # even sizes on the z-slab variant, padded sizes on the plain variant
    assert b._wavefront_m == 3
    a.step(3)
    b.step(3)
    for i in range(2):
        # summation-order rounding differs between the two formulations
        np.testing.assert_allclose(a.field(i), b.field(i), rtol=1e-6, atol=1e-6)


def test_astaroth_wavefront_schedule_matches_per_step():
    """The opt-in wavefront schedule (exchange every m<=3 steps, m-level
    kernel over the radius-3 shell) reproduces the per-step pallas schedule:
    a level-s shell cell computed in-kernel uses the same arithmetic the
    neighbor applies to the same level-(s-1) values, so skipping the
    intermediate exchanges changes nothing — up to the LAST ULP, which XLA
    may perturb by fusing the m levels into one graph (excess-precision /
    reassociation across the division); hence the analytic reassociation
    bound from tests/ulp.py, not array_equal (a depth-1 macro IS bitwise,
    see below): ≤ 6 roundings per level may land in a different order /
    excess precision, each contributing at most a half-ulp at the six-sum's
    magnitude (≤ 6·|field|)."""
    a = AstarothSim(28, 28, 28, num_quantities=2, kernel_impl="pallas", interpret=True,
                    schedule="per-step")
    a.realize()
    b = AstarothSim(28, 28, 28, num_quantities=2, kernel_impl="pallas", interpret=True,
                    schedule="wavefront")
    b.realize()
    assert b._wavefront_m >= 2
    a.step(5)
    b.step(5)  # macros + a shallower remainder dispatch
    for i in range(2):
        assert_reassociation_close(
            b.field(i), a.field(i), rounds=6 * 5, scale=6.0,
            context=f"fused wavefront q{i}",
        )

    # one step = a depth-1 remainder dispatch = the same exchange cadence:
    # near-identical (the engine's plane and wavefront passes evaluate the
    # same kernel arithmetic; only the shell handling differs)
    a1 = AstarothSim(28, 28, 28, kernel_impl="pallas", interpret=True,
                     schedule="per-step")
    a1.realize(); a1.step(1)
    b1 = AstarothSim(28, 28, 28, kernel_impl="pallas", interpret=True,
                     schedule="wavefront")
    b1.realize(); b1.step(1)
    np.testing.assert_array_equal(a1.field(0), b1.field(0))


def test_astaroth_halo_multiplier_deepens_wavefront():
    """A halo multiplier widens the radius-3 shell, letting the engine
    wavefront deeper than 3 levels per exchange — same field values."""
    a = AstarothSim(32, 32, 32, kernel_impl="pallas", interpret=True,
                    schedule="per-step")
    a.realize()
    b = AstarothSim(32, 32, 32, kernel_impl="pallas", interpret=True)
    b.dd.set_halo_multiplier(2)  # shell 6 -> m up to 6
    b.realize()
    assert b._wavefront_m == 6, b._wavefront_m
    a.step(7)
    b.step(7)  # one macro + a shallower remainder
    np.testing.assert_allclose(a.field(), b.field(), rtol=1e-6, atol=1e-6)

    with pytest.raises(ValueError, match="per-step"):
        c = AstarothSim(32, 32, 32, kernel_impl="pallas", interpret=True,
                        schedule="per-step")
        c.dd.set_halo_multiplier(2)
        c.realize()


def test_astaroth_wavefront_uneven_and_jnp_guard():
    # uneven sizes run the wavefront's PLAIN variant at full depth now
    m = AstarothSim(15, 14, 13, kernel_impl="pallas", interpret=True,
                    schedule="wavefront")
    m.realize()
    assert m._wavefront_m == 3
    # the temporal schedule needs the streaming engine
    with pytest.raises(ValueError, match="pallas"):
        AstarothSim(16, 16, 16, schedule="wavefront").realize()


def test_mean6_kernel_axes_variants():
    """The bespoke mean6 kernels' storage-dtype variants (ISSUE 7): nothing
    in the shipped models calls these two directly (the astaroth wavefront
    rides ops/stream.py), so pin the f32-accumulate forms HERE against
    their native siblings or they rot as the shared helper
    (_level_sum) evolves."""
    import jax.numpy as jnp

    from ulp import assert_bf16_storage_close

    from stencil_tpu.core.dim3 import Dim3
    from stencil_tpu.ops.plane_stencil import (
        mean6_plane_step,
        mean6_shell_wavefront_step,
    )

    rng = np.random.default_rng(11)
    src = rng.random((16, 16, 16)).astype(np.float32)
    # the wavefront kernel ALIASES its input (input_output_aliases={0: 0}),
    # so every call gets its own device buffer
    fresh = lambda dt=jnp.float32: jnp.asarray(src, dt)
    raw = fresh()

    # temporal wavefront: bf16 one downcast per pass.
    # Only the interior is valid at level m (the shell carries garbage by
    # the validity contract), so compare inside the shell_width=3 ring.
    core = (slice(3, 13),) * 3
    v = mean6_shell_wavefront_step(fresh(), m=2, shell_width=3, interpret=True)
    b = mean6_shell_wavefront_step(fresh(jnp.bfloat16), m=2,
                                   shell_width=3, interpret=True,
                                   f32_accumulate=True)
    assert b.dtype == jnp.bfloat16
    assert_bf16_storage_close(np.asarray(b)[core], np.asarray(v)[core],
                              passes=1, scale=1.0,
                              context="mean6 wavefront bf16")

    # single-level plane pass: same contract (interior window only — the
    # pass-through shell keeps its input bytes in every variant)
    one = Dim3(1, 1, 1)
    pv = mean6_plane_step(raw, one, one, interpret=True)
    pb = mean6_plane_step(raw.astype(jnp.bfloat16), one, one, interpret=True,
                          f32_accumulate=True)
    assert_bf16_storage_close(pb, pv, passes=1, scale=1.0,
                              context="mean6 plane bf16")


# --- exchange only what the kernel reads (ISSUE 30) ---------------------------
#
# On the plane route the step exchanges the quantities the kernel reads
# off-centre and no others (ops/stream_plan.py trace_plane_kernel).  Every case
# runs the same kernel three ways -- the XLA slice engine (which exchanges
# everything), the plane route as built, and the plane route with the rule
# switched off (the parent's program: every quantity exchanged) -- and holds
# every interior cell of every quantity BITWISE equal across the three.
# (The cellwise updates multiply by powers of two only: the CPU compiler
# contracts a multiply-add into one rounding in one engine's fusion and not in
# the other's, and an exact product rounds the same either way.)


def _star(v, r):
    """``test_stream.star_kernel``'s radius-``r`` star (distinct weights per
    direction and distance, so a wrong offset, ring slot or stale halo cell
    cannot cancel) over any one view."""
    from test_stream import star_kernel

    return star_kernel(r)({"u": v}, None)["u"]


def two_of_three_kernel(r):
    """``a`` and ``b`` are read off-centre; ``c`` is a coefficient read at the
    centre and never returned."""

    def kernel(views, info):
        a, b, c = views["a"], views["b"], views["c"]
        return {
            "a": _star(a, r) * (1.0 + 0.1 * c.center()) - 0.05 * b.sh(0, -r, 0),
            "b": 0.5 * _star(b, r) + 0.1 * a.center(),
        }

    return kernel


def centre_only_kernel(views, info):
    """Reads nothing off-centre: a cellwise update of two time levels."""
    u, v = views["u"].center(), views["v"].center()
    return {"u": 0.5 * u + 0.25 * v, "v": u}


def separable_kernel(r):
    """Correct on any subset of views: ``a`` diffuses, ``b`` decays in place."""

    def kernel(views, info):
        return {
            name: _star(v, r) if name == "a" else 0.5 * v.center() + 0.01
            for name, v in views.items()
        }

    return kernel


def _plane_domain(names, r, n_dev, extent=(16, 16, 16)):
    import jax

    from test_stream import _mk

    from stencil_tpu.core.radius import Radius

    return _mk(*extent, Radius.constant(r), names, jax.devices()[:n_dev])


def _plane_step(dd, kernel, r, plan_kw):
    """The plane route's step as ``_build_stream_step`` builds it, with the
    plan it resolved."""
    from stencil_tpu.ops import stream as sm

    request = dict(sp.plan_stream(dd, r, "plane", False), **plan_kw)
    plan = sp.resolve_stream_plan(dd, kernel, r, request, True)
    return sm._build_stream_step(dd, kernel, r, plan, interpret=True), plan


def _exchange_everything(monkeypatch):
    """The rules off: the trace every build makes fails closed, so every
    quantity is exchanged, ringed and written (PR 29's program)."""

    monkeypatch.setattr(
        sp, "trace_plane_kernel",
        lambda kernel, names, planes, r, *a: sp.PlaneTrace(
            tuple(names), tuple(names), tuple(names), r, None, kernel
        ),
    )


def _ppermute_cells(fn, curr) -> int:
    """Cells sent through ``ppermute`` by one step of the traced program."""
    import jax

    from stencil_tpu.analysis import jaxpr as jx

    closed = jax.make_jaxpr(fn, static_argnums=1)(curr, 1)
    return sum(
        int(np.prod(v.aval.shape))
        for e in jx.iter_eqns(closed)
        if e.primitive.name == "ppermute"
        for v in e.invars
    )


SPLIT = {"overlap": "split", "overlap_forced": True}


def _case(id, kernel, names, r, n_dev, readers, plan_kw=None, extent=(16, 16, 16)):
    return pytest.param(kernel, names, r, n_dev, readers, plan_kw or {}, extent, id=id)


_READER_CASES = [
    _case("two-of-three", two_of_three_kernel(2), ["a", "b", "c"], 2, 1, ("a", "b")),
    _case("order-is-names", two_of_three_kernel(1), ["c", "b", "a"], 1, 1, ("b", "a")),
    _case("nothing-off-centre", centre_only_kernel, ["u", "v"], 1, 1, ()),
    _case("nothing-off-centre-dev8", centre_only_kernel, ["u", "v"], 1, 8, ()),
    _case("separable-per-field", separable_kernel(2), ["a", "b"], 2, 1, ("a",),
          {"grouping": "per-field"}),
    _case("split", two_of_three_kernel(2), ["a", "b", "c"], 2, 8, ("a", "b"), SPLIT),
    _case("split-per-field", separable_kernel(1), ["a", "b"], 1, 8, ("a",),
          dict(SPLIT, grouping="per-field")),
    _case("mesh-2x2x2", two_of_three_kernel(2), ["a", "b", "c"], 2, 8, ("a", "b")),
    _case("mesh-splits-one-axis", separable_kernel(3), ["a", "b"], 3, 2, ("a",)),
    _case("padded", two_of_three_kernel(1), ["a", "b", "c"], 1, 8, ("a", "b"),
          extent=(15, 13, 15)),
]


@pytest.mark.parametrize("kernel,names,r,n_dev,readers,plan_kw,extent", _READER_CASES)
def test_plane_route_exchanges_only_what_the_kernel_reads(
    kernel, names, r, n_dev, readers, plan_kw, extent, monkeypatch
):
    def run_plane():
        dd, hs = _plane_domain(names, r, n_dev, extent)
        step, plan = _plane_step(dd, kernel, r, plan_kw)
        wires = _ppermute_cells(step, dd._curr)
        dd.run_step(step, 3)
        assert plan["route"] == "plane", plan
        for key, want in plan_kw.items():  # the variant engaged, it did not degrade
            assert plan[key] == want, plan
        return [dd.quantity_to_host(h) for h in hs], plan["halo_readers"], wires

    fields = {}
    dd, hs = _plane_domain(names, r, n_dev, extent)
    dd.run_step(dd.make_step(kernel, overlap=False), 3)
    fields["xla"] = [dd.quantity_to_host(h) for h in hs]
    fields["readers"], got, wires = run_plane()
    assert got == readers
    _exchange_everything(monkeypatch)
    fields["all"], got, wires_all = run_plane()
    assert got == tuple(names)

    for i, name in enumerate(names):
        assert np.isfinite(fields["xla"][i]).all() and np.ptp(fields["xla"][i]) > 0, name
        assert np.array_equal(fields["readers"][i], fields["all"][i]), name
        assert np.array_equal(fields["readers"][i], fields["xla"][i]), name
    # the joint message carries the readers' slabs and nothing else (on one
    # CPU device too: without the blend kernels an unsplit axis sends to itself)
    assert wires_all > 0 and wires * len(names) == wires_all * len(readers), (wires, wires_all)


@pytest.mark.parametrize("n_dev", [1, 2, 4, 8])
def test_the_plan_says_what_crosses_a_wire(n_dev):
    """``wired`` / ``wire_bytes`` (ISSUE 37) against the traced program, with
    the sweeps as the chip has them (blend kernels on: an unsplit axis wraps
    and sends nothing): the axes the plan names are the axes the mesh splits,
    and the bytes it states are the ``ppermute`` operands of one step -- the
    two readers' slabs of three quantities, both sides of every wired axis."""
    from stencil_tpu.analysis.programs import tpu_shaped_trace
    from stencil_tpu.parallel.mesh import MESH_AXES

    with tpu_shaped_trace():
        dd, _ = _plane_domain(["a", "b", "c"], 2, n_dev, extent=(32, 32, 16))
        step, plan = _plane_step(dd, two_of_three_kernel(2), 2, {})
        cells = _ppermute_cells(step, dd._curr)
    split = "".join(a for a, m in zip(MESH_AXES, dd.mesh_dim()) if m > 1)
    assert plan["halo_readers"] == ("a", "b") and plan["wired"] == split, plan
    assert plan["wire_bytes"] == 4 * cells and (cells > 0) == (n_dev > 1), (plan, cells)
    assert not set(plan["wired"]) & set(plan["pass_wrap_axes"]), plan


def test_a_kernel_that_reads_nothing_off_centre_exchanges_nothing():
    """``exchanged`` 0 through ``make_step`` and no ``exchange.*`` scope (nor
    any collective) anywhere in the step's program."""
    import jax

    from stencil_tpu.analysis import jaxpr as jx
    from stencil_tpu.analysis.programs import tpu_shaped_trace

    with tpu_shaped_trace():
        dd, _ = _plane_domain(["u", "v"], 1, 8)
        step = dd.make_step(centre_only_kernel, engine="stream", stream_path="plane",
                            interpret=True)
        args = step._span_args()
        assert (args["route"], args["streamed"], args["exchanged"]) == ("plane", 2, 0), args
        closed = jax.make_jaxpr(step._resilience.built(), static_argnums=1)(dd._curr, 2)
    eqns = list(jx.iter_eqns(closed))
    assert not [e for e in eqns if "exchange." in jx.name_stack_str(e)]
    assert not [e for e in eqns if e.primitive.name == "ppermute"]
    # one pass a step, and nothing else: ``v <- u`` is a rename (ISSUE 36), so
    # a trip of the step loop holds the permutation's period, two steps
    assert step._span_args()["renamed"] == 1
    assert sum(e.primitive.name == "pallas_call" for e in eqns) == 2


def test_a_footprint_trace_that_raises_exchanges_everything(monkeypatch):
    """Fail closed: the abstract trace could not be made, so every quantity
    rides the exchange as before -- and the step still runs and is right."""
    from stencil_tpu.ops import stream as sm

    real = spass.stream_plane_pass
    in_pass = []

    def spy(*a, **kw):
        in_pass.append(True)
        try:
            return real(*a, **kw)
        finally:
            in_pass.pop()

    monkeypatch.setattr(sm, "stream_plane_pass", spy)
    inner = two_of_three_kernel(2)

    def kernel(views, info):
        if not in_pass:
            raise RuntimeError("not traceable outside a pass")
        return inner(views, info)

    names = ["a", "b", "c"]
    dd, hs = _plane_domain(names, 2, 1)
    step, plan = _plane_step(dd, kernel, 2, {})
    assert plan["halo_readers"] == ("a", "b", "c"), plan
    dd.run_step(step, 2)
    got = [dd.quantity_to_host(h) for h in hs]
    dd, hs = _plane_domain(names, 2, 1)
    dd.run_step(dd.make_step(inner, overlap=False), 2)
    for name, a, h in zip(names, got, hs):
        assert np.array_equal(a, dd.quantity_to_host(h)), name


def _one_pass(kernel, **kw):
    """``stream_plane_pass`` itself over ``a`` and ``c``, abstractly."""
    import jax
    import jax.numpy as jnp

    from stencil_tpu.core.dim3 import Dim3
    from stencil_tpu.ops.stream_pass import stream_plane_pass

    r, n = 1, 8
    blk = jax.ShapeDtypeStruct((n + 2 * r,) * 3, jnp.float32)

    def fn(origin, a, c):
        return stream_plane_pass(
            kernel, ["a", "c"], [a, c], Dim3(r, r, r), Dim3(r, r, r), r, origin,
            Dim3(n, n, n), interpret=True, **kw,
        )

    return jax.make_jaxpr(fn)(jax.ShapeDtypeStruct((3,), jnp.int32), blk, blk)


@pytest.mark.parametrize(
    "wrap_fills",
    [(), ((1, 0, 8, 1), (1, 9, 1, 1), (2, 0, 8, 1), (2, 9, 1, 1))],
    ids=["exchanged", "wrapped-in-the-pass"],
)
def test_an_off_centre_read_the_pass_was_not_told_of_raises_by_name(wrap_fills):
    """The pass checks what it is told: a kernel that reads ``c`` off-centre
    in a pass whose ``halo_readers`` leave ``c`` out meets a ``c`` whose halo
    was not exchanged, and the pass says so at trace time -- never a stale
    read; off-centre ALONG X in a pass that holds no ring for ``c`` likewise.
    The pass that fills the y / z halo itself (ISSUE 34) fills it for the
    readers alone, so it fails closed the same way."""

    def kernel(dx, dz):
        return lambda views, info: {"a": _star(views["a"], 1) * views["c"].sh(dx, 0, dz)}

    kw = {"wrap_fills": wrap_fills}
    _one_pass(kernel(0, 1), halo_readers=("a", "c"), rings=("a",), **kw)  # told: fine
    with pytest.raises(ValueError, match=r"reads 'c' off-centre.*halo of 'c' was not exchanged"):
        _one_pass(kernel(0, 1), halo_readers=("a",), **kw)
    with pytest.raises(ValueError, match=r"reads 'c' off-centre along x.*no ring for 'c'"):
        _one_pass(kernel(1, 0), rings=("a",), **kw)


def test_a_step_traces_its_kernel_once_and_runs_what_that_trace_saw():
    """The build traces the user's callable ONCE; the passes run that trace
    (``PlaneTrace.pruned``), so a callable that would read or return
    something else the second time is never asked a second time: what the
    footprint saw -- ``c`` at the centre, ``a`` alone returned -- IS what
    runs, and lowering and running the step trace nothing again."""
    calls = []

    def kernel(views, info):
        calls.append(1)
        c = views["c"]
        out = {"a": _star(views["a"], 1) * (c.center() if len(calls) == 1 else c.sh(0, 0, 1))}
        if len(calls) > 1:
            out["c"] = c.center() + 1.0
        return out

    dd, hs = _plane_domain(["a", "c"], 1, 1)
    step, plan = _plane_step(dd, kernel, 1, {})
    assert plan["halo_readers"] == ("a",) and plan["writers"] == ("a",)
    step.lower(dd._curr, 1)
    dd.run_step(step, 2)
    assert len(calls) == 1
    got = [dd.quantity_to_host(h) for h in hs]
    dd, hs = _plane_domain(["a", "c"], 1, 1)
    first = lambda views, info: {"a": _star(views["a"], 1) * views["c"].center()}  # noqa: E731
    dd.run_step(dd.make_step(first, overlap=False), 2)
    for a, h in zip(got, hs):
        assert np.array_equal(a, dd.quantity_to_host(h)), h.name


# --- write only what the kernel writes (ISSUE 32) -----------------------------
#
# The same trace learns which quantities the kernel RETURNS; the others are
# inputs of the plane pass and nothing else (ops/stream_plan.py trace_plane_kernel,
# stream_plane_pass(writers=)).  A pass with the rule off wrote every such
# quantity back cell for cell, so the two must agree on every RAW cell of
# every quantity, shell included.


def two_of_four_kernel(r):
    """``a`` and ``b`` are written; ``c`` is read off-centre (a coefficient
    with a halo) and ``d`` at the centre, and neither is returned."""

    def kernel(views, info):
        a, b, c, d = (views[n] for n in "abcd")
        return {
            "a": _star(a, r) * (1.0 + 0.5 * d.center()) - 0.25 * c.sh(0, 0, -r),
            "b": 0.5 * _star(b, r) + 0.125 * a.center() * c.sh(r, 0, 0),
        }

    return kernel


def separable_two_of_three(r):
    """Correct on any subset of views: ``a`` diffuses, ``b`` decays, ``c`` is
    carried and never returned -- under per-field grouping its pass has
    nothing to write and is not made."""

    def kernel(views, info):
        return {
            name: _star(v, r) if name == "a" else 0.5 * v.center() + 0.25
            for name, v in views.items()
            if name != "c"
        }

    return kernel


def _raising_kernel(views, info):
    raise RuntimeError("not traceable")


def _footprint(kernel, names, r, groups=None):
    import jax
    import jax.numpy as jnp

    from stencil_tpu.core.dim3 import Dim3

    plane = jax.ShapeDtypeStruct((16 + 2 * r, 16 + 2 * r), jnp.float32)
    traces = [  # one trace per group, as plan_plane_stages makes them
        sp.trace_plane_kernel(kernel, [names[q] for q in g], [plane] * len(g), r, Dim3(16, 16, 16))
        for g in groups or [list(range(len(names)))]
    ]
    return tuple(
        tuple(nm for nm in names if any(nm in got for got in of))
        for of in ([t.readers for t in traces], [t.writers for t in traces])
    )


@pytest.mark.parametrize(
    "kernel,names,r,groups,readers,writers",
    [
        pytest.param(two_of_four_kernel(2), list("abcd"), 2, None,
                     ("a", "b", "c"), ("a", "b"), id="two-of-four"),
        pytest.param(two_of_four_kernel(1), list("dcba"), 1, None,
                     ("c", "b", "a"), ("b", "a"), id="order-is-names"),
        pytest.param(centre_only_kernel, ["u", "v"], 1, None,
                     (), ("u", "v"), id="returns-every-name"),
        pytest.param(separable_kernel(2), ["a", "b"], 2, [[0], [1]],
                     ("a",), ("a", "b"), id="per-field-groups"),
        pytest.param(_raising_kernel, list("abcd"), 2, None,
                     tuple("abcd"), tuple("abcd"), id="a-trace-that-raises-writes-everything"),
    ],
)
def test_the_footprint_trace_learns_what_the_kernel_returns(
    kernel, names, r, groups, readers, writers
):
    assert _footprint(kernel, names, r, groups) == (readers, writers)


def _pass_calls(closed):
    from stencil_tpu.analysis import jaxpr as jx
    from stencil_tpu.telemetry import names as tm

    return [
        e for e in jx.iter_eqns(closed)
        if e.primitive.name == "pallas_call"
        and e.params.get("name") == tm.KERNEL_STREAM_PLANE_PASS
    ]


def _alias_pairs(eqn):
    return tuple(tuple(int(v) for v in p) for p in eqn.params["input_output_aliases"])


@pytest.mark.parametrize("alias", [False, True], ids=["fresh", "aliased"])
def test_the_pass_has_one_output_per_writer_and_is_bitwise_the_full_pass(alias):
    """``stream_plane_pass`` itself, four quantities, two of them read-only:
    the Pallas call has two outputs (and, aliased, two alias pairs from raw
    ``1 + q`` to the writer's place among the outputs), a read-only quantity
    comes back as the very array that went in, and all four results equal the
    every-quantity-written pass on every raw cell."""
    import jax
    import jax.numpy as jnp

    from stencil_tpu.core.dim3 import Dim3
    from stencil_tpu.ops.stream_pass import stream_plane_pass

    r, n = 2, 12
    names = list("dabc")  # the writers sit at positions 1 and 2
    raw = n + 2 * r
    rng = np.random.default_rng(32)
    raws = [jnp.asarray(rng.standard_normal((raw,) * 3), jnp.float32) for _ in names]
    origin = jnp.zeros((3,), jnp.int32)

    def run(writers):
        def fn(origin, *raws):
            return stream_plane_pass(
                two_of_four_kernel(r), names, list(raws), Dim3(r, r, r), Dim3(r, r, r),
                r, origin, Dim3(n, n, n), alias=alias, interpret=True, writers=writers,
            )

        (call,) = _pass_calls(jax.make_jaxpr(fn)(origin, *raws))
        return call, fn(origin, *raws)

    call, got = run(("a", "b"))
    full_call, want = run(None)
    assert len(call.invars) == len(full_call.invars) == 1 + 4
    assert (len(call.outvars), len(full_call.outvars)) == (2, 4)
    assert _alias_pairs(call) == (((2, 0), (3, 1)) if alias else ())
    assert _alias_pairs(full_call) == (((1, 0), (2, 1), (3, 2), (4, 3)) if alias else ())
    assert got[0] is raws[0] and got[3] is raws[3]
    for name, a, b, src in zip(names, got, want, raws):
        assert np.array_equal(np.asarray(a), np.asarray(b)), name
        assert (name in "ab") == bool(np.any(np.asarray(a) != np.asarray(src))), name


def _write_everything(monkeypatch):
    """The rule off: the parent's pass, every quantity an output (the readers
    stay as the trace found them)."""
    import dataclasses

    real = sp.trace_plane_kernel
    monkeypatch.setattr(
        sp, "trace_plane_kernel",
        lambda kernel, names, *a: dataclasses.replace(
            real(kernel, names, *a), writers=tuple(names), closed=None
        ),
    )


_FOUR = (two_of_four_kernel(2), list("abcd"))
_WRITER_CASES = [
    # kernel, names, n_dev, plan_kw, Pallas outputs of each plane pass of one
    # step with the rule on and with it off
    pytest.param(*_FOUR, 1, {}, [2], [4], id="in-place"),
    pytest.param(*_FOUR, 1, {"alias": False, "alias_forced": True}, [2], [4],
                 id="fresh-output"),
    pytest.param(*_FOUR, 8, {}, [2], [4], id="mesh-2x2x2"),
    pytest.param(*_FOUR, 8, SPLIT, [2] * 7, [4] * 7, id="split"),  # interior + six bands
    # c's own pass has nothing to write and is not made
    pytest.param(separable_two_of_three(2), ["a", "b", "c"], 1, {"grouping": "per-field"},
                 [1, 1], [1, 1, 1], id="per-field"),
]


@pytest.mark.parametrize("kernel,names,n_dev,plan_kw,outputs,outputs_all", _WRITER_CASES)
def test_plane_route_writes_only_what_the_kernel_returns(
    kernel, names, n_dev, plan_kw, outputs, outputs_all, monkeypatch
):
    """The step as built, rule on against rule off, three steps: every raw
    cell of every quantity bitwise equal (no exchange in between: the arrays
    as the step left them), the interior equal to the XLA engine's."""
    import jax

    r = 2

    def run_plane():
        dd, hs = _plane_domain(names, r, n_dev)
        step, plan = _plane_step(dd, kernel, r, plan_kw)
        calls = _pass_calls(jax.make_jaxpr(step, static_argnums=1)(dd._curr, 1))
        dd.run_step(step, 3)
        for key, want in plan_kw.items():
            assert plan[key] == want, plan
        raws = {h.name: np.asarray(dd._curr[h.name]) for h in hs}
        return raws, [dd.quantity_to_host(h) for h in hs], plan, calls

    raws, fields, plan, calls = run_plane()
    assert plan["writers"] == ("a", "b"), plan
    assert [len(e.outvars) for e in calls] == outputs
    in_place = sp._plan_passes_in_place(plan)
    for e in calls:
        assert len(_alias_pairs(e)) == (len(e.outvars) if in_place else 0)
    _write_everything(monkeypatch)
    raws_all, _, plan_all, calls_all = run_plane()
    assert plan_all["writers"] == tuple(names) and plan_all["halo_readers"] == plan["halo_readers"]
    assert [len(e.outvars) for e in calls_all] == outputs_all
    dd, hs = _plane_domain(names, r, n_dev)
    dd.run_step(dd.make_step(kernel, overlap=False), 3)
    for i, (name, h) in enumerate(zip(names, hs)):
        assert np.array_equal(raws[name], raws_all[name]), name
        assert np.array_equal(fields[i], dd.quantity_to_host(h)), name


def test_the_span_counts_the_written_quantities():
    from stencil_tpu.analysis.programs import tpu_shaped_trace

    with tpu_shaped_trace():
        dd, _ = _plane_domain(list("abcd"), 2, 1)
        step = dd.make_step(two_of_four_kernel(2), engine="stream", x_radius=2,
                            interpret=True)
        args = step._span_args()
    assert (args["route"], args["streamed"], args["aliased"], args["exchanged"],
            args["written"]) == ("plane", 4, 4, 3, 2), args


def test_a_name_the_pass_was_not_told_of_returned_raises_by_name():
    """The pass checks what it is told: a kernel that returns ``c`` in a pass
    whose ``writers`` leave ``c`` out meets a pass in which ``c`` is no
    output, and the pass says so at trace time -- never a dropped result."""

    def kernel(views, info):
        return {"a": _star(views["a"], 1) * views["c"].center(),
                "c": views["c"].center() + 1.0}

    _one_pass(kernel, writers=("a", "c"))  # told: fine
    with pytest.raises(ValueError, match=r"returns 'c'.*'c' is not an output of the pass"):
        _one_pass(kernel, writers=("a",))


def test_fused_shell_keeps_every_quantity_an_output():
    """Under ``halo="fused"`` the written planes are where the fresh shell
    lands: the build names every quantity a writer, and the pass itself keeps
    every output whatever ``writers`` it is handed."""
    import jax
    import jax.numpy as jnp

    from stencil_tpu.core.dim3 import Dim3
    from stencil_tpu.ops.stream_pass import stream_plane_pass

    r, n = 1, 8
    raw = n + 2 * r
    blk = jax.ShapeDtypeStruct((raw,) * 3, jnp.float32)
    xs = jax.ShapeDtypeStruct((2 * r, raw, raw), jnp.float32)
    ys = jax.ShapeDtypeStruct((raw, 2 * r, raw), jnp.float32)

    def fn(origin, a, c, xa, xc, ya, yc, za, zc):
        return stream_plane_pass(
            lambda views, info: {"a": _star(views["a"], r) * views["c"].center()},
            ["a", "c"], [a, c], Dim3(r, r, r), Dim3(r, r, r), r, origin,
            Dim3(n, n, n), alias=True, interpret=True,
            fused_shell=([xa, xc], [ya, yc], [za, zc]), writers=("a",),
        )

    origin = jax.ShapeDtypeStruct((3,), jnp.int32)
    (call,) = _pass_calls(jax.make_jaxpr(fn)(origin, blk, blk, xs, xs, ys, ys, ys, ys))
    assert len(call.outvars) == 2 and _alias_pairs(call) == ((1, 0), (2, 1))


def test_a_fused_plane_step_writes_every_quantity():
    import jax

    from test_stream_fused import _mk

    dd, hs = _mk(dtypes=(np.float32, np.float32))

    def kernel(views, info):  # q1 is a coefficient: read, never returned
        return {"q0": _star(views["q0"], 1) * (1.0 + views["q1"].center())}

    step = dd.make_step(kernel, engine="stream", interpret=True, stream_halo="fused",
                        stream_path="plane")
    plan = step._stream_plan
    assert (plan["route"], plan["halo"]) == ("plane", "fused"), plan
    assert plan["writers"] == ("q0", "q1") and step._span_args()["written"] == 2
    (call,) = _pass_calls(
        jax.make_jaxpr(step._resilience.built(), static_argnums=1)(dd._curr, 1)
    )
    assert len(call.outvars) == 2


# --- the pass wraps the planes it loads (ISSUE 34) ----------------------------
#
# On an axis the mesh does not split, the halo of a plane is a copy of cells of
# that same plane: ``stream_plane_pass(wrap_fills=)`` makes the y and z fills
# itself, in VMEM, on every loaded plane of every halo reader, and the step's
# exchange sweeps the other axes only.  Here the pass ITSELF: over blocks whose
# y / z shell is garbage it must equal, on every raw cell of every writer, the
# pass over blocks that were swept x -> y -> z.


def _self_wrap(a, axis, lo, hi):
    """The numpy twin of ``halo_blend.wrap_halo``: both halos of ``axis``
    from the array's own interior, over the full extent of the other axes."""
    a = a.copy()
    n = a.shape[axis] - lo - hi
    cut = lambda s, e: tuple(slice(s, e) if ax == axis else slice(None) for ax in range(3))  # noqa: E731
    if lo:
        a[cut(0, lo)] = a[cut(n, n + lo)]
    if hi:
        a[cut(lo + n, lo + n + hi)] = a[cut(lo, lo + hi)]
    return a


def _lagged_y_kernel(views, info):
    """elastic's ``tyy``: ``b`` is differenced along y alone (no ring: fetched
    lagged) and never written; ``a`` is written and read at the centre."""
    a, b = views["a"], views["b"]
    return {"a": 0.5 * a.center() + (b.sh(0, 1, 0) - 0.25 * b.sh(0, -1, 0))}


def _lagged_self_kernel(views, info):
    """A reader with no ring that is also the writer: its pass-through carries
    the patched centre plane back to HBM."""
    b = views["b"]
    return {"b": 0.5 * b.center() + 0.25 * b.sh(0, 0, 1) + 0.125 * b.sh(0, -1, 0)}


def _ringed_star(r):
    from test_stream import star_kernel

    return star_kernel(r)


_PASS_WRAP_CASES = [
    # id, kernel, names, r, lo, hi, readers, rings, writers, axes, pass kwargs
    pytest.param(_ringed_star(2), ["u"], 2, (2, 2, 2), (2, 2, 2), ("u",), ("u",), ("u",),
                 "yz", {}, id="ringed-reader-written"),
    pytest.param(_ringed_star(2), ["u"], 2, (2, 2, 2), (2, 2, 2), ("u",), ("u",), ("u",),
                 "yz", {"alias": True}, id="ringed-in-place"),
    pytest.param(_lagged_y_kernel, ["a", "b"], 1, (1, 1, 1), (1, 1, 1), ("b",), (), ("a",),
                 "yz", {}, id="lagged-reader-y-only"),
    pytest.param(_lagged_self_kernel, ["b"], 1, (1, 1, 1), (1, 1, 1), ("b",), (), ("b",),
                 "yz", {}, id="lagged-reader-written"),
    pytest.param(_ringed_star(1), ["u"], 1, (1, 2, 3), (2, 1, 1), ("u",), ("u",), ("u",),
                 "yz", {}, id="asymmetric-shells"),
    pytest.param(_lagged_self_kernel, ["b"], 1, (1, 3, 1), (1, 1, 2), ("b",), (), ("b",),
                 "yz", {}, id="asymmetric-shells-lagged"),
    pytest.param(_ringed_star(2), ["u"], 2, (2, 2, 2), (2, 2, 2), ("u",), ("u",), ("u",),
                 "z", {}, id="z-alone-rides"),
    pytest.param(_ringed_star(2), ["u"], 2, (2, 2, 2), (2, 2, 2), ("u",), ("u",), ("u",),
                 "y", {}, id="y-alone-rides"),
    pytest.param(_ringed_star(1), ["u"], 1, (1, 1, 1), (1, 1, 1), ("u",), ("u",), ("u",),
                 "yz", {"f32_accumulate": True}, id="bf16-storage"),
]


@pytest.mark.parametrize(
    "kernel,names,r,lo,hi,readers,rings,writers,axes,kw", _PASS_WRAP_CASES
)
def test_the_pass_wraps_the_planes_it_loads(
    kernel, names, r, lo, hi, readers, rings, writers, axes, kw
):
    """Every raw cell of every writer, bit for bit: the pass with
    ``wrap_fills`` over blocks whose shell on the riding axes was never
    filled, against the plain pass over blocks swept x -> y -> z -- and the
    garbage mattered (the plain pass over the unswept blocks differs)."""
    import jax.numpy as jnp

    from stencil_tpu.core.dim3 import Dim3
    from stencil_tpu.ops.stream_pass import stream_plane_pass

    n = 10
    shape = tuple(n + a + b for a, b in zip(lo, hi))
    dtype = jnp.bfloat16 if kw.get("f32_accumulate") else jnp.float32
    rng = np.random.default_rng(34)
    swept, unswept, fills = [], [], []
    for axis in (1, 2):
        if "xyz"[axis] in axes:
            m = shape[axis] - lo[axis] - hi[axis]
            fills += [(axis, 0, m, lo[axis]), (axis, lo[axis] + m, lo[axis], hi[axis])]
    for name in names:
        a = np.asarray(jnp.asarray(rng.standard_normal(shape), dtype).astype(jnp.float32))
        if name in readers:
            a = _self_wrap(a, 0, lo[0], hi[0])  # the x sweep stays in the exchange
            b = a
            for axis in (1, 2):
                if "xyz"[axis] not in axes:  # a sweep that rides the wires came first
                    a = b = _self_wrap(b, axis, lo[axis], hi[axis])
            for axis in (1, 2):
                if "xyz"[axis] in axes:
                    b = _self_wrap(b, axis, lo[axis], hi[axis])
        else:
            b = a
        unswept.append(jnp.asarray(a, dtype))
        swept.append(jnp.asarray(b, dtype))
    origin = jnp.zeros((3,), jnp.int32)

    def run(raws, wrap_fills):
        return stream_plane_pass(
            kernel, names, raws, Dim3(*lo), Dim3(*hi), r, origin, Dim3(n, n, n),
            interpret=True, halo_readers=readers, rings=rings, writers=writers,
            wrap_fills=wrap_fills, **kw,
        )

    got = run(unswept, tuple(fills))
    want = run(swept, ())
    stale = run(unswept, ())
    for q, name in enumerate(names):
        if name not in writers:
            assert got[q] is unswept[q], name  # an input and nothing else: HBM keeps its shell
            continue
        a, b = (np.asarray(v.astype(jnp.float32)) for v in (got[q], want[q]))
        assert np.isfinite(b).all() and np.array_equal(a, b), name
    assert any(
        not np.array_equal(np.asarray(stale[q].astype(jnp.float32)),
                           np.asarray(want[q].astype(jnp.float32)))
        for q, name in enumerate(names) if name in writers
    )


# --- a time level renamed, not copied (ISSUE 36) ------------------------------
#
# An output that IS another writer's centre plane -- a leapfrog scheme's
# ``u_prev <- u`` -- is not written: the pass lands ``u``'s new value in
# ``u_prev``'s block and the step hands ``u``'s old array on under the name
# ``u_prev`` (ops/stream_plan.py trace_plane_kernel, stream_plane_pass(renames=)).
# The rule reads the kernel's jaxpr and fails closed on anything but the
# centre invar itself.


def _leapfrog(second):
    """``u`` takes a star of itself against the older level ``v``; ``second``
    says what ``v`` takes (and may add or drop outputs)."""

    def kernel(views, info):
        u, v = views["u"], views["v"]
        out = {"u": _star(u, 1) - 0.5 * v.center()}
        out.update(second(views, info))
        return out

    return kernel


def _masked_copy(views, info):
    import jax.numpy as jnp

    return {"v": jnp.where(info.coords()[1] >= 2, views["u"].center(), 0.0)}


def _acoustic_case():
    from stencil_tpu.models.acoustic import QUANTITIES, AcousticWave

    sim = AcousticWave(24, 24, 24, nbl=4, interpret=True, seed_words=None)
    return sim._kernel, QUANTITIES, 4


def _elastic_case(stage):
    from stencil_tpu.models import elastic_reference as eref
    from stencil_tpu.models.elastic import ElasticWave

    sim = ElasticWave(24, 24, 24, nbl=4, interpret=True)
    return getattr(sim, stage), eref.QUANTITIES, 4


def _two_levels(second, names=("u", "v")):
    """() -> (kernel, names, r) of a ``_leapfrog`` over ``names`` at radius 1."""
    return lambda: (_leapfrog(second), list(names), 1)


_UVW = ("u", "v", "w")
_RENAME_CASES = [
    # () -> (kernel, names, r), storage dtypes (None: the planes'), renames
    pytest.param(_acoustic_case, None, (("u_prev", "u"),), id="acoustic"),
    pytest.param(_two_levels(lambda vs, _: {"v": vs["u"].center()}), None, (("v", "u"),),
                 id="leapfrog"),
    pytest.param(_two_levels(lambda vs, _: {"v": vs["u"].sh(0, 0, 0)}), None, (("v", "u"),),
                 id="leapfrog-sh000"),
    pytest.param(_two_levels(lambda vs, _: {"v": vs["u"].center() + 0.0}), None, (),
                 id="plus-zero"),
    pytest.param(_two_levels(_masked_copy), None, (), id="masked-copy"),
    pytest.param(_two_levels(lambda vs, _: {"v": vs["u"].sh(1, 0, 0)}), None, (),
                 id="off-centre-plane"),
    # ``u`` keeps naming its array: nothing is free to take another name
    pytest.param(lambda: (lambda vs, _: {"v": vs["u"].center()}, ["u", "v"], 1), None, (),
                 id="source-not-written"),
    pytest.param(_two_levels(lambda vs, _: {"v": vs["u"].center()}), ("float32", "bfloat16"), (),
                 id="mismatched-storage"),
    pytest.param(lambda: _elastic_case("_stage_v"), None, (), id="elastic-stage-v"),
    pytest.param(lambda: _elastic_case("_stage_t"), None, (), id="elastic-stage-t"),
    # w <- v <- u: ``v`` has no value of its own, so ``w <- v`` stays a copy
    pytest.param(_two_levels(lambda vs, _: {"v": vs["u"].center(), "w": vs["v"].center()}, _UVW),
                 None, (("v", "u"),), id="chain"),
    pytest.param(lambda: (lambda vs, _: {"u": vs["v"].center(), "v": vs["u"].center()},
                          ["u", "v"], 1), None, (), id="swap"),
    pytest.param(_two_levels(lambda vs, _: {"v": vs["u"].center(), "w": vs["u"].center()}, _UVW),
                 None, (("v", "u"),), id="source-claimed-once"),
    pytest.param(_two_levels(lambda vs, _: {"v": vs["v"].center()}), None, (), id="itself"),
]


@pytest.mark.parametrize("case,storage,renames", _RENAME_CASES)
def test_the_footprint_trace_reports_a_rename_and_nothing_like_one(case, storage, renames):
    import jax
    import jax.numpy as jnp

    from stencil_tpu.core.dim3 import Dim3

    kernel, names, r = case()
    plane = jax.ShapeDtypeStruct((16 + 2 * r, 16 + 2 * r), jnp.float32)
    trace = sp.trace_plane_kernel(
        kernel, list(names), [plane] * len(names), r, Dim3(16, 16, 16), True, storage
    )
    assert trace.closed is not None and trace.renames == renames, trace


def test_a_trace_that_raises_renames_nothing():
    import jax
    import jax.numpy as jnp

    from stencil_tpu.core.dim3 import Dim3

    plane = jax.ShapeDtypeStruct((18, 18), jnp.float32)
    trace = sp.trace_plane_kernel(_raising_kernel, ["u", "v"], [plane] * 2, 1, Dim3(16, 16, 16))
    assert trace.closed is None and trace.renames == ()


def _renames_off(monkeypatch):
    """The rule off: the parent's program, every returned quantity written."""
    import dataclasses

    real = sp.trace_plane_kernel
    monkeypatch.setattr(
        sp, "trace_plane_kernel", lambda *a: dataclasses.replace(real(*a), renames=())
    )


@pytest.mark.parametrize("alias", [False, True], ids=["fresh", "aliased"])
def test_a_renaming_pass_swaps_two_handles_and_writes_one_array(alias):
    """``stream_plane_pass(renames=)`` itself, ``coupled_kernel``'s four
    quantities: TWO outputs where the plain pass has three (``v`` is not
    written), ``u``'s output aliased onto raw ``v`` (operand ``1 + 1``),
    ``v`` handed back as the very array ``u`` went in as, and every quantity
    equal to the plain pass's -- ``u`` and ``d`` on every raw cell, ``v`` on
    its interior (its shell is now ``u``'s)."""
    import jax
    import jax.numpy as jnp

    from test_stream import coupled_kernel

    from stencil_tpu.core.dim3 import Dim3
    from stencil_tpu.ops.stream_pass import stream_plane_pass

    r, n = 2, 12
    names = ["u", "v", "c", "d"]
    rng = np.random.default_rng(36)
    raws = [jnp.asarray(rng.standard_normal((n + 2 * r,) * 3), jnp.float32) for _ in names]
    origin = jnp.zeros((3,), jnp.int32)

    def run(writers, renames):
        def fn(origin, *raws):
            return stream_plane_pass(
                coupled_kernel(r), names, list(raws), Dim3(r, r, r), Dim3(r, r, r), r,
                origin, Dim3(n, n, n), alias=alias, interpret=True, writers=writers,
                rings=("u",), renames=renames,
            )

        (call,) = _pass_calls(jax.make_jaxpr(fn)(origin, *raws))
        return call, fn(origin, *raws)

    call, got = run(("u", "d"), (("v", "u"),))
    plain_call, want = run(("u", "v", "d"), ())
    assert (len(call.outvars), len(plain_call.outvars)) == (2, 3)
    assert _alias_pairs(call) == (((2, 0), (4, 1)) if alias else ())
    assert _alias_pairs(plain_call) == (((1, 0), (2, 1), (4, 2)) if alias else ())
    assert got[1] is raws[0] and got[2] is raws[2]
    inner = (slice(r, -r),) * 3
    for name, a, b in zip(names, got, want):
        cells = inner if name == "v" else ...
        assert np.array_equal(np.asarray(a)[cells], np.asarray(b)[cells]), name
    assert np.any(np.asarray(got[0]) != np.asarray(raws[0]))


def test_a_rename_the_pass_cannot_make_is_refused():
    """The source must be a writer and the target must not be one: the pass
    asserts what ``plan_plane_passes`` guarantees."""
    with pytest.raises(AssertionError):
        _one_pass(lambda vs, _: {"a": vs["a"].center() * 0.5}, writers=("a",),
                  renames=(("a", "c"),))


_RENAME_STEP_CASES = [
    # id, devices, plan overrides, renamed, steps of the one dispatch
    pytest.param(1, {}, ("v",), 4, id="in-place-even"),
    pytest.param(1, {}, ("v",), 3, id="in-place-odd"),
    pytest.param(8, {}, ("v",), 5, id="mesh-2x2x2-odd"),
    pytest.param(1, {"alias": False, "alias_forced": True}, (), 3, id="fresh-output"),
    pytest.param(8, SPLIT, (), 3, id="split"),
]


@pytest.mark.parametrize("n_dev,plan_kw,renamed,steps", _RENAME_STEP_CASES)
def test_plane_route_renames_a_time_level_where_its_passes_run_in_place(
    n_dev, plan_kw, renamed, steps, monkeypatch
):
    """The step as built, rule on against rule off: ``plan["renamed"]`` and
    the passes' ``renames`` say where it engaged (in place on the default
    schedule; not with fresh outputs, not under ``overlap="split"``), the
    renamed quantity leaves ``writers``, and every interior cell of every
    quantity is bitwise the same after an even and after an odd number of
    steps -- as is every raw cell once both sides have exchanged."""
    from test_stream import coupled_kernel

    names, r = ["u", "v", "c", "d"], 2

    def run():
        dd, hs = _plane_domain(names, r, n_dev)
        step, plan = _plane_step(dd, coupled_kernel(r), r, plan_kw)
        dd.run_step(step, steps)
        fields = [dd.quantity_to_host(h) for h in hs]
        dd.exchange()
        return fields, {h.name: np.asarray(dd._curr[h.name]) for h in hs}, plan

    fields, raws, plan = run()
    for key, want in plan_kw.items():
        assert plan[key] == want, plan
    assert plan["renamed"] == renamed, plan
    assert plan["writers"] == tuple(nm for nm in ("u", "v", "d") if nm not in renamed), plan
    (p,) = plan["stages"][0]["passes"]
    assert p["renames"] == ((("v", "u"),) if renamed else ()) and "v" in p["reads"], p
    _renames_off(monkeypatch)
    fields_off, raws_off, plan_off = run()
    assert plan_off["renamed"] == () and plan_off["writers"] == ("u", "v", "d"), plan_off
    for i, name in enumerate(names):
        assert np.array_equal(fields[i], fields_off[i]), name
        assert np.array_equal(raws[name], raws_off[name]), name


def test_a_chain_renames_its_head_and_copies_its_tail():
    """``w <- v <- u``: ``v <- u`` is a rename, ``w <- v`` the copy it was (it
    is read from ``v``'s block before ``u``'s new value is flushed onto it),
    and the step equals the XLA engine on every quantity after 1, 2 and 3
    steps."""
    kernel = _leapfrog(lambda vs, _: {"v": vs["u"].center(), "w": vs["v"].center()})
    names = ["u", "v", "w"]
    dd, hs = _plane_domain(names, 1, 1)
    step, plan = _plane_step(dd, kernel, 1, {})
    assert plan["renamed"] == ("v",) and plan["writers"] == ("u", "w"), plan
    ref_dd, ref_hs = _plane_domain(names, 1, 1)
    ref = ref_dd.make_step(kernel, overlap=False)
    for steps in (1, 2, 3):
        dd.run_step(step, steps)
        ref_dd.run_step(ref, steps)
        for h, g in zip(hs, ref_hs):
            assert np.array_equal(dd.quantity_to_host(h), ref_dd.quantity_to_host(g)), (h.name, steps)


def test_a_stage_cut_into_passes_renames_pass_by_pass(monkeypatch):
    """The rule applies pass by pass (ISSUE 57; it needed ONE pass that holds
    both quantities before): the renamed output is written by no pass, so under
    a VMEM budget one byte short of the renaming pass the stage does not fall
    back to copying ``v`` -- nothing is left to cut, and the planner says what
    does not fit.  With a second output that reads other quantities the stage
    is cut in two, and the pass that computes ``u`` lands it in ``v``'s block."""

    def kernel(views, info):
        return {"v": views["u"].center(), "u": _star(views["u"], 2) * views["c"].center()}

    names, r = ["v", "u", "c"], 2
    dd, _ = _plane_domain(names, r, 1)
    _, whole = _plane_step(dd, kernel, r, {})
    (p,) = whole["stages"][0]["passes"]
    assert p["renames"] == (("v", "u"),) and whole["writers"] == ("u",), whole
    monkeypatch.setenv("STENCIL_VMEM_LIMIT_BYTES", str(p["vmem_bytes"] - 1))
    dd, _ = _plane_domain(names, r, 1)
    with pytest.raises(ValueError, match=(
            r"writes \('u',\) \(into the blocks of \('v',\): renamed\) reads 3 quantities "
            r"\('v', 'u', 'c'\).*fits no pass")):
        _plane_step(dd, kernel, r, {})
    monkeypatch.delenv("STENCIL_VMEM_LIMIT_BYTES")

    def two(views, info):
        return {**kernel(views, info), "w": 2.0 * views["w"].center() + views["c"].sh(0, 1, 0)}

    names = ["v", "u", "c", "w"]
    dd, _ = _plane_domain(names, r, 1)
    _, joint = _plane_step(dd, two, r, {})
    (p,) = joint["stages"][0]["passes"]
    assert p["writes"] == ("u", "w") and p["renames"] == (("v", "u"),), joint
    monkeypatch.setenv("STENCIL_VMEM_LIMIT_BYTES", str(p["vmem_bytes"] - 1))
    dd, _ = _plane_domain(names, r, 1)
    _, cut = _plane_step(dd, two, r, {})
    passes = cut["stages"][0]["passes"]
    assert [(q["writes"], q["renames"]) for q in passes] == [(("u",), (("v", "u"),)), (("w",), ())], passes
    assert cut["renamed"] == ("v",) and cut["writers"] == ("u", "w") and cut["steps_per_trip"] == 2, cut


def test_the_carry_period_is_the_order_of_the_steps_permutation():
    from stencil_tpu.ops.stream_plan import _carry_period

    def stages(*passes):
        return tuple({"passes": tuple({"renames": r} for r in st)} for st in passes)

    names = list("abcd")
    assert _carry_period(names, stages([()])) == 1
    assert _carry_period(names, stages([(("b", "a"),)])) == 2
    assert _carry_period(names, stages([(("b", "a"), ("d", "c"))])) == 2
    # two stages that pass one block on: a cycle of three
    assert _carry_period(names, stages([(("b", "a"),)], [(("c", "b"),)])) == 3
