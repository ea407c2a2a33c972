"""The plane pass on its aligned windows (ISSUES 45, 46, 48): where the pass
makes both in-plane halo fills itself the kernel works on the bare interior of
every plane (``window="interior"``), whole or in strips, and beside a split y
on the ``"interior-z"`` window -- each bitwise the raw-plane pass; what the
planner reads the window and the strip off; the step as built.  Split out of
``tests/test_plane_stencil.py`` (ISSUE 55: a file is one worker's, and stays
under 400 test-seconds, ROADMAP D13)."""

import numpy as np
import pytest

from test_plane_stencil import _self_wrap, _star

from stencil_tpu.ops import stream_plan as sp
from stencil_tpu.ops import stream_pass as spass


# --- the pass works on the aligned interior plane (ISSUE 45) ------------------
#
# Where the pass makes BOTH in-plane halo fills itself and each is the
# self-wrap of the block's whole interior, the halo of a plane is what a rotate
# of its interior wraps around to: ``stream_plane_pass(window="interior")`` works
# on the bare interior of every fetched plane (held rotated by the low shell
# widths: the block's aligned corner, no unaligned access), holds such planes in
# its rings and hands the kernel such windows (whole vector tiles: every
# in-plane shift one native rotate).  The kernel's values are the ones the
# raw-plane pass computes in the cells it keeps, in the same order.


def _diagonal_r3_kernel(views, info):
    """Radius 3 read at full distance on every axis and on the y-z, x-y and
    x-z diagonals (the MHD step's mixed differences); ``c`` along y alone (no
    ring: fetched lagged), ``p`` at the centre; the cell's own coordinates
    enter; ``p <- u`` as the centre plane itself.  Every weight is a power of
    two, so each product is exact and the sum rounds the same whether or not
    the CPU compiler contracts a multiply into the add behind it -- which it
    decides per fusion, and fuses planes of another shape otherwise (on the
    chip Mosaic contracts nothing)."""
    u, c = views["u"], views["c"]
    _, y, z = info.coords()
    acc = 0.25 * u.center() + 2.0**-10 * (y + 2 * z).astype(u.center().dtype)
    for k in (1, 2, 3):
        acc = acc + 2.0**-k * (
            (u.sh(k, 0, 0) - 0.5 * u.sh(-k, 0, 0))
            + (u.sh(0, k, k) - 0.25 * u.sh(0, -k, k))
            + (u.sh(k, -k, 0) - 0.125 * u.sh(-k, k, 0))
            + (u.sh(-k, 0, k) - 0.0625 * u.sh(k, 0, -k))
            + (0.5 * c.sh(0, k, 0) - c.sh(0, -k, 0))
        )
    return {"u": acc + views["p"].center(), "p": u.center()}


def _whole_self_wrap(n, lo, hi):
    return tuple(
        (a, d, s, w)
        for a in (1, 2)
        for d, s, w in ((0, n[a], lo[a]), (lo[a] + n[a], lo[a], hi[a]))
    )


@pytest.mark.parametrize("interior,lo,hi,fills,storage,want", [
    pytest.param((16, 128), (3, 3, 3), (3, 3, 3), "yz", "float32", "interior", id="whole-tiles"),
    pytest.param((256, 256), (3, 3, 3), (3, 3, 3), "yz", "float32", "interior", id="mhd-256"),
    pytest.param((8, 128), (1, 2, 3), (2, 1, 1), "yz", "float32", "interior", id="uneven-shell"),
    pytest.param((16, 100), (3, 3, 3), (3, 3, 3), "yz", "float32", "raw", id="ragged-lanes"),
    pytest.param((600, 600), (4, 4, 4), (4, 4, 4), "yz", "float32", "raw", id="acoustic-600"),
    pytest.param((12, 128), (3, 3, 3), (3, 3, 3), "yz", "float32", "raw", id="ragged-sublanes"),
    pytest.param((16, 128), (3, 3, 3), (3, 3, 3), "z", "float32", "raw", id="y-split"),
    pytest.param((16, 128), (3, 3, 3), (3, 3, 3), "", "float32", "raw", id="no-fill"),
    pytest.param((8, 128), (3, 3, 3), (3, 3, 3), "yz", "bfloat16", "raw", id="bf16-half-a-tile"),
    pytest.param((16, 128), (3, 3, 3), (3, 3, 3), "yz", "bfloat16", "interior", id="bf16-whole-tiles"),
    # beside a split y (ISSUE 48): the z self-wrap alone, whole tiles, and a y
    # shell that rides in the tiles -- no wider than a tile, no more rows than
    # the plane has tiles
    pytest.param((64, 128), (3, 3, 3), (3, 3, 3), "z", "float32", "interior-z", id="y-split-whole-tiles"),
    pytest.param((256, 256), (3, 3, 3), (3, 3, 3), "z", "float32", "interior-z", id="mhd-256x4"),
    pytest.param((64, 128), (3, 4, 3), (4, 3, 5), "z", "float32", "interior-z", id="y-split-uneven-shell"),
    pytest.param((128, 128), (3, 3, 3), (3, 3, 3), "z", "bfloat16", "interior-z", id="y-split-bf16"),
    pytest.param((64, 128), (3, 3, 3), (3, 3, 3), "y", "float32", "raw", id="z-split"),
    pytest.param((64, 100), (3, 3, 3), (3, 3, 3), "z", "float32", "raw", id="y-split-ragged-lanes"),
    pytest.param((600, 600), (4, 4, 4), (4, 4, 4), "z", "float32", "raw", id="acoustic-1200x4"),
    pytest.param((60, 128), (3, 3, 3), (3, 3, 3), "z", "float32", "raw", id="y-split-ragged-sublanes"),
    pytest.param((128, 128), (3, 5, 3), (3, 4, 3), "z", "float32", "raw", id="y-shell-wider-than-a-tile"),
    pytest.param((40, 128), (3, 3, 3), (3, 3, 3), "z", "float32", "raw", id="y-split-fewer-tiles-than-shell-rows"),
    pytest.param((64, 128), (3, 3, 3), (3, 3, 3), "z", "bfloat16", "raw", id="y-split-bf16-four-tiles"),
])
def test_the_plane_window_is_read_off_the_fills_and_the_shape(interior, lo, hi, fills, storage, want):
    import jax.numpy as jnp

    from stencil_tpu.core.dim3 import Dim3

    n = (0,) + interior
    given = tuple(f for f in _whole_self_wrap(n, lo, hi) if "xyz"[f[0]] in fills)
    plane = tuple(n[a] + lo[a] + hi[a] for a in (1, 2))
    dtypes = [jnp.float32, jnp.dtype(storage)]
    assert spass.plane_window_form(given, Dim3(*lo), Dim3(*hi), plane, dtypes) == want
    # a fill of fewer cells than the interior (a ragged last shard) is no self-wrap of it
    short = tuple((a, d - (d > 0), s - (d == 0), w) for a, d, s, w in given)
    assert spass.plane_window_form(short, Dim3(*lo), Dim3(*hi), plane, dtypes) == "raw"


@pytest.mark.parametrize("window,plane,storage,r,want", [
    pytest.param("interior", (256, 256), "float32", 3, 16, id="mhd-256"),  # 16 strips of two tiles
    pytest.param("interior", (64, 128), "float32", 3, 32, id="four-vregs-a-value"),
    pytest.param("interior", (32, 128), "float32", 3, 32, id="one-strip"),
    pytest.param("interior", (24, 128), "float32", 3, 24, id="three-tiles"),
    pytest.param("interior", (16, 128), "float32", 3, 0, id="fewer-tiles-than-the-read-distance"),
    pytest.param("interior", (16, 128), "float32", 2, 16, id="as-many"),
    pytest.param("interior", (256, 1024), "float32", 3, 8, id="too-wide-one-tile"),
    pytest.param("interior", (40, 128), "float32", 4, 8, id="five-tiles-one-at-a-time"),
    pytest.param("interior", (256, 256), "bfloat16", 3, 32, id="bf16-16-row-tiles"),
    pytest.param("interior", (32, 128), "bfloat16", 3, 0, id="bf16-two-tiles"),
    pytest.param("raw", (256, 256), "float32", 3, 0, id="raw-window"),
    pytest.param("interior-z", (256, 256), "float32", 3, 16, id="mhd-256x4"),  # the twin's strips
    pytest.param("interior-z", (64, 128), "float32", 3, 32, id="y-split-four-vregs-a-value"),
    pytest.param("interior-z", (16, 128), "float32", 3, 0, id="y-split-fewer-tiles-than-the-read-distance"),
])
def test_the_strip_is_read_off_the_window_and_the_plane(window, plane, storage, r, want):
    """``plane_strip_rows``: whole tiles of the stored dtype that divide the
    plane, four vregs a value where the plane allows; none off the two aligned
    windows, nor where a y shift would wrap around the tiles more than once."""
    import jax.numpy as jnp

    assert spass.plane_strip_rows(window, plane, [jnp.float32, jnp.dtype(storage)], r) == want


def test_the_shared_rotations_are_those_two_rows_read():
    """``shared_rotations``: a plane is rotated once a grid step where two or
    more ``dy`` read it at the same ``(dx, dz)`` -- the y-z diagonals beside
    the reads along z --, and nowhere else."""
    reads = [("u", (0, 0, 1)), ("u", (0, 2, 1)), ("u", (0, 0, -1)), ("u", (1, 0, 1)),
             ("u", (1, 0, 0)), ("u", (1, 1, 0)), ("c", (0, -1, 2)), ("c", (0, 1, 2)),
             ("c", (0, 1, 0)), ("c", (0, -1, 0))]
    assert sp.shared_rotations(reads) == (("u", 0, 1), ("c", 0, 2))
    assert sp.shared_rotations([]) == ()


def test_the_strip_form_fails_closed_by_name():
    """The strip form checks what it is told as the whole-plane form does, at
    trace time and by name (ISSUE 46): a read off-centre of a quantity outside
    ``halo_readers`` (it holds no margin plane: the rows a y offset would read
    are not there), a read along x of one outside ``rings``, a returned name
    outside ``writers`` -- never a stale read, never a dropped result."""
    import jax
    import jax.numpy as jnp

    from stencil_tpu.core.dim3 import Dim3

    r, n = 1, (4, 8, 128)
    blk = jax.ShapeDtypeStruct(tuple(m + 2 * r for m in n), jnp.float32)
    fills = _whole_self_wrap(n, (r,) * 3, (r,) * 3)

    def one_pass(kernel, **kw):
        def fn(origin, a, c):
            return spass.stream_plane_pass(
                kernel, ["a", "c"], [a, c], Dim3(r, r, r), Dim3(r, r, r), r, origin,
                Dim3(*n), interpret=True, wrap_fills=fills, window="interior", strip=8, **kw,
            )

        return jax.make_jaxpr(fn)(jax.ShapeDtypeStruct((3,), jnp.int32), blk, blk)

    def reads(dx, dy):
        return lambda views, info: {"a": _star(views["a"], 1) * views["c"].sh(dx, dy, 0)}

    one_pass(reads(0, 1), halo_readers=("a", "c"), rings=("a",), writers=("a",))  # told: fine
    with pytest.raises(ValueError, match=r"reads 'c' off-centre.*halo of 'c' was not exchanged"):
        one_pass(reads(0, 1), halo_readers=("a",), rings=("a",), writers=("a",))
    with pytest.raises(ValueError, match=r"reads 'c' off-centre along x.*no ring for 'c'"):
        one_pass(reads(1, 0), rings=("a",), writers=("a",))

    def returns_c(views, info):
        return {"a": _star(views["a"], 1), "c": views["c"].center() + 1.0}

    with pytest.raises(ValueError, match=r"returns 'c'.*'c' is not an output of the pass"):
        one_pass(returns_c, writers=("a",))


_R3 = ((3, 3, 3), (3, 3, 3))
_INTERIOR_WINDOW_CASES = [
    pytest.param({}, (), _R3, id="plain"),
    pytest.param({"alias": True}, (), _R3, id="in-place"),
    pytest.param({}, (("p", "u"),), _R3, id="renamed"),
    pytest.param({"alias": True}, (("p", "u"),), _R3, id="renamed-in-place"),
    pytest.param({"f32_accumulate": True}, (), _R3, id="bf16-storage"),
    pytest.param({"f32_accumulate": True, "alias": True}, (("p", "u"),), _R3,
                 id="bf16-renamed-in-place"),
    pytest.param({"alias": True}, (("p", "u"),), ((3, 4, 3), (4, 3, 5)), id="uneven-shell"),
]


@pytest.mark.parametrize("strips", [0, 1, 2], ids=["whole", "one-strip", "two-strips"])
@pytest.mark.parametrize("kw,renames,shell", _INTERIOR_WINDOW_CASES)
def test_the_interior_window_pass_is_bitwise_the_raw_plane_pass(kw, renames, shell, strips):
    """The same blocks through both windows: every interior cell of every
    quantity bitwise equal, the x-shell planes of an output that is a halo
    reader equal on every raw cell (their interiors pass through, their y / z
    shell is the same fill), and on the interior window the y / z shell of EVERY stored plane
    is the self-wrap of the plane as stored -- where the raw window keeps the
    fills of the plane as loaded.  A quantity the pass does not write comes
    back as the array that went in.

    ``strips``: the interior window's STRIP form (ISSUE 46), the kernel
    evaluated a strip at a time over the planes' TILES (tile ``k`` holds rows
    ``k, K + k, ...``: a y shift of a strip is another tile, read at its
    address) -- on a plane of four tiles that is ONE strip and one of TWO, so
    every shifted read goes through the margin tiles and their one-sublane
    wrap: bitwise the whole-plane interior-window pass on EVERY raw cell, and
    so the raw-window pass as above -- renames, the lagged ``c`` (read along
    y) and ``p`` (read at the centre), x-shell planes, the y-z, x-y and x-z
    diagonals at radius 3, the cells' own coordinates, f32 and bf16 storage;
    on the plane of two strips with the planes two ``dy`` share ROTATED ONCE a
    grid step and read at their tiles (``prerotated``), on the other every
    strip rotating its own."""
    import jax.numpy as jnp

    from stencil_tpu.core.dim3 import Dim3

    dtype = jnp.bfloat16 if kw.get("f32_accumulate") else jnp.float32
    rows = 4 * spass.sublane_tile([dtype])  # four tiles of rows a plane
    strip = rows // strips if strips else 0
    r, n, names = 3, (6, rows if strips else 16, 128), ["u", "c", "p"]
    lo, hi = shell
    shape = tuple(m + a + b for m, a, b in zip(n, lo, hi))
    rng = np.random.default_rng(45)
    raws = [jnp.asarray(rng.standard_normal(shape), dtype) for _ in names]
    fills = _whole_self_wrap(n, lo, hi)
    writers = ("u",) if renames else ("u", "p")

    # the planes rotated ONCE a grid step for all their readers, on the plane of
    # two strips: ``u``'s centre plane (its y-z diagonals share the shift), a
    # ring plane off-centre along x, and the lagged ``c``
    shared = (("u", 0, 1), ("u", 0, 2), ("u", 0, 3), ("u", -2, 2), ("c", 0, -1))

    def run(window, strip=0):
        return spass.stream_plane_pass(
            _diagonal_r3_kernel, names, raws, Dim3(*lo), Dim3(*hi), r,
            jnp.asarray([5, 0, 0], jnp.int32), Dim3(64, n[1], n[2]), interpret=True,
            halo_readers=("u", "c"), rings=("u",), writers=writers, wrap_fills=fills,
            renames=renames, window=window, strip=strip,
            prerotated=shared if strips == 2 and strip else (), **kw,
        )

    got, want = run("interior", strip), run("raw")
    inner = tuple(slice(a, a + m) for a, m in zip(lo, n))
    as_np = lambda v: np.asarray(v.astype(jnp.float32))  # noqa: E731
    if strip:
        for a, b in zip(got, run("interior")):  # the whole-plane form, every raw cell
            assert np.array_equal(as_np(a), as_np(b))
    for q, name in enumerate(names):
        a, b = as_np(got[q]), as_np(want[q])
        assert np.isfinite(b[inner]).all() and np.array_equal(a[inner], b[inner]), name
        if name == "c":
            assert got[q] is raws[q] and want[q] is raws[q]
        elif name == "p" and renames:
            assert got[q] is raws[0] and want[q] is raws[0]  # the handles swapped
        else:
            # a halo reader's x-shell planes are filled alike by both; one the
            # raw window does not fill keeps its loaded shell there
            yz = (slice(None),) + (inner[1:] if name == "p" else (slice(None),) * 2)
            for x_shell in (slice(0, lo[0]), slice(lo[0] + n[0], None)):
                assert np.array_equal(a[x_shell][yz], b[x_shell][yz]), name
            assert np.array_equal(
                a, _self_wrap(_self_wrap(a, 1, lo[1], hi[1]), 2, lo[2], hi[2])), name
            assert not np.array_equal(a, b), name  # the raw window's shell is the OLD plane's


@pytest.mark.parametrize("strips", [1, 4], ids=["one-strip", "four-strips"])
@pytest.mark.parametrize("kw,renames,shell", _INTERIOR_WINDOW_CASES)
def test_the_window_beside_a_split_y_is_bitwise_the_raw_plane_pass(kw, renames, shell, strips):
    """The pass beside a SPLIT y (ISSUE 48: ``window="interior-z"``, the strip
    form only): it is handed the z fills alone, and the y halo rows of every
    block hold data of their own -- a neighbour's rows, here random numbers
    that are NOT the plane's periodic wrap (the same blocks through a pass that
    wraps y give other interiors: a form that wrapped y would fail here).  The
    planes are tiles over RAW rows ``[0, Yi)``, the ``lo.y + hi.y`` margin
    tiles behind them carry the block's tail rows at their last sublane, and
    the strips run over tiles ``[lo.y, lo.y + K)``: every interior cell of
    every quantity bitwise the raw window's; the x-shell planes of an output
    that is a halo reader equal on every raw cell; the y halo ROWS of every
    stored plane the raw window's (the centre plane's, passed through); and
    the z shell of every stored plane the self-wrap of the plane as stored.
    Renames, the lagged ``c`` (read along y) and ``p`` (read at the centre),
    in place, f32 and bf16 storage, an uneven shell, the y-z / x-y / x-z
    diagonals at radius 3, the cells' own coordinates; on the plane of four
    strips (two tiles each, the benchmark's) with the planes two ``dy`` share
    rotated ONCE a grid step, margins and all (``prerotated``)."""
    import jax.numpy as jnp

    from stencil_tpu.core.dim3 import Dim3

    dtype = jnp.bfloat16 if kw.get("f32_accumulate") else jnp.float32
    rows = 8 * spass.sublane_tile([dtype])  # eight tiles of rows a plane
    r, n, names = 3, (6, rows, 128), ["u", "c", "p"]
    lo, hi = shell
    shape = tuple(m + a + b for m, a, b in zip(n, lo, hi))
    rng = np.random.default_rng(48)
    raws = [jnp.asarray(rng.standard_normal(shape), dtype) for _ in names]
    fills = tuple(f for f in _whole_self_wrap(n, lo, hi) if f[0] == 2)
    writers = ("u",) if renames else ("u", "p")
    shared = (("u", 0, 1), ("u", 0, 2), ("u", 0, 3), ("u", -2, 2), ("c", 0, -1))

    def run(window, strip=0, fills=fills):
        return spass.stream_plane_pass(
            _diagonal_r3_kernel, names, raws, Dim3(*lo), Dim3(*hi), r,
            jnp.asarray([5, 7, 0], jnp.int32), Dim3(64, 4 * n[1], n[2]), interpret=True,
            halo_readers=("u", "c"), rings=("u",), writers=writers, wrap_fills=fills,
            renames=renames, window=window, strip=strip,
            prerotated=shared if strips > 1 and strip else (), **kw,
        )

    assert spass.plane_window_form(
        fills, Dim3(*lo), Dim3(*hi), shape[1:], [dtype]) == "interior-z"
    got, want = run("interior-z", rows // strips), run("raw")
    wrapped = run("raw", fills=_whole_self_wrap(n, lo, hi))  # what wrapping y would give
    inner = tuple(slice(a, a + m) for a, m in zip(lo, n))
    as_np = lambda v: np.asarray(v.astype(jnp.float32))  # noqa: E731
    assert not np.array_equal(as_np(wrapped[0])[inner], as_np(want[0])[inner])
    for q, name in enumerate(names):
        a, b = as_np(got[q]), as_np(want[q])
        assert np.isfinite(b).all() and np.array_equal(a[inner], b[inner]), name
        if name == "c":
            assert got[q] is raws[q] and want[q] is raws[q]
        elif name == "p" and renames:
            assert got[q] is raws[0] and want[q] is raws[0]  # the handles swapped
        else:
            # the y halo rows pass through from the centre plane, as the raw
            # window's do (one the raw window does not z-fill keeps its loaded
            # z shell there: compare on the interior lanes)
            lanes = inner[2] if name == "p" else slice(None)
            for y_halo in (slice(0, lo[1]), slice(lo[1] + n[1], None)):
                assert np.array_equal(a[:, y_halo, lanes], b[:, y_halo, lanes]), name
            # a halo reader's x-shell planes are filled alike by both
            yz = (slice(None),) + (inner[1:] if name == "p" else (slice(None),) * 2)
            for x_shell in (slice(0, lo[0]), slice(lo[0] + n[0], None)):
                assert np.array_equal(a[x_shell][yz], b[x_shell][yz]), name
            assert np.array_equal(a, _self_wrap(a, 2, lo[2], hi[2])), name
            assert not np.array_equal(a, b), name  # the raw window's z shell is the OLD plane's


@pytest.mark.parametrize("extent,partition,window,strip", [
    pytest.param((8, 16, 128), (1, 1, 1), "interior", 0, id="whole-tiles"),
    pytest.param((8, 32, 128), (1, 1, 1), "interior", 32, id="whole-tiles-in-strips"),
    pytest.param((8, 16, 100), (1, 1, 1), "raw", 0, id="ragged-lanes"),
    pytest.param((8, 32, 128), (1, 2, 1), "raw", 0, id="y-split"),
    pytest.param((8, 128, 128), (1, 2, 1), "interior-z", 32, id="y-split-whole-tiles-in-strips"),
])
def test_a_plane_step_takes_the_interior_window_where_it_wraps_onto_itself(
    extent, partition, window, strip, monkeypatch
):
    """The step as built, blend kernels on as on the chip: ``plan["plane_
    window"]`` follows the fills and the block's shape; where it says "raw"
    the traced program IS the one built with the rule off (the parent's), and
    where it says "interior" the program differs -- its rings hold interior
    planes -- and every cell of every quantity is bitwise what the raw-plane
    program gives after an even and an odd count of steps.  ``inplace-order`` holds on the program either way.
    Beside a SPLIT y (ISSUE 48) a shard of whole tiles -- eight tiles of rows
    for its six-row y shell -- takes the ``"interior-z"`` window in strips: the
    y halo arrives over the mesh, the z halo is the rotates' wraparound, and
    every cell is bitwise the raw-plane program's all the same."""
    import jax

    from program_fingerprint import fingerprint_text
    from test_stream import _pass_wrap_domain

    from stencil_tpu import analysis
    from stencil_tpu.analysis import jaxpr as jx
    from stencil_tpu.ops import stream as sm

    monkeypatch.setenv("STENCIL_HALO_BLEND", "1")
    names, r = ["u", "c", "p"], 3
    if strip:  # (this kernel is a light one: the planner would keep it over whole planes)
        monkeypatch.setattr(sp, "_STRIP_MIN_OPS", 0)

    def run(execute=True):
        dd, hs = _pass_wrap_domain(names, r, partition, None, extent)
        request = sp.plan_stream(dd, r, "plane", False)
        plan = sp.resolve_stream_plan(dd, _diagonal_r3_kernel, r, request, True)
        step = sm._build_stream_step(dd, _diagonal_r3_kernel, r, plan, interpret=True)
        closed = jax.make_jaxpr(step, static_argnums=1)(dd._curr, 2)
        fields = []
        for steps in (2, 3) if execute else ():
            dd.run_step(step, steps)
            fields.append([dd.quantity_to_host(h) for h in hs])
        return plan, closed, fields

    plan, closed, fields = run()
    assert plan["plane_window"] == window, plan
    assert plan["pass_wrap_axes"] == ("yz" if partition == (1, 1, 1) else "z"), plan
    assert plan["renamed"] == ("p",) and plan["steps_per_trip"] == 2, plan
    assert sm.stream_span_args(plan, r, len(names))["plane_window"] == window
    art = analysis.ProgramArtifact(
        label="test:plane-window", kind="step", closed=closed, plan=dict(plan.plan),
        n_devices=int(np.prod(partition)),
    )
    for contract in ("inplace-order", "tiling-legal", "vmem-budget"):
        found = analysis.check(art, contract=contract)
        assert not found, "\n".join(f.render() for f in found)
    monkeypatch.setattr(sp, "plane_window_form", lambda *a: "raw")
    # (where the window says "raw" the two traced programs are ONE program, held
    # so right below: running it a second time would compare it with itself)
    plan_raw, closed_raw, fields_raw = run(execute=window != "raw")
    assert plan_raw["plane_window"] == "raw"
    same = fingerprint_text(closed) == fingerprint_text(closed_raw)
    assert same == (window == "raw")
    assert all(np.isfinite(x).all() for a in fields for x in a)
    rings = {  # the planes the passes' rings hold (the strip form: what the passes hold)
        tuple(sc.shape) if strip else tuple(sc.shape[-2:])
        for e in jx.iter_eqns(closed)
        if e.primitive.name == "pallas_call" and "stream_plane_pass" in str(e.params.get("name"))
        for sc in e.params["grid_mapping"].scratch_avals
    }
    raw = tuple(m + 2 * r for m in np.asarray(extent[1:]) // np.asarray(partition[1:]))
    # (the strip form, ISSUE 46: every quantity's plane as its four tiles between
    # three margin tiles a side -- ``u`` seven planes deep, ``c`` and ``p`` one
    # --, ``u``'s staging plane of tiles, and ``u`` rotated once a grid step
    # for the z shifts its y-z diagonals share)
    assert plan["plane_strip"] == strip, plan
    assert sm.stream_span_args(plan, r, len(names))["plane_strip"] == strip
    (p,) = plan["stages"][0]["passes"]
    assert set(p["prerotated"]) == ({("u", 0, k) for k in (1, 2, 3)} if strip else set())
    # (beside a split y: the shard's eight tiles before six margin tiles)
    held, plane_tiles = (14, 8) if window == "interior-z" else (10, 4)
    tiles = {(7, held, 8, 128), (1, held, 8, 128), (plane_tiles, 8, 128), (held, 8, 128)}
    assert rings == (tiles if strip else {tuple(extent[1:])} if window == "interior" else {raw}), rings
    assert (window == "raw") == (plan["plane_window"] == plan_raw["plane_window"])
    for a, b in zip(fields, fields_raw):
        for name, x, y in zip(names, a, b):
            assert np.isfinite(y).all() and np.array_equal(x, y), name
