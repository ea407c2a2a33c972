"""The z-slab wavefront step carries the domain's raw blocks (ISSUE 41).

``stream_wavefront_pass`` in its z-slab form works on planes that are whole
lane tiles.  Until ISSUE 41 the step made them in HBM: a ``jnp.pad`` of every
quantity to ``lane_pad_width(Zr)`` lanes at the start of a dispatch and a cut
back at its end (13.5% of the astaroth cell).  Now the pass streams the raw
``(Xr, Yr, Zr)`` block through ``(1, Yr, Zp)`` boundary blocks and zeroes the
dead lanes in VMEM.  These tests hold that step, bitwise and on EVERY raw
cell, to a reference that pads in HBM, runs the plain pass on ``Zp``-wide
arrays and cuts; hold the traced program to no ``pad`` and no whole-block
``slice`` / ``copy`` / ``concatenate``; and hold a step whose ``Zr`` is whole
lane tiles already to the program the parent commit traced.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from stencil_tpu import telemetry
from stencil_tpu.analysis import jaxpr as jx
from stencil_tpu.core.radius import Radius
from stencil_tpu.domain import DistributedDomain
from stencil_tpu.ops import stream as sm
from stencil_tpu.ops import stream_pass as spass
from stencil_tpu.telemetry import names as tm

NAMES = ("a", "b")
S = 3  # the shell: the step plans depth 3, ``stream_depth`` caps it at 2


def coupled_kernel(views, info):
    """Two quantities in one joint pass, the second reading the first, every
    axis read off-centre, bounded.  Sums and one division only: nothing the
    CPU compiler could contract into a fused multiply-add in one program and
    not in the other."""
    a, b = views["a"], views["b"]
    new_a = (a.sh(-1, 0, 0) + a.sh(1, 0, 0) + a.sh(0, -1, 0) + a.sh(0, 1, 0)
             + a.sh(0, 0, -1) + a.sh(0, 0, 1)) / 6.0
    new_b = (b.sh(-1, 0, 0) + b.sh(1, 0, 0) + b.sh(0, -1, 0) + b.sh(0, 1, 0)
             + b.sh(0, 0, -1) + a.sh(0, 0, 1)) / 6.0
    return {"a": new_a, "b": new_b}


def _domain(mesh, z, storage="native"):
    """8 x 8 x ``z`` cells a shard under a 3-wide shell, two f32 quantities."""
    n = mesh[0] * mesh[1] * mesh[2]
    dd = DistributedDomain(8 * mesh[0], 8 * mesh[1], z * mesh[2])
    dd.set_radius(Radius.constant(S))
    dd.set_devices(jax.devices()[:n])
    dd.set_partition(*mesh)
    dd.set_storage(storage)
    hs = [dd.add_data(nm, dtype=jnp.float32) for nm in NAMES]
    dd.realize()
    for i, h in enumerate(hs):
        dd.init_by_coords(h, lambda x, y, z_, i=i: jnp.sin(0.37 * (x + 2 * y + 3 * z_) + i))
    return dd


def _step(dd, m):
    step = dd.make_step(coupled_kernel, engine="stream", x_radius=1, interpret=True,
                        stream_path="wavefront", stream_depth=m)
    plan = step._stream_plan
    assert (plan["route"], plan["m"], plan["z_slabs"]) == ("wavefront", m, True), plan
    return step


def _raw(dd):
    return [np.asarray(dd._curr[nm].astype(jnp.float32)) for nm in NAMES]


def hbm_padded_pass(real):
    """The z-slab pass as the step ran it before ISSUE 41, from the plain
    pass alone: every block padded to ``Zp`` lanes IN HBM, the slab's columns
    set into its z halo there, the plain ``stream_wavefront_pass`` over the
    ``Zp``-wide arrays, the result cut back to ``Zr`` lanes and the outgoing
    slabs cut out of it.  The planes the pass does not write (the last ``m``)
    are the block's own where the pass runs in place."""

    def run(kernel, names, raws, m, s, origin, gsize, z_slabs=None, alias=False, **kw):
        if z_slabs is None:
            return real(kernel, names, raws, m, s, origin, gsize, alias=alias, **kw)
        Xr, Yr, Zr = raws[0].shape
        pad = spass.lane_pad_width(Zr) - Zr
        padded = []
        for b, zs in zip(raws, z_slabs):
            zst = jnp.swapaxes(zs, 1, 2)  # (Xr, Yr, 2s)
            p = jnp.pad(b, ((0, 0), (0, 0), (0, pad)))
            padded.append(p.at[:, :, :s].set(zst[:, :, :s]).at[:, :, Zr - s : Zr].set(zst[:, :, s:]))
        outs, _ = real(kernel, names, padded, m, s, origin, gsize, alias=False, **kw)
        res, zouts = [], []
        for b, o in zip(raws, outs):
            o = o[:, :, :Zr]
            zouts.append(jnp.concatenate(
                [jnp.swapaxes(o[:, :, Zr - 2 * s : Zr - s], 1, 2), jnp.swapaxes(o[:, :, s : 2 * s], 1, 2)],
                axis=1,
            ))
            res.append(jnp.concatenate([o[: Xr - m], b[Xr - m :]]) if alias else o)
        return res, zouts

    return run


GEOMETRIES = {"1x1x1": ((1, 1, 1), 10), "2x2x1": ((2, 2, 1), 10), "2x2x1-straddles-128": ((2, 2, 1), 124)}
MACROS = {"m3-rem1": (3, 7), "m3-rem2": (3, 8), "m2-rem1": (2, 5), "m2-whole": (2, 4)}
# in place (what astaroth's eight quantities run) on every combination, fresh
# results (the static rule under four quantities) on a few
CASES = [(g, st, mc, True) for g in GEOMETRIES for st in ("native", "bf16") for mc in MACROS] + [
    ("2x2x1", "native", "m3-rem1", False), ("2x2x1", "bf16", "m2-rem1", False),
    ("1x1x1", "native", "m3-rem2", False), ("2x2x1-straddles-128", "bf16", "m2-whole", False),
]


@pytest.mark.parametrize("geometry,storage,macros,alias", CASES,
                         ids=["-".join(c[:3]) + ("-in-place" if c[3] else "-fresh") for c in CASES])
def test_the_raw_block_step_is_bitwise_the_hbm_padded_one(geometry, storage, macros, alias, monkeypatch):
    """Every raw cell of both quantities -- shell included -- after one
    dispatch and after a second one behind it: whole macros of depth ``m`` and
    a remainder macro of depth 1 or 2, f32 and bf16 storage under
    ``f32_accumulate``, one shard and mesh [2,2,1], a 16-lane raw block (one
    lane tile in VMEM) and a 130-lane one (the hi halo straddles lane 128),
    the pass in place and writing fresh results."""
    (mesh, z), (m, steps) = GEOMETRIES[geometry], MACROS[macros]
    monkeypatch.setenv("STENCIL_STREAM_ALIAS", "1" if alias else "0")

    def two_dispatches():
        dd = _domain(mesh, z, storage)
        step = _step(dd, m)
        assert step._span_args()["lane_pad"] == "vmem" and dd.local_spec().raw_size().z % 128
        assert step._stream_plan["alias"] is alias
        states = [_raw(dd)]
        for _ in range(2):
            dd.run_step(step, steps)
            states.append(_raw(dd))
        return states

    ours = two_dispatches()  # traced before the reference is patched in
    monkeypatch.setattr(sm, "stream_wavefront_pass", hbm_padded_pass(spass.stream_wavefront_pass))
    ref = two_dispatches()
    for got, want in zip(ours, ref):
        for a, b in zip(got, want):
            np.testing.assert_array_equal(a, b)
    # no dead lane reached a stored z-shell lane (the x-shell planes are the
    # pass's own to leave unwritten or to fill from its uninitialised rings:
    # the interpreter's NaN on both sides)
    for a in ours[-1]:
        shards = a.reshape(mesh[0], a.shape[0] // mesh[0], *a.shape[1:])
        assert np.isfinite(shards[:, S:-S]).all()
    assert not np.array_equal(ours[-1][0], ours[0][0])


def _whole_block_ops(closed, raw):
    """The equations outside the Pallas calls that pad anything, or that cut,
    copy or join an array as large as a raw block."""
    cells = raw.x * raw.y * raw.z
    found = []
    for e in jx.iter_eqns(closed):
        name = e.primitive.name
        if name == "pad":
            found.append(name)
        elif name in ("slice", "dynamic_slice", "copy", "copy_p", "concatenate", "gather"):
            if any(int(np.prod(v.aval.shape)) >= cells for v in e.outvars):
                found.append(f"{name} -> {e.outvars[0].aval.str_short()}")
    return found


@pytest.mark.parametrize("mesh", [(1, 1, 1), (2, 2, 1)], ids=["1x1x1", "2x2x1"])
def test_the_traced_step_pads_and_cuts_no_block(mesh, monkeypatch):
    """The guard on the program (as ``tests/test_jacobi_macro_loop.py`` guards
    the copy ISSUE 38 removed): outside the Pallas calls the traced step holds
    no ``pad`` and no whole-block ``slice`` / ``copy`` / ``concatenate`` -- and
    the HBM-padded reference, traced the same way, shows the guard fires."""
    monkeypatch.setenv("STENCIL_STREAM_ALIAS", "1")  # in place, as astaroth's eight
    dd = _domain(mesh, 10)
    raw = dd.local_spec().raw_size()
    closed = jax.make_jaxpr(_step(dd, 3), static_argnums=1)(dd._curr, 7)
    assert _whole_block_ops(closed, raw) == []
    calls = [e for e in jx.iter_eqns(closed) if e.primitive.name == "pallas_call"
             and e.params.get("name") == tm.KERNEL_STREAM_WAVEFRONT_PASS]
    assert calls
    for e in calls:  # the raw block goes in and comes out: 16 lanes, blocks of 128
        gm = e.params["grid_mapping"]
        blocks = [(tuple(bm.array_aval.shape), tuple(int(getattr(b, "block_size", 1)) for b in bm.block_shape))
                  for bm in gm.block_mappings]
        assert blocks.count(((raw.x, raw.y, raw.z), (1, raw.y, 128))) == 2 * len(NAMES)
        assert {pair[1] for pair in e.params["input_output_aliases"]} == set(range(len(NAMES)))
    monkeypatch.setattr(sm, "stream_wavefront_pass", hbm_padded_pass(spass.stream_wavefront_pass))
    padded = jax.make_jaxpr(_step(dd, 3), static_argnums=1)(dd._curr, 7)
    assert any(op == "pad" for op in _whole_block_ops(padded, raw))


def _is_noop_pad(eqn) -> bool:
    return eqn.primitive.name == "pad" and all(not any(w) for w in eqn.params["padding_config"])


def _kernels_and_outer_ops(closed) -> str:
    """A hash of the program that is blind to one thing: a ``jnp.pad`` by
    nothing.  Every Pallas call as ``program_fingerprint`` prints it (name,
    scope, grid, blocks, index maps, aliases) with its body, then every
    equation outside them in program order -- primitive, scope and result
    shapes -- less the ``jit(_pad)`` equations that hold a ``pad`` whose every
    width is zero, with what they hold."""
    import hashlib

    import program_fingerprint as pf

    parts = []
    for e in pf._pallas_calls(closed.jaxpr):
        parts += [pf._pallas_detail(e), str(e.params["jaxpr"].pretty_print(source_info=False))]
    eqns = list(jx.iter_eqns(closed))
    skip = set()
    for e in eqns:
        inner = [i for sub in jx.eqn_subjaxprs(e) for i in sub.eqns]
        if e.params.get("name") == "_pad" and any(_is_noop_pad(i) for i in inner):
            skip |= {id(e)} | {id(i) for i in inner}
    for e in eqns:
        if id(e) not in skip:
            outs = ",".join(v.aval.str_short() for v in e.outvars)
            parts.append(f"{e.primitive.name} {e.source_info.name_stack} -> {outs}")
    text = pf._SPACE.sub(" ", pf._ADDR.sub("0x", pf._PATH.sub("<src>", "\n".join(parts))))
    return hashlib.sha256(text.encode()).hexdigest()


#: ``_kernels_and_outer_ops`` of the step below as the PARENT commit (11b2c74,
#: before ISSUE 41) traces it, recorded there with this very builder.  Its
#: whole-program fingerprint cannot hold: the parent traced ``jnp.pad(b, 0)``
#: as one zero-width ``pad`` equation a quantity (XLA folds it away), which
#: went with the pad, and every name behind it in the printed jaxpr shifts.
#: Re-recorded in PR 50 (it read 73383a41...9e53dbf from ISSUE 41 to PR 49): the
#: step's shell exchange on mesh [2,2,1] sweeps x and y JOINTLY since then
#: (``ops/exchange.py _sweep_groups``: both axes' faces sent at once, two corner
#: relays) -- equations OUTSIDE the kernels, which this hash holds too; the
#: kernels' part is as it was (hashed apart on both trees: 0b2b7b36...13feb226;
#: ``tests/data/program_fingerprints.json`` also tells
#: the two apart: the one-chip wavefront programs hold).
PARENT_WHOLE_TILES_PROGRAM = "f945b1d7d34bdd5e1c8ad5cc1007676cb1f7c7d1f4814f0a83af245bb9bba2be"


def _whole_tiles_program():
    """A z-slab wavefront step whose raw z extent is whole lane tiles: 122
    cells under a 3-wide shell, 128 lanes."""
    dd = _domain((2, 2, 1), 122)
    assert dd.local_spec().raw_size().z == 128
    step = _step(dd, 3)
    return step, jax.make_jaxpr(step, static_argnums=1)(dd._curr, 7)


def test_whole_lane_tiles_keep_the_parents_program():
    """``Zr % 128 == 0``: nothing to pad, nothing changes -- the traced program
    is the one the parent commit traced, kernel for kernel (bodies, grids,
    exact ``(1, Yr, Zr)`` blocks, aliases) and equation for equation outside
    them, less the parent's zero-width ``pad``; the plan and the span say
    ``lane_pad: "none"``."""
    step, closed = _whole_tiles_program()
    assert step._stream_plan["lane_pad"] == "none"
    assert step._span_args()["lane_pad"] == "none"
    assert not any(e.primitive.name == "pad" for e in jx.iter_eqns(closed))
    assert _kernels_and_outer_ops(closed) == PARENT_WHOLE_TILES_PROGRAM


def test_the_astaroth_step_says_where_its_lane_padding_lives(monkeypatch):
    """The astaroth-shaped step (eight quantities, depth 3, one shard, a raw z
    extent off the lane tile): ``domain.step`` carries ``lane_pad: "vmem"``
    beside ``z_halo_patch`` -- registered under ``SPAN_STEP`` -- and a plan off
    the z-slab wavefront carries neither."""
    import inspect

    from stencil_tpu.models.astaroth import AstarothSim

    sim = AstarothSim(16, 16, 16, num_quantities=8, kernel_impl="pallas", schedule="wavefront",
                      interpret=True, devices=jax.devices()[:1])
    sim.realize()
    plan = sim._step._stream_plan
    assert (plan["route"], plan["m"], plan["z_slabs"], plan["lane_pad"]) == ("wavefront", 3, True, "vmem")
    seen = []
    real = telemetry.span

    def spy(name, *a, **kw):
        seen.append((name, kw))
        return real(name, *a, **kw)

    monkeypatch.setattr(telemetry, "span", spy)
    sim.step(3)
    (kw,) = [kw for name, kw in seen if name == tm.SPAN_STEP]
    assert (kw["lane_pad"], kw["z_halo_patch"], kw["route"]) == ("vmem", "tile", "wavefront")
    assert "lane_pad" in inspect.getsource(tm).split('SPAN_STEP = "domain.step"')[0]
    dd = _domain((1, 1, 1), 10)
    wrap = dd.make_step(coupled_kernel, engine="stream", x_radius=1, interpret=True)
    assert wrap._stream_plan["route"] == "wrap"
    assert "lane_pad" not in wrap._stream_plan and "lane_pad" not in wrap._span_args()
