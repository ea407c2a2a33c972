"""Program fingerprints: a hash of what a traced program IS, blind to where
its source lines sit.

The text that is hashed holds the whole closed jaxpr (every equation with
its name stack, so every ``exchange.*`` / ``step.*`` scope and every inner
jaxpr of a ``pallas_call``) and, for each ``pallas_call`` in program order,
what the pretty-printer abbreviates: the kernel name, the grid, every
``BlockSpec`` (block shape, array, index map, pipeline mode), the scratch
shapes, the aliases and the compiler parameters.  File paths, line numbers
and object addresses are dropped, so moving code moves no fingerprint; a
changed kernel body, grid, block, alias, name or scope does.

Goldens live in ``tests/data/program_fingerprints.json``; the one command
that rewrites them, after a change that is MEANT to change a program:

    python tests/program_fingerprint.py --write

(``tests/test_analysis.py::test_program_fingerprint`` holds every entry.)
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
GOLDEN_PATH = os.path.join(HERE, "data", "program_fingerprints.json")

#: raw steps a model's step program is traced for: two depth-3 macros and a
#: remainder on astaroth, a remainder under every temporal depth
MODEL_STEPS = 7

_PATH = re.compile(r"(?:[\w.\-]*/)+[\w.\-]+\.py(?::\d+)*")
_ADDR = re.compile(r"0x[0-9a-fA-F]+")
_SPACE = re.compile(r"\s+")
_SET = re.compile(r"frozenset\(\{([^{}]*)\}\)")  # printed in hash order: sort


def _sub_jaxprs(value):
    items = value if isinstance(value, (list, tuple)) else (value,)
    for item in items:
        inner = getattr(item, "jaxpr", item)  # ClosedJaxpr -> Jaxpr
        if hasattr(inner, "eqns"):
            yield inner


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for value in eqn.params.values():
            for inner in _sub_jaxprs(value):
                yield from _pallas_calls(inner)


def _pallas_detail(eqn) -> str:
    p = eqn.params
    gm = p["grid_mapping"]
    lines = [
        f"pallas_call name={p.get('name')} scope={eqn.source_info.name_stack}",
        f" grid={gm.grid} grid_names={gm.grid_names} vmapped={gm.vmapped_dims}",
        f" inputs={gm.num_inputs} outputs={gm.num_outputs} "
        f"index_operands={gm.num_index_operands} scratch={gm.scratch_avals}",
        f" aliases={p.get('input_output_aliases')} "
        f"compiler_params={p.get('compiler_params')} out={p.get('out_avals')}",
    ]
    for bm in gm.block_mappings:
        lines.append(
            f" block {bm.origin} {bm.block_shape} of {bm.array_aval} "
            f"pipeline={bm.pipeline_mode} transforms={bm.transforms} index_map="
            + str(bm.index_map_jaxpr.pretty_print(source_info=False))
        )
    return "\n".join(lines)


def fingerprint_text(closed) -> str:
    """The normalised text of a ClosedJaxpr that the fingerprint hashes."""
    jaxpr = closed.jaxpr
    parts = [str(jaxpr.pretty_print(source_info=False, name_stack=True))]
    parts += [_pallas_detail(e) for e in _pallas_calls(jaxpr)]
    text = _ADDR.sub("0x", _PATH.sub("<src>", "\n".join(parts)))
    text = _SET.sub(lambda m: "{" + ", ".join(sorted(m.group(1).split(", "))) + "}", text)
    return _SPACE.sub(" ", text)


def step_loop_and_text(closed) -> tuple:
    """A traced step program as (the trips of its ONE step loop, ``fingerprint_
    text`` with that number taken out): two dispatches that differ in their step
    count alone are the same text and another count -- held so, a test runs the
    longer one as dispatches of the shorter and lowers nothing more."""
    text = fingerprint_text(closed)
    (loop,) = re.finditer(r"\} length=(\d+) linear", text)
    return int(loop.group(1)), text[: loop.start(1)] + text[loop.end(1):]


def fingerprint(closed) -> str:
    return hashlib.sha256(fingerprint_text(closed).encode()).hexdigest()


# --- the five benchmark configurations, as their models build them ----------


def _trace_step(dd, step):
    """The program ``dd.run_step(step, MODEL_STEPS)`` dispatches: the current
    rung of a ladder-wrapped step, else the step itself."""
    import jax

    ladder = getattr(step, "_resilience", None)
    fn = ladder.built() if ladder is not None else step
    return jax.make_jaxpr(fn, static_argnums=1)(dd._curr, MODEL_STEPS)


def _jacobi_wrap():
    import jax

    from stencil_tpu.models.jacobi import Jacobi3D

    # depth 3: MODEL_STEPS is one trip of the macro loop (two macros: ISSUE
    # 38) and a remainder, so the fingerprint holds the loop
    m = Jacobi3D(16, 16, 16, kernel_impl="pallas", interpret=True,
                 devices=jax.devices()[:1], temporal_k=3)
    m.realize()
    assert m._pallas_path == "wrap" and m._wrap_k == 3, m._pallas_path
    return _trace_step(m.dd, m._step)


def _jacobi_zring():
    import jax

    from stencil_tpu.models.jacobi import Jacobi3D

    m = Jacobi3D(32, 32, 128, kernel_impl="pallas", interpret=True,
                 devices=jax.devices()[:4], temporal_k=3)  # a trip and a remainder, as above
    m.dd.set_partition(2, 2, 1)
    m.realize()
    assert m._pallas_path == "wavefront" and m._wavefront_z_ring and m._wavefront_m == 3
    # x and y are wired; on z the outgoing slab buffer is the incoming one (ISSUE 56:
    # ``program_fingerprint`` traces every model with the blend kernels on)
    args = m._step._span_args()
    assert (args["wired"], args["slab_wrap"]) == ("xy", "z"), args
    return _trace_step(m.dd, m._step)


def _astaroth():
    import jax

    from stencil_tpu.models.astaroth import AstarothSim

    s = AstarothSim(16, 16, 16, num_quantities=8, kernel_impl="pallas",
                    schedule="wavefront", interpret=True,
                    devices=jax.devices()[:1])
    s.realize()
    plan = s._step._stream_plan
    assert (plan["route"], plan["m"]) == ("wavefront", 3), plan
    # every slab extension is the self-wrap kernel, nothing is sent (ISSUE 56)
    args = s._step._span_args()
    assert (args["wired"], args["slab_wrap"]) == ("", "xyz"), args
    return _trace_step(s.dd, s._step)


def _weak_exchange():
    import jax

    from stencil_tpu import DistributedDomain, Radius

    dd = DistributedDomain(32, 32, 16)
    dd.set_radius(Radius.constant(3))
    dd.set_devices(jax.devices()[:4])
    dd.set_partition(2, 2, 1)
    for i in range(4):
        dd.add_data(f"q{i}")
    dd.realize()
    assert dd.exchange_route() == "direct"
    return jax.make_jaxpr(dd.make_exchange_route_fn("direct", donate=False))(dd._curr)


def _acoustic():
    import jax

    from stencil_tpu.models.acoustic import AcousticWave

    s = AcousticWave(24, 24, 24, nbl=4, interpret=True, devices=jax.devices()[:1])
    s.realize()
    args = s._step._span_args()
    assert (args["route"], args["x_radius"]) == ("plane", 4), args
    return _trace_step(s.dd, s._step)


def _acoustic_x4():
    import jax

    from stencil_tpu.models.acoustic import AcousticWave

    # x = y = 2z as the cell, the mesh the partitioner picks for it
    s = AcousticWave(48, 48, 24, nbl=4, interpret=True, devices=jax.devices()[:4])
    s.realize()
    args = s._step._span_args()
    assert tuple(s.dd.mesh_dim()) == (2, 2, 1), s.dd.mesh_dim()
    assert (args["route"], args["wired"], args["wrapped"]) == ("plane", "xy", "z"), args
    return _trace_step(s.dd, s._step)


def _elastic():
    import jax

    from stencil_tpu.models.elastic import ElasticWave

    s = ElasticWave(24, 24, 24, nbl=4, interpret=True, devices=jax.devices()[:1])
    s.realize()
    args = s._step._span_args()
    assert (args["route"], args["x_radius"], args["stages"]) == ("plane", 4, 2), args
    return _trace_step(s.dd, s._step)


def _lbm():
    import jax

    from stencil_tpu.models.lbm import LatticeBoltzmann

    # a box whose y-z interior is whole vector tiles, as the cell's 256 x 256
    # is, so that the dispatch carries the raw blocks at its edges (ISSUE 52);
    # it plans depth 8: cap it at the cell's 2, so that MODEL_STEPS is the edge
    # call that reads the raw blocks, one trip of the wrap route's macro loop
    # (two bare macros) and the remainder as the edge call that writes them
    s = LatticeBoltzmann(16, 8, 128, interpret=True, devices=jax.devices()[:1])
    s.realize()
    s._step = s.dd.make_step(s._kernel, engine="stream", x_radius=1, interpret=True,
                             stream_depth=2)
    args = s._step._span_args()
    assert (args["route"], args["macros_per_trip"], args["diagonal"]) == ("wrap", 2, 12), args
    assert s._step._stream_plan["m"] == 2 and args["edges"] == "raw", args
    return _trace_step(s.dd, s._step)


def _astaroth_mhd():
    import jax

    from stencil_tpu.models.astaroth_mhd import AstarothMHD

    # a box whose y-z interior is whole vector tiles, as the cell's 256 x 256
    # is: the passes take the interior window (ISSUE 45), the cell's program
    s = AstarothMHD(8, 32, 128, interpret=True, devices=jax.devices()[:1], seed_words=None)
    s.realize()
    args = s._step._span_args()
    assert (args["route"], args["stages"], args["renamed"], args["steps_per_trip"]) == (
        "plane", 3, "8/8/8", 2), args
    assert (args["wrapped"], args["plane_window"]) == ("yz", "interior"), args
    assert args["plane_strip"] == 32, args  # ... in its strip form (ISSUE 46: one strip of four tiles)
    return _trace_step(s.dd, s._step)


def _astaroth_mhd_x4():
    import math

    import jax

    from stencil_tpu.models.astaroth_mhd import AstarothMHD
    from stencil_tpu.models.astaroth_mhd_reference import MhdSetup

    # on mesh [2,2,1] as the cell, on a uniform cell (the box grown with the grid:
    # a side an axis), at a shard that IS the cell's program in small (ISSUE 48):
    # 8 x 64 x 128 -- an interior of whole tiles, eight tiles of rows for its
    # six-row y shell, as the cell's 256 x 256 --, so the passes take the aligned
    # window beside the split y, in strips
    shape = (16, 128, 128)
    setup = MhdSetup(shape, box=tuple(2.0 * math.pi * n / 128 for n in shape))
    s = AstarothMHD(*shape, setup=setup, interpret=True, devices=jax.devices()[:4], seed_words=None)
    s.dd.set_partition(2, 2, 1)
    s.realize()
    args = s._step._span_args()
    assert tuple(s.dd.mesh_dim()) == (2, 2, 1), s.dd.mesh_dim()
    assert (args["route"], args["wired"], args["wrapped"], args["wired_edges"]) == (
        "plane", "xy", "z", "xy"), args
    # y arrives over a wire, z is the rotates' wraparound: two strips of four tiles
    assert (args["plane_window"], args["plane_strip"]) == ("interior-z", 32), args
    return _trace_step(s.dd, s._step)


def _astaroth_mhd_512():
    """The MHD model under a VMEM budget that, like 104.9 MB at 512 x 512, holds
    no substep in one pass: every stage is two y-tiled passes that write their
    new fields into the ``*_prev`` blocks (ISSUE 57: renames pass by pass, the
    handles swapped at the stage's end, each pass under ``pass.<i>``) --
    7,128,768 B each by the model at 8 x 32 x 128 (tests/test_stage_passes.py)."""
    import jax

    from stencil_tpu.models.astaroth_mhd import AstarothMHD

    was = os.environ.get("STENCIL_VMEM_LIMIT_BYTES")
    os.environ["STENCIL_VMEM_LIMIT_BYTES"] = "7150000"
    try:
        s = AstarothMHD(8, 32, 128, interpret=True, devices=jax.devices()[:1], seed_words=None)
        s.realize()
        args = s._step._span_args()
        assert (args["route"], args["stages"], args["passes"], args["renamed"]) == (
            "plane", 3, 6, "8/8/8"), args
        assert args["passes_by_stage"] == "/".join(["w4-r12-g8-t32-y1-n4+w4-r12-g8-t32-y1-n4"] * 3), args
        assert (args["plane_window"], args["plane_lanes"], args["steps_per_trip"]) == ("interior", "raw", 2), args
        return _trace_step(s.dd, s._step)
    finally:
        if was is None:
            del os.environ["STENCIL_VMEM_LIMIT_BYTES"]
        else:
            os.environ["STENCIL_VMEM_LIMIT_BYTES"] = was


def _lbm_tiled(shape, mesh, budget, said):
    """A lattice-Boltzmann model under a VMEM budget that, like 104.9 MB at 512 x
    512, holds the nineteen planes in y tiles only: the pass is the tiled one,
    and a dispatch moves the lane tile behind the window at its two edges alone
    (ISSUES 54, 58: ``plane_lanes`` "window" -- three forms of the pass, the first
    call's raw in / window out, the loop's window in / window out, the last
    call's window in / raw out, behind ONE traced exchange)."""
    import jax

    from stencil_tpu.models.lbm import LatticeBoltzmann

    was = os.environ.get("STENCIL_VMEM_LIMIT_BYTES")
    os.environ["STENCIL_VMEM_LIMIT_BYTES"] = str(budget)
    try:
        s = LatticeBoltzmann(
            *shape, interpret=True, devices=jax.devices()[: mesh[0] * mesh[1]], seed_words=None)
        s.dd.set_partition(*mesh)
        s.realize()
        args = s._step._span_args()
        assert (args["route"], args["wired"], args["wrapped"]) == ("plane",) + said[:2], args
        assert args["wired_edges"] == said[0], args
        assert (args["plane_window"], args["tile_rows"], args["y_tiles"], args["aliased"]) == (
            said[2:] + (2, 19)), args
        assert args["plane_lanes"] == "window", args
        return _trace_step(s.dd, s._step)
    finally:
        if was is None:
            del os.environ["STENCIL_VMEM_LIMIT_BYTES"]
        else:
            os.environ["STENCIL_VMEM_LIMIT_BYTES"] = was


def _lbm_512():
    # one device, 6 x 64 x 256: y and z the pass's own fills, two y tiles of 32
    # rows (tests/test_plane_tiles.py ``_tiled_bytes(64, 256, 32)``)
    return _lbm_tiled((6, 64, 256), (1, 1, 1), 13371072, ("", "yz", "interior", 32))


def _lbm_x4():
    # on mesh [2,2,1] as the cell, at a shard that IS the cell's program in small
    # (ISSUE 53): 4 x 32 x 256 -- an interior of whole tiles beside a split y --,
    # 16 rows a y tile, two a plane (tests/test_lbm.py has the arithmetic), so
    # the pass is the tiled one on the "interior-z" window
    return _lbm_tiled((8, 64, 256), (2, 2, 1), 9291456, ("xy", "z", "interior-z", 16))


#: label -> builder of the ClosedJaxpr, at a CPU size under interpret
MODEL_PROGRAMS = {
    "model:jacobi3d-512/wrap": _jacobi_wrap,
    "model:jacobi3d-512x4/zring-wavefront": _jacobi_zring,
    "model:astaroth-8q-512/wavefront-m3": _astaroth,
    "model:weak-r3-512x4/exchange-direct": _weak_exchange,
    "model:acoustic-so8-600/plane-r4": _acoustic,
    "model:elastic-so8-600/plane-r4": _elastic,
    "model:acoustic-so8-1200x4/plane-r4": _acoustic_x4,
    "model:lbm-d3q19-256/wrap-m2": _lbm,
    "model:astaroth-mhd-256/plane-r3": _astaroth_mhd,
    "model:astaroth-mhd-256x4/plane-r3": _astaroth_mhd_x4,
    "model:lbm-d3q19-512x4/plane-y-tiles": _lbm_x4,
    "model:lbm-d3q19-512/plane-y-tiles": _lbm_512,
    "model:astaroth-mhd-512/plane-y-tiles-renamed": _astaroth_mhd_512,
}


def labels() -> list:
    from stencil_tpu.analysis import programs as aprog

    return [s.label for s in aprog.CANONICAL_PROGRAMS] + list(MODEL_PROGRAMS)


def program_fingerprint(label: str) -> str:
    from stencil_tpu.analysis import programs as aprog

    if label in MODEL_PROGRAMS:
        with aprog.tpu_shaped_trace():
            return fingerprint(MODEL_PROGRAMS[label]())
    return fingerprint(aprog.build_matrix([label])[0].closed)


def load_goldens() -> dict:
    with open(GOLDEN_PATH) as f:
        return json.load(f)


if __name__ == "__main__":
    sys.path[:0] = [HERE, os.path.dirname(HERE)]
    import conftest  # noqa: F401  the fake 8-chip CPU fleet, x64 as the tests have it

    got = {label: program_fingerprint(label) for label in labels()}
    if "--write" in sys.argv[1:]:
        with open(GOLDEN_PATH, "w") as f:
            json.dump(got, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"wrote {len(got)} fingerprints to {GOLDEN_PATH}")
    else:
        want = load_goldens()
        bad = sorted(k for k in set(got) | set(want) if got.get(k) != want.get(k))
        print(f"{len(got) - len(bad)} of {len(got)} hold" + (f"; differ: {bad}" if bad else ""))
        sys.exit(1 if bad else 0)
