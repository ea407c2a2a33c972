"""Tier-2: PROOF of compute/communication overlap in the scheduled TPU HLO.

The reference's entire transport layer exists to overlap halo exchange with
interior compute (src/stencil.cu:670-864); SURVEY.md §7 calls
profiler-verified scheduling the performance make-or-break.  Here the
overlapped step (``make_step(overlap=True)``) is AOT-compiled for a REAL
4-chip v5e topology via ``jax.experimental.topologies`` — no hardware needed,
the actual TPU compiler runs — and the scheduled module must show
``collective-permute-start`` issued BEFORE the interior-compute fusion with
the matching ``-done`` AFTER it: XLA's latency-hiding scheduler hides the
halo messages behind the interior update, replacing the reference's
hand-rolled sender/recver state machines.
"""

import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from stencil_tpu.core.radius import Radius
from stencil_tpu.domain import DistributedDomain
from stencil_tpu.parallel.mesh import MESH_AXES
from stencil_tpu.ops import stream_plan as sp
from stencil_tpu.ops import stream_pass as spass

# Mosaic lowering of the split-step macro (interior pass + six band passes
# in one fori_loop body) recurses deeper than CPython's default 1000 frames
# once pytest's own stack is underneath it; the overflow surfaces as a
# nonsense "RecursionError in __instancecheck__" LoweringException on a
# scalar convert.  The same build compiles fine from a bare interpreter.
if sys.getrecursionlimit() < 10_000:
    sys.setrecursionlimit(10_000)


def _topology_devices():
    import os

    from jax.experimental import topologies

    # Device-less AOT needs no instance metadata, but libtpu still burns
    # ~7 minutes retrying the GCP metadata server (30 tries x 7 variables)
    # before giving up — the bulk of this module's measured 481s/test.
    # Skipping the query turns each AOT compile into seconds.
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "true")
    try:
        topo = topologies.get_topology_desc(
            topology_name="v5e:2x2x1", platform="tpu"
        )
        return list(topo.devices)
    except Exception as e:  # no local TPU compiler support
        pytest.skip(f"TPU AOT topology unavailable: {e}")


def _jacobi_kernel(views, info):
    src = views["q"]
    return {
        "q": (
            src.sh(1, 0, 0)
            + src.sh(-1, 0, 0)
            + src.sh(0, 1, 0)
            + src.sh(0, -1, 0)
            + src.sh(0, 0, 1)
            + src.sh(0, 0, -1)
        )
        / 6.0
    }


def _computation_block(lines, idx):
    """[start, end) line range of the HLO computation containing line idx."""
    start = idx
    while start > 0 and not lines[start].rstrip().endswith("{"):
        start -= 1
    end = idx
    while end < len(lines) and lines[end].strip() != "}":
        end += 1
    return start, end


@pytest.mark.slow  # tier-2 (the module docstring's intent): one AOT compile
# of the overlapped step against the real TPU compiler costs ~8 MINUTES of
# wall clock — over half the tier-1 870s budget (measured 481s, 2026-08-03)
def test_overlapped_step_schedule_straddles_interior():
    devices = _topology_devices()
    dd = DistributedDomain(256, 256, 128)
    dd.set_radius(Radius.constant(1))
    dd.add_data("q", dtype=jnp.float32)
    dd.set_devices(devices)
    dd.realize(allocate=False)
    assert dd.num_subdomains() == 4

    step = dd.make_step(_jacobi_kernel, overlap=True, donate=False)
    text = step.lower(dd.abstract_arrays(), 1).compile().as_text()
    assert "is_scheduled=true" in text

    lines = text.splitlines()
    # the interior update carries the named_scope tag through fusion metadata
    interior = [
        i
        for i, l in enumerate(lines)
        if "step.overlap.interior" in l and re.search(r"=\s+\S*\s*fusion", l)
    ]
    assert interior, "no interior fusion found in scheduled module"
    i0 = interior[0]
    lo, hi = _computation_block(lines, i0)
    starts = [
        i
        for i in range(lo, hi)
        if re.search(r"=.*collective-permute-start\(", lines[i])
    ]
    dones = [
        i
        for i in range(lo, hi)
        if re.search(r"=.*collective-permute-done\(", lines[i])
    ]
    assert starts and dones, (len(starts), len(dones))
    # the straddle: at least one permute is in flight across the interior
    # fusion — its start scheduled before, its done after
    assert min(starts) < i0, (min(starts), i0)
    assert max(dones) > i0, (max(dones), i0)


@pytest.mark.slow  # tier-2 with its siblings: one more real-TPU-compiler AOT
# compile (Mosaic kernels included) against the device-less topology
def test_stream_split_step_schedule_straddles_interior():
    """The STREAM engine's split-step schedule (ops/stream.py overlap=split)
    under the real TPU compiler: the scheduled HLO must issue
    ``collective-permute-start`` BEFORE the interior stream pass (the
    tpu_custom_call carrying the ``step.overlap.interior`` scope) and the
    matching ``-done`` after it — the latency-hiding scheduler flies the
    packed shell messages behind the m-level pallas pass, which the tier-1
    jaxpr proof (tests/test_overlap_structural.py) shows is legal by
    dataflow."""
    from stencil_tpu.ops import stream as sm

    devices = _topology_devices()
    # conftest enables x64 for the numerical tiers, but Mosaic's lowering of
    # pallas scratch-ref indexing under x64 loops forever on the resulting
    # i64->i32 scalar convert (a pallas/x64 toolchain limitation, not a
    # schedule property) — the proof is about SCHEDULING of f32 kernels, so
    # trace it with the default 32-bit index widths every driver runs with.
    x64_was = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    try:
        dd = DistributedDomain(256, 256, 128)
        dd.set_radius(Radius.constant(1))
        dd.set_halo_multiplier(2)
        dd.add_data("q", dtype=jnp.float32)
        dd.set_devices(devices)
        dd.realize(allocate=False)
        assert dd.num_subdomains() == 4

        def kernel(views, info):
            return _jacobi_kernel(views, info)

        plan = sp.resolve_stream_plan(dd, kernel, 1, {
            "route": "wavefront", "m": 2, "z_slabs": False,
            "grouping": "joint", "overlap": "split", "overlap_forced": True,
        }, False)
        step = sm._build_stream_step(dd, kernel, 1, plan, interpret=False,
                                     donate=False)
        text = step.lower(dd.abstract_arrays(), 1).compile().as_text()
    finally:
        jax.config.update("jax_enable_x64", x64_was)
    assert "is_scheduled=true" in text

    lines = text.splitlines()
    interior = [
        i
        for i, l in enumerate(lines)
        if "step.overlap.interior" in l and "custom-call" in l and "=" in l
    ]
    assert interior, "no interior stream custom-call in scheduled module"
    i0 = interior[0]
    lo, hi = _computation_block(lines, i0)
    starts = [
        i
        for i in range(lo, hi)
        if re.search(r"=.*collective-permute-start\(", lines[i])
    ]
    dones = [
        i
        for i in range(lo, hi)
        if re.search(r"=.*collective-permute-done\(", lines[i])
    ]
    assert starts and dones, (len(starts), len(dones))
    # the straddle: at least one packed shell permute is in flight across
    # the interior stream pass
    assert min(starts) < i0, (min(starts), i0)
    assert max(dones) > i0, (max(dones), i0)


@pytest.mark.slow  # tier-2 with its sibling above: same real-TPU-compiler
# AOT compile; standalone (without the first test having warmed the
# compiler) it costs minutes of tier-1 wall clock
def test_no_overlap_step_schedule_serializes():
    """Sanity inverse: without the interior/exterior split the whole-region
    compute depends on every halo, so no permute can remain in flight across
    it — all dones come before the (single) compute fusion's consumers.
    Verifies the overlap assertion above is measuring the split, not an
    artifact of the scheduler."""
    devices = _topology_devices()
    dd = DistributedDomain(256, 256, 128)
    dd.set_radius(Radius.constant(1))
    dd.add_data("q", dtype=jnp.float32)
    dd.set_devices(devices)
    dd.realize(allocate=False)

    step = dd.make_step(_jacobi_kernel, overlap=False, donate=False)
    text = step.lower(dd.abstract_arrays(), 1).compile().as_text()
    assert "step.overlap.interior" not in text


@pytest.mark.slow  # tier-2 with its siblings: a real-TPU-compiler AOT
# compile at the benchmark's size (15 s alone, and it loads libtpu into the
# worker that runs it)
def test_acoustic_step_carries_its_blocks_in_place(monkeypatch):
    """The acoustic cell's step (600^3, four quantities, plane route) as the
    chip's compiler leaves it: the pass's custom call has ONE result, the new
    ``u`` (``m`` and ``damp`` are operands only: ISSUE 32; ``u_prev <- u`` is
    a rename: ISSUE 36), aliased onto raw ``u_prev`` (operand 2), the
    ``while`` body holds TWO steps and NO whole-array copy, and nothing is
    temporary — un-aliased, XLA copies every written 608^3 block every step
    to put the fresh result where the loop's carry lives (PERF.md §6, PR 28:
    11.5 of 29.35 ms when all four were written), and so it would for a body
    that returned its carry permuted.  Nine steps run the ninth behind the
    loop: the program's outputs are permuted against its donated inputs and
    three copies (one block of temporaries) come back at its edge, once a
    dispatch.  The check ISSUEs 28, 32 and 36 ask for before any chip call."""
    from stencil_tpu.models.acoustic import RADIUS, AcousticWave
    from stencil_tpu.ops import halo_blend
    from stencil_tpu.ops import stream as sm

    devices = _topology_devices()
    monkeypatch.setattr(halo_blend, "pallas_interpret", lambda: False)
    x64_was = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)  # Mosaic index arithmetic is 32-bit
    try:
        texts = {}
        for alias, steps in ((None, 8), (None, 9), (False, 8)):
            sim = AcousticWave(600, 600, 600, devices=devices[:1], seed_words=None)
            sim.dd.realize(allocate=False)
            plan = sp.plan_stream(sim.dd, RADIUS, "auto", False)
            if alias is not None:
                plan = dict(plan, alias=alias, alias_forced=True)
            plan = sp.resolve_stream_plan(sim.dd, sim._kernel, RADIUS, plan, False)
            step = sm._build_stream_step(sim.dd, sim._kernel, RADIUS, plan, interpret=False)
            compiled = step.lower(sim.dd.abstract_arrays(), steps).compile()
            texts[alias, steps] = (
                compiled.as_text(), compiled.memory_analysis().temp_size_in_bytes, plan
            )
    finally:
        jax.config.update("jax_enable_x64", x64_was)
    big_copy = re.compile(r"=\s+f32\[608,608,608\]\S*\s+copy\(")

    def custom_calls(text, name=""):
        return [
            l for l in text.splitlines()
            if "custom-call(" in l and "tpu_custom_call" in l and l.lstrip().startswith("%" + name)
        ]

    text, temp, plan = texts[None, 8]
    assert plan["renamed"] == ("u_prev",) and plan["writers"] == ("u",), plan
    passes = custom_calls(text, "stream_plane_pass")
    assert len(passes) == 2  # a trip of the loop is two steps
    for line in passes:
        assert line.lstrip().split(" custom-call(")[0].count("f32[608,608,608]") == 1
        assert "output_to_operand_aliasing={{}: (2, {})}, " in line
    # the passes and the x wraps of ``u``: the y and z wraps ride in the pass (ISSUE 34)
    assert len(custom_calls(text)) == 4 and len(custom_calls(text, "blend_planes")) == 2
    assert not big_copy.findall(text) and temp == 0
    text_odd, temp_odd, _ = texts[None, 9]
    assert len(custom_calls(text_odd, "stream_plane_pass")) == 3
    assert len(big_copy.findall(text_odd)) == 3 and 0.9e9 < temp_odd < 1.0e9
    text_off, temp_off, plan_off = texts[False, 8]
    assert plan_off["renamed"] == () and plan_off["writers"] == ("u", "u_prev"), plan_off
    assert len(big_copy.findall(text_off)) == 2 and temp_off > 1.8e9


@pytest.mark.slow  # tier-2 with its siblings: a real-TPU-compiler AOT
# compile at the benchmark's size for all four chips (5 s alone)
def test_acoustic_step_over_four_chips_carries_its_blocks_in_place(monkeypatch):
    """The four-chip acoustic cell's 8-step program (1200 x 1200 x 600 on mesh
    [2,2,1], 600^3 a chip: ISSUE 37) as the chip's compiler leaves it: the
    rename survives a loop body that holds collectives -- the ``while`` body
    is two steps, each FOUR ``collective-permute``s (x low / high, then y low
    / high, of ``u`` alone), two ``blend_planes``, two ``blend_slab`` and one
    ``stream_plane_pass`` whose one result aliases raw ``u_prev``; no ``copy``
    of ``f32[608,608,608]`` anywhere and nothing temporary (the messages live
    in the loop's own buffers); the only copies are the x message's relayouts,
    ``f32[1,2432,608]``.  The check ISSUE 37 asks for before any chip call."""
    from stencil_tpu.models.acoustic import RADIUS, AcousticWave
    from stencil_tpu.ops import halo_blend
    from stencil_tpu.ops import stream as sm

    devices = _topology_devices()
    monkeypatch.setattr(halo_blend, "pallas_interpret", lambda: False)
    x64_was = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)  # Mosaic index arithmetic is 32-bit
    try:
        sim = AcousticWave(1200, 1200, 600, devices=devices, seed_words=None)
        sim.dd.realize(allocate=False)
        assert tuple(sim.dd.mesh_dim()) == (2, 2, 1)  # the partitioner's own pick
        plan = sp.plan_stream(sim.dd, RADIUS, "auto", False)
        plan = sp.resolve_stream_plan(sim.dd, sim._kernel, RADIUS, plan, False)
        step = sm._build_stream_step(sim.dd, sim._kernel, RADIUS, plan, interpret=False)
        compiled = step.lower(sim.dd.abstract_arrays(), 8).compile()
        text, temp = compiled.as_text(), compiled.memory_analysis().temp_size_in_bytes
    finally:
        jax.config.update("jax_enable_x64", x64_was)
    assert (plan["route"], plan["pass_wrap_axes"], plan["wired"]) == ("plane", "z", "xy"), plan
    # four faces and, behind the y faces of the joint x-y sweep, two corner relays
    assert plan["wire_bytes"] == 2 * 2 * 4 * 608 * 608 * 4 + 2 * 8 * 4 * 608 * 4
    assert plan["joint"] == "xy"
    assert plan["renamed"] == ("u_prev",) and plan["halo_readers"] == ("u",), plan

    def custom_calls(name):
        return [
            l for l in text.splitlines()
            if "custom-call(" in l and "tpu_custom_call" in l and l.lstrip().startswith("%" + name)
        ]

    passes = custom_calls("stream_plane_pass")
    assert len(passes) == 2  # a trip of the loop is two steps
    for line in passes:
        assert line.lstrip().split(" custom-call(")[0].count("f32[608,608,608]") == 1
        assert "output_to_operand_aliasing={{}: (2, {})}, " in line
    assert len(custom_calls("blend_planes")) == 4 and len(custom_calls("blend_slab")) == 4
    assert len(custom_calls("")) == 10  # and no other kernel
    assert len(re.findall(r"=.*collective-permute-start\(", text)) == 12
    assert not re.findall(r"=\s+f32\[608,608,608\]\S*\s+copy\(", text) and temp == 0
    copied = set(re.findall(r"=\s+(f32\[[\d,]+\])\S*\s+copy\(", text))
    assert copied <= {"f32[1,2432,608]"}, copied


def _jacobi_cell_step(chips, devices):
    """The step of a jacobi cell of the benchmark, built for described
    devices: ``Jacobi3D.realize()`` without the allocation and the fill."""
    from stencil_tpu.models.jacobi import Jacobi3D

    if chips == 1:
        sim = Jacobi3D(512, 512, 512, devices=devices[:1], kernel_impl="pallas")
        sim._wavefront_m = 0
        sim._resolve_storage()
        sim.dd.realize(allocate=False)
        return sim, sim._make_pallas_step()
    sim = Jacobi3D(1024, 1024, 512, devices=devices, kernel_impl="pallas")
    sim._resolve_storage()
    sim._wavefront_m = sim._plan_wavefront()
    sim.dd.set_halo_multiplier(sim._wavefront_m)
    sim.dd.realize(allocate=False)
    return sim, sim._make_wavefront_step()


@pytest.mark.slow  # tier-2 with its siblings: real-TPU-compiler AOT compiles
# at the benchmark's sizes, six of them (15-40 s each)
@pytest.mark.parametrize("chips", [1, 4])
def test_jacobi_macro_loop_carries_its_block_in_place(chips, monkeypatch):
    """Both jacobi cells' dispatch as the chip's compiler leaves it (ISSUE 38):
    256 steps of the wrap route for a described v5e, 160 steps of the z-ring
    wavefront for a described v5e:2x2.  The ``while`` body holds TWO stencil
    calls -- the second result takes the buffer the trip's operand died in, so
    the carry is back in its own place -- and NO ``copy`` of the whole block
    (``f32[512,512,512]`` / ``f32[544,544,512]``), the stencil call aliases no
    operand (``pallas_hbm_pct`` reads only fresh results), and nothing is
    temporary that the one-a-trip loop did not hold: that control has one
    stencil call and one whole-block copy a trip (10.4% / 7.2% of the cells'
    busy time: PERF.md, PR 38).  An odd macro count (17 / 11) runs its last
    macro behind the loop: no copy either, but on four chips 0.9 GB more of
    temporaries at the program's edge -- dispatch an even count."""
    from stencil_tpu.models import jacobi as jm

    devices = _topology_devices()
    shape, kernel, k, macros = {
        1: ("f32[512,512,512]", "jacobi_wrap_step", 16, 16),
        4: ("f32[544,544,512]", "jacobi_zring_wavefront_step", 16, 10),
    }[chips]
    x64_was = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)  # Mosaic index arithmetic is 32-bit
    try:
        got = {}
        for per_trip, count in ((None, macros), (None, macros + 1), (1, macros)):
            with monkeypatch.context() as mp:
                if per_trip is not None:
                    mp.setattr(jm, "_macros_per_trip", lambda in_place: per_trip)
                sim, step = _jacobi_cell_step(chips, devices)
                assert (sim._pallas_path, getattr(sim, "_wrap_k", sim._wavefront_m)) == (
                    "wrap" if chips == 1 else "wavefront", k)
                assert chips == 1 or (sim._wavefront_z_ring and tuple(sim.dd.mesh_dim()) == (2, 2, 1))
                compiled = step.lower(sim.dd.abstract_arrays(), count * k).compile()
            got[per_trip, count] = (
                compiled.as_text(), compiled.memory_analysis().temp_size_in_bytes,
                step._span_args(),
            )
    finally:
        jax.config.update("jax_enable_x64", x64_was)
    big_copy = re.compile(rf"=\s+{re.escape(shape)}\S*\s+copy\(")

    def stencil_calls(text):
        return [
            l for l in text.splitlines()
            if "custom-call(" in l and "tpu_custom_call" in l and l.lstrip().startswith("%" + kernel)
        ]

    def in_the_loop(text):
        """Lines of the computations a ``while`` runs as its body."""
        lines = text.splitlines()
        bodies = set(re.findall(r"\bwhile\([^\n]*body=%?([\w.\-]+)", text))
        assert bodies, "the macro loop is gone"
        out = []
        for i, l in enumerate(lines):
            m = re.match(r"%?([\w.\-]+) \(", l.lstrip())
            if m and m.group(1) in bodies and l.rstrip().endswith("{"):
                out += lines[i:_computation_block(lines, i + 1)[1]]
        return "\n".join(out)

    # the z-ring step also says where its kernel patches the z halo (ISSUE 40)
    # ... and every step what it sends over wires (PR 49) and which sweeps fly jointly (ISSUE 50):
    # the 16-wide shell's faces, the z slabs' extensions and the two corner relays behind the y faces
    patch = {"wired": "", "wire_bytes": 0, "joint": ""} if chips == 1 else {
        # (the blend kernels are off where the default backend is the CPU: no slab self-wrap, ISSUE 56)
        "z_halo_patch": "tile", "slab_wrap": "", "wired": "xy", "wire_bytes": 4_734_976 + 2 * 32 * 16 * 512 * 4 // 16,
        "joint": "xy"}
    text, temp, args = got[None, macros]
    assert args == {"macros_per_trip": 2, **patch}
    assert len(stencil_calls(text)) == 2 == len(stencil_calls(in_the_loop(text)))
    assert not big_copy.findall(text)
    assert not any("output_to_operand_aliasing" in l for l in stencil_calls(text))
    text_one, temp_one, args_one = got[1, macros]
    assert args_one == {"macros_per_trip": 1, **patch}
    assert len(stencil_calls(text_one)) == 1
    assert len(big_copy.findall(in_the_loop(text_one))) == 1
    # the same two blocks taking turns; on four chips a second pair of z-slab buffers
    # (and 2 MB, the joint sweep's relay buffers, that the one-a-trip loop holds apart)
    assert temp_one * 0.997 <= temp <= temp_one * 1.002, (temp, temp_one)
    if chips == 4:
        assert len(re.findall(r"=.*collective-permute-start\(", text)) == 2 * len(
            re.findall(r"=.*collective-permute-start\(", text_one)
        )
    text_odd, temp_odd, _ = got[None, macros + 1]
    assert len(stencil_calls(text_odd)) == 3 and len(stencil_calls(in_the_loop(text_odd))) == 2
    assert not big_copy.findall(text_odd)
    assert temp_odd <= (temp * 1.002 if chips == 1 else temp + 1.0e9), (temp_odd, temp)


@pytest.mark.slow  # tier-2 with its siblings: real-TPU-compiler AOT compiles
# of single kernels at the cells' plane sizes, a few seconds each
@pytest.mark.parametrize("kernel,zv", [("stream", 518), ("stream", 514), ("shell", 544)],
                         ids=["astaroth-518", "straddles-512", "shell-544"])
def test_the_tile_form_z_halo_patch_lowers_for_the_chip(kernel, zv):
    """What interpret mode cannot show (ISSUE 40): Mosaic takes the tile-form
    z-halo patch -- the (Yr, 2s) -> (Yr, 128) lane pad, the lane rotate, the
    tile sliced out at a multiple of 128 -- on astaroth's 518-row planes (off
    the sublane tile: the rotate takes its slice + concatenate form), with the
    hi halo inside one lane tile and straddling lane 512, and on the shell
    kernel's lane-padded (544, 640) plane.  Few planes and one level: the
    patch is the same at any depth, the compile stays short.  Since ISSUE 41
    the engine's pass takes the RAW 518- / 514-lane block through a
    ``(1, 518, 640)`` boundary block, in place: that lowering is held here too."""
    from jax.sharding import SingleDeviceSharding

    from stencil_tpu.core.dim3 import Dim3
    from stencil_tpu.ops import jacobi_pallas as jp

    one = SingleDeviceSharding(_topology_devices()[0])

    def shaped(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    x64_was = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)  # Mosaic index arithmetic is 32-bit
    try:
        if kernel == "stream":
            s, xr, yr = 3, 16, 518
            assert jp.z_halo_patch_form(spass.lane_pad_width(zv), s) == "tile"

            # the raw block as the domain stores it, in place as astaroth's
            # eight run: the pass makes the 640-lane plane in VMEM from a
            # boundary block (ISSUE 41), and Mosaic takes that with the alias
            def run(origin, raw, zs):
                return spass.stream_wavefront_pass(
                    _jacobi_kernel, ["q"], [raw], 1, s, origin, Dim3(1024, 1024, 512),
                    z_slabs=[zs], alias=True,
                )

            args = (shaped((3,), jnp.int32), shaped((xr, yr, zv)), shaped((xr, 2 * s, yr)))
        else:
            s, xr, yr = 16, 40, 544
            assert jp.z_halo_patch_form(640, s) == "tile"

            def run(origin, raw, zs, d2):
                return jp.jacobi_shell_wavefront_step(
                    raw, 1, origin, d2, (1024, 1024, 512), interior_offset=s,
                    z_slabs=zs, z_valid=zv,
                )

            args = (shaped((3,), jnp.int32), shaped((xr, yr, 640)), shaped((xr, 2 * s, yr)),
                    shaped((yr, 640), jnp.int32))
        donate = {"donate_argnums": 1} if kernel == "stream" else {}
        text = jax.jit(run, **donate).lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_x64", x64_was)
    assert "tpu_custom_call" in text
    if kernel == "stream":  # the raw block's own shape in and out, aliased
        assert f"f32[{xr},{yr},{zv}]" in text and "output_to_operand_aliasing" in text


@pytest.mark.slow  # tier-2 with its siblings: a real-TPU-compiler AOT
# compile at the benchmark's size (12 s alone)
def test_elastic_step_carries_its_blocks_in_place(monkeypatch):
    """The elastic cell's step (600^3, thirteen quantities, two stages) as
    the chip's compiler leaves it: four ``stream_plane_pass`` custom calls of
    2, 1, 4 and 2 results -- the passes the planner forms at 608 x 608 planes
    -- every result aliased onto its operand, 27 self-wrap kernels, NO
    whole-array copy in the ``while`` body and no temporary: a step holds its
    thirteen arrays and nothing else.  The check ISSUE 33 asks for before any
    chip call."""
    from stencil_tpu.models.elastic import RADIUS, ElasticWave
    from stencil_tpu.ops import halo_blend
    from stencil_tpu.ops import stream as sm

    devices = _topology_devices()
    monkeypatch.setattr(halo_blend, "pallas_interpret", lambda: False)
    x64_was = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)  # Mosaic index arithmetic is 32-bit
    try:
        sim = ElasticWave(600, 600, 600, devices=devices[:1], seed_words=None)
        sim.dd.realize(allocate=False)
        stages = (sim._stage_v, sim._stage_t)
        plan = sp.plan_stream(sim.dd, RADIUS, "plane", False)
        plan = sp.resolve_stream_plan(sim.dd, stages, RADIUS, plan, False)
        step = sm._build_stream_step(sim.dd, stages, RADIUS, plan, interpret=False)
        compiled = step.lower(sim.dd.abstract_arrays(), 4).compile()
        text, temp = compiled.as_text(), compiled.memory_analysis().temp_size_in_bytes
    finally:
        jax.config.update("jax_enable_x64", x64_was)
    assert [len(p["writes"]) for st in plan["stages"] for p in st["passes"]] == [2, 1, 4, 2]
    calls = [l for l in text.splitlines() if "custom-call(" in l and "tpu_custom_call" in l]
    passes = [l for l in calls if l.lstrip().startswith("%stream_plane_pass")]
    results = sorted(l.lstrip().split(" custom-call(")[0].count("f32[608,608,608]") for l in passes)
    assert plan["pass_wrap_axes"] == "yz"
    assert results == [1, 2, 2, 4] and len(calls) - len(passes) == 9
    assert all(l.lstrip().startswith("%blend_planes") for l in calls if l not in passes)
    for l in passes:
        n = l.lstrip().split(" custom-call(")[0].count("f32[608,608,608]")
        aliasing = l[l.index("output_to_operand_aliasing="):].split("}, ")[0]
        assert aliasing.count("(") == n, aliasing
    assert not re.findall(r"=\s+f32\[608,608,608\]\S*\s+copy\(", text) and temp == 0


@pytest.mark.slow  # tier-2 with its siblings: one real-TPU-compiler AOT
# compile at the benchmark's size (10 s alone since ISSUE 46: six passes of
# ~840 operations on four-vreg strips; 40 s on 99-vreg planes)
def test_mhd_step_carries_its_blocks_in_place(monkeypatch):
    """The MHD cell's dispatch (256^3, sixteen quantities, three stages) as the
    chip's compiler leaves it (ISSUE 44): the ``while`` body holds TWO steps --
    SIX ``stream_plane_pass`` custom calls of eight results, each result aliased
    onto a ``*_prev`` operand, the renamed handles home again after the trip --
    and 48 ``blend_planes`` x wraps (eight fields a substep; the y and z halos
    are the pass's own fills), NO ``copy`` of a block and no temporary: a step
    holds its sixteen arrays and nothing else.  Mosaic takes the whole-stage
    pass on its INTERIOR window (ISSUE 45: the aligned 256 x 256 corner of
    each 262 x 262 block, sliced loads and stores of a ref at whole tiles and
    the few rows and lanes of the fills beside them; 80.0 MB by the planner's
    model -- 48 raw pipeline planes, 48 interior ring planes and sixteen
    margins, 86.9 MB on the raw window)."""
    from stencil_tpu.models.astaroth_mhd import RADIUS, AstarothMHD
    from stencil_tpu.ops import halo_blend
    from stencil_tpu.ops import stream as sm

    devices = _topology_devices()
    monkeypatch.setattr(halo_blend, "pallas_interpret", lambda: False)
    x64_was = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)  # Mosaic index arithmetic is 32-bit
    try:
        sim = AstarothMHD(256, 256, 256, devices=devices[:1], seed_words=None)
        sim.dd.realize(allocate=False)
        stages = tuple(sim._substep(s) for s in range(3))
        plan = sp.plan_stream(sim.dd, RADIUS, "auto", False)
        plan = sp.resolve_stream_plan(sim.dd, stages, RADIUS, plan, False)
        step = sm._build_stream_step(sim.dd, stages, RADIUS, plan, interpret=False)
        compiled = step.lower(sim.dd.abstract_arrays(), 4).compile()
        text, temp = compiled.as_text(), compiled.memory_analysis().temp_size_in_bytes
    finally:
        jax.config.update("jax_enable_x64", x64_was)
    assert (plan["route"], plan["pass_wrap_axes"], plan["steps_per_trip"]) == ("plane", "yz", 2)
    assert plan["plane_window"] == "interior"
    # ... in its strip form (ISSUE 46): Mosaic takes the planes as tiles (a
    # transposition of a tile's two leading dimensions a plane), the loop over
    # sixteen strips of two tiles read at a traced index of a leading
    # dimension, and the 24 whole-plane lane rotates; 97.0 MB by the model
    assert plan["plane_strip"] == 16
    assert [len(p["prerotated"]) for st in plan["stages"] for p in st["passes"]] == [24, 24, 24]
    assert [len(p["renames"]) for st in plan["stages"] for p in st["passes"]] == [8, 8, 8]
    calls = [l for l in text.splitlines() if "custom-call(" in l and "tpu_custom_call" in l]
    passes = [l for l in calls if l.lstrip().startswith("%stream_plane_pass")]
    assert len(passes) == 6 and len(calls) - len(passes) == 48
    assert all(l.lstrip().startswith("%blend_planes") for l in calls if l not in passes)
    for l in passes:
        assert l.lstrip().split(" custom-call(")[0].count("f32[262,262,262]") == 8
        aliasing = l[l.index("output_to_operand_aliasing="):].split("}, ")[0]
        assert aliasing.count("(") == 8, aliasing
    assert not re.findall(r"=\s+f32\[262,262,262\]\S*\s+copy\(", text) and temp == 0


@pytest.mark.slow  # tier-2 with its siblings: one real-TPU-compiler AOT
# compile at the benchmark's size (30 s alone: twelve passes of four kinds)
def test_mhd_512_step_runs_in_place_in_renaming_passes(monkeypatch):
    """The card-filling MHD cell's dispatch as the chip's compiler leaves it
    (ISSUE 57): 512^3 x 16 through the normal planner for a described v5e -- two
    time steps are 24 ``stream_plane_pass`` custom calls, FOUR a substep (one over
    whole planes, three over y tiles of 256 rows at radius 3: Mosaic takes the
    104.6 MB of VMEM the model prices for the ``ux uy`` pass), of 1, 2, 4 and 1
    results, EVERY result aliased onto the ``*_prev`` operand of its field (the
    pass's last operands: a rename, in a tiled pass as in a whole one), 48
    ``blend_planes`` x wraps, no ``copy`` of a block -- the handles swapped at a
    stage's end cost XLA nothing -- and NOTHING temporary beside 11.03 GB of
    arguments."""
    from stencil_tpu.models.astaroth_mhd import RADIUS, AstarothMHD
    from stencil_tpu.ops import halo_blend
    from stencil_tpu.ops import stream as sm

    devices = _topology_devices()
    monkeypatch.setattr(halo_blend, "pallas_interpret", lambda: False)
    x64_was = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)  # Mosaic index arithmetic is 32-bit
    try:
        sim = AstarothMHD(512, 512, 512, devices=devices[:1], seed_words=None)
        sim.dd.realize(allocate=False)
        stages = tuple(sim._substep(s) for s in range(3))
        plan = sp.plan_stream(sim.dd, RADIUS, "auto", False)
        plan = sp.resolve_stream_plan(sim.dd, stages, RADIUS, plan, False)
        step = sm._build_stream_step(sim.dd, stages, RADIUS, plan, interpret=False)
        compiled = step.lower(sim.dd.abstract_arrays(), 2).compile()
        text, memory = compiled.as_text(), compiled.memory_analysis()
    finally:
        jax.config.update("jax_enable_x64", x64_was)
    assert (plan["route"], plan["plane_window"], plan["plane_strip"], plan["steps_per_trip"]) == (
        "plane", "interior", 8, 2), plan
    said = [(len(p["writes"]), len(p["reads"]), p["tile_rows"], len(p["renames"]))
            for p in plan["stages"][0]["passes"]]
    assert said == [(1, 5, 0, 1), (2, 10, 256, 2), (4, 12, 256, 4), (1, 9, 256, 1)], said
    calls = [l.lstrip() for l in text.splitlines() if "custom-call(" in l and "tpu_custom_call" in l]
    passes = [l for l in calls if l.startswith("%stream_plane_pass")]
    assert len(passes) == 24 and len([l for l in calls if l.startswith("%blend_planes")]) == 48
    shapes = {(1, 5): 0, (2, 10): 0, (4, 12): 0, (1, 9): 0}
    for call in passes:
        results = call.split(" custom-call(")[0].count("f32[518,518,518]")
        operands = call.split(" custom-call(")[1].split("), custom_call_target")[0].count("%") - 1
        shapes[results, operands] += 1
        aliasing = call[call.index("output_to_operand_aliasing="):].split("}, frontend")[0]
        # result k IS one of the pass's LAST operands (operand 0 is the origin): its field's *_prev
        got = sorted(int(m) for m in re.findall(r"\((\d+), \{\}\)", aliasing))
        assert got == list(range(operands - results + 1, operands + 1)), (aliasing, operands)
    assert shapes == {(1, 5): 6, (2, 10): 6, (4, 12): 6, (1, 9): 6}, shapes
    assert not re.findall(r"=\s+f32\[518,518,518\]\S*\s+copy\(", text)
    assert memory.temp_size_in_bytes == 0 and memory.argument_size_in_bytes == 11_032_985_600 + 0


@pytest.mark.slow  # tier-2 with its siblings: one real-TPU-compiler AOT
# compile at the benchmark's size for all four chips (40 s alone: six passes of
# ~840 operations over whole 99-vreg planes, the raw window)
def test_mhd_step_over_four_chips_carries_its_blocks_in_place(monkeypatch):
    """The four-chip MHD cell's 8-step program (512 x 512 x 256 on mesh [2,2,1],
    256^3 a chip: ISSUE 47) as the chip's compiler leaves it: eight renames a
    stage survive a loop body that holds collectives -- the ``while`` body is
    two steps = SIX ``stream_plane_pass`` of eight aliased results and the
    collectives of SIX exchanges, 24 ``collective-permute``s (x low / high,
    then y low / high, the eight fields of a direction in ONE message), 96
    ``blend_planes`` and 96 ``blend_slab`` --, no ``copy`` of a block (the
    copies are the messages' relayouts) and nothing temporary.  Beside a y
    halo that arrives over a wire the passes work on the RAW window, whole
    planes: PR 45's interior window and PR 46's strips are the one-chip
    cell's (ROADMAP X11).  The check ISSUE 47 asks for before any chip call."""
    from stencil_tpu.models.astaroth_mhd import RADIUS, AstarothMHD
    from stencil_tpu.models.astaroth_mhd_reference import MhdSetup
    from stencil_tpu.ops import halo_blend
    from stencil_tpu.ops import stream as sm

    devices = _topology_devices()
    monkeypatch.setattr(halo_blend, "pallas_interpret", lambda: False)
    x64_was = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)  # Mosaic index arithmetic is 32-bit
    try:
        shape = (512, 512, 256)
        cell = MhdSetup((256,) * 3).box / 256  # the one-chip cell's: the box grows with the grid
        setup = MhdSetup(shape, box=tuple(cell * n for n in shape))
        sim = AstarothMHD(*shape, setup=setup, devices=devices, seed_words=None)
        sim.dd.realize(allocate=False)
        assert tuple(sim.dd.mesh_dim()) == (2, 2, 1)  # the partitioner's own pick
        stages = tuple(sim._substep(s) for s in range(3))
        plan = sp.plan_stream(sim.dd, RADIUS, "auto", False)
        plan = sp.resolve_stream_plan(sim.dd, stages, RADIUS, plan, False)
        step = sm._build_stream_step(sim.dd, stages, RADIUS, plan, interpret=False)
        compiled = step.lower(sim.dd.abstract_arrays(), 8).compile()
        text, temp = compiled.as_text(), compiled.memory_analysis().temp_size_in_bytes
    finally:
        jax.config.update("jax_enable_x64", x64_was)
    assert (plan["route"], plan["pass_wrap_axes"], plan["wired"], plan["steps_per_trip"]) == (
        "plane", "z", "xy", 2), plan
    # beside the y halo that arrives over a wire the passes take the aligned
    # window in its strip form (ISSUE 48: the z halo the rotates' wraparound, the
    # y halo rows in the margin tiles; the twin's scratch shapes, 97.0 MB)
    assert (plan["plane_window"], plan["plane_strip"]) == ("interior-z", 16)
    assert [len(p["prerotated"]) for st in plan["stages"] for p in st["passes"]] == [24, 24, 24]
    stage_bytes = 2 * 8 * 6 * 262 * 262 * 4 + 2 * 8 * 6 * 3 * 262 * 4  # faces + the two corner relays
    assert plan["wire_bytes_by_stage"] == (stage_bytes,) * 3 and plan["wire_bytes"] == 79_983_360
    assert plan["joint"] == "xy"
    assert plan["wired_edges"] == ("xy",)
    assert [len(p["renames"]) for st in plan["stages"] for p in st["passes"]] == [8, 8, 8]
    calls = [l.lstrip() for l in text.splitlines() if "custom-call(" in l and "tpu_custom_call" in l]
    passes = [l for l in calls if l.startswith("%stream_plane_pass")]
    assert len(passes) == 6  # a trip of the loop is two steps of three stages
    for l in passes:
        assert l.split(" custom-call(")[0].count("f32[262,262,262]") == 8
        aliasing = l[l.index("output_to_operand_aliasing="):].split("}, ")[0]
        assert aliasing.count("(") == 8, aliasing
    assert len([l for l in calls if l.startswith("%blend_planes")]) == 96
    assert len([l for l in calls if l.startswith("%blend_slab")]) == 96
    assert len(calls) == 6 + 96 + 96  # and no other kernel
    assert len(re.findall(r"=.*collective-permute-start\(", text)) == 36
    assert not re.findall(r"=\s+f32\[262,262,262\]\S*\s+copy\(", text) and temp == 0
    copied = set(re.findall(r"=\s+(f32\[[\d,]+\])\S*\s+copy\(", text))
    # slab-sized, every one: the messages and the slabs they are cut into, and
    # (the x and y sweeps flying jointly) the corner strips of the relay
    assert copied <= {"f32[1,3,262,262]", "f32[1,1,786,262]", "f32[8,262,3,262]", "f32[8,1,786,262]",
                      "f32[8,3,3,262]"}, copied


@pytest.mark.slow  # tier-2 with its siblings: real-TPU-compiler AOT compiles
# at the benchmark's size, three of them (5-6 s each)
def test_lbm_macro_loop_carries_its_blocks_in_place(monkeypatch):
    """The lattice-Boltzmann cell's dispatch as the chip's compiler leaves it
    (ISSUE 39, ISSUE 52): 256^3 x 19 on the stream engine's wrap route at
    depth 2 for a described v5e.  The dispatch carries the domain's RAW blocks
    at its two edges: its first ``stream_wrap_pass`` reads the nineteen
    ``f32[258,258,258]`` blocks, its last one writes them, every result aliased
    onto the step's own operand, and the program holds NO
    ``dynamic-update-slice``, NO ``slice`` and no ``copy`` of a block.  Between
    them the ``while`` body holds TWO bare calls -- every second result takes
    the buffer the trip's operand died in --; the one-a-trip control has one
    call and NINETEEN copies of ``f32[256,256,256]`` a trip (2.55 GB, as much
    as the pass moves).  Nothing is temporary that the control did not hold, an
    odd macro count runs its last bare macro behind the loop without a copy,
    and with the edge forms taken away (``wrap_edge_form`` patched to the
    parent's) the cut and the write-back are back, nineteen of each."""
    from stencil_tpu.models.lbm import RADIUS, LatticeBoltzmann
    from stencil_tpu.ops import stream as sm

    devices = _topology_devices()
    x64_was = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)  # Mosaic index arithmetic is 32-bit
    try:
        got = {}
        for per_trip, macros, edges in ((None, 6, "raw"), (None, 7, "raw"), (1, 6, "raw"), (None, 6, "xla")):
            with monkeypatch.context() as mp:
                if per_trip is not None:
                    mp.setattr(sp, "macros_per_trip", lambda in_place: per_trip)
                if edges == "xla":
                    mp.setattr(sp, "wrap_edge_form", lambda dd, plan: "xla")
                sim = LatticeBoltzmann(256, 256, 256, devices=devices[:1], seed_words=None)
                sim.dd.realize(allocate=False)
                plan = sp.plan_stream(sim.dd, RADIUS, "auto", False)
                assert (plan["route"], plan["m"], plan["grouping"]) == ("wrap", 2, "joint"), plan
                plan = sp.resolve_stream_plan(sim.dd, sim._kernel, RADIUS, plan, False)
                assert (plan["m"], plan["edges"]) == (2, edges), plan
                step = sm._build_stream_step(sim.dd, sim._kernel, RADIUS, plan, interpret=False)
                compiled = step.lower(sim.dd.abstract_arrays(), macros * plan["m"]).compile()
            got[per_trip, macros, edges] = (
                compiled.as_text(), compiled.memory_analysis().temp_size_in_bytes, plan,
            )
    finally:
        jax.config.update("jax_enable_x64", x64_was)
    big_copy = re.compile(r"=\s+f32\[25[68],25[68],25[68]\]\S*\s+copy\(")
    edge_ops = re.compile(r"=\s+f32\[25[68],25[68],25[68]\]\S*\s+(?:dynamic-update-slice|slice)\(")

    def passes(text):
        return [
            l for l in text.splitlines()
            if "custom-call(" in l and "tpu_custom_call" in l and l.lstrip().startswith("%stream_wrap_pass")
        ]

    def results(line, shape):
        return line.split(" custom-call(")[0].count(shape)

    def aliased(line):
        if "output_to_operand_aliasing=" not in line:
            return 0
        return line[line.index("output_to_operand_aliasing="):].split("}, ")[0].count("(")

    text, temp, plan = got[None, 6, "raw"]
    assert plan["macros_per_trip"] == 2 and plan["footprint"]["diagonal"] == 12
    # raw in, two in the ``while`` (two trips of them), raw out: no other kernel
    calls = passes(text)
    assert len(calls) == 4 and not big_copy.findall(text) and not edge_ops.findall(text)
    assert sorted(results(l, "f32[258,258,258]") for l in calls) == [0, 0, 0, 19]
    assert sorted(aliased(l) for l in calls) == [0, 0, 0, 19]
    assert all(results(l, "f32[256,256,256]") == 19 for l in calls if not aliased(l))
    text_one, temp_one, plan_one = got[1, 6, "raw"]
    assert plan_one["macros_per_trip"] == 1
    assert len(passes(text_one)) == 3 and len(big_copy.findall(text_one)) == 19
    assert temp <= temp_one  # two sets of nineteen blocks taking turns, either way
    text_odd, temp_odd, _ = got[None, 7, "raw"]
    assert len(passes(text_odd)) == 5 and not big_copy.findall(text_odd) and not edge_ops.findall(text_odd)
    assert temp_odd <= temp * 1.002
    # the parent's edges: the whole loop between a cut and a write-back
    text_xla, temp_xla, _ = got[None, 6, "xla"]
    assert len(passes(text_xla)) == 2 and not any(aliased(l) for l in passes(text_xla))
    assert len(edge_ops.findall(text_xla)) == 38 and temp <= temp_xla * 1.002


@pytest.mark.slow  # tier-2 with its siblings: a real-TPU-compiler AOT compile
# at the benchmark's size (6 s)
def test_lbm_512_step_runs_in_place_in_y_tiles(monkeypatch):
    """The card-filling lattice-Boltzmann cell's dispatch as the chip's compiler
    leaves it (ISSUE 51): 512^3 x 19 through the normal planner for a described
    v5e -- THREE ``stream_plane_pass`` custom calls (ISSUES 54, 58: the dispatch's
    first call raw in / window out, the loop's window in / window out, the last
    call's window in / raw out: the lane tile behind the window moved at the
    dispatch's two edges alone) of nineteen results each, every one aliased
    onto its own operand, eighteen ``blend_planes`` x wraps a call, no ``copy``
    of a block and NOTHING temporary beside 13.0 GB of arguments: Mosaic takes
    the 101.9 MB of VMEM the model prices (y tiles of 128 rows) and the
    ``(1, 128, 512)`` blocks of a 514-lane array on BOTH sides of one call.  A
    dispatch of two steps holds the two edge forms and no third."""
    from stencil_tpu.models.lbm import RADIUS, LatticeBoltzmann
    from stencil_tpu.ops import halo_blend
    from stencil_tpu.ops import stream as sm

    devices = _topology_devices()
    monkeypatch.setattr(halo_blend, "pallas_interpret", lambda: False)
    x64_was = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)  # Mosaic index arithmetic is 32-bit
    try:
        sim = LatticeBoltzmann(512, 512, 512, devices=devices[:1], seed_words=None)
        sim.dd.realize(allocate=False)
        plan = sp.plan_stream(sim.dd, RADIUS, "auto", False)
        plan = sp.resolve_stream_plan(sim.dd, sim._kernel, RADIUS, plan, False)
        step = sm._build_stream_step(sim.dd, sim._kernel, RADIUS, plan, interpret=False)
        compiled = step.lower(sim.dd.abstract_arrays(), 8).compile()
        two = step.lower(sim.dd.abstract_arrays(), 2).compile().as_text()
    finally:
        jax.config.update("jax_enable_x64", x64_was)
    assert (plan["route"], plan["plane_window"], plan["tile_rows"], plan["y_tiles"]) == (
        "plane", "interior", 128, 4), plan
    assert plan["plane_lanes"] == "window", plan
    assert sp.plane_lane_forms(plan, 8) == (
        ((True, False), 1), ((False, False), 6), ((False, True), 1))
    text, memory = compiled.as_text(), compiled.memory_analysis()
    calls = [l.lstrip() for l in text.splitlines() if "custom-call(" in l and "tpu_custom_call" in l]
    passes = [l for l in calls if l.startswith("%stream_plane_pass")]
    assert len(passes) == 3 and len([l for l in calls if l.startswith("%blend_planes")]) == 3 * 18
    assert len(re.findall(r"^\s*%stream_plane_pass\S* = .*custom-call\(", two, re.M)) == 2
    for call in passes:
        assert call.split(" custom-call(")[0].count("f32[514,514,514]") == 19
        for k in range(19):
            assert f"{{{k}}}: ({k + 1}, {{}})" in call, k  # result k IS operand 1 + k
    assert not re.findall(r"=\s+f32\[514,514,514\]\S*\s+copy\(", text)
    assert memory.temp_size_in_bytes == 0 and memory.argument_size_in_bytes == 13_000_499_200 + 0


@pytest.mark.slow  # tier-2 with its siblings: a real-TPU-compiler AOT compile
# at the benchmark's size (10 s)
def test_lbm_512x4_step_runs_in_place_beside_a_split_y(monkeypatch):
    """The four-chip lattice-Boltzmann cell's dispatch as the chip's compiler
    leaves it (ISSUE 53): 1024 x 1024 x 512 on mesh [2,2,1] through the normal
    planner for a described v5e:2x2 -- THREE ``stream_plane_pass`` custom calls
    (ISSUES 54, 58: the dispatch's first call, the loop's call narrow both ways,
    the last call) of
    nineteen results each, every one aliased onto its own operand (y tiles of 128
    rows on the ``"interior-z"`` window: Mosaic takes the 101.9 MB the model
    prices), before each the joint x-y exchange of the eighteen moving
    populations as six ``collective-permute``s (four faces, two corner relays)
    with 36 + 36 blends, no ``copy`` of a block, and beside 13.0 GB of arguments
    a chip only the messages: nothing of a block's size (0.68 GB) is temporary."""
    from stencil_tpu.models.lbm import RADIUS, LatticeBoltzmann
    from stencil_tpu.ops import halo_blend
    from stencil_tpu.ops import stream as sm

    devices = _topology_devices()
    monkeypatch.setattr(halo_blend, "pallas_interpret", lambda: False)
    x64_was = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)  # Mosaic index arithmetic is 32-bit
    try:
        sim = LatticeBoltzmann(1024, 1024, 512, devices=devices, seed_words=None)
        sim.dd.realize(allocate=False)
        assert tuple(sim.dd.mesh_dim()) == (2, 2, 1)  # the partitioner's own pick
        plan = sp.plan_stream(sim.dd, RADIUS, "auto", False)
        plan = sp.resolve_stream_plan(sim.dd, sim._kernel, RADIUS, plan, False)
        step = sm._build_stream_step(sim.dd, sim._kernel, RADIUS, plan, interpret=False)
        compiled = step.lower(sim.dd.abstract_arrays(), 6).compile()
    finally:
        jax.config.update("jax_enable_x64", x64_was)
    assert (plan["route"], plan["plane_window"], plan["tile_rows"], plan["y_tiles"]) == (
        "plane", "interior-z", 128, 4), plan
    assert (plan["wired"], plan["joint"], plan["wired_edges"], plan["wire_bytes"]) == (
        "xy", "xy", ("xy",), (4 * 18 * 514 * 514 + 2 * 18 * 2 * 514) * 4), plan
    text, memory = compiled.as_text(), compiled.memory_analysis()
    calls = [l.lstrip() for l in text.splitlines() if "custom-call(" in l and "tpu_custom_call" in l]
    assert plan["plane_lanes"] == "window", plan
    passes = [l for l in calls if l.startswith("%stream_plane_pass")]
    assert len(passes) == 3 and len(calls) == 3 * (1 + 36 + 36)
    assert len([l for l in calls if l.startswith("%blend_planes")]) == 3 * 36
    assert len([l for l in calls if l.startswith("%blend_slab")]) == 3 * 36
    for call in passes:
        assert call.split(" custom-call(")[0].count("f32[514,514,514]") == 19
        for k in range(19):
            assert f"{{{k}}}: ({k + 1}, {{}})" in call, k  # result k IS operand 1 + k
    assert len(re.findall(r"=.*collective-permute-start\(", text)) == 3 * 6
    assert not re.findall(r"=\s+f32\[514,514,514\]\S*\s+copy\(", text)
    assert memory.argument_size_in_bytes == 13_000_499_200
    assert memory.temp_size_in_bytes < 514 * 520 * 640 * 4 // 4  # messages: no quarter of a block
