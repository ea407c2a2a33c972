"""Halo pack/unpack — fused per-neighbor message buffers.

Parity target: ``DevicePacker``/``DeviceUnpacker`` (reference
include/stencil/packer.cuh:71-366) and the pack/unpack kernels
(pack_kernel.cuh:5-46, copy.cuh:26-83).  The reference fuses all quantities ×
all directions of one neighbor into ONE contiguous aligned device buffer:
for each message (sorted by direction), for each quantity, the offset is
aligned to the element size and the ``halo_extent(-dir)`` region is appended
(packer.cuh:146-160) — the ``-dir`` convention: the *receiver's* halo width
rules the message size (packer.cuh:91-93).

TPU design: the production exchange (ops/exchange.py) has two message
shapes.  The ``direct`` route sends slabs as sliced — XLA fuses the slicing
into the ppermute, playing the role of the pack kernel.  The packed routes
(``zpack_*`` / ``yzpack_*``, tuner axes since the exchange-route PRs) send
the thin shells through THIS module's pack pipelines instead, one twin per
shell ORIENTATION:

* **z shell** (``pack_zshell_*`` / ``unpack_zshell_*``): on the
  (8,128)-tiled layout a thin-z sliver read/write is ~64×-amplified
  (PERF_NOTES "Thin z-region access"), so the shell leaves HBM as whole
  x-plane DMAs, is cut and transposed in VMEM, and travels LANE-major as
  ``(2m, Y, Xpad)`` — the thin ``2m`` extent becomes the untiled leading
  dim, X (whole, well-shaped, lane-padded to a 128 multiple with dead
  columns the unpack never reads) becomes the lane dim.
* **y shell** (``pack_yshell_*`` / ``unpack_yshell_*``): the y window is a
  SUBLANE sliver — ``2m`` rows of the 8-row (f32) sublane granule, so a
  radius-r y exchange through the big array is ~8/(2r)-amplified
  (PERF_NOTES "Thin y-region access").  The same move, one axis over: the
  shell leaves HBM as whole x-planes, the row window is cut in VMEM, and
  the message travels SUBLANE-major as ``(2m, X, Z)`` — the thin extent is
  again the untiled leading dim, X becomes the (padding-tolerant) sublane
  dim, and Z stays the lane dim untouched, so no explicit pad is needed
  (ragged sublane extents are nearly free, PERF_NOTES "Ragged lane
  extents").

Both orientations keep the invariant that the BIG array is only ever read
(and, on the pallas twins, written) as whole x-planes; the thin cut exists
only in VMEM and in the small message buffer.
This module also holds (a) parity of the reference's buffer-layout math
(``PackPlan``, byte-exact with the reference incl. the 264-byte multi-dtype
case, test_cuda_packer.cu:74-92) and (b) the ``bench-pack`` kernel
benchmark.  Two backends:

* ``xla`` — gather/scatter via slice + bitcast + concat; XLA fuses this into
  a handful of copies (the analog of the reference's CUDA-Graph replay being
  jit's compilation cache, packer.cuh:168-187).
* ``pallas`` — per-plane pipelined kernels: the pallas grid streams whole
  x-planes HBM -> VMEM (lane-tile-aligned movement) and the VPU cuts or
  patches the unaligned halo window in VMEM.

Slab-internal element order is C-order on (x, y, z) arrays (z fastest); the
reference's flatten is x fastest (pack_kernel.cuh:16-40).  Offsets and sizes
are identical; only the within-slab byte order differs (both sides of our
exchange use the same order, so the invariant is preserved).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from stencil_tpu.core.dim3 import Dim3
from stencil_tpu.core.geometry import LocalSpec
from stencil_tpu.telemetry import names as tm


def next_align_of(x: int, align: int) -> int:
    """Round ``x`` up to a multiple of ``align`` (reference align.cuh:7)."""
    return (x + align - 1) // align * align


@dataclasses.dataclass(frozen=True)
class PackSlot:
    """One (message, quantity) slice of the packed buffer."""

    direction: Dim3
    quantity: int
    offset: int  # bytes from buffer start (aligned to itemsize)
    pos: Dim3  # allocation-relative source position (interior side)
    unpack_pos: Dim3  # allocation-relative destination position (halo side)
    extent: Dim3
    itemsize: int

    @property
    def nbytes(self) -> int:
        return self.extent.flatten() * self.itemsize


@dataclasses.dataclass(frozen=True)
class PackPlan:
    """Buffer layout for one neighbor's fused message
    (packer.cuh:136-178 prepare)."""

    slots: Tuple[PackSlot, ...]
    size: int  # total bytes

    @staticmethod
    def make(spec: LocalSpec, directions: Sequence, itemsizes: Sequence[int]) -> "PackPlan":
        dirs = sorted((Dim3.of(d) for d in directions))  # sorted by dir (packer.cuh:140)
        slots: List[PackSlot] = []
        size = 0
        for d in dirs:
            for qi, isz in enumerate(itemsizes):
                size = next_align_of(size, isz)
                ext = spec.halo_extent(-d)  # receiver's -d halo width rules
                slots.append(
                    PackSlot(
                        direction=d,
                        quantity=qi,
                        offset=size,
                        pos=spec.halo_pos(d, halo=False),
                        unpack_pos=spec.halo_pos(-d, halo=True),
                        extent=ext,
                        itemsize=isz,
                    )
                )
                size += ext.flatten() * isz
        if size == 0:
            raise ValueError("zero-size packer was prepared")  # packer.cuh:162
        return PackPlan(tuple(slots), size)


def _slab(block: jax.Array, pos: Dim3, ext: Dim3) -> jax.Array:
    return block[
        pos.x : pos.x + ext.x,
        pos.y : pos.y + ext.y,
        pos.z : pos.z + ext.z,
    ]


def _to_bytes(slab: jax.Array) -> jax.Array:
    """Flatten a typed slab to its uint8 representation."""
    if slab.dtype == jnp.uint8:
        return slab.ravel()
    return lax.bitcast_convert_type(slab, jnp.uint8).ravel()


def _from_bytes(buf: jax.Array, ext: Dim3, dtype) -> jax.Array:
    """Inverse of ``_to_bytes`` for one slab's bytes."""
    dtype = jnp.dtype(dtype)
    if dtype == jnp.uint8:
        return buf.reshape(tuple(ext))
    shaped = buf.reshape(tuple(ext) + (dtype.itemsize,))
    return lax.bitcast_convert_type(shaped, dtype)


def make_pack_fn(spec: LocalSpec, directions: Sequence, dtypes: Sequence):
    """Jitted ``pack(blocks) -> uint8 buffer`` over one subdomain's raw blocks
    (one per quantity, each of shape ``spec.raw_size()``)."""
    dtypes = [jnp.dtype(t) for t in dtypes]
    plan = PackPlan.make(spec, directions, [t.itemsize for t in dtypes])

    @jax.jit
    def pack(blocks: Sequence[jax.Array]) -> jax.Array:
        parts = []
        cursor = 0
        for slot in plan.slots:
            if slot.offset != cursor:  # alignment gap
                parts.append(jnp.zeros((slot.offset - cursor,), jnp.uint8))
            parts.append(_to_bytes(_slab(blocks[slot.quantity], slot.pos, slot.extent)))
            cursor = slot.offset + slot.nbytes
        return jnp.concatenate(parts)

    return pack, plan


def make_unpack_fn(spec: LocalSpec, directions: Sequence, dtypes: Sequence):
    """Jitted ``unpack(buffer, blocks) -> blocks`` writing each slot into the
    halo shell (copy.cuh:26-64 semantics)."""
    dtypes = [jnp.dtype(t) for t in dtypes]
    plan = PackPlan.make(spec, directions, [t.itemsize for t in dtypes])

    @partial(jax.jit, donate_argnums=1)
    def unpack(buf: jax.Array, blocks: Sequence[jax.Array]) -> List[jax.Array]:
        out = list(blocks)
        for slot in plan.slots:
            chunk = buf[slot.offset : slot.offset + slot.nbytes]
            slab = _from_bytes(chunk, slot.extent, dtypes[slot.quantity])
            p, e = slot.unpack_pos, slot.extent
            out[slot.quantity] = out[slot.quantity].at[
                p.x : p.x + e.x, p.y : p.y + e.y, p.z : p.z + e.z
            ].set(slab)
        return out

    return unpack, plan


# --- Pallas backend ----------------------------------------------------------


def pallas_pack_slab(block: jax.Array, pos: Dim3, ext: Dim3, interpret: bool = False):
    """Pack one halo slab with an explicit DMA kernel: the block stays in
    HBM/ANY; each grid step DMAs one full x-plane into VMEM, then the VPU
    slices out the (possibly tiling-unaligned) halo window (pallas_guide.md
    "Async DMA (Local Copies)").  HBM DMAs must be lane-tile aligned, so the
    plane is copied whole and the unaligned cut happens in VMEM.  This is the
    hand-written analog of the reference's grid-stride ``grid_pack``
    (pack_kernel.cuh:16-40)."""
    from jax.experimental import pallas as pl

    raw_y, raw_z = block.shape[1], block.shape[2]

    def kernel(src_ref, out_ref):
        out_ref[0] = src_ref[0, pos.y : pos.y + ext.y, pos.z : pos.z + ext.z]

    return pl.pallas_call(
        kernel,
        name=tm.KERNEL_PACK_SLAB,
        grid=(ext.x,),
        # one full x-plane per step: HBM->VMEM movement must be lane-tile
        # aligned, so the pipeline streams whole planes and the VPU cuts the
        # (possibly unaligned) halo window in VMEM
        in_specs=[pl.BlockSpec((1, raw_y, raw_z), lambda i: (pos.x + i, 0, 0))],
        out_specs=pl.BlockSpec((1, ext.y, ext.z), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(tuple(ext), block.dtype),
        interpret=interpret,
    )(block)


def pallas_unpack_slab(
    block: jax.Array, slab: jax.Array, pos: Dim3, ext: Dim3, interpret: bool = False
):
    """Scatter a packed slab back into the halo shell at ``pos`` with per-plane
    DMA, updating ``block`` in place (input_output_aliases — the analog of
    unpacking into the existing allocation, copy.cuh:64-83)."""
    from jax.experimental import pallas as pl

    raw_y, raw_z = block.shape[1], block.shape[2]

    def kernel(blk_ref, slab_ref, out_ref):
        # read-modify-write one full x-plane: copy it through, then patch the
        # halo window (unwritten planes keep the aliased input's data)
        out_ref[0] = blk_ref[0]
        out_ref[0, pos.y : pos.y + ext.y, pos.z : pos.z + ext.z] = slab_ref[0]

    plane = pl.BlockSpec((1, raw_y, raw_z), lambda i: (pos.x + i, 0, 0))
    return pl.pallas_call(
        kernel,
        name=tm.KERNEL_UNPACK_SLAB,
        grid=(ext.x,),
        in_specs=[plane, pl.BlockSpec((1, ext.y, ext.z), lambda i: (i, 0, 0))],
        out_specs=plane,
        out_shape=jax.ShapeDtypeStruct(block.shape, block.dtype),
        input_output_aliases={0: 0},
        interpret=interpret,
    )(block, slab)


def make_pack_fn_pallas(spec: LocalSpec, directions: Sequence, dtype, interpret: bool = False):
    """Pallas-backed ``pack(block) -> list of slabs`` for one quantity.  Each
    direction's slab is produced by its own DMA kernel; the caller may ravel
    and concatenate for a flat buffer (layout per ``PackPlan``)."""
    dtype = jnp.dtype(dtype)
    plan = PackPlan.make(spec, directions, [dtype.itemsize])

    @jax.jit
    def pack(block: jax.Array) -> List[jax.Array]:
        return [
            pallas_pack_slab(block, slot.pos, slot.extent, interpret=interpret)
            for slot in plan.slots
        ]

    return pack, plan


def make_unpack_fn_pallas(spec: LocalSpec, directions: Sequence, dtype, interpret: bool = False):
    """Pallas-backed ``unpack(block, slabs) -> block`` (single quantity)."""
    dtype = jnp.dtype(dtype)
    plan = PackPlan.make(spec, directions, [dtype.itemsize])

    @jax.jit
    def unpack(block: jax.Array, slabs: Sequence[jax.Array]) -> jax.Array:
        for slot, slab in zip(plan.slots, slabs):
            block = pallas_unpack_slab(
                block, slab, slot.unpack_pos, slot.extent, interpret=interpret
            )
        return block

    return unpack, plan


# --- Production z-shell pack route -------------------------------------------
#
# The exchange's packed z route (ops/exchange.py ``zpack_*``): the z shell of
# a (X, Y, Z) shard travels as a lane-major ``(depth, Y, Xpad)`` buffer.
# Rationale (PERF_NOTES "Thin z-region access" / "Block SHAPE orientation"):
# a (X, Y, depth) z-sliver has ``depth`` lanes — lane-padded to 128, every
# read/write of it through the big array costs a whole tile-column pass
# (~64× amplification at depth 2).  z-major, the lane dim is X (whole, well
# shaped, padded up to a 128 multiple with dead columns the unpack never
# reads), and the thin ``depth`` extent sublane-pads to at most 8.


def lane_pad(n: int) -> int:
    """Round a lane extent up to the (8,128) tiling's 128-lane multiple."""
    return next_align_of(n, 128)


def zshell_buffer_shape(block_shape, depth: int):
    """Shape of one z-shell message buffer for a ``(X, Y, Z)`` block."""
    X, Y = block_shape[0], block_shape[1]
    return (depth, Y, lane_pad(X))


def pack_zshell_xla(block: jax.Array, z0: int, depth: int) -> jax.Array:
    """``block[:, :, z0:z0+depth]`` as the lane-major ``(depth, Y, Xpad)``
    message buffer, via plain XLA (slice + transpose + lane pad).  XLA is
    free to fuse the reshaping into the ppermute operand — a measurably
    different message shape from ``direct``, hence its own tuner candidate."""
    X = block.shape[0]
    buf = jnp.transpose(block[:, :, z0 : z0 + depth], (2, 1, 0))
    pad = lane_pad(X) - X
    if pad:
        buf = jnp.pad(buf, ((0, 0), (0, 0), (0, pad)))
    return buf


def zshell_to_slab(buf: jax.Array, x: int) -> jax.Array:
    """Inverse of the pack transpose: the received ``(depth, Y, Xpad)``
    buffer as an ``(x, Y, depth)`` slab (dead pad columns dropped) — the
    shape the exchange's existing halo-write path (blend kernel or set)
    consumes.  Only the small message buffer is read thin-z here, never the
    big array."""
    return jnp.transpose(buf[:, :, :x], (2, 1, 0))


def pack_zshell_pallas(
    block: jax.Array, z0: int, depth: int, interpret: bool = False
) -> jax.Array:
    """Pallas z-shell pack: grid-stream whole x-planes HBM -> VMEM (lane-
    tile-aligned movement), cut the ``[z0, z0+depth)`` window and transpose
    it z-major on the VPU (small (Y, depth) <-> (depth, Y) in-kernel
    transposes are supported — PERF_NOTES "Mosaic limits"), land each
    plane's column in the ``(depth, Y, Xpad)`` buffer.  Pad columns past X
    are never visited (their contents are dead; the unpack never reads
    them)."""
    from jax.experimental import pallas as pl

    X, Y, Z = block.shape

    def kernel(src_ref, out_ref):
        out_ref[:, :, 0] = src_ref[0, :, z0 : z0 + depth].T

    return pl.pallas_call(
        kernel,
        name=tm.KERNEL_PACK_ZSHELL,
        grid=(X,),
        in_specs=[pl.BlockSpec((1, Y, Z), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((depth, Y, 1), lambda i: (0, 0, i)),
        out_shape=jax.ShapeDtypeStruct(zshell_buffer_shape(block.shape, depth), block.dtype),
        interpret=interpret,
    )(block)


def unpack_zshell_pallas(
    block: jax.Array, buf: jax.Array, z0: int, depth: int, interpret: bool = False
) -> jax.Array:
    """Blend a received ``(depth, Y, Xpad)`` z-shell buffer into
    ``block[:, :, z0:z0+depth]`` — aliased read-modify-write of whole
    x-planes (``input_output_aliases``), the transpose back happening in
    VMEM.  The big array is written plane-at-a-time in its native tiled
    layout; the thin-z patch exists only inside VMEM, so the ``sliver-dus``
    relayout trap is impossible by construction."""
    from jax.experimental import pallas as pl

    X, Y, Z = block.shape

    def kernel(blk_ref, buf_ref, out_ref):
        out_ref[0] = blk_ref[0]
        out_ref[0, :, z0 : z0 + depth] = buf_ref[:, :, 0].T

    plane = pl.BlockSpec((1, Y, Z), lambda i: (i, 0, 0))
    return pl.pallas_call(
        kernel,
        name=tm.KERNEL_UNPACK_ZSHELL,
        grid=(X,),
        in_specs=[plane, pl.BlockSpec((depth, Y, 1), lambda i: (0, 0, i))],
        out_specs=plane,
        out_shape=jax.ShapeDtypeStruct(block.shape, block.dtype),
        input_output_aliases={0: 0},
        interpret=interpret,
    )(block, buf)


# --- Production y-shell pack route -------------------------------------------
#
# The exchange's packed y sweep (ops/exchange.py ``yzpack_*``): the y shell
# of a (X, Y, Z) shard travels as a sublane-major ``(depth, X, Z)`` buffer.
# Rationale (PERF_NOTES "Thin y-region access"): a (X, depth, Z) y-sliver
# has ``depth`` sublanes — sublane-padded to the 8-row (f32) granule, every
# read/write of it through the big array costs ~8/depth× its logical bytes.
# Sublane-major, the thin ``depth`` extent is the untiled leading dim, X
# becomes the sublane dim (whole; ragged sublane extents are nearly free),
# and Z stays the lane dim untouched — no explicit pad needed, unlike the
# z twin's lane_pad.


def yshell_buffer_shape(block_shape, depth: int):
    """Shape of one y-shell message buffer for a ``(X, Y, Z)`` block."""
    X, Z = block_shape[0], block_shape[2]
    return (depth, X, Z)


def pack_yshell_xla(block: jax.Array, y0: int, depth: int) -> jax.Array:
    """``block[:, y0:y0+depth, :]`` as the sublane-major ``(depth, X, Z)``
    message buffer, via plain XLA (slice + transpose).  XLA is free to fuse
    the reshaping into the ppermute operand — the y twin of
    ``pack_zshell_xla``."""
    return jnp.transpose(block[:, y0 : y0 + depth, :], (1, 0, 2))


def yshell_to_slab(buf: jax.Array) -> jax.Array:
    """Inverse of the pack transpose: the received ``(depth, X, Z)`` buffer
    as an ``(X, depth, Z)`` slab — the shape the exchange's existing
    halo-write path (blend kernel or set) consumes.  Only the small message
    buffer is read thin-y here, never the big array."""
    return jnp.transpose(buf, (1, 0, 2))


def pack_yshell_pallas(
    block: jax.Array, y0: int, depth: int, interpret: bool = False
) -> jax.Array:
    """Pallas y-shell pack: grid-stream whole x-planes HBM -> VMEM (lane-
    tile-aligned movement), cut the ``[y0, y0+depth)`` row window in VMEM,
    land each plane's rows in the ``(depth, X, Z)`` buffer.  No transpose is
    needed (the row cut keeps Z as the lane dim), so the kernel is a pure
    VMEM window copy — the y twin of ``pack_zshell_pallas``."""
    from jax.experimental import pallas as pl

    X, Y, Z = block.shape

    def kernel(src_ref, out_ref):
        out_ref[:, 0] = src_ref[0, y0 : y0 + depth, :]

    return pl.pallas_call(
        kernel,
        name=tm.KERNEL_PACK_YSHELL,
        grid=(X,),
        in_specs=[pl.BlockSpec((1, Y, Z), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((depth, 1, Z), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct(
            yshell_buffer_shape(block.shape, depth), block.dtype
        ),
        interpret=interpret,
    )(block)


def unpack_yshell_pallas(
    block: jax.Array, buf: jax.Array, y0: int, depth: int, interpret: bool = False
) -> jax.Array:
    """Blend a received ``(depth, X, Z)`` y-shell buffer into
    ``block[:, y0:y0+depth, :]`` — aliased read-modify-write of whole
    x-planes, the row patch happening in VMEM.  Like the z twin, the big
    array is written plane-at-a-time in its native tiled layout; the
    sublane sliver exists only inside VMEM."""
    from jax.experimental import pallas as pl

    X, Y, Z = block.shape

    def kernel(blk_ref, buf_ref, out_ref):
        out_ref[0] = blk_ref[0]
        out_ref[0, y0 : y0 + depth, :] = buf_ref[:, 0]

    plane = pl.BlockSpec((1, Y, Z), lambda i: (i, 0, 0))
    return pl.pallas_call(
        kernel,
        name=tm.KERNEL_UNPACK_YSHELL,
        grid=(X,),
        in_specs=[plane, pl.BlockSpec((depth, 1, Z), lambda i: (0, i, 0))],
        out_specs=plane,
        out_shape=jax.ShapeDtypeStruct(block.shape, block.dtype),
        input_output_aliases={0: 0},
        interpret=interpret,
    )(block, buf)
