"""On-device array redistribution: mesh A -> mesh B without a full gather.

The elastic-restore path (io/checkpoint.py) moves a domain between meshes
through a disk round trip: gather interiors to host, re-scatter onto the
new mesh.  This module is the IN-MEMORY generalization of that re-scatter
("Memory-efficient array redistribution through portable collective
communication", PAPERS.md arxiv 2112.01075): the sharded interior state
moves from the source mesh to the target mesh as a SCHEDULE of portable
collectives — one ``lax.ppermute`` of a bounded staging buffer per round —
with peak per-chip memory bounded by a constant number of shard-sized
buffers.  No chip ever materializes more than its own source block, its
own target block, and the round's staging chunks.

The schedule, planned entirely on host (``plan_redistribution``):

1. Both partitions are padded equal splits with a last-shard remainder
   (``DistributedDomain.realize``'s rule), so the intersection of any
   source shard's VALID interior with any target shard's is one global
   rectangle — the **chunk** that must travel from source chip i to
   target chip j.
2. Chunks are grouped into **rounds** where every chip appears at most
   once as a sender and once as a receiver — each round is one permutation,
   i.e. one ``ppermute`` over the 1-D **union mesh** (source ∪ target
   devices).  Chips without a chunk in a round run the same program on
   garbage and mask it away (SPMD uniformity).
3. Within a round all chunks pad to the round's elementwise-max shape (the
   **staging buffer**, never larger than a shard); per-rank offset tables
   drive the slicing, the in-buffer alignment roll, and the receiver's
   masked blend — all traced through ``lax.axis_index`` lookups so the
   program is one jaxpr for every rank.

The traced program is machine-checked by the ``redistribute-bounded``
program contract (stencil_tpu/analysis): every intermediate inside the
shard-mapped body stays under a constant multiple of the shard size, and
no gathering collective appears anywhere.

The result is bitwise-identical to checkpoint-elastic-restore: target
blocks are zero-initialized and only valid interiors are written — exactly
``set_quantity``'s scatter — and values move at the STORED dtype (bf16
storage included), so not a single ulp is touched in flight.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np

from stencil_tpu.core.dim3 import Dim3

#: the staging-memory bound the redistribute-bounded contract enforces:
#: no intermediate in the shard-mapped body may exceed this many times the
#: larger of the source/target block sizes (the alignment roll's concat
#: doubles one staging buffer; everything else is <= one block)
STAGING_BOUND_FACTOR = 3

#: the 1-D union-mesh axis every redistribution ppermute rides
UNION_AXIS = "r"


class ReshardImpossibleError(ValueError):
    """The requested target mesh cannot receive this domain (no admissible
    partition, shard smaller than the shell, source buffers already
    consumed/gone).  The supervisor answers with the checkpoint-elastic-
    restore fallback; direct callers see a pointed error."""


@dataclasses.dataclass(frozen=True)
class SideGeometry:
    """One side of a redistribution: the padded-equal-split facts that
    place every shard's valid interior in global coordinates."""

    dim: Tuple[int, int, int]  # mesh extent per axis
    n: Tuple[int, int, int]  # per-shard interior (padded equal split)
    raw: Tuple[int, int, int]  # allocated shard extent (interior + shell)
    lo: Tuple[int, int, int]  # shell offset of the interior in the block
    valid_last: Tuple[Optional[int], Optional[int], Optional[int]]
    devices: Tuple  # flattened device grid, C order over (x, y, z)

    @classmethod
    def of_domain(cls, dd) -> "SideGeometry":
        dim = dd.placement.dim()
        raw = dd.local_spec().raw_size()
        lo = dd._shell_radius.lo()
        return cls(
            dim=(dim.x, dim.y, dim.z),
            n=tuple(dd.local_spec().sz),
            raw=(raw.x, raw.y, raw.z),
            lo=(lo.x, lo.y, lo.z),
            valid_last=tuple(dd._valid_last),
            devices=tuple(dd.mesh.devices.flat),
        )

    def n_shards(self) -> int:
        return self.dim[0] * self.dim[1] * self.dim[2]

    def shard_index(self, flat: int) -> Tuple[int, int, int]:
        dx, dy, dz = self.dim
        return (flat // (dy * dz), (flat // dz) % dy, flat % dz)

    def valid(self, idx: Tuple[int, int, int]) -> Tuple[int, int, int]:
        return tuple(
            self.valid_last[a]
            if (idx[a] == self.dim[a] - 1 and self.valid_last[a] is not None)
            else self.n[a]
            for a in range(3)
        )


@dataclasses.dataclass(frozen=True)
class ChunkMove:
    """One rectangle travelling from source shard to target shard, in
    block-local coordinates on both ends."""

    src_rank: int  # union-mesh rank holding the source shard
    dst_rank: int  # union-mesh rank holding the target shard
    src_off: Tuple[int, int, int]  # offset inside the source block
    dst_off: Tuple[int, int, int]  # offset inside the target block
    size: Tuple[int, int, int]


@dataclasses.dataclass(frozen=True)
class Round:
    """One permutation round: a staging shape plus per-rank host tables
    (rows indexed by union rank; non-participants carry zero rows and a
    zero mask extent, so every rank runs the same traced program)."""

    staging: Tuple[int, int, int]
    pairs: Tuple[Tuple[int, int], ...]  # ppermute (src, dst) routing
    send_start: np.ndarray  # (R, 3) clamped dynamic_slice starts
    send_shift: np.ndarray  # (R, 3) in-buffer alignment roll
    recv_start: np.ndarray  # (R, 3) clamped write-window starts
    recv_pos: np.ndarray  # (R, 3) valid-data offset inside the window
    recv_size: np.ndarray  # (R, 3) valid extent (zeros = not a receiver)


@dataclasses.dataclass(frozen=True)
class RedistributionPlan:
    """The full host-side schedule for one (size, mesh A, mesh B) move."""

    size: Tuple[int, int, int]
    src: SideGeometry
    dst: SideGeometry
    union_devices: Tuple  # source ∪ target devices, source order first
    src_rank: Dict[int, int]  # source flat shard -> union rank
    dst_rank: Dict[int, int]  # target flat shard -> union rank
    rounds: Tuple[Round, ...]

    def moved_cells(self) -> int:
        return int(np.prod(self.size))

    def bound_bytes(self, itemsize: int, cell_count: int = 1) -> int:
        """The per-chip staging bound the contract enforces for a quantity
        of this itemsize: STAGING_BOUND_FACTOR x the larger block."""
        blk = max(int(np.prod(self.src.raw)), int(np.prod(self.dst.raw)))
        return STAGING_BOUND_FACTOR * blk * cell_count * itemsize


def _chunks(src: SideGeometry, dst: SideGeometry,
            src_rank: Dict[int, int], dst_rank: Dict[int, int]) -> List[ChunkMove]:
    """Every (source shard ∩ target shard) valid-interior rectangle."""
    out: List[ChunkMove] = []
    for jf in range(dst.n_shards()):
        jidx = dst.shard_index(jf)
        jv = dst.valid(jidx)
        jlo = [jidx[a] * dst.n[a] for a in range(3)]
        jhi = [jlo[a] + jv[a] for a in range(3)]
        for if_ in range(src.n_shards()):
            iidx = src.shard_index(if_)
            iv = src.valid(iidx)
            ilo = [iidx[a] * src.n[a] for a in range(3)]
            ihi = [ilo[a] + iv[a] for a in range(3)]
            glo = [max(ilo[a], jlo[a]) for a in range(3)]
            ghi = [min(ihi[a], jhi[a]) for a in range(3)]
            if any(ghi[a] <= glo[a] for a in range(3)):
                continue
            out.append(
                ChunkMove(
                    src_rank=src_rank[if_],
                    dst_rank=dst_rank[jf],
                    src_off=tuple(
                        src.lo[a] + glo[a] - ilo[a] for a in range(3)
                    ),
                    dst_off=tuple(
                        dst.lo[a] + glo[a] - jlo[a] for a in range(3)
                    ),
                    size=tuple(ghi[a] - glo[a] for a in range(3)),
                )
            )
    return out


def _permutation_rounds(chunks: List[ChunkMove]) -> List[List[ChunkMove]]:
    """Greedy split into rounds with unique senders AND unique receivers —
    the ppermute constraint (bin/_common._dst_unique_rounds' shape)."""
    rounds: List[List[ChunkMove]] = []
    for c in chunks:
        for r in rounds:
            if all(q.src_rank != c.src_rank and q.dst_rank != c.dst_rank for q in r):
                r.append(c)
                break
        else:
            rounds.append([c])
    return rounds


def _round_tables(group: List[ChunkMove], n_ranks: int,
                  src: SideGeometry, dst: SideGeometry) -> Round:
    staging = tuple(
        max(c.size[a] for c in group) for a in range(3)
    )
    send_start = np.zeros((n_ranks, 3), np.int32)
    send_shift = np.zeros((n_ranks, 3), np.int32)
    recv_start = np.zeros((n_ranks, 3), np.int32)
    recv_pos = np.zeros((n_ranks, 3), np.int32)
    recv_size = np.zeros((n_ranks, 3), np.int32)
    for c in group:
        for a in range(3):
            # dynamic_slice clamps a start so the window fits — pass the
            # CLAMPED start so host and device agree on where data sits
            ss = min(c.src_off[a], src.raw[a] - staging[a])
            ws = min(c.dst_off[a], dst.raw[a] - staging[a])
            spos = c.src_off[a] - ss  # data offset inside the staging buffer
            rpos = c.dst_off[a] - ws  # where the receiver needs it
            send_start[c.src_rank, a] = ss
            send_shift[c.src_rank, a] = rpos - spos
            recv_start[c.dst_rank, a] = ws
            recv_pos[c.dst_rank, a] = rpos
            recv_size[c.dst_rank, a] = c.size[a]
    return Round(
        staging=staging,
        pairs=tuple((c.src_rank, c.dst_rank) for c in group),
        send_start=send_start,
        send_shift=send_shift,
        recv_start=recv_start,
        recv_pos=recv_pos,
        recv_size=recv_size,
    )


def plan_redistribution(size, src: SideGeometry, dst: SideGeometry) -> RedistributionPlan:
    """Host-side schedule: union device order, chunk decomposition,
    permutation rounds with their staging shapes and offset tables."""
    size = tuple(Dim3.of(size)) if not isinstance(size, tuple) else size
    union: List = list(src.devices)
    have = {d.id for d in union}
    for d in dst.devices:
        if d.id not in have:
            union.append(d)
            have.add(d.id)
    rank_of = {d.id: i for i, d in enumerate(union)}
    src_rank = {f: rank_of[src.devices[f].id] for f in range(src.n_shards())}
    dst_rank = {f: rank_of[dst.devices[f].id] for f in range(dst.n_shards())}
    chunks = _chunks(src, dst, src_rank, dst_rank)
    rounds = [
        _round_tables(g, len(union), src, dst)
        for g in _permutation_rounds(chunks)
    ]
    return RedistributionPlan(
        size=tuple(size),
        src=src,
        dst=dst,
        union_devices=tuple(union),
        src_rank=src_rank,
        dst_rank=dst_rank,
        rounds=tuple(rounds),
    )


def _union_mesh(plan: RedistributionPlan):
    import numpy as _np
    from jax.sharding import Mesh

    return Mesh(_np.array(plan.union_devices), (UNION_AXIS,))


def _aligned_roll(x, shift, axis: int, extent: int):
    """Cyclic roll by a TRACED per-rank shift: double the buffer along
    ``axis`` and slice the rotated window back out.  The concat is the one
    place the staging footprint exceeds a single buffer (2x, inside the
    STAGING_BOUND_FACTOR)."""
    import jax.numpy as jnp
    from jax import lax

    if extent == 1:
        return x  # a 1-wide axis cannot be misaligned
    doubled = jnp.concatenate([x, x], axis=axis)
    start = [jnp.int32(0)] * doubled.ndim
    start[axis] = jnp.mod(
        jnp.int32(extent) - shift.astype(jnp.int32), jnp.int32(extent)
    )
    sizes = list(x.shape)
    return lax.dynamic_slice(doubled, start, sizes)


def build_redistribute_fn(plan: RedistributionPlan, components: Tuple[int, ...], dtype):
    """The jitted collective schedule for one quantity signature.

    Takes the ``(R, *components, *src.raw)`` stacked source blocks sharded
    over the union mesh; returns the ``(R, *components, *dst.raw)`` stacked
    target blocks (zero shells, valid interiors installed) on the same
    mesh.  Ranks outside the target mesh return zero blocks that are
    simply dropped at re-assembly.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from stencil_tpu import telemetry
    from stencil_tpu.telemetry import names as tm
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = _union_mesh(plan)
    ncomp = len(components)
    rounds = plan.rounds
    dst_raw = plan.dst.raw

    def per_shard(src_block):
        # src_block: (1, *components, *src.raw) — this rank's stacked slice
        rank = lax.axis_index(UNION_AXIS)
        block = src_block[0]
        out = jnp.zeros(components + dst_raw, dtype=dtype)
        with telemetry.annotate(tm.SPAN_RESHARD):
            for rnd in rounds:
                sstart = jnp.asarray(rnd.send_start)[rank]
                sshift = jnp.asarray(rnd.send_shift)[rank]
                rstart = jnp.asarray(rnd.recv_start)[rank]
                rpos = jnp.asarray(rnd.recv_pos)[rank]
                rsize = jnp.asarray(rnd.recv_size)[rank]
                chunk = lax.dynamic_slice(
                    block,
                    [jnp.int32(0)] * ncomp + [sstart[a] for a in range(3)],
                    components + rnd.staging,
                )
                for a in range(3):
                    chunk = _aligned_roll(
                        chunk, sshift[a], ncomp + a, rnd.staging[a]
                    )
                moved = lax.ppermute(chunk, UNION_AXIS, rnd.pairs)
                # masked blend of the valid extent into the write window:
                # 1-D iotas keep the mask at 1 B/cell, and ranks with a
                # zero recv_size blend nothing (the SPMD-uniform no-op)
                masks = []
                for a in range(3):
                    i = jnp.arange(rnd.staging[a], dtype=jnp.int32)
                    masks.append((i >= rpos[a]) & (i < rpos[a] + rsize[a]))
                mask = (
                    masks[0][:, None, None]
                    & masks[1][None, :, None]
                    & masks[2][None, None, :]
                )
                window = lax.dynamic_slice(
                    out,
                    [jnp.int32(0)] * ncomp + [rstart[a] for a in range(3)],
                    components + rnd.staging,
                )
                window = jnp.where(mask, moved, window)
                # stencil-lint: disable=sliver-dus one-shot reshard staging-window write, not a per-step halo path; the traced form is bounds-checked by the redistribute-bounded contract instead
                out = lax.dynamic_update_slice(
                    out,
                    window,
                    [jnp.int32(0)] * ncomp + [rstart[a] for a in range(3)],
                )
        return out[None]

    fn = shard_map(
        per_shard,
        mesh=mesh,
        in_specs=P(UNION_AXIS),
        out_specs=P(UNION_AXIS),
        # the offset tables/masks are replicated literals blended into
        # varying blocks — the packed exchange routes run with the same
        # setting for the same reason
        check_vma=False,
    )
    return jax.jit(fn), mesh


def _stack_source(plan: RedistributionPlan, arr, components, dtype):
    """Reinterpret the source global array's per-device shards as the
    ``(R, ...)`` stacked union-mesh array WITHOUT any host round trip.
    Union ranks outside the source mesh contribute one zero block each
    (shard-sized staging, inside the memory bound)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = _union_mesh(plan)
    per_shard = components + plan.src.raw
    by_dev = {s.device.id: s.data for s in arr.addressable_shards}
    blocks = []
    for d in plan.union_devices:
        data = by_dev.get(d.id)
        if data is None:
            blocks.append(
                jax.device_put(jnp.zeros((1,) + per_shard, dtype=dtype), d)
            )
        else:
            blocks.append(jnp.reshape(data, (1,) + per_shard))
    shape = (len(plan.union_devices),) + per_shard
    return jax.make_array_from_single_device_arrays(
        shape, NamedSharding(mesh, P(UNION_AXIS)), blocks
    ), mesh


def _assemble_target(plan: RedistributionPlan, stacked, components, dtype,
                     dst_mesh, dst_spec):
    """Per-device target blocks -> the global raw array on the target
    mesh (the sharded layout ``realize()`` allocates)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    by_dev = {s.device.id: s.data for s in stacked.addressable_shards}
    dim = plan.dst.dim
    raw = plan.dst.raw
    gshape = components + tuple(dim[a] * raw[a] for a in range(3))
    sharding = NamedSharding(dst_mesh, dst_spec)
    blocks = []
    for f in range(plan.dst.n_shards()):
        dev = plan.dst.devices[f]
        data = by_dev[dev.id]
        blocks.append(jnp.reshape(data, components + raw))
    # order blocks by the sharding's device->index map so assembly is
    # explicit about which block is which global slice
    index_map = sharding.addressable_devices_indices_map(gshape)
    ordered = []
    by_target_dev = {
        plan.dst.devices[f].id: blocks[f] for f in range(plan.dst.n_shards())
    }
    for dev in index_map:
        ordered.append(by_target_dev[dev.id])
    return jax.make_array_from_single_device_arrays(
        gshape, sharding, ordered
    )


def redistribute_array(plan: RedistributionPlan, arr, components, dtype,
                       dst_mesh, dst_spec, fn=None):
    """Move ONE quantity's global raw array across the plan.  Returns the
    new global array on the target mesh; the source array is left intact
    (the caller installs the result and drops its references).  ``fn``
    reuses a prebuilt schedule: jitted functions are fresh closures per
    ``build_redistribute_fn`` call, so a multi-quantity caller must cache
    per (components, dtype) signature or pay one trace+compile per
    quantity (``DistributedDomain.reshard`` does)."""
    components = tuple(components)
    stacked, _ = _stack_source(plan, arr, components, dtype)
    if fn is None:
        fn, _ = build_redistribute_fn(plan, components, dtype)
    out = fn(stacked)
    return _assemble_target(plan, out, components, dtype, dst_mesh, dst_spec)


def redistribution_program(plan: RedistributionPlan, components=(), dtype=None):
    """(fn, example_arg, meta) for tracing/verification: the exact jitted
    schedule ``redistribute_array`` runs, plus the staging bound the
    ``redistribute-bounded`` contract enforces on its traced form."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    dtype = jnp.float32 if dtype is None else dtype
    components = tuple(components)
    fn, mesh = build_redistribute_fn(plan, components, dtype)
    shape = (len(plan.union_devices),) + components + plan.src.raw
    example = jax.ShapeDtypeStruct(
        shape, dtype, sharding=NamedSharding(mesh, P(UNION_AXIS))
    )
    cell = 1
    for c in components:
        cell *= c
    meta = {
        "bound_bytes": plan.bound_bytes(jnp.dtype(dtype).itemsize, cell),
        "rounds": len(plan.rounds),
        "union_ranks": len(plan.union_devices),
    }
    return fn, example, meta
