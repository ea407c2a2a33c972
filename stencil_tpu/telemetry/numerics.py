"""On-device numerics observatory: fused field-health statistics.

The PR-1 divergence sentinel answered "did a field go NaN?" by gathering
every quantity to the host per check — a full device→host interior copy per
quantity, and an answer that names only a quantity and a cadence step.  T3
(PAPERS.md, arxiv 2401.16677) sets the production bar instead: numerical
health as always-on, fine-grained telemetry whose overhead is low enough to
leave enabled.  This module is that layer:

* :class:`NumericsEngine` builds ONE fused, jitted, sharded program per
  realized domain that computes, per floating quantity, interior-only
  min / max / absmax / mean / L2 (accumulated at >= f32, the PR-7
  f32-accumulate contract) / non-finite count **and the global 3D
  coordinate of the first non-finite cell** — all reduced across the mesh
  with ``psum``/``pmin``/``pmax`` INSIDE the shard_map, so the host
  transfer is O(#quantities) scalars.  Never a gather: the
  ``numerics-bounded`` program contract (``analysis/contracts.py``)
  machine-checks that claim on the canonical matrix.
* The program is memoized per geometry signature (mesh, spec, per-quantity
  ``(components, dtype)`` — the same signature discipline as
  ``DistributedDomain.reshard``'s redistribute-fn cache) and rebuilt
  automatically after a mesh transition (``on_mesh_change``).
* Snapshots land in a bounded in-memory ring (crash reports embed it) and
  run the registered **guardbands** — per-quantity invariants over the
  stats (shipped examples: the jacobi max-principle bound, the astaroth
  magnitude envelope).  Violations emit ``numerics.drift`` events + the
  counter; observe-only by default, ``STENCIL_NUMERICS_ABORT=1`` escalates
  to a classified ``DIVERGENCE``.

Knobs (validated reads): ``STENCIL_NUMERICS_EVERY`` (snapshot cadence in
raw steps through ``run_step``; 0 = off; ``--numerics-every`` on the model
drivers), ``STENCIL_NUMERICS_ABORT`` (guardband escalation).  The
divergence sentinel (``resilience/sentinel.py``) rides the same engine on
its own cadence — a ``DIVERGENCE`` failure now names the quantity, the
global first-non-finite coordinate, and the bracketing step window.

jax-free at import, like the whole telemetry package (the ``jax-import``
lint rule): jax is touched only when a program is actually built.
"""

from __future__ import annotations

import collections
import dataclasses
import math
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

#: snapshots retained in the in-memory ring (crash reports embed the tail;
#: a ring, not a log — long runs must stay O(1) in memory)
RING_SIZE = 16

#: scalar outputs the stats program emits per floating quantity (min, max,
#: absmax, sum, sumsq, finite count, non-finite count, first-bad key) —
#: the numerics-bounded contract bounds the traced program's output count
#: by this
SCALARS_PER_QUANTITY = 8


def _finite_or_none(v) -> Optional[float]:
    """JSON-safe float: non-finite (empty-field inf sentinels, NaN means
    from zero finite cells) becomes None rather than poisoning a document."""
    try:
        f = float(v)
    except (TypeError, ValueError):
        return None
    return f if math.isfinite(f) else None


@dataclasses.dataclass(frozen=True)
class FieldStats:
    """One quantity's interior-only health at a snapshot.  Moment stats
    (``min``/``max``/``absmax``/``mean``/``l2``) are over FINITE interior
    cells (None when none are finite); the non-finite story is carried
    separately by ``nonfinite`` and ``first_nonfinite`` (the global 3D
    coordinate of the first non-finite cell in row-major order, or None)."""

    name: str
    dtype: str
    min: Optional[float]
    max: Optional[float]
    absmax: Optional[float]
    mean: Optional[float]
    l2: Optional[float]
    finite: int
    nonfinite: int
    first_nonfinite: Optional[Tuple[int, int, int]]

    def as_json(self) -> dict:
        d = dataclasses.asdict(self)
        if self.first_nonfinite is not None:
            d["first_nonfinite"] = list(self.first_nonfinite)
        return d


@dataclasses.dataclass(frozen=True)
class NumericsSnapshot:
    """One fused-dispatch health snapshot of every floating quantity."""

    step: Optional[int]
    window: Optional[Tuple[int, int]]
    ts: float
    seconds: float
    stats: Tuple[FieldStats, ...]

    def stat(self, name: str) -> Optional[FieldStats]:
        for s in self.stats:
            if s.name == name:
                return s
        return None

    def as_json(self) -> dict:
        return {
            "step": self.step,
            "window": list(self.window) if self.window is not None else None,
            "ts": self.ts,
            "seconds": round(self.seconds, 6),
            "quantities": {s.name: s.as_json() for s in self.stats},
        }


@dataclasses.dataclass(frozen=True)
class Guardband:
    """A registered invariant over one snapshot's per-quantity stats.

    ``check(stats)`` returns a violation message (the drift event's
    ``why``) or None; ``quantities`` scopes it (None = every floating
    quantity).  Guardbands see FieldStats, never arrays — they run on the
    O(#quantities) host scalars, so a registered band costs nothing on
    device."""

    label: str
    check: Callable[[FieldStats], Optional[str]]
    quantities: Optional[Tuple[str, ...]] = None

    def applies_to(self, name: str) -> bool:
        return self.quantities is None or name in self.quantities


def max_principle(lo: float, hi: float, quantities: Optional[Sequence[str]] = None) -> Guardband:
    """The diffusion max principle: a pure-averaging update (jacobi's
    mean-of-6 with clamped forcing) can never leave the initial value
    band — a cell outside ``[lo, hi]`` is numerical drift, long before
    anything overflows to inf."""

    def check(st: FieldStats) -> Optional[str]:
        if st.min is not None and st.min < lo:
            return f"min {st.min:g} below the max-principle bound {lo:g}"
        if st.max is not None and st.max > hi:
            return f"max {st.max:g} above the max-principle bound {hi:g}"
        return None

    return Guardband(
        label=f"max-principle[{lo:g},{hi:g}]",
        check=check,
        quantities=tuple(quantities) if quantities is not None else None,
    )


def magnitude_envelope(limit: float, quantities: Optional[Sequence[str]] = None) -> Guardband:
    """A per-quantity magnitude envelope: |field| must stay under
    ``limit`` (the astaroth proxy's averaging update is non-expansive on
    its unit-amplitude sin init, so a growing absmax means the numerics
    drifted)."""

    def check(st: FieldStats) -> Optional[str]:
        if st.absmax is not None and st.absmax > limit:
            return f"absmax {st.absmax:g} outside the magnitude envelope {limit:g}"
        return None

    return Guardband(
        label=f"magnitude-envelope[{limit:g}]",
        check=check,
        quantities=tuple(quantities) if quantities is not None else None,
    )


def _is_floating(dtype) -> bool:
    import numpy as np

    return np.issubdtype(np.dtype(dtype), np.inexact)


class NumericsEngine:
    """Per-domain on-device field-statistics engine (module docstring).

    Bound to a realized :class:`~stencil_tpu.domain.DistributedDomain`;
    hand one out via ``dd.numerics()``.  The fused stats program is built
    lazily on first snapshot and memoized on the domain's geometry
    signature, so a reshard/re-realize transparently rebuilds it (the
    supervisor's ``on_mesh_change`` hook also invalidates eagerly)."""

    def __init__(self, dd, every: int = 0):
        if every < 0:
            raise ValueError(f"numerics cadence must be >= 0, got {every}")
        self.dd = dd
        self.every = int(every)
        self.steps_done = 0
        self.ring = collections.deque(maxlen=RING_SIZE)
        self._guardbands: List[Guardband] = []
        self._fn = None
        self._names: List[str] = []
        self._sig = None

    # --- cadence --------------------------------------------------------------

    def set_every(self, every: int) -> None:
        """Change the snapshot cadence WITHOUT resetting the accumulated
        step count (the same mid-run contract as the sentinel's
        ``set_every``)."""
        if every < 0:
            raise ValueError(f"numerics cadence must be >= 0, got {every}")
        self.every = int(every)

    def after_steps(self, steps: int) -> Optional[NumericsSnapshot]:
        """Account ``steps`` raw iterations just run; snapshot on cadence
        crossings.  With ``every == 0`` this is pure bookkeeping."""
        before = self.steps_done
        self.steps_done += steps
        if not self.every:
            return None
        if before // self.every == self.steps_done // self.every:
            return None
        last = self.last
        if last is not None and last.step == self.steps_done:
            # the sentinel (or a direct caller) already snapshotted this
            # exact step through the same engine — one dispatch serves both
            return last
        return self.snapshot(step=self.steps_done, window=(before, self.steps_done))

    @property
    def last(self) -> Optional[NumericsSnapshot]:
        return self.ring[-1] if self.ring else None

    def last_as_json(self) -> Optional[dict]:
        last = self.last
        return last.as_json() if last is not None else None

    def ring_as_json(self) -> List[dict]:
        return [s.as_json() for s in self.ring]

    # --- guardbands -----------------------------------------------------------

    def register_guardband(self, band: Guardband) -> None:
        """Register (or replace, by label — model rebuilds re-register
        idempotently) one invariant guardband."""
        self._guardbands = [g for g in self._guardbands if g.label != band.label]
        self._guardbands.append(band)

    def guardbands(self) -> Tuple[Guardband, ...]:
        return tuple(self._guardbands)

    def _check_guardbands(self, snap: NumericsSnapshot) -> None:
        from stencil_tpu import telemetry
        from stencil_tpu.telemetry import names as tm
        from stencil_tpu.utils.config import env_bool

        abort = env_bool("STENCIL_NUMERICS_ABORT", False)
        for st in snap.stats:
            for band in self._guardbands:
                if not band.applies_to(st.name):
                    continue
                why = band.check(st)
                if why is None:
                    continue
                telemetry.inc(tm.NUMERICS_DRIFT)
                telemetry.emit_event(
                    tm.NUMERICS_DRIFT,
                    quantity=st.name,
                    guardband=band.label,
                    why=why,
                    step=snap.step,
                    window=list(snap.window) if snap.window else None,
                    abort=abort,
                )
                if abort:
                    from stencil_tpu.resilience.taxonomy import DivergenceError

                    raise DivergenceError(
                        quantity=st.name,
                        step=snap.step,
                        window=snap.window,
                        why=f"guardband {band.label}: {why} "
                        "(STENCIL_NUMERICS_ABORT=1)",
                    )

    # --- the fused stats program ----------------------------------------------

    def _signature(self):
        """Geometry + quantity signature the memoized program is keyed on
        — anything that changes the traced program's shapes, sharding, or
        masking.  A reshard changes the mesh/spec/devices; add_data is
        pre-realize only."""
        dd = self.dd
        dim = dd.placement.dim()
        n = dd._spec.sz
        lo = dd._shell_radius.lo()
        return (
            (dim.x, dim.y, dim.z),
            (n.x, n.y, n.z),
            (lo.x, lo.y, lo.z),
            tuple(dd._valid_last),
            tuple(d.id for d in dd.mesh.devices.flat),
            tuple(
                (h.name, tuple(h.components), str(dd.field_dtype(h)))
                for h in dd._handles
            ),
        )

    def on_mesh_change(self) -> None:
        """Invalidate the memoized program (the supervisor's reshard hook;
        the signature check would also catch it lazily)."""
        self._fn = None
        self._sig = None

    def program(self):
        """``(fn, example_args, names)`` — the fused jitted stats program
        over the floating quantities (in ``names`` order), its example
        inputs (the live buffers), and the quantity names.  Exposed so the
        ``numerics-bounded`` contract can trace exactly the program
        ``snapshot`` dispatches."""
        assert self.dd._realized, "numerics needs a realized domain"
        sig = self._signature()
        if self._fn is None or self._sig != sig:
            self._fn, self._names = self._build()
            self._sig = sig
        args = tuple(self.dd._curr[k] for k in self._names)
        return self._fn, args, list(self._names)

    def _build(self):
        """Build the fused sharded stats program for the CURRENT geometry.

        One shard_map over every floating quantity: per shard the interior
        block is masked to its VALID cells (uneven pad-and-mask shards
        contribute only real cells), moment stats accumulate at >= f32
        (bf16/f32 upcast to f32, f64 stays f64 — the PR-7 contract), and
        everything reduces across the mesh in-program (psum/pmin/pmax), so
        each output is one replicated scalar.  The first-non-finite cell
        reduces as a global row-major linear index (pmin of per-shard
        winners; shard-local row-major order IS global row-major order
        within a shard, so the local argmax of the bad-mask is the shard's
        globally-first bad cell).
        """
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax import lax

        from stencil_tpu.domain import _qspec
        from stencil_tpu.parallel.mesh import MESH_AXES
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        dd = self.dd
        handles = [h for h in dd._handles if _is_floating(h.dtype)]
        names = [h.name for h in handles]
        if not handles:
            return (lambda *args: ()), names
        dim = dd.placement.dim()
        n = dd._spec.sz
        lo = dd._shell_radius.lo()
        size = dd._size
        valid_last = dd._valid_last
        # the global row-major linear index must be exact: int32 covers
        # ~1290^3 cells; larger domains need the x64 mode this container's
        # tests run under (jnp would silently truncate an int64 request)
        total_cells = size.x * size.y * size.z
        if jax.config.jax_enable_x64:
            idx_dtype = jnp.int64
        else:
            idx_dtype = jnp.int32
            if total_cells >= np.iinfo(np.int32).max:
                from stencil_tpu.utils.logging import log_warn

                log_warn(
                    "numerics: first-non-finite index needs int64 for "
                    f"{total_cells} cells but jax x64 is disabled; the "
                    "reported coordinate may wrap on this domain"
                )
        sentinel = int(np.iinfo(np.dtype(idx_dtype)).max)

        def acc_dtype(h):
            # >= f32 accumulation: f64 fields keep f64, everything else
            # (f32 storage, bf16 storage) accumulates at f32
            return jnp.float64 if jnp.dtype(h.dtype) == jnp.float64 else jnp.float32

        def axis_valid(ax, idx):
            v = valid_last[ax]
            if v is None:
                return n[ax]
            return jnp.where(idx == dim[ax] - 1, v, n[ax])

        def per_shard(*blocks):
            idxs = [lax.axis_index(MESH_AXES[ax]) for ax in range(3)]
            # per-axis validity masks (pad-and-mask: the last shard on a
            # padded axis owns fewer valid cells)
            masks = [
                jnp.arange(n[ax]) < axis_valid(ax, idxs[ax]) for ax in range(3)
            ]
            mask3 = (
                masks[0][:, None, None]
                & masks[1][None, :, None]
                & masks[2][None, None, :]
            )
            outs = []
            for h, block in zip(handles, blocks):
                acc = acc_dtype(h)
                interior = block[
                    ...,
                    lo.x : lo.x + n.x,
                    lo.y : lo.y + n.y,
                    lo.z : lo.z + n.z,
                ].astype(acc)
                mask = jnp.broadcast_to(mask3, interior.shape)
                finite = jnp.isfinite(interior) & mask
                inf = jnp.asarray(jnp.inf, acc)
                mn = lax.pmin(
                    jnp.min(jnp.where(finite, interior, inf)), MESH_AXES
                )
                mx = lax.pmax(
                    jnp.max(jnp.where(finite, interior, -inf)), MESH_AXES
                )
                am = lax.pmax(
                    jnp.max(jnp.where(finite, jnp.abs(interior), 0.0)),
                    MESH_AXES,
                )
                zero = jnp.asarray(0.0, acc)
                s = lax.psum(
                    jnp.sum(jnp.where(finite, interior, zero)), MESH_AXES
                )
                s2 = lax.psum(
                    jnp.sum(jnp.where(finite, interior * interior, zero)),
                    MESH_AXES,
                )
                nf = lax.psum(
                    jnp.sum(finite.astype(idx_dtype)), MESH_AXES
                )
                bad = mask & ~jnp.isfinite(interior)
                nbad = lax.psum(jnp.sum(bad.astype(idx_dtype)), MESH_AXES)
                # first bad cell: collapse component dims, then the local
                # row-major argmax (first True) is this shard's globally
                # first bad cell — encode as a global linear index, pmin
                bad_cell = bad
                while bad_cell.ndim > 3:
                    bad_cell = jnp.any(bad_cell, axis=0)
                flat = bad_cell.reshape(-1)
                local = jnp.argmax(flat).astype(idx_dtype)
                has = jnp.any(flat)
                ly_z = jnp.asarray(n.y * n.z, idx_dtype)
                lz = jnp.asarray(n.z, idx_dtype)
                gx = idxs[0] * n.x + local // ly_z
                gy = idxs[1] * n.y + (local // lz) % n.y
                gz = idxs[2] * n.z + local % n.z
                key = (
                    gx.astype(idx_dtype) * (size.y * size.z)
                    + gy.astype(idx_dtype) * size.z
                    + gz.astype(idx_dtype)
                )
                key = jnp.where(has, key, jnp.asarray(sentinel, idx_dtype))
                key = lax.pmin(key, MESH_AXES)
                outs.extend([mn, mx, am, s, s2, nf, nbad, key])
            return tuple(outs)

        specs = tuple(_qspec(h) for h in handles)
        out_specs = tuple(P() for _ in range(SCALARS_PER_QUANTITY * len(handles)))
        fn = jax.jit(
            shard_map(
                per_shard,
                mesh=dd.mesh,
                in_specs=specs,
                out_specs=out_specs,
            )
        )
        return fn, names

    # --- snapshots ------------------------------------------------------------

    def snapshot(
        self, step: Optional[int] = None, window: Optional[Tuple[int, int]] = None
    ) -> NumericsSnapshot:
        """Take one fused on-device health snapshot: ONE sharded dispatch,
        O(#quantities) scalars to the host, appended to the ring; then the
        registered guardbands run over the host scalars (observe-only by
        default — ``STENCIL_NUMERICS_ABORT=1`` escalates a violation to a
        classified ``DIVERGENCE``)."""
        import numpy as np

        from stencil_tpu import telemetry
        from stencil_tpu.telemetry import names as tm

        t0 = time.perf_counter()
        # the read-back drains the dispatch queue: a span on the profiler's
        # timeline (the divergence sentinel's check comes through here too)
        with telemetry.span(tm.SPAN_NUMERICS_SNAPSHOT, step=step):
            fn, args, names = self.program()
            raw = [np.asarray(v) for v in fn(*args)]  # the O(#q)-scalar transfer
        dd = self.dd
        size = dd._size
        stats = []
        k = SCALARS_PER_QUANTITY
        handles = {h.name: h for h in dd._handles}
        for i, name in enumerate(names):
            mn, mx, am, s, s2, nf, nbad, key = raw[i * k : (i + 1) * k]
            nf = int(nf)
            nbad = int(nbad)
            key = int(key)
            coord = None
            if nbad and 0 <= key < size.x * size.y * size.z:
                coord = (
                    key // (size.y * size.z),
                    (key // size.z) % size.y,
                    key % size.z,
                )
            mean = float(s) / nf if nf else None
            l2 = math.sqrt(float(s2)) if nf else None
            stats.append(
                FieldStats(
                    name=name,
                    dtype=np.dtype(handles[name].dtype).name,
                    min=_finite_or_none(mn),
                    max=_finite_or_none(mx),
                    absmax=_finite_or_none(am),
                    mean=_finite_or_none(mean),
                    l2=_finite_or_none(l2),
                    finite=nf,
                    nonfinite=nbad,
                    first_nonfinite=coord,
                )
            )
        dt = time.perf_counter() - t0
        snap = NumericsSnapshot(
            step=step, window=window, ts=time.time(), seconds=dt,
            stats=tuple(stats),
        )
        self.ring.append(snap)
        telemetry.inc(tm.NUMERICS_SNAPSHOTS)
        telemetry.observe(tm.NUMERICS_SNAPSHOT_SECONDS, dt)
        self._check_guardbands(snap)
        return snap
