"""Checkpoint / resume of a DistributedDomain — the long-run survival layer.

The reference has NO restore path (SURVEY.md §5: paraview dumps only); this
module is the deliberate improvement called out there, hardened for
preemption-tolerant long runs (docs/resilience.md "Long-run operation"):

* **Atomic commit** — every checkpoint is staged into a temp directory next
  to its destination (state first, fsync'd; the versioned ``MANIFEST.json``
  last) and renamed into place in one step.  A kill at ANY byte leaves
  either the previous checkpoint or no checkpoint at that path — never a
  half-written directory a later resume would half-parse.  The manifest is
  the commit marker: a directory without one is, by construction, an
  interrupted save.
* **Versioned manifest with per-array digests** — ``MANIFEST.json`` carries
  a schema number, the domain geometry at save time, the full run state
  (step counter, the ``storage_dtype`` axis, tuned decisions in
  effect — whatever the caller passes), and one sha256 per quantity over the
  PORTABLE interior representation (interior cells at the native dtype —
  bf16-stored fields upcast exactly per the PR-7 f32-accumulate contract).
  Restores verify the digests on the LOADED data before installing it;
  a mismatch raises a classified :class:`CheckpointCorruptError`.
* **Retention ring** — ``save_to_ring`` keeps the last N checkpoints under
  step-numbered directories (``ckpt-000000000042``); ``latest_valid`` walks
  the ring newest→oldest, skipping (and counting) corrupt or partial
  entries, so one bad checkpoint falls back to the previous good one
  instead of killing the resume.
* **Elastic restore** — a checkpoint taken on mesh A restores onto mesh B.
  The ``npz`` backend is portable by construction (interiors re-scatter
  through ``set_quantity``); the ``orbax`` backend detects a topology or
  storage-axis change and re-routes through a host round trip using the
  geometry recorded in the manifest, instead of its historical
  same-topology requirement ("Memory-efficient array redistribution",
  PAPERS.md arxiv 2112.01075, is the on-device generalization of this
  re-scatter).

Backends:

* ``orbax`` (default when installed) — saves the sharded raw arrays (halo
  shells included) directly from device memory; the production path on
  pods.  Same-topology restores stay sharded end-to-end.
* ``npz`` — gathers interiors to host and saves a portable npz; restores
  onto any device count.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import time
import zipfile
from typing import List, Optional, Tuple

import numpy as np

from stencil_tpu import telemetry
from stencil_tpu.resilience.taxonomy import CheckpointCorruptError
from stencil_tpu.telemetry import names as tm
from stencil_tpu.utils.artifact import atomic_write, atomic_write_json, fsync_dir
from stencil_tpu.utils.logging import log_info, log_warn

#: the commit marker and single source of checkpoint metadata
MANIFEST = "MANIFEST.json"

#: bump when the manifest vocabulary changes incompatibly; a mismatch is a
#: classified corruption (the ring falls back), never a half-parse.
#: History: 1 — atomic manifest+digests+run_state (the long-run PR; the
#: pre-ring ``meta.json`` format is rejected with a pointed error).
SCHEMA = 1

#: retention-ring entry prefix: ``ckpt-<step:012d>``
RING_PREFIX = "ckpt-"


def _orbax_available() -> bool:
    try:
        import orbax.checkpoint  # noqa: F401

        return True
    except ImportError:
        return False


def _digest(arr: np.ndarray) -> str:
    return "sha256:" + hashlib.sha256(
        np.ascontiguousarray(arr).tobytes()
    ).hexdigest()


def _commit_dir(stage: str, path: str) -> None:
    """Atomically make ``stage`` the content of ``path``.  An existing
    checkpoint at ``path`` is moved aside first and removed only after the
    new one is in place, so a crash at any point leaves one of the two
    intact."""
    path = os.path.abspath(path)
    parent = os.path.dirname(path) or "."
    old = None
    if os.path.lexists(path):
        old = f"{path}.old.{os.getpid()}"
        if os.path.lexists(old):
            shutil.rmtree(old, ignore_errors=True)
        os.rename(path, old)
    try:
        os.rename(stage, path)
    except BaseException:
        if old is not None and not os.path.lexists(path):
            os.rename(old, path)
        raise
    fsync_dir(parent)
    if old is not None:
        shutil.rmtree(old, ignore_errors=True)


def save_checkpoint(
    dd,
    path: str,
    step: int = 0,
    backend: Optional[str] = None,
    run_state: Optional[dict] = None,
    reason: str = "explicit",
    digests: Optional[bool] = None,
) -> str:
    """Write all quantities + geometry + run state atomically; returns the
    backend used.  ``run_state`` is the caller's resumable decision record
    (tuned picks, model knobs) — merged over the domain-derived axes this
    function records on its own (``storage_dtype``, ``halo_multiplier``,
    ``exchange_route``).

    ``digests`` controls the per-quantity sha256 over the portable interior
    representation.  The npz backend always has the interiors on host
    anyway, so it always digests; on the orbax backend the gather exists
    ONLY for the digests, so pod-scale cadences can trade verification for
    the sharded direct-from-device save with ``digests=False`` /
    ``STENCIL_CHECKPOINT_DIGESTS=0`` (manifest records ``null`` digests;
    restores then skip byte verification for this checkpoint).

    Multi-host runs (``jax.process_count() > 1``) require the orbax
    backend and save COORDINATED: every process calls into orbax on the
    one shared destination, digests are forced off (the gather would span
    non-addressable shards), and process 0 alone writes the manifest —
    removed first, re-written after orbax completes, so it stays the
    commit marker.  Elastic (cross-mesh) restore is single-controller
    only; multi-host restores require the same topology.

    The whole commit runs under a ``checkpoint.save`` span [step]: the
    gather + write + fsync is host work that stalls a dispatch pipeline, and
    a profiler trace then shows it beside the device ops."""
    with telemetry.span(tm.EVENT_CHECKPOINT_SAVE, step=int(step)):
        return _save_checkpoint(dd, path, step, backend, run_state, reason, digests)


def _save_checkpoint(dd, path, step, backend, run_state, reason, digests) -> str:
    import jax

    t0 = time.perf_counter()
    backend = backend or ("orbax" if _orbax_available() else "npz")
    multiprocess = jax.process_count() > 1
    if multiprocess and backend != "orbax":
        raise ValueError(
            "multi-process checkpointing requires the orbax backend: the "
            "npz path gathers whole arrays to host, which spans "
            "non-addressable devices on a multi-host run"
        )
    if digests is None:
        if backend == "npz":
            digests = True
        else:
            from stencil_tpu.utils.config import env_bool

            digests = env_bool("STENCIL_CHECKPOINT_DIGESTS", True)
    if multiprocess and digests and backend == "orbax":
        log_warn(
            "checkpoint digests disabled: the digest gather would span "
            "non-addressable shards on a multi-process run"
        )
        digests = False
    path = os.path.abspath(path)
    parent = os.path.dirname(path) or "."
    os.makedirs(parent, exist_ok=True)
    # portable interiors (native dtype — bf16 storage upcasts exactly):
    # the representation the digests cover for BOTH backends, so a save
    # on one backend/axis config is comparable to any other.  Gathered
    # only when something needs it (the npz payload, or digests).
    interiors = (
        {h.name: dd.quantity_to_host(h) for h in dd._handles}
        if (backend == "npz" or digests)
        else None
    )
    nbytes = (
        int(sum(a.nbytes for a in interiors.values()))
        if interiors is not None
        else int(
            sum(
                int(np.prod(dd.size())) * h.cell_count() * np.dtype(h.dtype).itemsize
                for h in dd._handles
            )
        )
    )
    dim = dd.placement.dim()
    raw = dd.local_spec().raw_size()
    lo = dd._shell_radius.lo()
    # caller record first, domain-derived axes LAST: restore routing
    # (the orbax same-topology storage gate) reads these, so a caller
    # key can never shadow what the domain actually is
    state = dict(run_state or {})
    state.update(
        storage_dtype=dd.storage_dtype(),
        halo_multiplier=dd.halo_multiplier(),
        exchange_route=dd.exchange_route(),
    )
    meta = {
        "schema": SCHEMA,
        "size": list(dd.size()),
        "step": int(step),
        "backend": backend,
        "created": time.time(),
        "quantities": [
            {
                "name": h.name,
                "dtype": str(np.dtype(h.dtype)),
                "components": list(h.components),
                "digest": _digest(interiors[h.name]) if digests else None,
            }
            for h in dd._handles
        ],
        "geometry": {
            "mesh": [dim.x, dim.y, dim.z],
            "raw": [raw.x, raw.y, raw.z],
            "shell_lo": [lo.x, lo.y, lo.z],
            "valid_last": list(dd._valid_last),
        },
        "run_state": state,
    }
    if backend == "orbax" and multiprocess:
        # COORDINATED multi-host save: every process must call orbax on the
        # ONE shared destination (orbax owns the cross-process commit
        # protocol); per-process staging would defeat the coordination and
        # race the final rename.  The manifest stays the commit marker:
        # process 0 removes any previous one first — the entry reads
        # invalid (ring falls back) while being rewritten — and writes the
        # new one only after orbax reports completion.
        import orbax.checkpoint as ocp

        os.makedirs(path, exist_ok=True)
        if jax.process_index() == 0:
            try:
                os.unlink(os.path.join(path, MANIFEST))
            except OSError:
                pass
        ckptr = ocp.StandardCheckpointer()
        arrays = {h.name: dd.get_curr(h) for h in dd._handles}
        ckptr.save(os.path.join(path, "state.orbax"), arrays, force=True)
        ckptr.wait_until_finished()
        ckptr.close()
        if jax.process_index() != 0:
            return backend  # one manifest writer, one telemetry record
        atomic_write_json(os.path.join(path, MANIFEST), meta)
        fsync_dir(path)
    else:
        stage = f"{path}.tmp.{os.getpid()}"
        if os.path.lexists(stage):
            shutil.rmtree(stage)
        os.makedirs(stage)
        try:
            if backend == "orbax":
                import orbax.checkpoint as ocp

                ckptr = ocp.StandardCheckpointer()
                arrays = {h.name: dd.get_curr(h) for h in dd._handles}
                ckptr.save(os.path.join(stage, "state.orbax"), arrays, force=True)
                ckptr.wait_until_finished()
                ckptr.close()
            else:
                with atomic_write(os.path.join(stage, "state.npz"), "wb") as f:
                    np.savez(f, **interiors)
            # manifest LAST: it is the commit marker within the stage — a
            # stage (or a legacy non-atomic dir) without one is an
            # interrupted save
            atomic_write_json(os.path.join(stage, MANIFEST), meta)
            fsync_dir(stage)
            _commit_dir(stage, path)
        except BaseException:
            shutil.rmtree(stage, ignore_errors=True)
            raise
    dt = time.perf_counter() - t0
    telemetry.inc(tm.CHECKPOINT_SAVES)
    telemetry.inc(tm.CHECKPOINT_SAVE_BYTES, nbytes)
    telemetry.observe(tm.CHECKPOINT_SAVE_SECONDS, dt)
    telemetry.emit_event(
        tm.EVENT_CHECKPOINT_SAVE,
        path=path,
        step=int(step),
        backend=backend,
        bytes=nbytes,
        seconds=round(dt, 6),
        reason=reason,
    )
    log_info(f"checkpoint step {step} -> {path} ({backend}, {nbytes} B, {dt:.3f}s)")
    return backend


def load_manifest(path: str) -> dict:
    """The checkpoint's manifest, or a classified error explaining exactly
    why the directory is not usable (the satellite fix: a missing/partial
    manifest must reject with a clear message, not a stack trace
    mid-restore)."""
    mpath = os.path.join(path, MANIFEST)
    if not os.path.isdir(path):
        raise CheckpointCorruptError(path, "no such directory")
    if not os.path.exists(mpath):
        legacy = os.path.join(path, "meta.json")
        why = (
            "pre-atomic 'meta.json' checkpoint format (schema predates the "
            "manifest; re-save with this version)"
            if os.path.exists(legacy)
            else f"missing {MANIFEST} — not a checkpoint, or an interrupted "
            "save that never committed"
        )
        raise CheckpointCorruptError(path, why)
    try:
        with open(mpath) as f:
            meta = json.load(f)
    except (OSError, json.JSONDecodeError, UnicodeDecodeError) as e:
        raise CheckpointCorruptError(path, f"unreadable manifest: {e}") from None
    if not isinstance(meta, dict) or meta.get("schema") != SCHEMA:
        raise CheckpointCorruptError(
            path,
            f"manifest schema {meta.get('schema') if isinstance(meta, dict) else '?'} "
            f"!= {SCHEMA} (saved by an incompatible version)",
        )
    for key in ("size", "step", "backend", "quantities"):
        if key not in meta:
            raise CheckpointCorruptError(path, f"manifest is missing {key!r}")
    return meta


def validate_checkpoint(path: str, verify_digests: bool = True) -> dict:
    """Full standalone validation: manifest well-formed, state present, and
    (npz) every quantity present with a matching content digest.  Returns
    the manifest; raises :class:`CheckpointCorruptError` otherwise.  The
    orbax state is validated structurally here (its array bytes are verified
    against the digests during restore, where they are gathered anyway)."""
    meta = load_manifest(path)
    if meta["backend"] == "orbax":
        if not os.path.isdir(os.path.join(path, "state.orbax")):
            raise CheckpointCorruptError(path, "missing state.orbax directory")
        return meta
    spath = os.path.join(path, "state.npz")
    if not os.path.exists(spath):
        raise CheckpointCorruptError(path, "missing state.npz")
    try:
        with np.load(spath) as data:
            for q in meta["quantities"]:
                if q["name"] not in data.files:
                    raise CheckpointCorruptError(
                        path, f"state.npz is missing quantity {q['name']!r}"
                    )
                if verify_digests and q.get("digest"):
                    got = _digest(data[q["name"]])
                    if got != q["digest"]:
                        raise CheckpointCorruptError(
                            path,
                            f"digest mismatch for {q['name']!r}: manifest "
                            f"{q['digest']} != data {got}",
                        )
    except (OSError, ValueError, zipfile.BadZipFile) as e:
        raise CheckpointCorruptError(path, f"unreadable state.npz: {e}") from None
    return meta


def _check_compat(dd, meta: dict, path: str) -> None:
    if meta["size"] != list(dd.size()):
        raise ValueError(
            f"checkpoint size {meta['size']} != domain {list(dd.size())}"
        )
    by_name = {h.name: h for h in dd._handles}
    saved = {q["name"] for q in meta["quantities"]}
    if saved != set(by_name):
        raise ValueError(
            f"checkpoint quantities {sorted(saved)} != domain "
            f"{sorted(by_name)} ({path})"
        )
    for q in meta["quantities"]:
        h = by_name[q["name"]]
        if q["dtype"] != str(np.dtype(h.dtype)) or tuple(q.get("components", ())) != tuple(
            h.components
        ):
            raise ValueError(
                f"quantity {q['name']!r}: checkpoint dtype/components "
                f"({q['dtype']}, {q.get('components')}) != domain "
                f"({np.dtype(h.dtype)}, {list(h.components)})"
            )


def _interiors_from_raw_global(raw_arr: np.ndarray, geom: dict, size) -> np.ndarray:
    """Extract the valid interiors from a SAVE-TIME raw global array using
    the geometry recorded in the manifest — the standalone (cross-mesh)
    twin of ``DistributedDomain._from_raw_global``, keyed off the saving
    domain's mesh rather than the restoring one's."""
    dim = geom["mesh"]
    raw = geom["raw"]
    lo = geom["shell_lo"]
    valid_last = geom.get("valid_last", [None, None, None])
    # per-axis shard interior is the padded equal split, ceil(size/dim) —
    # the same rule realize() used on the saving mesh
    n = [-(-size[a] // dim[a]) for a in range(3)]
    comps = raw_arr.shape[:-3]
    out = np.zeros(comps + tuple(size), dtype=raw_arr.dtype)
    for ix in range(dim[0]):
        for iy in range(dim[1]):
            for iz in range(dim[2]):
                idx = (ix, iy, iz)
                v = [
                    valid_last[a]
                    if (idx[a] == dim[a] - 1 and valid_last[a] is not None)
                    else n[a]
                    for a in range(3)
                ]
                out[
                    ...,
                    ix * n[0] : ix * n[0] + v[0],
                    iy * n[1] : iy * n[1] + v[1],
                    iz * n[2] : iz * n[2] + v[2],
                ] = raw_arr[
                    ...,
                    ix * raw[0] + lo[0] : ix * raw[0] + lo[0] + v[0],
                    iy * raw[1] + lo[1] : iy * raw[1] + lo[1] + v[1],
                    iz * raw[2] + lo[2] : iz * raw[2] + lo[2] + v[2],
                ]
    return out


def restore_checkpoint(dd, path: str, verify: bool = True) -> int:
    """Load quantities into a realized domain; returns the saved step.

    Digest verification (``verify=True``) happens on the LOADED portable
    interiors BEFORE they are installed — so a corrupt file is rejected
    with a classified :class:`CheckpointCorruptError` while the domain
    still holds its previous state, and a restore onto a storage axis that
    legitimately rounds (native→bf16) is still verified against what was
    actually on disk."""
    t0 = time.perf_counter()
    path = os.path.abspath(path)
    meta = load_manifest(path)
    _check_compat(dd, meta, path)
    by_name = {h.name: h for h in dd._handles}
    geom = meta.get("geometry") or {}
    dim = dd.placement.dim()
    elastic = list(geom.get("mesh", [])) != [dim.x, dim.y, dim.z]
    if meta["backend"] == "orbax":
        import orbax.checkpoint as ocp

        state_path = os.path.join(path, "state.orbax")
        if not os.path.isdir(state_path):
            raise CheckpointCorruptError(path, "missing state.orbax directory")
        same_raw_shape = not elastic and all(
            tuple(h.components)
            + tuple(g * r for g, r in zip([dim.x, dim.y, dim.z], geom.get("raw", [])))
            == dd.get_curr(h).shape
            for h in dd._handles
        )
        storage_match = (meta.get("run_state") or {}).get(
            "storage_dtype", "native"
        ) == dd.storage_dtype()
        ckptr = ocp.StandardCheckpointer()
        try:
            if same_raw_shape and storage_match:
                # same topology AND same storage axis: sharded end-to-end
                target = {h.name: dd.get_curr(h) for h in dd._handles}
                restored = ckptr.restore(state_path, target)
                if verify:
                    installed = dict(dd._curr)
                    dd._curr.update(
                        {q["name"]: restored[q["name"]] for q in meta["quantities"]}
                    )
                    try:
                        for q in meta["quantities"]:
                            if not q.get("digest"):
                                continue  # saved with digests off
                            got = _digest(dd.quantity_to_host(by_name[q["name"]]))
                            if got != q["digest"]:
                                raise CheckpointCorruptError(
                                    path,
                                    f"digest mismatch for {q['name']!r}: "
                                    f"manifest {q['digest']} != restored {got}",
                                )
                    except CheckpointCorruptError:
                        dd._curr = installed  # keep the pre-restore state
                        raise
                else:
                    for q in meta["quantities"]:
                        dd._curr[q["name"]] = restored[q["name"]]
            else:
                # ELASTIC (mesh B != mesh A, or the storage axis changed):
                # restore to host numpy, cut the interiors out of the saved
                # raw layout via the manifest geometry, re-scatter
                restored = ckptr.restore(state_path)
                # verify everything BEFORE installing anything (the npz
                # path's two-phase contract)
                interiors = {}
                for q in meta["quantities"]:
                    h = by_name[q["name"]]
                    interior = _interiors_from_raw_global(
                        np.asarray(restored[q["name"]]), geom, meta["size"]
                    ).astype(h.dtype)
                    if verify and q.get("digest"):
                        got = _digest(interior)
                        if got != q["digest"]:
                            raise CheckpointCorruptError(
                                path,
                                f"digest mismatch for {q['name']!r}: manifest "
                                f"{q['digest']} != data {got}",
                            )
                    interiors[q["name"]] = interior
                for q in meta["quantities"]:
                    dd.set_quantity(by_name[q["name"]], interiors[q["name"]])
        finally:
            ckptr.close()
    else:
        spath = os.path.join(path, "state.npz")
        if not os.path.exists(spath):
            raise CheckpointCorruptError(path, "missing state.npz")
        try:
            data = np.load(spath)
        except (OSError, ValueError) as e:
            raise CheckpointCorruptError(path, f"unreadable state.npz: {e}") from None
        with data:
            # two phases — load+verify EVERYTHING, then install: a digest
            # mismatch on the last quantity must leave the domain fully on
            # its previous state, never half-restored
            loaded = {}
            for q in meta["quantities"]:
                if q["name"] not in data.files:
                    raise CheckpointCorruptError(
                        path, f"state.npz is missing quantity {q['name']!r}"
                    )
                arr = data[q["name"]]
                if verify and q.get("digest"):
                    got = _digest(arr)
                    if got != q["digest"]:
                        raise CheckpointCorruptError(
                            path,
                            f"digest mismatch for {q['name']!r}: manifest "
                            f"{q['digest']} != data {got}",
                        )
                loaded[q["name"]] = arr
        for q in meta["quantities"]:
            h = by_name[q["name"]]
            dd.set_quantity(h, loaded[q["name"]].astype(h.dtype))
    dt = time.perf_counter() - t0
    telemetry.inc(tm.CHECKPOINT_RESTORES)
    telemetry.observe(tm.CHECKPOINT_RESTORE_SECONDS, dt)
    telemetry.emit_event(
        tm.EVENT_CHECKPOINT_RESTORE,
        path=path,
        step=int(meta["step"]),
        backend=meta["backend"],
        elastic=elastic,
        seconds=round(dt, 6),
    )
    log_info(
        f"restored step {meta['step']} from {path} "
        f"({meta['backend']}{', elastic' if elastic else ''}, {dt:.3f}s)"
    )
    return int(meta["step"])


# --- retention ring -----------------------------------------------------------


def ring_entries(root: str) -> List[Tuple[int, str]]:
    """(step, path) for every ring entry under ``root``, oldest first.
    Stage/aside directories from interrupted saves are ignored (and never
    counted against the ring)."""
    if not os.path.isdir(root):
        return []
    out = []
    for name in os.listdir(root):
        if not name.startswith(RING_PREFIX) or name.endswith(".tmp") or ".tmp." in name or ".old." in name:
            continue
        try:
            step = int(name[len(RING_PREFIX):])
        except ValueError:
            continue
        out.append((step, os.path.join(root, name)))
    return sorted(out)


def ring_path(root: str, step: int) -> str:
    return os.path.join(root, f"{RING_PREFIX}{step:012d}")


def save_to_ring(
    dd,
    root: str,
    step: int,
    keep: int = 3,
    backend: Optional[str] = None,
    run_state: Optional[dict] = None,
    reason: str = "cadence",
) -> str:
    """Atomic checkpoint into the retention ring at ``root`` and prune to
    the newest ``keep`` entries; returns the committed path."""
    path = ring_path(root, step)
    save_checkpoint(dd, path, step=step, backend=backend, run_state=run_state, reason=reason)
    entries = ring_entries(root)
    for _, old in entries[: max(len(entries) - max(keep, 1), 0)]:
        shutil.rmtree(old, ignore_errors=True)
    # sweep stage/aside survivors of KILLED saves: same-pid cleanup cannot
    # run after a SIGKILL, and the ring has one writer at a time, so any
    # `.tmp.`/`.old.` ring-prefixed dir here is garbage the size of a full
    # checkpoint
    for name in os.listdir(root):
        if name.startswith(RING_PREFIX) and (".tmp." in name or ".old." in name):
            shutil.rmtree(os.path.join(root, name), ignore_errors=True)
    telemetry.set_gauge(tm.CHECKPOINT_RETAINED, min(len(entries), max(keep, 1)))
    return path


def restore_latest(dd, root: str, verify: bool = True) -> Optional[Tuple[str, dict, int]]:
    """Restore the newest ring checkpoint that RESTORES CLEANLY, falling
    back past entries that fail at any stage — structural validation or
    restore-time digest verification (the orbax backends verify bytes only
    at restore, so a standalone ``latest_valid`` pass cannot catch their
    bit rot).  Digest hashing happens exactly once per attempted entry.
    Returns ``(path, manifest, step)``, or None when nothing restores;
    compatibility errors (size/quantity mismatch — a config error, not
    corruption) propagate immediately."""
    for _, path in reversed(ring_entries(root)):
        try:
            meta = load_manifest(path)
            step = restore_checkpoint(dd, path, verify=verify)
            return path, meta, step
        except CheckpointCorruptError as e:
            telemetry.inc(tm.CHECKPOINT_INVALID)
            telemetry.emit_event(tm.EVENT_CHECKPOINT_FALLBACK, path=path, why=e.why)
            log_warn(
                f"checkpoint {path} failed restore ({e.why}); falling back "
                "to the previous ring entry"
            )
    return None


def latest_valid(root: str, verify_digests: bool = True) -> Optional[Tuple[str, dict]]:
    """The newest VALID ring checkpoint as ``(path, manifest)``, or None.
    Corrupt/partial entries are skipped with a warning, a
    ``checkpoint.invalid`` count, and a ``checkpoint.fallback`` event —
    the corruption-detection rung of the resilience story: one bad
    checkpoint costs one cadence of progress, not the run."""
    for step, path in reversed(ring_entries(root)):
        try:
            return path, validate_checkpoint(path, verify_digests=verify_digests)
        except CheckpointCorruptError as e:
            telemetry.inc(tm.CHECKPOINT_INVALID)
            telemetry.emit_event(tm.EVENT_CHECKPOINT_FALLBACK, path=path, why=e.why)
            log_warn(
                f"checkpoint {path} failed validation ({e.why}); falling "
                "back to the previous ring entry"
            )
    return None
