"""Fabric observatory: measured point-to-point interconnect model.

The TPU analog of the reference's NVML link-distance matrix: instead of
asking the driver how GPUs are wired, we MEASURE every realized neighbor
hop of the mesh with a single-edge ``lax.ppermute`` sweep and persist the
result as a per-link bandwidth matrix.  Consumers:

* ``scripts/perf_report.py`` — joins the probed link model against the
  per-direction exchange attribution into a comms roofline (achieved vs
  probed GB/s per mesh axis per direction, bottleneck named).
* heartbeat / ``python -m stencil_tpu.status`` — the fabric matrix and the
  slowest-link callout render in the live status surface.
* future placement/tuner consumers — ``link_model(mesh)`` exposes the
  per-axis/per-direction aggregate without re-probing.

Probe protocol (the repo's one timing discipline, ``tune/trial.py``):
every unique ordered neighbor pair gets a jitted single-pair ppermute
over a flat ``"d"``-axis mesh; all edges are warmed, then measured under
``measure_alternating`` — ``reps + 1`` alternating rounds with the rep-0
post-idle burst discarded and the host round trip subtracted — and each
edge reports the median sample.  An optional second sweep at a small
payload (``lat_nbytes``) reports per-edge latency.

Persistence mirrors ``tune/cache.py`` exactly: one stamped JSON per
``(topology, chip, payload)`` key under ``STENCIL_FABRIC_CACHE`` (default
``~/.cache/stencil_tpu/fabric``), schema + jax/jaxlib toolchain checked on
load, corrupt/stale files are a MISS (warn/info, never crash), stores go
through the atomic write-rename.  A warm ``ensure(mesh)`` does zero device
work.

jax-free at import time (``jax-import`` lint rule): jax enters only inside
the probe path.
"""

from __future__ import annotations

import hashlib
import json
import os
import time
from typing import Dict, List, Optional, Tuple

from stencil_tpu.telemetry import names
from stencil_tpu.utils.config import env_str

#: bump when the persisted-link vocabulary changes incompatibly; a schema
#: mismatch is a MISS (stale matrices re-probe, never crash).  History:
#: 1 — per-edge gbps links + NxN matrix (the fabric-observatory PR).
SCHEMA = 1

_DEFAULT_DIR = os.path.join("~", ".cache", "stencil_tpu", "fabric")

#: default probe payload per shard (bytes); large enough that the host
#: round trip does not dominate, small enough to stay off the HBM
#: high-water mark of a running job
DEFAULT_NBYTES = 8 << 20

#: process-local override (driver --fabric-cache); None = env/default
_dir_override: Optional[str] = None


def set_dir_override(path: Optional[str]) -> None:
    global _dir_override
    _dir_override = path


def cache_dir() -> str:
    path = _dir_override or env_str("STENCIL_FABRIC_CACHE", _DEFAULT_DIR)
    return os.path.abspath(os.path.expanduser(path))


def _toolchain() -> Tuple[str, str]:
    import jax

    try:
        import jaxlib

        jaxlib_v = getattr(jaxlib, "__version__", "")
    except Exception:  # noqa: BLE001 — jaxlib layout varies across builds
        jaxlib_v = ""
    return jax.__version__, jaxlib_v


def probe_key(
    topology: Tuple[int, ...], chip: str, nbytes: int, lat_nbytes: Optional[int]
) -> dict:
    """The identity a persisted matrix is keyed by.  Payload sizes are part
    of the key: bandwidth at 8 MiB and at 4 KiB are different facts."""
    return {
        "topology": list(topology),
        "chip": chip,
        "nbytes": int(nbytes),
        "lat_nbytes": None if lat_nbytes is None else int(lat_nbytes),
    }


def key_digest(key: dict) -> str:
    canon = json.dumps(key, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(canon.encode()).hexdigest()[:16]


def path_for(key: dict) -> str:
    return os.path.join(cache_dir(), f"{key_digest(key)}.json")


def load(key: dict) -> Optional[dict]:
    """The persisted probe doc for ``key``, or None on a miss (absent,
    corrupt, or persisted by a different toolchain/schema)."""
    path = path_for(key)
    try:
        with open(path) as f:
            doc = json.load(f)
    except FileNotFoundError:
        return None
    except (OSError, json.JSONDecodeError, UnicodeDecodeError) as e:
        from stencil_tpu.utils.logging import log_warn

        log_warn(f"fabric cache {path} is unreadable ({e}); treating as a miss")
        return None
    jax_v, jaxlib_v = _toolchain()
    if (
        not isinstance(doc, dict)
        or doc.get("schema") != SCHEMA
        or doc.get("jax") != jax_v
        or doc.get("jaxlib") != jaxlib_v
        or not isinstance(doc.get("links"), list)
    ):
        from stencil_tpu.utils.logging import log_info

        log_info(
            f"fabric cache {path} is stale (schema/toolchain mismatch); "
            "link models must be re-probed on this toolchain — treating as a miss"
        )
        return None
    return doc


def store(doc: dict) -> str:
    """Persist a probe doc atomically (utils/artifact.py write-rename: a
    crashed probe must not leave a truncated matrix a later run half-parses)."""
    from stencil_tpu.utils.artifact import atomic_write_json

    key = probe_key(
        tuple(doc["topology"]), doc["chip"], doc["nbytes"], doc.get("lat_nbytes")
    )
    return atomic_write_json(path_for(key), doc)


# --- hop enumeration ----------------------------------------------------------


def neighbor_links(shape: Dict[str, int]) -> List[dict]:
    """Every (mesh axis, side, src, dst) hop of a torus mesh, as FLAT device
    indices (C-order over the mesh grid — the index space the flat ``"d"``
    probe mesh and the persisted matrix share).

    Direction naming matches ``ops/exchange.py``: side ``low`` is the link a
    shard RECEIVES its -1 neighbor's slab on (data moves +, so the ordered
    pair is ``i -> i+1``); side ``high`` receives from the +1 neighbor
    (``i+1 -> i``).  Axes of size 1 contribute nothing (a self-wrap is the
    periodic boundary inside one chip, not fabric traffic).  On size-2 axes
    the low and high hop sets coincide as ordered pairs — ``probe`` dedupes
    the measurements, not the attribution rows.
    """
    axes = list(shape)
    sizes = [shape[a] for a in axes]
    strides = [1] * len(axes)
    for i in range(len(axes) - 2, -1, -1):
        strides[i] = strides[i + 1] * sizes[i + 1]

    def flat(coord) -> int:
        return sum(c * s for c, s in zip(coord, strides))

    def coords():
        out = [()]
        for n in sizes:
            out = [c + (i,) for c in out for i in range(n)]
        return out

    links = []
    for ai, axis in enumerate(axes):
        n = sizes[ai]
        if n < 2:
            continue
        for c in coords():
            up = list(c)
            up[ai] = (c[ai] + 1) % n
            # low: every shard receives from its -1 neighbor -> c sends up
            links.append(
                {"axis": axis, "side": "low", "src": flat(c), "dst": flat(tuple(up))}
            )
            # high: every shard receives from its +1 neighbor -> up sends to c
            links.append(
                {"axis": axis, "side": "high", "src": flat(tuple(up)), "dst": flat(c)}
            )
    return links


# --- the probe ----------------------------------------------------------------


def _edge_run(flat_mesh, n_dev: int, src: int, dst: int, n_elems: int):
    """``run(k)``: k chained synchronous dispatches of a jitted single-pair
    ``ppermute`` src->dst (the point-to-point primitive, one compile per
    static edge — ``bin/_common.make_edge_transfer`` reimplemented here so
    telemetry/ never imports the driver layer)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from jax import shard_map

    @jax.jit
    def go(x):
        def f(blk):
            return lax.ppermute(blk, "d", [(src, dst)])

        return shard_map(f, mesh=flat_mesh, in_specs=P("d"), out_specs=P("d"))(x)

    x = jax.device_put(
        jnp.ones((n_elems * n_dev,), jnp.float32), NamedSharding(flat_mesh, P("d"))
    )

    def run(k: int) -> None:
        y = x
        for _ in range(k):
            y = go(y)
        jax.block_until_ready(y)

    return run


def _host_round_trip_s() -> float:
    """One device->host readback latency (subtracted from edge timings —
    ``bench.py``'s discipline)."""
    import jax.numpy as jnp

    x = jnp.zeros((8,))
    float(jnp.sum(x))
    t0 = time.perf_counter()
    for _ in range(5):
        float(jnp.sum(x))
    return (time.perf_counter() - t0) / 5


def _median(xs: List[float]) -> float:
    s = sorted(xs)
    n = len(s)
    return s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])


def _sweep_edges(
    flat_mesh, n_dev: int, edges: List[Tuple[int, int]], nbytes: int,
    reps: int, inner: int, rt: float,
) -> Dict[Tuple[int, int], float]:
    """Median seconds per ``(src, dst)`` edge at ``nbytes`` per shard, under
    the alternating rep-0-drop protocol (``tune/trial.measure_alternating``)."""
    from stencil_tpu.tune.trial import measure_alternating

    n_elems = max(1, nbytes // 4)
    runs = [_edge_run(flat_mesh, n_dev, s, d, n_elems) for s, d in edges]
    for run in runs:  # compile + warm OUTSIDE the timed rounds
        run(1)
    samples = measure_alternating(runs, inner, rt, reps)
    return {
        edge: max(_median(samples[i]), 1e-9) for i, edge in enumerate(edges)
    }


def probe(
    mesh,
    nbytes: int = DEFAULT_NBYTES,
    lat_nbytes: Optional[int] = None,
    reps: int = 3,
    inner: int = 1,
) -> dict:
    """Measure every neighbor hop of ``mesh`` and return the stamped probe
    doc (``bench: fabric_probe``).  Does NOT consult or write the cache —
    ``ensure`` is the load-or-probe entry."""
    import jax
    from jax.sharding import Mesh

    from stencil_tpu import telemetry
    from stencil_tpu.tune.key import chip_kind

    devices = mesh.devices.flatten()
    n_dev = len(devices)
    shape = dict(mesh.shape)
    topology = tuple(shape[a] for a in mesh.axis_names)
    links = neighbor_links(shape)
    edges = sorted({(l["src"], l["dst"]) for l in links})

    t_start = time.perf_counter()
    flat_mesh = Mesh(devices, ("d",))
    bw = lat = {}
    if edges:
        rt = _host_round_trip_s()
        bw = _sweep_edges(flat_mesh, n_dev, edges, nbytes, reps, inner, rt)
        if lat_nbytes is not None:
            lat = _sweep_edges(flat_mesh, n_dev, edges, lat_nbytes, reps, inner, rt)
        telemetry.inc(names.FABRIC_PROBE_RUNS, len(edges))
    seconds = time.perf_counter() - t_start

    matrix = [[0.0] * n_dev for _ in range(n_dev)]
    out_links = []
    for l in links:
        sec = bw[(l["src"], l["dst"])]
        gbps = nbytes / sec / 1e9
        entry = dict(l, gbps=round(gbps, 3))
        if lat:
            entry["latency_us"] = round(lat[(l["src"], l["dst"])] * 1e6, 3)
        out_links.append(entry)
        matrix[l["src"]][l["dst"]] = round(gbps, 3)
        telemetry.observe(names.FABRIC_LINK_GBPS, gbps)
    if edges:
        telemetry.observe(names.FABRIC_PROBE_SECONDS, seconds)

    jax_v, jaxlib_v = _toolchain()
    return {
        "schema": SCHEMA,
        "bench": "fabric_probe",
        "jax": jax_v,
        "jaxlib": jaxlib_v,
        "chip": chip_kind(),
        "topology": list(topology),
        "axes": list(mesh.axis_names),
        "n_devices": n_dev,
        "nbytes": int(nbytes),
        "lat_nbytes": None if lat_nbytes is None else int(lat_nbytes),
        "ts": time.time(),
        "protocol": {"reps": reps, "inner": inner, "edges": len(edges)},
        "seconds": round(seconds, 6),
        "links": out_links,
        "matrix": matrix,
    }


def ensure(
    mesh,
    nbytes: int = DEFAULT_NBYTES,
    lat_nbytes: Optional[int] = None,
    reps: int = 3,
    inner: int = 1,
    force: bool = False,
) -> dict:
    """Load-or-probe: the cached matrix for this (topology, chip, payload)
    when the stamp matches — ZERO device work on a warm cache — else one
    probe sweep, persisted for every later run."""
    from stencil_tpu import telemetry
    from stencil_tpu.tune.key import chip_kind

    shape = dict(mesh.shape)
    topology = tuple(shape[a] for a in mesh.axis_names)
    key = probe_key(topology, chip_kind(), nbytes, lat_nbytes)
    doc = None if force else load(key)
    if doc is not None:
        telemetry.inc(names.FABRIC_CACHE_HIT)
        _emit(doc, source="cache")
        return doc
    telemetry.inc(names.FABRIC_CACHE_MISS)
    doc = probe(mesh, nbytes=nbytes, lat_nbytes=lat_nbytes, reps=reps, inner=inner)
    store(doc)
    _emit(doc, source="probe")
    return doc


def _emit(doc: dict, source: str) -> None:
    from stencil_tpu import telemetry

    slowest = link_model(doc).get("slowest") or {}
    telemetry.emit_event(
        names.EVENT_FABRIC_PROBE,
        source=source,
        topology=doc["topology"],
        chip=doc["chip"],
        edges=doc["protocol"]["edges"],
        seconds=doc["seconds"],
        slowest_gbps=slowest.get("gbps"),
    )


# --- derived views ------------------------------------------------------------


def link_model(doc_or_mesh, **ensure_kwargs) -> dict:
    """Per-mesh-axis/per-direction aggregate of a probe doc — the shape
    placement and tuner consumers key on.  Accepts a probe doc, or a Mesh
    (which goes through ``ensure``: a cold cache PROBES).

    Returns ``{"axes": {axis: {side: {"gbps_min", "gbps_med", "links"}}},
    "slowest": {axis, side, gbps, src, dst} | None}``.
    """
    doc = (
        doc_or_mesh
        if isinstance(doc_or_mesh, dict)
        else ensure(doc_or_mesh, **ensure_kwargs)
    )
    axes: Dict[str, dict] = {}
    slowest = None
    for l in doc.get("links", []):
        side = axes.setdefault(l["axis"], {}).setdefault(
            l["side"], {"gbps_min": None, "gbps_med": None, "_gbps": [], "links": 0}
        )
        side["_gbps"].append(l["gbps"])
        side["links"] += 1
        if slowest is None or l["gbps"] < slowest["gbps"]:
            slowest = {k: l[k] for k in ("axis", "side", "gbps", "src", "dst")}
    for per_side in axes.values():
        for side in per_side.values():
            gs = side.pop("_gbps")
            side["gbps_min"] = min(gs)
            side["gbps_med"] = round(_median(gs), 3)
    return {"axes": axes, "slowest": slowest}


def summary(doc: dict) -> dict:
    """Compact JSON-safe fabric state for the heartbeat's ``fabric`` key
    (status.json stays small; the full matrix lives in the artifact)."""
    model = link_model(doc)
    return {
        "topology": doc["topology"],
        "chip": doc["chip"],
        "nbytes": doc["nbytes"],
        "axes": {
            axis: {side: s["gbps_med"] for side, s in per_side.items()}
            for axis, per_side in model["axes"].items()
        },
        "slowest": model["slowest"],
        "matrix": doc["matrix"],
    }
