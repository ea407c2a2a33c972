"""Persistent tuned-config cache: one JSON file per workload key.

Layout: ``<cache_dir>/<key.digest()>.json`` with

    {"schema": 1,
     "jax": "<jax.__version__>", "jaxlib": "<jaxlib.__version__>",
     "key": {...WorkloadKey...},
     "config": {...the winning config...},
     "meta": {...trial provenance (steady-state numbers, trial counts)...}}

``load`` returns ``(config, meta)`` only when the schema AND the jax/jaxlib
versions match the running process — a toolchain upgrade silently
invalidates every persisted config (PERF_NOTES.md: "re-qualify them when
the toolchain or chip generation changes"), exactly like a cold cache —
and the config names no kernel form that has since been removed.  A
corrupt or truncated file is treated as a miss (warn, never crash): the
cache is an accelerator, not a dependency.

The directory comes from ``STENCIL_TUNE_CACHE`` (validated read,
default ``~/.cache/stencil_tpu/tune``); drivers override it per run via
``--tune-cache`` (``tune.set_cache_dir``).
"""

from __future__ import annotations

import json
import os
from typing import Optional, Tuple

from stencil_tpu.tune.key import WorkloadKey
from stencil_tpu.utils.config import env_str

#: bump when the persisted-config vocabulary changes incompatibly; a schema
#: mismatch is a MISS (stale entries re-qualify, never crash).  History:
#: 1 — depth/alias/layout/stream-plan configs (the autotuner PR);
#: 2 — the ``exchange_route`` field (exchange-route PR): entries persisted
#:     before the packed z-shell routes existed must not be consulted as if
#:     they had compared against them.
SCHEMA = 2

_DEFAULT_DIR = os.path.join("~", ".cache", "stencil_tpu", "tune")

#: process-local override (driver --tune-cache); None = use the env/default
_dir_override: Optional[str] = None


def set_dir_override(path: Optional[str]) -> None:
    global _dir_override
    _dir_override = path


def cache_dir() -> str:
    path = _dir_override or env_str("STENCIL_TUNE_CACHE", _DEFAULT_DIR)
    return os.path.abspath(os.path.expanduser(path))


def _toolchain() -> Tuple[str, str]:
    import jax

    try:
        import jaxlib

        jaxlib_v = getattr(jaxlib, "__version__", "")
    except Exception:  # noqa: BLE001 — jaxlib layout varies across builds
        jaxlib_v = ""
    return jax.__version__, jaxlib_v


def path_for(key: WorkloadKey) -> str:
    return os.path.join(cache_dir(), f"{key.digest()}.json")


def load(key: WorkloadKey) -> Optional[Tuple[dict, dict]]:
    """(config, meta) for ``key``, or None on a miss (absent, corrupt, or
    persisted by a different toolchain/schema)."""
    path = path_for(key)
    try:
        with open(path) as f:
            doc = json.load(f)
    except FileNotFoundError:
        return None
    except (OSError, json.JSONDecodeError, UnicodeDecodeError) as e:
        from stencil_tpu.utils.logging import log_warn

        log_warn(f"tune cache {path} is unreadable ({e}); treating as a miss")
        return None
    jax_v, jaxlib_v = _toolchain()
    if (
        not isinstance(doc, dict)
        or doc.get("schema") != SCHEMA
        or doc.get("jax") != jax_v
        or doc.get("jaxlib") != jaxlib_v
        or not isinstance(doc.get("config"), dict)
    ):
        from stencil_tpu.utils.logging import log_info

        log_info(
            f"tune cache {path} is stale (schema/toolchain mismatch); "
            "configs must be re-qualified on this toolchain — treating as a miss"
        )
        return None
    config = doc["config"]
    # the stale-record rule of the removed matrix-unit axis pair: a depth
    # measured under another kernel form is not this kernel's depth.  Entries
    # naming the one value that stayed (vpu / f32), or none, stay warm.
    unit, operand = config.get("compute_unit"), config.get("mxu_input")
    if unit not in (None, "vpu") or operand not in (None, "f32"):
        from stencil_tpu.utils.logging import log_warn

        log_warn(
            f"tune cache {path} was measured on a kernel form that no longer "
            f"exists (compute_unit={unit!r}, mxu_input={operand!r}); "
            "treating as a miss"
        )
        return None
    return config, doc.get("meta") or {}


def store(key: WorkloadKey, config: dict, meta: Optional[dict] = None) -> str:
    """Persist the winning config atomically (utils/artifact.py write-rename:
    a crashed run must not leave a truncated file a later run would
    half-parse)."""
    jax_v, jaxlib_v = _toolchain()
    doc = {
        "schema": SCHEMA,
        "jax": jax_v,
        "jaxlib": jaxlib_v,
        "key": key.to_dict(),
        "config": config,
        "meta": meta or {},
    }
    from stencil_tpu.utils.artifact import atomic_write_json

    return atomic_write_json(path_for(key), doc)
