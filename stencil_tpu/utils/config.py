"""Runtime configuration enums.

Parity target: ``MethodFlags`` (reference include/stencil/stencil.hpp:29-41)
and ``PlacementStrategy`` (partition.hpp:312).  On TPU the five transports
collapse into XLA collectives, so the method flags select the *exchange
implementation* used by ``DistributedDomain.exchange`` — primarily for
benchmarking alternatives, exactly the role the reference's flags play:

* ``Ppermute``   — 3-axis-sweep ``lax.ppermute`` inside ``shard_map`` (the
                   production path; subsumes CudaMpi / CudaAwareMpi /
                   CudaMpiColocated / CudaMemcpyPeer / CudaKernel).
* ``AllGather``  — debug path: all-gather the global field and re-slice
                   (obviously slow; validates the ppermute path).
* ``RollCompare`` — host/debug: exchange implied by ``jnp.roll`` on the
                   gathered global array (test oracle).
"""

from __future__ import annotations

import enum
import os


def _parse_env(name: str, raw: str, conv, kind: str, minimum=None):
    try:
        val = conv(raw)
    except (TypeError, ValueError):
        raise ValueError(
            f"{name}={raw!r} is not a valid {kind} (set a plain {kind} or "
            f"unset {name})"
        ) from None
    if minimum is not None and val < minimum:
        raise ValueError(
            f"{name}={raw!r} is below the minimum {minimum} (a too-small "
            f"value would silently disable the feature {name} tunes)"
        )
    return val


def env_int(name: str, default: int, minimum: int = None) -> int:
    """Validated integer env read: a malformed or out-of-range value raises a
    message NAMING the env var at the read site, instead of a bare
    ``ValueError`` deep inside planning/compile."""
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return default
    return _parse_env(name, raw, int, "integer", minimum)


def env_float(name: str, default: float, minimum: float = None) -> float:
    """``env_int`` for floats."""
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return default
    return _parse_env(name, raw, float, "number", minimum)


_BOOL_WORDS = {
    "1": True, "true": True, "yes": True, "on": True,
    "0": False, "false": False, "no": False, "off": False,
}


def env_bool(name: str, default: bool) -> bool:
    """``env_int`` for booleans: 1/true/yes/on and 0/false/no/off; anything
    else raises naming the variable at the read site."""
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return default
    val = _BOOL_WORDS.get(raw.strip().lower())
    if val is None:
        raise ValueError(
            f"{name}={raw!r} is not a valid boolean (use 1/0, true/false, "
            f"yes/no, on/off — or unset {name})"
        )
    return val


def env_str(name: str, default=None):
    """Validated-read-site string env read: empty and unset both mean
    "use the default", so a knob cleared with ``NAME=`` behaves like an
    unset one instead of smuggling an empty path/choice downstream."""
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return default
    return raw


def env_choice(name: str, default: str, choices) -> str:
    """``env_bool`` for small closed vocabularies (e.g. auto/0/1): anything
    outside ``choices`` raises naming the variable at the read site instead
    of silently falling through a string-compare chain."""
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return default
    val = raw.strip()
    if val not in choices:
        raise ValueError(
            f"{name}={raw!r} is not one of {'/'.join(sorted(choices))} "
            f"(or unset {name})"
        )
    return val


def pallas_interpret() -> bool:
    """Run pallas kernels in interpret mode?  THE one inference, shared by
    every driver and by the ops that pick between a Mosaic kernel and its
    XLA twin: only the ``tpu`` backend compiles them; any other backend
    interprets."""
    import jax

    return jax.default_backend() != "tpu"


#: the checkout root, from this file's own location
#: (<root>/stencil_tpu/utils/config.py) — never a temporary name, a pid or
#: a time: the directory is part of every cache key, so one that moves
#: never hits
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def compile_cache_dir() -> str:
    """The persistent XLA compilation cache directory:
    ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (used as is),
    otherwise the fixed ``<checkout>/.jax_cache`` (git-ignored)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        _CHECKOUT, ".jax_cache"
    )


def apply_compile_cache() -> str:
    """Turn jax's persistent compilation cache on at ``compile_cache_dir()``.

    Called at ``stencil_tpu`` package import, i.e. before any of this
    framework's code can trigger a backend compile.  Where the environment
    already names a directory the program sets nothing: jax reads that
    variable itself.  Otherwise the fixed in-checkout path is exported as
    ``JAX_COMPILATION_CACHE_DIR`` (jax reads it at its own import, and
    child processes inherit it) and, when jax is already imported, applied
    to the live config too (the cache initializes lazily at first compile,
    so post-import application is still "before first backend use").  jax's
    own thresholds decide which entries are worth keeping.  Returns the
    directory in use.

    It is also where the set-up account starts listening to jax's own
    compile and cache events (``telemetry.watch_jax``: only if jax is
    already imported; ``realize()`` makes sure later)."""
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.environ["JAX_COMPILATION_CACHE_DIR"] = path
        import sys

        if "jax" in sys.modules:  # jax read the env at its own import
            import jax

            jax.config.update("jax_compilation_cache_dir", path)
    from stencil_tpu import telemetry

    telemetry.watch_jax()
    return path


class MethodFlags(enum.Flag):
    Non = 0
    # TPU-native methods
    Ppermute = enum.auto()
    AllGather = enum.auto()
    RollCompare = enum.auto()
    # Reference-compat aliases (stencil.hpp:29-41): all map onto the collective
    # path; accepted so reference-style driver flags keep working.
    CudaMpi = Ppermute
    CudaAwareMpi = Ppermute
    CudaMpiColocated = Ppermute
    CudaMemcpyPeer = Ppermute
    CudaKernel = Ppermute
    # Reference All (stencil.hpp:36-40) is the production-transport set — all
    # of which collapse to the collective path here; the debug AllGather
    # method is opt-in only.
    All = Ppermute

    def and_(self, o: "MethodFlags") -> bool:
        return bool(self & o)


class PlacementStrategy(enum.Enum):
    """partition.hpp:312 — NodeAware maps to torus-aware mesh axis ordering."""

    NodeAware = 0
    Trivial = 1
