"""AOT executable cache: admission-time compiles, bounded and pre-warmed.

Admission control must answer "is this workload key WARM?" without running
anything: a warm key dispatches immediately; a cold key pays a
``jax.jit(...).lower().compile()`` at admission, bounded by the admission
budget (``compile_budget_s``) so one tenant's exotic workload cannot park
the dispatch loop behind an unbounded compile.  A cold compile that blows
the budget is STILL kept — the work is done, discarding it would re-pay it
— but the triggering request is refused with a classified, retryable
``OverloadError(compile_budget)``: its re-submission hits the now-warm key
and admits instantly, and every other tenant saw one bounded stall instead
of an open-ended one.

Two warmth layers (docs/serving.md "Admission"):

* **in-process** — the compiled executable itself, keyed by
  ``tune/key.py`` ``WorkloadKey.digest()``;
* **cross-process** — a JSON stamp per digest (tune/cache.py's schema +
  toolchain-stamp pattern: corrupt/stale = miss, never a crash) recording
  that this key compiled before.  A stamped key re-compiles WITHOUT the
  budget refusal on a server restart: the persistent XLA executable cache
  (``utils/config.compile_cache_dir``, applied at package import) makes
  that rebuild a cache read, so treating it as warm is honest — and when the
  XLA cache was wiped the stamp's recorded seconds tell admission what the
  rebuild will really cost.
"""

from __future__ import annotations

import json
import os
import time
from typing import Callable, Optional

from stencil_tpu import telemetry
from stencil_tpu.resilience.taxonomy import OverloadError
from stencil_tpu.telemetry import names as tm

#: bump when the stamp vocabulary changes incompatibly (tune/cache.py SCHEMA
#: convention: a mismatch is a MISS, never a crash)
SCHEMA = 1


def _toolchain():
    import jax

    try:
        import jaxlib

        jaxlib_v = getattr(jaxlib, "__version__", "")
    except Exception:  # noqa: BLE001 — jaxlib layout varies across builds
        jaxlib_v = ""
    return jax.__version__, jaxlib_v


def default_stamp_dir() -> str:
    """``serve_aot/`` inside the persistent XLA cache directory — the
    stamps describe ITS contents, so they live beside it.  (An empty
    ``stamp_dir`` keeps an ``AOTCache`` in-process only.)"""
    from stencil_tpu.utils.config import compile_cache_dir

    return os.path.join(compile_cache_dir(), "serve_aot")


class AOTCache:
    """Compiled executables by workload-key digest, with persisted warmth
    stamps.  ``clock`` is injectable (fake-clock tests measure compiles
    without sleeping)."""

    def __init__(self, stamp_dir: Optional[str] = None, clock: Callable[[], float] = time.monotonic):
        self._exec: dict = {}
        self._stamps: dict = {}
        self.clock = clock
        self.stamp_dir = stamp_dir if stamp_dir is not None else default_stamp_dir()
        if self.stamp_dir:
            self._load_stamps()

    # --- warmth ---------------------------------------------------------------

    def warm(self, digest: str) -> bool:
        """True when the executable is resident in THIS process."""
        return digest in self._exec

    def stamped(self, digest: str) -> bool:
        """True when a previous process compiled this key on this
        toolchain (re-compiling it is a persistent-XLA-cache read, not a
        fresh compile — admission treats it as warm)."""
        return digest in self._stamps

    def get(self, digest: str):
        return self._exec.get(digest)

    # --- compile --------------------------------------------------------------

    def compile(
        self,
        digest: str,
        build: Callable[[], object],
        budget_s: Optional[float] = None,
        label: str = "serve",
        key_doc: Optional[dict] = None,
    ):
        """Build (``jax.jit(...).lower().compile()`` inside ``build``),
        cache, and stamp the executable for ``digest``.  Raises a
        retryable ``OverloadError(compile_budget)`` when the measured
        compile exceeded ``budget_s`` AND the key was not stamped warm by
        a previous process — AFTER caching, so the refusal can never
        repeat for this key."""
        t0 = self.clock()
        with telemetry.span(tm.EVENT_COMPILE, total=tm.PHASE_COMPILE, label=f"serve:{label}"):
            exe = build()
        seconds = self.clock() - t0
        telemetry.observe(tm.SERVE_COMPILE_SECONDS, seconds)
        self._exec[digest] = exe
        was_stamped = self.stamped(digest)
        self._store_stamp(digest, seconds, key_doc)
        if budget_s is not None and seconds > budget_s and not was_stamped:
            raise OverloadError(
                why="compile_budget",
                tenant=label,
                # the key is warm NOW: an immediate re-submission admits
                retry_after_s=0.0,
            )
        return exe, seconds

    # --- persisted stamps (tune/cache.py pattern) -----------------------------

    def _stamp_path(self, digest: str) -> str:
        return os.path.join(self.stamp_dir, f"{digest}.json")

    def _load_stamps(self) -> None:
        try:
            entries = os.listdir(self.stamp_dir)
        except OSError:
            return  # absent dir = cold cache
        jax_v, jaxlib_v = _toolchain()
        for name in entries:
            if not name.endswith(".json"):
                continue
            try:
                with open(os.path.join(self.stamp_dir, name)) as f:
                    doc = json.load(f)
            except (OSError, json.JSONDecodeError, UnicodeDecodeError):
                continue  # corrupt stamp = miss, never a crash
            if (
                not isinstance(doc, dict)
                or doc.get("schema") != SCHEMA
                or doc.get("jax") != jax_v
                or doc.get("jaxlib") != jaxlib_v
            ):
                continue  # stale toolchain: the XLA cache entry is too
            self._stamps[name[: -len(".json")]] = doc

    def _store_stamp(self, digest: str, seconds: float, key_doc: Optional[dict]) -> None:
        doc = {"schema": SCHEMA, "seconds": seconds, "key": key_doc or {}}
        jax_v, jaxlib_v = _toolchain()
        doc["jax"], doc["jaxlib"] = jax_v, jaxlib_v
        self._stamps[digest] = doc
        if not self.stamp_dir:
            return
        try:
            from stencil_tpu.utils.artifact import atomic_write_json

            atomic_write_json(self._stamp_path(digest), doc)
        except OSError as e:
            from stencil_tpu.utils.logging import log_warn

            log_warn(
                f"serve AOT stamp for {digest} not persisted ({e}); "
                "the key stays warm in-process only"
            )
