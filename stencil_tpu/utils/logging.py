"""Leveled, rank-tagged logging.

Parity target: reference include/stencil/logging.hpp:12-53 — SPEW/DEBUG/INFO/
WARN/ERROR/FATAL macros, each line tagged ``LEVEL[file:line]{rank}``, filtered
by ``STENCIL_OUTPUT_LEVEL``.  Reference semantics replicated exactly: a
message prints when the configured level >= its verbosity number (SPEW=5,
DEBUG=4, INFO=3, WARN=2, ERROR=1, FATAL=0 — CMakeLists.txt:55-66), i.e.
HIGHER level = MORE verbose; default INFO (3).  The env var accepts both the
symbolic names (SPEW..FATAL, like the CMake option) and the numeric values.
"""

from __future__ import annotations

import datetime
import os
import sys

# verbosity numbers (CMakeLists.txt:55-66): higher = chattier
SPEW, DEBUG, INFO, WARN, ERROR, FATAL = 5, 4, 3, 2, 1, 0
_NAMES = {SPEW: "SPEW", DEBUG: "DEBUG", INFO: "INFO", WARN: "WARN", ERROR: "ERROR", FATAL: "FATAL"}
_BY_NAME = {v: k for k, v in _NAMES.items()}


def _parse_level(raw: str) -> int:
    raw = raw.strip().upper()
    if raw in _BY_NAME:
        return _BY_NAME[raw]
    try:
        return int(raw)
    except ValueError:
        print(f"WARN unrecognized STENCIL_OUTPUT_LEVEL={raw!r}, using INFO", file=sys.stderr)
        return INFO


# stencil-lint: disable=env-read import-time level parse: a logging import must never crash, so malformed values warn-and-default instead of raising like the env_* helpers do
_LEVEL = _parse_level(os.environ.get("STENCIL_OUTPUT_LEVEL", "INFO"))


def _parse_timestamps() -> bool:
    # validated boolean read (utils/config.py pattern) — but a logging import
    # must never crash the process, so like STENCIL_OUTPUT_LEVEL above a
    # malformed value warns and falls back to the default
    from stencil_tpu.utils.config import env_bool

    try:
        return env_bool("STENCIL_LOG_TIMESTAMPS", False)
    except ValueError as e:
        print(f"WARN {e}; timestamps stay off", file=sys.stderr)
        return False


# ISO-8601 UTC timestamps on every line (STENCIL_LOG_TIMESTAMPS=1): off by
# default to preserve the reference line format, on when log lines must be
# correlated with telemetry JSONL events (whose ``ts`` is epoch seconds)
_TIMESTAMPS = _parse_timestamps()


def set_level(level) -> None:
    global _LEVEL
    _LEVEL = _parse_level(str(level))


def set_timestamps(on: bool = True) -> None:
    global _TIMESTAMPS
    _TIMESTAMPS = bool(on)


def _rank() -> int:
    # ONLY consult jax if a backend is ALREADY initialized: a log line must
    # never force a backend bring-up (jax.process_index() initializes the
    # default backend even when jax is merely imported — and a process that
    # has touched the backend HOLDS the chip, so a launcher parent that only
    # logged would starve the child it starts).  Pre-initialization log
    # lines tag rank 0.
    jax = sys.modules.get("jax")
    if jax is None:
        return 0
    # FAIL CLOSED: only ask jax for the rank when a backend is verifiably
    # already up — if the (private) bridge module or its _backends registry
    # is absent on some jax version, degrade the rank tag to 0 rather than
    # risk triggering the bring-up
    xb = sys.modules.get("jax._src.xla_bridge")
    if xb is None or not getattr(xb, "_backends", None):
        return 0
    try:
        return jax.process_index()
    except Exception:
        return 0


def _emit(verbosity: int, msg: str, stacklevel: int = 2) -> None:
    # print when configured level >= message verbosity (logging.hpp:12-53).
    # ``stacklevel`` counts frames above _emit to the line being attributed
    # (2 = the caller of a log_* function); a wrapper that forwards to log_*
    # passes a larger stacklevel so its CALLER's file:line is tagged, not the
    # wrapper's.  An out-of-range walk degrades to "?:0" rather than raising
    # from inside a log line.
    if _LEVEL < verbosity:
        return
    try:
        f = sys._getframe(stacklevel)
        fname, lineno = os.path.basename(f.f_code.co_filename), f.f_lineno
    except ValueError:
        fname, lineno = "?", 0
    stamp = ""
    if _TIMESTAMPS:
        stamp = (
            datetime.datetime.now(datetime.timezone.utc).isoformat(
                timespec="microseconds"
            )
            + " "
        )
    tag = f"[{fname}:{lineno}]{{{_rank()}}}"
    print(f"{stamp}{_NAMES[verbosity]}{tag} {msg}", file=sys.stderr)


def log_spew(msg: str, stacklevel: int = 1) -> None:
    _emit(SPEW, msg, stacklevel + 1)


def log_debug(msg: str, stacklevel: int = 1) -> None:
    _emit(DEBUG, msg, stacklevel + 1)


def log_info(msg: str, stacklevel: int = 1) -> None:
    _emit(INFO, msg, stacklevel + 1)


def log_warn(msg: str, stacklevel: int = 1) -> None:
    _emit(WARN, msg, stacklevel + 1)


def log_error(msg: str, stacklevel: int = 1) -> None:
    _emit(ERROR, msg, stacklevel + 1)


def log_fatal(msg: str, stacklevel: int = 1) -> None:
    """Unlike the reference's exit(1) (logging.hpp:47-50), raise — a Python
    framework should unwind, not kill the interpreter under the user."""
    _emit(FATAL, msg, stacklevel + 1)
    raise RuntimeError(msg)
