"""Rank-tagged structured JSONL event sink.

One line per event, appended to ``<dir>/events_<rank>.jsonl``:

    {"ts": <epoch seconds>, "event": "<names.EVENT_*>", "rank": <int>,
     ...event-specific fields...}

The rank tag uses the same fail-closed probe as ``utils/logging._rank``: jax
is consulted ONLY when a backend is verifiably already initialized, so
emitting an event can never trigger a backend bring-up (which would make a
launcher parent hold the chip its child needs).  Before
initialization events tag rank 0 — and the whole sink path is resolved
lazily at first emit, after which the rank is stable for the file's
lifetime.

Writes are line-buffered appends; every line is one complete JSON document,
so a crashed run leaves a readable (if truncated) log.  Non-JSON field
values degrade to ``str()`` rather than failing the run.
"""

from __future__ import annotations

import json
import os
import time
from typing import Optional

from stencil_tpu.utils.logging import _rank


class EventSink:
    def __init__(self, out_dir: str):
        self._dir = out_dir
        self._f = None
        self._path: Optional[str] = None

    def path(self) -> str:
        if self._path is None:
            self._path = os.path.join(self._dir, f"events_{_rank()}.jsonl")
        return self._path

    def emit(self, event: str, fields: dict) -> None:
        if self._f is None:
            os.makedirs(self._dir, exist_ok=True)
            self._f = open(self.path(), "a", buffering=1)
        rec = {"ts": time.time(), "event": event, "rank": _rank()}
        rec.update(fields)
        self._f.write(json.dumps(rec, default=str) + "\n")

    def close(self) -> None:
        if self._f is not None:
            self._f.close()
            self._f = None
