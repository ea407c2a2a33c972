"""A domain's raw blocks, saved once and put back for every case that shares it.

The files that build ONE model (or domain) a configuration and run many cases
on it (ROADMAP D13: a model's traced programs are most of a case's time) need
it back in its seeded state for each case.  ``fill`` / ``init_by_coords`` build
a fresh ``jax.jit`` a quantity a call (0.3 s each even on a cache hit), so the
seeded state is saved on the host once and restored by ``device_put``: every
raw cell, shell included, as the seeded fill left it, whatever ran since.
"""

import jax
import numpy as np


def snapshot(dd) -> dict:
    """``dd``'s current raw blocks on the host, each with where it lives."""
    return {name: (np.asarray(a), a.sharding) for name, a in dd._curr.items()}


def restore(dd, blocks: dict) -> None:
    """Put ``blocks`` (a ``snapshot`` of ``dd``) back as its current slot."""
    dd._curr = {name: jax.device_put(a, sh) for name, (a, sh) in blocks.items()}
