"""One built cell a variant for the ``tests/test_bench_*.py`` rehearsals.

``benchmark/harness/window.py run`` asks the configuration's factory for a new
cell every run, and a new cell is a new model, a new step and -- interpreted on
the CPU -- tens of seconds of tracing and lowering its Pallas calls before the
first dispatch (ROADMAP D13: what tier-1's seconds are spent on).  A rehearsal
file runs the same program several times over (untraced, traced, the controls
that alter only what is compared): while ``shared_build`` is open the factory
hands out ONE cell a (rehearsed configuration, storage), whose compiled objects
serve every such run, as ``--also-verify`` serves more seeds on the chip.
``window.run`` re-fills the cell from its seed, so no run sees another's
state.  A control that BREAKS the cell (rebuilds its step, edits its set-up)
asks for a cell of its own: it runs outside the context, as before.  No file
under ``benchmark/`` changes: the factory is looked up by name at run time.
"""

import contextlib
import importlib
import json

import pytest

_CELLS = {}


@contextlib.contextmanager
def shared_build(factory: str):
    module = importlib.import_module(factory)
    real = module.build

    def build(config, devices, interpret, lower_precision=False):
        key = (factory, json.dumps(config, sort_keys=True), len(devices), bool(lower_precision))
        if key not in _CELLS:
            _CELLS[key] = real(config, devices, interpret, lower_precision=lower_precision)
        return _CELLS[key]

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(module, "build", build)
        yield
