"""Test configuration: fake an 8-chip mesh on CPU.

Mirrors the reference's "fake cluster" trick (test_exchange.cu:57 forces two
subdomains onto one GPU): here we force the host platform to expose 8 virtual
devices so mesh/sharding tests run anywhere (SURVEY.md §4 port note).  Must be
set before jax initializes its backends.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
# Ask for the CPU explicitly, even on a machine with a chip: the test tiers
# are defined over the fake 8-chip fleet (STENCIL_TEST_PLATFORM=tpu runs the
# compiled tier on a real one).  Env and config knob are both set, so the
# drivers' "CPU only when asked for" guard sees the request either way.
_platform = os.environ.get("STENCIL_TEST_PLATFORM", "cpu")
os.environ["JAX_PLATFORMS"] = _platform
os.environ.setdefault("JAX_ENABLE_X64", "1")

# Hermetic autotuner: the fast-path planners consult the persistent tuned-
# config cache (stencil_tpu/tune/), and a developer's real cache entries
# must not leak into route/depth assertions (nor test runs pollute theirs) —
# so FORCE a fresh directory, overriding any exported STENCIL_TUNE_CACHE.
# Tests that exercise the cache point it at their own tmp_path.
import tempfile  # noqa: E402

os.environ["STENCIL_TUNE_CACHE"] = tempfile.mkdtemp(prefix="stencil_tune_test_")
# same hermeticity for the fabric observatory's link-matrix cache
# (stencil_tpu/telemetry/fabric.py): a developer's probed matrices must not
# warm-hit test ensure() calls, nor test probes pollute theirs
os.environ["STENCIL_FABRIC_CACHE"] = tempfile.mkdtemp(prefix="stencil_fabric_test_")

# ...and for jax's persistent compilation cache, unless the machine places
# it (JAX_COMPILATION_CACHE_DIR set — used as is): a session-private
# directory, so test runs neither read a developer's <checkout>/.jax_cache
# nor fill it
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(prefix="stencil_xla_test_")

import jax  # noqa: E402

jax.config.update("jax_platforms", _platform)
jax.config.update("jax_enable_x64", os.environ["JAX_ENABLE_X64"] != "0")
# ...and it keeps EVERY executable, not only those that took a second to
# compile (jax's default): the suite compiles the same small programs over and
# over -- a fill a quantity a domain, the control side of the bitwise cases --
# and a hit costs a tenth of the compile (``tests/test_lane_pad_vmem.py`` alone:
# 119 s -> 95 s; ROADMAP D13).  ``benchmark/harness/window.py`` sets the same two
# for its runs, process-wide, so a worker that had run one rehearsal worked this
# way already: now every test does, whatever ran before it.
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

import sys  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _trace_root_of_this_process():
    """The benchmark's rehearsals write their traces under ``window.OUT`` and
    ``harness/timeline.py`` reads "the newest ``*.xplane.pb``" under
    ``TRACE_ROOT``: in the checkout that is ONE directory for every xdist
    worker, and a traced rehearsal has read another worker's trace (ROADMAP
    M7 (viii): ``test_selftest_elastic[a]``, ``test_bench_lbm``).  A worker is
    a process and runs its tests in turn, so a root per process is a root per
    run; the benchmark's own files stay as they are."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark.harness import timeline, window

    out = tempfile.mkdtemp(prefix="stencil_bench_out_")
    window.OUT, timeline.TRACE_ROOT = out, os.path.join(out, "trace")
    yield
