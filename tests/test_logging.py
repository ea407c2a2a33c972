"""Tier-1: logging level semantics (higher = more verbose, logging.hpp)."""

import subprocess
import sys


def _run(env_level, code, extra_env=None):
    env = {"PATH": "/usr/bin:/bin", "PYTHONPATH": "."}
    if env_level is not None:
        env["STENCIL_OUTPUT_LEVEL"] = env_level
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        env=env,
        cwd="/root/repo",
        # a child that somehow initializes a backend (and waits on a
        # chip another process holds) must fail the test, not stall the suite
        timeout=120,
    )


CODE = (
    "from stencil_tpu.utils.logging import log_spew, log_info, log_error;"
    "log_spew('s'); log_info('i'); log_error('e')"
)


# stencil-lint: disable=slow-marker jax-free `python -c` child importing only utils.logging (~0.1s); level parsing happens at import so a fresh interpreter is the only honest probe
def test_symbolic_name_accepted():
    r = _run("SPEW", CODE)
    assert r.returncode == 0
    assert "SPEW" in r.stderr and "INFO" in r.stderr and "ERROR" in r.stderr


# stencil-lint: disable=slow-marker jax-free `python -c` child importing only utils.logging (~0.1s); level parsing happens at import so a fresh interpreter is the only honest probe
def test_higher_is_more_verbose():
    r = _run("5", CODE)  # SPEW: everything prints
    assert "SPEW" in r.stderr
    r = _run("1", CODE)  # ERROR: only error
    assert "SPEW" not in r.stderr and "INFO" not in r.stderr and "ERROR" in r.stderr


# stencil-lint: disable=slow-marker jax-free `python -c` child importing only utils.logging (~0.1s); level parsing happens at import so a fresh interpreter is the only honest probe
def test_default_is_info():
    r = _run(None, CODE)  # env var absent: default must be INFO
    assert "INFO" in r.stderr and "SPEW" not in r.stderr


# stencil-lint: disable=slow-marker jax-free `python -c` child importing only utils.logging (~0.1s); level parsing happens at import so a fresh interpreter is the only honest probe
def test_garbage_level_does_not_crash_import():
    r = _run("bogus", CODE)
    assert r.returncode == 0
    assert "unrecognized" in r.stderr


# stencil-lint: disable=slow-marker jax-free `python -c` child importing only utils.logging (~0.1s); level parsing happens at import so a fresh interpreter is the only honest probe
def test_timestamps_opt_in():
    """STENCIL_LOG_TIMESTAMPS=1 prefixes an ISO-8601 UTC timestamp (so log
    lines correlate with telemetry JSONL event ``ts`` fields); default
    format is unchanged."""
    import re

    iso = r"^\d{4}-\d{2}-\d{2}T\d{2}:\d{2}:\d{2}\.\d{6}\+00:00 INFO\["
    r = _run(None, CODE, extra_env={"STENCIL_LOG_TIMESTAMPS": "1"})
    lines = [l for l in r.stderr.splitlines() if "INFO" in l]
    assert lines and re.match(iso, lines[0]), lines
    r = _run(None, CODE, extra_env={"STENCIL_LOG_TIMESTAMPS": "true"})
    lines = [l for l in r.stderr.splitlines() if "INFO" in l]
    assert lines and re.match(iso, lines[0]), lines  # env_bool words accepted
    r = _run(None, CODE)  # default: no timestamp prefix
    lines = [l for l in r.stderr.splitlines() if "INFO" in l]
    assert lines and lines[0].startswith("INFO["), lines
    # malformed: warn + stay off, never crash the import (the
    # STENCIL_OUTPUT_LEVEL rule)
    r = _run(None, CODE, extra_env={"STENCIL_LOG_TIMESTAMPS": "bogus"})
    assert r.returncode == 0
    assert "STENCIL_LOG_TIMESTAMPS" in r.stderr
    lines = [l for l in r.stderr.splitlines() if "INFO[" in l]
    assert lines and lines[0].startswith("INFO["), lines


def test_stacklevel_attributes_through_wrappers(capsys):
    """A wrapper forwarding to log_* passes stacklevel so the [file:line]
    tag names the wrapper's CALLER, not the wrapper (telemetry event lines
    and log lines stay correlatable)."""
    from stencil_tpu.utils import logging as slog

    def wrapper(msg):
        slog.log_warn(msg, stacklevel=2)

    def plain(msg):
        slog.log_warn(msg)  # default: tags THIS line inside plain()

    wrapper("via-wrapper")  # tag must point at THIS file
    plain("via-plain")
    err = capsys.readouterr().err.splitlines()
    assert "test_logging.py" in err[0], err
    assert "test_logging.py" in err[1], err
    wrapped_line = int(err[0].split(":")[1].split("]")[0])
    plain_line = int(err[1].split(":")[1].split("]")[0])
    # the wrapper call is attributed to its caller (this test function),
    # dozens of lines below plain()'s in-function tag... both in this file,
    # and they must differ (the wrapper did NOT tag its own body)
    assert wrapped_line != plain_line


def test_emit_survives_out_of_range_stacklevel(capsys):
    from stencil_tpu.utils.logging import log_error

    log_error("deep", stacklevel=10_000)  # degrade to ?:0, never raise
    assert "[?:0]" in capsys.readouterr().err


def test_hashable_geometry():
    from stencil_tpu.core.geometry import LocalSpec
    from stencil_tpu.core.radius import Radius

    s = LocalSpec.make((4, 4, 4), (0, 0, 0), Radius.constant(1))
    assert hash(s) == hash(LocalSpec.make((4, 4, 4), (0, 0, 0), Radius.constant(1)))
    assert {s: 1}[s] == 1
