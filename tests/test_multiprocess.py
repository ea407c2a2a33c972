"""Tier-3: REAL multi-process distributed tests (2 coordinated processes).

The reference's third test tier is a genuinely multi-process binary — 2 MPI
ranks under cuda-memcheck (test/CMakeLists.txt:34-45).  The analog here:
spawn 2 subprocesses that join one ``jax.distributed`` job on CPU (4 fake
devices each, 8 total), and run the ripple halo exchange across the process
boundary plus the host-coordination API (mp_worker.py).  This is the only
place ``distributed.initialize``/``barrier``/``broadcast_from_host0``/
``allgather_hosts`` and the DCN process-split execute with
``process_count() > 1``.
"""

import os
import socket
import subprocess
import sys

import pytest


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


@pytest.mark.slow
def test_two_process_exchange_and_coordination():
    port = _free_port()
    worker = os.path.join(os.path.dirname(__file__), "mp_worker.py")
    env = {
        k: v
        for k, v in os.environ.items()
        # workers set their own platform/device-count flags
        if k not in ("XLA_FLAGS", "JAX_PLATFORMS", "JAX_NUM_CPU_DEVICES")
    }
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(worker))
    procs = [
        subprocess.Popen(
            [sys.executable, worker, str(port), str(i), "2"],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            env=env,
        )
        for i in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=540)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail("multi-process workers timed out:\n" + "\n".join(outs))
    # some jax builds cannot run true multi-process collectives on the CPU
    # backend at all ("Multiprocess computations aren't implemented on the
    # CPU backend") — a capability absence, not a regression in this repo
    if any(
        "Multiprocess computations aren't implemented on the CPU backend" in o
        for o in outs
    ):
        pytest.skip("this jax build lacks multi-process CPU collectives")
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {i} failed:\n{out}"
        assert f"MP_OK {i}" in out, f"worker {i} output:\n{out}"
