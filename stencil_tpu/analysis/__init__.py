"""stencil-analysis — the program-contract verifier.

Where ``stencil_tpu.lint`` machine-checks SOURCE invariants over the stdlib
AST, this package machine-checks the TRACED-PROGRAM invariants over closed
jaxprs (and lowered HLO text): var-level taint/reachability, eqn visitors
that descend into pjit/scan/while subjaxprs (pallas calls and custom calls
stay opaque to the TAINT analysis, conservatively — the kernel verifier
``analysis/kernels.py`` descends into pallas bodies deliberately), and a
registry of program contracts checked
against REAL built artifacts — the canonical route × overlap × halo ×
storage-dtype matrix (``analysis/programs.py``).

Entry points:

* ``python -m stencil_tpu.analysis``      — verify the canonical matrix
  (exit 0 clean / 1 findings / 2 usage; ``--select``, ``--json``,
  ``--list-contracts``, ``--program``, ``--fixture`` — mirroring the lint
  CLI).
* :func:`check` / :func:`check_artifacts` — in-process verification, the
  tier-1 gate's path (``tests/test_analysis.py``).
* :func:`check_vmem` — the static VMEM verdict ``tune/space.py`` and the
  stream ladder consult to prune candidates before a compile-and-catch
  VMEM_OOM.
* :func:`check_kernel_legal` — the static Mosaic tiling-legality verdict
  (``analysis/kernels.py``), wired beside ``check_vmem``: the tuner prunes
  statically-illegal candidates with zero compile attempts and the ladder
  records them as COMPILE_REJECT descents without compiling.

This module stays import-light (no jax at import time): the lint rules
read the coverage ledger (``analysis/registry.py``) through it, and
``--list-contracts`` must answer in milliseconds.
"""

from stencil_tpu.analysis.framework import (  # noqa: F401
    Contract,
    Finding,
    ProgramArtifact,
    all_contracts,
    check,
    check_artifacts,
    register,
    step_artifact,
    trace_artifact,
)


def check_vmem(dd, plan, budget=None):
    """Static scoped-VMEM verdict for a stream plan on a realized domain —
    ``None`` fits, else the reason (``analysis/vmem.py``)."""
    from stencil_tpu.analysis import vmem as _vmem

    return _vmem.check_vmem(dd, plan, budget=budget)


def check_kernel_legal(dd, plan):
    """Static Mosaic tiling-legality verdict for a stream plan on a realized
    domain — ``None`` legal, else the reason (``analysis/kernels.py``)."""
    from stencil_tpu.analysis import kernels as _kernels

    return _kernels.check_kernel_legal(dd, plan)
