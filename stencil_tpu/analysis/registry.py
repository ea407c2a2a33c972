"""Canonical-matrix registry — the jax-free ground truth of WHAT the
program-contract verifier sweeps.

``stencil_tpu.analysis`` machine-checks the traced-program invariants
against real built artifacts (docs/static-analysis.md "Program contracts"),
and its value collapses the moment a new route ships outside the sweep: an
exchange route or overlap schedule that no canonical program exercises is
an unverified fast path.  This module records, per tuner axis, which ops/
module DEFINES the axis vocabulary and which values the canonical matrix
(``analysis/programs.py``) covers — and the ``contract-coverage`` lint rule
(``lint/rules/contract_coverage.py``) fails any ops/ module that grows the
vocabulary without growing the matrix.

Kept deliberately jax-free (plain literals, stdlib only): the lint rules
import it at check time, and the linter must run in milliseconds in any
interpreter.  The analysis package itself asserts the literals against the
real matrix (``tests/test_analysis.py::test_registry_matches_matrix``), so
this file cannot drift from the programs it describes.
"""

from __future__ import annotations

#: axis-vocabulary assignments the coverage rule watches: the NAME of the
#: module-level tuple in ops/ -> (defining module, values the canonical
#: matrix covers).  Growing the tuple in ops/ without growing the matching
#: entry here (and a canonical program for the new value) fails lint.
CANONICAL_AXES = {
    "EXCHANGE_ROUTES": {
        "module": "stencil_tpu/ops/exchange.py",
        "covered": (
            "direct",
            "zpack_xla",
            "zpack_pallas",
            "yzpack_xla",
            "yzpack_pallas",
        ),
    },
    "STREAM_OVERLAP": {
        "module": "stencil_tpu/ops/stream_plan.py",
        "covered": ("off", "split"),
    },
    "STREAM_HALO": {
        "module": "stencil_tpu/ops/stream_plan.py",
        "covered": ("array", "fused"),
    },
    "STORAGE_DTYPES": {
        "module": "stencil_tpu/ops/jacobi_pallas.py",
        "covered": ("native", "bf16"),
    },
    "SERVE_MODES": {
        "module": "stencil_tpu/serve/pack.py",
        "covered": ("batched", "subslice"),
    },
}

#: kernel-coverage ledger — the ``contract-coverage`` pattern one level
#: down: every top-level ops/ function that issues a ``pallas_call`` must
#: be named here, per defining module, so the kernel verifier's sweep
#: (``analysis/kernels.py``; contracts ``kernel-race``/``kernel-coverage``/
#: ``tiling-legal``) has a statically-checkable inventory of the pallas
#: box it is expected to open.  The ``kernel-ledger`` lint rule
#: (``lint/rules/kernel_ledger.py``) fails any ops/ module that grows a
#: kernel without growing this ledger; the kernels themselves are reached
#: through the canonical matrix (``analysis/programs.py``) plus the
#: fixture corpus (``tests/analysis_fixtures/``).
PALLAS_KERNELS = {
    "stencil_tpu/ops/halo_blend.py": (
        "blend_slab",
        "wrap_halo",
        "blend_slab_dynamic",
    ),
    "stencil_tpu/ops/jacobi_pallas.py": (
        "jacobi_wrap_step",
        "jacobi_shell_wavefront_step",
        "jacobi_zring_wavefront_step",
        "jacobi_slab_step",
        "jacobi_plane_step",
    ),
    "stencil_tpu/ops/pack.py": (
        "pallas_pack_slab",
        "pallas_unpack_slab",
        "pack_zshell_pallas",
        "unpack_zshell_pallas",
        "pack_yshell_pallas",
        "unpack_yshell_pallas",
    ),
    "stencil_tpu/ops/plane_stencil.py": (
        "mean6_shell_wavefront_step",
        "mean6_plane_step",
    ),
    "stencil_tpu/ops/stream_pass.py": (
        "stream_plane_pass",
        "stream_plane_pass_tiled",
        "stream_wavefront_pass",
        "stream_wrap_pass",
    ),
}

#: in-place streaming passes — kernels whose ``alias=`` lands an output on
#: the buffer the sequential grid is still reading from, some planes behind
#: — and the fixtures under ``tests/analysis_fixtures`` that trace the REAL
#: pass aliased and hold it to the ``inplace-order`` contract
#: (``analysis/kernels.py``).  ``tests/test_analysis.py::
#: test_inplace_passes_are_proven_aliased`` pins that each fixture carries
#: that kernel aliased and comes out clean: a fixture that silently lost
#: its alias would prove nothing.
INPLACE_PASSES = {
    "stream_plane_pass": (
        "inplace_order_plane_r1_clean.py",
        "inplace_order_plane_r4_clean.py",
        "inplace_order_plane_writers_clean.py",
        "inplace_order_plane_lagged_clean.py",
        "inplace_order_plane_wrapped_clean.py",
        "inplace_order_plane_renamed_clean.py",
        "inplace_order_plane_tiled_clean.py",
    ),
    "stream_wavefront_pass": ("inplace_order_wavefront_clean.py",),
}
