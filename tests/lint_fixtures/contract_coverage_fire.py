# lint-fixture: select=contract-coverage rel=stencil_tpu/ops/exchange.py expect=contract-coverage,contract-coverage,bad-suppression
# Seeded violations: an axis vocabulary grown past the canonical-matrix
# ledger, and one assembled dynamically (not statically checkable); a
# reasoned suppression silences a third; a bare suppression fails.

EXCHANGE_ROUTES = ("direct", "zpack_xla", "zpack_pallas", "ypack_fused")

STREAM_OVERLAP = tuple(["off"] + ["split"])


def _experimental():
    return None


# stencil-lint: disable=contract-coverage fixture: prototype vocabulary behind a feature gate, matrix entry lands with the route PR
STORAGE_DTYPES = ("native", "bf16", "fp8")
# stencil-lint: disable=contract-coverage
