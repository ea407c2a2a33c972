# lint-fixture: select=span-name rel=stencil_tpu/fake.py expect=clean
# The sanctioned pattern: span labels are SPAN constants from names.py
# (device-time attribution keys on them), and non-literal labels pass
# through unexamined (the runtime registry is the backstop).
import jax

from stencil_tpu import telemetry
from stencil_tpu.telemetry import names as tm

with telemetry.annotate(tm.SPAN_OVERLAP_INTERIOR):
    pass
with telemetry.span(tm.SPAN_STEP, histogram=tm.STEP_SECONDS):
    pass
with telemetry.span(tm.SPAN_REALIZE, total=tm.PHASE_REALIZE):
    pass

with jax.named_scope(tm.SPAN_EXCHANGE_Z_LOW):  # a registered literal form
    pass


def dynamic(label, axis):
    telemetry.annotate(label)  # parameterized: not a literal
    # in-kernel direction scopes through the registry helper (the
    # span-registry contract checks the resolved string at trace level)
    return jax.named_scope(tm.exchange_direction_span(axis, "low"))
