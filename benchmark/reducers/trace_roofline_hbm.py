"""HBM roofline share of a class of kernels whose operand shape is in the
op's own label: per call one read and one write of the operand."""

import re

from benchmark.harness import trace
from benchmark.reducers.trace_share import matching

ITEMSIZE = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1, "u8": 1, "f64": 8}
_SHAPE = re.compile(r"_([a-z]+[0-9]+)((?:_[0-9]+)+)_")


def operand_bytes(label: str) -> int:
    """Bytes of every array in the label (``custom-call_f32_512_512_512_``
    -> 512^3 x 4; a tuple result sums its members)."""
    total = 0
    for dtype, dims in _SHAPE.findall(label):
        n = 1
        for d in dims.strip("_").split("_"):
            n *= int(d)
        total += n * ITEMSIZE[dtype]
    return total


def reduce(ctx, include, exclude=(), passes=2):
    """100 x (bytes / peak HBM bytes/s) / (the calls' device time), over all
    chips.  ``bytes`` is ``passes`` (one read + one write) x the result's
    bytes per call, from the calls as made; the time is the union of the
    calls' intervals.  A kernel that reads and writes its operand at least
    once cannot take less time than that traffic at the peak, so the share
    cannot pass 100 unless the label's shape is not the operand's."""
    table = ctx["table"]
    if table is None:
        return None
    least_s = took_s = 0.0
    for ops in table["devices"].values():
        hit = matching(ops, include, exclude)
        least_s += sum(passes * operand_bytes(o[0]) for o in hit) / ctx["peaks"]["hbm_bytes_per_s"]
        took_s += trace.busy_ns(hit) / 1e9
    if not took_s or not least_s:
        return None
    return 100.0 * least_s / took_s
