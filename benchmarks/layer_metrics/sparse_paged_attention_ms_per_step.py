"""Kernels on the serve path, learned sparse attention inside paged K / V:
device time of the walk over the selection (the Pallas call the program
names ``sparse_paged_attention``, once a layer) per traced step. A program
without the call reads nothing. Source: device trace."""

from benchmarks import kinds_trace

CALL = r"^sparse_paged_attention"


def read(ctx):
    steps = kinds_trace.traced_steps(ctx)
    s = ctx.reduced.op_seconds(CALL) if steps else 0
    return 1e3 * s / steps if s > 0 else None
