"""Kernels on the serve path, power retention over the slot states: device
time of the Pallas call(s) the program names ``power_retention`` (once a
retention layer) per traced step. A program without the call yields nothing.
Source: device trace."""

from benchmarks import kinds_trace

CALLS = r"^power_retention"


def read(ctx):
    steps = kinds_trace.traced_steps(ctx)
    s = ctx.reduced.op_seconds(CALLS) if steps else 0
    return 1e3 * s / steps if s > 0 else None
