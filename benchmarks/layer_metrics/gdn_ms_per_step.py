"""Kernels on the serve path, Gated DeltaNet over the slot states: device time
of the Pallas call(s) the program names ``gated_delta_attention`` (once a
Gated DeltaNet layer) per traced step. A program without the call yields
nothing. Source: device trace."""

from benchmarks import kinds_trace

CALLS = r"^gated_delta_attention"


def read(ctx):
    steps = kinds_trace.traced_steps(ctx)
    s = ctx.reduced.op_seconds(CALLS) if steps else 0
    return 1e3 * s / steps if s > 0 else None
