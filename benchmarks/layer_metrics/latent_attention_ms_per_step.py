"""Kernels on the serve path, latent attention without a selection: device time
of the Pallas call(s) the program names ``latent_attention`` (once a latent layer:
the walk over every key of each slot's context) per traced step. A program
without the call yields nothing. Source: device trace."""

from benchmarks import kinds_trace

CALLS = r"^latent_attention"


def read(ctx):
    steps = kinds_trace.traced_steps(ctx)
    s = ctx.reduced.op_seconds(CALLS) if steps else 0
    return 1e3 * s / steps if s > 0 else None
