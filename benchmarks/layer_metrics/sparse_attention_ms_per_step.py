"""Kernels on the serve path, learned sparse attention: device time of the
attention calls over the selection (the Pallas call the program names
``sparse_latent_attention``, once a layer) per traced step. Source: device
trace."""

from benchmarks import kinds_trace


def read(ctx):
    steps = kinds_trace.traced_steps(ctx)
    s = ctx.reduced.op_seconds(r"^sparse_latent_attention") if steps else 0
    return 1e3 * s / steps if s > 0 else None
