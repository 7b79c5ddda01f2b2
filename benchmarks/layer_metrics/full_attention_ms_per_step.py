"""Kernels on the serve path, full layers: device time of the paged
attention calls of the full layers of a model that has window layers too
(the Pallas call the program names ``paged_attention_full``, once a full
layer) per traced step. Source: device trace."""

from benchmarks import kinds_trace


def read(ctx):
    steps = kinds_trace.traced_steps(ctx)
    s = ctx.reduced.op_seconds(r"^paged_attention_full") if steps else 0
    return 1e3 * s / steps if s > 0 else None
