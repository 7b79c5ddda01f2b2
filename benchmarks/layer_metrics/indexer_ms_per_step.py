"""Kernels on the serve path, learned sparse attention: device time of the
indexer's calls (the Pallas calls the program names ``indexer_scores`` and
``selection_topk``, once a layer each) per traced step. Source: device
trace."""

from benchmarks import kinds_trace

CALLS = r"^(indexer_scores|selection_topk)"


def read(ctx):
    steps = kinds_trace.traced_steps(ctx)
    s = ctx.reduced.op_seconds(CALLS) if steps else 0
    return 1e3 * s / steps if s > 0 else None
