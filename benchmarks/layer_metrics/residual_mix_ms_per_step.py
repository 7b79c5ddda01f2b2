"""Residual path on the serve path, hyper-connections: device time per traced
step of the operations that take the stream tensor as an operand or write
it (``[streams, 1, token_budget, hidden]`` as the walk carries it, streams
leading; the compiler may drop the 1): the projection of the streams to
their mixing values, the mix a half-layer reads, and the write-back through
the doubly-stochastic mix, at every boundary, with the spread of the
embedding and the final sum. The Sinkhorn rounds themselves work on [n, n,
rows] values and are not among them. A program without residual streams (no
``residual_streams`` on its steps) yields nothing. Source: device trace."""

from benchmarks import kinds_trace


def seconds(ctx):
    """(seconds of those operations over the traced steps, the steps' counts)
    or None."""
    counts = kinds_trace.step_counts(ctx)
    if not counts or not counts.get("residual_streams"):
        return None
    n = int(round(counts["residual_streams"] / counts["steps"]))
    stream = rf"\[{n},(1,)?{int(ctx.counters['token_budget'])},{ctx.shape.d}\]"
    sec = kinds_trace.operand_seconds(ctx, stream)
    return (sec, counts) if sec else None


def read(ctx):
    steps = kinds_trace.traced_steps(ctx)
    got = seconds(ctx) if steps else None
    return 1e3 * got[0] / steps if got else None
