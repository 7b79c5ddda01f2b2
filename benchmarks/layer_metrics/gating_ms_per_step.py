"""Expert layer on the serve path, the router: device time per traced step
of the gate's own operations, every routed layer: the float32 product with
the router ([hidden, routed experts]), the sigmoid, the top-k of the routed
experts, the renormalisation, and the placement's index work over the
``token_budget x top_k`` (row, chosen expert) pairs (one-hots, ranks and the
slot tables). They are told from the rest of the step by the shapes only
they have: an operand or a result whose trailing axes are [token_budget,
routed experts], [token_budget, top_k] or [token_budget, top_k x experts
held] (the families' ``gating_shapes``). A family without ``gating_shapes``
yields nothing. Source: device trace."""

from benchmarks import kinds_trace


def read(ctx):
    shapes = getattr(ctx.family, "gating_shapes", None)
    steps = kinds_trace.traced_steps(ctx)
    if shapes is None or not steps:
        return None
    sec = kinds_trace.operand_seconds(
        ctx, shapes(ctx.shape, int(ctx.counters["token_budget"])))
    return 1e3 * sec / steps if sec else None
