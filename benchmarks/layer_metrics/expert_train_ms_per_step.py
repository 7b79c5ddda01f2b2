"""Experts in training: device time of the grouped expert products per
traced training step: every ``ragged-dot`` call of the step (what
``jax.lax.ragged_dot`` is on the chip: a metadata call and a grouped matrix
product), forward, the remat's second forward, dX and dW, the three
products of every routed block. A program without such calls (the parent of
the PR that added them) yields nothing. Source: device trace."""

CALLS = r"^ragged-dot"


def read(ctx):
    r = ctx.reduced
    steps = len(r.spans.get("bench/train_batch", [])) if r else 0
    sec = r.op_seconds(CALLS) if steps else 0.0
    return 1e3 * sec / steps if sec > 0 else None
