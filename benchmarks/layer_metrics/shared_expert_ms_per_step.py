"""Expert layer on the serve path, the shared branch: device time per traced
step of the operations that take the stacked bank of the shared experts as
an operand: the program holds the averaged experts side by side as one bank
([layers, hidden, shared experts x expert width] and its transpose, the
mean's weight in the down projection), so these are its three products over
every row of the step, every layer, with the activation fused between them.
The attention projections of a model with as many query columns have the
same shape; the operand's name tells them apart (a device event is named by
its whole instruction, and a parameter by its path in the tree: ``...mlp
.shared.wg``). The routed bank is not among them (``expert_ms_per_step``
reads it). A configuration without averaged shared experts (no ``shared`` on
its shape) yields nothing. Source: device trace."""

from benchmarks import kinds_trace


def seconds(ctx):
    """Seconds of those operations over the traced steps, or None."""
    s = ctx.shape
    n = getattr(s, "shared", 0)
    if not n:
        return None
    d, w = s.d, n * s.ffn
    bank = rf"\[{s.layers},({d},{w}|{w},{d})\]\S* %\S*shared"
    return kinds_trace.operand_seconds(ctx, bank)


def read(ctx):
    steps = kinds_trace.traced_steps(ctx)
    sec = seconds(ctx) if steps else None
    return 1e3 * sec / steps if sec else None
