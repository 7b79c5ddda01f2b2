"""Expert layer on the serve path: device time of the expert matmuls per
traced step: the operations that take a stacked expert bank ([layers,
experts, hidden, expert width] or its transpose, the shapes the
configuration file gives) as an operand, which are the three matmuls of
``moe_experts`` with the activation fused between them, every layer. (The
program traces them under ``jax.named_scope("moe_experts")``, but a device
event carries its instruction, not its scope.) Source: device trace."""

from benchmarks import kinds_trace


def read(ctx):
    steps = kinds_trace.traced_steps(ctx)
    s = ctx.shape
    bank = rf"\[{s.layers},{s.experts},({s.d},{s.ffn}|{s.ffn},{s.d})\]"
    sec = kinds_trace.operand_seconds(ctx, bank) if steps else None
    return 1e3 * sec / steps if sec else None
