"""Train step: model FLOP/s utilisation. Tokens per second per chip x
``flops.train_flops_per_token`` (forward + backward, the remat's recompute
not counted, causal attention counted once) over the chip's bf16 peak."""


def read(ctx):
    rate = ctx.counters["train_tokens_per_s_per_chip"]
    per_token = ctx.flops.train_flops_per_token(ctx.shape, ctx.counters["seq"])
    return 100.0 * rate * per_token / ctx.peak["bf16_flops_per_s"]
