"""Kernels: the flash kernels' share of their roofline. The least time the
chip could take for the attention a step needs (``flops.flash_train_cost``:
7 causal matmuls a layer, the remat's second forward NOT counted as needed;
bytes of Q, K, V, O and their gradients) over the measured device time of
the Pallas calls in a step. At these shapes the bound is compute. Source:
device trace + ``flops.py`` + ``peaks.json``."""


def read(ctx):
    r = ctx.reduced
    steps = len(r.spans.get("bench/train_batch", [])) if r else 0
    if not steps or r.pallas_seconds() <= 0:
        return None
    c = ctx.counters
    need, _bound = ctx.flops.roofline_seconds(
        *ctx.flops.flash_train_cost(ctx.shape, c["micro_batch"], c["seq"]),
        ctx.peak)
    return 100.0 * need * steps / r.pallas_seconds()
