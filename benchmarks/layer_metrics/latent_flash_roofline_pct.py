"""Latent attention in training: the flash kernels' share of their
roofline. The least time the chip could take for the attention a step needs
(the family's ``latent_flash_train_cost``: 7 causal matmuls a block over
every block, dense, routed and the MTP module's, the remat's second forward
NOT counted as needed; bytes of Q, K, V, O and their gradients) over the
measured device time of the step's kernel calls that are no ``ragged-dot``.
Source: device trace + the family's arithmetic + ``peaks.json``."""

CALLS = r"^(?!ragged-dot)\S+ .*tpu_custom_call$"


def read(ctx):
    r = ctx.reduced
    cost = getattr(ctx.family, "latent_flash_train_cost", None)
    steps = len(r.spans.get("bench/train_batch", [])) if r else 0
    sec = r.op_seconds(CALLS) if steps else 0.0
    if cost is None or sec <= 0:
        return None
    c = ctx.counters
    need, _bound = ctx.flops.roofline_seconds(
        *cost(ctx.shape, c["micro_batch"], c["seq"]), ctx.peak)
    return 100.0 * need * steps / sec
