"""Latent attention in training: device time of the flash-attention Pallas
calls per traced training step: every kernel call of the step that is no
``ragged-dot`` (the grouped expert products are the step's only other
kernels): forward, the remat's second forward, dq and dk/dv, once a block,
at one head of keys and values for every query head, all as wide as the
wider of the qk and the value widths. Read only for a family that gives the
kernels' cost at those shapes (``latent_flash_train_cost``). Source: device
trace."""

CALLS = r"^(?!ragged-dot)\S+ .*tpu_custom_call$"


def read(ctx):
    r = ctx.reduced
    steps = len(r.spans.get("bench/train_batch", [])) if r else 0
    if not steps or not hasattr(ctx.family, "latent_flash_train_cost"):
        return None
    sec = r.op_seconds(CALLS)
    return 1e3 * sec / steps if sec > 0 else None
