"""Kernels: device time of the flash-attention Pallas calls per traced
training step, per chip. They are the train step's only Pallas calls
(layernorm, Adam and the cross-entropy are XLA there): forward, the remat's
second forward, dq and dk/dv, once a layer. Source: device trace."""


def read(ctx):
    r = ctx.reduced
    steps = len(r.spans.get("bench/train_batch", [])) if r else 0
    if not steps or r.pallas_seconds() <= 0:
        return None
    return 1e3 * r.pallas_seconds() / steps
