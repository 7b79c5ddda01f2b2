"""Kernels on the serve path: share of the device's busy time spent inside
Pallas calls (the rmsnorm kernels; attention in a [slots, budget > 1] step is
XLA by construction). Source: device trace."""


def read(ctx):
    r = ctx.reduced
    if r is None or r.busy_s <= 0:
        return None
    return 100.0 * r.pallas_seconds() / r.busy_s
