"""Device: share of the traced window with no operation running on the
device (1 - union of device-op intervals over the window), averaged over the
chips. Source: device trace."""


def read(ctx):
    return None if ctx.reduced is None else ctx.reduced.idle_pct()
