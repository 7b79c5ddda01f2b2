"""Serving host: time of one ``engine.step()`` (scheduler plan, dispatch,
fence, sample bookkeeping) that the device did not spend computing: the
benchmark's span around the call minus the device's busy time inside it,
median over the traced steps. Source: device trace + benchmark span."""

import statistics


def read(ctx):
    if ctx.reduced is None:
        return None
    host = ctx.reduced.host_outside_device_s("bench/engine.step")
    return 1e3 * statistics.median(host) if host else None
