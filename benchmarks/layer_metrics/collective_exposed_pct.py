"""Collectives: share of the traced window in which a collective runs on a
device and no other operation of that device does, averaged over the chips.
Source: device trace."""


def read(ctx):
    return None if ctx.reduced is None else ctx.reduced.exposed_collective_pct()
