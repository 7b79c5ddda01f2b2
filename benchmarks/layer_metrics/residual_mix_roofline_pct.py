"""Residual path on the serve path, hyper-connections: the mixes' share of
their roofline. The least time the chip could take for what the traced steps
needed (the family's ``residual_mix_cost``: at every boundary the streams
read once and written once, the branch's input out and its output in, the
projection to the mixing values and the two mixes; the greater of the
compute and the memory time; the Sinkhorn rounds counted as free) for the
``token_budget`` rows every step computes, over the measured device time of
the operations that take the stream tensor
(``residual_mix_ms_per_step.seconds``). A program without residual streams
yields nothing. Source: device trace + program counters + ``peaks.json``."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "residual_mix_ms_per_step", os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "residual_mix_ms_per_step.py"))
_ms = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_ms)


def read(ctx):
    cost = getattr(ctx.family, "residual_mix_cost", None)
    got = _ms.seconds(ctx) if cost is not None else None
    if not got:
        return None
    measured, counts = got
    need, _bound = ctx.flops.roofline_seconds(
        *cost(ctx.shape, counts["steps"] * int(ctx.counters["token_budget"])),
        ctx.peak)
    return 100.0 * need / measured
