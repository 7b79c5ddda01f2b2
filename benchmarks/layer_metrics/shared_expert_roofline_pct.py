"""Expert layer on the serve path, the shared branch: its share of its
roofline. The least time the chip could take for what the traced steps needed
(the family's ``shared_expert_cost``: three products of 2 x hidden x width a
shared expert a REAL row; the bank read once a step a layer, a row in and a
row out; the greater of the compute and the memory time) over the measured
device time of the operations that take the shared bank
(``shared_expert_ms_per_step.seconds``). Rows a step computes beyond the
real ones (the budget's idle rows) earn no share. Source: device trace +
program counters + ``peaks.json``."""

import importlib.util
import os

from benchmarks import kinds_trace

_spec = importlib.util.spec_from_file_location(
    "shared_expert_ms_per_step", os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "shared_expert_ms_per_step.py"))
_ms = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_ms)


def read(ctx):
    cost = getattr(ctx.family, "shared_expert_cost", None)
    counts = kinds_trace.step_counts(ctx) if cost is not None else None
    measured = _ms.seconds(ctx) if counts else None
    if not measured:
        return None
    need, _bound = ctx.flops.roofline_seconds(
        *cost(ctx.shape, counts["rows"], counts["steps"]), ctx.peak)
    return 100.0 * need / measured
