"""Kernels on the serve path, power retention: the kernel's share of its
roofline. The least time the chip could take for what the traced steps
needed (the family's ``retention_cost``: for every real row the read-out of
every query head and the update of every kv head over state and normaliser
at the symmetric square's width, and the row's own pair; every live state
and normaliser read and written once a slot a step, float32; the real rows'
q, k, v and log-gates in and o out; the greater of the compute and the
memory time) over the measured device time of the calls named
``power_retention``. The counts are the program's own, carried by the trace
with the steps it timed (``kinds_trace.step_counts``), for one layer; the
time is divided by the number of layers. Source: device trace + program
counters + ``peaks.json``."""

from benchmarks import kinds_trace

CALLS = r"^power_retention"


def read(ctx):
    counts = kinds_trace.step_counts(ctx)
    cost = getattr(ctx.family, "retention_cost", None)
    if not counts or "retention_state_slots" not in counts or cost is None:
        return None
    measured = ctx.reduced.op_seconds(CALLS) / ctx.shape.layers
    need, _bound = ctx.flops.roofline_seconds(
        *cost(ctx.shape, counts["retention_rows"],
              counts["retention_state_slots"]), ctx.peak)
    return 100.0 * need / measured if measured > 0 else None
