"""Kernels on the serve path, Gated DeltaNet: the kernel's share of its
roofline. The least time the chip could take for what the traced steps
needed (the family's ``gdn_cost``: for every real row a value head's decay,
erase, write and read-out of the state, ``K K^T`` / ``Q K^T`` being the chunk
form's own and counted once a KEY head as nothing needed; every live state
read and written once a slot a step, float32; the real rows' q, k, v,
log-decays and step sizes in and o out; the greater of the compute and the
memory time) over the measured device time of the calls named
``gated_delta_attention``. The counts are the program's own, carried by the
trace with the steps it timed (``kinds_trace.step_counts``), for one layer;
the time is divided by the number of Gated DeltaNet layers. Source: device
trace + program counters + ``peaks.json``."""

from benchmarks import kinds_trace

CALLS = r"^gated_delta_attention"


def read(ctx):
    counts = kinds_trace.step_counts(ctx)
    cost = getattr(ctx.family, "gdn_cost", None)
    if not counts or "gdn_state_slots" not in counts or cost is None:
        return None
    measured = ctx.reduced.op_seconds(CALLS) / ctx.shape.count("gdn")
    need, _bound = ctx.flops.roofline_seconds(
        *cost(ctx.shape, counts["gdn_rows"], counts["gdn_state_slots"]),
        ctx.peak)
    return 100.0 * need / measured if measured > 0 else None
