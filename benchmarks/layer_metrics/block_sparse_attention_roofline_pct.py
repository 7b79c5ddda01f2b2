"""Kernels on the serve path, block-sparse attention: the attention kernel's
share of its roofline. The least time the chip could take for what the
traced steps needed (the family's ``block_sparse_attention_cost``: QK^T and
PV of every head over ``min(context, kept)`` keys a real query, all of its
context inside ``dense_len``; K and V of the tokens some query of a slot
attends, once a slot, counted as the fewest they can be; the real rows'
queries in and outputs out; the greater of the compute and the memory time)
over the measured device time of the calls named ``block_sparse_attention``.
The counts are the program's own, carried by the trace with the steps it
timed (``kinds_trace.step_counts``), for one layer; the time is divided by
the number of sparse layers. Source: device trace + program counters +
``peaks.json``."""

from benchmarks import kinds_trace

CALLS = r"^block_sparse_attention"


def read(ctx):
    counts = kinds_trace.step_counts(ctx)
    cost = getattr(ctx.family, "block_sparse_attention_cost", None)
    if not counts or "chosen_min" not in counts or cost is None or (
            "compressed_keys" not in counts):
        return None
    measured = ctx.reduced.op_seconds(CALLS) / ctx.shape.count("sparse")
    need, _bound = ctx.flops.roofline_seconds(
        *cost(ctx.shape, counts["attended_sparse"], counts["chosen_min"],
              counts["rows"]), ctx.peak)
    return 100.0 * need / measured if measured > 0 else None
