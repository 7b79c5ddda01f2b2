"""Kernels on the serve path, learned sparse attention inside paged K / V:
the walk's share of its roofline. The least time the chip could take for
what the traced steps needed (the family's ``sparse_attention_cost``: for
every real query token ``min(context, topk)`` keys, each QK^T and PV in
every query head; K and V of the tokens a slot's queries chose, every KV
head, once a slot, counted as the fewest they can be; the real rows' queries
in and outputs out; the greater of the compute and the memory time) over the
measured device time of the calls named ``sparse_paged_attention``. A kernel
that reads keys the selection left out earns no share for them. The counts
are the program's own, carried by the trace with the steps it timed
(``kinds_trace.step_counts``), for one layer; the time is divided by the
number of layers. A program without the call, or a family without the cost,
reads nothing. Source: device trace + program counters + ``peaks.json``."""

from benchmarks import kinds_trace

CALL = r"^sparse_paged_attention"


def read(ctx):
    counts = kinds_trace.step_counts(ctx)
    cost = getattr(ctx.family, "sparse_attention_cost", None)
    if not counts or "attended_sparse" not in counts or cost is None:
        return None
    measured = ctx.reduced.op_seconds(CALL) / ctx.shape.layers
    need, _bound = ctx.flops.roofline_seconds(
        *cost(ctx.shape, counts["attended_sparse"], counts["chosen_min"],
              counts["rows"]), ctx.peak)
    return 100.0 * need / measured if measured > 0 else None
