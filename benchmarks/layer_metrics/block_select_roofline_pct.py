"""Kernels on the serve path, block-sparse attention: the selection's share
of its roofline. The least time the chip could take for what the traced
steps needed (the family's ``block_select_cost``: a dot product of every
head for every real query and every compressed key at or before it; the
compressed keys at or before a slot's last real query, once a slot; the
real rows' queries in; the softmax, the pooling to blocks and the top-k
counted as free; the greater of the compute and the memory time) over the
measured device time of the calls named ``block_select``. The counts are
the program's own, carried by the trace with the steps it timed
(``kinds_trace.step_counts``), for one layer; the time is divided by the
number of sparse layers. Source: device trace + program counters +
``peaks.json``."""

from benchmarks import kinds_trace

CALLS = r"^block_select"


def read(ctx):
    counts = kinds_trace.step_counts(ctx)
    cost = getattr(ctx.family, "block_select_cost", None)
    if not counts or "compressed_keys" not in counts or cost is None:
        return None
    measured = ctx.reduced.op_seconds(CALLS) / ctx.shape.count("sparse")
    need, _bound = ctx.flops.roofline_seconds(
        *cost(ctx.shape, counts["compressed_keys"],
              counts["compressed_rows"], counts["rows"]), ctx.peak)
    return 100.0 * need / measured if measured > 0 else None
