"""Kernels on the serve path, full layers: the full layers' paged attention's
share of its roofline, for a model that keeps pages by layer kind. The least
time the chip could take for what the traced steps needed (the family's
``full_attention_cost``: QK^T and PV over the keys every real query token
sees, its whole context; K and V of the pages that hold one of those keys,
counted by the page and once for all the rows and all the query heads of a
slot, so a kernel that fetches more, or again for every tile of a chunk's
rows, earns no share; the real rows' queries in and outputs out; the greater
of the compute and the memory time) over the measured device time of the
calls named ``paged_attention_full``. The counts are the program's own,
carried by the trace with the steps it timed (``kinds_trace.step_counts``),
for one full layer; the time is divided by the number of full layers. A
family without ``full_attention_cost`` yields nothing. Source: device trace
+ program counters + ``peaks.json``."""

from benchmarks import kinds_trace


def read(ctx):
    counts = kinds_trace.step_counts(ctx)
    cost = getattr(ctx.family, "full_attention_cost", None)
    if not counts or "attended_full" not in counts or cost is None:
        return None
    layers = ctx.shape.kind_layers("full_attention")
    measured = ctx.reduced.op_seconds(r"^paged_attention_full") / layers
    need, _bound = ctx.flops.roofline_seconds(
        *cost(ctx.shape, counts["attended_full"], counts["fetched_full"],
              counts["rows"]), ctx.peak)
    return 100.0 * need / measured if measured > 0 else None
