"""Kernels on the serve path, learned sparse attention over POOLED index keys:
the indexer's share of its roofline. The least time the chip could take for
what the traced steps needed (the family's ``indexer_cost``: a dot product
of every index head for every real query token and every whole block of
pooled keys at or before it; the pooled keys of a slot's context once a
slot; the real rows' index queries in; the greater of the compute and the
memory time; the selection itself counted as free) over the measured device
time of the calls named ``indexer_scores`` and ``selection_topk``. The counts
are the program's own, carried by the trace with the steps it timed
(``kinds_trace.step_counts``: ``index_keys`` the pooled keys scored,
``index_rows`` those read once a slot), for one layer; the time is divided
by the number of indexed (``mla``) layers. A program whose index keys are
not pooled (no ``index_rows``) yields nothing. Source: device trace +
program counters + ``peaks.json``."""

from benchmarks import kinds_trace

CALLS = r"^(indexer_scores|selection_topk)"


def read(ctx):
    counts = kinds_trace.step_counts(ctx)
    cost = getattr(ctx.family, "indexer_cost", None)
    if not counts or "index_rows" not in counts or cost is None:
        return None
    measured = ctx.reduced.op_seconds(CALLS) / ctx.shape.count("mla")
    need, _bound = ctx.flops.roofline_seconds(
        *cost(ctx.shape, counts["index_keys"], counts["index_rows"],
              counts["rows"]), ctx.peak)
    return 100.0 * need / measured if measured > 0 else None
