"""Kernels on the serve path, attention layers of 64-wide heads named beside
convolution layers: the paged attention call's share of its roofline, for a
model whose pool holds two KV heads a 128-lane row (their call is
``paged_attention_full`` too, and ``full_attention_ms_per_step`` reads its
time). The least time the chip could take for what the traced steps needed
(the family's ``full_attention_cost``: QK^T and PV at the 64-WIDE products
every real query token needs over its whole context, not the 128-lane ones a
lane pairing spends; K and V of the pages that hold one of those keys, once
for all the rows and all the query heads of a slot; the real rows' queries in
and outputs out; the greater of the compute and the memory time) over the
measured device time of the calls, with the program's own counts
(``attended_full``, ``fetched_full``, ``rows`` of ``kinds_trace.step_counts``)
for one layer; the time is divided by the number of such layers. The same
arithmetic as ``full_attention_roofline_pct``, whose list of cells an accepted
test pins at one (tests/benchmark/test_bench_cohere.py): a new name, not a
new quantity. A program without the convolution layers' counts yields nothing.
Source: device trace + program counters + ``peaks.json``."""

from benchmarks import kinds_trace

CALLS = r"^paged_attention_full"


def read(ctx):
    counts = kinds_trace.step_counts(ctx)
    cost = getattr(ctx.family, "full_attention_cost", None)
    if not counts or cost is None or not (
            "attended_full" in counts and "conv_state_slots" in counts):
        return None
    layers = ctx.shape.kind_layers("full_attention")
    measured = ctx.reduced.op_seconds(CALLS) / layers
    need, _bound = ctx.flops.roofline_seconds(
        *cost(ctx.shape, counts["attended_full"], counts["fetched_full"],
              counts["rows"]), ctx.peak)
    return 100.0 * need / measured if measured > 0 else None
