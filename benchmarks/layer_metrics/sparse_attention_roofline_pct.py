"""Kernels on the serve path, learned sparse attention: the sparse latent
attention's share of its roofline. The least time the chip could take for
what the traced steps needed (the family's ``sparse_attention_cost``: for
every real query token ``min(context, index_topk)`` keys, each a dot product
over latent and rotary key and a weighted sum of the latent for every head;
the latent rows a slot's queries chose, once a slot, counted as the fewest
they can be; the real rows' absorbed queries in and attended latents out;
the greater of the compute and the memory time) over the measured device
time of the calls named ``sparse_latent_attention``. A kernel that scores
keys the selection left out earns no share for them. The counts are the
program's own, carried by the trace with the steps it timed
(``kinds_trace.step_counts``), for one layer; the time is divided by the
number of layers. Source: device trace + program counters +
``peaks.json``."""

from benchmarks import kinds_trace


def read(ctx):
    counts = kinds_trace.step_counts(ctx)
    cost = getattr(ctx.family, "sparse_attention_cost", None)
    if not counts or "attended_sparse" not in counts or cost is None:
        return None
    layers = ctx.shape.layers + ctx.shape.dense_layers
    measured = ctx.reduced.op_seconds(r"^sparse_latent_attention") / layers
    need, _bound = ctx.flops.roofline_seconds(
        *cost(ctx.shape, counts["attended_sparse"], counts["chosen_min"],
              counts["rows"]), ctx.peak)
    return 100.0 * need / measured if measured > 0 else None
