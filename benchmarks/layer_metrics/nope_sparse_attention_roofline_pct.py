"""Kernels on the serve path, sparse attention over latent rows without a
rotary part, under a selection by block: the kernel's share of its roofline.
The least time the chip could take for what the traced steps needed (the
family's ``sparse_attention_cost``: for every real query and every token of
its chosen blocks and its tail, a dot product over the latent and a weighted
sum of it for every head; each chosen latent row once a slot, the fewest
they can be; the absorbed queries in and the attended latents out; the
greater of the compute and the memory time) over the measured device time
of the calls named ``sparse_latent_attention``. A kernel that walks keys the
selection left out earns no share for them. The counts are the program's
own, carried by the trace with the steps it timed
(``kinds_trace.step_counts``), for one layer; the time is divided by the
number of indexed (``mla``) layers. A program whose selection has no tail
(no ``tail_keys``) yields nothing. Source: device trace + program counters
+ ``peaks.json``."""

from benchmarks import kinds_trace

CALLS = r"^sparse_latent_attention"


def read(ctx):
    counts = kinds_trace.step_counts(ctx)
    cost = getattr(ctx.family, "sparse_attention_cost", None)
    if not counts or "tail_keys" not in counts or cost is None:
        return None
    measured = ctx.reduced.op_seconds(CALLS) / ctx.shape.count("mla")
    need, _bound = ctx.flops.roofline_seconds(
        *cost(ctx.shape, counts["attended_sparse"], counts["chosen_min"],
              counts["rows"]), ctx.peak)
    return 100.0 * need / measured if measured > 0 else None
