"""Kernels on the serve path, latent attention without a selection: the
kernel's share of its roofline. The least time the chip could take for what
the traced steps needed (the family's ``latent_walk_cost``: every head's
score and value of every real query over the cached latents at or before it,
in the absorbed form; the latents at or before a slot's last real query read
once a slot, the real rows' absorbed queries in and attended latents out; the
greater of the compute and the memory time) over the measured device time of
the calls named ``latent_attention``. The counts are the program's own,
carried by the trace with the steps it timed (``kinds_trace.step_counts``),
for one layer; the time is divided by the number of latent layers. Source:
device trace + program counters + ``peaks.json``."""

from benchmarks import kinds_trace

CALLS = r"^latent_attention"


def read(ctx):
    counts = kinds_trace.step_counts(ctx)
    cost = getattr(ctx.family, "latent_walk_cost", None)
    if not counts or "latent_keys_walked" not in counts or cost is None:
        return None
    measured = ctx.reduced.op_seconds(CALLS) / ctx.shape.count("latent")
    need, _bound = ctx.flops.roofline_seconds(
        *cost(ctx.shape, counts["context_keys"], counts["latent_keys_walked"],
              counts["latent_rows"]), ctx.peak)
    return 100.0 * need / measured if measured > 0 else None
