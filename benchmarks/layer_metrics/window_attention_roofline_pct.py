"""Kernels on the serve path, window layers: the window layers' paged
attention's share of its roofline. The least time the chip could take for
what the traced steps needed (the family's ``window_attention_cost``: QK^T
and PV over the keys every real query token sees, at most the window; K and
V of the pages that hold one of those keys, counted by the page and not by
the block the kernel reads in, so a kernel that fetches more earns no
share; the real rows' queries in and outputs out; the greater of the
compute and the memory time) over the measured device time of the calls named
``paged_attention_window``. The counts are the program's own, carried by the
trace with the steps it timed (``kinds_trace.step_counts``), for one window
layer; the time is divided by the number of window layers. Source: device
trace + program counters + ``peaks.json``."""

from benchmarks import kinds_trace


def read(ctx):
    counts = kinds_trace.step_counts(ctx)
    cost = getattr(ctx.family, "window_attention_cost", None)
    if not counts or "attended_window" not in counts or cost is None:
        return None
    layers = ctx.shape.kind_layers("sliding_attention")
    measured = ctx.reduced.op_seconds(r"^paged_attention_window") / layers
    need, _bound = ctx.flops.roofline_seconds(
        *cost(ctx.shape, counts["attended_window"], counts["fetched_window"],
              counts["rows"]), ctx.peak)
    return 100.0 * need / measured if measured > 0 else None
