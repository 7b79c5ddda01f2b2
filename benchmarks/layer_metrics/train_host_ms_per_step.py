"""Train step, host side: per ``train/step`` the durations of its
``train/batch_prep`` + ``train/dispatch`` (the batch laid out and uploaded,
the jitted step handed over), median over the traced steps: what the host
adds before the device can start. Source: program spans on the profiler's
host plane (``span_trace``); needs no device."""

from benchmarks import span_trace


def read(ctx):
    return span_trace.median_children_ms(
        ctx, span_trace.TRAIN_STEP, ("train/batch_prep", "train/dispatch"))
