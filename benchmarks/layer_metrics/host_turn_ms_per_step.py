"""Serving host: how long the host's own turn is, hidden under the device
step or not: per ``serve/step`` that dispatched or folded, the durations of
its ``serve/plan`` + ``serve/dispatch`` + ``serve/complete`` (the wait for the
step in flight, ``serve/device``, is not the host's work), median over the
traced steps. What bounds how far the device step can shrink before the
device waits again. Source: program spans on the profiler's host plane
(``span_trace``); needs no device."""

from benchmarks import span_trace


def read(ctx):
    return span_trace.median_children_ms(
        ctx, span_trace.SERVE_STEP,
        ("serve/plan", "serve/dispatch", "serve/complete"),
        counted=span_trace.is_a_step)
