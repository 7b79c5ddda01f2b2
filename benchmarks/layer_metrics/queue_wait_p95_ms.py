"""Scheduler (admission): from the instant a request was due to its first
scheduled chunk (``prefill_start_t``), 95th percentile over every request of
the window; the generator's own lateness is inside it, and a request never
admitted counts as the worst. Source: program counter + benchmark clock."""

from benchmarks.loadgen import percentile


def read(ctx):
    return 1e3 * percentile(ctx.counters["queue_wait_s"], 95)
