"""Input layer: mean time the training loop waited for its next batch
(the benchmark's feeder thread stands where ``data_pipeline/`` will), over
every step of the window. Source: benchmark span (host clock)."""


def read(ctx):
    waits = ctx.counters["data_wait_s"]
    return 1e3 * sum(waits) / len(waits)
