"""Device: share of the traced window in which the first device runs nothing
while the host is inside ``serve/device``: the host waits for the device and
the device runs nothing (a transfer, the runtime). A part of
``device_idle_pct`` of the same run, beside ``idle_in_host_work_pct``.
Source: device trace + program spans."""

from benchmarks import span_trace


def read(ctx):
    return span_trace.idle_inside_pct(ctx, span_trace.SERVE_DEVICE_WAIT)
