"""Device: share of the traced window in which the first device runs nothing
WHILE THE HOST WORKS inside the program's step: ``serve/plan``,
``serve/dispatch``, ``serve/complete``, ``serve/page_in`` (serving) or
``train/input_wait``, ``train/batch_prep``, ``train/dispatch``,
``train/commit`` (training). A part of ``device_idle_pct`` of the same run:
the window, the device and its busy intervals are ``trace_reduce.Reduced``'s.
``device_idle_pct`` less this and ``idle_in_device_wait_pct`` is idle time
outside any step of the program: the load generator, or no request.
Source: device trace + program spans."""

from benchmarks import span_trace


def read(ctx):
    # a run holds one engine: the other one's names match nothing
    return span_trace.idle_inside_pct(
        ctx, span_trace.SERVE_HOST_WORK + span_trace.TRAIN_HOST_WORK)
