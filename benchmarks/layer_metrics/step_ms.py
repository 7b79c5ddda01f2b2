"""Serve step: the time of one step of the one program, median over the
traced steps: fold to fold, the end of ``serve/device(n)`` less the end of
``serve/device(n-1)``, taken where the device went straight from n-1 to n
(``step_kinds``: a step that starts from idle is left out; where no step of
the tail was queued behind another, every interval of consecutive steps).
The other half of a closed loop's rate beside ``tokens_per_step``; what
``host_ms_per_step`` (zero by construction since PR 37) no longer says.
Source: program spans on the profiler's host plane; needs no device."""

from benchmarks import step_kinds


def read(ctx):
    return step_kinds.step_ms(step_kinds.steps(ctx))
