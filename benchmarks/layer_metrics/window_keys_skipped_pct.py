"""Paged cache by layer kind: how much of the traffic the window works on.
One minus the keys the window layers' queries attend over the keys the same
queries attend in a full layer, over the traced steps (the program's own
counts, carried by the trace: ``kinds_trace.step_counts``). 0 = every context
was inside the window; 90 = a window layer did a tenth of a full layer's
work. Source: program counter."""

from benchmarks import kinds_trace


def read(ctx):
    counts = kinds_trace.step_counts(ctx)
    if not counts or not counts.get("attended_full") or (
            "attended_window" not in counts):
        return None
    return 100.0 * (1.0 - counts["attended_window"] / counts["attended_full"])
