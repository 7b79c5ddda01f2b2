"""Expert layer on the serve path: the share of the held experts that got at
least one row, over the routed layers and the traced steps: what a bank read
by touched expert could save against one read whole. The program's own count
(the device's, read back with each step's tokens and carried by the trace two
steps behind the step it describes: ``kinds_trace.step_counts``) over the
experts held x routed layers of the same steps. 100 = every held expert of
every layer worked in every step. Source: program counter."""

from benchmarks import kinds_trace


def read(ctx):
    counts = kinds_trace.step_counts(ctx)
    if not counts or not counts.get("experts_held"):
        return None
    return 100.0 * counts["experts_touched"] / counts["experts_held"]
