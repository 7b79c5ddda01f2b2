"""Learned sparse attention: how much of the context the selection spares
attention. One minus the keys the real query tokens attend (their
``index_topk`` best) over the cached tokens at or before them, which the
indexer scores, over the traced steps (the program's own counts, carried by
the trace: ``kinds_trace.step_counts``). 0 = every context was inside
``index_topk``; 90 = attention saw a tenth of what a dense layer would.
Source: program counter."""

from benchmarks import kinds_trace


def read(ctx):
    counts = kinds_trace.step_counts(ctx)
    if not counts or not counts.get("context_keys") or (
            "attended_sparse" not in counts):
        return None
    return 100.0 * (1.0 - counts["attended_sparse"] / counts["context_keys"])
