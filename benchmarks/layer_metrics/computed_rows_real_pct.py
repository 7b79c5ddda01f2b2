"""Serve step: share of the rows the program COMPUTED that carried a token:
sum of ``scheduled_tokens`` over sum of ``dense_rows`` (what the layers'
row-by-row work runs over a step: ``token_budget`` packed rows since PR 42,
``max_slots x token_budget`` in the slot layout), over the traced steps.
Stands where ``real_rows_pct`` is blind: that one divides by the
benchmark's ``slots x token_budget``, rows nothing computes in a packed
step. Source: program spans (``serve/device`` and ``serve/device_step``,
``step_kinds``)."""

from benchmarks import step_kinds


def read(ctx):
    return step_kinds.computed_rows_real_pct(step_kinds.steps(ctx))
