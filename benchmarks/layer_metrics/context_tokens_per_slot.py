"""Serve step: what a working slot's attention or state has behind it after
the step (``start_pos + num_new``), mean over the working slots of the
traced steps. A description of how deep into its requests the tail lies:
attention and selection kernels' per-step times move with it. Source:
program spans (the arguments of ``serve/device``, ``step_kinds``)."""

from benchmarks import step_kinds


def read(ctx):
    return step_kinds.context_tokens_per_slot(step_kinds.steps(ctx))
