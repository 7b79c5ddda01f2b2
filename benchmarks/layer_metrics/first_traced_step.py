"""Serve step: the lowest step number among the traced folds (dispatches
are counted from 1 an engine, the samples of set-up included). A
description of WHERE in the replay the traced tail began: two runs whose
numbers differ traced other steps, and their per-layer pair is read with
that in mind. Source: program spans (``serve/device``, ``step_kinds``)."""

from benchmarks import step_kinds


def read(ctx):
    return step_kinds.first_traced_step(step_kinds.steps(ctx))
