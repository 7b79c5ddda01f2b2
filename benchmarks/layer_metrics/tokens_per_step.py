"""Serve step: real rows a traced step, mean of ``scheduled_tokens`` over
the traced folds: with ``step_ms`` the two halves of the rate
(1000 x tokens_per_step / step_ms is the tail's tokens a second). Source:
program spans (the arguments of ``serve/device``, ``step_kinds``)."""

from benchmarks import step_kinds


def read(ctx):
    return step_kinds.tokens_per_step(step_kinds.steps(ctx))
