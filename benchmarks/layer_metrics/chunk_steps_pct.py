"""Serve step: share of the traced steps that held a prompt chunk
(``prompt_rows > 0``); 0 in a decode-only tail, never nothing where a step
was traced. A description of WHICH steps the tail held, as
``experts_touched_pct`` is: the kernels' per-step numbers of the same line
move with it (a step with a chunk touches nearly every expert and runs its
state kernels over a chunk's rows). Source: program spans (the arguments of
``serve/device``, ``step_kinds``)."""

from benchmarks import step_kinds


def read(ctx):
    return step_kinds.chunk_steps_pct(step_kinds.steps(ctx))
