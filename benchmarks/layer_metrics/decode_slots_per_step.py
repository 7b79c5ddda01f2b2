"""Serve step: the slots with exactly ONE new row a traced step (a decoding
slot), mean over the traced steps: whether a cell of many slots is the mix
of decode rows beside a prompt chunk that it says it is. The program's own
count, carried by each step's annotation (``decode_slots`` of
``kinds_trace.step_counts``, which a model with convolution layers writes
beside ``conv_state_slots``); a program without it yields nothing. Source:
program counter."""

from benchmarks import kinds_trace


def read(ctx):
    counts = kinds_trace.step_counts(ctx)
    if not counts or not ("decode_slots" in counts
                          and "conv_state_slots" in counts):
        return None
    return counts["decode_slots"] / counts["steps"]
