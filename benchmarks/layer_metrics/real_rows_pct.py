"""Serve step: share of the rows the one [max_slots, token_budget] program
computed that carried a token: scheduled_tokens / (steps x slots x budget),
over the whole window. Source: program counter (``ServingMetrics``)."""


def read(ctx):
    c = ctx.counters
    return 100.0 * c["scheduled_tokens"] / (
        c["steps"] * c["slots"] * c["token_budget"])
