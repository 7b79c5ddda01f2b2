"""Experts in training: the grouped expert products' share of their
roofline. The least time the chip could take for what the traced steps
needed (the family's ``expert_train_cost``: nine products a routed block,
forward, dX and dW, the remat's second forward NOT counted as needed; the
greater of the compute and the memory time) over the measured device time
of the step's ``ragged-dot`` calls. The rows are those the router sent to
the held experts in the traced steps themselves: the program's own counter
``moe_rows_held`` (rows a routed layer) on the ``train/device`` spans of its
step tracer, which the cell's configuration switches on; a program without
the counter is read at the rows a balanced router sends
(``expected_rows``). Source: device trace + the program's counter + the
family's arithmetic + ``peaks.json``."""

CALLS = r"^ragged-dot"


def traced_rows(steps: int):
    """Mean ``moe_rows_held`` over the last ``steps`` steps the program's
    step tracer timed; None where it kept none."""
    try:
        from deepspeed_tpu.profiling import steptrace
    except ImportError:
        return None
    reg = steptrace.get_registry()
    spans = reg.spans_named("train/device")[-steps:] if reg else []
    rows = [s["args"]["moe_rows_held"] for s in spans
            if "moe_rows_held" in s.get("args", {})]
    return sum(rows) / len(rows) if rows else None


def read(ctx):
    r = ctx.reduced
    cost = getattr(ctx.family, "expert_train_cost", None)
    steps = len(r.spans.get("bench/train_batch", [])) if r else 0
    sec = r.op_seconds(CALLS) if steps else 0.0
    if cost is None or sec <= 0:
        return None
    c = ctx.counters
    rows = traced_rows(steps)
    if rows is None:
        rows = ctx.family.expected_rows(ctx.shape, c["micro_batch"] * c["seq"])
    need, _bound = ctx.flops.roofline_seconds(*cost(ctx.shape, rows), ctx.peak)
    return 100.0 * need * steps / sec
