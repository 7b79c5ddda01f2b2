"""R6 — HBM capacity: the static OOM-before-compile check.

The cost planner (analysis/cost) estimates the per-device HBM peak of
the traced step — state bytes from the ShapeDtypeStruct shardings,
activation live-set high-water mark, collective scratch. When the
context carries an HBM budget (``tools/shardplan.py --hbm-gb``, the
``SHARDPLAN_HBM_GB`` env, or an explicit ``hbm_budget_bytes``), a peak
above it is an error finding *before anything compiles* — the OOM that
used to surface minutes into a TPU run (as a cryptic RESOURCE_EXHAUSTED
from the compiler) becomes a one-second CPU lint.

No budget in the context → the rule is silent: generic lints (the test
suite's captured configs, ``shardlint --all-examples`` without flags)
never guess a machine size.
"""

from __future__ import annotations

import os
from typing import List

from ..base import ERROR, Finding, LintContext
from . import register_rule

_GIB = float(1 << 30)


def _armed_budget_bytes(ctx: LintContext):
    """Explicit context budget first, then the documented
    ``SHARDPLAN_HBM_GB`` env arm; None when neither is set."""
    if ctx.hbm_budget_bytes is not None:
        return float(ctx.hbm_budget_bytes)
    env = os.environ.get("SHARDPLAN_HBM_GB")
    if env:
        return float(env) * _GIB
    return None


@register_rule("R6", "hbm-capacity")
def hbm_capacity(ctx: LintContext) -> List[Finding]:
    budget_armed = _armed_budget_bytes(ctx)
    if budget_armed is None:
        return []
    from ..cost import plan_for_context

    plan = plan_for_context(ctx)
    budget = budget_armed
    if plan.peak_hbm_bytes <= budget:
        return []
    return [Finding(
        rule="R6",
        severity=ERROR,
        message=(
            f"estimated peak HBM {plan.peak_hbm_bytes / _GIB:.2f} GiB "
            f"exceeds the {budget / _GIB:.2f} GiB per-device budget "
            f"(params {plan.param_bytes / _GIB:.2f} + opt "
            f"{plan.opt_bytes / _GIB:.2f} + activations "
            f"{plan.act_peak_bytes / _GIB:.2f} + collective scratch "
            f"{plan.collective_scratch_bytes / _GIB:.2f} GiB) — this "
            "config OOMs before the first step; shard further, offload, "
            "or lower the micro-batch/remat policy"
        ),
        where="<plan>",
    )]
