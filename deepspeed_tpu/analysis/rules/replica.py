"""R1 — replica-divergence.

A shard_map output whose out_spec omits a live manual mesh axis claims
the value is identical on every member of that axis. With the replication
checker off (``check_vma=False`` — what the engine traces use), nothing
verifies the claim: a value derived from axis-partitioned data (e.g.
per-dp-member local gradients) that never crosses a reduction over that
axis silently diverges per replica — the exact "parameter update whose
gradient was never all-reduced" bug class. This rule fills that gap with
a taint analysis per (shard_map, axis):

- taint seeds: body inputs partitioned over the axis, and axis_index
  over the axis;
- reductions over the axis (psum/pmin/pmax/all_gather — value becomes
  member-identical) clear taint;
- a tainted value reaching an output that claims replication → finding.
"""

from __future__ import annotations

from typing import List

from ..base import ERROR, Finding, LintContext
from ..trace import (
    DataflowAnalysis,
    as_jaxpr,
    collective_axes,
    iter_jaxprs,
    names_spec_axes,
    shard_map_manual_axes,
    shard_map_names,
)
from . import register_rule

# collectives whose output is identical on every member of the reduced
# axis (psum covers pmean: jax lowers pmean to psum + div)
_REDUCING = {"psum", "pmin", "pmax", "all_gather", "pgather"}
# per-member value sources even with untainted inputs
_MEMBER_VARYING = {"axis_index"}


class _AxisTaint(DataflowAnalysis):
    def __init__(self, axis: str):
        self.axis = axis

    def transfer(self, eqn, in_vals: List[bool]) -> List[bool]:
        name = eqn.primitive.name
        if name in _MEMBER_VARYING and self.axis in collective_axes(eqn):
            return [True] * len(eqn.outvars)
        if name in _REDUCING and self.axis in collective_axes(eqn):
            return [False] * len(eqn.outvars)
        return [any(in_vals)] * len(eqn.outvars)


@register_rule("R1", "replica-divergence")
def replica_divergence(ctx: LintContext) -> List[Finding]:
    findings: List[Finding] = []
    for jaxpr, path in iter_jaxprs(ctx.closed_jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name != "shard_map":
                continue
            where = f"{path}/shard_map"
            body = as_jaxpr(eqn.params["jaxpr"])
            in_names = shard_map_names(eqn, "in_specs")
            out_names = shard_map_names(eqn, "out_specs")
            manual = shard_map_manual_axes(eqn)
            for axis, size in manual.items():
                if size <= 1:
                    continue  # one member: replication is vacuous
                seeds = [
                    axis in names_spec_axes(entry) for entry in in_names
                ]
                out_vals = _AxisTaint(axis).run(body, seeds, where)
                for i, (val, entry) in enumerate(zip(out_vals, out_names)):
                    if val and axis not in names_spec_axes(entry):
                        findings.append(Finding(
                            rule="R1",
                            severity=ERROR,
                            message=(
                                f"shard_map output #{i} claims replication "
                                f"over mesh axis {axis!r} (size {size}) but "
                                f"derives from {axis}-partitioned data with "
                                f"no reduction over {axis!r} — replicas "
                                "diverge (missing psum/pmean?)"
                            ),
                            where=where,
                        ))
    return findings
