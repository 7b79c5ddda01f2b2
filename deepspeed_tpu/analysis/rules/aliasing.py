"""R4 — donation/aliasing.

Two statically visible read-after-overwrite classes around donated and
rotating buffers:

(a) stale slot read: inside a scan/while body, a loop-carried buffer that
    is overwritten in place (``dynamic_update_slice`` / scatter — the
    rotating-slot idiom of the double-buffered offload stream and the KV
    cache) must not be read again *after* the updating equation. In SSA
    form the stale pre-update variable is still nameable; XLA either
    inserts a defensive copy (defeating the rotation) or, for donated /
    host-pinned slots, serves the overwritten bytes.

(b) read-after-donate: a value consumed by an inner jit that donates it
    (``donated_invars``) is dead — any later use at the same jaxpr level
    reads a buffer the callee was free to overwrite.

Both only fire on evidence in the program itself; the engine-level
donation/aval audit lives in shardlint.lint_engine.
"""

from __future__ import annotations

from typing import List, Set

from ..base import ERROR, Finding, LintContext
from ..trace import Jaxpr, Literal, as_jaxpr, iter_jaxprs, scan_split
from . import register_rule

_INPLACE = {"dynamic_update_slice", "scatter", "scatter-add", "scatter-mul",
            "scatter-min", "scatter-max"}


def _loop_carry_invars(jaxpr: Jaxpr, eqn) -> Set:
    if eqn.primitive.name == "scan":
        body = as_jaxpr(eqn.params["jaxpr"])
        nc, ncar = scan_split(eqn)
        return set(body.invars[nc:nc + ncar])
    if eqn.primitive.name == "while":
        body = as_jaxpr(eqn.params["body_jaxpr"])
        bn = eqn.params["body_nconsts"]
        return set(body.invars[bn:])
    return set()


def _stale_slot_reads(body: Jaxpr, carries: Set, path: str) -> List[Finding]:
    findings = []
    overwritten = {}  # stale var -> index of the updating eqn
    for i, eqn in enumerate(body.eqns):
        for a in eqn.invars:
            if isinstance(a, Literal):
                continue
            if a in overwritten and not (
                eqn.primitive.name in _INPLACE and eqn.invars[0] is a
            ):
                findings.append(Finding(
                    rule="R4",
                    severity=ERROR,
                    message=(
                        f"loop-carried buffer is read by {eqn.primitive.name} "
                        f"after being overwritten in place (eqn "
                        f"#{overwritten[a]} {body.eqns[overwritten[a]].primitive.name}) "
                        "— a rotating slot served stale bytes (or forces a "
                        "defensive copy)"
                    ),
                    where=path,
                ))
        if eqn.primitive.name in _INPLACE and eqn.invars and not isinstance(
            eqn.invars[0], Literal
        ) and eqn.invars[0] in carries:
            overwritten.setdefault(eqn.invars[0], i)
    return findings


@register_rule("R4", "donation-aliasing")
def donation_aliasing(ctx: LintContext) -> List[Finding]:
    findings: List[Finding] = []
    for jaxpr, path in iter_jaxprs(ctx.closed_jaxpr):
        # (a) stale rotating-slot reads inside loop bodies
        for eqn in jaxpr.eqns:
            carries = _loop_carry_invars(jaxpr, eqn)
            if not carries:
                continue
            body = as_jaxpr(
                eqn.params["jaxpr"]
                if eqn.primitive.name == "scan"
                else eqn.params["body_jaxpr"]
            )
            findings.extend(_stale_slot_reads(
                body, carries, f"{path}/{eqn.primitive.name}"
            ))
        # (b) read-after-donate at this level
        donated_at = {}  # var -> eqn index that donated it
        for i, eqn in enumerate(jaxpr.eqns):
            for a in eqn.invars:
                if isinstance(a, Literal):
                    continue
                if a in donated_at:
                    findings.append(Finding(
                        rule="R4",
                        severity=ERROR,
                        message=(
                            f"value is used by {eqn.primitive.name} after "
                            f"being donated to an inner jit (eqn "
                            f"#{donated_at[a]}) — the callee may already "
                            "have overwritten the buffer"
                        ),
                        where=f"{path}/{eqn.primitive.name}",
                    ))
            if eqn.primitive.name == "jit":  # an inner jax.jit call
                for a, don in zip(eqn.invars,
                                  eqn.params.get("donated_invars") or ()):
                    if don and not isinstance(a, Literal):
                        donated_at.setdefault(a, i)
        for a in jaxpr.outvars:
            if not isinstance(a, Literal) and a in donated_at:
                findings.append(Finding(
                    rule="R4",
                    severity=ERROR,
                    message=(
                        "a donated value is returned from the enclosing "
                        "program — the caller receives a buffer the inner "
                        "jit was free to overwrite"
                    ),
                    where=path,
                ))
    return findings
