"""R8 — overlap budget: a declared-overlapped stream must fit its window.

The engine's performance story for hidden streams — the double-buffered
ZeRO-offload prefetch (PR 1) and the decomposed-TP ring hops (PR 3) — is
an overlap *claim*: the stream's wall time hides under the compute the
step provides. The ceiling is analytic (speedup ≈ 1/(1 − f·overlap_ratio)
only while the hidden bytes fit the window); this rule enforces it
statically.

For every stream the engine declares as overlapped
(``engine.analytic_streams()`` → ``overlapped: True``), the per-device
stream seconds (bytes over the host-DMA or ICI link from the hardware
model) must not exceed the step's analytic roofline window — the larger
of the MXU-compute and HBM-traffic terms the planner extracts from the
same jaxpr. A stream that cannot be hidden even in the best case means
the knob buys nothing but complexity (and double-buffer slots): the
config should drop it or rebalance before a chip ever measures it.

No declared streams → silent (plain configs never see R8). A
materiality floor keeps toy configs quiet: the *exposed* stream time
(stream seconds beyond the window) must cost at least 10 ms per step —
below that the static claim is numerically meaningless (test-sized
models run whole steps in microseconds) and the finding would be noise.
"""

from __future__ import annotations

from typing import List

from ..base import ERROR, Finding, LintContext
from . import register_rule

_GIB = float(1 << 30)
_MIN_EXPOSED_S = 0.010  # findings only when the un-hideable tail is real


@register_rule("R8", "overlap-budget")
def overlap_budget(ctx: LintContext) -> List[Finding]:
    streams = {
        k: s for k, s in (ctx.streams or {}).items()
        if s and s.get("overlapped")
    }
    if not streams:
        return []
    from ..cost import plan_for_context

    plan = plan_for_context(ctx)
    hw = plan.hardware
    findings: List[Finding] = []
    for name, s in streams.items():
        nbytes = float(
            s.get("per_device_bytes_per_step")
            or s.get("bytes_per_step", 0.0)
        )
        if nbytes <= 0:
            continue
        kind = s.get("kind", "offload")
        if kind == "offload":
            bw = hw.host_bw
        elif kind == "hbm":  # serving KV-arena stream
            bw = hw.hbm_bw
        else:
            bw = hw.ici_bw
        stream_s = nbytes / bw if bw > 0 else 0.0
        # the window one step provides THIS stream: host-DMA and ICI
        # streams hide under the larger of the MXU and HBM roofline
        # terms, but an HBM stream shares the very link that produces
        # hbm_s — it can only hide under the MXU term, else it simply
        # extends the HBM-bound step
        window_s = (
            plan.compute_s if kind == "hbm"
            else max(plan.compute_s, plan.hbm_s)
        )
        if stream_s <= window_s or stream_s - window_s < _MIN_EXPOSED_S:
            continue
        findings.append(Finding(
            rule="R8",
            severity=ERROR,
            message=(
                f"stream '{name}' is declared overlapped but its "
                f"{nbytes / _GIB:.2f} GiB/step over the "
                f"{ {'offload': 'host DMA', 'hbm': 'HBM'}.get(kind, 'ICI') }"
                " link "
                f"({bw / 1e9:.0f} GB/s) needs {stream_s:.4f}s — more than "
                f"the {window_s:.4f}s compute window the step provides "
                f"(MXU {plan.compute_s:.4f}s, HBM {plan.hbm_s:.4f}s); the "
                "bytes cannot be hidden even at full overlap — shrink the "
                "stream or drop the knob"
            ),
            where="<plan>",
        ))
    return findings
