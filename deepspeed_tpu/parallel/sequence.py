"""Sequence/context parallelism: DS-Ulysses and ring attention.

Parity: deepspeed/sequence/layer.py (DistributedAttention — the DS-Ulysses
all-to-all head<->sequence exchange) and the reference's long-context story.
TPU-native design:

- **Ulysses** is pure sharding arithmetic: activations arrive sequence-
  sharded over the ``sp`` mesh axis; constraining q/k/v to *head*-sharded
  (full sequence per device) makes XLA insert exactly the two all-to-alls
  the reference codes by hand, and any attention impl (XLA softmax or the
  Pallas flash kernel) runs unmodified on the full sequence. The output
  constraint swaps back to sequence sharding.
- **Ring attention** keeps q/k/v sequence-sharded and rotates KV blocks
  around the sp ring with ``ppermute`` (ICI neighbor hops), accumulating
  flash-style online softmax in fp32. Peak memory per chip is O(S/sp),
  enabling sequences that do not fit any single chip — the reference's
  blocked-attention / Ulysses-offload regime.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from jax.sharding import PartitionSpec as P

from ..models.sharding import (constrain, current_topology,
                               manual_axis_names)

_SP_MODE = "ulysses"  # process default; engines attach sp_mode to their topology

_VALID_MODES = ("ulysses", "ring")


def set_sp_mode(mode: str) -> None:
    """Set the process-wide default. Engines override per-topology
    (topology.sp_mode), so two engines with different modes don't fight."""
    global _SP_MODE
    if mode not in _VALID_MODES:
        raise ValueError(f"sequence_parallel mode {mode!r} (ulysses|ring)")
    _SP_MODE = mode


def get_sp_mode() -> str:
    topo = current_topology()
    mode = getattr(topo, "sp_mode", None) if topo is not None else None
    return mode or _SP_MODE


def ulysses_attention(q, k, v, *, causal=True, bias=None, segment_ids=None,
                      alibi_slopes=None):
    """DS-Ulysses: all-to-all seq->head, full-seq attention, all-to-all back.

    Parity: deepspeed/sequence/layer.py DistributedAttention.forward — the
    reference's explicit ``_SeqAllToAll`` pair becomes two sharding
    constraints; XLA's SPMD partitioner emits the all-to-alls over ICI.
    """
    from ..ops.attention import attention as attn_op

    # stage 1: pin the incoming seq-sharded 4D layout, so the backward's
    # dq/dk/dv reshapes happen inside one sharding instead of resharding
    # *through* a reshape (GSPMD falls back to full remat there)
    q = constrain(q, ("dp", "fsdp"), "sp", "tp", None)
    k = constrain(k, ("dp", "fsdp"), "sp", _kv_tp_axis(k.shape[2]), None)
    v = constrain(v, ("dp", "fsdp"), "sp", _kv_tp_axis(v.shape[2]), None)
    # stage 2: heads over (sp, tp): each device sees H/(sp*tp) heads, full
    # sequence. sp-major matches the mesh linearization, so the seq→head
    # move lowers to one contiguous all-to-all, not a permuted resharding.
    q = constrain(q, ("dp", "fsdp"), None, ("sp", "tp"), None)
    kv_ax = _kv_head_axes(k.shape[2])
    k = constrain(k, ("dp", "fsdp"), None, kv_ax, None)
    v = constrain(v, ("dp", "fsdp"), None, kv_ax, None)
    out = attn_op(
        q, k, v, causal=causal, bias=bias, segment_ids=segment_ids,
        alibi_slopes=alibi_slopes,
    )
    # back to sequence sharding for the rest of the block
    return constrain(out, ("dp", "fsdp"), "sp", "tp", None)


def _kv_tp_axis(kv_heads: int):
    """tp on the head dim when it divides, else replicated (GQA kv < tp)."""
    topo = current_topology()
    tp = topo.tp_size if topo is not None else 1
    return "tp" if tp > 1 and kv_heads % tp == 0 else None


def _kv_head_axes(kv_heads: int):
    """Largest ("sp","tp") combination that divides the KV head count.

    GQA under Ulysses (reference: DeepSpeed-Ulysses requires
    num_kv_heads % sp == 0, else it replicates KV): when kv_heads < sp*tp
    the KV tensors can't be fully head-sharded — constraining them onto an
    oversized axis set forces GSPMD into involuntary full rematerialization
    (padded 2-over-4 shardings). Shard what divides; the remainder
    replicates via an sp all-gather, which is the Ulysses-GQA semantics."""
    topo = current_topology()
    if topo is None:
        return None
    live = [a for a in ("sp", "tp") if topo.sizes[a] > 1]
    if not live:
        return None
    prod = 1
    for a in live:
        prod *= topo.sizes[a]
    if kv_heads % prod == 0:
        return tuple(live) if len(live) > 1 else live[0]
    for a in ("tp", "sp"):  # prefer tp: matches the model's TP weight layout
        if topo.sizes[a] > 1 and kv_heads % topo.sizes[a] == 0:
            return a
    return None


def _ring_attention_local(q, k, v, seg_q, seg_k, slopes, *, causal: bool,
                          axis: str):
    """Online-softmax ring pass over the ``axis`` ring (inside shard_map).

    q/k/v: local blocks [B, S_loc, H|KV, hd]; positions are globalized from
    the ring index, so causal masking is exact across blocks.
    """
    sp = jax.lax.axis_size(axis)
    i = lax.axis_index(axis)
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    reps = H // KV  # GQA: expand per-step at compute time, so the ring
    # carries only the KV-head payload (H/KV x less ICI traffic)
    qf = q.astype(jnp.float32)
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    qpos = i * Sq + jnp.arange(Sq)  # global positions of local queries
    perm = [(r, (r + 1) % sp) for r in range(sp)]

    m0 = jnp.full((B, H, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    acc0 = jnp.zeros((B, Sq, H, hd), jnp.float32)

    def accum(m, l, acc, kb, vb, segb, s):
        """Online-softmax update with the KV block held at ring step s."""
        blk = (i - s) % sp  # whose KV block we hold at step s
        kpos = blk * Sq + jnp.arange(Sq)
        ke = jnp.repeat(kb, reps, axis=2) if reps > 1 else kb
        ve = jnp.repeat(vb, reps, axis=2) if reps > 1 else vb
        logits = jnp.einsum("bqhd,bkhd->bhqk", qf, ke.astype(jnp.float32)) * scale
        if slopes is not None:
            # ALiBi from *global* positions: exact across ring blocks
            rel = -jnp.abs(
                qpos[:, None].astype(jnp.float32) - kpos[None, :].astype(jnp.float32)
            )  # [Sq, Sk]
            logits = logits + slopes[None, :, None, None] * rel[None, None]
        valid = jnp.ones((B, 1, Sq, Sq), jnp.bool_)
        if causal:
            valid = valid & (kpos[None, None, None, :] <= qpos[None, None, :, None])
        if segb is not None:
            same = seg_q[:, None, :, None] == segb[:, None, None, :]
            valid = valid & same
        logits = jnp.where(valid, logits, -jnp.inf)
        m_new = jnp.maximum(m, logits.max(-1))
        # fully-masked-so-far rows keep m=-inf; guard the exp against inf-inf
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(logits - m_safe[..., None]) * valid  # [B,H,Sq,Sk]
        corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_safe))
        l = l * corr + p.sum(-1)
        acc = acc * corr.transpose(0, 2, 1)[..., None] + jnp.einsum(
            "bhqk,bkhd->bqhd", p, ve.astype(jnp.float32)
        )
        return m_new, l, acc

    def step(carry, s):
        m, l, acc, kb, vb, segb = carry
        m, l, acc = accum(m, l, acc, kb, vb, segb, s)
        kb = lax.ppermute(kb, axis, perm)
        vb = lax.ppermute(vb, axis, perm)
        if segb is not None:
            segb = lax.ppermute(segb, axis, perm)
        return (m, l, acc, kb, vb, segb), None

    # sp-1 rotated steps in the scan; final block's accum outside, so the
    # ring does not pay a last rotation whose result is discarded
    (m, l, acc, kb, vb, segb), _ = lax.scan(
        step, (m0, l0, acc0, k, v, seg_k), jnp.arange(sp - 1)
    )
    m, l, acc = accum(m, l, acc, kb, vb, segb, sp - 1)
    denom = jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return (acc / denom).astype(q.dtype)


def ring_attention(q, k, v, *, causal=True, segment_ids=None,
                   alibi_slopes=None, topo=None, axis: str = "sp"):
    """Ring attention over the sp mesh axis (q/k/v arrive seq-sharded).

    q: [B, S, H, hd] global. ALiBi rides as per-head slopes, applied from
    global positions inside the ring (exact across blocks); RoPE is already
    applied upstream with global positions.
    """
    topo = topo or current_topology()
    if topo is None or topo.sp_size == 1:
        from ..ops.attention import attention as attn_op

        return attn_op(
            q, k, v, causal=causal, segment_ids=segment_ids,
            alibi_slopes=alibi_slopes,
        )

    has_seg = segment_ids is not None
    has_alibi = alibi_slopes is not None
    seg = (
        segment_ids
        if has_seg
        else jnp.zeros((q.shape[0], q.shape[1]), jnp.int32)
    )
    slopes = (
        jnp.asarray(alibi_slopes, jnp.float32)
        if has_alibi
        else jnp.zeros((q.shape[2],), jnp.float32)
    )

    # flash-ring when the flash kernel is the active impl and the local
    # chunk tiles; else the dense online-softmax ring (same math, O(S_loc²)
    # logits per hop instead of O(block²) kernel tiles)
    from ..ops.attention import resolve_attention_impl
    from ..ops.pallas.ring_flash import ring_blocks, ring_flash_attention_local

    B, S, H, hd = q.shape
    KV = k.shape[2]
    S_loc = S // topo.sp_size
    blocks = ring_blocks(S_loc)
    use_flash = (
        resolve_attention_impl() == "flash"
        and blocks is not None
        and H % KV == 0
        and hd % 8 == 0
    )

    def body(ql, kl, vl, segl, sl):
        if use_flash:
            return ring_flash_attention_local(
                ql, kl, vl,
                segl if has_seg else None,
                segl if has_seg else None,
                sl if has_alibi else None,
                causal=causal, axis=axis,
                block_q=blocks[0], block_k=blocks[1],
                block_q_bwd=blocks[2], block_k_bwd=blocks[3],
            )
        return _ring_attention_local(
            ql, kl, vl, segl, segl if has_seg else None,
            sl if has_alibi else None, causal=causal, axis=axis,
        )

    run = jax.shard_map(
        body,
        mesh=topo.mesh,
        in_specs=(
            P(None, axis, None, None),
            P(None, axis, None, None),
            P(None, axis, None, None),
            P(None, axis),
            P(None),  # slopes replicated over the ring
        ),
        out_specs=P(None, axis, None, None),
        axis_names={axis},
        check_vma=False,
    )
    return run(q, k, v, seg, slopes)


_warned_fallback = set()


def sp_attention(q, k, v, *, causal=True, bias=None, segment_ids=None,
                 alibi_slopes=None):
    """Dispatch by configured SP mode; called from the model's attention
    when the installed topology has sp_size > 1."""
    mode = get_sp_mode()
    if mode == "ring":
        if bias is None and not manual_axis_names():
            return ring_attention(
                q, k, v, causal=causal, segment_ids=segment_ids,
                alibi_slopes=alibi_slopes,
            )
        reason = (
            "dense attention bias is unsupported on the ring path"
            if bias is not None
            else "ring cannot nest inside the pipeline's manual shard_map"
        )
        if reason not in _warned_fallback:  # memory profile changes: say so
            from ..utils.logging import log_dist

            log_dist(
                f"warning: sequence_parallel mode 'ring' falling back to "
                f"ulysses: {reason} (full sequence will be materialized per "
                f"chip inside attention)"
            )
            _warned_fallback.add(reason)
    return ulysses_attention(
        q, k, v, causal=causal, bias=bias, segment_ids=segment_ids,
        alibi_slopes=alibi_slopes,
    )
