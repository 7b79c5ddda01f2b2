"""Ring flash attention: the Pallas flash kernel composed around the sp ring.

Parity: the reference's long-context story (DeepSpeed-Ulysses + blocked
attention; ring attention in the wider ecosystem). The dense ring path
(parallel/sequence.py `_ring_attention_local`) materializes a fp32
[B, H, S_loc, S_loc] logits tensor per hop — exactly the memory the flash
kernel exists to avoid. Here each ring hop runs the flash forward on the
visiting KV block with **global position offsets** carried into the kernel
(SMEM [qoff, koff]; causal/ALiBi masks are exact across hops), and partial
results merge by logsumexp — the associative flash merge, so the composite
is bit-comparable to single-device flash.

Backward follows FlashAttention-2's final-lse trick ring-style: p is
recomputed per hop from the SAVED final lse, dq accumulates locally, and
dk/dv accumulators TRAVEL WITH their kv block around the ring (one extra
hop at the end delivers every accumulator home). Peak memory stays
O(S_loc) per chip; ICI carries kv + dkv payloads only.

Called inside the shard_map of parallel/sequence.py `ring_attention`;
layouts here are [B, H, S_loc, D] (kernel layout).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


from .flash_attention import (
    AUX_LANES,
    NEG_INF,
    _flash_bwd,
    _flash_fwd,
    _pick_block,
    current_block_sizes,
    current_bwd_block_sizes,
)


def ring_blocks(S_loc: int):
    """(block_q, block_k, block_q_bwd, block_k_bwd) for the local chunk, or
    None when ineligible.

    Resolves through current_block_sizes()/current_bwd_block_sizes() so
    scoped/tuned tile overrides (engine tpu_kernels.flash_block_*,
    autotuner winners) apply on the ring path exactly as on the flat path;
    unset bwd tiles inherit the resolved fwd ones."""
    pref_q, pref_k = current_block_sizes()
    bq = _pick_block(S_loc, pref_q)
    bk = _pick_block(S_loc, pref_k)
    if not (bq and bk):
        return None
    pref_qb, pref_kb = current_bwd_block_sizes()
    bqb = (_pick_block(S_loc, pref_qb) if pref_qb else None) or bq
    bkb = (_pick_block(S_loc, pref_kb) if pref_kb else None) or bk
    return (bq, bk, bqb, bkb)


def _offsets(i, blk, S_loc):
    """SMEM (1,2) int32 [qoff, koff]: global positions of the local q block
    and of the kv block visiting at this hop."""
    return jnp.stack(
        [i * S_loc, blk * S_loc]
    ).astype(jnp.int32).reshape(1, 2)


def _seg_arg(seg_q, seg_k):
    return (seg_q, seg_k) if seg_q is not None else None


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11, 12))
def _ring_flash_bhsd(q, k, v, seg_q, seg_k, slopes, causal, axis, block_q,
                     block_k, block_q_bwd, block_k_bwd, interpret):
    out, _ = _rf_fwd(q, k, v, seg_q, seg_k, slopes, causal, axis, block_q,
                     block_k, block_q_bwd, block_k_bwd, interpret)
    return out


def _rf_fwd(q, k, v, seg_q, seg_k, slopes, causal, axis, block_q, block_k,
            block_q_bwd, block_k_bwd, interpret):
    sp = jax.lax.axis_size(axis)
    i = lax.axis_index(axis)
    B, H, S_loc, D = q.shape
    scale = 1.0 / (D**0.5)
    perm = [(r, (r + 1) % sp) for r in range(sp)]

    kb, vb, segb = k, v, seg_k
    out_acc = jnp.zeros((B, H, S_loc, D), jnp.float32)
    lse_acc = jnp.full((B, H, S_loc), NEG_INF, jnp.float32)
    # python-unrolled: sp is static; which block visits (blk) is dynamic
    # per device, so hop masking happens in-kernel via the offsets
    for s in range(sp):
        blk = (i - s) % sp
        o_s, lse_full = _flash_fwd(
            q, kb, vb, None, _seg_arg(seg_q, segb), slopes, None,
            _offsets(i, blk, S_loc), causal=causal, scale=scale,
            block_q=block_q, block_k=block_k, interpret=interpret,
        )
        lse_s = lse_full[..., 0]
        # associative flash merge of (out, lse) partials
        lse_new = jnp.logaddexp(lse_acc, lse_s)
        out_acc = (
            out_acc * jnp.exp(lse_acc - lse_new)[..., None]
            + o_s.astype(jnp.float32) * jnp.exp(lse_s - lse_new)[..., None]
        )
        lse_acc = lse_new
        if s < sp - 1:
            kb = lax.ppermute(kb, axis, perm)
            vb = lax.ppermute(vb, axis, perm)
            if segb is not None:
                segb = lax.ppermute(segb, axis, perm)
    out = out_acc.astype(q.dtype)
    return out, (q, k, v, seg_q, seg_k, slopes, out, lse_acc)


def _rf_bwd(causal, axis, block_q, block_k, block_q_bwd, block_k_bwd,
            interpret, res, do):
    q, k, v, seg_q, seg_k, slopes, out, lse = res
    sp = jax.lax.axis_size(axis)
    i = lax.axis_index(axis)
    B, H, S_loc, D = q.shape
    scale = 1.0 / (D**0.5)
    perm = [(r, (r + 1) % sp) for r in range(sp)]

    # FA2 final-lse backward: one global delta/lse, p recomputed per hop
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta_b = jnp.broadcast_to(delta[..., None], (*delta.shape, AUX_LANES))
    lse_b = jnp.broadcast_to(lse[..., None], (*lse.shape, AUX_LANES))

    kb, vb, segb = k, v, seg_k
    dq_acc = jnp.zeros(q.shape, jnp.float32)
    # dkv accumulators travel WITH their kv block (same permutation), so
    # every (q_i, kv_j) pair contributes exactly once, on q_i's device
    dk_acc = jnp.zeros(k.shape, jnp.float32)
    dv_acc = jnp.zeros(v.shape, jnp.float32)
    for s in range(sp):
        blk = (i - s) % sp
        dq_s, dk_s, dv_s, _ = _flash_bwd(
            q, kb, vb, None, lse_b, do, None, _seg_arg(seg_q, segb), slopes,
            None, _offsets(i, blk, S_loc), causal=causal, scale=scale,
            block_q=block_q_bwd, block_k=block_k_bwd, interpret=interpret,
            delta=delta_b,
        )
        dq_acc = dq_acc + dq_s.astype(jnp.float32)
        dk_acc = dk_acc + dk_s.astype(jnp.float32)
        dv_acc = dv_acc + dv_s.astype(jnp.float32)
        if s < sp - 1:
            kb = lax.ppermute(kb, axis, perm)
            vb = lax.ppermute(vb, axis, perm)
            if segb is not None:
                segb = lax.ppermute(segb, axis, perm)
            dk_acc = lax.ppermute(dk_acc, axis, perm)
            dv_acc = lax.ppermute(dv_acc, axis, perm)
    # after the last hop, block (i+1)%sp's accumulator sits here: one more
    # rotation delivers every dkv accumulator to its home device
    dk_acc = lax.ppermute(dk_acc, axis, perm)
    dv_acc = lax.ppermute(dv_acc, axis, perm)

    import numpy as np

    f0 = jax.dtypes.float0
    dseg_q = None if seg_q is None else np.zeros(seg_q.shape, f0)
    dseg_k = None if seg_k is None else np.zeros(seg_k.shape, f0)
    # slope grads: not computed by the kernels (ALiBi slopes are fixed by
    # construction); zeros, same contract as the flat flash path
    dslopes = None if slopes is None else jnp.zeros_like(slopes)
    return (dq_acc.astype(q.dtype), dk_acc.astype(k.dtype),
            dv_acc.astype(v.dtype), dseg_q, dseg_k, dslopes)


_ring_flash_bhsd.defvjp(_rf_fwd, _rf_bwd)


def ring_flash_attention_local(q, k, v, seg_q, seg_k, slopes, *, causal,
                               axis, block_q, block_k, block_q_bwd=0,
                               block_k_bwd=0, interpret=None):
    """Model layout entry ([B, S_loc, H|KV, D]), inside the ring shard_map."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = _ring_flash_bhsd(
        qt, kt, vt, seg_q, seg_k, slopes, causal, axis, block_q, block_k,
        block_q_bwd or block_q, block_k_bwd or block_k, interpret,
    )
    return jnp.swapaxes(out, 1, 2)
