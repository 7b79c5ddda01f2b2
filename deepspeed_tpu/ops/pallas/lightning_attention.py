"""Pallas kernel of lightning (linear) attention over a slot's state.

Parity: Lightning Attention (Qin et al. 2024; MiniMax-Text-01's and
MiniCPM-SALA's ``lightning-attn`` layers), for the one ``[max_slots,
token_budget]`` step the serving engine compiles. A head keeps no keys: its
cache is a float32 state ``S`` ``[hd, hd]`` a slot, ``S_t = lambda S_{t-1} +
k_t v_t^T``, ``o_t = q_t^T S_t``. Over a chunk of ``n`` real rows from the
state ``S`` the recurrence closes to four products::

    O  = (((Q K^T) * D) V + Lambda (Q S)) * scale
    S' = lambda^n S + sum_{i<n} lambda^(n-1-i) k_i v_i^T

with ``D_ij = lambda^(i-j)`` for ``j <= i`` (else 0) and ``Lambda_i =
lambda^(i+1)``. ``lambda^(i-j)`` is taken as ``exp((i - j) log lambda)`` of
the difference, never as a quotient of powers, so nothing overflows.

One program a (slot, head). The state stack ``[L, slots, H, hd, hd]`` is
read and written in place (``input_output_aliases``) at the layer's index, a
scalar in SMEM beside the slots' frontiers: a slot that begins at position
0 starts from zeros, padded rows (``i >= n``) add nothing to the state, and
a slot with no real row gets its state back bit for bit.

:func:`dense_lightning` is the same chunk in plain ``jax.numpy``: the path
of an engine without kernel injection and the oracle of the kernel's tests.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
_HI = lax.Precision.HIGHEST


def _lightning_kernel(cl_ref, nn_ref, layer_ref, ll_ref, q_ref, k_ref, v_ref,
                      s_ref, o_ref, s_out, *, scale):
    b = pl.program_id(0)
    cl, nn = cl_ref[b], nn_ref[b]
    W = q_ref.shape[2]
    ll = ll_ref[pl.program_id(1)]  # log lambda of this head, a scalar
    q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
    held = s_ref[0, 0, 0]
    # a request's first chunk starts from nothing, whatever the slot held
    s0 = jnp.where((cl == 0) & (nn > 0), 0.0, held)
    row = lax.broadcasted_iota(jnp.int32, (W, 1), 0)
    col = lax.broadcasted_iota(jnp.int32, (1, W), 1)
    diff = row - col
    decay = jnp.where((diff >= 0) & (col < nn),
                      jnp.exp(diff.astype(F32) * ll), 0.0)
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=F32)
    intra = lax.dot_general((s * decay).astype(v.dtype), v,
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=F32)
    carried = lax.dot_general(
        q.astype(F32) * jnp.exp((row + 1).astype(F32) * ll), s0,
        (((1,), (0,)), ((), ())), preferred_element_type=F32, precision=_HI)
    o_ref[0, 0] = ((intra + carried) * scale).astype(o_ref.dtype)
    # the state after the chunk's REAL rows
    w = jnp.where(row < nn, jnp.exp((nn - 1 - row).astype(F32) * ll), 0.0)
    add = lax.dot_general(k.astype(F32) * w, v.astype(F32),
                          (((0,), (0,)), ((), ())),
                          preferred_element_type=F32, precision=_HI)
    after = jnp.exp(nn.astype(F32) * ll) * s0 + add
    s_out[0, 0, 0] = jnp.where(nn > 0, after, held)


def lightning_attention(q, k, v, log_decay, state, cache_len, num_new, *,
                        layer, scale: float,
                        interpret: Optional[bool] = None):
    """q/k/v ``[B, S, H, hd]`` of one chunk a slot, ``log_decay`` [H] float32
    (``log lambda`` of each head), ``state`` the stack ``[L, B, H, hd, hd]``
    float32 and ``layer`` this layer's (traced) index in it; ``cache_len``
    [B] each slot's position before the chunk, ``num_new`` [B] its real
    rows. Returns (out ``[B, S, H, hd]``, the stack with ``[layer]``
    advanced in place)."""
    B, S, H, hd = q.shape
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    heads_first = lambda a: a.transpose(0, 2, 1, 3)
    row_spec = pl.BlockSpec((1, 1, S, hd), lambda b, h, *_: (b, h, 0, 0))
    state_spec = pl.BlockSpec((1, 1, 1, hd, hd),
                              lambda b, h, cl, nn, layer, ll: (
                                  layer[0], b, h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=(B, H),  # the last: log lambda a head
        in_specs=[row_spec, row_spec, row_spec, state_spec],
        out_specs=[row_spec, state_spec],
    )
    out, state = pl.pallas_call(
        functools.partial(_lightning_kernel, scale=float(scale)),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands count the four scalar-prefetch vectors: the stack is 8th
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret, name="lightning_attention",
    )(jnp.asarray(cache_len, jnp.int32), jnp.asarray(num_new, jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), jnp.asarray(log_decay, F32),
      heads_first(q), heads_first(k), heads_first(v), state)
    return heads_first(out), state


def dense_lightning(q, k, v, log_decay, state, cache_len, num_new, *,
                    scale: float):
    """The chunk of :func:`lightning_attention` by plain lines, float32:
    ``state`` is ONE layer's ``[B, H, hd, hd]``. Returns (out float32
    ``[B, S, H, hd]``, the layer's state after the real rows)."""
    S = q.shape[1]
    q, k, v = (a.astype(F32) for a in (q, k, v))
    ll = jnp.asarray(log_decay, F32)
    nn = jnp.asarray(num_new, jnp.int32)
    live = (nn > 0)[:, None, None, None]
    fresh = (jnp.asarray(cache_len) == 0)[:, None, None, None] & live
    s0 = jnp.where(fresh, 0.0, state)
    row, col = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    # [B, H, S, S]: lambda^(i-j) at or below the diagonal, real keys alone
    decay = jnp.where(
        ((row >= col) & (col < nn[:, None, None]))[:, None],
        jnp.exp((row - col).astype(F32) * ll[None, :, None, None]), 0.0)
    # [B, S, H, 1]: what row i's key still weighs after the last real row
    left = (nn[:, None] - 1 - jnp.arange(S)[None, :])[:, :, None, None]
    w = jnp.where(left >= 0,
                  jnp.exp(left.astype(F32) * ll[None, None, :, None]), 0.0)
    lead = jnp.exp((jnp.arange(S) + 1).astype(F32)[None, :, None, None]
                   * ll[None, None, :, None])
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("bihd,bjhd->bhij", q, k)
        intra = jnp.einsum("bhij,bjhd->bihd", s * decay, v)
        carried = jnp.einsum("bihd,bhde->bihe", q * lead, s0)
        add = jnp.einsum("bihd,bihe->bhde", k * w, v)
    after = jnp.exp(nn.astype(F32)[:, None, None, None]
                    * ll[None, :, None, None]) * s0 + add
    return (intra + carried) * scale, jnp.where(live, after, state)
