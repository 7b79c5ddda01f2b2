"""Pallas matmul over a stacked expert bank that reads the experts a step
reached and no other.

The serving expert layer (``moe/sharded_moe.moe_serving_mlp``) lays its rows
``[E, C, K]``, an expert's ``C`` capacity rows under its index, and multiplies
them by the layer's bank ``[E, K, N]``. One member's share of a wide router
reaches a fraction of its ``E`` held experts in a step of a few dozen tokens
(``fill`` [E], the rows each got), and an einsum over ``E`` reads every bank
whatever ``fill`` says. Here the grid walks ``(E, tiles of N)`` over the
touched experts FIRST, in their order, and the untouched after them:

- the bank is taken as the STACK ``[L, E, K, N]`` with the layer's index a
  scalar in SMEM beside the walk's tables, as the state kernels take their
  stacks: a slice of a custom call's operand would be a copy of the bank;
- the weight (and the rows') index map names the expert of the grid step
  while it is a touched one, and the LAST touched expert's last block at
  every step after them: consecutive steps then name one block, the pipeline
  fetches nothing, and ``pl.when`` keeps the product away. The touched
  experts' blocks follow one another, so each is fetched under the product
  before it;
- an untouched expert's output rows are written as zeros, not left: the
  combine gathers slot 0 of expert 0 for a pair that is held nowhere and
  weighs it 0, and what HBM held before times 0 can be NaN.

The contraction is whole; a weight block is the widest tile of ``N`` (a
multiple of 128 lanes that divides it) of at most ``BLOCK_BYTES``; products
accumulate in float32 and the epilogue (SiLU of the gate's product times the
other, or GELU) runs on the float32 sums before the one rounding to the
compute type.

:func:`dense_bank` is the einsum the kernel stands in for, and its oracle.
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
LANES = 128
BLOCK_BYTES = 4 << 20  # a weight block; Ling's [2560, 768] bf16 bank is one
VMEM_CAP = 96 << 20    # of the 128 MiB a v5e core has


def touched_first(fill):
    """``fill`` [E] (rows an expert got) -> (``order`` [E] int32: the touched
    experts in their order, then the untouched in theirs; the touched count
    [1] int32). A rank a side and one comparison: no sort, no scatter."""
    E = fill.shape[0]
    hit = fill > 0
    n = jnp.sum(hit.astype(jnp.int32))
    rank = jnp.where(hit, jnp.cumsum(hit) - 1, n + jnp.cumsum(~hit) - 1)
    e = jnp.arange(E, dtype=jnp.int32)
    order = jnp.sum(jnp.where(rank[None, :] == e[:, None], e[None, :], 0),
                    axis=1, dtype=jnp.int32)
    return order, n.reshape(1)


def tile_of(K: int, N: int, itemsize: int) -> int:
    """The widest tile of ``N`` whose ``[K, tile]`` block is at most
    ``BLOCK_BYTES``: all of ``N``, or a multiple of the lanes dividing it."""
    if K * N * itemsize <= BLOCK_BYTES or N % LANES:
        return N
    fits = [t for t in range(LANES, N, LANES)
            if N % t == 0 and K * t * itemsize <= BLOCK_BYTES]
    return max(fits, default=LANES)


def _bank_kernel(layer_ref, n_ref, order_ref, x_ref, *refs, gelu):
    *w_refs, o_ref = refs
    live = pl.program_id(0) < n_ref[0]

    @pl.when(live)
    def _product():
        x = x_ref[0]
        y = jnp.dot(x, w_refs[0][0, 0], preferred_element_type=F32)
        if len(w_refs) == 2:
            y = jax.nn.silu(jnp.dot(x, w_refs[1][0, 0],
                                    preferred_element_type=F32)) * y
        elif gelu:
            y = jax.nn.gelu(y)
        o_ref[0] = y.astype(o_ref.dtype)

    @pl.when(jnp.logical_not(live))
    def _untouched():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)


def expert_bank(x, bank, layer, fill, *, gate=None, gelu: bool = False,
                interpret: Optional[bool] = None):
    """``x`` [E, C, K] capacity rows times ``bank[layer]``, ``bank`` the stack
    ``[L, E, K, N]`` and ``layer`` the (traced) index in it -> [E, C, N] in
    ``x``'s type. ``fill`` [E] int32: the rows each expert got; an expert
    with none has its bank left in HBM and its output rows zero. With
    ``gate`` (a second stack of ``bank``'s shape) the result is ``silu(x @
    gate) * (x @ bank)``; ``gelu`` (no gate) gives ``gelu(x @ bank)``."""
    E, C, K = x.shape
    N = bank.shape[-1]
    assert bank.shape[1:] == (E, K, N), (x.shape, bank.shape)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    banks = (bank,) if gate is None else (bank, gate)
    tn = tile_of(K, N, bank.dtype.itemsize)
    J = N // tn
    order, n = touched_first(fill)

    def held(e, n, order):
        # the step's expert while it is a touched one, then the last of them
        return order[jnp.minimum(e, jnp.maximum(n[0] - 1, 0))]

    def w_map(e, j, layer, n, order):
        return layer[0], held(e, n, order), 0, jnp.where(e < n[0], j, J - 1)

    x_spec = pl.BlockSpec((1, C, K),
                          lambda e, j, layer, n, order: (held(e, n, order),
                                                         0, 0))
    w_spec = pl.BlockSpec((1, 1, K, tn), w_map)
    o_spec = pl.BlockSpec((1, C, tn),
                          lambda e, j, layer, n, order: (order[e], 0, j))
    need = 2 * (len(banks) * K * tn * bank.dtype.itemsize
                + (C * K + C * tn) * x.dtype.itemsize) + 4 * C * tn * 4
    return pl.pallas_call(
        functools.partial(_bank_kernel, gelu=gelu),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(E, J),
            in_specs=[x_spec] + [w_spec] * len(banks), out_specs=o_spec),
        out_shape=jax.ShapeDtypeStruct((E, C, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=min(VMEM_CAP, max(32 << 20, 2 * need))),
        interpret=interpret, name="expert_bank",
    )(jnp.asarray(layer, jnp.int32).reshape(1), n, order, x, *banks)


def dense_bank(x, bank, layer, *, gate=None, gelu: bool = False):
    """:func:`expert_bank` by the einsum over every expert."""
    at = lambda w: lax.dynamic_index_in_dim(w, layer, 0, False)
    y = jnp.einsum("eck,ekn->ecn", x, at(bank))
    if gate is not None:
        return jax.nn.silu(jnp.einsum("eck,ekn->ecn", x, at(gate))) * y
    return jax.nn.gelu(y) if gelu else y
