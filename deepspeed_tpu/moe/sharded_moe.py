"""Expert-parallel mixture-of-experts layer.

Parity: deepspeed/moe/sharded_moe.py (TopKGate + MOELayer with its NCCL
all-to-all dispatch). TPU-native design is the GShard/Switch dense-dispatch
formulation: routing builds one-hot dispatch/combine tensors and the
dispatch/combine "all-to-all" is an einsum whose output is sharding-
constrained onto the ``ep`` mesh axis — XLA lowers the resharding to the
same all-to-all the reference hand-codes, but fused and overlapped.

Top-1/top-k gating with capacity factor, token dropping, load-balance aux
loss and router z-loss match the reference's TopKGate semantics.

Placement is ONE pass a layer (:func:`_place_pairs`): the K argmax rounds
only choose (cheap elementwise work that fixes the tie rule); where every
(token, chosen expert) pair sits inside its expert comes from one
triangular product over the rounds' one-hots laid side by side plus a
[K, E] recurrence of fills that counts kept pairs only, in round-major
order (all of round 0 in token order, then round 1, ...); the gather
tables take one scatter over the N x K pairs (:func:`_slot_tables`). No
cumulative sum over rows, which the chip runs as a slow ``reduce-window``,
and no scatter a round. The softmax gate and the sigmoid gate's serving
placement (:func:`held_expert_tables`) share it; the round-by-round loop
it replaced is the tests' oracle (tests/moe_round_oracle.py).

Two dispatch formulations share one gating function (``moe_dispatch``):
- "einsum" (default): one-hot dispatch/combine dots — GShard-style, rides
  the MXU, sharding-friendly.
- "gather": index tables drive plain gathers — the one-hot dots are
  permutations written as dense matmuls (O(N·E·C·D) flops to move O(N·D)
  values; at 16k tokens / 8 experts / cap 2 that is ~1 TFLOP of pure data
  movement per layer per direction), so the gather form trades MXU flops
  for HBM bytes. A/B on-chip via the model config; parity-tested.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..models.sharding import constrain, current_topology


def _a2a_overlap_active(B: int, S: int, E: int, F: int):
    """(overlap_cfg, topology) when the decomposed-a2a scope is active AND
    the shapes divide the mesh (moe.overlap_a2a — parallel/a2a_overlap.py);
    (None, None) otherwise, and the serial GSPMD path runs."""
    from ..parallel.a2a_overlap import current_a2a, moe_a2a_applicable

    cfg = current_a2a()
    if cfg is None:
        return None, None
    topo = current_topology()
    if topo is None or not moe_a2a_applicable(topo, B=B, S=S, E=E, F=F):
        return None, None
    return cfg, topo


# rows one triangular product spans: an [N, N] operand is never built for
# the training callers' 16 k+ tokens (64 MB in bf16 at 4,096 rows already)
_RANK_BLOCK = 1024


def _rows_before(marks: jax.Array) -> jax.Array:
    """``marks`` [N, C] bool -> [N, C] int32: for row n and column c, how
    many rows BEFORE n are marked in c (an exclusive count down the rows).

    One product of a strictly-lower-triangular 0/1 matrix with the marks
    (bf16 operands, float32 accumulation: exact, the counts are whole
    numbers up to the block) — MXU work, where a cumulative sum over rows
    is a ``reduce-window`` the chip runs slowly. Rows go in blocks of at
    most ``_RANK_BLOCK`` (the block follows N), the product inside a
    block, and a block starts from the totals of the blocks before it."""
    N, C = marks.shape
    blocks = -(-N // _RANK_BLOCK)
    block = -(-N // blocks)
    x = jnp.pad(marks, ((0, blocks * block - N), (0, 0)))
    x = x.reshape(blocks, block, C)
    row = jnp.arange(block, dtype=jnp.int32)
    below = (row[:, None] > row[None, :]).astype(jnp.bfloat16)
    ranks = jnp.einsum("ij,bjc->bic", below, x.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32).astype(jnp.int32)
    if blocks > 1:
        totals = jnp.sum(x, axis=1, dtype=jnp.int32)  # [blocks, C]
        b = jnp.arange(blocks, dtype=jnp.int32)
        before = jnp.sum(jnp.where((b[:, None] > b[None, :])[:, :, None],
                                   totals[None], 0), axis=1)
        ranks = ranks + before[:, None, :]
    return ranks.reshape(blocks * block, C)[:N]


def _place_pairs(choice: jax.Array, live: jax.Array, n_experts: int,
                 capacity: int):
    """The placement both gates share, in one pass over the layer's
    (token, chosen expert) pairs: ``choice`` [N, K] int32 is the expert of
    pair (n, k) (in range wherever ``live``), ``live`` [N, K] bool which
    pairs take part at all (the softmax gate passes its ``valid`` rows, the
    sigmoid gate the pairs whose expert is held here).

    Pairs fill an expert in ROUND-MAJOR order: all of round 0 in token
    order, then round 1, ... So pair (n, k) sits at ``start[k, e]`` + the
    live tokens before n that chose e in round k. The second term, for all
    K rounds at once, is one triangular product over the rounds' one-hots
    side by side (:func:`_rows_before`); the first is [K, E] arithmetic: a
    round's positions in an expert are contiguous from ``start[k]``, so it
    KEEPS ``clip(capacity - start[k], 0, count[k])`` of them and the next
    round starts after the kept ones only.

    Returns ``pos`` [N, K] int32 (0 for a pair that is not live), ``keep``
    [N, K] bool (live and under the capacity), ``counts`` [K, E] int32 (live
    pairs a round an expert, kept or not) and ``fill`` [E] int32 (pairs kept
    an expert)."""
    N, K = choice.shape
    onehot = live[:, :, None] & (
        choice[:, :, None] == jnp.arange(n_experts, dtype=choice.dtype))
    ranks = _rows_before(onehot.reshape(N, K * n_experts))
    counts = jnp.sum(onehot, axis=0, dtype=jnp.int32)  # [K, E]
    fill = jnp.zeros((n_experts,), jnp.int32)
    start = []
    for k in range(K):
        start.append(fill)
        fill = fill + jnp.clip(capacity - fill, 0, counts[k])
    at = ranks.reshape(N, K, n_experts) + jnp.stack(start)[None]
    pos = jnp.sum(jnp.where(onehot, at, 0), axis=-1)  # [N, K]
    return pos, live & (pos < capacity), counts, fill


def _slot_tables(choice: jax.Array, pos: jax.Array, keep: jax.Array,
                 n_experts: int, capacity: int):
    """The index tables of a placement (:func:`_place_pairs`):
    ``tok_of_slot`` [E, C] int32, ``slot_valid`` [E, C] bool and
    ``slot_of_tok`` [N, K] int32 (flat ``e * C + c``; 0 for a pair not
    kept). ONE scatter over the N x K pairs writes ``token + 1`` into
    zeros, so an empty slot reads token 0, not valid; kept pairs have
    distinct slots, and every other pair writes the dummy slot that is
    sliced off."""
    N, K = choice.shape
    slots = n_experts * capacity
    flat = choice * capacity + jnp.minimum(pos, capacity - 1)
    target = jnp.where(keep, flat, slots)
    token = jnp.repeat(jnp.arange(1, N + 1, dtype=jnp.int32), K)
    held = jnp.zeros((slots + 1,), jnp.int32).at[target.reshape(-1)].set(
        token)[:-1].reshape(n_experts, capacity)
    return jnp.maximum(held - 1, 0), held > 0, jnp.where(keep, flat, 0)


def _gating_rounds(logits, top_k, capacity, rng, train, noise_std,
                   valid=None):
    """The shared top-k selection: per-round (expert idx, slot pos, keep
    mask, raw gate value) plus the aux metrics. ONE implementation so the
    einsum and gather dispatch paths cannot diverge. The rounds choose the
    experts (argmax, ties to the lower index, the chosen expert masked out
    of the next round); the positions of all K rounds come from one pass
    (:func:`_place_pairs`): round-major order inside an expert, a round
    starting where the KEPT pairs of the rounds before it end.

    The inference path accepts ``rng=None`` without consuming a key:
    router noise is only ever sampled when TRAINING with
    ``noise_std > 0`` — gating at eval is bitwise identical with and
    without an rng, so serving's deterministic per-request RNG discipline
    never threads a key through the router (unit-tested in
    tests/test_moe.py).

    ``valid`` ([N] bool, optional) is the serving engine's null-expert
    contract: rows marked invalid (padded chunk tails, idle slots, done
    requests) never enter the selection — they occupy no capacity slot,
    shift no other token's position, and carry zero combine
    weight — so routing of the REAL tokens is independent of batch
    occupancy and the one fixed-shape step never recompiles (or drops
    differently) as occupancy changes."""
    N, E = logits.shape
    if train and noise_std > 0.0 and rng is not None:
        logits = logits + jax.random.normal(rng, logits.shape) * noise_std
    if valid is not None:
        # zeroed (not -inf) logits: invalid rows route through finite
        # uniform gates, so no NaN/inf can leak out of garbage hidden
        # states into the masked arithmetic below
        logits = jnp.where(valid[:, None], logits, 0.0)
    gates = jax.nn.softmax(logits, axis=-1)  # [N, E]

    masked_gates = gates
    me = jnp.mean(gates, axis=0)  # gate fraction per expert
    choice, gate_vals = [], []
    for _ in range(top_k):
        idx = jnp.argmax(masked_gates, axis=-1)  # [N], ties to the lower
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)  # [N, E]
        if valid is not None:
            onehot = onehot * valid[:, None].astype(onehot.dtype)
        choice.append(idx)
        gate_vals.append(jnp.sum(gates * onehot, axis=-1))  # [N]
        masked_gates = masked_gates * (1.0 - onehot)  # exclude chosen expert
    choice = jnp.stack(choice, axis=1)  # [N, K]
    live = jnp.ones((N, top_k), bool) if valid is None else jnp.broadcast_to(
        valid[:, None], (N, top_k))
    pos, keep, counts, fill = _place_pairs(choice, live, E, capacity)
    rounds = [(choice[:, k], pos[:, k], keep[:, k], gate_vals[k])
              for k in range(top_k)]
    # token fraction per expert, summed over the rounds
    ce_acc = jnp.sum(counts, axis=0).astype(jnp.float32) / N
    routed = jnp.sum(fill)

    aux_loss = E * jnp.sum(me * (ce_acc / top_k))
    z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    n_routed = (
        jnp.sum(valid.astype(jnp.float32)) if valid is not None
        else jnp.asarray(float(N))
    )
    dropped = jnp.where(
        n_routed > 0,
        1.0 - routed.astype(jnp.float32) / jnp.maximum(n_routed * top_k, 1.0),
        0.0,
    )
    metrics = {
        "aux_loss": aux_loss,
        "z_loss": z_loss,
        "drop_fraction": dropped,
        # serving load-balance observability: tokens that actually landed
        # a capacity slot, per expert (the fill counters)
        "tokens_per_expert": fill,
        "routed_tokens": routed,
    }
    return rounds, metrics


def top_k_gating(
    logits: jax.Array,  # [N, E] fp32
    top_k: int,
    capacity: int,
    rng: Optional[jax.Array],
    train: bool,
    noise_std: float = 0.0,
    valid: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """Returns (dispatch [N,E,C] bool-ish, combine [N,E,C], aux metrics).

    Parity: TopKGate.forward (deepspeed/moe/sharded_moe.py top1gating/top2gating):
    softmax gates, top-k experts per token, positions in arrival order
    round by round (computed in one pass, :func:`_place_pairs`), overflow
    tokens dropped, load-balance loss = E * mean(gate_frac * token_frac).
    ``valid`` is the serving null-expert mask (see :func:`_gating_rounds`).
    """
    N, E = logits.shape
    rounds, metrics = _gating_rounds(logits, top_k, capacity, rng, train,
                                     noise_std, valid=valid)
    combine = jnp.zeros((N, E, capacity), jnp.float32)
    dispatch = jnp.zeros((N, E, capacity), jnp.bool_)
    for idx, pos_tok, keep, gate_val in rounds:
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)
        pos_oh = jax.nn.one_hot(
            jnp.where(keep, pos_tok, capacity), capacity + 1
        )[:, :capacity]
        contrib = onehot[:, :, None] * pos_oh[:, None, :]  # [N, E, C]
        combine = combine + contrib * gate_val[:, None, None] * keep[:, None, None]
        dispatch = dispatch | (contrib > 0) & keep[:, None, None]

    # renormalize combine weights over selected experts (top-2 reference behavior)
    denom = jnp.sum(combine, axis=(1, 2), keepdims=True)
    combine = jnp.where(denom > 0, combine / jnp.maximum(denom, 1e-9), combine)
    return dispatch.astype(jnp.float32), combine, metrics


def top_k_gating_indices(
    logits: jax.Array,  # [N, E] fp32
    top_k: int,
    capacity: int,
    rng: Optional[jax.Array],
    train: bool,
    noise_std: float = 0.0,
    valid: Optional[jax.Array] = None,
):
    """Index-table form of :func:`top_k_gating` (same selection, same
    one-pass placement; the tables are written by one scatter over the
    N x K pairs, :func:`_slot_tables`).

    Returns (tok_of_slot [E,C] int32, slot_valid [E,C] bool,
    slot_of_tok [N,K] int32 flat e*C+c, w_of_tok [N,K] fp32, metrics).
    The one-hot dispatch/combine einsums are permutations written as dense
    dots — O(N·E·C·D) MXU flops to move O(N·D) values; these tables drive
    plain gathers instead (O(N·D·K) bytes), the sort-based formulation TPU
    MoE stacks use (and the reference's all-to-all ordering implies).
    ``valid`` is the serving null-expert mask (see :func:`_gating_rounds`):
    invalid rows never occupy a slot and carry zero combine weight."""
    N, E = logits.shape
    rounds, metrics = _gating_rounds(logits, top_k, capacity, rng, train,
                                     noise_std, valid=valid)
    idx, pos, keep, gate_val = zip(*rounds)
    tok_of_slot, slot_valid, slot_of_tok = _slot_tables(
        *(jnp.stack(column, axis=1) for column in (idx, pos, keep)),
        E, capacity)
    # a round's weights multiplied before they are laid side by side: the
    # sum below then adds the K columns in order on the chip too (a
    # reduction over the stacked product adds them in another)
    w = jnp.stack([g * k for g, k in zip(gate_val, keep)], axis=1)  # [N, K]
    denom = jnp.sum(w, axis=1, keepdims=True)
    w = jnp.where(denom > 0, w / jnp.maximum(denom, 1e-9), w)
    return tok_of_slot, slot_valid, slot_of_tok, w, metrics


def sigmoid_group_gate(logits: jax.Array, sel_bias: jax.Array, top_k: int,
                       groups: int, groups_kept: int, routed_scale: float,
                       norm_eps: float = 0.0):
    """The router of DeepSeek-V3 (``noaux_tc``): ``logits`` [N, E] float32
    -> (expert idx [N, K] int32, weight [N, K] float32).

    Scores are ``sigmoid(logits)``. ``sel_bias`` [E] is added to CHOOSE and
    never to weigh: a group (E / groups consecutive experts) scores the sum
    of its two best biased scores, the ``groups_kept`` best groups stay, the
    ``top_k`` best biased scores inside them are chosen (ties to the lower
    index), and the weights are the chosen experts' unbiased scores
    normalised to one (over ``sum + norm_eps`` where a release adds one),
    times ``routed_scale``."""
    N, E = logits.shape
    scores = jax.nn.sigmoid(logits)
    biased = scores + sel_bias.astype(jnp.float32)[None, :]
    per = E // groups
    best2, _ = jax.lax.top_k(biased.reshape(N, groups, per), min(2, per))
    _, kept = jax.lax.top_k(jnp.sum(best2, axis=-1), groups_kept)  # [N, gk]
    in_kept = jnp.any(
        kept[:, :, None] == jnp.arange(groups)[None, None, :], axis=1)
    masked = jnp.where(jnp.repeat(in_kept, per, axis=1), biased, -jnp.inf)
    _, idx = jax.lax.top_k(masked, top_k)
    w = jnp.take_along_axis(scores, idx, axis=1)
    total = jnp.sum(w, axis=1, keepdims=True)
    w = w / (total + norm_eps if norm_eps else jnp.maximum(total, 1e-20))
    return idx.astype(jnp.int32), w * routed_scale


def sigmoid_gate(logits: jax.Array, top_k: int):
    """The plain sigmoid router (``moe_gate`` "sigmoid"): ``logits`` [N, E]
    float32 -> (expert idx [N, K] int32, weight [N, K] float32): the
    ``top_k`` largest sigmoid scores (ties to the lower index), normalised
    to one. No selection bias, no groups, no scale."""
    w, idx = jax.lax.top_k(jax.nn.sigmoid(logits), top_k)
    w = w / jnp.maximum(jnp.sum(w, axis=1, keepdims=True), 1e-20)
    return idx.astype(jnp.int32), w


def softmax_gate(logits: jax.Array, top_k: int):
    """The softmax router that drops nothing (``moe_gate`` "softmax" with
    ``moe_capacity_factor`` 0): ``logits`` [N, E] float32 -> (expert idx
    [N, K] int32, weight [N, K] float32): the ``top_k`` largest of a softmax
    over ALL ``E`` outputs (ties to the lower index), renormalised to one
    over the chosen."""
    w, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    w = w / jnp.maximum(jnp.sum(w, axis=1, keepdims=True), 1e-20)
    return idx.astype(jnp.int32), w


def route_dropless(cfg, p: Dict, logits: jax.Array, train: bool = False):
    """(idx, weight) of a router that drops no token
    (``cfg.moe_dropless``), by ``cfg.moe_gate``: either sigmoid router or
    the softmax one without a capacity. The one place the choice is made;
    with ``train`` the selection bias carries no gradient."""
    if cfg.moe_gate == "softmax":
        return softmax_gate(logits, cfg.moe_top_k)
    if cfg.moe_gate == "sigmoid":
        return sigmoid_gate(logits, cfg.moe_top_k)
    bias = jax.lax.stop_gradient(p["sel_bias"]) if train else p["sel_bias"]
    return sigmoid_group_gate(logits, bias, cfg.moe_top_k, cfg.moe_groups,
                              cfg.moe_groups_kept, cfg.moe_routed_scale,
                              cfg.moe_norm_eps)


def held_expert_tables(idx, w, valid, first: int, held: int, capacity: int):
    """Index tables of ONE member's share of an expert-parallel layer, in
    the layout of :func:`top_k_gating_indices`: of each token's chosen
    experts ``idx`` [N, K] (weights ``w``), those in ``first .. first +
    held`` get a row of their expert's ``capacity`` here; the others are
    computed elsewhere (slot 0, weight zero). No token is dropped while
    ``capacity`` is at least the real tokens (an expert is chosen once a
    token). Also returns the tokens per held expert [held] (every pair
    sent to it, kept or not) and the real tokens with no pair kept here.
    The placement is the softmax gate's (:func:`_place_pairs` with the
    pairs held here as its live ones, :func:`_slot_tables`)."""
    N, K = idx.shape
    local = idx - first
    here = (local >= 0) & (local < held)
    if valid is not None:
        here = here & valid[:, None]
    choice = jnp.clip(local, 0, held - 1)
    pos, keep, counts, _ = _place_pairs(choice, here, held, capacity)
    tok_of_slot, slot_valid, slot_of_tok = _slot_tables(
        choice, pos, keep, held, capacity)
    real = jnp.ones((N,), bool) if valid is None else valid
    unrouted = jnp.sum((real & ~jnp.any(keep, axis=1)).astype(jnp.int32))
    return (tok_of_slot, slot_valid, slot_of_tok, w * keep,
            jnp.sum(counts, axis=0), unrouted)


@jax.custom_vjp
def _rows_of_pairs(tokens, order, inv, here):
    """``tokens`` [N, D] -> the row of every (token, chosen expert) pair in
    sorted order [N x K, D]: a gather forward, and a gather backward too
    (``inv`` is the inverse of the permutation ``order``; the cotangent of a
    pair not held here, ``here`` false, is left out)."""
    return jnp.take(tokens, order // (order.shape[0] // tokens.shape[0]),
                    axis=0)


def _rows_of_pairs_fwd(tokens, order, inv, here):
    return _rows_of_pairs(tokens, order, inv, here), (inv, here,
                                                      tokens.shape[0])


def _rows_of_pairs_bwd(res, g):
    inv, here, n = res
    back = jnp.take(g, inv, axis=0).reshape(n, -1, g.shape[-1])
    back = jnp.where(here[..., None], back, 0)
    return jnp.sum(back, axis=1).astype(g.dtype), None, None, None


_rows_of_pairs.defvjp(_rows_of_pairs_fwd, _rows_of_pairs_bwd)


@jax.custom_vjp
def _pairs_of_rows(rows, order, inv):
    """Sorted rows [N x K, D] back in pair order: the inverse gather of
    :func:`_rows_of_pairs`, and its cotangent is the forward one."""
    return jnp.take(rows, inv, axis=0)


_pairs_of_rows.defvjp(
    lambda rows, order, inv: (jnp.take(rows, inv, axis=0), order),
    lambda order, g: (jnp.take(g, order, axis=0), None, None))


def moe_held_layer(cfg, p: Dict, x: jax.Array):
    """The sigmoid-routed layer in its training form: x [B, S, D] -> (the
    partial sum of the experts held here [B, S, D], routing stats).

    The router scores all ``cfg.routed_experts`` and chooses ``moe_top_k``
    of them a token (:func:`sigmoid_group_gate`, whose selection bias
    chooses and carries no gradient, or the plain :func:`sigmoid_gate`).
    The (token, chosen expert) pairs are sorted by expert, those of experts
    held elsewhere last; the rows are gathered once, the three products run
    over the ragged groups
    (``jax.lax.ragged_dot``: on the chip a grouped matrix product whose work
    follows the rows in the groups), and every pair reads its row back with
    its weight. The buffer holds every pair, so no token is dropped whatever
    the imbalance, and nothing but the products depends on how many rows
    are held. Backward reaches the router through the weights; the choice
    carries no gradient. The shared expert is the caller's.

    Stats: ``counts`` [routed experts] float32, the tokens that chose each
    expert of the layer (what moves the selection bias); ``held`` [experts
    held], those of the experts computed here."""
    B, S, D = x.shape
    N, K, E = B * S, cfg.moe_top_k, cfg.num_experts
    R, first = cfg.routed_experts, cfg.moe_first_expert
    tokens = x.reshape(N, D)
    with jax.named_scope("moe_route"):
        logits = jnp.einsum("nd,de->ne", tokens.astype(jnp.float32),
                            p["router"].astype(jnp.float32))
        idx, w = route_dropless(cfg, p, logits, train=True)
        counts = jnp.sum(idx[..., None] == jnp.arange(R), axis=(0, 1),
                         dtype=jnp.float32)
        local = idx - first
        here = (local >= 0) & (local < E)
        # pairs in token order, sorted by expert; E: held elsewhere, last
        order = jnp.argsort(jnp.where(here, local, E).reshape(-1),
                            stable=True).astype(jnp.int32)
        inv = jnp.argsort(order).astype(jnp.int32)
        held = counts[first:first + E]
        sizes = held.astype(jnp.int32)
        in_group = jnp.arange(N * K) < jnp.sum(sizes)
    with jax.named_scope("moe_experts"):
        rows = _rows_of_pairs(tokens, order, inv, here)
        h = jax.lax.ragged_dot(rows, p["wi"], sizes)
        if cfg.activation == "swiglu":
            from ..models.transformer import _swiglu

            h = _swiglu(cfg, jax.lax.ragged_dot(rows, p["wg"], sizes), h)
        else:
            h = jax.nn.gelu(h)
        # rows past the last group belong to no product: what a grouped
        # product leaves there is not read
        h = jnp.where(in_group[:, None], h, 0)
        out = jnp.where(in_group[:, None],
                        jax.lax.ragged_dot(h, p["wo"], sizes), 0)
        picked = _pairs_of_rows(out, order, inv).reshape(N, K, D)
        out = jnp.sum(picked * (w * here)[..., None].astype(x.dtype), axis=1)
    return out.reshape(B, S, D), {"counts": counts, "held": held}


def eval_capacity(cfg, n_tokens: int) -> int:
    """Per-expert capacity at inference for a program that feeds at most
    ``n_tokens`` real tokens: ``max(4, ceil(max(capacity_factor, 2.0) ·
    top_k · n_tokens / E))`` — the reference TopKGate eval rule. STATIC
    given static shapes, which is what keeps the serving step at one
    compile: the slot engine passes its token budget W (the scheduler
    never packs more than W real tokens per step), so occupancy changes
    never change capacity. No-drop guarantee: with
    ``max(capacity_factor, 2.0) · top_k >= E`` even the adversarial
    all-tokens-to-one-expert step fits, and per-token routing becomes
    independent of batch composition (the spec-on == spec-off and
    serving == generate parities for MoE need exactly that)."""
    cap_factor = max(cfg.moe_capacity_factor, 2.0)
    return max(4, int(math.ceil(cap_factor * cfg.moe_top_k * n_tokens
                                / cfg.num_experts)))


def _expert_proj(x: jax.Array, w) -> jax.Array:
    """Batched per-expert projection x[E, C, d] @ w[E, d, n] → [E, C, n].

    Dense expert banks take the plain einsum. PackedWeight banks
    (weight-only int8/int4 expert weights, [L, E, d, n] packed by the
    inference engine) stream through the Pallas matvec per expert
    (ops/pallas/quantized_matmul.packed_expert_proj — per-shard under a
    full-manual shard_map when the bank is ep/tp-sharded, the PR-3 tp
    path applied to experts) when the row count fits the streaming
    threshold; larger shapes dequantize once and ride the MXU."""
    from ..ops.quantizer import PackedWeight

    if isinstance(w, PackedWeight):
        from ..ops.pallas.quantized_matmul import packed_expert_proj

        y = packed_expert_proj(x, w)
        if y is not None:
            return y
        return jnp.einsum("ecd,edf->ecf", x, w.dequantize())
    return jnp.einsum("ecd,edf->ecf", x, w)


def _expert_ffn(cfg, p: Dict, expert_in: jax.Array) -> jax.Array:
    """The expert FFN stack on [E, C, D] capacity rows — ONE
    implementation shared by the training layer, the serving routed path
    and (structurally mirrored) the decode a2a ring, so the paths cannot
    diverge. Handles PackedWeight expert banks via :func:`_expert_proj`."""
    h = _expert_proj(expert_in, p["wi"])
    if cfg.activation == "swiglu":
        from ..models.transformer import _swiglu

        h = _swiglu(cfg, _expert_proj(expert_in, p["wg"]), h)
    else:
        h = jax.nn.gelu(h)
    h = constrain(h, "ep", None, "tp")
    expert_out = _expert_proj(h, p["wo"])
    return constrain(expert_out, "ep", None, None)


def _residual_mix(cfg, p: Dict, x: jax.Array, out: jax.Array) -> jax.Array:
    """Residual/PR-MoE (reference: deepspeed/moe/layer.py use_residual):
    a dense MLP runs on every token and a learned per-token 2-way
    softmax coefficient mixes dense vs routed outputs — the routed
    branch acts as a correction on top of the always-on dense expert.
    ONE implementation shared by the training layer and the serving
    path, so the mixes cannot diverge."""
    h = jnp.einsum("bsd,df->bsf", x, p["res_wi"])
    if cfg.activation == "swiglu":
        h = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, p["res_wg"])) * h
    else:
        h = jax.nn.gelu(h)
    h = constrain(h, ("dp", "fsdp"), "sp", "tp")
    dense = jnp.einsum("bsf,fd->bsd", h, p["res_wo"])
    coef = jax.nn.softmax(
        jnp.einsum(
            "bsd,dc->bsc", x.astype(jnp.float32),
            p["coef"].astype(jnp.float32),
        ),
        axis=-1,
    ).astype(x.dtype)
    return dense * coef[..., 0:1] + out * coef[..., 1:2]


def _experts_packed(p: Dict) -> bool:
    """Whether this layer's expert bank is weight-only quantized packed
    storage (the a2a rings fall back to stock collectives for packed
    leaves, exactly like the PR-3 tp rings do)."""
    from ..ops.quantizer import PackedWeight

    return any(
        isinstance(p.get(k), PackedWeight) for k in ("wi", "wg", "wo")
    )


# Expected rows an expert at a full step, r = budget x top_k / routed
# experts, under which the serving layer's bank products take the kernel that
# reads the touched experts' banks alone (ops/pallas/expert_bank.py). A share
# exp(-r) of the held experts gets no row at a full step (more at a lighter
# one), and that share of the read is all the kernel can save; what it costs
# is its fixed part (the zero rows it writes, a grid step an expert). On the
# chip at Ling's bank (64 x [2560, 768] bf16, 128 capacity rows; my run,
# PR 46, PERF.md section 6) a layer's three products take 1.047 ms by the
# einsum and 0.097 + 0.01555 ms x touched experts by the kernel: 1.092 ms
# with all 64 touched, even at 61 (4.5 % untouched, r = 3.1), 0.72 ms at 40.
# Ling-3.0-flash's member reads r = 2 (13 % untouched at a full step, 78 % at
# a decode-only one) and takes the kernel; DeepSeek-V3.2's reads 4 (2 %),
# Mellum's 16 and Mixtral's 32 (none): they could only lose, and keep the
# einsum.
TOUCHED_KERNEL_MAX_ROWS = 3.0


def expert_bank_path(cfg, p: Dict, budget_tokens: int,
                     stacked: bool) -> Tuple[str, Optional[str]]:
    """Which way the serving layer's three bank products go, from what the
    trace can see: ``("touched_kernel", None)`` (``expert_bank``: the banks
    of the experts the step reached are read, no other) or ``("einsum",
    why)`` (:func:`_expert_ffn`: every held expert's)."""
    r = budget_tokens * cfg.moe_top_k / cfg.routed_experts
    if r >= TOUCHED_KERNEL_MAX_ROWS:
        return "einsum", (
            f"{r:g} rows an expert expected at a full step (the kernel is "
            f"taken under {TOUCHED_KERNEL_MAX_ROWS:g}): about "
            f"{100 * math.exp(-r):.2g} % of the held experts would go "
            "untouched")
    if _experts_packed(p):
        return "einsum", "the bank is packed (weight-only quantized)"
    if cfg.swiglu_limit:
        return "einsum", ("the SwiGLU's inputs are clamped (swiglu_limit): "
                          "the kernel's epilogue is the plain SwiGLU")
    topo = current_topology()
    if topo is not None and (topo.sizes.get("ep", 1) > 1
                             or topo.tp_size > 1):
        return "einsum", (
            f"the bank is sharded over the mesh (ep {topo.sizes.get('ep', 1)}"
            f" x tp {topo.tp_size}): a kernel's operand is whole")
    if not stacked:
        return "einsum", ("the caller hands one layer's bank, not the "
                          "stack: a kernel's slice of it would be a copy")
    if p["wi"].shape[-1] % 128 or p["wi"].shape[-2] % 128:
        return "einsum", (f"bank widths {p['wi'].shape[-2:]} are no "
                          "multiples of the 128 lanes")
    return "touched_kernel", None


def moe_layer(cfg, p: Dict, x: jax.Array, rng: Optional[jax.Array], train: bool):
    """Routed expert MLP. x: [B, S, D] → ([B, S, D], aux_loss scalar).

    Expert compute is laid out [E, C, D] and constrained to the ``ep`` axis;
    combined aux = load-balance + z-loss (coefs applied by caller/config).
    """
    B, S, D = x.shape
    E = cfg.num_experts
    N = B * S
    if train:
        capacity = max(4, int(math.ceil(cfg.moe_capacity_factor
                                        * cfg.moe_top_k * N / E)))
    else:
        capacity = eval_capacity(cfg, N)

    tokens = x.reshape(N, D)
    router_logits = jnp.einsum(
        "nd,de->ne", tokens.astype(jnp.float32), p["router"].astype(jnp.float32)
    )
    dispatch_mode = getattr(cfg, "moe_dispatch", "einsum")
    if dispatch_mode not in ("einsum", "gather"):
        # an A/B sweep typo must not silently benchmark the wrong path
        raise ValueError(
            f"moe_dispatch {dispatch_mode!r} (must be 'einsum' or 'gather')"
        )
    use_gather = dispatch_mode == "gather"
    # decomposed-a2a overlap (moe.overlap_a2a): when the scope is active
    # and shapes divide, the dispatch/combine exchanges run as chunked
    # ppermute rings whose hops hide under the per-chunk expert FFN
    # (parallel/a2a_overlap.py); the serial GSPMD path below otherwise
    ov, otopo = _a2a_overlap_active(B, S, E, p["wi"].shape[-1])
    if _experts_packed(p):
        # packed int8/int4 expert banks stream through the Pallas matvec
        # path; the decomposed ring moves dense chunks — fall back to the
        # stock exchange (the PR-3 tp-ring rule applied to experts)
        ov, otopo = None, None
    if use_gather:
        # permutation as gathers, not one-hot dots: O(N·D·K) moved bytes
        # instead of O(N·E·C·D) MXU flops each way
        tok_of_slot, slot_valid, slot_of_tok, w_of_tok, metrics = (
            top_k_gating_indices(router_logits, cfg.moe_top_k, capacity, rng,
                                 train)
        )
    else:
        dispatch, combine, metrics = top_k_gating(
            router_logits, cfg.moe_top_k, capacity, rng, train
        )
    if ov is not None:
        from ..parallel.a2a_overlap import moe_a2a_ffn

        K = cfg.moe_top_k
        gating = (
            ("gather", tok_of_slot, slot_valid,
             slot_of_tok.reshape(B, S, K), w_of_tok.reshape(B, S, K))
            if use_gather
            else ("einsum",
                  dispatch.astype(x.dtype).reshape(B, S, E, capacity),
                  combine.astype(x.dtype).reshape(B, S, E, capacity))
        )
        out = moe_a2a_ffn(
            x, gating,
            (p["wi"], p.get("wg") if cfg.activation == "swiglu" else None,
             p["wo"]),
            otopo, chunks=int(ov.chunks),
            bidirectional=bool(ov.bidirectional),
        )
    else:
        if use_gather:
            expert_in = (
                jnp.take(tokens, tok_of_slot.reshape(-1), axis=0)
                .reshape(E, capacity, D)
                * slot_valid[..., None].astype(x.dtype)
            )
        else:
            # dispatch: [N,E,C] x [N,D] -> [E,C,D], sharded over ep
            expert_in = jnp.einsum(
                "nec,nd->ecd", dispatch.astype(x.dtype), tokens
            )
        expert_in = constrain(expert_in, "ep", None, None)
        expert_out = _expert_ffn(cfg, p, expert_in)

        if use_gather:
            picked = jnp.take(
                expert_out.reshape(E * capacity, D), slot_of_tok.reshape(-1),
                axis=0,
            ).reshape(N, cfg.moe_top_k, D)
            out = jnp.sum(picked * w_of_tok[..., None].astype(x.dtype), axis=1)
        else:
            out = jnp.einsum("nec,ecd->nd", combine.astype(x.dtype), expert_out)
    aux = metrics["aux_loss"] + (cfg.moe_z_loss_coef / max(cfg.moe_aux_loss_coef, 1e-9)) * metrics["z_loss"]
    out = out.reshape(B, S, D)

    if cfg.moe_use_residual:
        out = _residual_mix(cfg, p, x, out)
    return out, aux


def moe_serving_mlp(cfg, p: Dict, x: jax.Array,
                    token_valid: Optional[jax.Array] = None,
                    budget_tokens: Optional[int] = None,
                    stack: Optional[Tuple[Dict, jax.Array]] = None):
    """Routed expert MLP for the decode/serving path (ISSUE 14):
    x [B, S, D] → (out [B, S, D], load-balance stats).

    Rows computed: the router, the placement and the combine run over the
    ``B·S`` rows of ``x`` as they come. The slot engine hands the plan's
    tokens packed to ``[1, token_budget, D]`` (``models/decoding.ChunkRows``:
    real rows first, slot after slot in chunk order, which is the order the
    valid rows of ``[max_slots, token_budget]`` had; idle rows after them,
    marked by ``token_valid``), so none of them runs over a slot's padding;
    the expert matmuls run over capacity rows from ``budget_tokens`` either
    way.

    The serving engine's contract, end to end:

    - **capacity from the static token budget** — ``budget_tokens`` is
      the most REAL tokens the caller can feed (the slot engine's
      token_budget W; ``B·S`` for the lockstep engine where every
      position is real), so :func:`eval_capacity` is static and the ONE
      ``[max_slots, token_budget]`` step never recompiles as occupancy
      changes;
    - **null-expert padding** — ``token_valid`` [B, S] marks the real
      positions; padded chunk tails, idle slots and done rows route to
      no expert at all (zero capacity, zero combine weight, no shift of
      another token's position — :func:`_gating_rounds`);
    - **slot-ragged gather dispatch** — :func:`top_k_gating_indices`
      index tables drive plain gathers (O(N·D·K) bytes), not the one-hot
      dots (O(N·E·C·D) flops of data movement — decode steps are
      latency-bound);
    - **ep-sharded experts** — the FFN runs on [E, C, D] rows
      constrained onto the ``ep`` mesh axis (stock collectives), or
      through the decode-shaped chunked-ppermute ring
      (parallel/a2a_overlap.moe_decode_a2a) when the ``a2a_scope`` is
      active and shapes divide — both produce the FULL expert-output
      tensor, so the combine below is ONE shared implementation and
      ep-sharded output is bitwise the dense-replicated output;
    - **packed int8/int4 expert weights** stream through the Pallas
      matvec (:func:`_expert_proj`); packed banks always take the stock
      exchange (the tp-ring fallback rule).
    - **the bank's path** — ``stack`` is (the layer stack's ``mlp`` tree,
      this layer's index in it) where the caller walks a stack
      (``models/mixers.cached_layers``, every served model's walk). Where a full step is expected to
      leave held experts without a row (:func:`expert_bank_path`: under
      ``TOUCHED_KERNEL_MAX_ROWS`` rows an expert, a dense unsharded bank
      of lane-wide sides, outside the ring) the three products are two
      calls of ``ops/pallas/expert_bank`` over ``stack``'s ``wi`` / ``wg``
      / ``wo`` at that index, which read the banks of the experts that
      hold a valid capacity row (``tokens_per_expert`` > 0 where nothing
      is dropped) and write zeros for the others; everywhere else :func:`_expert_ffn` over ``p``'s. Placement,
      the capacity rows and the combine are the same lines either way. The
      choice is noted for the engine (``ServingEngine.expert_path``).

    Returns ``(out, stats)`` with stats = {"tokens_per_expert" [E] i32,
    "routed_tokens" i32, "drop_fraction" f32} — the serving metrics
    counters (serving/metrics.py ``on_moe``)."""
    B, S, D = x.shape
    E = cfg.num_experts
    N = B * S
    K = cfg.moe_top_k
    if budget_tokens is None:
        budget_tokens = S if token_valid is not None else N

    tokens = x.reshape(N, D)
    valid = token_valid.reshape(N) if token_valid is not None else None
    router_logits = jnp.einsum(
        "nd,de->ne", tokens.astype(jnp.float32),
        p["router"].astype(jnp.float32),
    )
    if cfg.moe_dropless:
        # the router (either sigmoid one, or a softmax without a capacity)
        # sees every expert of the layer; the ``E`` held here compute the
        # tokens sent to them, with room for every real token (no drop),
        # and the layer returns that partial sum
        capacity = int(budget_tokens)
        idx, w = route_dropless(cfg, p, router_logits)
        tok_of_slot, slot_valid, slot_of_tok, w_of_tok, fill, unrouted = (
            held_expert_tables(idx, w, valid, cfg.moe_first_expert, E,
                               capacity))
        metrics = {"tokens_per_expert": fill,
                   "drop_fraction": jnp.zeros((), jnp.float32),
                   "unrouted_tokens": unrouted}
    else:
        capacity = eval_capacity(cfg, int(budget_tokens))
        tok_of_slot, slot_valid, slot_of_tok, w_of_tok, metrics = (
            top_k_gating_indices(router_logits, K, capacity, rng=None,
                                 train=False, valid=valid)
        )

    ring_cfg = None
    topo = current_topology()
    if topo is not None and not _experts_packed(p):
        from ..parallel.a2a_overlap import (current_a2a,
                                            moe_decode_a2a_applicable)

        ov = current_a2a()
        if ov is not None and moe_decode_a2a_applicable(
            topo, E=E, F=p["wi"].shape[-1], n_tokens=N
        ):
            ring_cfg = ov
    from ..models.decoding import _note_expert_path

    if ring_cfg is not None:
        bank_path, why = "einsum", "the decode a2a ring runs the experts"
    else:
        bank_path, why = expert_bank_path(cfg, p, int(budget_tokens),
                                          stack is not None)
    _note_expert_path(bank_path, why)
    if ring_cfg is not None:
        # the chunked-ppermute decode ring runs dispatch + FFN + combine
        # per ep member (each member emits its own token block, the
        # stock combine expression verbatim — bitwise the stock path)
        from ..parallel.a2a_overlap import moe_decode_a2a

        out = moe_decode_a2a(
            tokens, tok_of_slot, slot_valid, slot_of_tok, w_of_tok,
            (p["wi"], p.get("wg") if cfg.activation == "swiglu" else None,
             p["wo"]),
            topo, chunks=int(ring_cfg.chunks),
            bidirectional=bool(ring_cfg.bidirectional),
        )
    else:
        expert_in = (
            jnp.take(tokens, tok_of_slot.reshape(-1), axis=0)
            .reshape(E, capacity, D)
            * slot_valid[..., None].astype(x.dtype)
        )
        if bank_path == "touched_kernel":
            from ..ops.pallas.expert_bank import expert_bank

            banks, at = stack
            # an expert's rows here: tokens_per_expert where none is dropped
            fill = jnp.sum(slot_valid, axis=1, dtype=jnp.int32)
            swiglu = cfg.activation == "swiglu"  # or GELU: _expert_ffn's
            h = expert_bank(expert_in, banks["wi"], at, fill, gelu=not swiglu,
                            gate=banks["wg"] if swiglu else None)
            expert_out = expert_bank(h, banks["wo"], at, fill)
        else:
            expert_in = constrain(expert_in, "ep", None, None)
            expert_out = _expert_ffn(cfg, p, expert_in)
        # combine: dropped/invalid tokens carry w == 0, so their slot-0
        # fallback gather contributes exact zeros
        picked = jnp.take(
            expert_out.reshape(E * capacity, D), slot_of_tok.reshape(-1),
            axis=0,
        ).reshape(N, K, D)
        out = jnp.sum(picked * w_of_tok[..., None].astype(x.dtype), axis=1)
    out = out.reshape(B, S, D)

    if cfg.moe_use_residual:
        out = _residual_mix(cfg, p, x, out)
    if cfg.moe_shared_width:
        from ..models.transformer import _mlp

        # the shared expert: a dense MLP every token takes, beside the routed
        shared = _mlp(cfg, p["shared"], x, None, False, dense=True)[0]
        if "shared_gate" in p:  # times sigmoid(x w_sg), one value a token
            shared = (shared.astype(jnp.float32) * jax.nn.sigmoid(jnp.einsum(
                "bsd,do->bso", x, p["shared_gate"],
                preferred_element_type=jnp.float32))).astype(shared.dtype)
        out = out + shared

    # routed_tokens stays derivable (tokens_per_expert.sum()) — the
    # metrics layer re-derives it, so the step ships no redundant scalar
    stats = {
        "tokens_per_expert": metrics["tokens_per_expert"],
        "drop_fraction": metrics["drop_fraction"],
    }
    if "unrouted_tokens" in metrics:
        stats["unrouted_tokens"] = metrics["unrouted_tokens"]
    return out, stats
