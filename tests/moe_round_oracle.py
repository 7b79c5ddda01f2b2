"""The oracle of the routed layers' placement (``moe/sharded_moe.py``
``_place_pairs`` / ``_slot_tables``): the round-by-round loop the layer ran
until PR 40, one cumulative sum over the rows and one scatter pair a round,
so there is neither a triangular product, a row block nor a fill recurrence
to get wrong. Used by tests/test_moe.py."""

import jax
import jax.numpy as jnp


def gating_rounds(logits, top_k, capacity, valid=None):
    """Softmax gate, eval form: the K ``(idx, pos_tok, keep, gate_val)``
    rounds and the metrics."""
    N, E = logits.shape
    if valid is not None:
        logits = jnp.where(valid[:, None], logits, 0.0)
    gates = jax.nn.softmax(logits, axis=-1)
    fill = jnp.zeros((E,), jnp.int32)
    masked_gates = gates
    me = jnp.mean(gates, axis=0)
    ce_acc = jnp.zeros((E,), jnp.float32)
    rounds = []
    kept_total = jnp.zeros((), jnp.float32)
    for _ in range(top_k):
        idx = jnp.argmax(masked_gates, axis=-1)
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)
        if valid is not None:
            onehot = onehot * valid[:, None].astype(onehot.dtype)
        pos_in_round = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot
        pos = pos_in_round + fill[None, :] * onehot
        pos_tok = jnp.sum(pos * onehot, axis=-1).astype(jnp.int32)
        keep = pos_tok < capacity
        if valid is not None:
            keep = keep & valid
        gate_val = jnp.sum(gates * onehot, axis=-1)
        rounds.append((idx, pos_tok, keep, gate_val))
        fill = fill + jnp.sum(onehot * keep[:, None], axis=0).astype(jnp.int32)
        ce_acc = ce_acc + jnp.mean(onehot, axis=0)
        kept_total = kept_total + jnp.sum(keep.astype(jnp.float32))
        masked_gates = masked_gates * (1.0 - onehot)
    n_routed = (jnp.sum(valid.astype(jnp.float32)) if valid is not None
                else jnp.asarray(float(N)))
    metrics = {
        "aux_loss": E * jnp.sum(me * (ce_acc / top_k)),
        "z_loss": jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1))),
        "drop_fraction": jnp.where(
            n_routed > 0,
            1.0 - kept_total / jnp.maximum(n_routed * top_k, 1.0), 0.0),
        "tokens_per_expert": fill,
        "routed_tokens": kept_total.astype(jnp.int32),
    }
    return rounds, metrics


def top_k_gating(logits, top_k, capacity, valid=None):
    """(dispatch [N, E, C], combine [N, E, C], metrics) of the einsum path."""
    N, E = logits.shape
    rounds, metrics = gating_rounds(logits, top_k, capacity, valid)
    combine = jnp.zeros((N, E, capacity), jnp.float32)
    dispatch = jnp.zeros((N, E, capacity), jnp.bool_)
    for idx, pos_tok, keep, gate_val in rounds:
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)
        pos_oh = jax.nn.one_hot(
            jnp.where(keep, pos_tok, capacity), capacity + 1)[:, :capacity]
        contrib = onehot[:, :, None] * pos_oh[:, None, :]
        combine = (combine
                   + contrib * gate_val[:, None, None] * keep[:, None, None])
        dispatch = dispatch | (contrib > 0) & keep[:, None, None]
    denom = jnp.sum(combine, axis=(1, 2), keepdims=True)
    combine = jnp.where(denom > 0, combine / jnp.maximum(denom, 1e-9), combine)
    return dispatch.astype(jnp.float32), combine, metrics


def top_k_gating_indices(logits, top_k, capacity, valid=None):
    """(tok_of_slot, slot_valid, slot_of_tok, w_of_tok, metrics) of the
    gather path: two scatters a round."""
    N, E = logits.shape
    rounds, metrics = gating_rounds(logits, top_k, capacity, valid)
    tok_flat = jnp.zeros((E * capacity + 1,), jnp.int32)
    valid_flat = jnp.zeros((E * capacity + 1,), jnp.bool_)
    slot_of_tok, w_raw = [], []
    arange_n = jnp.arange(N, dtype=jnp.int32)
    for idx, pos_tok, keep, gate_val in rounds:
        flat = idx * capacity + jnp.minimum(pos_tok, capacity - 1)
        target = jnp.where(keep, flat, E * capacity)
        tok_flat = tok_flat.at[target].set(arange_n)
        valid_flat = valid_flat.at[target].set(True)
        slot_of_tok.append(jnp.where(keep, flat, 0))
        w_raw.append(gate_val * keep)
    w = jnp.stack(w_raw, axis=1)
    denom = jnp.sum(w, axis=1, keepdims=True)
    w = jnp.where(denom > 0, w / jnp.maximum(denom, 1e-9), w)
    return (tok_flat[:-1].reshape(E, capacity),
            valid_flat[:-1].reshape(E, capacity),
            jnp.stack(slot_of_tok, axis=1), w, metrics)


def held_expert_tables(idx, w, valid, first, held, capacity):
    """The sigmoid gate's placement of one member's ``held`` experts:
    (tok_of_slot, slot_valid, slot_of_tok, w_of_tok, tokens per held
    expert, unrouted real tokens)."""
    N, K = idx.shape
    local = idx - first
    here = (local >= 0) & (local < held)
    if valid is not None:
        here = here & valid[:, None]
    tok_flat = jnp.zeros((held * capacity + 1,), jnp.int32)
    valid_flat = jnp.zeros((held * capacity + 1,), jnp.bool_)
    fill = jnp.zeros((held,), jnp.int32)
    slots = []
    arange_n = jnp.arange(N, dtype=jnp.int32)
    for k in range(K):
        onehot = jax.nn.one_hot(local[:, k], held, dtype=jnp.int32) * (
            here[:, k, None].astype(jnp.int32))
        pos = jnp.sum((jnp.cumsum(onehot, axis=0) - 1 + fill[None, :])
                      * onehot, axis=-1)
        keep = here[:, k] & (pos < capacity)
        flat = jnp.clip(local[:, k], 0, held - 1) * capacity + jnp.minimum(
            pos, capacity - 1)
        target = jnp.where(keep, flat, held * capacity)
        tok_flat = tok_flat.at[target].set(arange_n)
        valid_flat = valid_flat.at[target].set(True)
        slots.append(jnp.where(keep, flat, 0))
        fill = fill + jnp.sum(onehot, axis=0)
        here = here.at[:, k].set(keep)
    real = jnp.ones((N,), bool) if valid is None else valid
    unrouted = jnp.sum((real & ~jnp.any(here, axis=1)).astype(jnp.int32))
    return (tok_flat[:-1].reshape(held, capacity),
            valid_flat[:-1].reshape(held, capacity),
            jnp.stack(slots, axis=1), w * here, fill, unrouted)
