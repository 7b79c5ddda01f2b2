"""Speculative decoding for the serving engines — ONE implementation.

Parity: the DeepSpeed serving stack's speculative path (draft-then-verify
with a cheap proposer and a single verifier forward per window). Two
engines consume this module:

- the **lockstep** engine (inference/engine.py ``_build_spec_decode``):
  B=1 greedy, the whole draft/verify loop inside one jitted
  ``lax.while_loop`` — it calls :func:`ngram_propose`,
  :func:`longest_accepted_prefix` and :func:`clamp_advance_at_eos` from
  its traced body;
- the **slot** engine (serving/engine.py): batched-ragged spec over the
  continuous-batching step. Draft proposal runs HOST-side per decode
  slot (:func:`propose_drafts` over the slot's committed token buffer),
  the existing ONE jitted ``[max_slots, token_budget]`` step verifies
  every slot's window at once (:func:`verify_window` — each spec slot's
  row carries its committed token + up to ``k`` drafts, so a spec slot
  consumes ``k+1`` budget rows), and acceptance advances the per-slot
  frontier by ``n_accepted + 1`` tokens per step.

Losslessness — the oracle the tests assert: acceptance is
**sample-and-match** against the slot's own deterministic RNG chain.
For window position ``j`` the verifier samples exactly the token the
spec-OFF engine would have sampled there (same logits — the conditioning
prefix matched — same chain key ``j``), and a draft is accepted only
when it EQUALS that token. Emitted tokens are therefore bit-identical to
the spec-off run for greedy AND sampled-with-shared-keys; drafts only
change how many verifier steps the generation needs, never its content.
(This is stricter than Leviathan/Chen modified rejection sampling, which
is lossless in distribution but not token-for-token; the serving
engine's contract since PR 5 is bitwise reproducibility, so the stricter
rule is the only admissible one.)

Cache discipline: a verify window writes K/V for its drafts at
positions ``frontier+1 .. frontier+k``. Rejected drafts leave garbage
there, which is dead by the frontier invariant (docs/serving.md): a
later query at position ``q`` only attends ``kpos <= q``, and every
position in ``[frontier', q]`` is rewritten by that query's own step
before it can be attended. Under the paged arena the pages backing a
rejected window stay owned by the slot (refcounted — ``free + live ==
num_pages`` keeps holding) and are simply rewritten as the frontier
catches up; rollback never frees or leaks a page.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

__all__ = [
    "ngram_propose",
    "propose_drafts",
    "longest_accepted_prefix",
    "clamp_advance_at_eos",
    "advance_rng",
    "verify_window",
    "spec_verify_stream",
]


# --------------------------------------------------------------- proposing
def ngram_propose(buf, pos, k: int, n: int):
    """n-gram / prompt-lookup draft: propose ``k`` tokens for positions
    ``pos+1 .. pos+k`` of a ``[T]`` token buffer.

    The most recent earlier occurrence of the trailing ``n`` tokens at
    ``pos`` supplies the continuation (prompt-lookup decoding — zero
    parameters, a few VPU ops). With no match, the slice past ``pos``
    is returned instead: the lockstep engine keeps stale verifier
    predictions there, the slot engine appends the previous window's
    rejected targets (``RequestState.draft_tail``) — free, plausible
    proposals either way.

    Works traced (the lockstep jitted body: ``pos`` is a traced scalar)
    and host-side (the slot scheduler calls it per decode slot with
    concrete numpy inputs — that path runs pure NumPy, no device
    dispatch on the scheduling hot loop; SAME algorithm, the backends
    only differ in the final slice primitive). ``buf`` must have length
    >= pos + 1 + k so the fallback slice stays in bounds. The roll is
    safe: the ``idx >= n - 1`` guard keeps every compared index
    in-bounds, no wraparound match.
    """
    host = isinstance(buf, np.ndarray) and isinstance(pos, (int, np.integer))
    xp = np if host else jnp
    buf = xp.asarray(buf).astype(xp.int32)
    idx = xp.arange(buf.shape[0])
    match = (idx >= n - 1) & (idx < pos)
    for t in range(n):
        match &= xp.roll(buf, t) == xp.take(buf, pos - t)
    e = xp.max(xp.where(match, idx, -1))
    start = xp.where(e >= 0, e + 1, pos + 1)
    if host:
        start = int(start)
        return buf[start: start + k]
    return lax.dynamic_slice(buf, (start,), (k,))


def propose_drafts(prompt: Sequence[int], tokens: Sequence[int],
                   draft_tail: Sequence[int], k: int, n: int) -> np.ndarray:
    """Host-side draft proposal for one decode slot: ``k`` int tokens for
    the positions after the slot's last committed token.

    The lookup buffer is the committed stream (prompt + generated tokens,
    the last of which is the token this step feeds) with the previous
    verify's rejected targets appended as the no-match fallback run —
    exactly the lockstep buffer layout, through exactly the same
    :func:`ngram_propose`."""
    committed = np.concatenate([
        np.asarray(prompt, np.int32).reshape(-1),
        np.asarray(tokens, np.int32).reshape(-1),
    ])
    pos = int(committed.size - 1)
    tail = np.asarray(list(draft_tail), np.int32)
    pad = max(pos + 1 + k - (committed.size + tail.size), 0)
    buf = np.concatenate([committed, tail, np.zeros(pad, np.int32)])
    return np.asarray(ngram_propose(buf, pos, k, n), np.int32)


# -------------------------------------------------------------- acceptance
def longest_accepted_prefix(match):
    """Accepted-draft count from a ``[..., k]`` bool match vector: the
    length of the leading all-True run (a draft is only conditioned
    correctly when every draft before it was accepted)."""
    return jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=-1), axis=-1)


def clamp_advance_at_eos(targets, adv, eos_id):
    """Clamp a window advance at the first emitted eos: of the ``adv``
    tokens about to be emitted from ``targets [..., k]``, an eos at
    emitted index ``j`` cuts the advance to ``j + 1`` (the eos itself is
    emitted, nothing after it). Returns ``(adv, has_eos)``; ``eos_id``
    may be -1 (no eos — token ids are non-negative, nothing matches).
    Batched (``targets [N, k]``, ``adv``/``eos_id`` ``[N]``) and scalar
    (the lockstep body) forms share this one definition."""
    targets = jnp.asarray(targets)
    k = targets.shape[-1]
    adv_b = jnp.asarray(adv)[..., None]
    eos_b = jnp.asarray(eos_id)[..., None]
    acc = jnp.arange(k) < adv_b
    is_eos = (targets == eos_b) & acc
    has_eos = jnp.any(is_eos, axis=-1)
    adv = jnp.where(has_eos, jnp.argmax(is_eos, axis=-1) + 1,
                    jnp.asarray(adv))
    return adv, has_eos


# ------------------------------------------------------- the verify window
def advance_rng(key, flag):
    """One per-slot RNG chain advance: split ONLY when ``flag`` (the slot
    samples), mirroring the lockstep engine's chain. Returns
    ``(sample_key, next_chain)`` — both equal to ``key`` when gated."""
    pair = jax.random.split(key)  # [2, 2]: (sample key, next chain)
    use = jnp.broadcast_to(flag, key.shape)
    return (jnp.where(use, pair[0], key),
            jnp.where(use, pair[1], key))


def verify_window(sample_window, logits, tokens, rows, seen, spec_len, live,
                  rng, temperature, top_k, top_p, rep_penalty, eos_id):
    """Batched-ragged verification inside the ONE jitted serving step.

    Every live slot's row ends with a verify window: its committed token
    followed by ``spec_len`` drafts (``spec_len = 0`` is plain decode /
    the final prefill feed — bitwise the pre-spec sampling tail). For
    each of the ``spec_len + 1`` window positions this samples the
    target token with the slot's advancing RNG chain (position ``j``
    uses chain key ``j`` — exactly the key the spec-off engine would
    burn on that token), accepts the longest draft prefix that matches
    the targets, clamps the advance at an emitted eos, and restores the
    chain to the state after exactly ``n_emit`` advances.

    Shapes (N = max_slots, W = token_budget, Kw = max_draft + 1):
      logits [N, Kw, V] — the window's rows alone, as the forward computed
      them for ``rows`` [N, Kw] (models/decoding.verify_window_rows),
      tokens [N, W], seen [N, V],
      spec_len/eos_id [N] i32, live [N] bool, rng [N, 2] u32,
      temperature/top_p/rep_penalty [N] f32, top_k [N] i32.
    ``sample_window(win [N, Kw, V], keys [N, Kw, 2], live, temperature,
    top_k, top_p) -> [N, Kw]`` is the engine's sampler.

    Returns ``(out_tokens [N, Kw] i32, n_emit [N] i32, new_rng [N, 2])``
    — ``out_tokens[:, :n_emit]`` are the slot's emitted tokens this
    step; ``n_emit`` is 0 for non-sampling rows. Kw is STATIC (the
    step's fixed output shape); ``spec_len`` is traced, so any
    per-slot/per-step draft count runs the same compiled program.
    """
    from ..inference.engine import apply_repetition_penalty

    kw = rows.shape[1]
    max_draft = kw - 1
    # repetition penalty over the whole window with the pre-forward seen
    # matrix. Spec rows are penalty == 1.0 by the scheduler gate (the
    # seen matrix is built from FED tokens and spec-accepted tokens are
    # never re-fed — same reasoning as the prefix-cache bypass), so the
    # penalty math is bitwise identity there; spec_len == 0 rows take
    # exactly the pre-spec single-position path.
    win = apply_repetition_penalty(
        logits, seen, rep_penalty[:, None, None], active=live
    )
    # the RNG chain, advanced kw times (live rows only): chains[j] is the
    # state after j advances, keys[j] the sample key position j uses.
    # n_emit <= spec_len + 1 restores the chain to chains[n_emit], so
    # keys past the emitted run are never consumed — the next step's
    # first sample reuses exactly the key spec-off would.
    chains = [rng]
    keys = []
    for _ in range(kw):
        key_j, nxt = jax.vmap(advance_rng)(chains[-1], live)
        chains.append(nxt)
        keys.append(key_j)
    out_tokens = sample_window(
        win, jnp.stack(keys, axis=1), live, temperature, top_k, top_p
    ).astype(jnp.int32)  # [N, kw]
    # drafts ride in the row right after the committed token: window
    # position j's draft is the token at the window's row j + 1
    drafts = jnp.take_along_axis(tokens, rows[:, 1:], axis=1)
    in_window = jnp.arange(max_draft)[None, :] < spec_len[:, None]
    match = (drafts == out_tokens[:, :max_draft]) & in_window
    n_acc = longest_accepted_prefix(match)
    adv, _ = clamp_advance_at_eos(out_tokens, n_acc + 1, eos_id)
    n_emit = jnp.where(live, adv, 0).astype(jnp.int32)
    chain_stack = jnp.stack(chains, axis=1)  # [N, kw + 1, 2]
    new_rng = jnp.take_along_axis(
        chain_stack, n_emit[:, None, None].astype(jnp.int32), axis=1
    )[:, 0]
    return out_tokens, n_emit, new_rng


# ------------------------------------------------------- planner metadata
def spec_verify_stream(cfg, max_slots: int, max_draft: int,
                       storage_itemsize: int, quantized: bool,
                       tp: int = 1) -> Dict[str, Any]:
    """Analytic per-step HBM traffic the verify windows ADD to the
    serving step, in the shared analytic-streams schema
    (comm_logger.record_streams / cost planner / rule R8). Upper bound at
    full draft occupancy: every slot's ``max_draft`` draft rows write
    K/V at every layer and are re-read by the window logits gather
    ``[N, max_draft + 1, V]`` (fp32). The bulk arena traffic itself is
    already priced by the ``kv_cache`` stream — this entry prices what
    turning spec ON costs on top, so shardplan sees the verify-window
    bytes statically."""
    from ..models.decoding import SCALE_LANES

    per_tok = cfg.kv_heads * cfg.hd * (1 if quantized else storage_itemsize)
    scale_tok = SCALE_LANES * 4 if quantized else 0
    draft_tokens = cfg.num_layers * max_slots * max_draft
    kv = draft_tokens * (per_tok + scale_tok) * 2  # k + v write + re-read
    window_logits = max_slots * (max_draft + 1) * cfg.vocab_size * 4
    total = kv + window_logits
    return {
        "kind": "hbm",
        "bytes_per_step": total,
        "per_device_bytes_per_step": total // max(tp, 1),
        "overlapped": False,  # part of the step's own compute traffic
        "spec": True,
        "max_draft": max_draft,
        "slots": max_slots,
        "quantized": quantized,
    }
