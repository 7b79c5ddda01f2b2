"""Decomposed MoE all-to-all: overlap the expert exchange with the FFN.

moe/sharded_moe.py's GShard formulation builds [E, C, D] dispatch/combine
tensors and lets GSPMD insert whatever collective moves them onto the
``ep`` axis — one monolithic exchange that the expert FFN must wait out,
and (token batch replicated over ep) redundant dispatch compute on every
ep member. This module is the explicit schedule (The Big Send-off's
decomposed-collective treatment, the same move PR 3 made for the TP
projections): the token batch shards its sequence over ``(sp, ep)``, the
dispatch and combine exchanges decompose into chunked ``ppermute`` hops
on the ep ring, and each expert shard starts its FFN matmuls the moment
a capacity chunk lands instead of waiting for the whole [E, C, D]
tensor. With ``chunks > 1`` capacity chunks pipeline against each other:
chunk k+1's hops fly under chunk k's expert matmuls, and chunk k's
combine ride-back hides under chunk k+1's FFN (XLA's latency-hiding
scheduler overlaps the independent ``collective-permute-start``/``-done``
pairs with the dots, exactly as in parallel/tensor_overlap.py).

Ring structure, per capacity chunk:

- *dispatch* — each member computes, from its LOCAL tokens, the partial
  [E_loc, C_chunk, D] contribution to every expert block; partials
  destined for block j ride the forward ring accumulating per hop
  (slots are filled by exactly one token, so the "reduction" merges
  disjoint support — bitwise-safe in any order). Contributions from the
  dp/fsdp/sp token shards fold in with one psum per completed chunk.
- *FFN* — the landed chunk's expert matmuls run locally (wi/wg/wo are
  ep×tp sharded exactly like the serial path); the tp contraction psums.
- *combine* — each member's expert-output chunk rides the ring the other
  way; every member folds each arriving block into its local tokens'
  outputs (one combine einsum per block per chunk, accumulated in pinned
  ring order so the reference can mirror it bitwise).

``bidirectional=True`` splits each capacity chunk in half and rides the
halves around both ring directions simultaneously (full-duplex ICI:
half the wire time per hop, same hop count). ``reference=True`` is the
pure-XLA path — stock ``all_to_all``/``all_gather`` wires around the
SAME local loop structure and accumulation order, so ring == reference
is BITWISE on CPU meshes for both dispatch modes (the oracle
tests/test_moe_a2a_overlap.py pins; for ``top_k > 2`` the per-chunk
grouping of a token's combine terms is still shared by both paths).

Everything here is a FULL-manual ``shard_map`` over the whole mesh
and every hop goes through
:func:`deepspeed_tpu.comm.collectives.permute`, so the shardlint R3
ring contract is enforced at construction time and the comms logger
sees every hop's bytes.

Model wiring rides :func:`a2a_scope` (trace-time, the
tensor_overlap.overlap_scope protocol): the engine enters it from the
``moe.overlap_a2a`` config section and ``moe_layer`` dispatches through
:func:`moe_a2a_ffn`, falling back to the serial GSPMD path whenever the
scope is off, shapes don't divide, or tracing already sits inside a
manual shard_map (the pipeline schedule).
"""

from __future__ import annotations

import contextlib
import math
import threading
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..comm import collectives
from ..models.sharding import current_topology, manual_axis_names
from .tensor_overlap import _row_chunks, _shard_map_full

__all__ = [
    "a2a_scope",
    "current_a2a",
    "moe_a2a_ffn",
    "moe_a2a_applicable",
    "moe_a2a_bytes_per_step",
    "moe_decode_a2a",
    "moe_decode_a2a_applicable",
    "moe_decode_a2a_bytes_per_step",
]


# --------------------------------------------------------------------- scope
_local = threading.local()


def current_a2a():
    """The active moe.overlap_a2a config (None when off)."""
    cfg = getattr(_local, "a2a", None)
    if cfg is not None and getattr(cfg, "enabled", False):
        return cfg
    return None


@contextlib.contextmanager
def a2a_scope(cfg):
    """Trace-time activation of the decomposed MoE all-to-all (scoped like
    tensor_overlap.overlap_scope: engines with different configs in one
    process don't fight). ``cfg`` is a ``moe.overlap_a2a`` section
    (anything with .enabled/.chunks/.bidirectional) or None to keep the
    current setting."""
    prev = getattr(_local, "a2a", None)
    if cfg is not None:
        _local.a2a = cfg
    try:
        yield
    finally:
        _local.a2a = prev


# ------------------------------------------------------------ ring plumbing
def _ring_perms(ep: int) -> Tuple[list, list]:
    """(forward, backward) full-ring permutations — single full cycles,
    the exact shape shardlint R3 certifies as hang-free."""
    fwd = [(i, (i + 1) % ep) for i in range(ep)]
    bwd = [(i, (i - 1) % ep) for i in range(ep)]
    return fwd, bwd


def _hop(x, axis, perm):
    """One validated, comms-logged ring hop."""
    return collectives.permute(x, axis, perm)


def _pos(axes, sizes) -> jax.Array:
    """Flattened member index over ``axes`` in spec order (major→minor) —
    how a P((a, b)) entry lays blocks out on the mesh."""
    idx = jnp.zeros((), jnp.int32)
    for a in axes:
        idx = idx * sizes[a] + lax.axis_index(a).astype(jnp.int32)
    return idx


# ----------------------------------------------------- per-mode local kernels
def _einsum_fns(tokens, disp, comb, E_loc: int):
    """(part, contrib) closures for the one-hot "einsum" dispatch mode.

    part(blk, c0, lo, w): this member's tokens' contribution to expert
    block ``blk``'s capacity columns [c0+lo, c0+lo+w) — [E_loc, w, D].
    contrib(blk, c0, lo, w, buf): fold the arrived expert-output chunk
    ``buf`` for that block/column range into the local tokens — [n, D].
    ``blk`` is traced (ring arithmetic on axis_index); columns static."""
    n = tokens.shape[0]

    def part(blk, c0, lo, w):
        d = lax.dynamic_slice(
            disp, (0, blk * E_loc, c0 + lo), (n, E_loc, w)
        )
        return jnp.einsum("nec,nd->ecd", d, tokens)

    def contrib(blk, c0, lo, w, buf):
        c = lax.dynamic_slice(
            comb, (0, blk * E_loc, c0 + lo), (n, E_loc, w)
        )
        return jnp.einsum("nec,ecd->nd", c, buf)

    return part, contrib


def _gather_fns(tokens, tok_of_slot, slot_valid, slot_of_tok, w_of_tok,
                E_loc: int, C: int, S: int, S_loc: int, B_loc: int,
                b0, s0):
    """(part, contrib) closures for the index-table "gather" dispatch mode.

    Each member owns the tokens of its (batch, sequence) block; slot
    tables are global, so ownership is a mask: a slot's token belongs
    here iff its (b, s) coordinates fall in this member's block. Writes
    for unowned/dropped slots are exact zeros — the ring's disjoint-
    support merge absorbs them bitwise (the serial gather path's
    ``* slot_valid`` mask makes the same zeros)."""
    n = tokens.shape[0]
    D = tokens.shape[-1]

    def part(blk, c0, lo, w):
        t = lax.dynamic_slice(tok_of_slot, (blk * E_loc, c0 + lo),
                              (E_loc, w))
        v = lax.dynamic_slice(slot_valid, (blk * E_loc, c0 + lo),
                              (E_loc, w))
        bg, sg = t // S, t % S
        owned = (
            v
            & (bg >= b0) & (bg < b0 + B_loc)
            & (sg >= s0) & (sg < s0 + S_loc)
        )
        lidx = (bg - b0) * S_loc + (sg - s0)
        rows = jnp.take(
            tokens, jnp.clip(lidx, 0, n - 1).reshape(-1), axis=0
        ).reshape(E_loc, w, D)
        return jnp.where(owned[..., None], rows,
                         jnp.zeros((), tokens.dtype))

    def contrib(blk, c0, lo, w, buf):
        flat = buf.reshape(E_loc * w, D)
        e = slot_of_tok // C  # [n, K]
        c = slot_of_tok % C
        inb = (
            (e >= blk * E_loc) & (e < (blk + 1) * E_loc)
            & (c >= c0 + lo) & (c < c0 + lo + w)
        )
        li = jnp.clip(
            (e - blk * E_loc) * w + (c - c0 - lo), 0, E_loc * w - 1
        )
        out = jnp.zeros((n, D), tokens.dtype)
        for k in range(slot_of_tok.shape[1]):
            picked = jnp.take(flat, li[:, k], axis=0)
            out = out + jnp.where(
                inb[:, k:k + 1],
                w_of_tok[:, k:k + 1].astype(tokens.dtype) * picked,
                jnp.zeros((), tokens.dtype),
            )
        return out

    return part, contrib


# ----------------------------------------------------------- the ring bodies
def _dispatch_reduce_ring(part, i, c0, cw, *, axis, ep, bidirectional):
    """Complete expert chunk for MY block: partials ride the ring and
    accumulate per hop (source order i+1, …, i-1, i — the pinned order
    the reference mirrors). Returns [E_loc, cw, D]."""
    fwd, bwd = _ring_perms(ep)
    if not bidirectional or cw < 2:
        acc = part((i - 1) % ep, c0, 0, cw)
        for s in range(1, ep):
            acc = _hop(acc, axis, fwd)
            acc = acc + part((i - 1 - s) % ep, c0, 0, cw)
        return acc
    wa = cw - cw // 2
    wb = cw - wa
    acc_a = part((i - 1) % ep, c0, 0, wa)
    acc_b = part((i + 1) % ep, c0, wa, wb)
    for s in range(1, ep):
        acc_a = _hop(acc_a, axis, fwd)
        acc_b = _hop(acc_b, axis, bwd)
        acc_a = acc_a + part((i - 1 - s) % ep, c0, 0, wa)
        acc_b = acc_b + part((i + 1 + s) % ep, c0, wa, wb)
    return jnp.concatenate([acc_a, acc_b], axis=1)


def _combine_gather_ring(contrib, out, eo, i, c0, cw, *, axis, ep,
                         bidirectional):
    """Ride each member's expert-output chunk around the ring; every
    member folds each arriving block into its local tokens (arrival
    order i, i-1, … for the forward stream — pinned, mirrored by the
    reference). Returns the accumulated [n, D]."""
    fwd, bwd = _ring_perms(ep)
    if not bidirectional or cw < 2:
        buf = eo
        for s in range(ep):
            out = out + contrib((i - s) % ep, c0, 0, cw, buf)
            if s < ep - 1:
                buf = _hop(buf, axis, fwd)
        return out
    wa = cw - cw // 2
    wb = cw - wa
    buf_a, buf_b = eo[:, :wa], eo[:, wa:]
    for s in range(ep):
        out = out + contrib((i - s) % ep, c0, 0, wa, buf_a)
        out = out + contrib((i + s) % ep, c0, wa, wb, buf_b)
        if s < ep - 1:
            buf_a = _hop(buf_a, axis, fwd)
            buf_b = _hop(buf_b, axis, bwd)
    return out


def _ref_dispatch(part, i, c0, cw, *, axis, ep, bidirectional):
    """Stock-collective dispatch exchange accumulating in the SAME order
    as the ring (qgZ-style all-to-all + pinned local reduction), so ring
    == reference bitwise even though slot support is disjoint anyway."""
    def stack_parts(lo, w):
        blocks = [part(jnp.int32(j), c0, lo, w) for j in range(ep)]
        stacked = jnp.stack(blocks)  # by DESTINATION block
        # gathered[j] = source j's partial for MY block
        return collectives.all_to_all(stacked, axis, 0, 0, tiled=False)

    def dyn(g, j):
        return lax.dynamic_index_in_dim(g, j % ep, 0, keepdims=False)

    if not bidirectional or cw < 2:
        g = stack_parts(0, cw)
        acc = dyn(g, i + 1)
        for s in range(1, ep):
            acc = acc + dyn(g, i + 1 + s)
        return acc
    wa = cw - cw // 2
    wb = cw - wa
    ga, gb = stack_parts(0, wa), stack_parts(wa, wb)
    acc_a, acc_b = dyn(ga, i + 1), dyn(gb, i - 1)
    for s in range(1, ep):
        acc_a = acc_a + dyn(ga, i + 1 + s)
        acc_b = acc_b + dyn(gb, i - 1 - s)
    return jnp.concatenate([acc_a, acc_b], axis=1)


def _ref_combine(contrib, out, eo, i, c0, cw, *, axis, ep, bidirectional):
    """Stock all_gather of the expert-output chunks + the ring's exact
    local accumulation order."""
    g = collectives.all_gather(eo, axis, gather_dimension=0, tiled=False)

    def dyn(j):
        return lax.dynamic_index_in_dim(g, j % ep, 0, keepdims=False)

    wa = cw - cw // 2 if (bidirectional and cw >= 2) else cw
    for s in range(ep):
        if not bidirectional or cw < 2:
            out = out + contrib((i - s) % ep, c0, 0, cw, dyn(i - s))
        else:
            ja, jb = (i - s) % ep, (i + s) % ep
            out = out + contrib(ja, c0, 0, wa, dyn(ja)[:, :wa])
            out = out + contrib(jb, c0, wa, cw - wa, dyn(jb)[:, wa:])
    return out


# ----------------------------------------------------------- public wrapper
def moe_a2a_ffn(x, gating, weights, topo=None, *, axis: str = "ep",
                chunks: int = 1, bidirectional: bool = False,
                reference: bool = False,
                batch_axes=("dp", "fsdp"), seq_axes=("sp",)):
    """Decomposed MoE dispatch → expert FFN → combine on GLOBAL arrays.

    x: [B, S, D] with B dividing the batch axes and S dividing
    (seq_axes × ep) — the sequence shards over ``(sp, ep)`` so each ep
    member owns a token block (the big-mesh MoE layout; along ep this is
    a free slice of the previously-replicated batch).

    gating — one of:
      ("einsum", dispatch [B,S,E,C], combine [B,S,E,C])   one-hot dots
      ("gather", tok_of_slot [E,C], slot_valid [E,C],
                 slot_of_tok [B,S,K], w_of_tok [B,S,K])   index tables
    (tables use GLOBAL token ids n = b*S + s, exactly what
    top_k_gating_indices produces over the flattened batch).

    weights: (wi [E,D,F], wg [E,D,F] | None, wo [E,F,D]) — ep-sharded on
    E and tp-sharded on F like the serial path's constraints.

    Returns out [B, S, D] (sequence still sharded over (sp, ep) at the
    boundary; the caller's block constraint reshards as usual).
    ``reference=True`` is the stock-collectives XLA path the CPU-mesh
    oracles pin the ring against — bitwise-identical by construction."""
    topo = topo or current_topology()
    ep = topo.sizes[axis]
    if ep <= 1:
        raise ValueError(f"moe_a2a_ffn needs a >1 '{axis}' mesh axis")
    mode, *g = gating
    wi, wg, wo = weights
    E, C = (g[0].shape[2], g[0].shape[3]) if mode == "einsum" \
        else (g[0].shape[0], g[0].shape[1])
    E_loc = E // ep
    tp_live = topo.tp_size > 1
    red_axes = tuple(
        a for a in (*batch_axes, *seq_axes) if topo.sizes[a] > 1
    )
    chunk_list = _row_chunks(C, chunks)
    tok_spec = P(batch_axes, (*seq_axes, axis), None)
    w_specs = (P(axis, None, "tp" if tp_live else None),
               P(axis, "tp" if tp_live else None, None))
    if mode == "einsum":
        in_specs = (
            tok_spec,
            P(batch_axes, (*seq_axes, axis), None, None),
            P(batch_axes, (*seq_axes, axis), None, None),
            w_specs[0],
        ) + ((w_specs[0],) if wg is not None else ()) + (w_specs[1],)
    else:
        in_specs = (
            tok_spec,
            P(None, None),  # tok_of_slot
            P(None, None),  # slot_valid
            P(batch_axes, (*seq_axes, axis), None),  # slot_of_tok
            P(batch_axes, (*seq_axes, axis), None),  # w_of_tok
            w_specs[0],
        ) + ((w_specs[0],) if wg is not None else ()) + (w_specs[1],)

    B, S, D = x.shape
    S_loc = S // (math.prod(topo.sizes[a] for a in seq_axes) * ep)
    B_loc = B // math.prod(topo.sizes[a] for a in batch_axes)

    def body(xl, *rest):
        rest = list(rest)
        if mode == "einsum":
            disp, comb = rest.pop(0), rest.pop(0)
        else:
            tok_of_slot, slot_valid = rest.pop(0), rest.pop(0)
            slot_of_tok, w_of_tok = rest.pop(0), rest.pop(0)
        wi_l = rest.pop(0)
        wg_l = rest.pop(0) if wg is not None else None
        wo_l = rest.pop(0)
        i = lax.axis_index(axis).astype(jnp.int32)
        tokens = xl.reshape(-1, D)
        n_loc = tokens.shape[0]
        if mode == "einsum":
            part, contrib = _einsum_fns(
                tokens, disp.reshape(n_loc, E, C), comb.reshape(n_loc, E, C),
                E_loc,
            )
        else:
            b0 = _pos(batch_axes, topo.sizes) * B_loc
            s0 = _pos((*seq_axes, axis), topo.sizes) * S_loc
            part, contrib = _gather_fns(
                tokens, tok_of_slot, slot_valid,
                slot_of_tok.reshape(n_loc, -1), w_of_tok.reshape(n_loc, -1),
                E_loc, C, S, S_loc, B_loc, b0, s0,
            )

        def ffn(chunk):
            # the serial path's expert matmuls, restricted to the landed
            # capacity rows (rows are independent — chunking is pure
            # scheduling); tp contraction psums exactly where GSPMD would
            h = jnp.einsum("ecd,edf->ecf", chunk, wi_l)
            if wg_l is not None:
                h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", chunk, wg_l)) * h
            else:
                h = jax.nn.gelu(h)
            eo = jnp.einsum("ecf,efd->ecd", h, wo_l)
            if tp_live:
                eo = lax.psum(eo, "tp")
            return eo

        out = jnp.zeros((n_loc, D), xl.dtype)
        for c0, cw in chunk_list:
            if reference:
                chunk = _ref_dispatch(
                    part, i, c0, cw, axis=axis, ep=ep,
                    bidirectional=bidirectional,
                )
            else:
                chunk = _dispatch_reduce_ring(
                    part, i, c0, cw, axis=axis, ep=ep,
                    bidirectional=bidirectional,
                )
            if red_axes:
                # dp/fsdp/sp token shards contribute disjoint slots; one
                # psum per completed chunk folds them (both paths)
                chunk = lax.psum(chunk, red_axes)
            eo = ffn(chunk)
            if reference:
                out = _ref_combine(
                    contrib, out, eo, i, c0, cw, axis=axis, ep=ep,
                    bidirectional=bidirectional,
                )
            else:
                out = _combine_gather_ring(
                    contrib, out, eo, i, c0, cw, axis=axis, ep=ep,
                    bidirectional=bidirectional,
                )
        return out.reshape(xl.shape)

    args = (x,) + tuple(g) + (wi,) + ((wg,) if wg is not None else ()) + (wo,)
    return _shard_map_full(body, topo, in_specs, tok_spec)(*args)


# -------------------------------------------------- decode-shaped exchange
def moe_decode_a2a(tokens, tok_of_slot, slot_valid, slot_of_tok, w_of_tok,
                   weights, topo=None, *, axis: str = "ep",
                   chunks: int = 1, bidirectional: bool = False):
    """Decode-shaped expert exchange for the serving engine (ISSUE 14):
    tokens [N, D] REPLICATED, experts ep-sharded — returns the combined
    per-token outputs [N, D].

    The serving slot step is the opposite regime from training
    (:func:`moe_a2a_ffn`): per-step token counts are tiny (at most the
    token budget) and the slot batch is replicated, so the *dispatch*
    half of the exchange is free — each ep member slices its experts'
    rows straight out of its replicated token copy through the
    ``top_k_gating_indices`` tables. What remains on the wire is the
    *combine ride*: every member needs every expert block's outputs to
    fold its tokens' top-k picks. This decomposes that all-gather into
    chunked ``ppermute`` hops on the ep ring — chunk c's blocks ride
    while chunk c+1's expert FFN runs (The Big Send-off's small-message
    treatment: at decode sizes the exchange is latency- not
    bandwidth-bound, which is why the serving engine's ``auto`` form
    picks stock collectives below a payload threshold and this ring
    above it).

    Every member assembles the full [E, C, D] expert tensor from the
    riding blocks (blocks land by expert index, not arrival order) and
    then combines ITS OWN N/ep token block with the exact gather +
    weighted-sum the stock path uses — so the output honestly claims
    ep-PARTITIONED (shardlint R1's replication contract: a claim of
    replication over blocks assembled from ppermute hops is beyond the
    taint analysis, and partitioning is what each member actually owns)
    and is bitwise the stock form AND the dense-replicated (ep = 1)
    program — the tests/test_serving_moe.py oracle. GSPMD re-replicates
    the tiny [N, D] result at the boundary.

    Full-manual shard_map over the whole mesh; every hop goes through ``comm.collectives.permute`` so the shardlint
    R3 ring contract is enforced at construction (the seeded corpus pair
    ``moe_decode_ring_malformed``/``_clean`` pins the hazard form).
    """
    topo = topo or current_topology()
    ep = topo.sizes[axis]
    if ep <= 1:
        raise ValueError(f"moe_decode_a2a needs a >1 '{axis}' mesh axis")
    wi, wg, wo = weights
    E, C = tok_of_slot.shape
    E_loc = E // ep
    N, D = tokens.shape
    if N % ep != 0:
        raise ValueError(
            f"moe_decode_a2a needs the token count {N} to divide ep={ep} "
            "(each member combines its own token block)"
        )
    N_loc = N // ep
    K = slot_of_tok.shape[1]
    tp_live = topo.tp_size > 1
    chunk_list = _row_chunks(C, chunks)
    w_specs = (P(axis, None, "tp" if tp_live else None),
               P(axis, "tp" if tp_live else None, None))
    in_specs = (
        P(None, None),   # tokens (replicated slot batch)
        P(None, None),   # tok_of_slot (global tables)
        P(None, None),   # slot_valid
        P(None, None),   # slot_of_tok
        P(None, None),   # w_of_tok
        w_specs[0],
    ) + ((w_specs[0],) if wg is not None else ()) + (w_specs[1],)
    out_spec = P(axis, None)  # each member emits its own token block

    def body(tok, tof, sv, sot, wt, *ws):
        ws = list(ws)
        wi_l = ws.pop(0)
        wg_l = ws.pop(0) if wg is not None else None
        wo_l = ws.pop(0)
        i = lax.axis_index(axis).astype(jnp.int32)
        # dispatch = local slicing: my experts' capacity rows out of the
        # replicated token copy (invalid slots zeroed exactly like the
        # stock path's slot_valid mask)
        my_tok = lax.dynamic_slice(tof, (i * E_loc, 0), (E_loc, C))
        my_valid = lax.dynamic_slice(sv, (i * E_loc, 0), (E_loc, C))
        rows = jnp.take(tok, my_tok.reshape(-1), axis=0).reshape(
            E_loc, C, D
        )
        rows = rows * my_valid[..., None].astype(tok.dtype)

        def ffn(chunk):
            # the serial path's expert matmuls on the landed capacity
            # rows (rows independent — chunking is pure scheduling)
            h = jnp.einsum("ecd,edf->ecf", chunk, wi_l)
            if wg_l is not None:
                h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", chunk, wg_l)) * h
            else:
                h = jax.nn.gelu(h)
            eo = jnp.einsum("ecd,edf->ecf", h, wo_l)
            if tp_live:
                eo = lax.psum(eo, "tp")
            return eo

        fwd, bwd = _ring_perms(ep)
        full = jnp.zeros((E, C, D), tok.dtype)
        for c0, cw in chunk_list:
            eo = ffn(rows[:, c0:c0 + cw])
            if not bidirectional or cw < 2:
                buf = eo
                for s in range(ep):
                    blk = (i - s) % ep
                    full = lax.dynamic_update_slice(
                        full, buf, (blk * E_loc, c0, 0)
                    )
                    if s < ep - 1:
                        buf = _hop(buf, axis, fwd)
            else:
                wa = cw - cw // 2
                buf_a, buf_b = eo[:, :wa], eo[:, wa:]
                for s in range(ep):
                    full = lax.dynamic_update_slice(
                        full, buf_a, (((i - s) % ep) * E_loc, c0, 0)
                    )
                    full = lax.dynamic_update_slice(
                        full, buf_b, (((i + s) % ep) * E_loc, c0 + wa, 0)
                    )
                    if s < ep - 1:
                        buf_a = _hop(buf_a, axis, fwd)
                        buf_b = _hop(buf_b, axis, bwd)
        # combine MY token block with the stock path's exact expression
        # (the assembled full tensor is member-identical; the output spec
        # claims only the block each member actually owns)
        my_sot = lax.dynamic_slice(sot, (i * N_loc, 0), (N_loc, K))
        my_w = lax.dynamic_slice(wt, (i * N_loc, 0), (N_loc, K))
        picked = jnp.take(
            full.reshape(E * C, D), my_sot.reshape(-1), axis=0
        ).reshape(N_loc, K, D)
        return jnp.sum(picked * my_w[..., None].astype(tok.dtype), axis=1)

    args = (tokens, tok_of_slot, slot_valid, slot_of_tok, w_of_tok, wi) + (
        (wg,) if wg is not None else ()
    ) + (wo,)
    return _shard_map_full(body, topo, in_specs, out_spec)(*args)


def moe_decode_a2a_applicable(topo, *, E: int, F: int,
                              n_tokens: Optional[int] = None) -> bool:
    """Shape half of the decode-ring predicate (the ``a2a_scope`` being
    active is the other half): an ep axis exists, experts divide it, tp
    divides the FFN width, the token count divides ep (each member
    combines its own block), the slot batch really is replicated (no
    live dp/fsdp/sp/pp axes — the serving mesh), and tracing is not
    already inside a manual shard_map."""
    if topo is None or topo.sizes.get("ep", 1) <= 1:
        return False
    if E % topo.sizes["ep"] != 0:
        return False
    if topo.tp_size > 1 and F % topo.tp_size != 0:
        return False
    if n_tokens is not None and n_tokens % topo.sizes["ep"] != 0:
        return False
    if any(topo.sizes.get(a, 1) > 1 for a in ("dp", "fsdp", "sp", "pp")):
        return False
    if manual_axis_names():
        return False
    return True


def moe_decode_a2a_bytes_per_step(model_cfg, topo, token_budget: int,
                                  itemsize: int = 2) -> Optional[dict]:
    """Analytic per-device wire bytes of ONE serving step's expert
    exchange (the combine ride: every member receives the other ep − 1
    members' [E/ep, C, D] output blocks, per layer). Honest for BOTH
    forms — the stock path's all-gather moves the same logical volume in
    one collective; the chunked ring moves it as ppermute hops that hide
    under the per-chunk FFNs. None for non-MoE models or ep == 1."""
    E = int(getattr(model_cfg, "num_experts", 0) or 0)
    ep = topo.sizes.get("ep", 1)
    if E <= 0 or ep <= 1 or E % ep != 0:
        return None
    if token_budget <= 0:
        return None
    from ..moe.sharded_moe import eval_capacity

    capacity = eval_capacity(model_cfg, int(token_budget))
    d = model_cfg.hidden_size
    hops = ep - 1
    per_layer = (E // ep) * capacity * d * itemsize * hops
    total = per_layer * model_cfg.num_layers
    return {
        "bytes_per_step": total,
        "capacity": capacity,
        "hops_per_exchange": hops,
    }


# ------------------------------------------------------------- applicability
def moe_a2a_applicable(topo, *, B: int, S: int, E: int, F: int) -> bool:
    """The shape half of the dispatch predicate (the scope being active is
    the other half): every block dimension must divide its mesh axes, and
    tracing must not already sit inside a manual shard_map (pipeline)."""
    if topo is None or topo.sizes.get("ep", 1) <= 1:
        return False
    dpf = topo.sizes["dp"] * topo.sizes["fsdp"]
    spe = topo.sizes["sp"] * topo.sizes["ep"]
    if not (E % topo.sizes["ep"] == 0 and B % dpf == 0 and S % spe == 0):
        return False
    if topo.tp_size > 1 and F % topo.tp_size != 0:
        return False
    if manual_axis_names():
        return False
    return True


# ----------------------------------------------------------- byte accounting
def moe_a2a_bytes_per_step(model_cfg, topo, batch: int, seq: int,
                           itemsize: int = 2, accum_steps: int = 1,
                           train: bool = True) -> Optional[dict]:
    """Analytic per-device MoE exchange bytes for ONE optimizer step.

    This is the honest figure for BOTH paths: the serial GSPMD path moves
    the same logical dispatch/combine volume in one monolithic exchange
    (scanned layers trace their collectives once, so the trace-time hook
    bus under-counts — same rationale as ring_wire_bytes_per_step). Per
    layer, per direction, the per-device wire is the riding chunk
    [E/ep, C, D] × (ep−1) hops; backward doubles it (the transposed rings
    carry same-shaped cotangents). None for non-MoE models or ep == 1."""
    E = int(getattr(model_cfg, "num_experts", 0) or 0)
    ep = topo.sizes.get("ep", 1)
    if E <= 0 or ep <= 1 or E % ep != 0:
        return None
    for attr in ("hidden_size", "num_layers", "moe_top_k"):
        if not hasattr(model_cfg, attr):
            return None
    if batch <= 0 or seq <= 0:
        return None
    N = batch * seq
    cap_factor = model_cfg.moe_capacity_factor if train else max(
        model_cfg.moe_capacity_factor, 2.0
    )
    capacity = max(4, int(math.ceil(cap_factor * model_cfg.moe_top_k
                                    * N / E)))
    d = model_cfg.hidden_size
    hops = ep - 1
    per_dir = (E // ep) * capacity * d * itemsize * hops
    fwd = 2 * per_dir * model_cfg.num_layers * max(accum_steps, 1)
    return {
        "bytes_per_step": 2 * fwd,  # + transposed backward rings
        "fwd_bytes_per_step": fwd,
        "capacity": capacity,
        "hops_per_exchange": hops,
    }
