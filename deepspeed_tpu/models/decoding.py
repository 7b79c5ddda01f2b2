"""KV-cache decoding forward passes for the transformer core.

Parity: deepspeed/inference/engine.py + csrc/transformer/inference (the
fused decode path with static KV cache). TPU-native: the cache is a static
ring buffer [L, B, S_max, KV, hd] so every decode step is the same compiled
program (no dynamic shapes); the token loop is a ``lax.while_loop`` in
inference/engine.py.

Sharding: caches inherit the model's TP layout (KV heads over tp, batch over
dp) via constrain; decode attention is a [B,1,H,hd] x [B,S,KV,hd] contraction
that XLA maps onto the MXU as a batched matvec.
"""

from __future__ import annotations

import contextlib
import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..config import DeepSpeedConfigError
from .sharding import constrain
from .transformer import (
    MIXER_KINDS,
    Params,
    TransformerConfig,
    _norm,
    _latent_projections,
    _qk_norm,
    _rope,
    alibi_slopes,
    lm_head_logits,
)

Cache = Dict[str, jax.Array]

SCALE_LANES = 8  # redundant scale copies (min sublane tile; kernels read col 0)


_path_recorders: list = []  # active record_attention_path() scopes


@contextlib.contextmanager
def record_attention_path():
    """Scope that learns which attention the cached forward traced inside
    it took: yields a dict whose ``path`` reads ``paged_kernel`` (a Pallas
    kernel reads the page pool through the table), ``decode_kernel`` (the
    contiguous single-token Pallas kernel) or ``dense`` (the XLA lines),
    with the ``reasons`` for a dense path, and under ``kinds`` /
    ``kind_reasons`` the path of each layer kind and why it is a dense one;
    a routed model's bank products are under ``expert_path`` /
    ``expert_path_reason`` (:func:`_note_expert_path`). The choice is made
    at trace time, so the serving engine opens this around its step's
    trace."""
    rec = {"path": None, "reasons": (), "kinds": {}, "kind_reasons": {},
           "expert_path": None, "expert_path_reason": None}
    _path_recorders.append(rec)
    try:
        yield rec
    finally:
        _path_recorders.remove(rec)


def _note_attention_path(path: str, reasons=(), kind: str = "full") -> None:
    for rec in _path_recorders:
        rec["path"], rec["reasons"] = path, tuple(reasons)
        rec["kinds"][kind] = path
        rec["kind_reasons"][kind] = tuple(reasons)


def _note_expert_path(path: str, reason: Optional[str]) -> None:
    """A routed layer's bank products (``moe/sharded_moe.expert_bank_path``):
    ``touched_kernel`` or ``einsum`` with why, under ``expert_path`` /
    ``expert_path_reason`` of the scopes open."""
    for rec in _path_recorders:
        rec["expert_path"], rec["expert_path_reason"] = path, reason


def _is_ragged(cache_len) -> bool:
    """True when ``cache_len`` is a per-row [B] vector (the serving
    engine's slot batch), False for the classic shared scalar."""
    return getattr(cache_len, "ndim", 0) == 1


def _update_at(cache: jax.Array, new: jax.Array, layer,
               cache_len) -> jax.Array:
    """Write ``new`` [B, S, KV, hd] into layer ``layer`` of the cache
    stack [L, B, Smax, KV, hd] at per-batch offset ``cache_len`` (scalar
    or [B] vector), in place: the stack comes back whole. The vector form
    is a vmapped per-row dynamic_update_slice — each slot of a ragged
    serving batch advances its own write frontier."""
    if _is_ragged(cache_len):
        return jax.vmap(
            lambda c, u, off: lax.dynamic_update_slice(
                c, u[None], (layer, off, 0, 0)),
            in_axes=(1, 0, 0), out_axes=1,
        )(cache, new, cache_len)
    return lax.dynamic_update_slice(
        cache, new[None], (layer, 0, cache_len, 0, 0))


def _update_scale_at(scale: jax.Array, new: jax.Array, layer,
                     cache_len) -> jax.Array:
    """Scale-cache twin of :func:`_update_at`: ``scale`` is stored
    pre-transposed as [L, B, KV, Smax, SL]; ``new`` arrives [B, S, KV, SL]
    (the _quantize_kv layout) and is transposed here — tiny; the big int8
    value caches never relayout."""
    new = jnp.swapaxes(new, 1, 2)
    if _is_ragged(cache_len):
        return jax.vmap(
            lambda c, u, off: lax.dynamic_update_slice(
                c, u[None], (layer, 0, off, 0)),
            in_axes=(1, 0, 0), out_axes=1,
        )(scale, new, cache_len)
    return lax.dynamic_update_slice(
        scale, new[None], (layer, 0, 0, cache_len, 0))


def verify_window_rows(num_new, spec_len, max_draft: int, width: int):
    """The rows of a ragged chunk whose logits the sampler reads: each
    slot's last ``spec_len + 1`` REAL positions (the committed-token feed
    plus its drafts), left-aligned into a fixed [B, max_draft + 1] index
    into the chunk's ``width`` rows. Rows with ``spec_len = 0`` reduce to
    the single last-real-position the plain serving step always read;
    window slots past a row's ``spec_len`` and an idle slot
    (``num_new`` 0) hold clipped rows the caller masks. Column ``j + 1``
    is also where draft ``j`` rides in the chunk's tokens. ``max_draft``
    is static (the ONE step's fixed shape), ``spec_len`` is traced —
    per-slot draft counts never recompile."""
    base = num_new - 1 - spec_len
    return jnp.clip(
        base[:, None] + jnp.arange(max_draft + 1, dtype=jnp.int32)[None, :],
        0, width - 1,
    )


def row_layout(topology) -> Tuple[str, Optional[str]]:
    """How a ragged step traced under ``topology`` lays out the rows of its
    row-by-row layers: ``("packed", None)``, or ``("slots", reason)`` where
    the mesh shards the step's slot axis (``constrain(h, ("dp", "fsdp"),
    ...)``): a ``[1, token_budget]`` block has no slot axis to shard."""
    ways = 1 if topology is None else (
        topology.sizes.get("dp", 1) * topology.sizes.get("fsdp", 1))
    if ways > 1:
        return "slots", (
            f"the mesh shards the step's slot axis {ways} ways (dp x fsdp), "
            "and a [1, token_budget] block of packed rows has no slot axis")
    return "packed", None


class ChunkRows:
    """The rows a cached forward computes for a ``[B, S]`` chunk, and the
    way between them and the slot layout.

    Everything that works row by row (embedding, norms, projections, rotary,
    MLP, experts, residual adds) runs on ``rows.positions``' layout, and a
    page pool is written from it (:meth:`page_rows`: a computed row's
    place in the pool); the attention calls and a contiguous arena's write,
    whose operands are indexed by slot, take ``unpack``-ed ``[B, S, ...]``
    blocks, and the attention's output comes back through ``pack``.

    Identity (``budget`` None, or one slot): the rows ARE the slots'
    ``[B, S]`` and ``pack`` / ``unpack`` return their argument: the lockstep
    engine and ``generate``, where every row is real.

    Packed (``num_new`` [B] with the caller's promise that it sums to at
    most ``budget``: the scheduler's invariant 1): the rows are ``[1,
    budget]``, the real tokens slot after slot in chunk order and idle rows
    after them. Slot ``b`` owns the contiguous run ``start[b] : start[b] +
    num_new[b]``, so ``unpack`` is ``B`` slices of ``S`` rows out of the
    block padded by ``S`` (rows past a slot's ``num_new`` hold its
    neighbours' rows: garbage past its frontier, as padding always was),
    and ``pack`` is a gather of ``budget`` rows."""

    def __init__(self, B: int, S: int, cache_len, num_new=None,
                 budget: Optional[int] = None):
        self.B, self.S = B, S
        steps = jnp.arange(S, dtype=jnp.int32)
        if _is_ragged(cache_len):
            self.slot_positions = (
                cache_len[:, None].astype(jnp.int32) + steps[None, :])
        else:
            self.slot_positions = cache_len + jnp.broadcast_to(steps, (B, S))
        self.packed = budget is not None and num_new is not None and B > 1
        if not self.packed:
            self.count, self.valid = B * S, None
            self.positions = self.slot_positions
            return
        self.count = T = int(budget)
        ends = jnp.cumsum(num_new.astype(jnp.int32))
        self.start = jnp.minimum(ends - num_new, T)
        row = jnp.arange(T, dtype=jnp.int32)
        slot = jnp.minimum(
            jnp.sum(row[:, None] >= ends[None, :], axis=1), B - 1)
        # each packed row's row of the flattened slot layout (an idle row
        # re-reads a row of the last slot)
        self._src = slot * S + jnp.clip(row - self.start[slot], 0, S - 1)
        self.valid = (row < ends[-1])[None, :]
        self.positions = self.pack(self.slot_positions)

    def pack(self, x: jax.Array) -> jax.Array:
        """``[B, S, ...]`` by slot -> the computed rows ``[1, T, ...]``."""
        if not self.packed:
            return x
        return x.reshape(self.B * self.S, *x.shape[2:])[self._src][None]

    def pack_split(self, whole: jax.Array, first: jax.Array,
                   chunk: jax.Array) -> jax.Array:
        """:meth:`pack` of an output a call returns in two pieces: slot
        ``b``'s rows in ``whole`` ``[>= B, S, ...]`` where ``chunk[b]``,
        else its first rows in ``first`` ``[B, R, ...]`` and nothing after
        them (the delta-rule call: a slot with one real row or none writes
        a row tile). No row of ``whole`` is read for a slot that did not
        write it: an idle computed row, which re-reads a row of the last
        slot past its frontier, reads ``first``'s last row there."""
        R = first.shape[1]
        if not self.packed:
            short = jnp.pad(first, [(0, 0), (0, self.S - R)]
                            + [(0, 0)] * (first.ndim - 2))
            return jnp.where(chunk.reshape(-1, *[1] * (first.ndim - 1)),
                             whole[:self.B], short)
        slot, off = self._src // self.S, self._src % self.S
        rows = jnp.where(
            chunk[slot].reshape(-1, *[1] * (first.ndim - 2)),
            whole.reshape(-1, *whole.shape[2:])[self._src],
            first[slot, jnp.minimum(off, R - 1)])
        return rows[None]

    def unpack(self, x: jax.Array) -> jax.Array:
        """The computed rows ``[1, T, ...]`` -> ``[B, S, ...]`` by slot."""
        if not self.packed:
            return x
        block = jnp.concatenate(
            [x[0], jnp.zeros((self.S, *x.shape[2:]), x.dtype)], axis=0)
        # (a slice a slot, stacked: on the chip one fusion at the bandwidth
        # of its output; the vmapped form is a gather that takes twice as
        # long once a slot's slice is tens of MB: my chip run, PR 42)
        return jnp.stack([
            lax.dynamic_slice_in_dim(block, self.start[b], self.S)
            for b in range(self.B)])

    def origin(self) -> Tuple[jax.Array, jax.Array]:
        """(slot, row inside its slot's chunk) of every computed row, both
        in the computed rows' leading layout (``[B, S]``, or ``[1, T]``
        packed): what a layer that looks back along a slot's rows (a short
        convolution) needs to know where a slot's run begins."""
        if not self.packed:
            shape = (self.B, self.S)
            return (jnp.broadcast_to(
                jnp.arange(self.B, dtype=jnp.int32)[:, None], shape),
                jnp.broadcast_to(
                    jnp.arange(self.S, dtype=jnp.int32)[None, :], shape))
        return (self._src // self.S)[None], (self._src % self.S)[None]

    def page_rows(self, page_table: jax.Array,
                  pool: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """(physical page, offset inside it) of every computed row in the
        page pool ``pool`` [L, P+1, page_size, ...] under ``page_table``
        [B, max_pages], both in the computed rows' leading layout: where
        :func:`_paged_write` puts the row. A row of slot ``b`` at position
        ``t`` lies at ``page_table[b, t // page_size]``, ``t % page_size``.
        A row past its slot's mapped pages (the slot layout's padding) goes
        where the table points unmapped entries, and a packed row that is
        idle to the NULL page itself, ``P``: both hold what no query reads.
        The same for every layer of the pool, so a step forms it once a
        table (``mixers.cached_layers``), not once a write."""
        ps, null = pool.shape[2], pool.shape[1] - 1
        page = jnp.clip(self.positions // ps, 0, page_table.shape[1] - 1)
        phys = page_table[self.origin()[0], page]
        if self.packed:
            phys = jnp.where(self.valid, phys, null)
        return phys, self.positions % ps

    def take(self, x: jax.Array, chunk_rows: jax.Array) -> jax.Array:
        """Rows ``chunk_rows`` [B, K] (indices into each slot's chunk, as
        :func:`verify_window_rows` gives them) of the computed rows ``x``
        (the hidden state): ``[B, K, D]``."""
        if not self.packed:
            return jnp.take_along_axis(x, chunk_rows[:, :, None], axis=1)
        return x[0][jnp.minimum(self.start[:, None] + chunk_rows,
                                self.count - 1)]


WIN = "_win"  # suffix of the window layers' pool leaves and page table
LATENT, INDEX = "kv", "ki"  # a latent model's pools: latent rows, indexer keys
INDEX_TAIL = "ki_tail"  # a slot's index keys of the block it has not finished


def latent_row_width(cfg: TransformerConfig) -> int:
    """Values a row of the latent pool holds: ``cfg.latent_width`` in whole
    128-lane tiles (the chip tiles a narrower row up to that anyway)."""
    return -(-cfg.latent_width // 128) * 128


def index_row_width(cfg: TransformerConfig) -> int:
    """Values a row of the index pool beside K / V pools holds:
    ``cfg.index_dim`` in whole 128-lane tiles, zeros past the key (the
    scoring kernel's DMA takes whole tiles; a dot product over the zeros
    adds nothing)."""
    return -(-cfg.index_dim // 128) * 128


def pool_index_keys(block: jax.Array) -> jax.Array:
    """ONE index key of a block of tokens, from their rotated keys ``[...,
    index_kpool, index_dim]``: their mean, float32. (The operator behind a
    published ``index_kpool`` is this function, and no other line.)"""
    return jnp.mean(block.astype(jnp.float32), axis=-2)


def _pooled_index_write(cfg: TransformerConfig, pools: Cache, k_new, layer,
                        cache_len, num_new, page_table) -> Cache:
    """The rotated index keys ``k_new`` [B, S, Di] of a chunk a slot into the
    pool of POOLED keys (``pools[INDEX]`` [L, P+1, page_size / kpool, Di], on
    the latent pool's page table): every block of ``kpool`` tokens the chunk
    touches is pooled anew from the slot's carried keys (``pools[INDEX_TAIL]``
    [L, slots, kpool - 1, Di]: the last keys before the chunk, of which the
    block the slot had not finished takes its ``cache_len % kpool`` last)
    and the chunk's own, and written at its place. A block that is not
    whole yet, or lies past the real rows, holds what no query scores (a
    block is seen once its last token is at or before the query) and is
    written again when it is. The keys carried on are the last ``kpool - 1``
    of the carried and the REAL rows, so a slot with no real row keeps what
    it held."""
    kp = cfg.index_kpool
    B, S, _ = k_new.shape
    tail = pools[INDEX_TAIL]
    prev = lax.dynamic_index_in_dim(tail, layer, 0, False)
    # position cache_len - (kp - 1) + e of the slot at row e
    ext = jnp.concatenate([prev, k_new.astype(prev.dtype)], axis=1)
    cache_len = cache_len.astype(jnp.int32)
    nb = (S + kp - 2) // kp + 1  # the blocks a chunk can touch
    at = ((kp - 1 - cache_len % kp)[:, None, None]
          + kp * jnp.arange(nb, dtype=jnp.int32)[None, :, None]
          + jnp.arange(kp, dtype=jnp.int32)[None, None, :])
    take = lambda rows_at: jnp.take_along_axis(
        ext, jnp.minimum(rows_at, S + kp - 2).reshape(B, -1, 1), axis=1)
    pooled = pool_index_keys(take(at).reshape(B, nb, kp, -1))
    carried = take(num_new.astype(jnp.int32)[:, None]
                   + jnp.arange(kp - 1, dtype=jnp.int32)[None, :])
    return {
        **pools,
        INDEX: _paged_write(
            pools[INDEX], pooled.astype(pools[INDEX].dtype), layer,
            _page_indices(cache_len // kp, nb, page_table,
                          pools[INDEX].shape[2])),
        INDEX_TAIL: lax.dynamic_update_index_in_dim(tail, carried, layer, 0),
    }


@dataclass(frozen=True)
class Pool:
    """One leaf of the paged arena, ``[layers, entries, *row]``: ``table``
    says what indexes its second axis (``"page"``: the page table, the NULL
    page after the last; ``"window"``: the window layers' table, likewise;
    ``"slot"``: the slot, no page), ``row`` is one page's or one slot's
    entry, ``spec`` the leaf's partition spec on a mesh (cache heads over
    tp; a latent row, an index key and a family module's leaves whole)."""

    name: str
    table: str
    layers: int
    row: Tuple[int, ...]
    dtype: Any
    spec: P = P()

    @property
    def row_bytes(self) -> int:
        """Bytes of one entry of one layer."""
        return math.prod(self.row) * jnp.dtype(self.dtype).itemsize


# What a cache admits: a row a property a model's cache can have, a column an
# operation on its pages, slots or storage, an entry the reason the operation
# is refused (none: admitted; ``{window}``, ``{state_kinds}``, ``{page_holds}``
# come from the configuration). The columns, as the serving configuration
# names them: "paged false" (a contiguous arena) and "int8", asked by whoever
# builds the arena; "host_pages", "fleet.prefill_replicas" (and with it
# export_kv_pages / import_kv_pages) and "spec", asked by the serving engine;
# "prefix_cache", which the engine switches off with the reason, and the
# step's copy-on-write with it: no page is shared.
_WINDOW_MOVES = (
    "the model has window layers ({window} keys): a page that is kept, "
    "spilled or handed over holds the full layers' keys alone, and the "
    "window layers' last {window} keys would be missing")
_LATENT_MOVES = (
    "the model caches latents (kv_latent_dim): a spilled or handed-over page "
    "is laid out as the k and v of KV heads, which a latent pool and its "
    "indexer keys are not")
_INDEX_MOVES = (
    "the model selects by an indexer (index_topk) whose keys lie in a pool of "
    "their own beside K and V: a page that is spilled or handed over is laid "
    "out as the k and v of KV heads, and the index keys of the same tokens "
    "would be missing")
_STATE_MOVES = (
    "a page {page_holds}; the state layers' ({state_kinds}) state that "
    "summed the same tokens is a slot's and no page, so a page that is kept, "
    "spilled or handed over would serve a model with state layers a context "
    "its state never saw")
CACHE_ADMITS: Dict[str, Dict[str, str]] = {
    # a second pool behind a window (layer_pattern names window layers)
    "window pool": dict.fromkeys(
        ("host_pages", "fleet.prefill_replicas", "prefix_cache"),
        _WINDOW_MOVES),
    # every layer's cache is latent rows (kv_latent_dim, no mixer_types)
    "latent rows": {
        "paged false": "latent attention (kv_latent_dim) attends its cached "
                       "latents through the page table",
        "int8": "a latent cache (kv_latent_dim) holds one normed latent a "
                "token for all heads, and no per-head scale applies to it",
        "host_pages": _LATENT_MOVES, "fleet.prefill_replicas": _LATENT_MOVES,
    },
    # index keys in a pool of their own beside K / V (index_topk, no latent)
    "index keys": {
        "paged false": "an indexer (index_topk) scores its cached keys and "
                       "the walk reads the selection's K and V through the "
                       "page table",
        "int8": "the walk over an indexer's selection (index_topk) reads K "
                "and V as they are computed",
        "host_pages": _INDEX_MOVES, "fleet.prefill_replicas": _INDEX_MOVES,
        "spec": "a draft row's selection (index_topk) is made over index "
                "keys of drafts that may be rejected, and no test holds the "
                "verify window under a selection",
        "prefix_cache": "a kept page would hold index keys (index_topk) "
                        "beside K and V, and no test holds a selection over "
                        "reused pages",
    },
    # the layers are named (mixer_types): state a slot beside the page kinds
    "slot state": {
        "paged false": "a sparse or latent layer reads its keys through the "
                       "page table and a state layer keeps its state a slot "
                       "(mixer_types); all live in the paged arena",
        "int8": "a sparse layer's compressed keys are means of its cached "
                "keys, a latent layer's row takes no per-head scale, and a "
                "state layer's state is float32",
        "host_pages": _STATE_MOVES, "fleet.prefill_replicas": _STATE_MOVES,
        "spec": "the model has state layers ({state_kinds}), whose state "
                "sums every row it was fed; a rejected draft would need the "
                "state rolled back to the last accepted token, and the step "
                "keeps no such copy",
        "prefix_cache": _STATE_MOVES,
    },
}


@dataclass(frozen=True)
class CacheLayout:
    """What a model's cache is (:func:`cache_layout`): the rows of
    ``CACHE_ADMITS`` it has and the words their reasons take; the page
    tables a paged step takes (the suffixes of their pools' names); what a
    step's plan counts, in order (``counted``: a layer kind whose pages
    ``key_counts`` walks, or the serving engine's ``_count_<name>``); and
    ``unit_page``, the smallest page every pool is whole in (a sparse layer
    keeps one compressed key a page of ``kernel_stride`` tokens, an indexer
    one key a block of ``index_kpool``), over which a token's bytes are
    taken."""

    cfg: TransformerConfig
    rows: Tuple[str, ...]
    words: Dict[str, Any]
    tables: Tuple[str, ...]
    counted: Tuple[str, ...]
    unit_page: int

    def refused(self, op: str) -> Optional[str]:
        """Why this cache refuses ``op`` (a column of ``CACHE_ADMITS``), or
        None where it admits it."""
        return next((CACHE_ADMITS[r][op].format(**self.words)
                     for r in self.rows if op in CACHE_ADMITS[r]), None)

    def refuse(self, op: str) -> None:
        """Raise where ``op`` is refused: a key of the serving configuration
        by its name, the arena's storage in the words that
        ``InferenceEngine``'s callers read too."""
        why = self.refused(op)
        if why:
            said = {"paged false": "a contiguous KV arena is refused and "
                                   "with it serving.paged false",
                    "int8": "an int8 KV cache"}.get(op, "serving." + op)
            raise DeepSpeedConfigError(f"{said} is refused: {why}")

    def pools(self, page_size: int, dtype=jnp.bfloat16,
              quantized: bool = False) -> Tuple[Pool, ...]:
        """Every leaf of the paged arena. The leaves of a model that names
        its layers are its family module's (``init_pools``), read here off
        their shapes."""
        cfg = self.cfg
        if cfg.mixer_types:
            from .mixers import family

            slot = {n for k in cfg.mixer_types for n in cfg.slot_leaves_of(k)}
            leaves = jax.eval_shape(lambda: family(cfg).init_pools(
                cfg, 0, page_size, 1, dtype))
            return tuple(Pool(n, "slot" if n in slot else "page", a.shape[0],
                              a.shape[2:], a.dtype) for n, a in leaves.items())
        L = cfg.total_layers
        if cfg.is_latent:
            widths = {LATENT: latent_row_width(cfg)}
            if cfg.index_topk:
                widths[INDEX] = cfg.index_dim
            return tuple(Pool(n, "page", L, (page_size, w), dtype)
                         for n, w in widths.items())
        # int8 storage carries a float32 scale a (token, head), in the
        # pre-transposed layout the decode kernel consumes
        kv = dict(row=(page_size, cfg.kv_heads, cfg.hd),
                  dtype=jnp.int8 if quantized else dtype,
                  spec=P(None, None, None, "tp", None))
        scale = dict(row=(cfg.kv_heads, page_size, SCALE_LANES),
                     dtype=jnp.float32, spec=P(None, None, "tp", None, None))
        leaves = {"k": kv, "v": kv}
        if quantized:
            leaves.update(k_scale=scale, v_scale=scale)
        if cfg.has_window:  # pages by layer kind, a table each
            return tuple(Pool(n + sfx, table, cfg.kind_count(kind), **at)
                         for sfx, table, kind in (("", "page", "full"),
                                                  (WIN, "window", "window"))
                         for n, at in leaves.items())
        out = [Pool(n, "page", L, **at) for n, at in leaves.items()]
        if cfg.index_in_pages:  # an index key a token, on K and V's table
            out.append(Pool(INDEX, "page", L,
                            (page_size, index_row_width(cfg)), dtype))
        return tuple(out)


def cache_layout(cfg: TransformerConfig) -> CacheLayout:
    """The ONE description of ``cfg``'s cache (host arithmetic over the
    configuration): what ``init_paged_cache`` builds, what a token and a
    slot weigh in it, what may be done to it and what a step counts are
    read off this record. A new kind of cache is an entry of
    ``MIXER_KINDS``, its pools and its row of ``CACHE_ADMITS``."""
    kinds = dict.fromkeys(cfg.mixer_types)
    paged = ", ".join(k for k in kinds if MIXER_KINDS[k].page)
    words = {
        "window": cfg.attn_window,
        "state_kinds": ", ".join(k for k in kinds if cfg.slot_leaves_of(k)),
        # what a page of this model holds (nothing, where no kind of its
        # layers keeps a page)
        "page_holds": (f"holds the paged layers' ({paged}) keys alone"
                       if paged else
                       "holds nothing (no layer of this model keeps a page)"),
    }
    has = {"window pool": cfg.has_window, "latent rows": cfg.is_latent,
           "index keys": cfg.index_in_pages,
           "slot state": bool(cfg.mixer_types)}
    if cfg.mixer_types:
        counted = ("mixers",)
    elif cfg.is_latent:
        counted = ("selected",)
    elif cfg.index_in_pages:  # the selection's counts in place of the walk's
        counted = ("selected", "share")
    else:
        counted = (*dict.fromkeys(cfg.layer_pattern or ("full",)), "share")
    geom = cfg.block_sparse
    return CacheLayout(
        cfg, tuple(r for r in CACHE_ADMITS if has[r]), words,
        ("", WIN) if cfg.has_window else ("",), counted,
        geom.kernel_stride if geom is not None else cfg.index_kpool)


def init_paged_cache(cfg: TransformerConfig, num_pages: int, page_size: int,
                     dtype=jnp.bfloat16, quantized: bool = False,
                     window_pages: Optional[int] = None,
                     max_slots: Optional[int] = None) -> Cache:
    """The serving engine's paged arena, the pools of :func:`cache_layout`
    as zeros: a pool of pages is ``[L, pages + 1, *row]`` (``k``/``v`` rows
    are [page_size, KV, hd]) — the extra physical page at index ``pages`` is
    the NULL page, where unmapped logical pages, a packed step's idle rows
    and (in the slot layout) idle slots' padded chunk writes land (its bytes
    are garbage by design and never attendable: every query masks at its
    own frontier). A model with window layers keeps pages by layer kind:
    ``k``/``v`` hold the full layers alone and ``k_win``/``v_win``
    [L_window, window_pages + 1, ...] the window layers, whose pages a slot
    gives back once every query still to come is past them. A model that
    names its layers keeps pages for the layers whose kind keeps any
    (``MIXER_KINDS``) and, for its state layers, leaves indexed by SLOT and
    not through the page table, ``[L_kind, max_slots, *row]``: its family
    module's ``init_pools`` builds them."""
    layout = cache_layout(cfg)
    if quantized:
        layout.refuse("int8")
    if cfg.mixer_types:
        if max_slots is None:
            raise ValueError("a model with state layers needs max_slots")
        from .mixers import family

        return family(cfg).init_pools(cfg, num_pages, page_size, max_slots,
                                      dtype)
    if cfg.has_window and window_pages is None:
        raise ValueError("a model with window layers needs window_pages")
    entries = {"page": num_pages, "window": window_pages}
    return {p.name: jnp.zeros(
        (p.layers, int(entries[p.table]) + 1, *p.row), p.dtype)
        for p in layout.pools(page_size, dtype, quantized)}


def _page_indices(cache_len: jax.Array, S: int, page_table: jax.Array,
                  page_size: int):
    """Physical destination of ``S`` entries a slot written at the per-slot
    frontier ``cache_len``: (phys_page [B, S], offset [B, S]). The pooled
    index keys' (:func:`_pooled_index_write`: a few BLOCKS a slot, by
    slot); a pool of rows is written at :meth:`ChunkRows.page_rows`."""
    mp = page_table.shape[1]
    pos = cache_len[:, None].astype(jnp.int32) + jnp.arange(
        S, dtype=jnp.int32
    )[None, :]
    pageidx = jnp.clip(pos // page_size, 0, mp - 1)
    phys = jnp.take_along_axis(page_table, pageidx, axis=1)
    return phys, pos % page_size


def page_places(rows: ChunkRows, pools: Cache, tables: Dict[str, jax.Array]
                ) -> Dict[str, Tuple[jax.Array, jax.Array]]:
    """:meth:`ChunkRows.page_rows` under each of ``tables`` (leaf-name
    suffix -> page table, or None: a contiguous arena) whose pool keeps
    rows by page: what a step's walk forms once, before its layers."""
    out = {}
    for sfx, table in tables.items():
        pool = next((pools[n + sfx] for n in ("k", LATENT)
                     if n + sfx in pools), None)
        if table is not None and pool is not None:
            out[sfx] = rows.page_rows(table, pool)
    return out


def _paged_write(pool: jax.Array, new: jax.Array, layer,
                 page_rows: Tuple[jax.Array, jax.Array]) -> jax.Array:
    """Scatter the computed rows' new K/V ``new`` [B, S, KV, hd] (or
    [1, T, KV, hd] packed) into layer ``layer`` of the page pool stack
    [L, P+1, page_size, KV, hd], row ``t`` at ``page_rows`` = (physical
    page, offset) of :meth:`ChunkRows.page_rows`, in place: the stack comes
    back whole. One update row a computed row: ``token_budget`` of them in a
    packed step, whatever the slots. A pool that holds 64-wide heads two a
    128-lane row (ops/pallas/paged_attention.lane_pairs) takes the same
    values in the same order."""
    phys, off = page_rows
    row = pool.shape[3:]  # ([KV, hd], [KV / 2, 128] or a latent's [width])
    return pool.at[layer, phys, off].set(
        new.reshape(*new.shape[:new.ndim - len(row)], *row))


def _paged_write_scale(pool: jax.Array, new: jax.Array, layer,
                       page_rows: Tuple[jax.Array, jax.Array]) -> jax.Array:
    """Scale twin of :func:`_paged_write`: pool [L, P+1, KV, ps, SL], the
    computed rows' scales [B, S, KV, SL] (the _quantize_kv layout)."""
    phys, off = page_rows
    kv = jnp.arange(pool.shape[2])
    return pool.at[
        layer, phys[:, :, None], kv[None, None, :], off[:, :, None]
    ].set(new)


def _paged_gather(pool: jax.Array, page_table: jax.Array) -> jax.Array:
    """Per-slot contiguous K/V view [B, mp*ps, KV, hd] gathered from the
    pool through the page tables — bitwise the bytes the contiguous
    arena would hold at every mapped position."""
    B, mp = page_table.shape
    view = pool[page_table]  # [B, mp, ps, KV, hd]
    return view.reshape(B, mp * pool.shape[1], *pool.shape[2:])


def _paged_gather_scale(pool: jax.Array, page_table: jax.Array) -> jax.Array:
    """[P+1, KV, ps, SL] pool → [B, KV, mp*ps, SL] per-slot scale view
    (the dense scale-cache layout)."""
    B, mp = page_table.shape
    view = jnp.swapaxes(pool[page_table], 1, 2)  # [B, KV, mp, ps, SL]
    return view.reshape(B, pool.shape[1], mp * pool.shape[2], pool.shape[3])


def paged_cow_copy(cache: Cache, page_table: jax.Array, start_pos: jax.Array,
                   cow_src: jax.Array) -> Cache:
    """Copy-on-write inside the ONE jitted step: slots whose ``cow_src``
    is a physical page id (>= 0) copy that page's KV — all layers, scales
    included — onto their current frontier page BEFORE the chunk write,
    so a slot diverging from a shared prefix mid-page keeps the shared
    tokens without ever writing the shared page. Rows with
    ``cow_src == -1`` degrade to a self-copy of their frontier page
    (bitwise no-op), keeping the step at one trace for every COW mix."""
    ps = next(iter(cache.values())).shape[2]
    N, mp = page_table.shape
    rows = jnp.arange(N)
    dst = page_table[rows, jnp.clip(start_pos // ps, 0, mp - 1)]
    do = cow_src >= 0
    src = jnp.where(do, jnp.maximum(cow_src, 0), dst)
    out = {}
    for key, pool in cache.items():
        src_data = pool[:, src]  # [L, N, ...page]
        cur = pool[:, dst]
        sel = do.reshape((1, N) + (1,) * (pool.ndim - 2))
        out[key] = pool.at[:, dst].set(jnp.where(sel, src_data, cur))
    return out


def staged_promote(cache: Cache, stage: Cache,
                   stage_dst: jax.Array) -> Cache:
    """Tiered page-in inside the ONE jitted step (serving.host_pages):
    scatter the promotion staging buffer — ``stage`` leaves are
    [L, STAGE_SLOTS, ...]-shaped page payloads the engine decoded from
    the host tier, ``stage_dst`` [STAGE_SLOTS] their physical
    destinations — into the pool. Runs BEFORE :func:`paged_cow_copy` and
    the chunk scatter, and the per-slot gathers run after both, so a
    page promoted this step is attendable this step (scatter-before-
    gather program order). Unused stage slots point at the NULL sink
    page: a no-promotion step is a harmless garbage write there and the
    program never changes shape — one trace across every spill/restore
    mix."""
    return {
        k: v.at[:, stage_dst].set(stage[k].astype(v.dtype))
        for k, v in cache.items()
    }


def init_cache(cfg: TransformerConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16, quantized: bool = False) -> Cache:
    """Static KV ring buffer for all layers.

    quantized: int8 storage with per-(token, kv-head) fp32 absmax scales —
    halves KV HBM for long-context serving (reference: kv-cache quant in
    the inference engine family). Dequant happens at read (in-kernel on the
    Pallas decode path)."""
    cache_layout(cfg).refuse("paged false")
    shape = (cfg.total_layers, batch, max_len, cfg.kv_heads, cfg.hd)
    if quantized:
        # scales live pre-transposed as [B, KV, Smax, SL]: the Pallas decode
        # kernel consumes (Smax, SL) trailing blocks directly, so the
        # latency-critical decode step never pays a per-token relayout
        sshape = (cfg.total_layers, batch, cfg.kv_heads, max_len, SCALE_LANES)
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(sshape, jnp.float32),
            "v_scale": jnp.zeros(sshape, jnp.float32),
        }
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
    }


def _quantize_kv(t: jax.Array):
    """[B,S,KV,hd] → (int8 values, [B,S,KV,SCALE_LANES] fp32 scales)."""
    s = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1, keepdims=True) / 127.0
    s = jnp.maximum(s, 1e-8)
    q = jnp.clip(jnp.round(t.astype(jnp.float32) / s), -127, 127).astype(jnp.int8)
    return q, jnp.broadcast_to(s, (*s.shape[:-1], SCALE_LANES))


def _out_proj(x: jax.Array, w) -> jax.Array:
    """Row-parallel attention out-projection. Under the
    tensor_parallel.overlap_comm scope this is a decomposed ring
    (parallel/tensor_overlap.tp_out_proj): prefill takes the
    sequence-scatter form, the S=1 decode step the feature-scatter +
    gather form whose reduce-scatter half hides under the matmul; packed
    weights and non-dividing shapes fall back to the plain projection."""
    from ..parallel.tensor_overlap import tp_out_proj

    return tp_out_proj(x, w)


def _qkv(cfg: TransformerConfig, p: Params, x: jax.Array, positions: jax.Array,
         kind: str = "full"):
    from ..parallel.tensor_overlap import tp_in_proj

    B, S, _ = x.shape
    nh, nkv, hd = cfg.num_heads, cfg.kv_heads, cfg.hd
    # one shared gather ring under overlap_comm when the prefill sequence
    # divides the tp ring; decode (S=1) and packed weights fall back
    qp, kp, vp = tp_in_proj(x, (p["wq"], p["wk"], p["wv"]))
    gate = None
    if cfg.attn_out_gate:  # a head of W_q is [q | the gate of its output]
        qp = qp.reshape(B, S, nh, 2 * hd)
        qp, gate = qp[..., :hd], qp[..., hd:]
    q = qp.reshape(B, S, nh, hd)
    k = kp.reshape(B, S, nkv, hd)
    v = vp.reshape(B, S, nkv, hd)
    if cfg.use_bias:
        q = q + p["bq"].reshape(1, 1, nh, hd)
        k = k + p["bk"].reshape(1, 1, nkv, hd)
        v = v + p["bv"].reshape(1, 1, nkv, hd)
    if cfg.qk_norm:
        q, k = _qk_norm(cfg, p, q, k)
    if cfg.pos_embedding == "rope" and cfg.rope_of(kind) is not None:
        rd = cfg.rotary_dim
        if rd:  # the leading rd values of a head, pairs inside them
            qr, kr = _rope(q[..., :rd], k[..., :rd], positions,
                           cfg.rope_of(kind))
            q = jnp.concatenate([qr, q[..., rd:]], axis=-1)
            k = jnp.concatenate([kr, k[..., rd:]], axis=-1)
        else:
            q, k = _rope(q, k, positions, cfg.rope_of(kind))
    return q, k, v, gate


def _indexer(cfg: TransformerConfig, ix: Params, x: jax.Array, q_src,
             positions: jax.Array, rd: int):
    """The indexer's three projections of the computed rows ``x`` [B,S,d]:
    (index queries [B,S,Hi,Di] from ``q_src`` (the query latent through
    ``wq_b``, or ``x`` itself through ``wq``), ONE key a token [B,S,Di] =
    LayerNorm(x W_k), both with their ``rd`` leading values rotated by the
    full layers' table; head weights float32 [B,S,Hi] with every constant
    factor folded in)."""
    from ..ops.normalization import layernorm

    B, S, _ = x.shape
    Hi, Di = cfg.index_heads, cfg.index_dim
    wq = ix["wq_b"] if "wq_b" in ix else ix["wq"]
    q_idx = (q_src @ wq).reshape(B, S, Hi, Di)
    k_idx = layernorm(
        (x @ ix["wk"]).astype(jnp.float32),
        ix["k_norm"]["scale"].astype(jnp.float32),
        ix["k_norm"]["bias"].astype(jnp.float32), cfg.norm_eps,
    ).astype(x.dtype)[:, :, None, :]
    q_rot, k_rot = _rope(q_idx[..., :rd], k_idx[..., :rd], positions,
                         cfg.rope_of("full"))
    q_idx = jnp.concatenate([q_rot, q_idx[..., rd:]], axis=-1)
    k_idx = jnp.concatenate([k_rot, k_idx[..., rd:]], axis=-1)[:, :, 0]
    w_idx = jnp.einsum(
        "bsd,dh->bsh", x.astype(jnp.float32),
        ix["w_proj"].astype(jnp.float32)) * (Hi ** -0.5 * Di ** -0.5)
    return q_idx, k_idx, w_idx


def _cached_attention(cfg: TransformerConfig, p: Params, x: jax.Array,
                      rows: ChunkRows, layer, k_cache: jax.Array,
                      v_cache: jax.Array, cache_len,
                      k_scale=None, v_scale=None, page_table=None,
                      num_new=None, kind: str = "full", page_rows=None,
                      ki_cache=None):
    """Attend new tokens against cache[:cache_len] + themselves.

    ``x`` holds the rows ``rows`` computes (``[B, S, D]``, or ``[1, T, D]``
    packed): the projections, their biases, QK-norm, rotary, the KV
    quantisation and a page pool's write run on those rows; q (and k and v
    for a contiguous arena's write) is unpacked to the slot layout ``[B, S,
    ...]`` for the attention call, and the attention's output is packed
    again before ``wo``.

    ``k_cache``/``v_cache`` (and the scales) are the whole stacks of the
    layer's pool, ``[L, ...]`` as init_cache / init_paged_cache give them,
    and ``layer`` the (traced) index of this layer inside them: the new
    keys are written in place at ``[layer, ...]`` and the stacks come back
    whole, so the scan that carries them never copies a pool. Returns
    (out, new_k_cache, new_v_cache[, new_k_scale, new_v_scale]).
    Works for prefill (S=prompt, cache_len=0) and decode (S=1,
    cache_len=pos). int8 caches carry per-(token, head) scales; the fresh
    prefill attends with the exact (unquantized) new k/v — only reads from
    the cache dequantize.

    ``cache_len`` may be a per-row [B] vector (the serving engine's ragged
    slot batch): every row then writes and masks at its own frontier.
    Query positions past a row's real token count produce garbage outputs
    and garbage cache entries BEYOND that row's frontier — both are
    harmless by the frontier invariant (a later query only attends
    kpos <= its own position, and every position is rewritten by its real
    token before any query can reach it).

    ``page_table`` [B, max_pages] switches the cache operands to the
    block-paged form: ``k_cache``/``v_cache`` are page POOLS
    [L, P+1, page_size, KV, hd] (scales [L, P+1, KV, page_size, SL]) shared
    by every slot. The computed rows scatter to their (layer, physical page,
    offset) destinations ``page_rows`` (:meth:`ChunkRows.page_rows` of this
    table and pool) FIRST, then attention reads the slot's pages:
    through the table inside a Pallas kernel that takes the stack and the
    layer's index and whose work follows the slot's length when the
    registered attention is the kernel one (rotary or learned positions,
    unquantized pool, shapes the kernel takes), else through a per-slot
    gathered view of ``stack[layer]`` — which holds bitwise the bytes the
    contiguous arena would, so the attention math below is byte-for-byte
    the dense path. ``num_new`` [B] (optional) counts each row's real
    tokens: the kernel stops at the last key a real token needs.

    ``kind`` "window" (a layer of ``cfg.layer_pattern``) bounds every
    query to its last ``cfg.attn_window`` keys: the paged kernel then
    starts at the page of its first row's oldest visible key, and the
    other kernels (which know no window) leave such a layer to the XLA
    lines.

    ``ki_cache`` [L, P+1, page_size, index_row_width] (a paged model with
    ``cfg.index_topk`` and no latent): the layer's indexer scores every
    cached token for each query from the index key the token wrote there,
    and every head of the query attends the query's ``index_topk`` best
    inside its KV group (ops/pallas/sparse_paged_attention.py: scores,
    selection and a walk that masks; the same by plain lines over gathered
    views otherwise). The pool comes back last.
    """
    B, S = rows.B, rows.S
    nh, nkv, hd = cfg.num_heads, cfg.kv_heads, cfg.hd
    window = cfg.window_of(kind)
    q, k, v, gate = _qkv(cfg, p, x, rows.positions, kind)

    quantized = k_scale is not None
    paged = page_table is not None
    # 64-wide heads held two a 128-lane row (paged_attention.lane_pairs)
    held_paired = k_cache.shape[-1] != hd
    if paged:  # the rows as they were computed, each to its place
        put, put_scale, where = _paged_write, _paged_write_scale, page_rows
    else:  # a slot's chunk is one slice of its arena
        put, put_scale, where = _update_at, _update_scale_at, cache_len

    def write(stack, new, put=put):
        return put(stack, new if paged else rows.unpack(new), layer, where)

    if quantized:
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        k_cache, v_cache = write(k_cache, kq), write(v_cache, vq)
        k_scale = write(k_scale, ks, put_scale)
        v_scale = write(v_scale, vs, put_scale)
    else:
        k_cache = write(k_cache, k.astype(k_cache.dtype))
        v_cache = write(v_cache, v.astype(v_cache.dtype))
    q = rows.unpack(q)
    index = None
    if ki_cache is not None:
        # the indexer: queries from the normed input, a key a token written
        # beside its K and V; both as wide as the pool's row
        q_idx, k_idx, w_idx = _indexer(cfg, p["idx"], x, x, rows.positions,
                                       cfg.index_rope_dim or cfg.index_dim)
        pad = [(0, ki_cache.shape[-1] - cfg.index_dim)]
        ki_cache = _paged_write(
            ki_cache, jnp.pad(k_idx, [(0, 0)] * 2 + pad).astype(
                ki_cache.dtype), layer, page_rows)
        index = (rows.unpack(jnp.pad(q_idx, [(0, 0)] * 3 + pad)),
                 rows.unpack(w_idx))

    def at_layer(stack):
        # what cannot take a stack reads its layer (post-write) as a slice
        return None if stack is None else lax.dynamic_index_in_dim(
            stack, layer, 0, keepdims=False)

    def ret(out):
        pools = (k_cache, v_cache) + (
            (k_scale, v_scale) if quantized else ()) + (
            () if ki_cache is None else (ki_cache,))
        return (out, *pools)

    def project(out):
        out = rows.pack(out.astype(x.dtype).reshape(B, S, nh * hd))
        if gate is not None:  # a gate a channel, on the computed rows
            out = (out.astype(jnp.float32) * jax.nn.sigmoid(
                gate.astype(jnp.float32)).reshape(out.shape)).astype(x.dtype)
        out = _out_proj(out, p["wo"])
        if cfg.use_bias:
            out = out + p["bo"]
        return ret(out)

    from ..ops.attention import _resolve

    # kernel injection: the Pallas cached-KV kernels when the registered
    # impl is the kernel one; ALiBi stays on the XLA lines below
    why_dense = []
    if _resolve() != "flash":
        why_dense.append("the registered attention is not the kernel one")
    if cfg.pos_embedding == "alibi":
        why_dense.append("ALiBi positions")
    kernel_ok = not why_dense

    if index is not None:
        from ..ops.pallas import sparse_latent_attention as sla
        from ..ops.pallas import sparse_paged_attention as spa

        out = None
        if kernel_ok:
            out, why_dense = spa.indexed_paged_attention(
                q, *index, k_cache, v_cache, ki_cache, cache_len, page_table,
                layer=layer, topk=cfg.index_topk, num_new=num_new)
        if out is not None:
            _note_attention_path("paged_sparse_kernel", kind=kind)
            return project(out)
        _note_attention_path("dense", why_dense, kind)
        view = lambda stack: _paged_gather(at_layer(stack), page_table)
        chosen = sla.dense_selection(
            sla.dense_index_scores(*index, view(ki_cache)),
            rows.slot_positions, cfg.index_topk)
        return project(spa.dense_sparse_paged_attention(
            q, view(k_cache), view(v_cache), chosen))
    if paged:
        out = None
        if kernel_ok and S == 1 and window is None and not held_paired:
            # single-token paged decode: the Pallas kernel gathers K/V
            # page-by-page through the table (scalar prefetch drives the
            # block index map) — no [B, capacity] view materializes
            from ..ops.pallas.decode_attention import decode_attention

            out = decode_attention(
                q, at_layer(k_cache), at_layer(v_cache), cache_len,
                k_scale=at_layer(k_scale), v_scale=at_layer(v_scale),
                page_table=page_table,
            )
            if out is None:
                why_dense.append("shapes the decode kernel does not take")
        elif kernel_ok and quantized:
            why_dense.append("int8 KV cache")
        elif kernel_ok:
            # a [slots, chunk] block: each slot's pages come in through the
            # table inside the kernel, whose loop follows the slot's
            # length — no per-slot view, no repeated head, no
            # capacity-wide score tensor
            from ..ops.pallas.paged_attention import paged_attention

            out, why_dense = paged_attention(
                q, k_cache, v_cache, cache_len, page_table, layer=layer,
                num_new=num_new, window=window,
                # (a model that keeps its kinds apart names the call by kind)
                name="paged_attention_" + kind if (
                    cfg.has_window or cfg.mixer_types) else None,
            )
        if out is not None:
            _note_attention_path("paged_kernel", kind=kind)
            return project(out)
        _note_attention_path("dense", why_dense, kind)
        # XLA path: gather the per-slot contiguous views (post-write, so
        # they reproduce the dense arena bitwise) and fall through to the
        # shared attention math below
        k_att = _paged_gather(at_layer(k_cache), page_table).reshape(
            B, -1, nkv, hd)
        v_att = _paged_gather(at_layer(v_cache), page_table).reshape(
            B, -1, nkv, hd)
        ks_att = _paged_gather_scale(at_layer(k_scale), page_table) \
            if quantized else None
        vs_att = _paged_gather_scale(at_layer(v_scale), page_table) \
            if quantized else None
    else:
        k_att, v_att, ks_att, vs_att = map(
            at_layer, (k_cache, v_cache, k_scale, v_scale))

    if window is not None:
        kernel_ok = False  # the contiguous kernels know no window
    elif not paged and isinstance(cache_len, int) and cache_len == 0 and S > 1:
        # fresh prefill: the new tokens only attend among themselves, so the
        # registered attention impl applies (kernel injection: Pallas flash
        # prefill on TPU); the decode matvec below stays the einsum path
        from ..ops.attention import attention as attn_op

        # fresh-prefill positions are a contiguous arange, so ALiBi rides as
        # slopes (in-kernel on the flash path — no [B,H,S,S] bias in HBM)
        slopes = (
            jnp.asarray(alibi_slopes(nh))
            if cfg.pos_embedding == "alibi"
            else None
        )
        return project(attn_op(q, rows.unpack(k), rows.unpack(v), causal=True,
                               alibi_slopes=slopes))
    if S == 1 and kernel_ok:
        # fused decode path: Pallas cached-KV attention over the contiguous
        # (or gathered) view when shapes fit
        from ..ops.pallas.decode_attention import decode_attention

        out = decode_attention(
            q, k_att, v_att, cache_len, k_scale=ks_att, v_scale=vs_att,
        )
        if out is not None:
            _note_attention_path("decode_kernel", kind=kind)
            return project(out)
    if not paged:
        _note_attention_path("dense", why_dense or ["a contiguous cache"],
                             kind)
    return project(_dense_cached_attention(
        cfg, q, k_att, v_att, cache_len, ks_att, vs_att, window=window
    ))


def _latent_cached_attention(cfg: TransformerConfig, p: Params, x: jax.Array,
                             rows: ChunkRows, layer, pools: Cache,
                             cache_len, page_table, num_new=None,
                             kind: str = "full", page_rows=None):
    """Latent attention of new tokens ``x`` (the rows ``rows`` computes:
    [B,S,D], or [1,T,D] packed) over the paged latent cache, in the absorbed
    form: returns (out, in x's layout, and the pools with this layer's rows
    written in place). The projections, the indexer's and the two ``wkv_b``
    products run on the computed rows, which are written to their places
    ``page_rows`` (:meth:`ChunkRows.page_rows`); the attention calls and
    the pooled index keys' write take the slot layout.

    A token caches its normed ``kv_latent_dim``-wide latent and ONE rotated
    ``qk_rope_dim``-wide key for all heads (``pools[LATENT]``); a head's
    keys and values are up-projections of the latent, so its no-position
    query is taken through the key half of ``wkv_b`` once and scores
    against the latent itself, and the value half is applied after the
    weighted sum of latents. With an indexer (``cfg.index_topk``) each
    token also caches an index key (``pools[INDEX]``), every cached token at
    or before a query is scored from it, and the query attends its
    ``index_topk`` best alone; with ``cfg.index_kpool`` > 1 the pool holds
    one key a BLOCK of that many tokens (:func:`_pooled_index_write`), a
    query scores the blocks whose last token is at or before it and attends
    the tokens of its ``index_topk`` best blocks and the tokens after its
    last whole block. With a head-wise output gate (``p["wgate"]``,
    ``d -> heads``: a latent layer of models/ling.py) ``y = W_o (sigmoid(x
    W_g)_h * o)``.

    With the kernel attention registered the Pallas calls of
    ops/pallas/sparse_latent_attention.py read the pools through the table
    (scores, selection and the walk over the chosen with an indexer; the
    same walk over every key without one); otherwise the XLA lines gather a
    per-slot view."""
    from ..ops.pallas import sparse_latent_attention as sla

    B, S, _ = x.shape  # of the computed rows
    H, kl, rd = cfg.num_heads, cfg.kv_latent_dim, cfg.qk_rope_dim
    nope, vd = cfg.qk_nope_dim, cfg.v_head_dim
    positions = rows.positions
    c_q, q_nope, q_pe, c_kv, k_pe = _latent_projections(cfg, p, x, positions)
    pad = latent_row_width(cfg) - cfg.latent_width

    def row(latent, pe):  # [..., kl] + [..., rd] -> a row of the pool's width
        parts = [latent, pe] + (
            [jnp.zeros((*pe.shape[:-1], pad), pe.dtype)] if pad else [])
        return jnp.concatenate(parts, axis=-1)

    pools = dict(pools)
    pools[LATENT] = _paged_write(
        pools[LATENT], row(c_kv, k_pe[:, :, 0]).astype(pools[LATENT].dtype),
        layer, page_rows)
    # the key half of wkv_b absorbed into the query, the value half applied
    # to the attended latents: [kl, H, nope | vd]
    wkv_b = p["wkv_b"].reshape(kl, H, nope + vd)
    q_abs = rows.unpack(row(
        jnp.einsum("bshn,chn->bshc", q_nope, wkv_b[..., :nope]), q_pe))
    scale = (nope + rd) ** -0.5 * cfg.attn_scale_mult

    q_idx = w_idx = None
    kpool = cfg.index_kpool
    if cfg.index_topk:
        # (the indexer's own rotated values, or the attention's)
        q_idx, k_idx, w_idx = _indexer(cfg, p["idx"], x, c_q, positions,
                                       cfg.index_rope_dim or rd)
        k_idx = k_idx.astype(pools[INDEX].dtype)
        if kpool > 1:
            pools = _pooled_index_write(
                cfg, pools, rows.unpack(k_idx), layer, jnp.broadcast_to(
                    jnp.asarray(cache_len, jnp.int32), (rows.B,)),
                num_new, page_table)
        else:
            pools[INDEX] = _paged_write(pools[INDEX], k_idx, layer, page_rows)
        q_idx, w_idx = rows.unpack(q_idx), rows.unpack(w_idx)

    from ..ops.attention import _resolve

    out, why_dense = None, []
    if _resolve() != "flash":
        why_dense.append("the registered attention is not the kernel one")
    elif not cfg.index_topk:
        out, why_dense = sla.latent_attention(
            q_abs, pools[LATENT], cache_len, page_table, layer=layer,
            scale=scale, v_width=kl, num_new=num_new)
    else:
        out, why_dense = sla.latent_sparse_attention(
            q_abs, q_idx, w_idx, pools[LATENT], pools[INDEX], cache_len,
            page_table, layer=layer, topk=cfg.index_topk, scale=scale,
            v_width=kl, num_new=num_new, kpool=kpool)
    if out is not None:
        _note_attention_path(
            "latent_sparse_kernel" if cfg.index_topk else "latent_kernel",
            kind=kind)
    else:
        _note_attention_path("dense", why_dense, kind)

        def view(name):
            return _paged_gather(lax.dynamic_index_in_dim(
                pools[name], layer, 0, keepdims=False), page_table)

        kv_view = view(LATENT)
        chosen = jnp.arange(kv_view.shape[1])[None, None, :] <= (
            rows.slot_positions[..., None])
        if cfg.index_topk and kpool > 1:
            chosen = sla.tokens_of_blocks(sla.dense_selection(
                sla.dense_index_scores(q_idx, w_idx, view(INDEX)),
                sla.last_block(rows.slot_positions, kpool), cfg.index_topk),
                rows.slot_positions, kpool)
        elif cfg.index_topk:
            chosen = sla.dense_selection(
                sla.dense_index_scores(q_idx, w_idx, view(INDEX)),
                rows.slot_positions, cfg.index_topk)
        out = sla.dense_sparse_attention(q_abs, kv_view, chosen, scale, kl)
    out = jnp.einsum("bshc,chv->bshv", rows.pack(out.astype(x.dtype)),
                     wkv_b[..., nope:])
    if "wgate" in p:  # one gate a head
        gate = jax.nn.sigmoid((x @ p["wgate"]).astype(jnp.float32))
        out = (gate[..., None] * out.astype(jnp.float32)).astype(x.dtype)
    return _out_proj(out.reshape(B, S, H * vd), p["wo"]), pools


def _dense_cached_attention(cfg: TransformerConfig, q: jax.Array,
                            k_att: jax.Array, v_att: jax.Array, cache_len,
                            ks_att=None, vs_att=None,
                            window: Optional[int] = None) -> jax.Array:
    """The XLA lines: q [B,S,H,hd] against a per-row contiguous K/V view
    [B, S_max, KV, hd] (int8 with its [B, KV, S_max, SL] scales), each row
    masked at its own frontier ``kpos <= cache_len + i`` and, with
    ``window``, below it ``kpos > cache_len + i - window``. float32
    throughout; the oracle for the Pallas kernels and the path for ALiBi,
    int8 and contiguous caches. Returns [B,S,H,hd] float32."""
    S = q.shape[1]
    nh, nkv, hd = cfg.num_heads, cfg.kv_heads, cfg.hd
    S_max = k_att.shape[1]
    kf = k_att.astype(jnp.float32)
    vf = v_att.astype(jnp.float32)
    if ks_att is not None:
        # scale cache is [B, KV, Smax, SL]; align to the [B, Smax, KV, hd]
        # value layout for the dense dequant (fallback path only)
        kf = kf * jnp.swapaxes(ks_att, 1, 2)[..., :1]
        vf = vf * jnp.swapaxes(vs_att, 1, 2)[..., :1]
    if nkv != nh:
        kf = jnp.repeat(kf, nh // nkv, axis=2)
        vf = jnp.repeat(vf, nh // nkv, axis=2)

    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), kf) * scale
    kpos = jnp.arange(S_max)[None, None, None, :]
    # [B or 1, 1, S, 1]: each row masks at its own frontier when cache_len
    # is the serving engine's per-slot vector
    qpos = jnp.asarray(cache_len).reshape(-1, 1, 1, 1) + (
        jnp.arange(S)[None, None, :, None]
    )
    if cfg.pos_embedding == "alibi":
        slopes = jnp.asarray(alibi_slopes(nh))
        logits = logits + slopes[None, :, None, None] * (
            -jnp.abs(kpos.astype(jnp.float32) - qpos.astype(jnp.float32))
        )
    seen = kpos <= qpos  # causal + cache bound
    if window is not None:
        seen = seen & (kpos > qpos - window)
    logits = jnp.where(seen, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, vf)


def forward_with_cache(cfg: TransformerConfig, params: Params, input_ids: jax.Array,
                       cache: Cache, cache_len, *,
                       dtype=jnp.bfloat16,
                       page_table=None,
                       page_table_win=None,
                       token_valid=None,
                       num_new=None,
                       token_budget: Optional[int] = None,
                       logit_rows=None,
                       return_moe_stats: bool = False):
    """Run new tokens through all layers against the cache: embed the rows,
    walk the layers (models/mixers.py ``cached_layers``, the one walk of
    every model: runs of a period of (mixer, MLP) kinds, a scan each, the
    cache their carry), then the final norm and the head.

    Which rows are computed. ``token_budget`` (with ``num_new``) is the
    caller's promise that the chunk holds at most that many real tokens
    (the slot engine: the scheduler's invariant 1). The residual stream is
    then ``[1, token_budget, D]``, the real tokens slot after slot
    (:class:`ChunkRows`), and the embedding, every norm, projection, rotary,
    router, MLP, expert dispatch and residual add run on those rows, not on
    ``B x S``, and a page pool is written from them, a row to its place
    (:meth:`ChunkRows.page_rows`); the attention calls alone see ``[B, S,
    ...]``. Without it (the lockstep engine, ``generate``: every row real),
    or on a mesh that shards the slot axis (:func:`row_layout`), the same
    lines run on the slots' ``[B, S]`` rows.

    input_ids: [B, S] (prefill) or [B, 1] (decode). cache_len: tokens already
    cached — a shared scalar, or a per-row [B] vector for the serving
    engine's ragged slot batch. Returns (fp32 logits [B, S, V], updated
    cache) — plus per-step MoE load-balance stats as a third element when
    ``return_moe_stats`` is set on a routed-expert model.

    ``logit_rows`` [B, K] int32 names the rows of each chunk whose logits
    the caller reads (the serving step: :func:`verify_window_rows`): the
    hidden state is gathered to them after the layers, so the final norm
    and the head run over K rows a slot and the logits are [B, K, V].

    ``page_table`` [B, max_pages] switches ``cache`` to the block-paged
    pool form (init_paged_cache): every layer scatters its chunk through
    the shared table and attends the slot's pages (_cached_attention).
    ``num_new`` [B] (the serving engine's real tokens per row) lets the
    paged kernel stop at the last key a real token needs. A model with
    window layers brings ``page_table_win``, the table of the window
    layers' pool (entries behind a slot's window point at the NULL page).

    MoE models route the MLP through the serving expert path
    (moe/sharded_moe.moe_serving_mlp): slot-ragged gather dispatch over
    experts ep-sharded on the mesh, with capacity derived from the
    STATIC token budget. ``token_valid`` [B, S] marks the real positions
    of a slot-ragged chunk (the serving engine passes
    ``pos < num_new``); padded tails, idle slots and done rows route to
    the null expert — zero capacity, zero combine weight — so occupancy
    changes never change routing pressure (or the compiled program).
    ``token_valid=None`` (the lockstep engine) treats every position as
    real and budgets capacity at B·S.
    """
    B, S = input_ids.shape
    from ..ops.quantizer import cast_floating
    from .sharding import current_topology

    cast = lambda t: cast_floating(t, dtype)
    if token_budget is not None and row_layout(
            current_topology())[0] != "packed":
        token_budget = None
    rows = ChunkRows(B, S, cache_len, num_new, token_budget)
    positions = rows.positions
    if rows.packed:
        token_valid = rows.valid
    x = cast(params["embed"]["tok"])[rows.pack(input_ids)]
    if cfg.pos_embedding == "learned":
        x = x + cast(params["embed"]["pos"])[positions]
    if cfg.embed_norm:
        x = _norm(cfg, cast(params["embed_norm"]), x)
    if cfg.scale_emb != 1.0:  # muP
        x = x * jnp.asarray(cfg.scale_emb, x.dtype)
    x = constrain(x, ("dp", "fsdp"), None, None)
    if cfg.hc_mult:  # hyper-connections: every stream begins as the embedding
        x = jnp.broadcast_to(x[None], (cfg.hc_mult, *x.shape))
    # the layers: runs of a period of (mixer, MLP) kinds, a scan each over
    # the same carry, the hidden rows and the cache, every leaf whole and in
    # the layout it came in (models/mixers.py)
    from .mixers import cached_layers, stacks_of

    x, new_cache, moe_stats = cached_layers(
        cfg, {k: cast(params[k]) for k in stacks_of(cfg)}, x, rows,
        dict(cache), cache_len, page_table, num_new, token_valid=token_valid,
        page_table_win=page_table_win)
    if cfg.hc_mult:  # and the head reads their sum
        x = jnp.sum(x.astype(jnp.float32), axis=0).astype(x.dtype)
    x = rows.unpack(x) if logit_rows is None else rows.take(x, logit_rows)
    x = _norm(cfg, cast(params["final_norm"]), x)
    if cfg.dim_model_base:  # muP
        x = x / jnp.asarray(cfg.hidden_size / cfg.dim_model_base, x.dtype)
    logits = lm_head_logits(cfg, params, x)
    return (logits, new_cache, moe_stats) if return_moe_stats else (
        logits, new_cache)
