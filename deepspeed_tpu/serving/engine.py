"""Continuous-batching serving engine: ONE jitted step, slot-ragged KV.

Parity: DeepSpeed-MII / FastGen's continuous-batching engine. The classic
``InferenceEngine.generate`` is lockstep: one compiled program per
``(B, prompt_len, total_len)`` and a single scalar ``cache_len`` shared by
the whole batch, so ragged traffic pads to the worst case or recompiles.
This engine is slot-based:

- a static KV arena ``[L, max_slots, capacity, KV, hd]`` (int8 scales
  included) holds one region per in-flight request;
- per-slot ``cache_len``/``last_pos`` VECTORS replace the scalar
  (models/decoding.py grew the ragged form of the cache write + mask;
  ops/pallas/decode_attention.py takes the [B] frontier in SMEM);
- ONE jitted step of fixed shape ``[max_slots, token_budget]`` consumes
  whatever mix of prompt chunks and decode tokens the scheduler packed
  (Dynamic SplitFuse), with active-slot masking for sampling — arbitrary
  arrival patterns run with ZERO recompiles after the first step;
- sampling state is per-slot and deterministic per request (its own RNG
  chain, temperature/top-k/top-p/penalty vectors), so every request's
  tokens are bit-reproducible against a single-request ``generate`` call
  with the same params and key — the CPU-mesh oracle in
  tests/test_serving.py.

TP serving: the KV arena shards its head axis over ``tp`` exactly like
the lockstep engine's cache; the step carries the arena with an explicit
sharding constraint so the jit carry stays sharding-closed (shardlint R2
— the seeded corpus pair ``slot_cache_carry_drift`` shows the drifted
form).

``serving.paged`` swaps the contiguous per-slot regions for a
**block-paged arena** (vLLM / FastGen blocked-KV): a global page pool +
per-slot page tables traced as int32 vectors, host-side page
allocation/refcounts/prefix cache in the scheduler, copy-on-write folded
into the step via a ``cow_src`` vector — same ONE-jitted-step
discipline, outputs bitwise identical to the contiguous arena (see
docs/serving.md "Block-paged, prefix-shared arena" and
tests/test_serving_paged.py).

``serving.spec`` adds **speculative decoding** (serving/spec.py): each
decode slot's row may carry up to ``max_draft`` host-proposed n-gram
drafts after its committed token (a spec slot claims k+1 budget rows),
the step verifies every window at once and emits 1..k+1 tokens per slot
(``out_tokens``/``n_emit``), and sample-and-match acceptance against the
per-slot RNG chain keeps spec-on output bitwise identical to spec-off —
see docs/serving.md "Speculative decoding" and tests/test_serving_spec.py.
"""

from __future__ import annotations

import time
import weakref
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Format, Layout
from jax.sharding import (NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from ..comm.topology import MeshTopology, ParallelDims
from ..config import DeepSpeedConfigError
from ..inference.engine import (InferenceEngine, _align_cache,
                                init_inference)
from ..models.decoding import (INDEX, SCALE_LANES, cache_layout,
                               forward_with_cache, init_cache,
                               init_paged_cache, paged_cow_copy,
                               record_attention_path, row_layout,
                               staged_promote, verify_window_rows)
from ..models.sharding import use_topology
from ..models.transformer import LAYER_KINDS
from ..profiling import steptrace as _steptrace
from ..profiling.steptrace import Phase
from ..utils.logging import log_dist
from .metrics import ServingMetrics
from .paging import STAGE_SLOTS
from .request import Request, RequestState, RequestStatus
from .scheduler import Scheduler, StepPlan
from .spec import spec_verify_stream, verify_window


def _storage(itemsize: int):
    """The float type a cache of ``itemsize`` bytes a value is kept in."""
    return jnp.float32 if int(itemsize) == 4 else jnp.bfloat16


def cache_token_bytes(cfg, storage_itemsize: int, quantized: bool) -> int:
    """Bytes one token keeps in one layer of the cache, scales left out:
    keys and values of every KV head (and, under an indexer, its index key
    as the pool pads it), or for latent attention its one latent row (as
    the pool pads it) and its indexer key. Derived from the page table's
    pools (``cache_layout``): each pool's page over the tokens of a page,
    in whole bytes a pool (so a sparse layer's one compressed key a page is
    spread over the page's tokens, and an indexer's one key a block of
    ``index_kpool`` over the block's); nothing for a model that keeps no
    page."""
    lay = cache_layout(cfg)
    return sum(p.row_bytes // lay.unit_page for p in lay.pools(
        lay.unit_page, _storage(storage_itemsize), quantized)
        if p.table == "page" and not p.name.endswith("_scale"))


def serving_kv_stream(cfg, max_slots: int, capacity: int,
                      storage_itemsize: int, quantized: bool,
                      tp: int = 1) -> Dict[str, Any]:
    """Analytic per-step KV-cache HBM traffic of the slot engine, in the
    shared analytic-streams schema (comm_logger.record_streams / planner /
    rule R8). Upper bound: the dense slot design streams the whole arena
    per step (k+v read + the chunk write); the Pallas decode kernel's
    per-tile predication reads less when frontiers are short."""
    per_tok = cache_token_bytes(cfg, storage_itemsize, quantized)  # k + v
    arena_tokens = cfg.total_layers * max_slots * capacity
    data = arena_tokens * per_tok
    scales = (
        arena_tokens * SCALE_LANES * 4 * 2 if quantized else 0
    )
    total = data + scales
    return {
        "kind": "hbm",
        "bytes_per_step": total,
        "per_device_bytes_per_step": total // max(tp, 1),
        "overlapped": False,  # this IS the step's compute traffic, not a
                              # hidden side stream — R8 prices it only if
                              # some config declares it overlapped
        "slots": max_slots,
        "capacity": capacity,
        "quantized": quantized,
    }


def _make_sample_window(vocab: int):
    """The step's sampler over every slot's verify window, reproducing
    InferenceEngine._build_decode.sample on each [1, V] row — same
    masking composition, same categorical key shape — so a slot's tokens
    match the single-request engine bitwise. The static top_k/top_p gates
    become traced ``where`` gates (identity branches are bitwise
    identity), and the filters they gate (two full-vocabulary sorts, a
    softmax and a cumulative sum a row) run under ONE ``lax.cond`` a
    step, taken when a live slot asks for top-k or top-p: a step of
    greedy and temperature-only slots skips what every gate would have
    discarded, and a step with one filtering slot runs the filters for
    all, as before. Either way it is one compile for every sampling
    mix."""

    def filter_row(l, tk, tp_):
        # top-k: the k-th largest as threshold; identity when tk <= 0
        sorted_desc = jnp.sort(l, axis=-1)[:, ::-1]
        kth = jnp.take_along_axis(
            sorted_desc, jnp.clip(tk, 1, vocab).reshape(1, 1) - 1, axis=-1
        )
        l = jnp.where((tk > 0) & (l < kth), -1e30, l)
        # top-p nucleus over the (possibly top-k-masked) row; identity
        # when tp_ >= 1.0. Same construction as the lockstep sampler:
        # smallest prefix reaching the mass, top-1 always survives.
        nuc = jnp.sort(l, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(nuc, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = (cum - probs) < tp_
        keep = keep.at[:, 0].set(True)
        pth = jnp.min(jnp.where(keep, nuc, jnp.inf), axis=-1, keepdims=True)
        return jnp.where((tp_ < 1.0) & (l < pth), -1e30, l)

    def draw(l, key, temp):
        greedy = jnp.argmax(l, axis=-1)
        sampled = jax.random.categorical(key, l, axis=-1)
        return jnp.where(temp == 0.0, greedy, sampled)[0]

    def sample_window(win, keys, live, temp, tk, tp_):
        """win [N, Kw, V], keys [N, Kw, 2], the rest [N] -> [N, Kw]."""
        l = win[:, :, None, :] / jnp.maximum(temp, 1e-6)[:, None, None, None]
        l = jax.lax.cond(
            jnp.any(live & ((tk > 0) | (tp_ < 1.0))),
            jax.vmap(jax.vmap(filter_row, in_axes=(0, None, None))),
            lambda l, tk, tp_: l,
            l, tk, tp_,
        )
        return jax.vmap(jax.vmap(draw, in_axes=(0, 0, None)))(l, keys, temp)

    return sample_window


def paged_kv_stream(cfg, num_pages: int, page_size: int, max_slots: int,
                    pages_per_slot: int, token_budget: int,
                    storage_itemsize: int, quantized: bool,
                    tp: int = 1) -> Dict[str, Any]:
    """Analytic per-step HBM traffic of the PAGED serving step, in the
    shared analytic-streams schema. Upper bound: the per-slot view gather
    reads every mapped logical page (the Pallas paged kernel's frontier
    predication reads less), the chunk scatter writes token_budget
    tokens, and the COW lane copies at most one page per slot. The POOL
    bytes themselves (the R6 capacity term) are priced from the traced
    step's invars — num_pages here is reported for the summary line."""
    # k + v of a token, with their scales
    per_tok = cache_token_bytes(cfg, storage_itemsize, quantized) + (
        2 * SCALE_LANES * 4 if quantized else 0)
    # (a model with mixers keeps pages for the layers whose kind keeps any;
    # its state layers' leaves are read and written once a step)
    L = cfg.paged_layers
    state = state_bytes(cfg, max_slots, storage_itemsize)
    gather = 2 * state + L * max_slots * pages_per_slot * page_size * per_tok
    scatter = L * max_slots * token_budget * per_tok
    cow = L * max_slots * page_size * per_tok
    total = gather + scatter + cow
    pool_tokens = L * (num_pages + 1) * page_size
    stream = {
        "kind": "hbm",
        "bytes_per_step": total,
        "per_device_bytes_per_step": total // max(tp, 1),
        "overlapped": False,  # the step's own compute traffic
        "paged": True,
        "page_size": page_size,
        "num_pages": num_pages,
        "pages_per_slot": pages_per_slot,
        "pool_bytes": pool_tokens * per_tok + state,
        "slots": max_slots,
        "quantized": quantized,
    }
    if state:  # the arena's bytes that are no page
        stream["state_bytes"] = state
    return stream


def paged_geometry(cfg, serving, max_tokens: int,
                   max_slots: int) -> Tuple[int, int, int]:
    """(page_size, pages_per_slot, num_pages) of the paged arena: logical
    pages a slot cover ``max_tokens`` + the ``token_budget`` write margin.
    A model NONE of whose layers keeps a page (``paged_layers`` 0: every
    mixer's cache is leaves a slot) has no pool for a table to index: its
    page is a slot's whole length, one a slot, so the scheduler's page plan
    IS its slot plan (admission by slot and ``max_tokens``, nothing to run
    dry, a table of one column), whatever ``serving.page_size`` and
    ``serving.num_pages`` ask."""
    if not cfg.paged_layers:
        return int(max_tokens) + int(serving.token_budget), 1, int(max_slots)
    pages_per_slot = serving.pages_per_slot(max_tokens)
    num_pages = int(serving.num_pages) or max_slots * pages_per_slot
    if num_pages < pages_per_slot:
        # liveness floor: after evicting everything else, ONE request must
        # still be able to run to max_tokens, or forced eviction can never
        # make progress
        raise DeepSpeedConfigError(
            f"serving.num_pages {num_pages} is below the liveness floor "
            f"ceil((max_tokens + token_budget) / page_size) = "
            f"{pages_per_slot}; one request could never finish")
    return int(serving.page_size), pages_per_slot, num_pages


def state_bytes(cfg, max_slots: int, storage_itemsize: int = 2) -> int:
    """Bytes of the arena that are no page: every pool the model's state
    layers keep a slot (``cache_layout``: a float32 state a lightning or
    kda layer, and a kda layer's convolution rows in the storage type); 0
    for a model without state layers."""
    lay = cache_layout(cfg)
    return sum(p.layers * int(max_slots) * p.row_bytes for p in lay.pools(
        lay.unit_page, _storage(storage_itemsize)) if p.table == "slot")


def kv_spill_page_bytes(cfg, page_size: int, codec_name: str,
                        quantized: bool) -> int:
    """At-rest bytes of ONE spilled KV page under ``codec_name`` —
    exactly what serving/paging.encode_page produces: float k/v leaves
    ride the wire codec on the canonical ``[L, rows, lanes]`` layout;
    int8 pool leaves spill raw (already 1 byte/elem) with their f32
    scales codec-compressed."""
    from ..comm.wires import get_codec

    codec = get_codec(codec_name)
    L, KV, hd = cfg.num_layers, cfg.kv_heads, cfg.hd
    if quantized:
        raw = L * page_size * KV * hd * 1 * 2  # int8 k + v, raw
        scales = codec.payload_nbytes(L, KV * page_size, SCALE_LANES) * 2
        return raw + scales
    return codec.payload_nbytes(L, page_size * KV, hd) * 2  # k + v


def kv_spill_stream(cfg, page_size: int, host_pages: int, codec_name: str,
                    quantized: bool, tp: int = 1) -> Dict[str, Any]:
    """The ``kv_spill`` analytic stream: steady-state host-DMA traffic of
    the tiered KV hierarchy, in the shared analytic-streams schema.
    Upper bound per step: STAGE_SLOTS pages promote in (the rotating
    staging buffer is that wide — serving/paging.STAGE_SLOTS) and, under
    sustained pressure, STAGE_SLOTS demotions go out to make room —
    both at the codec's AT-REST width. Declared ``overlapped``: the
    page-in rides under the decode step's math (the staged scatter runs
    before the gathers inside the ONE jitted step), so R8/R13 price it
    on the host link (``hw.host_bw``) against the step's compute
    window rather than as exposed tail."""
    page_bytes = kv_spill_page_bytes(cfg, page_size, codec_name, quantized)
    total = page_bytes * STAGE_SLOTS * 2  # in + out
    return {
        "kind": "offload",
        "bytes_per_step": total,
        "per_device_bytes_per_step": total // max(tp, 1),
        "overlapped": True,  # hidden under the decode step (double-
                             # buffered staging; R8 budgets the window)
        "stage_slots": STAGE_SLOTS,
        "page_size": page_size,
        "host_pages": host_pages,
        "codec": codec_name,
        "page_bytes_at_rest": page_bytes,
        "quantized": quantized,
    }


# the "auto" moe_a2a form's payload threshold: below this many bytes per
# ring hop the exchange is latency-bound (The Big Send-off's small-message
# regime) and stock collectives win; above it the chunked ppermute ride
# can hide under the per-chunk expert FFNs. Static per engine — the form
# never changes at run time, so neither does the compiled program.
MOE_A2A_AUTO_THRESHOLD_BYTES = 1 << 20
# ring granularity of the serving chunked form (capacity chunks whose
# hops pipeline against each other) — fixed pending an on-chip A/B; ONE
# constant so the engine and the lint trace cannot diverge
MOE_A2A_CHUNKS = 2


def serving_ep_size(moe_section, mcfg) -> int:
    """The ep mesh degree a MoE serving config serves (and lints) on:
    ``moe.ep_size`` clamped to what divides the experts; 1 for dense
    models. ONE clamp shared by trace_serving_step and
    analysis.lint_serving_config."""
    if not getattr(mcfg, "is_moe", False):
        return 1
    ep = max(int(getattr(moe_section, "ep_size", 1)), 1)
    if ep > 1 and mcfg.num_experts % ep != 0:
        return 1
    return ep


def resolve_moe_a2a_form(serving_moe_a2a: str, mcfg, topology,
                         token_budget: int, itemsize: int,
                         packed_experts: bool = False,
                         max_slots: Optional[int] = None) -> str:
    """Resolve serving.moe_a2a ("auto"|"stock"|"chunked") into the form
    the step will actually trace: "off" (dense model or no ep axis),
    "stock" (GSPMD collectives) or "chunked" (the decode-shaped
    chunked-ppermute ring, parallel/a2a_overlap.moe_decode_a2a). ONE
    resolution shared by ServingEngine and the shardlint serving trace,
    so the linted program is the served program — including the
    slot-grid divisibility gate (``max_slots`` when known: the ring
    needs max_slots · token_budget to divide ep, and the declared form
    must describe the exchange that actually runs). The planner's
    serving moe-a2a axis enumerates stock vs chunked explicitly."""
    if not getattr(mcfg, "is_moe", False):
        return "off"
    if topology.sizes.get("ep", 1) <= 1:
        return "stock"  # dense-replicated experts: nothing on the wire
    from ..parallel.a2a_overlap import moe_decode_a2a_applicable

    applicable = (
        not packed_experts
        and moe_decode_a2a_applicable(
            topology, E=mcfg.num_experts, F=mcfg.ffn,
            n_tokens=(
                int(max_slots) * int(token_budget)
                if max_slots is not None else None
            ),
        )
    )
    form = serving_moe_a2a
    if form == "auto":
        from ..moe.sharded_moe import eval_capacity

        cap = eval_capacity(mcfg, int(token_budget))
        per_hop = (
            (mcfg.num_experts // topology.sizes["ep"]) * cap
            * mcfg.hidden_size * itemsize
        )
        form = (
            "chunked" if per_hop >= MOE_A2A_AUTO_THRESHOLD_BYTES
            else "stock"
        )
    if form == "chunked" and not applicable:
        form = "stock"
    return form


def moe_a2a_scope_cfg(form: str):
    """The a2a_scope config the serving step traces under (enabled only
    for the chunked form; a DISABLED cfg forces the stock exchange so an
    ambient training scope can never leak in). ONE construction shared
    by ServingEngine and trace_serving_step."""
    from ..config import MoEOverlapA2AConfig

    return MoEOverlapA2AConfig(enabled=form == "chunked",
                               chunks=MOE_A2A_CHUNKS)


def moe_decode_stream(mcfg, topology, token_budget: int, itemsize: int,
                      form: str) -> Optional[Dict[str, Any]]:
    """The ``moe_decode_a2a`` analytic stream dict (None when no expert
    exchange exists: dense model or ep == 1) — ONE construction shared
    by ServingEngine.analytic_streams and trace_serving_step, so the
    R8-priced stream always describes the served exchange."""
    ep = topology.sizes.get("ep", 1)
    if not getattr(mcfg, "is_moe", False) or ep <= 1:
        return None
    from ..parallel.a2a_overlap import moe_decode_a2a_bytes_per_step

    ring = moe_decode_a2a_bytes_per_step(
        mcfg, topology, int(token_budget), itemsize=itemsize,
    )
    if not ring:
        return None
    return {
        **ring,
        "kind": "ici",
        "per_device_bytes_per_step": ring["bytes_per_step"],
        "overlapped": form == "chunked",
        "form": form,
        "ep": ep,
    }


def make_step_fn(cfg, dtype, vocab: int, cache_shardings=None,
                 max_draft: int = 0):
    """The ONE serving step (pure; jitted by ServingEngine, traced
    abstractly by the shardlint serving branch).

    Inputs (fixed shapes; N = max_slots, W = token_budget):
      tokens [N, W] int32   chunk tokens, 0-padded past ``num_new``; a
                            spec decode slot's row is its committed token
                            followed by ``spec_len`` drafts
      num_new [N] int32     real tokens per slot (0 = idle slot)
      start_pos [N] int32   per-slot write frontier (== cached tokens)
      fresh [N] bool        slot newly allocated → clear its seen row
      sample_flag [N] bool  slot samples this step
      spec_len [N] int32    draft tokens in the row's verify window
                            (0 = plain decode / final prefill feed)
      eos_id [N] int32      per-request eos (-1 = none): the verify
                            advance clamps at an emitted eos so the RNG
                            chain stops exactly where spec-off would
      rng [N, 2] uint32     per-slot PRNG keys (split ONLY when a token
                            is emitted, mirroring the lockstep chain)
      temperature/top_p/rep_penalty [N] f32, top_k [N] i32
      from_prev [N] bool    the row's first token and the slot's key are
                            the outputs of the step before, which the host
                            has not fetched yet (a decode row planned while
                            that step was in flight): taken from
      prev_tok [N, max_draft + 1] i32, prev_rng [N, 2] u32
                            that step's ``out_tokens`` and ``new_rng``,
                            handed from call to call on the device (never
                            donated: the host still fetches them). With
                            the flag all false the program computes bit
                            for bit what it computes without the operands

    ``max_draft`` is STATIC (the step's fixed output shape
    [N, max_draft + 1]); 0 disables speculation and reduces the verify
    window to the pre-spec single-token sampling tail, bitwise.

    Rows computed: the scheduler never plans more than W real tokens a step
    (its invariant 1), and the step hands that promise on
    (``token_budget=W``), so the layers' row-by-row work (embedding, norms,
    projections, rotary, routers, MLPs, expert dispatch, residual adds) runs
    over W packed rows, not N x W, and a page pool is written from those
    rows, each to its (page, offset) (``ChunkRows.page_rows``); the attention
    calls (and a contiguous arena's write) alone take the [N, W] slot layout
    (``models/decoding.ChunkRows``), and a mesh that shards the slot axis
    keeps it throughout (``ServingEngine.row_layout``). The final norm and the head run over the
    verify window's rows alone (``verify_window_rows``): nothing else of the
    chunk has logits.

    ``page_table`` / ``page_table_win`` (keywords) are the paged step's:
    :func:`make_paged_step_fn` is this step over its pools.

    Returns (caches, seen, out_tokens [N, max_draft + 1] i32,
    n_emit [N] i32, new_rng [N, 2]) — MoE models append a sixth
    ``moe_stats`` output (tokens-per-expert/routed/dropped counters; the
    arity is static per engine).

    MoE models route the MLP through the expert-parallel serving path:
    ``pos < num_new`` marks each row's REAL tokens, so padded tails,
    idle slots and done rows route to the null expert and capacity stays
    a constant of the static token budget W (the scheduler never packs
    more than W real tokens per step) — occupancy changes recompile
    nothing.
    """
    sample_window = _make_sample_window(vocab)
    moe = bool(getattr(cfg, "is_moe", False))

    def step(params, caches, seen, tokens, num_new, start_pos, fresh,
             sample_flag, spec_len, eos_id, rng, temperature, top_k, top_p,
             rep_penalty, from_prev, prev_tok, prev_rng, page_table=None,
             page_table_win=None):
        # (before the seen bookkeeping: a token is booked where it is fed)
        tokens = tokens.at[:, 0].set(
            jnp.where(from_prev, prev_tok[:, 0], tokens[:, 0]))
        rng = jnp.where(from_prev[:, None], prev_rng, rng)
        live = sample_flag & (num_new > 0)
        seen = _book_seen(seen, tokens, num_new, spec_len, fresh, vocab)
        token_valid = (
            jnp.arange(tokens.shape[1])[None, :] < num_new[:, None]
            if moe else None
        )
        rows = verify_window_rows(num_new, spec_len, max_draft,
                                  tokens.shape[1])
        logits, caches, *moe_stats = forward_with_cache(
            cfg, params, tokens, caches, start_pos, dtype=dtype,
            page_table=page_table, page_table_win=page_table_win,
            num_new=num_new, token_budget=tokens.shape[1],
            token_valid=token_valid, logit_rows=rows, return_moe_stats=moe,
        )
        out_tok, n_emit, new_rng = verify_window(
            sample_window, logits, tokens, rows, seen, spec_len, live, rng,
            temperature, top_k, top_p, rep_penalty, eos_id,
        )
        if cache_shardings is not None:
            # keep the donated arena carry sharding-closed across steps, and
            # what the next call takes back (seen, prev_tok, prev_rng) as it
            # was handed in: one executable serves every step
            caches = jax.lax.with_sharding_constraint(
                caches, cache_shardings
            )
            rep = NamedSharding(
                next(iter(cache_shardings.values())).mesh, P())
            seen, out_tok, new_rng = jax.lax.with_sharding_constraint(
                (seen, out_tok, new_rng), rep)
        return (caches, seen, out_tok, n_emit, new_rng, *moe_stats)

    return step


def _book_seen(seen, tokens, num_new, spec_len, fresh, vocab):
    """seen bookkeeping BEFORE the forward, exactly where the lockstep
    engine books tokens (prompt before the first sample, each fed token
    before its successor samples); fresh slots reset first and padded
    positions never book (the ragged-batch hazard fix). DRAFT tokens
    (the last ``spec_len`` of a row) never book either: they are
    speculative, and spec is host-gated to repetition_penalty == 1.0
    requests whose ``seen`` row is never consulted — so the matrix only
    ever holds committed-fed tokens."""
    N, W = tokens.shape
    rows = jnp.arange(N)
    seen = jnp.where(fresh[:, None], jnp.zeros_like(seen), seen)
    valid = jnp.arange(W)[None, :] < (num_new - spec_len)[:, None]
    return seen.at[
        rows[:, None], jnp.clip(tokens, 0, vocab - 1)
    ].max(valid)


def make_paged_step_fn(cfg, dtype, vocab: int, cache_shardings=None,
                       max_draft: int = 0, tiered: bool = False):
    """Paged twin of :func:`make_step_fn`: same fixed [N, W] discipline,
    two extra traced int32 inputs instead of per-slot cache regions —

      page_table [N, max_pages]  physical page per logical page (unmapped
                                 entries point at the NULL page, where
                                 idle slots' and chunk tails' padded
                                 writes land)
      cow_src [N]                copy-on-write source page (-1 = none):
                                 a slot diverging from a shared prefix
                                 mid-page copies that page onto its own
                                 frontier page BEFORE the chunk write

    ``tiered`` (serving.host_pages > 0) adds the host-tier staging pair
    BETWEEN cow_src and fresh —

      stage_kv {leaf: [L, STAGE_SLOTS, ...]}  the rotating staging
                                 buffer: up to STAGE_SLOTS host pages
                                 decoded for promotion this step
      stage_dst [STAGE_SLOTS]    physical destination page per staging
                                 slot (NULL page = unused slot: its
                                 scatter lands in the sink)

    and scatters it onto the pool FIRST (models/decoding.staged_promote
    — before the COW lane and the gathers), so a page promoted this
    step is attendable this step and the page-in H2D rides under the
    step's math. The flag is STATIC per engine: an untiered engine's
    program is byte-identical to pre-tiering, and the tiered program is
    ONE trace across every spill/restore mix (stage_dst is traced,
    never baked).

    Page allocation/free/refcounts live host-side in the scheduler; the
    step only COPIES (cow), SCATTERS (the chunk + staged promotions) and
    GATHERS (per-slot views) through the tables, so every arrival/
    sharing/divergence mix runs the same compiled program — zero
    recompiles after warmup."""
    slot_step = make_step_fn(cfg, dtype, vocab, cache_shardings, max_draft)
    layout = cache_layout(cfg)
    # (a cache that keeps no prefix shares no page: nothing to copy)
    shares_pages = layout.refused("prefix_cache") is None

    def step(params, caches, seen, tokens, num_new, start_pos, page_table,
             cow_src, *rest, page_table_win=None):
        if shares_pages:
            caches = paged_cow_copy(caches, page_table, start_pos, cow_src)
        return slot_step(params, caches, seen, tokens, num_new, start_pos,
                         *rest, page_table=page_table,
                         page_table_win=page_table_win)

    if len(layout.tables) > 1:
        # pages by layer kind: the window layers' table rides beside the
        # full layers'
        def kinds_step(params, caches, seen, tokens, num_new, start_pos,
                       page_table, page_table_win, *rest):
            return step(params, caches, seen, tokens, num_new, start_pos,
                        page_table, *rest, page_table_win=page_table_win)

        return kinds_step
    if not tiered:
        return step

    def tiered_step(params, caches, seen, tokens, num_new, start_pos,
                    page_table, cow_src, stage_kv, stage_dst, *rest):
        # scatter-before-gather: promoted pages land in the pool before
        # the COW lane and the per-slot view gathers, so a slot whose
        # last host page promotes THIS step also schedules this step
        caches = staged_promote(caches, stage_kv, stage_dst)
        return step(params, caches, seen, tokens, num_new, start_pos,
                    page_table, cow_src, *rest)

    return tiered_step


def compiler_param_formats(params):
    """The step's ``in_shardings`` entry for its parameters when their
    layouts are the compiler's to choose: ``Layout.AUTO`` a leaf, each
    leaf's sharding kept (arrays or ``ShapeDtypeStruct``\\ s that carry
    one)."""
    return jax.tree.map(lambda a: Format(Layout.AUTO, a.sharding), params)


def jit_step(step_fn, num_args: int, param_formats=None):
    """The serve step jitted as the engine jits it: caches and ``seen``
    donated and, with ``param_formats`` (:func:`compiler_param_formats`),
    the layout of every parameter leaf left to the compiler; every other
    argument as it arrives. Such a jit cannot be called: it is lowered for
    the engine's shapes and the ONE compiled executable is what runs
    (``Compiled.input_formats`` says what was chosen)."""
    if param_formats is None:
        return jax.jit(step_fn, donate_argnums=(1, 2))
    return jax.jit(step_fn, donate_argnums=(1, 2),
                   in_shardings=(param_formats, *[None] * (num_args - 1)))


def _device_bytes(leaf) -> int:
    """Bytes of ``leaf`` on one of its devices."""
    return leaf.addressable_shards[0].data.nbytes


def _free_device_bytes(device) -> float:
    """What ``device`` has left, where its backend says (the CPU's does
    not: no limit is assumed there)."""
    stats = device.memory_stats() or {}
    if "bytes_limit" in stats and "bytes_in_use" in stats:
        return stats["bytes_limit"] - stats["bytes_in_use"]
    return float("inf")


@dataclass
class _Flying:
    """A step the device has been handed and the host has not folded."""

    plan: StepPlan
    reads: tuple      # (out_tokens, new_rng, n_emit, moe counters | []):
    #   device arrays, their copies to the host begun at dispatch
    overlapped: bool  # dispatched while the step before was unfetched
    filtered: bool    # a live slot asked for top-k or top-p
    n: int            # the step's number: dispatches are counted from 1
    t0: Optional[float]  # when its dispatch began (the registry's clock)


def _state_counts(prefix: str, by_path: bool = False):
    """The counter of a kind whose cache is a state a slot: ``<prefix>rows``
    the real rows its recurrence runs, ``<prefix>state_slots`` the live
    states the step reads and writes, ``state_resets`` those of them that
    begin at zero (the step's ``rows`` stands for a prefix of none).
    ``by_path``: the slots by the way they take through a call that moves
    what a slot holds, ``<prefix>one_row_slots`` (one real row: a row tile)
    and ``<prefix>chunk_slots`` (more: the chunk's blocks)."""
    def count(engine, cl, nn) -> Dict[str, int]:
        busy = nn > 0
        out = {prefix + "state_slots": int(busy.sum()),
               "state_resets": int((busy & (cl == 0)).sum())}
        if prefix:
            out[prefix + "rows"] = int(nn.sum())
        if by_path:
            out[prefix + "one_row_slots"] = int((nn == 1).sum())
            out[prefix + "chunk_slots"] = int((nn > 1).sum())
        return out
    return count


def _conv_counts(engine, cl, nn) -> Dict[str, int]:
    """A convolution layer (its cache the rows a slot carries):
    :func:`_state_counts`' ``conv_rows``, ``conv_state_slots`` and
    ``state_resets``, and ``decode_slots`` the slots with exactly one real
    row (a model of this kind runs many slots a step: how many of them
    decode beside the prompt chunks)."""
    return {**_state_counts("conv_")(engine, cl, nn),
            "decode_slots": int((nn == 1).sum())}


def _sparse_counts(engine, cl, nn) -> Dict[str, int]:
    """A sparse layer: ``context_keys`` the cached tokens at or before every
    real query token; ``attended_sparse`` those a query attends, all of them
    at a position inside ``dense_len`` and ``topk`` blocks' worth past it;
    ``compressed_keys`` the compressed keys at or before every real query,
    which its selection scores, and ``compressed_rows`` those at or before a
    slot's last real query, which it reads once a slot; ``chosen_min`` the
    fewest K / V rows a slot's queries can have chosen between them (its
    last query's). Books the attended keys on the metrics."""
    geom = engine.config.block_sparse
    busy = nn > 0
    kept = geom.topk * geom.block_size
    context = nn * cl + nn * (nn + 1) // 2
    # rows at a position past dense_len whose context passes the kept
    # blocks attend the blocks' worth
    edge = max(geom.dense_len, kept)
    over = np.clip(cl + nn - edge, 0, nn)
    attended = context - (over * (cl + nn - kept) - over * (over - 1) // 2)
    compressed = sum(
        int(np.maximum((np.arange(c, c + n) + 1 - geom.kernel_size)
                       // geom.kernel_stride + 1, 0).sum())
        for c, n in zip(cl[busy], nn[busy]))
    last = (cl + nn)[busy]
    counts = {
        "context_keys": int(context.sum()),
        "attended_sparse": int(attended.sum()),
        "compressed_keys": compressed,
        "compressed_rows": int(np.maximum(
            (last - geom.kernel_size) // geom.kernel_stride + 1, 0).sum()),
        "chosen_min": int(np.where(last > edge, kept, last).sum()),
    }
    engine.metrics.on_keys("sparse", counts["attended_sparse"],
                           counts["chosen_min"])
    return counts


def _latent_counts(engine, cl, nn) -> Dict[str, int]:
    """A latent layer without an indexer: ``latent_rows`` the real query
    rows, ``latent_keys_walked`` the cached latents at or before each slot's
    last real row, which the walk reads once a slot, ``context_keys`` those
    at or before every real query."""
    return {"latent_rows": int(nn.sum()),
            "latent_keys_walked": int((cl + nn)[nn > 0].sum()),
            "context_keys": int((nn * cl + nn * (nn + 1) // 2).sum())}


def _mla_counts(engine, cl, nn) -> Dict[str, int]:
    """An indexed latent layer named by ``mixer_types`` (its index keys
    pooled by ``index_kpool``, 1 = a key a token): ``context_keys`` the
    cached tokens at or before every real query token; ``index_keys`` the
    POOLED keys its indexer scores, the whole blocks at or before every real
    query, and ``index_rows`` those at or before a slot's last real query,
    which it reads once a slot; ``attended_sparse`` the tokens a query
    attends, those of its ``index_topk`` best blocks and its tail;
    ``tail_keys`` the tokens attended because they lie after a query's last
    whole block; ``chosen_min`` the fewest latent rows a slot's queries can
    have chosen between them (its last query's); ``selection_tiles`` of
    ``selection_tiles_grid`` and ``score_tiles`` of ``score_tiles_grid`` as
    :func:`_selection_tiles` counts them. Books the attended keys on the
    metrics."""
    cfg = engine.config
    kp, topk = int(cfg.index_kpool), int(cfg.index_topk) or (1 << 62)
    counts = dict.fromkeys(("context_keys", "index_keys", "index_rows",
                            "attended_sparse", "tail_keys", "chosen_min"), 0)
    counts.update(_selection_tiles(engine, cl, nn, topk, kp))
    for c, n in zip(cl[nn > 0], nn[nn > 0]):
        seen = np.arange(c, c + n) + 1  # a query's context, itself included
        whole = seen // kp
        tail = seen - whole * kp
        attended = np.minimum(whole, topk) * kp + tail
        for key, add in (("context_keys", seen.sum()),
                         ("index_keys", whole.sum()),
                         ("index_rows", whole[-1]),
                         ("attended_sparse", attended.sum()),
                         ("tail_keys", tail.sum()),
                         ("chosen_min", attended[-1])):
            counts[key] += int(add)
    engine.metrics.on_keys("sparse", counts["attended_sparse"],
                           counts["chosen_min"])
    return counts


def _selection_tiles(engine, cl, nn, topk: int,
                     kpool: int = 1) -> Dict[str, int]:
    """What share of the indexer's two grids has work: ``selection_tiles``
    the 8-row tiles of the ``[max_slots, token_budget]`` step that hold a
    real row whose context passes ``topk`` (the programs of
    ``selection_topk`` that search; the others fetch no score), of
    ``selection_tiles_grid`` programs a call; ``score_tiles`` the (row
    tile, key block) trips ``indexer_scores`` runs (a slot's real rows in
    16-row tiles, those a large tile covers among them, x its context in
    key blocks), of ``score_tiles_grid`` over every tile and every block
    the tables map."""
    from ..ops.pallas.sparse_latent_attention import (score_tiles,
                                                      selection_tiles)

    tiles = selection_tiles(cl, nn, engine.token_budget, topk, kpool)
    trips, full = 0 * cl, 0  # a latent model without an indexer scores none
    if engine.config.index_heads:
        trips, full = score_tiles(
            cl, nn, engine.token_budget, engine.config.index_heads,
            engine.pages_per_slot, engine.page_size, kpool)
    return {"selection_tiles": int(tiles.sum()),
            "selection_tiles_grid": int(tiles.size),
            "score_tiles": int(trips.sum()),
            "score_tiles_grid": int(full * len(cl))}


def _page_counts(engine, cl, nn, kind: str = "full") -> Dict[str, int]:
    """A layer of ``kind`` whose pages ``key_counts`` walks (a kind of
    ``layer_pattern``, or a full layer that ``mixer_types`` names):
    ``attended_<kind>`` the keys visible to every real query token,
    ``fetched_<kind>`` the keys in the pages that hold one of them,
    whatever block the kernel reads them in; ``small_tile_slots_<kind>``
    the slots whose program of the paged call computes the small tile of
    its query stack alone (``small_tile_slots``: 0 where the grid is row
    tiled, or the kind's path is not the kernel). Books them on the
    metrics."""
    from ..ops.pallas import paged_attention as pa

    cfg, ps, mp = engine.config, engine.page_size, engine.pages_per_slot
    attended, fetched = pa.key_counts(
        cl, nn, ps, mp, cfg.window_of(kind), block_k=ps)
    small = 0
    if engine.metrics.attention_paged_kernel_kinds.get(kind):
        # (the kernel's own heads: a lane pair of 64-wide heads is one)
        G, KV, hd = pa.kernel_heads(
            cfg.num_heads // engine.topology.tp_size,
            cfg.kv_heads // engine.topology.tp_size, cfg.hd)
        small = pa.small_tile_slots(nn, G, engine.token_budget, pa.row_tile(
            engine.token_budget, G, KV,
            hd, ps, pa._block_pages(pa.DEFAULT_BLOCK_K, ps, mp),
            jnp.dtype(engine.dtype).itemsize,
            jnp.dtype(engine.engine.kv_cache_storage_dtype).itemsize))
    engine.metrics.on_keys(kind, attended, fetched, small)
    return {"attended_" + kind: attended, "fetched_" + kind: fetched,
            "small_tile_slots_" + kind: small}


# what a step's plan says of one layer of each mixer kind ``mixer_types`` may
# name (``models/transformer.MIXER_KINDS``): the keys ride the
# ``serve/device_step`` annotation
_KIND_COUNTS = {
    "full": _page_counts,
    "gdn": _state_counts("gdn_"),
    "conv": _conv_counts,
    "sparse": _sparse_counts,
    "lightning": _state_counts(""),
    "kda": _state_counts("kda_", by_path=True),
    "latent": _latent_counts,
    "mla": _mla_counts,
    "retention": _state_counts("retention_"),
}


class ServingEngine:
    """Request-level front end over one slot-ragged jitted step.

    Drive it with :meth:`submit` + :meth:`step` (one scheduler plan, one
    device step dispatched and one folded per call: ``step_order`` says in
    which order), or :meth:`run_until_idle` to drain everything in
    flight. ``clock`` is injectable for tests/replay."""

    def __init__(
        self,
        model=None,
        serving=None,
        engine: Optional[InferenceEngine] = None,
        clock=time.monotonic,
        metrics: Optional[ServingMetrics] = None,
        comm_logger=None,
        steptrace=None,
        healthwatch=None,
        name: Optional[str] = None,
        **engine_kwargs,
    ):
        from ..config import ServingConfig, _parse_dc

        if serving is None:
            serving = ServingConfig()
        elif isinstance(serving, dict):
            serving = _parse_dc(ServingConfig, serving)
        # resolve "auto" spec/paged/moe_a2a/kv knobs from the measured
        # knob-default table before ANY read below (spec_enabled, paged,
        # the pre-engine kv dtype kwarg) — conservative off on a miss
        from ..config import resolve_auto_knobs

        resolve_auto_knobs(
            serving,
            model_config=(getattr(engine, "config", None)
                          if engine is not None
                          else getattr(model, "config", None)),
            topology=getattr(engine, "topology", None),
        )
        serving.validate()
        self.serving = serving
        if engine is None:
            if model is None:
                raise ValueError("ServingEngine needs a model or an engine")
            if serving.kv_cache_dtype != "auto":
                engine_kwargs.setdefault(
                    "kv_cache_dtype", serving.kv_cache_dtype
                )
            engine_kwargs.setdefault("max_tokens", serving.max_tokens)
            engine = init_inference(model, **engine_kwargs)
        self.engine = engine
        self.config = engine.config
        self.topology = engine.topology
        self.dtype = engine.dtype
        self.clock = clock
        self.comm_logger = comm_logger
        # fleet identity: the router names each replica ("r0", "r1", ...)
        # so the shared steptrace timeline's serve/step spans say which
        # replica stepped; None = the single-engine path, no annotation
        self.name = name

        N, W = serving.max_slots, serving.token_budget
        self.max_slots, self.token_budget = N, W
        # speculative decoding (serving.spec): per-slot draft-then-verify
        # in the ONE step. max_draft is STATIC (the verify-window output
        # shape); per-slot/per-step draft counts ride as the traced
        # spec_len vector, so spec never adds a compile.
        spec_cfg = serving.spec
        self.spec_enabled = bool(getattr(spec_cfg, "enabled", False))
        self.max_draft = int(spec_cfg.max_draft) if self.spec_enabled else 0
        self.spec_ngram_n = int(getattr(spec_cfg, "ngram_n", 3))
        # per-request cap; the +W margin absorbs the chunk a full slot
        # writes past its frontier (padding rows, never attendable)
        self.max_tokens = min(serving.max_tokens, engine.max_tokens)
        # ---- MoE serving (ISSUE 14): expert-parallel decode ------------
        # the step routes the MLP through the slot-ragged expert path;
        # under an ep mesh axis the expert exchange takes the form
        # resolved here (ONE resolution shared with the shardlint trace)
        mcfg = engine.config
        self.moe_serving = bool(getattr(mcfg, "is_moe", False))
        self.moe_ep = self.topology.sizes.get("ep", 1)
        self._a2a_cfg = None
        self.moe_a2a_form = "off"
        if self.moe_serving:
            from ..ops.quantizer import PackedWeight

            packed_experts = any(
                isinstance(leaf, PackedWeight) and len(leaf.shape) == 4
                for leaf in jax.tree_util.tree_leaves(
                    engine.params,
                    is_leaf=lambda a: isinstance(a, PackedWeight),
                )
            )
            self.moe_a2a_form = resolve_moe_a2a_form(
                serving.moe_a2a, mcfg, self.topology, W,
                jnp.dtype(engine.dtype).itemsize,
                packed_experts=packed_experts, max_slots=N,
            )
            # the scope is entered around every step call (trace-time
            # protocol)
            self._a2a_cfg = moe_a2a_scope_cfg(self.moe_a2a_form)
        self.paged = bool(serving.paged)
        if self.paged:
            # ONE definition of the page math, fed the engine-clamped
            # max_tokens
            self.page_size, self.pages_per_slot, self.num_pages = (
                paged_geometry(mcfg, serving, self.max_tokens, N))
            self.capacity = self.pages_per_slot * self.page_size
            self.null_page = self.num_pages  # physical id of the sink page
        else:
            self.page_size = self.num_pages = self.pages_per_slot = None
            self.capacity = _align_cache(self.max_tokens + W)
        # ---- what the model's cache is and what it admits
        # (models/decoding.py cache_layout, docs/serving.md "Layer kinds"):
        # each operation the configuration switches on is asked once
        self.cache = cache_layout(mcfg)
        self.host_pages = int(getattr(serving, "host_pages", 0) or 0) \
            if self.paged else 0
        for op, on in (("paged false", not self.paged),
                       ("host_pages", self.host_pages),
                       ("fleet.prefill_replicas", self.paged and int(
                           serving.fleet.prefill_replicas)),
                       ("spec", self.spec_enabled)):
            if on:
                self.cache.refuse(op)
        prefix_cache = self.paged and bool(serving.prefix_cache)
        why = prefix_cache and self.cache.refused("prefix_cache")
        if why:
            log_dist(f"serving: prefix cache off: {why}")
            prefix_cache = False
        self.window_pages_per_slot = self.window_num_pages = None
        if self.kinds_paged:
            # a slot's window pages: the keys row 0 of a chunk still sees
            # up to the chunk's last key, and one page of misalignment;
            # the pool holds every slot's most, so it never runs dry
            self.window_pages_per_slot = (
                -(-(mcfg.attn_window + W) // self.page_size) + 1)
            self.window_num_pages = N * self.window_pages_per_slot
        # ---- tiered KV (serving.host_pages > 0, ISSUE 18): a pinned-
        # host second tier behind the HBM pool. The ENGINE owns the
        # store + spiller (movement needs device access: export/encode on
        # demotion, decode/stage on promotion); the SCHEDULER owns policy
        self.tiered = self.host_pages > 0
        self._host_store = self._spiller = None

        self.metrics = metrics or ServingMetrics(clock=clock)
        self.metrics.configure(N, num_pages=self.num_pages or 0,
                               host_pages=self.host_pages)
        # rows a step norms and projects to the vocabulary: each slot's
        # verify window, not its chunk
        self.metrics.head_rows_per_step = N * (self.max_draft + 1)
        # rows the layers' row-by-row work runs over: the plan's tokens
        # packed to the budget, or every slot's chunk where the mesh shards
        # the slot axis (models/decoding.row_layout, which the step's trace
        # asks too)
        self.row_layout, self.row_layout_reason = row_layout(self.topology)
        packed = self.row_layout == "packed"
        self.metrics.dense_rows_per_step = W if packed else N * W
        # rows a layer's cache write takes: a page pool is written from the
        # computed rows, a contiguous arena a slot's chunk at a time
        self.metrics.cache_rows_per_step = (
            W if packed and self.paged else N * W)
        if self.tiered:
            from .paging import HostPageStore, PageSpiller, export_pages

            self._host_store = HostPageStore(
                self.host_pages, codec=serving.spill_codec,
                spill_dir=serving.spill_dir,
            )
            # late-bound caches: demote only runs inside plan(), between
            # steps, when self._caches is the settled functional carry
            self._spiller = PageSpiller(
                self._host_store,
                lambda ids: export_pages(self._caches, ids),
                metrics=self.metrics,
            )
        # ---- steptrace: the registry is config-gated (None = no
        # registry exists and nothing is stored; the span sites below
        # still feed the profiler's trace, through steptrace.Phase) -----
        self.tracer = None
        self._serve_tracer = None
        self._steptrace_export_path = None
        if steptrace is not None:
            from ..config import SteptraceConfig

            stc = (
                steptrace if isinstance(steptrace, SteptraceConfig)
                else _parse_dc(SteptraceConfig, steptrace)
            )
            stc.validate()
            if stc.enabled:
                self.tracer = _steptrace.configure(max_spans=stc.max_spans)
                self._serve_tracer = _steptrace.ServeTracer(self.tracer)
                self.metrics.tracer = self._serve_tracer
                self._steptrace_export_path = stc.export_path
        # ---- healthwatch (profiling/healthwatch.py; None = the zero-
        # overhead path: no ring buffer, no watchdog taps, no spans).
        # Enabling it implies tracing — goodput buckets classify off the
        # serve/* spans — so a missing steptrace section turns one on. --
        self.healthwatch = None
        if healthwatch is not None:
            from ..config import HealthwatchConfig

            hwc = (
                healthwatch if isinstance(healthwatch, HealthwatchConfig)
                else _parse_dc(HealthwatchConfig, healthwatch)
            )
            hwc.validate()
            if hwc.enabled:
                from ..profiling import healthwatch as _healthwatch

                if self.tracer is None:
                    self.tracer = _steptrace.configure()
                    self._serve_tracer = _steptrace.ServeTracer(self.tracer)
                    self.metrics.tracer = self._serve_tracer
                self.healthwatch = _healthwatch.HealthWatch(
                    hwc, self.tracer, source="serve",
                    context={"config": {"serving": {
                        "max_slots": N, "token_budget": W,
                        "paged": self.paged,
                        "queue_limit": int(serving.queue_limit),
                        "max_tokens": int(self.max_tokens),
                        "spec_max_draft": int(self.max_draft),
                    }}},
                )
                self.metrics.healthwatch = self.healthwatch
        self.scheduler = Scheduler(
            max_slots=N,
            token_budget=W,
            queue_limit=serving.queue_limit,
            request_timeout_s=serving.request_timeout_s,
            eviction_backoff_s=serving.eviction_backoff_s,
            max_tokens=self.max_tokens,
            clock=clock,
            metrics=self.metrics,
            page_size=self.page_size if self.paged else None,
            num_pages=self.num_pages if self.paged else None,
            pages_per_slot=self.pages_per_slot if self.paged else None,
            prefix_cache=prefix_cache,
            spec_max_draft=self.max_draft,
            spec_ngram_n=self.spec_ngram_n,
            spiller=self._spiller,
            window=mcfg.attn_window if self.kinds_paged else 0,
            window_num_pages=self.window_num_pages,
            slot_state=mcfg.has_state,
        )

        # ---- the KV arena (contiguous slots, or a paged pool): its shapes
        # first. The step is compiled from them and the parameters are
        # re-laid (below) before a byte of the arena is allocated ---------
        def make_arena():
            if self.paged:
                caches = init_paged_cache(
                    self.config, self.num_pages, self.page_size,
                    engine.kv_cache_storage_dtype,
                    quantized=engine.kv_cache_quantized,
                    window_pages=self.window_num_pages, max_slots=N,
                )
            else:
                caches = init_cache(
                    self.config, N, self.capacity,
                    engine.kv_cache_storage_dtype,
                    quantized=engine.kv_cache_quantized,
                )
            return caches, jnp.zeros((N, self.config.vocab_size), jnp.bool_)

        cache_shapes, seen_shape = jax.eval_shape(make_arena)
        self._cache_shardings = None
        if self.topology.world_size > 1:
            mesh = self.topology.mesh
            # (a contiguous arena's leaves are laid out as the page pools
            # of their names: cache heads over tp, the slots unsharded, the
            # scheduler owns placement)
            specs = {p.name: p.spec for p in self.cache.pools(
                self.page_size or 1, engine.kv_cache_storage_dtype,
                engine.kv_cache_quantized)}
            self._cache_shardings = {
                k: NamedSharding(mesh, specs[k]) for k in cache_shapes}
            rep = NamedSharding(mesh, P())
        else:
            rep = SingleDeviceSharding(self.topology.devices[0])
        # tiered: the rotating in-step staging buffer (the PR-1 double-
        # buffer carry): TWO numpy fills alternate so the buffer the
        # device may still be copying from is never the one the next
        # step's promotions decode into; a zero twin serves idle steps.
        # Pool-leaf shapes with the page axis narrowed to STAGE_SLOTS.
        self._stage_idx = 0
        self._stage_np = None
        self._stage_zero_np = None
        if self.tiered:
            def stage_like():
                return {
                    k: np.zeros(
                        (v.shape[0], STAGE_SLOTS) + tuple(v.shape[2:]),
                        dtype=v.dtype,
                    )
                    for k, v in cache_shapes.items()
                }

            self._stage_np = [stage_like(), stage_like()]
            self._stage_zero_np = stage_like()
            self._stage_dst_null = np.full(
                STAGE_SLOTS, self.null_page, np.int32
            )

        if self.paged:
            step_fn = make_paged_step_fn(
                self.config, self.dtype, self.config.vocab_size,
                cache_shardings=self._cache_shardings,
                max_draft=self.max_draft, tiered=self.tiered,
            )
        else:
            step_fn = make_step_fn(
                self.config, self.dtype, self.config.vocab_size,
                cache_shardings=self._cache_shardings,
                max_draft=self.max_draft,
            )
        # the recompile counter: a trace-time side effect fires once per
        # XLA compile — the zero-recompiles-after-warmup assertion
        self.step_traces = 0
        # which attention the compiled step took — "paged_kernel" (Pallas,
        # work follows each slot's length), "paged_sparse_kernel" (the
        # indexer's two calls and the walk of
        # ops/pallas/sparse_paged_attention.py over the selection inside
        # paged K / V), "latent_sparse_kernel" (the
        # indexer, selection and sparse latent attention calls of
        # ops/pallas/sparse_latent_attention.py), "decode_kernel" or "dense"
        # (the XLA lines, with the reasons a kernel declined) — chosen at
        # trace time from what the step can observe, so recorded by the same
        # side effect
        self.attention_path: Optional[str] = None
        self.attention_paths: Dict[str, str] = {}
        self.attention_fallback: Tuple[str, ...] = ()
        self._kind_reasons: Dict[str, Tuple[str, ...]] = {}
        # a routed model's bank products, chosen at trace time likewise
        # (moe/sharded_moe.expert_bank_path): "touched_kernel" (the banks of
        # the experts a step reached are read) or "einsum" (every held
        # expert's) with why; None for a model without routed layers
        self.expert_path: Optional[str] = None
        self.expert_path_reason: Optional[str] = None
        self.metrics.state_bytes = state_bytes(
            mcfg, N, jnp.dtype(engine.kv_cache_storage_dtype).itemsize)
        self.metrics.hyper_streams = int(getattr(mcfg, "hc_mult", 0))
        if "index keys" in self.cache.rows:  # beside K and V
            self.metrics.index_pool_bytes = int(
                np.prod(cache_shapes[INDEX].shape)
                * cache_shapes[INDEX].dtype.itemsize)
        # a routed model with mixers: the held experts that got a row in the
        # step folded last (the device's own count, read with its tokens)
        self._experts_touched: Optional[int] = None
        self._held_assignments = 0

        def counting_step(*args):
            self.step_traces += 1
            with record_attention_path() as rec:
                out = step_fn(*args)
            self.attention_path = rec["path"]
            self.attention_fallback = rec["reasons"]
            self._kind_reasons = dict(rec["kind_reasons"])
            self.expert_path = rec["expert_path"]
            self.expert_path_reason = rec["expert_path_reason"]
            self.metrics.expert_touched_kernel = float(
                self.expert_path == "touched_kernel")
            if mcfg.mixer_types:
                # a path a mixer kind: "block_sparse_kernel" / "dense" for
                # sparse layers, "lightning_kernel", "kda_kernel",
                # "latent_kernel", "gdn_kernel" and "retention_kernel"
                # likewise, "paged_kernel" / "dense" for full layers,
                # "short_conv" (plain lines on either backend) for conv
                # layers; attention_path is the sparse layers', else the
                # full layers' (the last layer kind's where there is
                # neither)
                self.attention_paths = dict(rec["kinds"])
                self.attention_path = rec["kinds"].get(
                    "sparse", rec["kinds"].get("full", rec["path"]))
            self.metrics.attention_paged_kernel = float(
                self.attention_path in ("paged_kernel", "paged_sparse_kernel",
                                        "latent_sparse_kernel",
                                        "block_sparse_kernel", "kda_kernel",
                                        "gdn_kernel", "latent_kernel",
                                        "retention_kernel")
            )
            self.metrics.attention_paged_kernel_kinds = {
                kind: float(path in ("paged_kernel", "paged_sparse_kernel")
                            or bool(mcfg.mixer_types)
                            and path.endswith("_kernel"))
                for kind, path in rec["kinds"].items()
            }
            return out

        # ---- the step's arguments after the parameters, abstract: what it
        # is lowered for (lower_step) and what every call hands it
        def sds(a, sharding=None):
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)

        def vec(dt, *tail, sharding=None):
            return jax.ShapeDtypeStruct((N, *tail), dt, sharding=sharding)

        paged_avals = ()
        if self.paged:
            paged_avals = (
                *[vec(jnp.int32, self.pages_per_slot)] * len(self.cache.tables),
                vec(jnp.int32))
            if self.tiered:
                paged_avals += (jax.tree.map(sds, self._stage_zero_np),
                                sds(self._stage_dst_null))
        self._step_avals = (
            {k: sds(v, (self._cache_shardings or {}).get(k, rep))
             for k, v in cache_shapes.items()},
            sds(seen_shape, rep),
            vec(jnp.int32, W), vec(jnp.int32), vec(jnp.int32), *paged_avals,
            vec(jnp.bool_), vec(jnp.bool_), vec(jnp.int32), vec(jnp.int32),
            vec(jnp.uint32, 2), vec(jnp.float32), vec(jnp.int32),
            vec(jnp.float32), vec(jnp.float32), vec(jnp.bool_),
            vec(jnp.int32, self.max_draft + 1, sharding=rep),
            vec(jnp.uint32, 2, sharding=rep),
        )
        # ---- the ONE compiled step, and the parameters in the layouts it
        # reads them in (docs/serving.md "Parameter layouts")
        self._compile_step(counting_step)
        self._adopt_params()
        # ---- the arena itself
        caches, seen = make_arena()
        self._caches = jax.device_put(caches, self._cache_shardings or rep)
        self._seen = jax.device_put(seen, rep)
        # ---- the order of a turn. "overlapped": step n+1 is planned and
        # dispatched before step n is fetched, so the device goes from one
        # step into the next while the host folds the first; the plan is a
        # projection (Scheduler.plan(ahead_of=...)). "serial" (plan,
        # dispatch, fetch and fold of one step in one call) where the
        # projection is impossible, with the reason: a property of the
        # engine, not a key
        self.step_order, self.step_order_reason = "overlapped", None
        if self.max_draft > 0:
            self.step_order_reason = (
                "a verify window advances a slot by n_emit, known only "
                "after the fetch, and drafts are proposed from the tokens "
                "it emitted")
        elif self.tiered:
            self.step_order_reason = (
                "the fold hands back the staging buffer and the host keys "
                "that the next plan's promotions and demotions use")
        if self.step_order_reason:
            self.step_order = "serial"
        self._flying: Optional[_Flying] = None  # dispatched, not folded
        self._dispatched = 0  # steps handed to the device, ever
        # the newest step's out_tokens and new_rng, the next call's
        # operands whether or not a row of it reads them (zeros before the
        # first step, placed as the step's outputs are)
        self._prev = (
            jax.device_put(np.zeros((N, self.max_draft + 1), np.int32), rep),
            jax.device_put(np.zeros((N, 2), np.uint32), rep),
        )
        # lazily-jitted fleet-handoff page scatter (pool donated; one
        # compile per distinct transferred-page count, bounded by
        # pages_per_slot)
        self._import_pages_fn = None
        # static per-step wire bytes of the expert exchange (0 without an
        # ep axis) — fed to the metrics counters and declared as the
        # moe_decode_a2a analytic stream (R8 prices it)
        self._moe_a2a_step_bytes = 0
        stream = moe_decode_stream(
            self.config, self.topology, W,
            jnp.dtype(self.dtype).itemsize, self.moe_a2a_form,
        )
        if stream:
            self._moe_a2a_step_bytes = int(stream["bytes_per_step"])
        arena = (
            f"pages={self.num_pages}x{self.page_size}tok "
            f"({self.pages_per_slot}/slot)"
            + (
                f" +window={self.window_num_pages} "
                f"({self.window_pages_per_slot}/slot)"
                if self.kinds_paged else ""
            )
            + (
                f" +host={self.host_pages}@{serving.spill_codec}"
                + ("+nvme" if serving.spill_dir else "")
                if self.tiered else ""
            )
            if self.paged else f"capacity={self.capacity}/slot"
        )
        log_dist(
            f"ServingEngine{f'[{name}]' if name else ''}: "
            f"slots={N}, token_budget={W}, {arena}, kv="
            f"{'int8' if engine.kv_cache_quantized else jnp.dtype(engine.kv_cache_storage_dtype).name}, "
            f"tp={self.topology.tp_size}, spec="
            f"{f'ngram(k<={self.max_draft})' if self.max_draft else 'off'}"
            f", order={self.step_order}, rows={self.row_layout}"
            + (
                f", moe=ep{self.moe_ep}/{self.moe_a2a_form}"
                f"/{self.expert_path}"
                if self.moe_serving else ""
            )
        )
        if self.healthwatch is not None:
            # price comm-exposed goodput off the declared streams (only
            # unoverlapped ici/offload kinds count — the KV arena's hbm
            # stream IS the step's compute traffic, not exposed wire)
            self.healthwatch.set_comm_estimate_from_streams(
                self.analytic_streams()
            )

    @property
    def kinds_paged(self) -> bool:
        """The arena keeps pages by layer kind: a second pool and page table
        for the window layers (docs/serving.md "Layer kinds")."""
        return self.paged and len(self.cache.tables) > 1

    # ---------------------------------------------------- parameter layouts
    def _compile_step(self, counting_step) -> None:
        """Lower the step for this engine's shapes and compile it, once:
        ``self._step`` is the jit (what :meth:`lower_step` lowers and whose
        traces ``step_traces`` counts), ``self._step_exec`` the executable
        every dispatch calls. The layouts of the parameter leaves are the
        compiler's to choose (``param_layout`` ``"compiled"``); where that
        lowering or its compile is refused the step is compiled for the
        layouts the leaves are held in (``"held"``, with the refusal)."""
        num_args = 1 + len(self._step_avals)
        self.param_layout, self.param_layout_reason = "compiled", None
        try:
            self._step = jit_step(counting_step, num_args,
                                  compiler_param_formats(self.engine.params))
            self._step_exec = self._lower_step().compile()
        except Exception as e:  # noqa: BLE001 - whatever refuses the choice
            self.param_layout = "held"
            self.param_layout_reason = (
                "the step could not be compiled with its parameters' "
                f"layouts left open ({type(e).__name__}: "
                f"{str(e).splitlines()[0] if str(e) else ''})")
            log_dist(f"serving: parameter layouts held: "
                     f"{self.param_layout_reason}")
            self.step_traces = 0
            self._step = jit_step(counting_step, num_args)
            self._step_exec = self._lower_step().compile()

    def _adopt_params(self) -> None:
        """Put ``engine.params`` into the layouts the compiled step reads
        them in (``Compiled.input_formats``: what the compiler reports for
        this model, mesh and shapes). The leaves held in another layout are
        re-laid in ONE jitted identity whose outputs have the formats asked
        for, the inputs donated: a re-laid leaf cannot alias its input, so
        for the length of the call the moved leaves exist twice, and after
        it the old ones are gone, whoever else held them. Where the device
        has not the room for all of them at once they go in as few calls as
        fit, largest first, a leaf that fits beside nothing alone. Leaves
        that need no move stay the same arrays; the tree, shapes and dtypes
        do not change, so every other reader of ``engine.params``
        (``generate``, checkpoints, a reference) goes on as it was."""
        leaves, tree = jax.tree.flatten(self.engine.params)
        wanted = tree.flatten_up_to(self._step_exec.input_formats[0][0])
        size = {i: _device_bytes(a)
                for i, (a, f) in enumerate(zip(leaves, wanted))
                if a.format.layout != f.layout}
        moved = sorted(size, key=lambda i: -size[i])
        nbytes = sum(size.values())
        self.metrics.relaid_param_leaves = len(moved)
        self.metrics.relaid_param_bytes = nbytes
        self.metrics.param_relayout_s = 0.0
        if moved:
            free = _free_device_bytes(self.topology.devices[0])
            calls, room = [], []  # first fit, largest first
            for i in moved:
                call = next(
                    (c for c, left in enumerate(room) if size[i] <= left),
                    None)
                if call is None:
                    call = len(calls)
                    calls.append([])
                    room.append(free)
                calls[call].append(i)
                room[call] -= size[i]
            # whatever still draws or loads the weights is not the re-lay's
            jax.block_until_ready([leaves[i] for i in moved])
            t0 = time.monotonic()
            with Phase(self.tracer, "serve/param_relayout", "serve",
                       leaves=len(moved), bytes=nbytes, calls=len(calls)):
                for call in calls:
                    news = jax.jit(
                        lambda *ls: ls,
                        donate_argnums=tuple(range(len(call))),
                        out_shardings=tuple(wanted[i] for i in call),
                    )(*[leaves[i] for i in call])
                    # the next call, or the arena, is allocated once this
                    # one's donated inputs are free
                    jax.block_until_ready(news)
                    for i, new in zip(call, news):
                        leaves[i] = new
            self.metrics.param_relayout_s = time.monotonic() - t0
            self.engine.params = tree.unflatten(leaves)
            log_dist(
                f"serving: {len(moved)} parameter leaves "
                f"({nbytes / 2**20:.1f} MiB a device) re-laid for the "
                f"compiled step in {self.metrics.param_relayout_s:.2f} s, "
                f"{len(calls)} call(s)")
        # weakly: whoever replaces the weights frees the old set first
        self._adopted = [weakref.ref(a) for a in leaves]

    def _params_adopted(self) -> bool:
        """Are ``engine.params`` still the arrays :meth:`_adopt_params` left
        there (about 40 us for 200 leaves, in a turn the device hides)."""
        leaves = jax.tree.leaves(self.engine.params)
        return len(leaves) == len(self._adopted) and all(
            ref() is a for ref, a in zip(self._adopted, leaves))

    # ------------------------------------------------------------- intake
    def submit(self, request: Request) -> RequestState:
        return self.scheduler.submit(request)

    # ------------------------------------------------------------- stepping
    def step(self) -> List[RequestState]:
        """One turn: plan and dispatch the next device step, then fold the
        one in flight (fetch its tokens, deliver them, retire what
        finished). Returns the requests the FOLDED step finished. In the
        overlapped order (``step_order``) that is the step dispatched by
        the call before: the first call after idle dispatches and returns
        ``[]``, and a call with nothing to plan folds what is in flight. In
        the serial order it is the step this call dispatched."""
        hw = self.healthwatch
        if hw is None:
            return self._step_inner()
        hw.on_step_start()
        traces_before = self.step_traces
        steps_before = self.metrics.steps
        finished = self._step_inner()
        if self.metrics.steps > steps_before:
            # a device step actually ran (idle ticks accrue as idle)
            hw.on_serve_step(
                step=self.metrics.steps, metrics=self.metrics,
                compiled=self.step_traces - traces_before,
            )
        return finished

    def _step_inner(self) -> List[RequestState]:
        # the plan in flight, which the next is projected over (None in
        # the serial order: every step is folded by the call it began in)
        due = self._flying
        ahead = due.plan if due is not None else None
        if ahead is None and not self.scheduler.has_work:
            # an idle tick (nothing queued, slotted or in flight) is told
            # BEFORE serve/step opens and writes no span: an annotation
            # cannot be taken back. The scheduler still ticks (its page
            # gauges follow the last fold)
            idle = self.scheduler.plan()
            assert idle is None, "a plan from a scheduler without work"
            return []
        # serve/step parent; serve/plan, serve/dispatch, serve/device,
        # serve/complete children cover the whole of it
        # (tools/trace_report.py --validate checks the coverage). In the
        # overlapped order plan and dispatch are the NEXT step's, device
        # (what is left of the wait) and complete the step's in flight:
        # each span carries the number of the step that caused it
        tr = self.tracer
        step_args = {"step": self.metrics.steps + 1}
        if self.name is not None:
            step_args["replica"] = self.name
        with Phase(tr, "serve/step", "serve", **step_args) as step_sp:
            with Phase(tr, "serve/plan", "serve") as plan_sp:
                plan = self.scheduler.plan(ahead_of=ahead)
                if plan is None and ahead is None:
                    # the scheduler held requests and planned none of
                    # them: no device step ran. (No way into this is known:
                    # admission is eager, a live slot always schedules and
                    # a starved pool evicts until one does; the scheduler's
                    # answer is taken all the same.) Whether a turn is a
                    # step is known only here, after the annotation was
                    # entered under its name: the registry drops both
                    # spans, the profile keeps a serve/step that says
                    # dispatched=0, folded=0
                    step_sp.annotate(dispatched=0, folded=0, overlapped=0)
                    plan_sp.cancel()
                    step_sp.cancel()
                    return []
                step_sp.annotate(**self._turn_args(plan, due))
            return self._run_plan(plan)

    def _turn_args(self, plan: Optional[StepPlan],
                   due: Optional["_Flying"]) -> Dict[str, int]:
        """What ``serve/step`` says of its turn: the number of the step it
        dispatches and of the one it folds (0: none), so a reader pairs a
        step's dispatch with its fold."""
        dispatched = self._dispatched + 1 if plan is not None else 0
        turn = dict(
            dispatched=dispatched,
            folded=(dispatched if self.step_order == "serial"
                    else due.n if due is not None else 0),
            overlapped=int(plan is not None and due is not None),
        )
        if plan is not None:
            turn["scheduled_tokens"] = int(plan.total_tokens)
            if plan.spec_len is not None and plan.spec_len.any():
                # spec observability: how many of this step's budget rows
                # are draft (verify-window) rows — trace_report shows it
                # per step
                turn["spec_draft_tokens"] = int(plan.spec_len.sum())
        return turn

    def _run_plan(self, plan: Optional[StepPlan]) -> List[RequestState]:
        """Dispatch ``plan`` (None: nothing to plan) and fold the step
        that is due: in the overlapped order the one dispatched a call
        ago, which the device ran while the host planned this one; in the
        serial order the one just dispatched."""
        due, self._flying = self._flying, None
        if plan is not None:
            self._flying = self._dispatch(plan, overlapped=due is not None)
        if self.step_order == "serial":
            due, self._flying = self._flying, None
        return self._fold(due) if due is not None else []

    def _dispatch(self, plan: StepPlan, overlapped: bool) -> "_Flying":
        """Hand one plan to the device. The jitted call returns at once
        (it queues behind the step in flight); the three small results the
        host reads start their way back here, so the fold finds them
        landed."""
        # dispatch span covers host-side array staging (the per-slot
        # numpy fills below, including jnp uploads) + the jit call; the
        # device span then waits for the outputs, so compile time lands
        # in dispatch (the first-step TTFT spike is visible as such) and
        # device wait time in device
        n = self._dispatched + 1
        # the step's size and mix ride its dispatch AND its fold's wait
        # (_fold), so a reader needs one event a step, on the clock the
        # device trace has: microseconds, whether or not a profile is taken
        with Phase(self.tracer, "serve/dispatch", "serve",
                   step=n, **plan.held()) as dispatch_sp:
            N = self.max_slots
            temp = np.zeros(N, np.float32)
            top_k = np.zeros(N, np.int32)
            top_p = np.ones(N, np.float32)
            penalty = np.ones(N, np.float32)
            eos = np.full(N, -1, np.int32)
            rng = np.zeros((N, 2), np.uint32)
            for w in plan.work:
                req = w.state.request
                temp[w.slot] = req.temperature
                top_k[w.slot] = req.top_k
                top_p[w.slot] = req.top_p
                penalty[w.slot] = req.repetition_penalty
                eos[w.slot] = req.eos_token_id
                rng[w.slot] = np.asarray(w.state.rng, np.uint32)
            spec_len = (
                plan.spec_len if plan.spec_len is not None
                else np.zeros(N, np.int32)
            )
            from_prev = (
                plan.from_prev if plan.from_prev is not None
                else np.zeros(N, np.bool_)
            )
            if self.paged:
                # idle rows need no dead-tail repoint: a packed step has no
                # row of theirs to write, and in the slot layout the
                # scheduler hands them an all-NULL page-table row, so their
                # padded W-wide writes land in the NULL sink page
                start_pos = plan.start_pos
                tables = (plan.page_table, plan.page_table_win)[
                    :len(self.cache.tables)]
                paged_args = (*tables, plan.cow_src)
                if self.tiered:
                    paged_args += self._stage_args(plan)
                # a plain one-kind cache pays for the count only under the
                # tracer
                keys = (self._count_keys(plan)
                        if self.cache.rows or self.tracer is not None else {})
                if keys:
                    dispatch_sp.annotate(**keys)
            else:
                keys = {}
                # rows the plan left idle (num_new == 0) still get a W-wide
                # padded cache write — repoint it at the DEAD TAIL margin
                # [capacity - W, capacity), which by construction never holds
                # live tokens (frontiers stop at max_tokens <= capacity - W).
                # Without this, an idle ACTIVE slot's row would write garbage
                # at its plan-default start_pos of 0, clobbering cached prompt
                # K/V the moment a scheduling policy ever skips a live slot.
                start_pos = np.where(
                    plan.num_new > 0, plan.start_pos,
                    self.capacity - self.token_budget,
                ).astype(np.int32)
                paged_args = ()
            traces_before = self.step_traces
            if not self._params_adopted():
                # the weights were replaced since (another seed's, a
                # checkpoint's): the executable takes them in its layouts
                self._adopt_params()
            # the step's attention work rides the profiler's host trace with
            # the call it describes (free while no trace is being taken)
            with jax.profiler.TraceAnnotation(
                    "serve/device_step", **keys,
                    dense_rows=self.metrics.dense_rows_per_step):
                # the plan's numpy vectors go to the compiled call as they
                # are: it uploads them itself, without a device_put apiece
                outs = self._step_exec(
                    self.engine.params, self._caches, self._seen,
                    plan.tokens, plan.num_new, start_pos, *paged_args,
                    plan.fresh, plan.sample, spec_len, eos, rng, temp, top_k,
                    top_p, penalty, from_prev, *self._prev,
                )
            # the step is the device's now: only then does it take its number
            self._dispatched = n
            self._caches, self._seen, out_tok, n_emit, new_rng, *moe = outs
            self._prev = (out_tok, new_rng)
            # one fetch for everything the host reads, begun now
            reads = (out_tok, new_rng, n_emit, moe and (
                moe[0]["tokens_per_expert"], moe[0]["drop_fraction"],
                moe[0].get("unrouted_tokens"),
                moe[0].get("experts_touched")))
            for a in jax.tree_util.tree_leaves(reads):
                a.copy_to_host_async()
            fl = _Flying(
                plan, reads, overlapped,
                # did the step pay for the sampler's sorts: the step's own
                # predicate, from the vectors the host filled for it
                filtered=bool(np.any(
                    plan.sample & (plan.num_new > 0)
                    & ((top_k > 0) | (top_p < 1.0)))),
                n=n, t0=dispatch_sp.t0,
            )
            dispatch_sp.annotate(traced=self.step_traces - traces_before)
        return fl

    def _fold(self, fl: "_Flying") -> List[RequestState]:
        """Fetch a dispatched step's results and fold them into the
        requests: the one place a token reaches the host."""
        plan = fl.plan
        # the host blocked on the results of the step in flight: the wait
        # itself, whether or not anything is being traced (the copies to
        # the host began at dispatch; device_get below finds them landed)
        with Phase(self.tracer, "serve/device", "serve",
                   step=fl.n, **plan.held()) as device_sp:
            jax.block_until_ready(fl.reads)
        if self._serve_tracer is not None:
            # prompt chunks fed this step become request-scoped spans
            # covering the dispatch+device window (statuses read BEFORE
            # complete() advances them; a row whose request went while
            # the step flew has no tree to hang on)
            for w in plan.work:
                if w.n_tokens > 0 \
                        and self.scheduler.slots[w.slot] is w.state \
                        and w.state.status is RequestStatus.PREFILL:
                    self._serve_tracer.on_chunk(
                        w.state, w.n_tokens, fl.t0, device_sp.t1
                    )
        with Phase(self.tracer, "serve/complete", "serve", step=fl.n):
            out_tok, new_rng, n_emit, moe_stats = jax.device_get(fl.reads)
            finished = self.scheduler.complete(
                plan, out_tok, new_rng, n_emit=n_emit,
            )
            self.metrics.on_step(
                filtered=fl.filtered, overlapped=fl.overlapped)
            if moe_stats:
                # expert load-balance counters (ISSUE 14 satellite): the step
                # already computed them on device
                self.metrics.on_moe(
                    moe_stats[0], float(moe_stats[1]),
                    a2a_bytes=self._moe_a2a_step_bytes, unrouted=moe_stats[2],
                    touched=moe_stats[3],
                )
                if moe_stats[3] is not None:
                    self._experts_touched = int(moe_stats[3])
                    # (row, chosen expert) pairs that landed on a held expert
                    self._held_assignments = int(np.sum(moe_stats[0]))
            if self.comm_logger is not None:
                self.comm_logger.record_streams(self.analytic_streams())
        return finished

    def _count_keys(self, plan: StepPlan) -> Dict[str, int]:
        """The step's attention work by layer kind, from the plan (one
        layer of the kind: :func:`_page_counts`; ``rows`` the real query
        tokens), booked on the metrics."""
        out = {"rows": int(plan.num_new.sum())}
        for what in self.cache.counted:
            if what not in LAYER_KINDS:  # _count_selected, _mixers, _share
                out.update(getattr(self, "_count_" + what)(plan))
            else:
                out.update(_page_counts(self, plan.start_pos, plan.num_new,
                                        what))
        return out

    def _count_share(self, plan: StepPlan) -> Dict[str, int]:
        """One member's share of an expert layer: the device's own count
        of the step folded last (as ``_count_mixers`` carries it)."""
        if not self.config.moe_routed_experts or (
                self._experts_touched is None):
            return {}
        return {"experts_touched": self._experts_touched,
                "experts_held": (self.config.num_experts
                                 * self.config.num_layers),
                "held_assignments": self._held_assignments}

    def _count_selected(self, plan: StepPlan) -> Dict[str, int]:
        """The attention work of one layer under an indexer (a latent
        model's, or one that selects inside paged K / V), from the plan
        (host arithmetic, nothing read back): ``context_keys`` the cached
        tokens at or before every real query token, which its indexer
        scores; ``attended_sparse`` those of them a query attends, its
        ``index_topk`` best; ``index_keys`` the tokens in the pages that
        hold a slot's context, which the indexer reads once a slot;
        ``chosen_min`` the fewest distinct latent rows a slot's queries can
        have chosen between them (its last query's); ``selection_tiles`` of
        ``selection_tiles_grid`` and ``score_tiles`` of ``score_tiles_grid``
        as :func:`_selection_tiles` counts them. Booked on the metrics."""
        cl = plan.start_pos.astype(np.int64)
        nn = plan.num_new.astype(np.int64)
        topk = int(self.config.index_topk) or (1 << 62)
        context = nn * cl + nn * (nn + 1) // 2
        # rows whose context passes topk attend topk of it
        over = np.clip(cl + nn - topk, 0, nn)
        attended = context - (over * (cl + nn - topk) - over * (over - 1) // 2)
        ps = self.page_size
        busy = nn > 0
        counts = {
            "context_keys": int(context.sum()),
            "attended_sparse": int(attended.sum()),
            "index_keys": int((-(-(cl + nn) // ps) * ps)[busy].sum()),
            "chosen_min": int(np.minimum(cl + nn, topk)[busy].sum()),
            **_selection_tiles(self, cl, nn, topk),
        }
        self.metrics.on_keys("sparse", counts["attended_sparse"],
                             counts["index_keys"])
        self.metrics.context_keys += counts["context_keys"]
        return counts

    def _count_mixers(self, plan: StepPlan) -> Dict[str, int]:
        """The work of one layer of every mixer kind the model names, from
        the plan (host arithmetic, nothing read back): what each kind
        counts is ``_KIND_COUNTS``' entry for it, beside the leaves the kind
        keeps (``MIXER_KINDS``), so a model is the union of its kinds. A
        routed model adds the device's own count of the step folded last
        (two calls behind the one it rides on: the sums over a window are of
        the same steps but its edges): ``experts_touched`` the held experts
        that got at least one row, over the routed layers, of
        ``experts_held`` a step. Booked on the metrics."""
        cfg = self.config
        cl = plan.start_pos.astype(np.int64)
        nn = plan.num_new.astype(np.int64)
        # (a configuration that names no layer is told by what it carries:
        # the hand counts of tests/benchmark build such)
        kinds = getattr(cfg, "mixer_types", None) or (
            ("sparse", "lightning") if cfg.block_sparse is not None
            else ("kda", "latent"))
        counts: Dict[str, int] = {}
        for kind in dict.fromkeys(kinds):
            counts.update(_KIND_COUNTS[kind](self, cl, nn))
        touched = getattr(self, "_experts_touched", None)
        if touched is not None:
            counts["experts_touched"] = touched
            counts["experts_held"] = cfg.num_experts * cfg.num_layers
        if "context_keys" in counts:
            self.metrics.context_keys += counts["context_keys"]
        self.metrics.state_resets += counts.get("state_resets", 0)
        # (the residual streams every half-layer mixes; a configuration
        # built by hand, as above, may carry no such field)
        if getattr(cfg, "hc_mult", 0):
            counts["residual_streams"] = cfg.hc_mult
        return counts

    def describe(self) -> Dict[str, Any]:
        """What the built engine is, as it observed it (the attention paths
        once its step has been traced): the order of a turn and the layout
        of the rows with their reasons, the attention path of every layer
        kind (``*_kernel`` or ``dense``) with the reasons for a dense one,
        a routed model's bank products (``touched_kernel`` or ``einsum`` and
        why), and the bytes of every leaf a slot keeps that is no page."""
        from ..models.mixers import slot_leaves

        mcfg = self.config
        leaves = slot_leaves(mcfg, self.max_slots,
                             self.engine.kv_cache_storage_dtype)
        kinds = self.attention_paths or (
            {"full": self.attention_path} if self.attention_path else {})
        kda_heads = 0  # the heads a program of the delta-rule call takes
        if kinds.get("kda") == "kda_kernel":
            from ..ops.pallas.kda_attention import heads_per_program
            kda_heads = heads_per_program(
                mcfg.num_heads, mcfg.hd, self.token_budget,
                jnp.dtype(self.engine.dtype).itemsize)
        return {
            "model": mcfg.name,
            "step_order": self.step_order,
            "step_order_reason": self.step_order_reason,
            "row_layout": self.row_layout,
            "row_layout_reason": self.row_layout_reason,
            "param_layout": self.param_layout,
            "param_layout_reason": self.param_layout_reason,
            "relaid_param_leaves": self.metrics.relaid_param_leaves,
            "relaid_param_bytes": self.metrics.relaid_param_bytes,
            "param_relayout_s": self.metrics.param_relayout_s,
            "attention": {kind: {"path": path, "reasons": list(
                self._kind_reasons.get(kind, ()))}
                for kind, path in kinds.items()},
            "expert_path": self.expert_path,
            "expert_path_reason": self.expert_path_reason,
            "kda_heads_per_program": kda_heads,
            "paged_layers": mcfg.paged_layers,
            "parallel_block": bool(mcfg.parallel_block),
            "shared_width": mcfg.moe_shared_width,
            "pool_pages": {"full": self.num_pages or 0,
                           "window": self.window_num_pages or 0},
            # an indexer: heads x width, the keys a query attends, and the
            # bytes of the pool its keys lie in (0: no indexer)
            "indexer": {"heads": mcfg.index_heads, "dim": mcfg.index_dim,
                        "topk": mcfg.index_topk,
                        "pool_bytes": self.metrics.index_pool_bytes},
            # one member's share of a routed layer: experts held of those
            # the router chooses among, from which on, by which router
            "experts": {"held": mcfg.num_experts,
                        "routed": mcfg.routed_experts,
                        "first": mcfg.moe_first_expert,
                        "gate": mcfg.moe_gate if mcfg.is_moe else None,
                        "dropless": bool(mcfg.is_moe and mcfg.moe_dropless)},
            "residual_streams": getattr(mcfg, "hc_mult", 0) or 1,
            "state_bytes": self.metrics.state_bytes,
            "state_leaves": {
                name: int(np.prod(leaf.shape)) * leaf.dtype.itemsize
                for name, leaf in leaves.items()},
            # what ONE slot keeps of them (a conv layer's carried rows, a
            # state layer's state)
            "state_bytes_per_slot": state_bytes(mcfg, 1, jnp.dtype(
                self.engine.kv_cache_storage_dtype).itemsize),
            # KV heads a 128-lane row of the K / V pools (2: heads of 64
            # held in pairs, which the paged kernel reads as one head)
            "kv_heads_per_pool_row": (
                mcfg.kv_heads // self._caches["k"].shape[3]
                if self.paged and "k" in self._caches else 1),
        }

    def _stage_args(self, plan: StepPlan) -> tuple:
        """Decode this step's promotions into the rotating staging buffer
        (host side) and return the ``(stage_kv, stage_dst)`` step args.
        An idle step reuses the zero twin and the all-NULL destination
        vector — same shapes, same dtypes, zero recompiles. The wall
        time spent here is the page-in STALL (the host-side slice NOT
        hidden under device math); the H2D upload + scatter themselves
        ride under the step."""
        if not plan.stage:
            return (self._stage_zero_np, self._stage_dst_null)
        # inside serve/dispatch: the step that is being dispatched
        with Phase(self.tracer, "serve/page_in", "serve",
                   step=self._dispatched + 1) as page_in_sp:
            t0 = self.clock()
            # rotate: the buffer filled LAST step may still be feeding an
            # in-flight H2D copy — fill the other one (the PR-1 two-
            # generation discipline, host side)
            bufs = self._stage_np[self._stage_idx]
            self._stage_idx ^= 1
            stage_dst = np.full(STAGE_SLOTS, self.null_page, np.int32)
            at_rest = 0
            for i, s in enumerate(plan.stage):
                leaves, nbytes = self._spiller.load(s.key)
                at_rest += nbytes
                stage_dst[i] = s.dst_page
                for name, arr in leaves.items():
                    bufs[name][:, i] = arr[:, 0]
            stall = self.clock() - t0
            self.metrics.on_page_in(
                pages=len(plan.stage), nbytes=at_rest, stall_s=stall,
            )
            page_in_sp.annotate(
                pages=len(plan.stage), at_rest_bytes=int(at_rest))
        return (bufs, stage_dst)

    # ------------------------------------------------- fleet KV handoff
    def _refuse_page_moves(self, what: str) -> None:
        why = self.cache.refused("fleet.prefill_replicas")
        if why:
            raise RuntimeError(f"{what}: {why}")

    def export_kv_pages(self, page_ids) -> Dict[str, Any]:
        """Snapshot the payload of physical ``page_ids`` out of this
        replica's paged pool (serving/paging.py export_pages) — the
        prefill half of the fleet's prefill→decode handoff."""
        from .paging import export_pages

        if not self.paged:
            raise RuntimeError(
                "export_kv_pages needs the paged arena (serving.paged) — "
                "the fleet KV handoff is a page transfer"
            )
        self._refuse_page_moves("export_kv_pages")
        return export_pages(self._caches, page_ids)

    def import_kv_pages(self, payload: Dict[str, Any], dst_page_ids
                        ) -> None:
        """Scatter an exported payload into ``dst_page_ids`` of this
        replica's pool. The scatter runs jitted with the pool DONATED,
        so the update happens in place — O(pages moved), never an
        O(arena) copy per handoff — and the result keeps exactly the
        sharding the step compiled against (donated-buffer reuse), so
        the import never buys a step recompile (the fleet oracle
        asserts ``step_traces == 1`` per replica)."""
        from .paging import check_page_payload, scatter_pages

        if not self.paged:
            raise RuntimeError(
                "import_kv_pages needs the paged arena (serving.paged)"
            )
        self._refuse_page_moves("import_kv_pages")
        ids = np.asarray(dst_page_ids, np.int32)
        check_page_payload(self._caches, payload, ids.size)
        if self._import_pages_fn is None:
            self._import_pages_fn = jax.jit(
                scatter_pages, donate_argnums=(0,)
            )
        caches = self._import_pages_fn(
            self._caches, payload, jnp.asarray(ids)
        )
        if self._cache_shardings is not None:
            # re-assert the tp sharding the step compiled against: the
            # donated scatter USUALLY reuses the input buffers (keeping
            # their placement), but nothing pins its output sharding —
            # and a drifted carry would buy a step recompile. device_put
            # onto an identical sharding is a no-op, so the in-place win
            # survives whenever the layout did.
            caches = jax.device_put(caches, self._cache_shardings)
        self._caches = caches

    def run_until_idle(self, max_steps: int = 100_000
                       ) -> List[RequestState]:
        """Drain queue + slots and fold the last step in flight: nothing
        is left unfetched. Returns every request finished on the way (DONE
        order). Timed-out requests surface through their states."""
        finished: List[RequestState] = []
        steps = 0
        while self.scheduler.has_work or self._flying is not None:
            if steps >= max_steps:
                raise RuntimeError(
                    f"serving did not drain within {max_steps} steps"
                )
            finished.extend(self.step())
            steps += 1
        return finished

    def _lower_step(self):
        from ..parallel.a2a_overlap import a2a_scope

        with use_topology(self.topology), self.engine._impl_ctx(), \
                a2a_scope(self._a2a_cfg):
            return self._step.lower(
                jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(
                        a.shape, a.dtype, sharding=a.sharding),
                    self.engine.params),
                *self._step_avals)

    def lower_step(self):
        """The ONE jitted step, lowered for this engine's own argument
        shapes (abstract: nothing runs, the arena is not donated), the
        parameters' layouts left to the compiler where ``param_layout``
        says ``"compiled"``: ``.compile()`` is the program the engine
        calls. Its ``.as_text()`` answers whether a kernel really is in the
        served program (``tpu_custom_call``) — a config value cannot."""
        traces = self.step_traces
        try:
            return self._lower_step()
        finally:
            # lowering may re-trace; that is not a recompile of the step
            self.step_traces = traces

    # --------------------------------------------------------- steptrace
    def trace_export(self, path: Optional[str] = None) -> str:
        """Write the Chrome trace-event JSON (Perfetto-loadable). Before
        exporting, every declared ``analytic_streams()`` stream is added
        as a ``plan/<name>`` span carrying its shardplan-predicted
        bytes/seconds next to the measured average step wall clock —
        the per-component drift view. Load with ``tools/trace_report.py``
        for the per-phase table and schema validation."""
        if self.tracer is None:
            raise RuntimeError(
                "steptrace is not enabled on this ServingEngine — pass "
                'steptrace={"enabled": True} (or set the "steptrace" '
                "config section) at construction"
            )
        measured = self.tracer.mean_dur("serve/step")
        for name, stream in self.analytic_streams().items():
            self.tracer.plan_span(name, stream, measured_step_s=measured)
        path = path or self._steptrace_export_path or "steptrace_serve.json"
        out = self.tracer.export(path)
        log_dist(f"steptrace: wrote {out}")
        return out

    # --------------------------------------------------- planner metadata
    def analytic_streams(self, include_potential: bool = False
                         ) -> Dict[str, Any]:
        """Shared analytic-streams schema (comm_logger.record_streams /
        cost planner / rule R8): the per-step KV arena traffic, plus the
        inner engine's declared TP ring when overlap_comm serves."""
        streams = dict(self.engine.analytic_streams(
            batch=self.max_slots, seq=self.token_budget,
            include_potential=include_potential,
        ))
        if self.paged:
            streams["kv_cache"] = paged_kv_stream(
                self.config, self.num_pages, self.page_size,
                self.max_slots, self.pages_per_slot, self.token_budget,
                jnp.dtype(self.engine.kv_cache_storage_dtype).itemsize,
                self.engine.kv_cache_quantized,
                tp=self.topology.tp_size,
            )
        else:
            streams["kv_cache"] = serving_kv_stream(
                self.config, self.max_slots, self.capacity,
                jnp.dtype(self.engine.kv_cache_storage_dtype).itemsize,
                self.engine.kv_cache_quantized,
                tp=self.topology.tp_size,
            )
        if self.tiered:
            # the host-tier page traffic (demotions out + staged
            # promotions in, codec at-rest widths) — declared overlapped
            # on the host link so R8/R13 budget it against the step
            streams["kv_spill"] = kv_spill_stream(
                self.config, self.page_size, self.host_pages,
                self.serving.spill_codec,
                self.engine.kv_cache_quantized,
                tp=self.topology.tp_size,
            )
        if self.max_draft > 0:
            # the verify-window bytes spec adds on top of the arena
            # traffic — declared so shardplan R8 prices spec statically
            streams["spec_verify"] = spec_verify_stream(
                self.config, self.max_slots, self.max_draft,
                jnp.dtype(self.engine.kv_cache_storage_dtype).itemsize,
                self.engine.kv_cache_quantized,
                tp=self.topology.tp_size,
            )
        # the decode-shaped expert exchange (combine ride): the stock
        # form moves it as one all-gather (exposed), the chunked form as
        # ppermute hops declared overlapped — R8 statically checks the
        # hops fit the compute window
        moe_stream = moe_decode_stream(
            self.config, self.topology, self.token_budget,
            jnp.dtype(self.dtype).itemsize, self.moe_a2a_form,
        )
        if moe_stream:
            streams["moe_decode_a2a"] = moe_stream
        return streams

    def parity_pairs(self):
        """The declared-bitwise form pairs of this engine's slot step
        (analysis/parity.py — the static half of the replay oracles):
        paged vs contiguous always, moe_a2a stock vs chunked when the
        ring can actually run. Each pair's thunks re-trace the step
        abstractly; ``tools/paritycheck.py`` proves them all."""
        import dataclasses

        from ..analysis.parity import config_parity_pairs

        srv = dataclasses.asdict(self.serving)
        srv.pop("fleet", None)
        raw = {
            "serving": dict(srv, enabled=True),
            "tensor_parallel": {"tp_size": self.topology.tp_size},
            "bf16": {"enabled": jnp.dtype(self.dtype) == jnp.bfloat16},
        }
        if self.moe_ep > 1:
            raw["moe"] = {"enabled": True, "ep_size": self.moe_ep,
                          "num_experts": self.config.num_experts}
        return config_parity_pairs(raw, self.engine.model)


# ----------------------------------------------------------- lint surface
def trace_serving_step(model, ds_config, topology: Optional[MeshTopology]
                       = None):
    """Abstract serving-step trace for shardlint: (closed_jaxpr,
    arg_shardings, streams, meta). Nothing materializes — params and the
    KV arena are ShapeDtypeStructs carrying the real shardings, so the
    R1–R11 registry (and the cost planner) see exactly the program the
    serving engine would compile.

    ``meta`` carries the trace-stability evidence rule R11 consumes:
    ``traced_manifest`` (argument name → flat invar index range) and
    ``required_traced`` — the per-tick host-state vectors (slot
    occupancy, frontiers, spec_len, page tables, cow_src, per-slot
    keys) that MUST be traced, never baked, for ``step_traces == 1`` to
    hold across arbitrary arrival patterns."""
    from ..config import DeepSpeedConfig

    cfg = (
        ds_config if isinstance(ds_config, DeepSpeedConfig)
        else DeepSpeedConfig(ds_config)
    )
    srv = cfg.serving
    tp = max(int(cfg.tensor_parallel.tp_size), 1)
    mcfg = model.config
    # same "auto" resolution the live engine applies — the linted program
    # and the served program must read identical knob values
    from ..config import resolve_auto_knobs

    resolve_auto_knobs(cfg, model_config=mcfg, topology=topology)
    # MoE serving configs lint on the ep mesh they would serve on: the
    # expert exchange only exists in the traced program when the ep axis
    # does (serving_ep_size — the ONE moe.ep_size clamp)
    ep = serving_ep_size(cfg.moe, mcfg)
    if topology is None:
        topology = MeshTopology(
            dims=ParallelDims(tp=tp, ep=ep),
            devices=jax.devices()[:tp * ep],
        )
    mesh = topology.mesh
    dtype = cfg.compute_dtype
    quantized = srv.kv_cache_dtype == "int8"
    storage = jnp.bfloat16 if srv.kv_cache_dtype in ("bf16", "bfloat16") \
        else dtype
    N, W = int(srv.max_slots), int(srv.token_budget)
    V = mcfg.vocab_size
    max_tokens = min(int(srv.max_tokens), mcfg.max_seq_len)
    capacity = _align_cache(max_tokens + W)
    max_draft = (
        int(srv.spec.max_draft) if getattr(srv.spec, "enabled", False) else 0
    )

    sharded = topology.world_size > 1 and hasattr(model, "partition_specs")

    def sds(shape, dt, spec=None):
        sharding = (
            NamedSharding(mesh, spec) if sharded and spec is not None else None
        )
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    params_shape = jax.eval_shape(
        lambda k: model.init(k, dtype=dtype), jax.random.PRNGKey(0)
    )
    if sharded:
        tp_specs = model.partition_specs(topology)
        params = jax.tree.map(
            lambda spec, leaf: sds(leaf.shape, leaf.dtype, spec),
            tp_specs, params_shape,
            is_leaf=lambda x: isinstance(x, P),
        )
    else:
        params = jax.tree.map(
            lambda leaf: sds(leaf.shape, leaf.dtype), params_shape
        )
    paged = bool(srv.paged)
    if paged:
        page_size, pages_per_slot, num_pages = paged_geometry(
            mcfg, srv, max_tokens, N)
        cache_shape = init_paged_cache(
            mcfg, num_pages, page_size, storage, quantized=quantized,
            max_slots=N,
        )
    else:
        cache_shape = init_cache(
            mcfg, N, capacity, storage, quantized=quantized
        )
    cache_specs = {p.name: p.spec for p in cache_layout(mcfg).pools(
        page_size if paged else 1, storage, quantized)}
    caches = {
        k: sds(v.shape, v.dtype, cache_specs[k])
        for k, v in cache_shape.items()
    }
    cache_shardings = (
        {k: NamedSharding(mesh, cache_specs[k]) for k in cache_shape}
        if sharded else None
    )
    tiered = paged and int(getattr(srv, "host_pages", 0) or 0) > 0
    paged_args = (
        (
            ("page_table", sds((N, pages_per_slot), jnp.int32, P())),
            ("cow_src", sds((N,), jnp.int32, P())),
        )
        if paged else ()
    )
    if tiered:
        # the host-tier staging pair (serving.host_pages > 0): pool-leaf
        # shapes with the page axis narrowed to STAGE_SLOTS, sharded
        # like the pool so the linted program is the served program
        paged_args += (
            ("stage_kv", {
                k: sds((v.shape[0], STAGE_SLOTS) + tuple(v.shape[2:]),
                       v.dtype, cache_specs[k])
                for k, v in cache_shape.items()
            }),
            ("stage_dst", sds((STAGE_SLOTS,), jnp.int32, P())),
        )
    named_args = (
        ("params", params),
        ("caches", caches),
        ("seen", sds((N, V), jnp.bool_, P())),
        ("tokens", sds((N, W), jnp.int32, P())),
        ("num_new", sds((N,), jnp.int32, P())),
        ("start_pos", sds((N,), jnp.int32, P())),
        *paged_args,
        ("fresh", sds((N,), jnp.bool_, P())),
        ("sample_flag", sds((N,), jnp.bool_, P())),
        ("spec_len", sds((N,), jnp.int32, P())),
        ("eos_id", sds((N,), jnp.int32, P())),
        ("rng", sds((N, 2), jnp.uint32, P())),
        ("temperature", sds((N,), jnp.float32, P())),
        ("top_k", sds((N,), jnp.int32, P())),
        ("top_p", sds((N,), jnp.float32, P())),
        ("rep_penalty", sds((N,), jnp.float32, P())),
        ("from_prev", sds((N,), jnp.bool_, P())),
        ("prev_tok", sds((N, max_draft + 1), jnp.int32, P())),
        ("prev_rng", sds((N, 2), jnp.uint32, P())),
    )
    args = tuple(v for _, v in named_args)
    if paged:
        step_fn = make_paged_step_fn(
            mcfg, dtype, V, cache_shardings=cache_shardings,
            max_draft=max_draft, tiered=tiered,
        )
    else:
        step_fn = make_step_fn(mcfg, dtype, V,
                               cache_shardings=cache_shardings,
                               max_draft=max_draft)
    # the traced program IS the served program: resolve the expert-
    # exchange form exactly like ServingEngine.__init__ and enter the
    # scope around the trace (R3 then lints the ring's perms when the
    # chunked form is resolved)
    moe_form = resolve_moe_a2a_form(
        srv.moe_a2a, mcfg, topology, W, jnp.dtype(dtype).itemsize,
        max_slots=N,
    )
    a2a_cfg = (
        moe_a2a_scope_cfg(moe_form)
        if getattr(mcfg, "is_moe", False) else None
    )
    from ..parallel.a2a_overlap import a2a_scope
    with use_topology(topology), a2a_scope(a2a_cfg):
        closed = jax.make_jaxpr(step_fn)(*args)
    flat = jax.tree_util.tree_leaves(args)
    invars = list(closed.jaxpr.invars)
    arg_shardings = {}
    if len(flat) == len(invars):
        for v, leaf in zip(invars, flat):
            s = getattr(leaf, "sharding", None)
            if s is not None:
                arg_shardings[v] = s
    if paged:
        streams = {
            "kv_cache": paged_kv_stream(
                mcfg, num_pages, page_size, N, pages_per_slot, W,
                jnp.dtype(storage).itemsize, quantized, tp=tp,
            )
        }
    else:
        streams = {
            "kv_cache": serving_kv_stream(
                mcfg, N, capacity, jnp.dtype(storage).itemsize, quantized,
                tp=tp,
            )
        }
    if paged and tiered:
        streams["kv_spill"] = kv_spill_stream(
            mcfg, page_size, int(srv.host_pages), srv.spill_codec,
            quantized, tp=tp,
        )
    if max_draft > 0:
        streams["spec_verify"] = spec_verify_stream(
            mcfg, N, max_draft, jnp.dtype(storage).itemsize, quantized,
            tp=tp,
        )
    moe_stream = moe_decode_stream(
        mcfg, topology, W, jnp.dtype(dtype).itemsize, moe_form,
    )
    if moe_stream:
        streams["moe_decode_a2a"] = moe_stream
    # R11 evidence: argument name → flat invar range, plus the per-tick
    # host-state names the slot engine's ONE-trace contract hinges on
    manifest, lo = {}, 0
    for arg_name, leaf_tree in named_args:
        n = len(jax.tree_util.tree_leaves(leaf_tree))
        manifest[arg_name] = (lo, lo + n)
        lo += n
    required = [
        "tokens", "num_new", "start_pos", "fresh", "sample_flag",
        "spec_len", "eos_id", "rng", "from_prev", "prev_tok", "prev_rng",
    ]
    if paged:
        required += ["page_table", "cow_src"]
    if tiered:
        # which pages promote varies per tick — baking stage_dst would
        # recompile on every distinct promotion mix (R11)
        required += ["stage_dst"]
    meta = {
        "traced_manifest": manifest if lo == len(invars) else {},
        "required_traced": tuple(required) if lo == len(invars) else (),
        "moe_a2a_form": moe_form,
    }
    return closed, arg_shardings, streams, meta
