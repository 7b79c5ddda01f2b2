"""DeepSeek-V3.2's mechanisms at a small size on the CPU, float32, seeded
weights: latent attention through the paged latent cache, the lightning
indexer's own key cache and its exact selection, the sigmoid router with its
selection bias and groups, one member's share of an expert-parallel layer,
and a leading dense layer in a stack of its own, each against the plain
reference ``benchmarks/families/deepseek.py``."""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from benchmarks import reference
from benchmarks.run import merged
from deepspeed_tpu.config import DeepSpeedConfigError
from deepspeed_tpu.models import deepseek
from deepspeed_tpu.models.decoding import (INDEX, LATENT, _paged_gather,
                                           init_paged_cache)
from deepspeed_tpu.moe import sharded_moe as sm
from deepspeed_tpu.ops.attention import attention_impl
from deepspeed_tpu.ops.pallas import sparse_latent_attention as sla
from deepspeed_tpu.serving import Request
from slot_program import chunked_logits, jit_init, reference_logits

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32 = jnp.float32
fam = reference.family("deepseek")
logits_of = reference_logits(fam)


@pytest.fixture(scope="module")
def model():
    return deepseek("deepseek-tiny")


@pytest.fixture(scope="module")
def params(model):
    p = jit_init(model, jax.random.PRNGKey(7))
    # a selection bias large enough to decide choices (init draws 0.02)
    bias = jax.random.normal(jax.random.PRNGKey(8),
                             p["layers"]["mlp"]["sel_bias"].shape) * 0.3
    p["layers"]["mlp"]["sel_bias"] = bias
    return p


@pytest.fixture(scope="module")
def shape():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "deepseek-v3.2.json")) as f:
        cfg = json.load(f)
    return fam.shape_of(merged(cfg, cfg["rehearse"]))


def test_the_tiny_preset_is_the_rehearsals_shape(model, shape):
    c = model.config
    assert (c.hidden_size, c.num_layers, c.num_heads, c.kv_heads, c.hd, c.ffn,
            c.vocab_size, c.num_experts, c.moe_top_k) == (
        shape.d, shape.layers, shape.heads, shape.kv_heads, shape.hd,
        shape.ffn, shape.vocab, shape.experts, shape.top_k)
    assert (c.lead_dense_layers, c.lead_dense_ffn, c.routed_experts,
            c.index_topk, c.kv_latent_dim, c.q_latent_dim) == (
        shape.dense_layers, shape.dense_ffn, shape.routed, shape.index_topk,
        shape.kv_rank, shape.q_rank)
    assert c.attn_scale_mult == pytest.approx(shape.mscale ** 2)
    table = dict(c.rope_tables)["full"]
    np.testing.assert_allclose(table.inv_freq(c.qk_rope_dim),
                               fam.rope_table(shape), rtol=1e-6)


def test_the_published_preset_is_the_catalogs_model():
    c = deepseek("deepseek-v3.2").config
    assert (c.total_layers, c.lead_dense_layers, c.hidden_size, c.num_heads,
            c.q_latent_dim, c.kv_latent_dim, c.qk_nope_dim, c.qk_rope_dim,
            c.v_head_dim, c.index_heads, c.index_dim, c.index_topk,
            c.num_experts, c.moe_top_k, c.moe_groups, c.moe_groups_kept,
            c.ffn, c.moe_shared_width, c.lead_dense_ffn, c.vocab_size) == (
        61, 3, 7168, 128, 1536, 512, 128, 64, 128, 64, 128, 2048, 256, 8, 8,
        4, 2048, 2048, 18432, 129280)
    assert c.moe_routed_scale == 2.5 and c.norm_eps == 1e-6
    # 671 B parameters (the release counts its MTP module too)
    assert 6.55e11 < c.num_params() < 6.75e11


def paged_logits(model, params, ids, W=16, ps=16, slot=1, slots=2,
                 kernel=False):
    """Prefill ``ids`` in chunks of ``W`` and nothing else: the logits of
    every position through the paged latent cache, slot ``slot`` of
    ``slots`` (the others idle), pages handed out in a scrambled order."""
    mp = -(-(len(ids) + W) // ps)
    order = np.random.default_rng(3).permutation(slots * mp)
    return chunked_logits(model, params, ids, order.reshape(slots, mp),
                          slot=slot, chunk=W, page_size=ps, kernels=kernel,
                          valid=True)


@pytest.mark.parametrize("length", [21, 75], ids=["inside-topk", "past-topk"])
def test_the_paged_latent_cache_computes_the_reference(model, params, shape,
                                                       length):
    """Chunks of 16 over pages of 16 (boundaries crossed, the last chunk
    ragged), a context under and one three times over ``index_topk`` 24."""
    ids = np.random.default_rng(length).integers(0, 512, length, np.int32)
    want = np.asarray(logits_of(params, ids, shape))
    got = paged_logits(model, params, ids)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_the_kernels_serve_what_the_dense_lines_serve(model, params, shape):
    """The three Pallas calls (interpret mode here) in the served forward:
    the same logits as the reference past ``index_topk``, so the same chosen
    sets."""
    ids = np.random.default_rng(5).integers(0, 512, 60, np.int32)
    want = np.asarray(logits_of(params, ids, shape))
    got = paged_logits(model, params, ids, kernel=True)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("fault", fam.FAULTS)
def test_every_fault_moves_the_reference(params, shape, fault):
    ids = np.random.default_rng(9).integers(0, 512, 75, np.int32)
    clean = np.asarray(fam.logits(params, ids, shape))
    broken = np.asarray(fam.logits(
        ids=ids, shape=shape, **fam.faulted(params, fault, shape)))
    assert np.abs(broken - clean).max() > 1e-3, fault


def kernel_inputs(seed=0, B=3, S=16, Hi=2, Di=128, H=4, W=128, L=2, ps=16,
                  mp=12):
    rng = np.random.default_rng(seed)
    P = B * mp
    f = lambda *s: jnp.asarray(rng.normal(size=s), F32)
    return dict(
        ki=f(L, P + 1, ps, Di), kv=f(L, P + 1, ps, W),
        pt=jnp.asarray(rng.permutation(P).reshape(B, mp), jnp.int32),
        cl=jnp.asarray([0, 37, 150], jnp.int32),
        nn=jnp.asarray([16, 1, 9], jnp.int32),
        q_idx=f(B, S, Hi, Di), w_idx=f(B, S, Hi), q_abs=f(B, S, H, W))


def chosen_by_kernel(scores, thr, tie, qpos):
    key = sla._sort_key(sla.unblocked(scores))
    pos = jnp.arange(key.shape[-1])[None, None]
    return (pos <= qpos[..., None]) & (
        (key > thr[..., None]) | ((key == thr[..., None])
                                  & (pos <= tie[..., None])))


@pytest.mark.parametrize("ties", [False, True], ids=["distinct", "ties"])
def test_the_selection_kernel_chooses_the_references_set(ties):
    """Scores through the table, then the exact top-24 of each real row as a
    threshold and a tie position: the set ``lax.top_k`` chooses, ties to the
    lower position; a row inside 24 tokens keeps them all."""
    k = kernel_inputs()
    topk, layer, S = 24, 1, 16
    scores = sla.index_scores(k["q_idx"], k["w_idx"], k["ki"], k["cl"],
                              k["pt"], layer=layer, num_new=k["nn"],
                              interpret=True)
    dense = sla.dense_index_scores(
        k["q_idx"], k["w_idx"], _paged_gather(k["ki"][layer], k["pt"]))
    qpos = k["cl"][:, None] + jnp.arange(S)[None]
    real = (jnp.arange(S)[None] < k["nn"][:, None])[..., None]
    seen = (jnp.arange(dense.shape[-1])[None, None] <= qpos[..., None]) & real
    np.testing.assert_allclose(
        jnp.where(seen, sla.unblocked(scores), 0),
        jnp.where(seen, dense, 0), atol=1e-4)
    if ties:  # many equal scores, signed zeros among them
        scores = jnp.round(scores * 2) / 2
    thr, tie = sla.select_topk(scores, k["cl"], k["nn"], topk, interpret=True)
    got = chosen_by_kernel(scores, thr, tie, qpos)
    want = sla.dense_selection(
        jnp.where(seen, sla.unblocked(scores), 0.0), qpos, topk)
    assert bool(jnp.all(jnp.where(real, got == want, True)))
    counts = np.asarray(got.sum(-1))
    assert list(counts[2, :9]) == [24] * 9 and list(counts[0]) == list(
        range(1, 17))


def selection_by_sort(scores, cl, nn, topk, kpool=1):
    """(thr, tie) as :func:`sla.select_topk` defines them, by a sort: the
    ``topk``-th largest sort key of what a row sees and the position of the
    ``need``-th of its ties, ``need`` what the keys above it leave of
    ``topk``; a row inside ``topk`` keeps everything."""
    key = sla._sort_key(sla.unblocked(scores))
    qpos = sla.last_block(cl[:, None] + jnp.arange(key.shape[1])[None], kpool)
    seen = jnp.arange(key.shape[-1])[None, None] <= qpos[..., None]
    key = jnp.where(seen, key, sla.INT_MIN)
    thr = jnp.sort(key, axis=-1)[..., -topk]
    tied = key == thr[..., None]
    need = topk - jnp.sum(key > thr[..., None], -1)
    tie = jnp.argmax(tied & (jnp.cumsum(tied, -1) == need[..., None]), -1)
    inside = qpos < topk
    return (jnp.where(inside, sla.INT_MIN, thr),
            jnp.where(inside, 2 ** 31 - 1, tie).astype(jnp.int32))


SEL = dict(B=4, NB=24, S=16, bk=128, topk=24)  # 3 counting steps of 8 blocks


def _selection_case(name):
    """(scores [B, NB, S, bk], cache_len, num_new, kpool) of a case; what no
    row may read (past its position, past ``num_new``) is NaN."""
    B, NB, S, bk, topk = (SEL[k] for k in ("B", "NB", "S", "bk", "topk"))
    rng = np.random.default_rng(sum(map(ord, name)))
    flat = rng.normal(size=(B, S, NB * bk)).astype(np.float32)
    kpool = 1
    cl, nn = [0, 37, 150, 700], [16, 1, 9, 16]
    if name == "ties":  # many equal scores, signed zeros among them
        flat = np.round(flat * 2) / 2
    elif name == "exact_beside_excess":
        # one tile: row 0's ties at the threshold are just what it needs
        # (4 of 4), row 1's exceed it (4 of 10), row 2's are its whole need
        flat = np.round(flat) * 0.0  # signed zeros under every row
        cl, nn = [100, 37, 150, 700], [3, 1, 9, 16]
        for row, ties in ((0, 4), (1, 10), (2, 30)):
            at = rng.permutation(100)
            flat[0, row, at[:20]] = 2.0
            flat[0, row, at[20:20 + ties]] = 1.0
    elif name == "decode_far_past_topk":
        cl, nn = [2900, 1500, 10, 2047], [1, 1, 1, 1]
    elif name == "idle_first_and_between":
        cl, nn = [500, 300, 900, 1100], [0, 5, 0, 16]
    elif name == "idle_last_and_all_but_one":
        cl, nn = [500, 300, 900, 1100], [0, 0, 12, 0]
    elif name == "partial_last_group":  # 1,024 keys a counting step
        cl, nn = [1500, 2041, 1016, 1023], [16, 9, 16, 2]
    elif name == "kpool4":  # a key a block of 4 tokens
        kpool = 4
        cl, nn = [0, 150, 9000, 4093], [16, 1, 9, 16]
    cl, nn = np.asarray(cl, np.int32), np.asarray(nn, np.int32)
    qpos = (cl[:, None] + np.arange(S)[None] + 1) // kpool - 1
    defined = (np.arange(NB * bk)[None, None] <= qpos[..., None]) & (
        np.arange(S)[None] < nn[:, None])[..., None]
    flat = np.where(defined, flat, np.nan).astype(np.float32)
    return flat.reshape(B, S, NB, bk).swapaxes(1, 2), cl, nn, kpool


@functools.lru_cache(maxsize=None)
def _select(kpool):  # one program a shape
    return (jax.jit(lambda s, cl, nn: sla.select_topk(
                s, cl, nn, SEL["topk"], interpret=True, kpool=kpool)),
            jax.jit(lambda s, cl, nn: selection_by_sort(
                s, cl, nn, SEL["topk"], kpool)))


@pytest.mark.parametrize("case", [
    "distinct", "ties", "exact_beside_excess", "decode_far_past_topk",
    "idle_first_and_between", "idle_last_and_all_but_one",
    "partial_last_group", "kpool4"])
def test_the_selection_is_the_sorts_threshold_and_tie(case):
    """``select_topk``'s (threshold, tie position) EQUAL a sort's on every
    real row, whatever the call skips: an idle tile's scores (parked on a
    neighbour's, before or after it), the tie search where every row of a
    tile needs all its ties, the counting steps past a context."""
    scores, cl, nn, kpool = _selection_case(case)
    kernel, by_sort = _select(kpool)
    real = np.arange(SEL["S"])[None] < nn[:, None]
    assert real.sum() == nn.sum() > 0
    for got, want in zip(kernel(scores, cl, nn), by_sort(scores, cl, nn)):
        np.testing.assert_array_equal(np.asarray(got)[real],
                                      np.asarray(want)[real])
    tiles = sla.selection_tiles(cl, nn, SEL["S"], SEL["topk"], kpool)
    assert tiles.shape == (SEL["B"], 2) and not tiles[nn == 0].any()
    if case == "decode_far_past_topk":  # the slot at 10 keeps its 11 keys
        assert tiles.tolist() == [[True, False]] * 2 + [[False] * 2] + [
            [True, False]]


# the scoring call of the three cells that run it: (indexer heads, the key's
# own width in its 128-lane row, tokens a pooled key)
SCORE_SHAPES = {"keye": (16, 64, 1), "deepseek": (64, 128, 1),
                "glm5-pooled": (32, 128, 4)}
# (cache_len, num_new) of three slots in tokens a key, and the chunk's rows
SCORE_CASES = {
    "all-decoding": ([300, 37, 150], [1, 1, 1], 16),
    "a-chunk-beside-decoders": ([300, 100, 150], [1, 16, 1], 16),
    "an-idle-slot": ([300, 37, 150], [16, 0, 1], 16),
    "rows-no-multiple-of-a-tile": ([300, 37, 150], [25, 5, 48], 48),
    "a-chunk-under-a-tile": ([300, 37, 150], [8, 3, 1], 8),
}


def score_inputs(shape, case, seed=0):
    """Operands of :func:`sla.index_scores` for a shape and a case, six key
    blocks a slot (the kernel's ring of four goes round): 64-key blocks of
    16-token pages, or pooled keys through :func:`sla.pooled_view`. Returns
    (a call of the kernel, the dense scores over every key, cl, nn, kpool)."""
    Hi, width, kpool = SCORE_SHAPES[shape]
    cl, nn, S = SCORE_CASES[case]
    B, Di, ps = 3, 128, 16
    # six blocks a slot: of 64 tokens, or of POOLED_BLOCK_K pooled keys
    mp = 6 * (sla.POOLED_BLOCK_K * kpool if kpool > 1 else 64) // ps
    rng = np.random.default_rng(seed)
    lanes = np.arange(Di) < width
    f = lambda *s: jnp.asarray(rng.normal(size=s) * lanes, F32)
    q_idx, w_idx = f(B, S, Hi, Di), jnp.asarray(rng.normal(size=(B, S, Hi)), F32)
    pool = f(2, B * mp + 1, ps // kpool, Di)
    table = jnp.asarray(rng.permutation(B * mp).reshape(B, mp), jnp.int32)
    cl = jnp.asarray(cl, jnp.int32) * (8 if kpool > 1 else 1)
    nn = jnp.asarray(nn, jnp.int32)
    if kpool > 1:
        def call():
            view, counting = sla.pooled_view(pool, table, 1)
            return sla.index_scores(
                q_idx, w_idx, view, cl, counting, layer=0, num_new=nn,
                block_k=sla.POOLED_BLOCK_K, kpool=kpool, interpret=True)
    else:
        def call():
            return sla.index_scores(q_idx, w_idx, pool, cl, table, layer=1,
                                    num_new=nn, block_k=64, interpret=True)
    dense = sla.dense_index_scores(
        q_idx[..., :width], w_idx, _paged_gather(pool[1], table)[..., :width])
    return call, dense, cl, nn, kpool


@pytest.mark.parametrize("case", SCORE_CASES)
@pytest.mark.parametrize("shape", SCORE_SHAPES)
def test_index_scores_is_its_dense_twin(shape, case):
    """The scoring kernel at the three cells' head counts and key widths
    against the plain lines, on every real row and every key of its
    context, whatever the slots hold (a large tile of 128, 32 or 64 rows
    where a slot has most of that many, 16-row tiles for the rest)."""
    call, dense, cl, nn, kpool = score_inputs(shape, case)
    got = np.asarray(sla.unblocked(jax.jit(call)()))
    dense = np.asarray(dense)
    cl, nn = np.asarray(cl), np.asarray(nn)
    bk = sla.POOLED_BLOCK_K if kpool > 1 else 64
    assert (cl[0] + nn[0]) // kpool > sla.SCORE_RING * bk  # the ring goes round
    for b in range(3):
        rows, keys = nn[b], (cl[b] + nn[b]) // kpool
        np.testing.assert_allclose(got[b, :rows, :keys], dense[b, :rows, :keys],
                                   rtol=1e-5, atol=1e-4)


def test_score_tiles_are_the_trips_the_kernel_runs():
    """Every slot scores its own constant, so a (16-row tile, key block) of
    the output holds it exactly where that slot's program computed it: the
    count of those is :func:`sla.score_tiles`: an idle slot's none, a
    decoding slot's one tile a block of its context, a chunk's large tiles
    (32 rows at 64 heads) where half of one is real and small ones for the
    rest."""
    B, S, Hi, Di, ps, mp = 6, 64, 64, 128, 16, 96  # 3 blocks of 512 a slot
    cl = np.asarray([0, 1100, 1500, 700, 30, 500], np.int32)
    nn = np.asarray([64, 1, 20, 0, 40, 15], np.int32)
    own = jnp.arange(1, B + 1, dtype=F32)[:, None, None, None]
    q_idx = jnp.broadcast_to(own, (B, S, Hi, Di))
    w_idx = jnp.ones((B, S, Hi), F32)
    pool = jnp.ones((1, B * mp + 1, ps, Di), F32)
    table = jnp.arange(B * mp, dtype=jnp.int32).reshape(B, mp)
    scores = np.asarray(sla.index_scores(q_idx, w_idx, pool, cl, table,
                                         layer=0, num_new=nn, interpret=True))
    assert sla.score_rows(S, Hi) == (16, 32)
    tiled = scores.reshape(B, scores.shape[1], S // 16, 16, -1)
    mine = (tiled == np.arange(1, B + 1).reshape(B, 1, 1, 1, 1) * Hi * Di
            ).all(axis=(3, 4))
    trips, full = sla.score_tiles(cl, nn, S, Hi, mp, ps)
    # tiles: two large; one small; a large one (12 padded rows); none; a
    # large one and a small one; one small (a large one would be half empty)
    assert mine.sum(axis=(1, 2)).tolist() == trips.tolist() == [
        4 * 1, 1 * 3, 2 * 3, 0, 3 * 1, 1 * 2]
    assert full == 4 * 3 and sla.score_grid(mp, ps) == (3, 512)
    assert sla.score_grid(264, 16, kpool=4) == (9, sla.POOLED_BLOCK_K)
    assert sla.score_rows(128, 16) == (16, 128) and sla.score_rows(
        8, 16) == (8, 8) and sla.score_rows(128, 32) == (16, 64)


def _undefined_scores(scores, cl, nn, heads, kpool=1):
    """``scores`` with NaN wherever :func:`sla.index_scores` defines
    nothing: the row tiles past a slot's real rows, the blocks past its
    context."""
    B, NB, S, bk = scores.shape
    rows, large = sla.score_rows(S, heads)
    n_large, n_small = sla._tiles(np.asarray(nn), S, rows, large)
    tiles = n_large * large + n_small * rows
    blocks = -(-((np.asarray(cl) + np.asarray(nn)) // kpool) // bk)
    written = (np.arange(S)[None, None, :, None] < tiles[:, None, None, None]
               ) & (np.arange(NB)[None, :, None, None]
                    < blocks[:, None, None, None])
    return jnp.where(written, scores, jnp.nan)


@pytest.mark.parametrize("walk", ["latent", "paged", "pooled"])
def test_nothing_reads_a_score_the_kernel_does_not_define(walk):
    """The selection and the three walks over scores whose undefined part
    is NaN (the row tiles past a slot's real rows, the blocks past its
    context, an idle slot's everything): the real rows of the layer's output
    are finite and EQUAL those of the run on the scores as the kernel left
    them."""
    from deepspeed_tpu.ops.pallas import sparse_paged_attention as spa

    B, S, H, Hi, W, ps, mp, topk = 3, 32, 2, 2, 128, 16, 64, 40
    kpool = 4 if walk == "pooled" else 1
    rng = np.random.default_rng(3)
    f = lambda *s: jnp.asarray(rng.normal(size=s), F32)
    P = B * mp
    table = jnp.asarray(rng.permutation(P).reshape(B, mp), jnp.int32)
    cl = jnp.asarray([400, 77, 300], jnp.int32)
    nn = jnp.asarray([20, 5, 0], jnp.int32)  # a large tile, a small, idle
    q_idx, w_idx = f(B, S, Hi, 128), f(B, S, Hi)
    ki = f(1, P + 1, ps // kpool, 128)
    kw = dict(num_new=nn, interpret=True)
    if walk == "pooled":
        view, counting = sla.pooled_view(ki, table, 0)
        scores = sla.index_scores(q_idx, w_idx, view, cl, counting, layer=0,
                                  block_k=sla.POOLED_BLOCK_K, kpool=kpool, **kw)
    else:
        scores = sla.index_scores(q_idx, w_idx, ki, cl, table, layer=0, **kw)
    if walk == "paged":
        q, k, v = f(B, S, 4, 16), f(1, P + 1, ps, 2, 16), f(1, P + 1, ps, 2, 16)
    else:
        q_abs, kv = f(B, S, H, W), f(1, P + 1, ps, W)

    @jax.jit
    def layer(scores):
        thr, tie = sla.select_topk(scores, cl, nn, topk, interpret=True,
                                   kpool=kpool)
        if walk == "paged":
            return spa.sparse_paged_attention_kernel(
                q, k, v, scores, thr, tie, cl, table, layer=0, **kw)
        return sla.sparse_attention(
            q_abs, kv, scores, thr, tie, cl, table, layer=0, scale=0.1,
            v_width=W, kpool=kpool, **kw)

    dirty = _undefined_scores(scores, cl, nn, Hi, kpool)
    assert bool(jnp.isnan(dirty[0, :, 31]).all()) is False  # in its tile
    assert bool(jnp.isnan(dirty[1, :, 16:]).all()) and bool(
        jnp.isnan(dirty[2]).all()) and bool(jnp.isnan(dirty[0, -1]).all())
    clean, soiled = np.asarray(layer(scores)), np.asarray(layer(dirty))
    for b in range(B):
        n = int(nn[b])
        assert np.isfinite(soiled[b, :n]).all()
        np.testing.assert_array_equal(soiled[b, :n], clean[b, :n])


def test_the_attention_kernel_attends_the_chosen_rows_alone():
    k = kernel_inputs(seed=2)
    layer, topk, S, V = 0, 24, 16, 64
    out, why = sla.latent_sparse_attention(
        k["q_abs"], k["q_idx"], k["w_idx"], k["kv"], k["ki"], k["cl"],
        k["pt"], layer=layer, topk=topk, scale=0.1, v_width=V,
        num_new=k["nn"], interpret=True)
    assert why == []
    qpos = k["cl"][:, None] + jnp.arange(S)[None]
    chosen = sla.dense_selection(sla.dense_index_scores(
        k["q_idx"], k["w_idx"], _paged_gather(k["ki"][layer], k["pt"])),
        qpos, topk)
    want = sla.dense_sparse_attention(
        k["q_abs"], _paged_gather(k["kv"][layer], k["pt"]), chosen, 0.1, V)
    real = (jnp.arange(S)[None] < k["nn"][:, None])[..., None, None]
    np.testing.assert_allclose(jnp.where(real, out, 0),
                               jnp.where(real, want, 0), atol=1e-5)


def test_the_absorbed_form_is_the_plain_form(model, params, shape):
    """One attention block over a fresh chunk: the program scores absorbed
    queries against cached latents and up-projects after the sum; the
    reference builds every head's keys and values. Same numbers."""
    from deepspeed_tpu.models.decoding import (ChunkRows,
                                               _latent_cached_attention)

    cfg = model.config
    S, ps = 48, 16
    x = jax.random.normal(jax.random.PRNGKey(3), (1, S, cfg.hidden_size))
    a = jax.tree.map(lambda w: w[1], params["layers"]["attn"])
    pools = init_paged_cache(cfg, 4, ps, F32)
    table = jnp.asarray([[2, 0, 3, 1]], jnp.int32)
    normed = reference.rmsnorm(x, {"scale": jnp.ones(cfg.hidden_size)},
                               cfg.norm_eps)
    rows = ChunkRows(1, S, jnp.zeros(1, jnp.int32))
    got, pools = _latent_cached_attention(
        cfg, a, normed, rows, 2, pools, jnp.zeros(1, jnp.int32), table,
        page_rows=rows.page_rows(table, pools[LATENT]))
    ones = {"scale": jnp.ones(cfg.hidden_size)}
    with reference.HIGHEST():
        want = fam._attn(x[0], ones, a, shape,
                         jnp.asarray(fam.rope_table(shape))) - x[0]
    np.testing.assert_allclose(got[0], want, atol=2e-5, rtol=2e-4)
    # the cache holds the latent and the rotated key, one row a token
    assert pools[LATENT].shape[-1] == 128 and pools[INDEX].shape[-1] == 16
    rows = _paged_gather(pools[LATENT][2], table)[0, :S]
    assert float(jnp.abs(rows[:, cfg.latent_width:]).max()) == 0.0
    assert float(jnp.abs(pools[LATENT][1]).max()) == 0.0  # another layer


def test_the_gate_against_a_case_worked_by_hand():
    """8 experts in 4 groups of 2, 2 groups kept, top-2, scaling 2.5. The
    bias changes the choice and not the weight."""
    s = np.array([[0.9, 0.1, 0.6, 0.55, 0.5, 0.45, 0.2, 0.8]], np.float32)
    logits = jnp.asarray(np.log(s / (1 - s)))
    zero = jnp.zeros(8)
    # groups score 1.0, 1.15, 0.95, 1.0: groups 1 and 0 kept (a tie at 1.0
    # goes to the lower group); inside them the two best are 0.9 and 0.6
    idx, w = sm.sigmoid_group_gate(logits, zero, 2, 4, 2, 2.5)
    assert idx.tolist() == [[0, 2]]
    np.testing.assert_allclose(w, [[2.5 * 0.9 / 1.5, 2.5 * 0.6 / 1.5]],
                               rtol=1e-5)
    # a bias on expert 5: group 2 scores 1.35 and stays with group 1; the
    # choice is 5 (biased 0.85) then 2 (0.6), weights from the UNBIASED
    # scores 0.45 and 0.6
    bias = zero.at[5].set(0.4)
    idx, w = sm.sigmoid_group_gate(logits, bias, 2, 4, 2, 2.5)
    assert idx.tolist() == [[5, 2]]
    np.testing.assert_allclose(w, [[2.5 * 0.45 / 1.05, 2.5 * 0.6 / 1.05]],
                               rtol=1e-5)
    # all groups kept: the plain top-2 of the biased scores
    idx, _ = sm.sigmoid_group_gate(logits, zero, 2, 4, 4, 1.0)
    assert idx.tolist() == [[0, 7]]
    # the reference routes alike
    x = jnp.asarray([[1.0, 0.0, 0.0, 0.0]])  # normed: [2, 0, 0, 0]
    router = jnp.zeros((4, 8)).at[0].set(logits[0] / 2.0)
    _, wr, margin = fam._route(
        x, {"scale": jnp.ones(4)}, router, bias, top_k=2, groups=4,
        groups_kept=2, scale=2.5, first=0, held=8, eps=0.0)
    np.testing.assert_allclose(
        wr[0, [5, 2]], [2.5 * 0.45 / 1.05, 2.5 * 0.6 / 1.05], rtol=1e-4)
    assert float(wr.sum()) == pytest.approx(2.5, rel=1e-5)
    assert float(margin[0]) == pytest.approx(0.6 - 0.55, abs=1e-5)


def test_the_sixteen_shares_add_up(model, params):
    """The partial outputs of every member of the expert-parallel layer, the
    shared expert counted once, are the uncut layer's output; and no member
    drops a token."""
    cfg = model.config  # 4 of 16 experts a member: 4 members
    E, held = cfg.routed_experts, cfg.num_experts
    rng = jax.random.PRNGKey(11)
    p = jax.tree.map(lambda w: w[0], params["layers"]["mlp"])
    d, f = cfg.hidden_size, cfg.ffn
    ks = jax.random.split(rng, 4)
    bank = {n: jax.random.normal(k, (E, *p[n].shape[1:])) * 0.1
            for n, k in zip(("wi", "wg", "wo"), ks)}
    x = jax.random.normal(ks[3], (2, 16, d))
    valid = jnp.arange(16)[None, :] < jnp.asarray([16, 5])[:, None]
    whole_cfg = dataclasses.replace(cfg, num_experts=E, moe_routed_experts=E)
    whole, _ = sm.moe_serving_mlp(whole_cfg, {**p, **bank}, x,
                                  token_valid=valid)
    from deepspeed_tpu.models.transformer import _mlp

    shared = _mlp(cfg, p["shared"], x, None, False, dense=True)[0]
    parts, seen = [], 0
    for m in range(E // held):
        share = dataclasses.replace(cfg, moe_first_expert=m * held)
        mine = {n: w[m * held:(m + 1) * held] for n, w in bank.items()}
        out, stats = sm.moe_serving_mlp(share, {**p, **mine}, x,
                                        token_valid=valid)
        assert float(stats["drop_fraction"]) == 0.0
        seen += int(stats["tokens_per_expert"].sum())
        parts.append(out)
    assert seen == 21 * cfg.moe_top_k  # every choice of every real token
    total = sum(parts) - (E // held - 1) * shared
    real = valid[..., None]
    np.testing.assert_allclose(jnp.where(real, total, 0),
                               jnp.where(real, whole, 0), atol=1e-5)
    # a member differs from the whole (the test would pass vacuously else)
    assert float(jnp.abs(jnp.where(real, parts[0] - whole, 0)).max()) > 1e-3


def serving(**over):
    return dict(dict(max_slots=4, token_budget=16, max_tokens=384, paged=True,
                     page_size=16, num_pages=0, prefix_cache=False), **over)


def test_the_engine_serves_the_references_argmax_and_counts_its_work(
        model, params, shape):
    srv = deepspeed_tpu.init_serving(model, serving=serving(), params=params,
                                     dtype=F32)
    rng = np.random.default_rng(0)
    states = [srv.submit(Request(
        request_id=f"r{i}", prompt=rng.integers(0, 512, n, np.int32),
        max_new_tokens=6, temperature=0.0, eos_token_id=-1))
        for i, n in enumerate((20, 75, 130))]
    srv.run_until_idle()
    assert srv.step_traces == 1
    assert srv.attention_path == "dense" and srv.attention_fallback
    for st in states:
        ids = np.concatenate([st.request.prompt, np.asarray(st.tokens)])
        want = logits_of(params, ids[:-1], shape, last=6)
        assert reference.served_token_gaps(want, st.tokens).max() == 0.0
    snap = srv.metrics.snapshot()
    # the plan's arithmetic: a token at position p scores p + 1 keys and
    # attends min(p + 1, 24)
    ctx = sum(p + 1 for n in (20, 75, 130) for p in range(n + 5))
    att = sum(min(p + 1, 24) for n in (20, 75, 130) for p in range(n + 5))
    assert snap["context_keys"] == ctx and snap["attended_keys_sparse"] == att
    assert snap["moe_steps"] == srv.metrics.steps
    assert snap["moe_dropped_fraction"] == 0.0
    rows = 3 * sum(n + 5 for n in (20, 75, 130))  # real tokens x 3 layers
    assert 0 < snap["moe_unrouted_tokens"] < rows
    assert snap["moe_routed_tokens"] + snap["moe_unrouted_tokens"] >= rows


def test_the_engine_with_the_kernels_serves_the_dense_tokens(model, params):
    def served():
        srv = deepspeed_tpu.init_serving(model, serving=serving(),
                                         params=params, dtype=F32)
        rng = np.random.default_rng(4)
        states = [srv.submit(Request(
            request_id=f"r{i}", prompt=rng.integers(0, 512, n, np.int32),
            max_new_tokens=4, temperature=0.0, eos_token_id=-1))
            for i, n in enumerate((33, 61))]
        srv.run_until_idle()
        return srv, [st.tokens for st in states]

    dense, want = served()
    with attention_impl("flash"):  # the kernels, in interpret mode here
        kern, got = served()
    assert got == want
    assert dense.attention_path == "dense"
    assert kern.attention_path == "latent_sparse_kernel"
    assert kern.attention_fallback == ()
    assert kern.metrics.snapshot()["attention_paged_kernel"] == 1.0
    kern.lower_step()


@pytest.mark.parametrize("what,kw,match", [
    ("int8-kv", dict(kv_cache_dtype="int8"), "int8 KV cache is refused"),
    ("contiguous-arena", dict(serving=serving(paged=False)),
     "contiguous KV arena is refused"),
    ("host-pages", dict(serving=serving(host_pages=8)),
     "host_pages is refused"),
])
def test_what_the_latent_path_cannot_take_is_refused_by_mechanism(
        model, params, what, kw, match):
    kw = dict(dict(serving=serving()), **kw)
    with pytest.raises(DeepSpeedConfigError, match=match):
        deepspeed_tpu.init_serving(model, params=params, dtype=F32, **kw)


def test_training_refuses_the_indexer_by_name_and_runs_the_rest(model, params):
    batch = {"input_ids": jnp.zeros((2, 8), jnp.int32),
             "labels": jnp.zeros((2, 8), jnp.int32)}
    with pytest.raises(DeepSpeedConfigError, match="index_topk") as e:
        model.loss(params, batch)
    for mechanism in ("kv_latent_dim", "lead_dense_layers", "sigmoid_groups",
                      "moe_shared_width", "moe_routed_experts"):
        assert mechanism not in str(e.value)
    assert "deepseek" not in str(e.value).lower()
    # without its indexer the same model trains: latent attention, the
    # leading dense layer, the sigmoid router over one member's share and
    # the shared expert all run in the uncached forward
    from deepspeed_tpu.models.transformer import TransformerModel

    plain = TransformerModel(dataclasses.replace(
        model.config, index_topk=0, index_heads=0, index_dim=0))
    p = jit_init(plain, jax.random.PRNGKey(7))
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, 512)
    batch = {"input_ids": ids, "labels": jnp.roll(ids, -1, axis=1)}
    (loss, m), g = jax.jit(jax.value_and_grad(
        lambda p: plain.loss(p, batch, dtype=F32), has_aux=True))(p)
    assert np.isfinite(float(loss)) and float(m["moe_rows_held"]) > 0
    for leaf in (g["lead_layers"]["mlp"]["wi"], g["layers"]["attn"]["wkv_b"],
                 g["layers"]["mlp"]["router"], g["layers"]["mlp"]["wo"],
                 g["layers"]["mlp"]["shared"]["wi"]):
        assert float(jnp.abs(leaf).max()) > 0
    assert not np.any(np.asarray(g["layers"]["mlp"]["sel_bias"]))
    # one mechanism alone runs too: a shared expert beside a softmax router
    from deepspeed_tpu.models import mixtral

    base = mixtral("mixtral-tiny").config
    shared = TransformerModel(dataclasses.replace(base, moe_shared_width=16))
    ps = jit_init(shared, jax.random.PRNGKey(3))
    ids = ids % base.vocab_size
    batch = {"input_ids": ids, "labels": jnp.roll(ids, -1, axis=1)}
    loss_of = jax.jit(lambda p: shared.loss(p, batch, dtype=F32)[0])
    with_it = loss_of(ps)
    ps["layers"]["mlp"]["shared"]["wo"] *= 0.0
    assert float(jnp.abs(with_it - loss_of(ps))) > 0
    # ... and one member's share of a softmax-routed layer is refused where
    # the configuration is made, by its own name
    with pytest.raises(ValueError, match="moe_routed_experts"):
        dataclasses.replace(base, moe_routed_experts=2 * base.num_experts)
