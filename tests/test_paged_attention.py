"""The paged Pallas attention of the serving step's [slots, chunk] block
(ops/pallas/paged_attention.py), in interpret mode: the kernel against the
dense XLA lines of models/decoding.py on the same pools. The kernel takes
the pools as init_paged_cache stacks them, [L, P+1, ps, KV, hd], and a
layer's index; the dense lines read ``stack[layer]``."""

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.decoding import (_dense_cached_attention,
                                           _paged_gather)
from deepspeed_tpu.ops.pallas.paged_attention import (SMALL_ROWS,
                                                      paged_attention,
                                                      paged_attention_kernel,
                                                      row_tile,
                                                      small_tile_slots)

PS, MP, NULL = 8, 32, 40  # page size, pages a slot, the NULL page's index


def _pools_and_table(r, KV, hd, dtype):
    """40 pages + NULL, five slots: ragged frontiers off every page and
    block boundary, a shuffled table, two slots sharing prefix pages, a
    table partly on the NULL page, an idle slot, a context of 3+ blocks.
    The pools are stacks of one layer."""
    k_pool = jnp.asarray(r.randn(1, NULL + 1, PS, KV, hd), jnp.float32)
    v_pool = jnp.asarray(r.randn(1, NULL + 1, PS, KV, hd), jnp.float32)
    pt = np.full((5, MP), NULL, np.int32)
    pt[0, :7] = [5, 2, 7, 11, 30, 1, 9]
    # slot 1 shares slot 0's first three pages, then diverges
    pt[1, :6] = [5, 2, 7, 12, 13, 3]
    # slot 2 is idle: every entry on the NULL page
    # slot 3 holds a long context: 14 pages = 112 positions
    pt[3, :14] = r.permutation(np.arange(14, 28))
    # slot 4: a short prompt from empty, its tail on the NULL page
    pt[4, :1] = [0]
    cache_len = np.asarray([37, 29, 0, 91, 0], np.int32)
    return (k_pool.astype(dtype), v_pool.astype(dtype), jnp.asarray(pt),
            jnp.asarray(cache_len))


def _case(S, G, dtype, seed):
    dtype = jnp.dtype(dtype)
    KV, hd = 2, 32
    H = KV * G
    r = np.random.RandomState(seed)
    k_pool, v_pool, pt, cache_len = _pools_and_table(r, KV, hd, dtype)
    q = jnp.asarray(r.randn(pt.shape[0], S, H, hd), jnp.float32).astype(dtype)
    cfg = types.SimpleNamespace(num_heads=H, kv_heads=KV, hd=hd,
                                pos_embedding="rope")
    ref = np.asarray(_dense_cached_attention(
        cfg, q, _paged_gather(k_pool[0], pt), _paged_gather(v_pool[0], pt),
        cache_len,
    ))
    # float32 to 1e-5; bf16 operands, probabilities and output each round
    # to 2^-9 relative
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    return q, k_pool, v_pool, pt, cache_len, ref, tol


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("G", [1, 4])
@pytest.mark.parametrize("S", [1, 8, 128])
def test_paged_attention_kernel_matches_dense_lines(S, G, dtype):
    q, k_pool, v_pool, pt, cache_len, ref, tol = _case(
        S, G, dtype, S * 10 + G
    )
    num_new = jnp.asarray([S, max(S - 3, 1), 0, S, min(S, 5)], jnp.int32)
    # block_k 32 = 4 pages a block: slot 3 walks 3 blocks or more, slot 0 two
    out = np.asarray(paged_attention_kernel(
        q, k_pool, v_pool, cache_len, pt, layer=0, num_new=num_new,
        block_k=32,
    ).astype(jnp.float32))
    assert out.shape == ref.shape
    for b in range(pt.shape[0]):
        n = int(num_new[b])
        np.testing.assert_allclose(out[b, :n], ref[b, :n], atol=tol, rtol=tol)
    # rows past num_new and an idle slot's are padding: finite, no more
    assert np.isfinite(out).all()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_paged_attention_wrapper_every_row_real(dtype):
    """Without num_new every row is real: the whole block matches, through
    the wrapper and its default block (one block holds every context)."""
    q, k_pool, v_pool, pt, cache_len, ref, tol = _case(8, 4, dtype, 5)
    out, reasons = paged_attention(q, k_pool, v_pool, cache_len, pt, layer=0)
    assert reasons == []
    np.testing.assert_allclose(
        np.asarray(out.astype(jnp.float32)), ref, atol=tol, rtol=tol
    )


def test_paged_attention_work_follows_length():
    """The loop's trip count comes from the frontier: pages past the last
    key a real row needs are never read, so poisoning them changes
    nothing — and a table pointing past a short slot's length costs no
    NaN."""
    KV, G, hd, S = 2, 2, 32, 8
    r = np.random.RandomState(7)
    k_pool, v_pool, pt, cache_len = _pools_and_table(r, KV, hd, jnp.float32)
    q = jnp.asarray(r.randn(pt.shape[0], S, KV * G, hd), jnp.float32)
    num_new = jnp.asarray([S, S, 0, S, 4], jnp.int32)
    out = paged_attention_kernel(q, k_pool, v_pool, cache_len, pt, layer=0,
                                 num_new=num_new, block_k=16)
    # slot 0 needs keys 0..44: blocks 0-2 of 16 = pages 0-5; its 7th page
    # (physical 9) and the NULL page are never fetched for it
    poisoned_k = k_pool.at[0, 9].set(jnp.nan).at[0, NULL].set(jnp.nan)
    out2 = paged_attention_kernel(q, poisoned_k, v_pool, cache_len, pt,
                                  layer=0, num_new=num_new, block_k=16)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(out2[0]))
    np.testing.assert_array_equal(np.asarray(out[3]), np.asarray(out2[3]))


def _layer_cases():
    return [(L, layer) for L in (1, 3) for layer in range(L)]


def _stack_with_noise_elsewhere(pool, L, layer):
    """[1, P+1, ...] -> [L, P+1, ...]: ``pool`` at ``layer``, NaN in every
    other layer, so a read of the wrong layer shows."""
    return jnp.full((L, *pool.shape[1:]), jnp.nan, pool.dtype).at[layer].set(
        pool[0])


@pytest.mark.parametrize("L,layer", _layer_cases())
def test_paged_attention_under_tp_matches_one_device(L, layer):
    """Heads over tp through shard_map: the stack's spec has the layer
    axis unsharded in front, the layer's index rides replicated."""
    from deepspeed_tpu.comm.topology import MeshTopology, ParallelDims
    from deepspeed_tpu.models.sharding import use_topology

    KV, G, hd, S = 2, 2, 32, 8
    r = np.random.RandomState(3)
    k_pool, v_pool, pt, cache_len = _pools_and_table(r, KV, hd, jnp.float32)
    q = jnp.asarray(r.randn(pt.shape[0], S, KV * G, hd), jnp.float32)
    one, _ = paged_attention(q, k_pool, v_pool, cache_len, pt, layer=0)
    k_stack = _stack_with_noise_elsewhere(k_pool, L, layer)
    v_stack = _stack_with_noise_elsewhere(v_pool, L, layer)
    topo = MeshTopology(dims=ParallelDims(tp=2), devices=jax.devices()[:2])
    with use_topology(topo):
        two, reasons = jax.jit(
            lambda at: paged_attention(q, k_stack, v_stack, cache_len, pt,
                                       layer=at)
        )(jnp.int32(layer))
    assert reasons == []
    np.testing.assert_allclose(np.asarray(two), np.asarray(one), atol=1e-6)


@pytest.mark.parametrize(
    "kw,why",
    [
        (dict(hd=32), "head_dim 32 not 128-aligned"),
        (dict(KV=1, H=4), "1 local KV heads in bfloat16 do not fill"),
        (dict(pool_dtype="int8"), "int8 KV pool"),
        (dict(mp=16384), "page table is over the 512 KiB of SMEM"),
        # (a 1,024-row chunk runs as row tiles since PR 56; one whose rows
        # divide into no sublane-whole tile is still declined)
        (dict(S=1028), "MiB of VMEM"),
        (dict(H=6, KV=4), "H=6 not a multiple of KV=4"),
    ],
    ids=["head-dim", "one-bf16-head", "int8", "smem", "vmem", "ragged-gqa"],
)
def test_paged_attention_steps_aside_with_reasons(kw, why):
    """What the chip's compiler would refuse is refused by the wrapper:
    no kernel, the reason named (and logged once), nothing raised."""
    cell = dict(B=16, S=128, H=32, KV=8, hd=128, mp=528,
                pool_dtype="bfloat16")
    B, S, H, KV, hd, mp, pool_dtype = {**cell, **kw}.values()
    ps, sds = 16, jax.ShapeDtypeStruct

    def fn(q, k, v, cl, pt):
        out, reasons = paged_attention(q, k, v, cl, pt, layer=1,
                                       interpret=False)
        assert out is None
        assert any(why in r for r in reasons), reasons
        return cl

    jax.eval_shape(
        fn, sds((B, S, H, hd), jnp.bfloat16),
        sds((3, 65, ps, KV, hd), jnp.dtype(pool_dtype)),
        sds((3, 65, ps, KV, hd), jnp.dtype(pool_dtype)),
        sds((B,), jnp.int32), sds((B, mp), jnp.int32),
    )


@pytest.mark.parametrize("name,window", [
    (None, None), ("paged_attention_full", None),
    ("paged_attention_window", 24),
])
@pytest.mark.parametrize("L,layer", _layer_cases())
def test_kernel_reads_its_layer_of_the_stack(L, layer, name, window):
    """The pools come stacked, [L, P+1, ps, KV, hd], with the layer's index
    a traced scalar: the kernel attends ``stack[layer]`` as the dense lines
    do on that slice, under each of its three names, and touches no other
    layer (they hold NaN)."""
    KV, G, hd, S = 2, 2, 32, 8
    r = np.random.RandomState(11 * L + layer)
    k_pool, v_pool, pt, cache_len = _pools_and_table(r, KV, hd, jnp.float32)
    q = jnp.asarray(r.randn(pt.shape[0], S, KV * G, hd), jnp.float32)
    num_new = jnp.asarray([S, S - 3, 0, S, 5], jnp.int32)
    k_stack = _stack_with_noise_elsewhere(k_pool, L, layer)
    v_stack = _stack_with_noise_elsewhere(v_pool, L, layer)
    cfg = types.SimpleNamespace(num_heads=KV * G, kv_heads=KV, hd=hd,
                                pos_embedding="rope")
    ref = np.asarray(_dense_cached_attention(
        cfg, q, _paged_gather(k_stack[layer], pt),
        _paged_gather(v_stack[layer], pt), cache_len, window=window))
    out = np.asarray(jax.jit(
        lambda at: paged_attention_kernel(
            q, k_stack, v_stack, cache_len, pt, layer=at, num_new=num_new,
            block_k=32, window=window, name=name)
    )(jnp.int32(layer)))
    assert np.isfinite(out).all()
    for b in range(pt.shape[0]):
        n = int(num_new[b])
        np.testing.assert_allclose(out[b, :n], ref[b, :n], atol=1e-5,
                                   rtol=1e-5)


# --------------------------- the widths the online softmax's statistics meet
# decode_attention._tile_update keeps its running max and sum [rows, 128],
# every lane the same, and meets the [rows, block_k] scores and the
# [rows, hd] accumulator through flash_attention._lanes_to, which branches
# on the static width: whole vregs tiled (a multiple of 128), a lane prefix
# (narrower), a broadcast (wider and odd). Each caller against its float32
# oracle at each branch, at the tolerances the cases above hold.
WIDE_PS, WIDE_MP = 16, 80  # 1,280 tokens a slot: three blocks of 512


def _wide_pools(r, B, KV, hd, L=1):
    """B slots of WIDE_MP pages each, the table a permutation, NULL last."""
    P = B * WIDE_MP
    k_pool = jnp.asarray(r.randn(L, P + 1, WIDE_PS, KV, hd), jnp.float32)
    v_pool = jnp.asarray(r.randn(L, P + 1, WIDE_PS, KV, hd), jnp.float32)
    pt = jnp.asarray(r.permutation(P).reshape(B, WIDE_MP), jnp.int32)
    return k_pool, v_pool, pt


def _heads(H, KV, hd):
    return types.SimpleNamespace(num_heads=H, kv_heads=KV, hd=hd,
                                 pos_embedding="rope")


def _dense(q, k_pool, v_pool, pt, cache_len, window=None):
    return np.asarray(_dense_cached_attention(
        _heads(q.shape[2], *k_pool.shape[3:]), q,
        _paged_gather(k_pool[0], pt), _paged_gather(v_pool[0], pt),
        cache_len, window=window))


@pytest.mark.parametrize("hd", [64, 128])
@pytest.mark.parametrize("block_k", [128, 192, 256, 512])
def test_paged_attention_at_every_score_and_accumulator_width(block_k, hd):
    """Score tiles of 128, 256 and 512 keys (whole vregs), of 192 (no
    multiple of the lanes), accumulators of 64 lanes (a prefix) and 128: a
    chunk deep in a context of three 512-key blocks, a prompt from empty, a
    slot with ``num_new`` 0 and a decoding slot, in float32 to 1e-5."""
    S, KV, G = 8, 2, 2
    r = np.random.RandomState(block_k + hd)
    k_pool, v_pool, pt = _wide_pools(r, 4, KV, hd)
    q = jnp.asarray(r.randn(4, S, KV * G, hd), jnp.float32)
    cache_len = jnp.asarray([1070, 0, 300, 517], jnp.int32)
    num_new = jnp.asarray([S, S - 3, 0, 1], jnp.int32)
    out = np.asarray(paged_attention_kernel(
        q, k_pool, v_pool, cache_len, pt, layer=0, num_new=num_new,
        block_k=block_k))
    ref = _dense(q, k_pool, v_pool, pt, cache_len)
    for b in range(4):
        n = int(num_new[b])
        np.testing.assert_allclose(out[b, :n], ref[b, :n], atol=1e-5,
                                   rtol=1e-5)
    assert np.isfinite(out).all() and not out[2].any()  # the idle slot


@pytest.mark.parametrize("block_k,hd", [(128, 64), (256, 128)])
def test_a_row_with_no_visible_key_in_its_first_tile(block_k, hd):
    """A window layer's loop starts at the block of ROW 0's oldest visible
    key. With 128 rows under a window of 64 at a frontier of 200, row 0
    sees keys 137..200 and the last row 264..327: the first block the loop
    reads (keys 128..255, or 0..255) holds no key of rows 73 and later, so
    their running max is still NEG_INF after it (the guard that keeps
    ``exp(NEG_INF - NEG_INF)`` out) and their first real tile comes
    second."""
    S, KV, G, window = 128, 2, 1, 64
    r = np.random.RandomState(hd)
    k_pool, v_pool, pt = _wide_pools(r, 2, KV, hd)
    q = jnp.asarray(r.randn(2, S, KV * G, hd), jnp.float32)
    cache_len = jnp.asarray([200, 0], jnp.int32)
    out = np.asarray(paged_attention_kernel(
        q, k_pool, v_pool, cache_len, pt, layer=0, block_k=block_k,
        window=window))
    ref = _dense(q, k_pool, v_pool, pt, cache_len, window=window)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def _quantized(r, shape):
    from deepspeed_tpu.models.decoding import _quantize_kv

    return _quantize_kv(jnp.asarray(r.randn(*shape), jnp.float32))


@pytest.mark.parametrize("hd", [64, 128])
@pytest.mark.parametrize("block_s", [128, 192, 256, 512])
def test_dense_decode_int8_at_every_tile_width(block_s, hd):
    """The dense decode kernel over an int8 cache with its per-token
    scales, one [block_s, hd] tile a grid step: the same four score widths
    and two accumulator widths against the dequantize-then-attend lines."""
    from deepspeed_tpu.ops.pallas.decode_attention import (
        decode_attention_kernel)

    B, Smax, H, KV = 2, 1536, 4, 2
    r = np.random.RandomState(block_s + hd)
    q = jnp.asarray(r.randn(B, 1, H, hd), jnp.float32)
    kq, ks = _quantized(r, (B, Smax, KV, hd))
    vq, vs = _quantized(r, (B, Smax, KV, hd))
    ks, vs = jnp.swapaxes(ks, 1, 2), jnp.swapaxes(vs, 1, 2)  # as stored
    cache_len = jnp.asarray([5, 1100], jnp.int32)
    out = decode_attention_kernel(q, kq, vq, cache_len, k_scale=ks,
                                  v_scale=vs, block_s=block_s)
    ref = _dense_cached_attention(_heads(H, KV, hd), q, kq, vq, cache_len,
                                  ks, vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("hd", [64, 128])
def test_paged_decode_int8_gathers_scales_through_the_table(hd):
    """The paged decode kernel's tile is a page (16 keys: a lane prefix of
    the statistics): int8 pools with scales, shuffled pages, a slot on its
    first page and one on its last."""
    from deepspeed_tpu.models.decoding import _paged_gather_scale
    from deepspeed_tpu.ops.pallas.decode_attention import (
        paged_decode_attention_kernel)

    B, mp, ps, H, KV, P = 3, 6, 16, 4, 2, 18
    r = np.random.RandomState(hd)
    q = jnp.asarray(r.randn(B, 1, H, hd), jnp.float32)
    kq, ks = _quantized(r, (P + 1, ps, KV, hd))
    vq, vs = _quantized(r, (P + 1, ps, KV, hd))
    ks, vs = jnp.swapaxes(ks, 1, 2), jnp.swapaxes(vs, 1, 2)  # [P+1,KV,ps,SL]
    pt = jnp.asarray(r.permutation(P).reshape(B, mp), jnp.int32)
    cache_len = jnp.asarray([3, 95, 40], jnp.int32)
    out = paged_decode_attention_kernel(q, kq, vq, cache_len, pt,
                                        k_scale=ks, v_scale=vs)
    ref = _dense_cached_attention(
        _heads(H, KV, hd), q, _paged_gather(kq, pt), _paged_gather(vq, pt),
        cache_len,
        _paged_gather_scale(ks, pt), _paged_gather_scale(vs, pt))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# ---- one program a slot: the small tile of a slot's stack (PR 63). A slot
# whose real rows fit SMALL_ROWS stacked rows computes those alone; the
# whole-stack body is forced by calling with every row real.
SMALL_S = 16  # a 16-row chunk: [64, hd] and [128, hd] stacks at G = 4 and 8


@pytest.mark.parametrize("window", [None, 200])
@pytest.mark.parametrize("hd", [128, 256])
@pytest.mark.parametrize("G", [4, 8])
def test_a_slot_whose_real_rows_fit_the_small_tile_computes_it_alone(
        G, hd, window):
    """An idle slot, a decoding one, the most rows the small tile takes, one
    more (the first that takes the whole stack) and a full chunk in one
    call, at frontiers inside a page and inside a key block: real rows match
    the dense lines and are the whole-stack body's to the bit, rows past the
    small tile read zeros exactly where ``small_tile_slots`` says so."""
    KV, S = 2, SMALL_S
    fit = SMALL_ROWS // G
    r = np.random.RandomState(G * hd + (window or 0))
    k_pool, v_pool, pt = _wide_pools(r, 5, KV, hd)
    k_pool, v_pool = k_pool.astype(jnp.bfloat16), v_pool.astype(jnp.bfloat16)
    q = jnp.asarray(r.randn(5, S, KV * G, hd), jnp.bfloat16)
    cache_len = jnp.asarray([1070, 517, 300, 1029, 0], jnp.int32)
    num_new = np.asarray([1, fit, 0, fit + 1, S], np.int32)
    call = jax.jit(functools.partial(
        paged_attention_kernel, layer=0, window=window))
    out = np.asarray(call(q, k_pool, v_pool, cache_len, pt,
                          num_new=jnp.asarray(num_new)).astype(jnp.float32))
    whole = np.asarray(call(q, k_pool, v_pool, cache_len, pt,
                            num_new=jnp.full((5,), S, jnp.int32)
                            ).astype(jnp.float32))
    ref = _dense(q, k_pool, v_pool, pt, cache_len, window=window)
    took_small = 0
    for b, n in enumerate(num_new):
        np.testing.assert_allclose(out[b, :n], ref[b, :n], atol=2e-2,
                                   rtol=2e-2)
        np.testing.assert_array_equal(out[b, :n], whole[b, :n])
        took_small += bool(n) and not out[b, fit:].any()
    assert np.isfinite(out).all() and not out[2].any()  # the idle slot
    assert took_small == small_tile_slots(num_new, G, S) == 2


def test_small_tile_slots_is_the_kernels_predicate():
    """Nothing where the grid is a program a slot and row tile (Command
    A+'s 16 query heads a KV head under a 256-row chunk), nor where the
    whole stack is no larger than the small tile."""
    nn = np.asarray([0, 1, 2, 3, 256])
    rows = row_tile(256, 16, 8, 128, 16, 32, 2, 2)
    assert rows < 256 and small_tile_slots(nn, 16, 256, rows) == 0
    assert row_tile(256, 8, 2, 256, 64, 8, 2, 2) == 256  # Qwen3-Next's
    assert small_tile_slots(nn, 8, 256, 256) == small_tile_slots(
        nn, 8, 256) == 3
    assert small_tile_slots(nn, 4, 256) == 3 and small_tile_slots(
        [8, 9], 4, 256) == 1
    assert small_tile_slots([1, 1], 4, SMALL_ROWS // 4) == 0
