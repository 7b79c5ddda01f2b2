"""Ask the chip's compiler, without the chip.

The TPU compiler is installed here and compiles for a v5e that is
described, not attached. Every Pallas kernel on chip_smoke.py's two paths
is compiled with ``interpret=False`` at the widths the smoke runs (BLOOM-560m
for training, Mixtral-8x7B widths for serving), and the compiled text must
hold the kernel as a ``tpu_custom_call``. Interpret-mode tests pin the
numerics; these pin that the chip would accept the kernel at all — block
shapes off the (8, 128) tiling, SMEM operands and VMEM budgets are only
refused here.

One file on purpose: under ``README.md``'s command only one process may load
libtpu (a second dies on its lock file, and the ``topo`` fixture turns that
into a skip), and the worker that is handed this file keeps it; under the
driver's ``ALLOW_MULTIPLE_LIBTPU_LOAD=1`` two processes at once both describe
``v5e:2x2`` and compile for it. The topology is described inside a fixture,
never at import (on-chip-measurement guide, section 2).
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from deepspeed_tpu.models.decoding import SCALE_LANES
from deepspeed_tpu.models.transformer import alibi_slopes
from deepspeed_tpu.ops.pallas import (
    decode_attention as da,
    flash_attention as fa,
    layernorm as ln,
    paged_attention as pa,
    rmsnorm as rn,
)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever libtpu raises here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache off around these
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_chip_norms(monkeypatch):
    """The norm kernels pick interpret mode from the backend, which is the
    CPU here: steer them from the test, as the chip would."""
    monkeypatch.setattr(rn, "_interpret", lambda: False)
    monkeypatch.setattr(ln, "_interpret", lambda: False)


def _compile(fn, one_chip, *shapes):
    """Compile ``fn`` for the described chip and return the compiled text;
    raises whatever the chip's compiler would raise."""
    args = [
        jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes
    ]
    return jax.jit(fn).lower(*args).compile().as_text()


BF16, F32, I8, I32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32


def _kernel_products(fn, *shapes):
    """The ``dot_general``s in the kernels of ``fn``'s Pallas calls, as
    traced: a body of the paged call has two a block loop."""
    def products(jaxpr, inside):
        n = 0
        for eqn in jaxpr.eqns:
            n += inside and eqn.primitive.name == "dot_general"
            for v in eqn.params.values():
                for x in v if isinstance(v, (tuple, list)) else (v,):
                    sub = getattr(x, "jaxpr", x)  # closed or open
                    if hasattr(sub, "eqns"):
                        n += products(
                            sub, inside or eqn.primitive.name == "pallas_call")
        return n

    return products(jax.make_jaxpr(fn)(
        *[jax.ShapeDtypeStruct(s, d) for s, d in shapes]).jaxpr, False)


# ----------------------------------------------------------------- flash
@pytest.mark.parametrize(
    "B,S,H,KV,hd,alibi,seg",
    [
        (1, 2048, 16, 16, 64, True, False),   # BLOOM-560m: the train phase
        (1, 4096, 32, 8, 128, False, False),  # Mixtral/Llama-8B GQA widths
        (2, 2048, 8, 4, 128, False, True),    # packed sequences (segment ids)
        (4, 2048, 16, 16, 128, True, False),  # BLOOM-1b7: a chip of the dp=4 cell
        (4, 2048, 16, 16, 64, True, False),   # bloom560m-pretrain-2k's call
        (2, 4096, 20, 20, 256, False, False),  # glm47flash-pretrain-4k's call
    ],
    ids=["bloom560m-alibi-hd64", "gqa-h32kv8-hd128-s4096", "segment-ids",
         "bloom1b7-alibi-hd128-b4", "bloom560m-alibi-hd64-b4",
         "glm-latent-hd256-s4096"],
)
def test_flash_fwd_bwd_compiles(one_chip, B, S, H, KV, hd, alibi, seg):
    from deepspeed_tpu.analysis.shardlint import pallas_grids

    slopes = jnp.asarray(alibi_slopes(H), F32) if alibi else None

    def loss(q, k, v, seg_ids):
        out = fa.flash_attention(
            q, k, v, causal=True, alibi_slopes=slopes,
            segment_ids=seg_ids if seg else None, interpret=False,
        )
        return out.astype(F32).sum()

    shapes = (((B, S, H, hd), BF16), ((B, S, KV, hd), BF16),
              ((B, S, KV, hd), BF16), ((B, S), I32))
    grad = jax.grad(loss, argnums=(0, 1, 2))
    text = _compile(grad, one_chip, *shapes)
    # forward + dq + dk/dv kernels
    assert text.count("tpu_custom_call") >= 3, text[:2000]
    # each over the tiles the causal call can see and no others: 10 of 16
    # at 2,048 rows, 36 of 64 at 4,096
    steps, live = fa.walk_steps(fa.causal_layout(S, 512, 512))
    assert steps == live == {2048: 10, 4096: 36}[S]
    grids = pallas_grids(jax.make_jaxpr(grad)(
        *(jax.ShapeDtypeStruct(*sd) for sd in shapes)).jaxpr)
    assert grids == [(k, (B, H, live)) for k in FLASH_KERNELS], grids


FLASH_KERNELS = ("_fwd_kernel", "_bwd_dq_kernel", "_bwd_dkv_kernel")


def test_flash_block_sparse_walk_compiles(one_chip):
    """A block-sparse layout's walk for the described chip: a Longformer
    window with a q-block and a k-block that nothing sees, so the kernels
    carry the dead entries' run predicate, which the causal triangle's do
    not."""
    from deepspeed_tpu.analysis.shardlint import pallas_grids
    from deepspeed_tpu.ops.sparse_attention import (
        BSLongformerSparsityConfig,
        causal_trim,
    )

    B, S, H, hd, blk = 2, 2048, 8, 128, 128
    layout = causal_trim(BSLongformerSparsityConfig(
        block=blk, num_sliding_window_blocks=3).make_layout(S))
    layout[5, :] = 0
    layout[:, 9] = 0

    def loss(q, k, v):
        return fa.flash_attention(
            q, k, v, causal=True, block_mask=layout, block_q=blk,
            block_k=blk, interpret=False).astype(F32).sum()

    shapes = (((B, S, H, hd), BF16),) * 3
    grad = jax.grad(loss, argnums=(0, 1, 2))
    text = _compile(grad, one_chip, *shapes)
    assert text.count("tpu_custom_call") >= 3, text[:2000]
    rows, cols = fa.walk_steps(layout), fa.walk_steps(layout, by_col=True)
    assert rows[0] == rows[1] + 1 and cols[0] == cols[1] + 1  # one dead each
    grids = pallas_grids(jax.make_jaxpr(grad)(
        *(jax.ShapeDtypeStruct(*sd) for sd in shapes)).jaxpr)
    assert grids == [(k, (B, H, n)) for k, n in zip(
        FLASH_KERNELS, (rows[0], rows[0], cols[0]))], grids


def _check_flash_grids(engine, B, H, live, family, capsys):
    """Every flash call of the engine's train step (forward, the remat's
    second forward, dq, dk/dv) runs over (B, H, live tiles): the grid walks
    the tiles a causal call can see and no others."""
    from deepspeed_tpu.analysis import shardlint

    grids = [kg for kg in shardlint.pallas_grids(
        shardlint.trace_train_step(engine)[0].jaxpr)
        if kg[0] in FLASH_KERNELS]
    with capsys.disabled():
        print(f"\n{family} train step: flash grids {grids}")
    assert {k for k, _ in grids} == set(FLASH_KERNELS), grids
    assert {g for _, g in grids} == {(B, H, live)}, grids


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_dense_decode_compiles(one_chip, int8):
    B, Smax, H, KV, hd = 8, 2048, 32, 8, 128
    cache_dt = I8 if int8 else BF16

    def step(q, k, v, cl, ks, vs):
        return da.decode_attention_kernel(
            q, k, v, cl, k_scale=ks if int8 else None,
            v_scale=vs if int8 else None, interpret=False,
        )

    text = _compile(
        step, one_chip,
        ((B, 1, H, hd), BF16), ((B, Smax, KV, hd), cache_dt),
        ((B, Smax, KV, hd), cache_dt), ((B,), I32),
        ((B, KV, Smax, SCALE_LANES), F32), ((B, KV, Smax, SCALE_LANES), F32),
    )
    assert "tpu_custom_call" in text, text[:2000]


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_paged_decode_compiles(one_chip, int8):
    B, H, KV, hd, ps, pages = 8, 32, 8, 128, 16, 1025
    per_slot = 2048 // ps
    cache_dt = I8 if int8 else BF16

    def step(q, k, v, cl, pt, ks, vs):
        return da.paged_decode_attention_kernel(
            q, k, v, cl, pt, k_scale=ks if int8 else None,
            v_scale=vs if int8 else None, interpret=False,
        )

    text = _compile(
        step, one_chip,
        ((B, 1, H, hd), BF16), ((pages, ps, KV, hd), cache_dt),
        ((pages, ps, KV, hd), cache_dt), ((B,), I32), ((B, per_slot), I32),
        ((pages, KV, ps, SCALE_LANES), F32),
        ((pages, KV, ps, SCALE_LANES), F32),
    )
    assert "tpu_custom_call" in text, text[:2000]


# ------------------------------------------------- paged chunk attention
@pytest.mark.parametrize(
    "B,S,pages,per_slot",
    [
        (16, 128, 4097, 528),  # the benchmark's serving cells (Mixtral)
        (8, 128, 1025, 66),    # chip_smoke's serve phase (1056 tokens a slot)
        (8, 16, 257, 24),      # a small token budget (the rehearsal's)
    ],
    ids=["cell-16x128", "smoke-8x128", "budget-16"],
)
def test_paged_attention_compiles(one_chip, B, S, pages, per_slot):
    H, KV, hd, ps = 32, 8, 128, 16

    def step(q, k, v, cl, nn, pt, layer):
        return pa.paged_attention_kernel(
            q, k, v, cl, pt, layer=layer, num_new=nn, interpret=False,
        )

    text = _compile(
        step, one_chip,
        ((B, S, H, hd), BF16), ((3, pages, ps, KV, hd), BF16),
        ((3, pages, ps, KV, hd), BF16), ((B,), I32), ((B,), I32),
        ((B, per_slot), I32), ((), I32),
    )
    assert "tpu_custom_call" in text, text[:2000]


@pytest.mark.parametrize("window", [None, 4096], ids=["full", "window"])
@pytest.mark.parametrize("S", [128, 256, 512])
def test_paged_attention_compiles_at_16_heads_a_kv_head(one_chip, S, window):
    """Command A+'s calls (128 query heads on 8 KV heads of 128, 8 slots of
    66,560 tokens): the whole [8, S x 16, 128] query block with its float32
    accumulators passes the VMEM budget from 256 rows on, so the grid takes
    a program a slot AND row tile there; the chip's compiler takes each."""
    B, H, KV, hd, ps, per_slot = 8, 128, 8, 128, 16, 4192
    rows = pa.row_tile(S, H // KV, KV, hd, ps, 32, 2, 2)
    assert rows == (S if S == 128 else 16)

    def step(q, k, v, cl, nn, pt, layer):
        return pa.paged_attention_kernel(
            q, k, v, cl, pt, layer=layer, num_new=nn, interpret=False,
            window=window)

    shapes = (
        ((B, S, H, hd), BF16), ((1, 2049, ps, KV, hd), BF16),
        ((1, 2049, ps, KV, hd), BF16), ((B,), I32), ((B,), I32),
        ((B, per_slot), I32), ((), I32),
    )
    text = _compile(step, one_chip, *shapes)
    assert "tpu_custom_call" in text, text[:2000]
    # the row-tiled grid traces ONE body of two or three block loops; one
    # program a slot traces the small tile's beside the whole stack's
    loops = 2 if window is None else 3
    assert _kernel_products(step, *shapes) == 2 * loops * (
        1 if rows < S else 2)


@pytest.mark.parametrize("window", [None, 1024], ids=["full", "window"])
def test_paged_attention_compiles_at_mellums_shapes(one_chip, window):
    """Mellum's two calls (32 query heads on 4 KV heads of 128, 8 slots of
    16,640 tokens under [8, 128]): one program a slot, its [4, 128 x 8, 128]
    query block whole, over the full layers' pool and the window layers'."""
    B, S, H, KV, hd, ps = 8, 128, 32, 4, 128, 16
    L, pages = (3, 8385) if window is None else (9, 585)
    assert pa.row_tile(S, H // KV, KV, hd, ps, 32, 2, 2) == S

    def step(q, k, v, cl, nn, pt, layer):
        return pa.paged_attention_kernel(
            q, k, v, cl, pt, layer=layer, num_new=nn, interpret=False,
            window=window,
            name="paged_attention_" + ("full" if window is None else "window"))

    text = _compile(
        step, one_chip,
        ((B, S, H, hd), BF16), ((L, pages, ps, KV, hd), BF16),
        ((L, pages, ps, KV, hd), BF16), ((B,), I32), ((B,), I32),
        ((B, 16640 // ps + S // ps), I32), ((), I32),
    )
    assert "tpu_custom_call" in text, text[:2000]


@pytest.mark.parametrize("S", [128, 256, 512])
def test_paged_attention_compiles_at_256_wide_heads(one_chip, S):
    """Qwen3-Next's calls (16 query heads on 2 KV heads of 256, 4 slots of
    262,144 tokens in pages of 64 whose row is [2 KV, 256]): the first pool
    with 256 lanes a head. The whole [2, S x 8, 256] query block with its
    float32 accumulators passes the VMEM budget up to the cell's 256 rows
    (one program a slot); at 512 the grid takes a program a slot and row
    tile of 32 rows. The chip's compiler takes each."""
    B, H, KV, hd, ps, per_slot = 4, 16, 2, 256, 64, 4096
    rows = pa.row_tile(S, H // KV, KV, hd, ps, 8, 2, 2)
    assert rows == (S if S <= 256 else 32)

    def step(q, k, v, cl, nn, pt, layer):
        return pa.paged_attention_kernel(
            q, k, v, cl, pt, layer=layer, num_new=nn, interpret=False,
            name="paged_attention_full")

    shapes = (
        ((B, S, H, hd), BF16), ((3, 2049, ps, KV, hd), BF16),
        ((3, 2049, ps, KV, hd), BF16), ((B,), I32), ((B,), I32),
        ((B, per_slot), I32), ((), I32),
    )
    text = _compile(step, one_chip, *shapes)
    assert "tpu_custom_call" in text and "paged_attention_full" in text
    # (two block loops of two products a body: the row-tiled grid at 512
    # rows traces one, one program a slot the small tile's as well)
    assert _kernel_products(step, *shapes) == 2 * 2 * (1 if rows < S else 2)


def test_paged_attention_compiles_at_two_64_wide_heads_a_lane_row(one_chip):
    """LFM2-8B-A1B's call (32 query heads on 8 KV heads of 64, 64 slots of
    8,192 tokens (132 pages a slot with the chunk in flight) under [64, 256],
    pages of 64): the pool is held two KV heads
    a 128-lane row, [.., 4, 128] (the bytes of a row-major [64, 8, 64] page
    in the same order: the chip keeps no 64-lane row unpadded), and the
    kernel runs 4 KV heads of 128 whose group is both heads' 8 queries: one
    program a slot with the small tile for a decoding slot, the [64, 132]
    page table 33 KiB of SMEM. A pool held a head a row is refused on the
    chip with its reason, and so is a lone 64-wide KV head."""
    B, S, H, KV, hd, ps, per_slot = 64, 256, 32, 8, 64, 64, 132
    assert pa.lane_pairs(hd, KV) == 2 and pa.lane_pairs(hd, 1) == 1
    assert pa.kernel_heads(H, KV, hd) == (8, 4, 128)
    assert pa.paired_pool_row(KV, hd) == (4, 128)
    assert pa.row_tile(S, 8, 4, 128, ps, 8, 2, 2) == S
    assert pa.small_tile_slots([1, 0, 200, 4, 5], 8, S, S) == 2

    def step(q, k, v, cl, nn, pt, layer):
        out, why = pa.paged_attention(
            q, k, v, cl, pt, layer=layer, num_new=nn, interpret=False,
            name="paged_attention_full")
        assert out is not None, why
        return out

    shapes = (
        ((B, S, H, hd), BF16), ((3, 8193, ps, 4, 128), BF16),
        ((3, 8193, ps, 4, 128), BF16), ((B,), I32), ((B,), I32),
        ((B, per_slot), I32), ((), I32),
    )
    text = _compile(step, one_chip, *shapes)
    assert "tpu_custom_call" in text and "paged_attention_full" in text
    # the pools reach the call as they are held: no copy of one's size
    assert not re.search(r"bf16\[3,8193,64,4,128\]\S* copy\(", text)
    assert _kernel_products(step, *shapes) == 2 * 2 * 2
    q, pt = jnp.zeros((2, 16, H, hd), BF16), jnp.zeros((2, 4), I32)
    for pool, said in (((1, 5, ps, KV, hd), "two a 128-lane row"),
                       ((1, 5, ps, 1, hd), "not 128-aligned")):
        out, why = pa.paged_attention(
            q[:, :, :H * pool[3] // KV], jnp.zeros(pool, BF16),
            jnp.zeros(pool, BF16), jnp.zeros(2, I32), pt, layer=0,
            interpret=False)
        assert out is None and said in " ".join(why), why


def test_a_128_wide_pools_call_is_lowered_as_before():
    """The lane pairing is the wrapper's alone: at heads of 128 and 256 the
    traced call (its jaxpr, kernel body included) has no ``select_n`` or
    ``concatenate`` of a query stack round it and the same grid, scratches
    and products as ever (Mixtral's and Qwen3-Next's shapes)."""
    def step(q, k, v, cl, nn, pt, layer):
        return pa.paged_attention_kernel(
            q, k, v, cl, pt, layer=layer, num_new=nn, interpret=False)

    for H, KV, hd, ps in ((32, 8, 128, 16), (16, 2, 256, 64)):
        args = [jax.ShapeDtypeStruct(s, d) for s, d in (
            ((4, 128, H, hd), BF16), ((3, 65, ps, KV, hd), BF16),
            ((3, 65, ps, KV, hd), BF16), ((4,), I32), ((4,), I32),
            ((4, 16), I32), ((), I32))]
        jaxpr = jax.make_jaxpr(step)(*args).jaxpr
        outside = [e.primitive.name for e in jaxpr.eqns]
        assert "select_n" not in outside and "concatenate" not in outside
        call = next(e for e in jaxpr.eqns if e.primitive.name == "pallas_call")
        assert call.invars[4].aval.shape == (4, KV, 128 * H // KV, hd)
        assert [v.aval.shape for v in call.invars[5:7]] == [
            (3, 65, ps, KV, hd)] * 2


@pytest.mark.parametrize("cell", ["keye", "deepseek", "glm5", "minicpm"])
def test_the_selected_walks_compile_at_their_cells_shapes(one_chip, cell):
    """The three walks that fold their key tiles through
    decode_attention._tile_update beside the paged calls, alone at their
    cells' shapes: Keye-VL-2.0's selection inside paged K / V ([4, 128], 32
    heads on 4), DeepSeek-V3.2's over latent rows (128 heads, a 640-lane row
    whose first 512 are the value), GLM-5.3's by pooled blocks of 4 tokens
    (64 heads on a 512-lane row) and MiniCPM-SALA's block walk with its
    selection (32 heads on 2, 132,096 tokens a slot). The statistics meet
    512-key score tiles and accumulators of 128 and 512 lanes whole."""
    from deepspeed_tpu.ops.pallas import block_sparse_attention as bsa
    from deepspeed_tpu.ops.pallas import sparse_latent_attention as sla
    from deepspeed_tpu.ops.pallas import sparse_paged_attention as spa

    S, ps = 128, 16
    frontiers = lambda B, mp: (((B,), I32), ((B,), I32), ((B, mp), I32),
                               ((), I32))
    if cell == "keye":
        B, H, KV, hd, mp = 4, 32, 4, 128, 66560 // ps + S // ps
        nb = -(-mp * ps // 512)

        def walk(q, k, v, scores, thr, tie, cl, nn, pt, layer):
            return spa.sparse_paged_attention_kernel(
                q, k, v, scores, thr, tie, cl, pt, layer=layer, num_new=nn,
                interpret=False)

        shapes = (((B, S, H, hd), BF16), ((12, 16641, ps, KV, hd), BF16),
                  ((12, 16641, ps, KV, hd), BF16), ((B, nb, S, 512), F32),
                  ((B, S), I32), ((B, S), I32), *frontiers(B, mp))
        name = "sparse_paged_attention"
    elif cell in ("deepseek", "glm5"):
        B, H, W, kpool, tokens = ((4, 128, 640, 1, 66560) if cell == "deepseek"
                                  else (8, 64, 512, 4, 67584))
        mp = tokens // ps + S // ps
        nb = -(-mp * ps // 512)

        def walk(q, pool, scores, thr, tie, cl, nn, pt, layer):
            return sla.sparse_attention(
                q, pool, scores, thr, tie, cl, pt, layer=layer,
                scale=192 ** -0.5, v_width=512, num_new=nn, interpret=False,
                kpool=kpool)

        shapes = (((B, S, H, W), BF16), ((4, B * mp + 1, ps, W), BF16),
                  ((B, nb, S, 512 // kpool), F32), ((B, S), I32),
                  ((B, S), I32), *frontiers(B, mp))
        name = "sparse_latent_attention"
    else:
        B, H, KV, hd, mp = 4, 32, 2, 128, 132096 // ps + S // ps
        geom = bsa.BlockSparse()
        nbp = bsa._padded_blocks(geom, mp * ps)

        def walk(q, k, v, planes, cl, nn, pt, layer):
            kept = bsa.block_select(q, planes, cl, nn, geom, interpret=False)
            return bsa.block_sparse_attention(
                q, k, v, kept, cl, pt, layer=layer, geom=geom, num_new=nn,
                interpret=False)

        shapes = (((B, S, H, hd), BF16), ((3, 33057, ps, KV, hd), BF16),
                  ((3, 33057, ps, KV, hd), BF16),
                  ((B, KV, geom.planes, nbp, hd), BF16), *frontiers(B, mp))
        name = "block_sparse_attention"
    text = _compile(walk, one_chip, *shapes)
    assert name in text and "tpu_custom_call" in text, text[:2000]


@pytest.mark.parametrize("cell", ["keye", "deepseek", "glm5"])
def test_the_scoring_call_compiles_at_its_cells_shapes(one_chip, cell):
    """``indexer_scores`` alone at the three cells that run it: Keye-VL-2.0
    (16 heads over a 64-wide key in a 128-lane row), DeepSeek-V3.2 (64 heads
    of 128) and GLM-5.3 (32 heads, keys pooled by 4 through ``pooled_view``),
    a ``[slots, 128]`` step over 66,560-token slots. One body whatever the
    shape (two products in one kernel: a large tile's and a small one's;
    the ring of key blocks and the unrolled page copies are no second
    path), and the host's count of its trips at the cell's frontiers: a
    decoding slot at 26 k runs one small tile over 51 blocks of 512 keys,
    a whole chunk 8 tiles' rows in large ones."""
    from deepspeed_tpu.ops.pallas import sparse_latent_attention as sla

    B, Hi, kpool, tokens = {"keye": (4, 16, 1, 66560),
                            "deepseek": (4, 64, 1, 66560),
                            "glm5": (8, 32, 4, 67584)}[cell]
    S, ps, Di = 128, 16, 128
    mp = tokens // ps + S // ps

    def call(q, w, pool, cl, nn, pt, layer):
        if kpool > 1:
            view, counting = sla.pooled_view(pool, pt, layer)
            return sla.index_scores(
                q, w, view, cl, counting, layer=0, num_new=nn, kpool=kpool,
                block_k=sla.POOLED_BLOCK_K, interpret=False)
        return sla.index_scores(q, w, pool, cl, pt, layer=layer, num_new=nn,
                                interpret=False)

    shapes = (((B, S, Hi, Di), BF16), ((B, S, Hi), F32),
              ((4, B * mp + 1, ps // kpool, Di), BF16), ((B,), I32),
              ((B,), I32), ((B, mp), I32), ((), I32))
    text = _compile(call, one_chip, *shapes)
    assert "indexer_scores" in text and "tpu_custom_call" in text
    assert _kernel_products(call, *shapes) == 2
    assert sla.score_rows(S, Hi)[1] == {16: 128, 64: 32, 32: 64}[Hi]
    blocks, bk = sla.score_grid(mp, ps, kpool)
    assert (blocks, bk) == ((131, 512) if kpool == 1 else (133, 128))
    cl = np.asarray([26000] * (B - 1) + [13000])
    nn = np.asarray([1] * (B - 1) + [S])
    trips, full = sla.score_tiles(cl, nn, S, Hi, mp, ps, kpool)
    per = -(-26001 // (bk * kpool))  # key blocks a decoding slot's context
    assert trips.tolist() == [per] * (B - 1) + [
        8 * -(-(13000 + S) // (bk * kpool))]
    assert per == 51 and full == 8 * blocks
    idle, _ = sla.score_tiles(cl, 0 * nn, S, Hi, mp, ps, kpool)
    assert not idle.any()


# --------------------------------- a whole slot step beside its arena
GIB = 2.0 ** 30
# the slot steps' temporaries before the caches rode the layer scan as its
# carry (memory_analysis() for the described v5e of these same two tests
# on PR 30's tree): each held a sliced layer and a rebuilt stack of every
# pool
PARENT_TEMP_GIB = {"mixtral": 1.32, "mellum": 1.89}
# and while the head still ran over every row of the chunk (the same
# tests on PR 34's tree): each held the float32 [N, W, V] logits
WHOLE_CHUNK_TEMP_BYTES = {"mixtral": 298311680, "mellum": 818951680,
                          "deepseek": 340044288}
# and before the step took the token and the key of a row from the step in
# flight (the same tests on PR 36's tree, as they print it): three small
# operands more may not cost a buffer of any size that counts. (The chunk's
# tokens are a computed [N, W] array now and no longer the argument's
# buffer; the compiler's layout of the rest moves by 0.16 MB for Mixtral,
# 2.7 MB for Mellum, 0.14 MB for DeepSeek: under a hundredth.)
PR36_TEMP_MB = {"mixtral": 52.3, "mellum": 374.7, "deepseek": 339.9}
# and while the layers' row-by-row work still ran over every slot's chunk
# (the same tests on PR 41's tree, as they print it): (arguments GiB,
# temporaries GiB). Packed to the budget's rows the step may hold no more
PR41_ARGS_TEMP_GIB = {"mixtral": (9.35, 0.034), "mellum": (11.11, 0.347),
                      "deepseek": (10.54, 0.316), "minicpm": (8.95, 0.86)}


def _pool_copies(text, caches):
    """The instructions of a compiled step that re-materialise a cache:
    a ``copy``, ``dynamic-slice`` or ``dynamic-update-slice``, or a fusion
    that ends in one, whose result is a whole stack of ``caches``, one
    layer of it or any other leading split of it (a period's share). An
    in-place scatter and the Pallas call are what may touch a stack."""
    tails = {",".join(map(str, a.shape[1:])) for a in caches.values()}
    moves = ("copy", "dynamic-slice", "dynamic-update-slice")
    roots = _compiled_roots(text)
    found = []
    for line in text.splitlines():
        m = re.match(
            r"^\s+(?:ROOT )?%([\w.\-]+) = \w+\[([\d,]+)\]\S* ([\w\-]+)\(",
            line)
        if not m:
            continue
        name, dims, op = m.groups()
        if not any(dims == t or dims.endswith("," + t) for t in tails):
            continue
        if op == "fusion":
            called = re.search(r"calls=%([\w.\-]+)", line)
            op = roots.get(called.group(1), op) if called else op
        if op in moves:
            found.append(f"{name}: {op} -> [{dims}]")
    return found


def _compiled_roots(text):
    """The root operation of every computation of a compiled module."""
    roots, comp = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) \(", line)
        if head:
            comp = head.group(1)
        root = re.match(r"^\s+ROOT %[\w.\-]+ = [^ ]+ ([\w\-]+)\(", line)
        if root and comp:
            roots[comp] = root.group(1)
    return roots


def _param_copies(text, params):
    """The ``copy`` instructions of a compiled step (a fusion that ends in
    one too: what a ``dynamic-slice`` + ``copy`` pair fuses to) whose result
    is a parameter in another layout: a whole leaf, one layer of a stack
    (``[1, ...]`` or the bare matrix), for every leaf of at least one
    layer's smallest attention projection (a matrix under ``attn`` with
    both sides 128 or more). With the parameters' layouts left to the
    compiler (PR 45) the step reads each weight as it is held."""
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    floor = min(int(np.prod(a.shape[1:])) for path, a in leaves
                if a.ndim == 3 and min(a.shape[1:]) >= 128
                and any(getattr(k, "key", None) == "attn" for k in path))
    shapes = set()
    for _, a in leaves:
        layer = list(a.shape[1:])
        if a.ndim >= 3 and int(np.prod(layer)) >= floor:
            shapes |= {",".join(map(str, d))
                       for d in (a.shape, [1, *layer], layer)}
        elif a.ndim == 2 and a.size >= floor:
            shapes.add(",".join(map(str, a.shape)))
    roots = _compiled_roots(text)
    found = []
    for line in text.splitlines():
        m = re.match(
            r"^\s+(?:ROOT )?%([\w.\-]+) = \w+\[([\d,]+)\]\S* ([\w\-]+)\(",
            line)
        if not m or m.group(2) not in shapes:
            continue
        name, dims, op = m.groups()
        if op == "fusion":
            called = re.search(r"calls=%([\w.\-]+)", line)
            op = roots.get(called.group(1), op) if called else op
        if op == "copy":
            found.append(f"{name}: copy -> [{dims}]")
    return found


def _check_weights_are_read_as_held(compiled, model, family, capsys):
    """The step compiled with the chosen formats copies no parameter, and
    says which leaves the compiler wanted in another layout than the
    row-major one (the chip's own default for a narrow or unaligned last
    dimension among them: the engine compares with what a leaf is held in,
    ``ServingEngine._adopt_params``)."""
    params = jax.eval_shape(
        lambda k: model.init(k, dtype=BF16), jax.random.PRNGKey(0))
    chosen = jax.tree_util.tree_flatten_with_path(
        compiled.input_formats[0][0])[0]
    moved = [
        f"{jax.tree_util.keystr(path)} {list(a.shape)}"
        for (path, f), a in zip(chosen, jax.tree.leaves(params))
        if f.layout.major_to_minor != tuple(range(a.ndim))]
    with capsys.disabled():
        print(f"{family} slot step, layouts left to the compiler: "
              f"{len(moved)} leaves not row-major: " + "; ".join(moved))
    assert _param_copies(compiled.as_text(), params) == []


def _check_head_runs_over_the_window(compiled, N, W, V, family, capsys):
    """The tail computes what the sampler reads: no float32 array of the
    chunk's [N, W, V] logits in any shape, and the temporaries fell (by
    that buffer where it stood at their peak: Mixtral's and Mellum's; the
    peak of DeepSeek's is inside its attention)."""
    chunk_logits = N * W * V * 4
    m = compiled.memory_analysis()
    with capsys.disabled():
        print(f"{family} slot step, head over {N} rows of {N * W}: "
              f"temporaries {m.temp_size_in_bytes / 1e6:.1f} MB (with the "
              f"whole chunk's logits "
              f"{WHOLE_CHUNK_TEMP_BYTES[family] / 1e6:.1f}, of which they "
              f"were {chunk_logits / 1e6:.1f})")
    held = [dims for dims in set(
        re.findall(r"\bf32\[([\d,]+)\]", compiled.as_text()))
        if np.prod([int(d) for d in dims.split(",")]) == N * W * V]
    assert held == []
    assert m.temp_size_in_bytes < WHOLE_CHUNK_TEMP_BYTES[family]
    assert m.temp_size_in_bytes / 1e6 < PR36_TEMP_MB[family] * 1.01


def _check_dense_rows_are_the_budgets(compiled, N, W, family, capsys):
    """The layers' matmuls run over the plan's tokens (PR 42): outside the
    attention calls (Pallas kernels: no ``convolution``) no matmul of the
    compiled step has a result of ``N x W`` rows, as ``[N, W, ...]`` or as
    ``[N * W, ...]``; and the step's arguments and temporaries are no higher
    than they were with every slot's chunk computed."""
    text = compiled.as_text()
    wide = []
    for m in re.finditer(
            r"%([\w.\-]+) = \w+\[([\d,]+)\]\S* (?:convolution|dot)\(", text):
        dims = [int(d) for d in m.group(2).split(",")]
        # ([N, W] alone is a table a slot a row, not rows of features)
        if dims[0] == N * W or (dims[:2] == [N, W] and len(dims) > 2):
            wide.append(f"{m.group(1)} -> [{m.group(2)}]")
    assert wide == []
    assert re.search(rf"\[1,{W},[\d,]+\]\S* (?:convolution|dot)\(", text) or (
        re.search(rf"\[{W},[\d,]+\]\S* (?:convolution|dot)\(", text))
    m = compiled.memory_analysis()
    args, temp = PR41_ARGS_TEMP_GIB[family]
    with capsys.disabled():
        print(f"{family} slot step, rows packed to {W} of {N * W}: arguments "
              f"{m.argument_size_in_bytes / GIB:.2f} GiB (were {args}), "
              f"temporaries {m.temp_size_in_bytes / GIB:.3f} GiB (were "
              f"{temp})")
    assert (m.argument_size_in_bytes + m.temp_size_in_bytes) / GIB < (
        args + temp + 0.1)


def _check_placement_is_one_pass(compiled, rows, cfg, family, capsys):
    """A routed layer ranks its (token, chosen expert) pairs with one
    triangular product (PR 40): the compiled step holds no ``reduce-window``
    (what a cumulative sum over rows is on the chip) over a routing one-hot,
    i.e. over ``rows x experts`` elements (a round's) or ``top_k`` times
    that (a layer's), in any layout. Prints the step's counts of the three operations the round
    loop paid a round."""
    text = compiled.as_text()
    counts = {op: len(re.findall(rf"= (?:\([^)]*\)|\S+) {op}\(", text))
              for op in ("reduce-window", "scatter", "sort")}
    with capsys.disabled():
        print(f"{family} slot step: " + ", ".join(
            f"{n} {op}" for op, n in counts.items()) + " instructions")
    shapes = {m.group(1): m.group(2) for m in re.finditer(
        r"%([\w.\-]+) = \w+\[([\d,]*)\]", text)}
    over_onehots = []
    for m in re.finditer(
            r"%([\w.\-]+) = \S+ reduce-window\(%([\w.\-]+)", text):
        dims = shapes.get(m.group(2), "")
        size = int(np.prod([int(d) for d in dims.split(",") if d] or [0]))
        if size in (rows * cfg.num_experts,
                    rows * cfg.num_experts * cfg.moe_top_k):
            over_onehots.append(f"{m.group(1)} over [{dims}]")
    assert over_onehots == []


def _check_pool_writes_take_the_budget(compiled, caches, names, N, W,
                                       family, capsys, by_slot=()):
    """A page pool is written from the rows the step computed (PR 53): every
    ROW scatter of the compiled step into a pool of ``names`` (one whose
    index names a layer, a page and an offset; the page copies of
    copy-on-write and promotion index a page alone) takes ``W`` update rows,
    not ``N x W``, and each of those pools has one (pools of one shape
    share their scatters). ``by_slot``: pools that
    are written by block a slot on purpose (pooled index keys), whose
    scatters are printed and not held to the budget."""
    text = compiled.as_text()
    shapes = {m.group(1): [int(d) for d in m.group(2).split(",") if d]
              for m in re.finditer(r"%([\w.\-]+) = \w+\[([\d,]*)\]", text)}
    found = {n: [] for n in (*names, *by_slot)}
    for m in re.finditer(
            r"= \w+\[([\d,]+)\]\S* scatter\(%[\w.\-]+, %[\w.\-]+, "
            r"%([\w.\-]+)\), [^\n]*scatter_dims_to_operand_dims=\{([\d,]+)\}",
            text):
        target = tuple(int(d) for d in m.group(1).split(","))
        for name, rows in found.items():
            pool = tuple(caches[name].shape)
            # (a pool of one layer is written as its one layer)
            if (target, m.group(3)) in (
                    (pool, "0,1,2"), (pool[1:], "0,1") * (pool[0] == 1)):
                rows.append(int(np.prod(shapes[m.group(2)]))
                            // int(np.prod(pool[3:])))
    with capsys.disabled():
        print(f"{family} slot step, update rows a pool scatter (budget {W}, "
              f"by slot {N * W}): " + ", ".join(
                  f"{name} {rows}" for name, rows in found.items()))
    for name in names:
        assert found[name] and set(found[name]) == {W}, (name, found[name])


def _compile_slot_step(model, caches, one_chip, N, W, mp):
    """``make_paged_step_fn`` of ``model`` jitted as the serving engine
    jits it (caches and ``seen`` donated, the parameter leaves' layouts
    left to the compiler), compiled for the described chip with the kernel
    attention registered."""
    from deepspeed_tpu.ops.attention import attention_impl
    from deepspeed_tpu.serving.engine import (compiler_param_formats,
                                              jit_step, make_paged_step_fn)

    cfg = model.config

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    def vec(dt, *tail):
        return jax.ShapeDtypeStruct((N, *tail), dt, sharding=one_chip)

    params = jax.tree.map(sds, jax.eval_shape(
        lambda k: model.init(k, dtype=BF16), jax.random.PRNGKey(0)))
    step = make_paged_step_fn(cfg, BF16, cfg.vocab_size)
    # a model with window layers brings their table beside the full ones'
    tables = [vec(I32, mp)] * (2 if cfg.has_window else 1)
    args = (
        params, jax.tree.map(sds, caches),
        vec(jnp.bool_, cfg.vocab_size), vec(I32, W),
        vec(I32), vec(I32), *tables, vec(I32),
        vec(jnp.bool_), vec(jnp.bool_), vec(I32), vec(I32),
        vec(jnp.uint32, 2), vec(F32), vec(I32), vec(F32), vec(F32),
        # the row flag, and the step in flight's tokens and keys
        vec(jnp.bool_), vec(I32, 1), vec(jnp.uint32, 2),
    )
    with attention_impl("flash"):
        return jit_step(step, len(args), compiler_param_formats(params)
                        ).lower(*args).compile()


def _check_caches_stay_in_place(compiled, caches, family, capsys):
    """No pool is re-materialised, the donated pools are the outputs'
    buffers, and the temporaries fell by the copies no longer held."""
    m = compiled.memory_analysis()
    pools = sum(a.size * a.dtype.itemsize for a in caches.values())
    with capsys.disabled():
        print(f"\n{family} slot step, described v5e: arguments "
              f"{m.argument_size_in_bytes / GIB:.2f} GiB, temporaries "
              f"{m.temp_size_in_bytes / GIB:.2f} GiB (the parent's "
              f"{PARENT_TEMP_GIB[family]:.2f}), output "
              f"{m.output_size_in_bytes / GIB:.2f} GiB (aliased "
              f"{m.alias_size_in_bytes / GIB:.2f}, the pools "
              f"{pools / GIB:.2f})")
    assert _pool_copies(compiled.as_text(), caches) == []
    assert m.alias_size_in_bytes >= pools
    assert m.temp_size_in_bytes < PARENT_TEMP_GIB[family] * GIB - pools
    return m


def test_mixtral_slot_step_keeps_its_pools_in_place(one_chip, monkeypatch,
                                                    capsys):
    """The one [16, 128] serving step of Mixtral-8x7B at the benchmark's
    depth 3 over its arena of 4,096 pages: the K/V stacks ride the layer
    scan as its carry, so the compiled step holds no copy, slice or
    write-back of a pool's or a layer's size."""
    from deepspeed_tpu.models import mixtral
    from deepspeed_tpu.models.decoding import init_paged_cache

    # the kernels pick interpret mode from the backend, the CPU here
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = mixtral("mixtral-8x7b", num_layers=3, max_seq_len=32768,
                    moe_capacity_factor=4.0)
    N, W, ps, cap = 16, 128, 16, 8320
    caches = jax.eval_shape(
        lambda: init_paged_cache(model.config, 4096, ps, BF16))
    compiled = _compile_slot_step(model, caches, one_chip, N, W,
                                  -(-(cap + W) // ps))
    _check_caches_stay_in_place(compiled, caches, "mixtral", capsys)
    _check_pool_writes_take_the_budget(compiled, caches, ("k", "v"), N, W,
                                       "mixtral", capsys)
    _check_head_runs_over_the_window(compiled, N, W, model.config.vocab_size,
                                     "mixtral", capsys)
    _check_placement_is_one_pass(compiled, W, model.config, "mixtral",
                                 capsys)
    _check_dense_rows_are_the_budgets(compiled, N, W, "mixtral", capsys)
    _check_weights_are_read_as_held(compiled, model, "mixtral", capsys)
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 15.75 * GIB
    assert "paged_attention" in compiled.as_text()


def test_mellum_slot_step_compiles_beside_its_arena(one_chip, monkeypatch,
                                                    capsys):
    """The one [8, 128] serving step of Mellum2-12B-A2.5B at its published
    widths and the benchmark's depth (12 layers, three periods of three
    window layers and a full one), with the benchmark's arena: 8,384 pages
    for the full layers, 592 for the window layers. The chip's compiler
    has to take it beside the 16 GB, with both named attention kernels,
    and with neither pool re-materialised in any form (whole, a layer, a
    period's share)."""
    from deepspeed_tpu.models import mellum
    from deepspeed_tpu.models.decoding import init_paged_cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = mellum("mellum2-12b-a2.5b", num_layers=12)
    N, W, ps, cap = 8, 128, 16, 16640
    mp = -(-(cap + W) // ps)
    caches = jax.eval_shape(lambda: init_paged_cache(
        model.config, N * mp, ps, BF16, window_pages=592))
    compiled = _compile_slot_step(model, caches, one_chip, N, W, mp)
    m = _check_caches_stay_in_place(compiled, caches, "mellum", capsys)
    _check_pool_writes_take_the_budget(
        compiled, caches, ("k", "v", "k_win", "v_win"), N, W, "mellum",
        capsys)
    _check_head_runs_over_the_window(compiled, N, W, model.config.vocab_size,
                                     "mellum", capsys)
    _check_placement_is_one_pass(compiled, W, model.config, "mellum",
                                 capsys)
    _check_dense_rows_are_the_budgets(compiled, N, W, "mellum", capsys)
    _check_weights_are_read_as_held(compiled, model, "mellum", capsys)
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 15.75 * GIB
    text = compiled.as_text()
    assert "paged_attention_window" in text and "paged_attention_full" in text


@pytest.mark.parametrize("W", [256])
def test_cohere_slot_step_compiles_beside_its_arena(one_chip, monkeypatch,
                                                    capsys, W):
    """The one [8, budget] serving step of Command A+ at its published
    widths and the benchmark's cut (one period: three window layers and a
    NoPE full one, 16 of 128 experts, an eighth of the vocabulary) beside
    its arena: 33,408 pages for the full layer, 8 x 273 for the window
    layers. The chip's compiler takes it inside the 16 GB with both named
    attention calls, no pool re-materialised and every pool's scatter
    taking the budget's rows."""
    from deepspeed_tpu.models import cohere
    from deepspeed_tpu.models.decoding import init_paged_cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = cohere("command-a-plus-05-2026", num_layers=4, num_experts=16,
                   moe_routed_experts=128, vocab_size=32768,
                   max_seq_len=66560)
    N, ps, cap = 8, 16, 66560
    mp = -(-(cap + W) // ps)
    window_pages = N * (-(-(model.config.attn_window + W) // ps) + 1)
    caches = jax.eval_shape(lambda: init_paged_cache(
        model.config, N * mp, ps, BF16, window_pages=window_pages))
    compiled = _compile_slot_step(model, caches, one_chip, N, W, mp)
    m = compiled.memory_analysis()
    pools = sum(a.size * a.dtype.itemsize for a in caches.values())
    with capsys.disabled():
        print(f"\ncohere slot step [8, {W}], described v5e: arguments "
              f"{m.argument_size_in_bytes / GIB:.2f} GiB, temporaries "
              f"{m.temp_size_in_bytes / GIB:.2f} GiB, aliased "
              f"{m.alias_size_in_bytes / GIB:.2f} (the pools "
              f"{pools / GIB:.2f})")
    text = compiled.as_text()
    assert _pool_copies(text, caches) == []
    assert m.alias_size_in_bytes >= pools
    _check_pool_writes_take_the_budget(
        compiled, caches, ("k", "v", "k_win", "v_win"), N, W, "cohere",
        capsys)
    _check_placement_is_one_pass(compiled, W, model.config, "cohere",
                                 capsys)
    _check_weights_are_read_as_held(compiled, model, "cohere", capsys)
    # no array of the chunk's [N, W, V] logits: the head runs over N rows
    V = model.config.vocab_size
    assert not re.search(rf"\[({N},{W}|{N * W}),{V}\]", text)
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 15.0 * GIB
    assert "paged_attention_window" in text and "paged_attention_full" in text


def test_deepseek_slot_step_keeps_both_pools_in_place(one_chip, monkeypatch,
                                                      capsys):
    """The one [4, 128] serving step of DeepSeek-V3.2 at its published
    widths and the benchmark's cut (1 dense + 4 routed layers, 16 of 256
    experts, an eighth of the vocabulary) over its arena of 16,640 pages:
    the latent pool and the indexer-key pool ride BOTH layer scans (the
    dense layer's and the routed layers') as one carry, so the compiled step
    holds no copy, slice or write-back of either pool's or a layer's size;
    the three named kernels are in it; and it fits the chip with 0.75 GiB
    to spare."""
    from deepspeed_tpu.models import deepseek
    from deepspeed_tpu.models.decoding import init_paged_cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = deepseek("deepseek-v3.2", num_layers=4, lead_dense_layers=1,
                     num_experts=16, moe_routed_experts=256, vocab_size=16160)
    N, W, ps, cap = 4, 128, 16, 66560
    caches = jax.eval_shape(
        lambda: init_paged_cache(model.config, 16640, ps, BF16))
    assert {k: v.shape[-1] for k, v in caches.items()} == {"kv": 640,
                                                           "ki": 128}
    compiled = _compile_slot_step(model, caches, one_chip, N, W,
                                  -(-(cap + W) // ps))
    m = compiled.memory_analysis()
    pools = sum(a.size * a.dtype.itemsize for a in caches.values())
    with capsys.disabled():
        print(f"\ndeepseek slot step, described v5e: arguments "
              f"{m.argument_size_in_bytes / GIB:.2f} GiB, temporaries "
              f"{m.temp_size_in_bytes / GIB:.2f} GiB, aliased "
              f"{m.alias_size_in_bytes / GIB:.2f} (the pools "
              f"{pools / GIB:.2f})")
    _check_head_runs_over_the_window(compiled, N, W, model.config.vocab_size,
                                     "deepseek", capsys)
    _check_placement_is_one_pass(compiled, W, model.config, "deepseek",
                                 capsys)
    _check_dense_rows_are_the_budgets(compiled, N, W, "deepseek", capsys)
    _check_weights_are_read_as_held(compiled, model, "deepseek", capsys)
    text = compiled.as_text()
    assert _pool_copies(text, caches) == []
    _check_pool_writes_take_the_budget(compiled, caches, ("kv", "ki"), N, W,
                                       "deepseek", capsys)
    assert m.alias_size_in_bytes >= pools
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 15.0 * GIB
    for name in ("indexer_scores", "selection_topk",
                 "sparse_latent_attention"):
        assert name in text


def test_keye_slot_step_keeps_the_three_pools_in_place(one_chip, monkeypatch,
                                                       capsys):
    """The one [4, 128] serving step of Keye-VL-2.0-30B-A3B's language model
    at its published widths and the benchmark's cut (12 of 48 layers, 32 of
    128 experts, a quarter of the vocabulary) over its arena of 16,640
    pages: K, V and the indexer-key pool (64 values a token, stored 128
    wide) ride the layer scan as one carry, so the compiled step holds no
    copy of a pool's or a layer's size and every pool's scatter takes the
    budget's rows; the indexer's two calls and the walk over the selection
    are in it by name; and it fits the described chip."""
    from deepspeed_tpu.models import keye
    from deepspeed_tpu.models.decoding import init_paged_cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = keye("keye-vl-2.0-30b-a3b", num_layers=12, num_experts=32,
                 moe_routed_experts=128, vocab_size=37984)
    N, W, ps, cap = 4, 128, 16, 66560
    caches = jax.eval_shape(
        lambda: init_paged_cache(model.config, 16640, ps, BF16))
    assert {k: v.shape[2:] for k, v in caches.items()} == {
        "k": (ps, 4, 128), "v": (ps, 4, 128), "ki": (ps, 128)}
    compiled = _compile_slot_step(model, caches, one_chip, N, W,
                                  -(-(cap + W) // ps))
    m = compiled.memory_analysis()
    pools = sum(a.size * a.dtype.itemsize for a in caches.values())
    with capsys.disabled():
        print(f"\nkeye slot step, described v5e: arguments "
              f"{m.argument_size_in_bytes / GIB:.2f} GiB, temporaries "
              f"{m.temp_size_in_bytes / GIB:.2f} GiB, aliased "
              f"{m.alias_size_in_bytes / GIB:.2f} (the pools "
              f"{pools / GIB:.2f})")
    text = compiled.as_text()
    assert _pool_copies(text, caches) == []
    assert m.alias_size_in_bytes >= pools
    _check_pool_writes_take_the_budget(
        compiled, caches, ("k", "v", "ki"), N, W, "keye", capsys)
    _check_placement_is_one_pass(compiled, W, model.config, "keye", capsys)
    _check_weights_are_read_as_held(compiled, model, "keye", capsys)
    for call in ("indexer_scores", "selection_topk",
                 "sparse_paged_attention"):
        assert call in text, call
    assert "paged_attention_full" not in text
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 15.0 * GIB


def test_qwen3_next_slot_step_keeps_pools_and_both_state_leaves_in_place(
        one_chip, monkeypatch, capsys):
    """The one [4, 256] serving step of Qwen3-Next-80B-A3B at its published
    widths and the benchmark's cut (published layers 0-11, 64 of 512
    experts, an eighth of the vocabulary) over its arena of 16,384 pages of
    64 tokens: the three gated-attention layers' K and V pools ([2 KV, 256]
    a row) ride the layer scans beside the nine Gated DeltaNet layers' two
    slot leaves, the float32 state aliased into the ``gated_delta_attention``
    call, so the compiled step holds no copy of a pool's, a leaf's or a
    layer's size; both calls are in it by name; and it fits the described
    chip beside 5.86 GB of weights."""
    from deepspeed_tpu.models import qwen3_next
    from deepspeed_tpu.models.decoding import init_paged_cache
    from deepspeed_tpu.ops.pallas import gated_delta as gd

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = qwen3_next("qwen3-next-80b-a3b", layer_ids=list(range(12)),
                       num_experts=64, moe_routed_experts=512,
                       vocab_size=18992)
    cfg = model.config
    assert cfg.kind_count("gdn") == 9 and cfg.kind_count("full") == 3
    N, W, ps, cap = 4, 256, 64, 262144
    caches = jax.eval_shape(
        lambda: init_paged_cache(cfg, 16384, ps, BF16, max_slots=N))
    assert {k: (v.shape[2:], v.dtype) for k, v in caches.items()} == {
        "k": ((ps, 2, 256), BF16), "v": ((ps, 2, 256), BF16),
        "state": ((32, 128, 128), F32), "conv": ((3, 8192), BF16)}
    compiled = _compile_slot_step(model, caches, one_chip, N, W, cap // ps)
    m = compiled.memory_analysis()
    pools = sum(a.size * a.dtype.itemsize for a in caches.values())
    with capsys.disabled():
        print(f"\nqwen3-next slot step, described v5e: "
              f"{model.num_params():,} parameters, arguments "
              f"{m.argument_size_in_bytes / GIB:.2f} GiB, temporaries "
              f"{m.temp_size_in_bytes / GIB:.2f} GiB, aliased "
              f"{m.alias_size_in_bytes / GIB:.2f} (the pools and leaves "
              f"{pools / GIB:.2f})")
    text = compiled.as_text()
    # (5 rows an expert at a full step: the banks go through the einsum)
    for call in ("gated_delta_attention", "paged_attention_full"):
        assert call in text, call
    assert "expert_bank" not in text
    # 8 of the 16 key heads a program, with their 16 value heads
    assert gd.key_heads_per_program(16, 2, 128, 128, W, 2) == 8
    # (the convolution rows are 0.2 MB a layer: a layer's block is read and
    # written by plain slices of the scan's carry, the layer's own work)
    assert _pool_copies(
        text, {k: v for k, v in caches.items() if k != "conv"}) == []
    assert m.alias_size_in_bytes >= pools
    _check_pool_writes_take_the_budget(
        compiled, caches, ("k", "v"), N, W, "qwen3-next", capsys)
    _check_weights_are_read_as_held(compiled, model, "qwen3-next", capsys)
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 15.0 * GIB


def test_lfm2_slot_step_keeps_its_pools_and_carried_rows_in_place(
        one_chip, monkeypatch, capsys):
    """The one [64, 256] serving step of LFM2-8B-A1B at its published widths
    and the benchmark's cut (published layers 0-13: two leading dense layers
    and three periods A c c c; all 32 experts, the whole vocabulary, the head
    tied) over its arena of 8,192 pages of 64 tokens: the three attention
    layers' K and V pools (two 64-wide KV heads a 128-lane row) ride the
    layer scans beside the eleven convolutions' carried rows, the compiled
    step holds no copy of a pool's size, the paged call is in it by name
    (32 rows an expert at a full step: the banks go through the einsum);
    and it fits the described chip beside 9.33 GB of weights."""
    from deepspeed_tpu.models import lfm2
    from deepspeed_tpu.models.decoding import init_paged_cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = lfm2("lfm2-8b-a1b", layer_ids=list(range(14)), max_seq_len=8192)
    cfg = model.config
    assert cfg.kind_count("conv") == 11 and cfg.kind_count("full") == 3
    assert (cfg.lead_dense_layers, cfg.num_layers) == (2, 12)
    assert model.num_params() == 4_667_077_376  # 9.33 GB in bf16
    N, W, ps, cap = 64, 256, 64, 8192 + 256  # a slot's table: 132 pages
    caches = jax.eval_shape(
        lambda: init_paged_cache(cfg, 8192, ps, BF16, max_slots=N))
    assert {k: (v.shape[2:], v.dtype) for k, v in caches.items()} == {
        "k": ((ps, 4, 128), BF16), "v": ((ps, 4, 128), BF16),
        "conv": ((2, 2048), BF16)}
    compiled = _compile_slot_step(model, caches, one_chip, N, W, cap // ps)
    m = compiled.memory_analysis()
    pools = sum(a.size * a.dtype.itemsize for a in caches.values())
    with capsys.disabled():
        print(f"\nlfm2 slot step, described v5e: "
              f"{model.num_params():,} parameters, arguments "
              f"{m.argument_size_in_bytes / GIB:.2f} GiB, temporaries "
              f"{m.temp_size_in_bytes / GIB:.2f} GiB, aliased "
              f"{m.alias_size_in_bytes / GIB:.2f} (the pools and leaves "
              f"{pools / GIB:.2f})")
    text = compiled.as_text()
    assert "paged_attention_full" in text and "expert_bank" not in text
    assert _pool_copies(
        text, {k: v for k, v in caches.items() if k != "conv"}) == []
    assert m.alias_size_in_bytes >= pools
    _check_pool_writes_take_the_budget(
        compiled, caches, ("k", "v"), N, W, "lfm2", capsys)
    _check_weights_are_read_as_held(compiled, model, "lfm2", capsys)
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 15.0 * GIB


def test_minicpm_sala_slot_step_keeps_pools_and_states_in_place(
        one_chip, monkeypatch, capsys):
    """The one [4, 128] serving step of MiniCPM-SALA at its published widths
    and the benchmark's cut (published layers 16-27: 3 sparse, 9 lightning)
    over its arena of 33,056 pages: the sparse layers' K / V pages and
    compressed keys and the lightning layers' slot states ride every run's
    scan as one carry, so the compiled step holds no copy, slice or
    write-back the size of a pool or of the state stack; the three named
    kernels (and the dense GQA models' paged kernel, for steps inside
    dense_len) are in it; and it fits the chip."""
    from deepspeed_tpu.models import minicpm
    from deepspeed_tpu.models.decoding import init_paged_cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = minicpm("minicpm-sala", layer_ids=list(range(16, 28)))
    cfg = model.config
    assert cfg.mixer_types.count("sparse") == 3
    N, W, ps, cap = 4, 128, 16, 132096
    caches = jax.eval_shape(
        lambda: init_paged_cache(cfg, 33056, ps, BF16, max_slots=N))
    assert caches["state"].shape == (9, N, 32, 128, 128)
    assert caches["state"].dtype == F32 and caches["kc"].shape[2:] == (2, 128)
    compiled = _compile_slot_step(model, caches, one_chip, N, W,
                                  -(-(cap + W) // ps))
    m = compiled.memory_analysis()
    pools = sum(a.size * a.dtype.itemsize for a in caches.values())
    with capsys.disabled():
        print(f"\nminicpm-sala slot step, described v5e: arguments "
              f"{m.argument_size_in_bytes / GIB:.2f} GiB, temporaries "
              f"{m.temp_size_in_bytes / GIB:.2f} GiB, aliased "
              f"{m.alias_size_in_bytes / GIB:.2f} (pools and states "
              f"{pools / GIB:.2f})")
    text = compiled.as_text()
    # the head runs over each slot's window: no chunk-wide logits
    assert [dims for dims in set(re.findall(r"\bf32\[([\d,]+)\]", text))
            if np.prod([int(d) for d in dims.split(",")])
            == N * W * cfg.vocab_size] == []
    assert _pool_copies(text, caches) == []
    _check_pool_writes_take_the_budget(compiled, caches, ("k", "v"), N, W,
                                       "minicpm-sala", capsys)
    _check_dense_rows_are_the_budgets(compiled, N, W, "minicpm", capsys)
    _check_weights_are_read_as_held(compiled, model, "minicpm", capsys)
    assert m.alias_size_in_bytes >= pools
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 15.75 * GIB
    for name in ("lightning_attention", "block_select",
                 "block_sparse_attention", "paged_attention_full"):
        assert name in text


LING_IDS = [0, *range(6, 18)]  # the benchmark's cut of Ling-3.0-flash


@pytest.mark.parametrize("B,H,L", [(16, 32, 11), (8, 64, 4)],
                         ids=["ling3flash-reason16", "glm53flash-longreason8"])
def test_kda_call_takes_several_heads_a_program(one_chip, B, H, L):
    """The delta-rule call at the two cells' shapes (``[16, 128]`` rows of
    32 heads over an 11-layer stack, ``[8, 128]`` of 64 over 4): the chip's
    compiler takes a program of ``hb`` heads (lane blocks of ``hb x 128``
    parked by a scalar, the row tiles, the columns turned in VMEM), the
    traced call's grid is ``(B, H / hb)``, ``B x H / hb`` programs and not
    the ``B x H`` of a head each, and its two outputs are the whole rows
    with a spare block and the row tile."""
    from deepspeed_tpu.analysis import shardlint
    from deepspeed_tpu.ops.pallas import kda_attention as ka

    S, hd = 128, 128
    hb = ka.heads_per_program(H, hd, S, 2)
    assert 1 < hb <= H and H % hb == 0

    def kda(q, k, v, g, beta, state, cl, nn, layer):
        return ka.kda_attention(q, k, v, g, beta, state, cl, nn,
                                layer=layer, scale=hd ** -0.5,
                                interpret=False)

    row = ((B, S, H, hd), BF16)
    shapes = (row, row, row, ((B, S, H, hd), F32), ((B, S, H), F32),
              ((L, B, H, hd, hd), F32), ((B,), I32), ((B,), I32), ((), I32))
    text = _compile(kda, one_chip, *shapes)
    assert "kda_attention" in text and "tpu_custom_call" in text
    assert f"bf16[{B + 1},{S},{H * hd}]" in text
    assert f"bf16[{B},{ka.ROW_TILE},{H * hd}]" in text
    assert f"f32[{B},{H},{hd},4]" not in text
    jaxpr = jax.make_jaxpr(kda)(
        *(jax.ShapeDtypeStruct(s, d) for s, d in shapes))
    assert shardlint.pallas_grids(jaxpr.jaxpr) == [
        ("kda_attention", (B, H // hb))]


def test_ling_kernels_compile_at_published_widths(one_chip):
    """The two kernels Ling-3.0-flash brings, at the benchmark cell's shapes
    ([16, 128] rows, 32 heads of 128, a 576-wide latent padded to 640 lanes,
    18,432 pages): the chip's compiler takes the delta rule's chunk form
    (sub-block slices, the in-place state stack, the one-row path's column
    operand) and the latent walk without a selection."""
    from deepspeed_tpu.ops.pallas import kda_attention as ka
    from deepspeed_tpu.ops.pallas import sparse_latent_attention as sla

    B, S, H, hd, L = 16, 128, 32, 128, 11
    row = ((B, S, H, hd), BF16)

    def kda(q, k, v, g, beta, state, cl, nn, layer):
        return ka.kda_attention(q, k, v, g, beta, state, cl, nn, layer=layer,
                                scale=hd ** -0.5, interpret=False)

    text = _compile(kda, one_chip, row, row, row, ((B, S, H, hd), F32),
                    ((B, S, H), F32), ((L, B, H, hd, hd), F32), ((B,), I32),
                    ((B,), I32), ((), I32))
    assert "kda_attention" in text and "tpu_custom_call" in text
    # the one-row path's columns are turned in VMEM: no 4-lane operand
    assert f"f32[{B},{H},{hd},4]" not in text
    mp = 18432 // B + S // 16

    def walk(q, pool, cl, nn, table, layer):
        out, why = sla.latent_attention(
            q, pool, cl, table, layer=layer, scale=192 ** -0.5, v_width=512,
            num_new=nn, interpret=False)
        assert why == []
        return out

    text = _compile(walk, one_chip, ((B, S, H, 640), BF16),
                    ((2, 18433, 16, 640), BF16), ((B,), I32), ((B,), I32),
                    ((B, mp), I32), ((), I32))
    assert "latent_attention" in text and "tpu_custom_call" in text


@pytest.mark.parametrize("L, E, C, K, N, gated", [
    (12, 64, 128, 2560, 768, True),    # Ling's wi / wg: one block a bank
    (12, 64, 128, 768, 2560, False),   # Ling's wo
    (3, 8, 128, 4096, 14336, True),    # Mixtral's: 28 tiles of 512 lanes
], ids=["ling_up", "ling_down", "tiled_up"])
def test_expert_bank_compiles_over_the_stack(one_chip, L, E, C, K, N, gated):
    """The touched-experts bank matmul at the widths of the cell that takes
    it (and at a bank wide enough to be tiled): the stack whole with the
    layer's index and the walk's tables in SMEM, the double-buffered 4 MB
    weight blocks inside the VMEM limit the call sets."""
    from deepspeed_tpu.ops.pallas import expert_bank as eb

    def bank(x, w, g, layer, fill):
        return eb.expert_bank(x, w, layer, fill, gate=g if gated else None,
                              interpret=False)

    text = _compile(bank, one_chip, ((E, C, K), BF16), ((L, E, K, N), BF16),
                    ((L, E, K, N), BF16), ((), I32), ((E,), I32))
    assert "expert_bank" in text and "tpu_custom_call" in text
    # no slice or copy of the stack feeds the call
    assert not re.search(rf"= bf16\[(?:1,)?{E},{K},{N}\]", text)


def test_ling_slot_step_keeps_pools_and_both_state_leaves_in_place(
        one_chip, monkeypatch, capsys):
    """The one [16, 128] serving step of Ling-3.0-flash at its published
    widths and the benchmark's cut (published layers 0 and 6-17: 11 KDA + 2
    latent mixers over 1 dense + 12 routed MLPs, 64 of 512 experts, an
    eighth of the vocabulary) over its arena of 18,560 pages: the latent
    pool, the KDA states and the convolution rows ride every run's scan as
    one carry, so the compiled step holds no copy, slice or write-back the
    size of a pool or of a state stack; both named kernels are in it, and
    the bank matmul that reads the touched experts (``expert_bank``, over
    the bank stacks whole); the layers' matmuls run over the budget's rows;
    and it fits the chip."""
    from deepspeed_tpu.models import ling
    from deepspeed_tpu.models.decoding import init_paged_cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = ling("ling-3.0-flash", layer_ids=LING_IDS, num_experts=64,
                 moe_routed_experts=512, vocab_size=19648)
    cfg = model.config
    assert (cfg.kind_count("kda"), cfg.kind_count("latent")) == (11, 2)
    assert (cfg.lead_dense_layers, cfg.num_layers) == (1, 12)
    N, W, ps, cap = 16, 128, 16, 18432
    pages = N * (cap + W) // ps
    caches = jax.eval_shape(
        lambda: init_paged_cache(cfg, pages, ps, BF16, max_slots=N))
    assert caches["state"].shape == (11, N, 32, 128, 128)
    assert caches["state"].dtype == F32
    assert caches["conv"].shape == (11, N, 3, 3 * 4096)
    assert caches["kv"].shape == (2, pages + 1, ps, 640)
    compiled = _compile_slot_step(model, caches, one_chip, N, W,
                                  -(-(cap + W) // ps))
    m = compiled.memory_analysis()
    pools = sum(a.size * a.dtype.itemsize for a in caches.values())
    with capsys.disabled():
        print(f"\nling slot step, described v5e: {model.num_params():,} "
              f"parameters, arguments {m.argument_size_in_bytes / GIB:.2f} "
              f"GiB, temporaries {m.temp_size_in_bytes / GIB:.2f} GiB, "
              f"aliased {m.alias_size_in_bytes / GIB:.2f} (pools and states "
              f"{pools / GIB:.2f})")
    text = compiled.as_text()
    assert [dims for dims in set(re.findall(r"\bf32\[([\d,]+)\]", text))
            if np.prod([int(d) for d in dims.split(",")])
            == N * W * cfg.vocab_size] == []
    # (the convolution rows are 1.2 MB a layer, 13 MB the stack: a layer's
    # block is read and written by plain slices of the scan's carry, which
    # is the layer's own work; the leaves that matter are the GB-sized ones)
    assert _pool_copies(
        text, {k: v for k, v in caches.items() if k != "conv"}) == []
    _check_pool_writes_take_the_budget(compiled, caches, ("kv",), N, W,
                                       "ling", capsys)
    assert m.alias_size_in_bytes >= pools
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 12.0 * GIB
    _check_weights_are_read_as_held(compiled, model, "ling", capsys)
    for name in ("kda_attention", "latent_attention", "expert_bank"):
        assert name in text
    # (PR 55) no 4-lane one-row operand is built for the delta-rule call
    assert "f32[16,32,128,4]" not in text
    # the routed layers' banks go to the kernel as the stack they are held
    # in: no instruction makes one layer's bank ([64, 2560, 768] or its
    # transpose, 252 MB), by slice, copy or fusion
    assert re.findall(
        r"= bf16\[(?:1,)?64,(?:2560,768|768,2560)\]\S* [\w\-]+\(", text) == []
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 15.75 * GIB


def test_glm5_slot_step_keeps_pools_and_slot_leaves_in_place(
        one_chip, monkeypatch, capsys):
    """The one [8, 128] serving step of GLM-5.3-Flash at its published widths
    and the benchmark's cut (published layers 0 and 4-7: 4 KDA + 1 indexed
    latent mixer over 1 dense + 4 routed MLPs, 36 of 288 experts, an eighth
    of the vocabulary, four residual streams) over its arena of 33,856
    pages: the latent pool, the pooled index keys (a quarter as long, on the
    same table), the KDA states, the convolution rows and the unfinished
    index keys ride every run's scan as one carry, so the compiled step
    holds no copy of a pool or of the state stack; the indexer's three
    calls and the KDA call are in it; and it fits the chip."""
    from deepspeed_tpu.models import glm5
    from deepspeed_tpu.models.decoding import init_paged_cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = glm5("glm-5.3-flash", layer_ids=[0, 4, 5, 6, 7], num_experts=36,
                 moe_routed_experts=288, vocab_size=19360)
    cfg = model.config
    assert (cfg.kind_count("kda"), cfg.kind_count("mla")) == (4, 1)
    assert (cfg.lead_dense_layers, cfg.num_layers) == (1, 4)
    N, W, ps, cap = 8, 128, 16, 67584
    pages = N * (cap + W) // ps
    assert pages == 33856
    caches = jax.eval_shape(
        lambda: init_paged_cache(cfg, pages, ps, BF16, max_slots=N))
    assert caches["state"].shape == (4, N, 64, 128, 128)
    assert caches["conv"].shape == (4, N, 3, 3 * 8192)
    assert caches["kv"].shape == (1, pages + 1, ps, 512)
    assert caches["ki"].shape == (1, pages + 1, ps // 4, 128)
    assert caches["ki_tail"].shape == (1, N, 3, 128)
    compiled = _compile_slot_step(model, caches, one_chip, N, W,
                                  -(-(cap + W) // ps))
    m = compiled.memory_analysis()
    pools = sum(a.size * a.dtype.itemsize for a in caches.values())
    with capsys.disabled():
        print(f"\nglm5 slot step, described v5e: {model.num_params():,} "
              f"parameters, arguments {m.argument_size_in_bytes / GIB:.2f} "
              f"GiB, temporaries {m.temp_size_in_bytes / GIB:.2f} GiB, "
              f"aliased {m.alias_size_in_bytes / GIB:.2f} (pools and states "
              f"{pools / GIB:.2f})")
    text = compiled.as_text()
    # (the pooled keys are gathered a slot for the scoring call: 35 MB, the
    # whole of their pool, and that copy is the design; the convolution rows
    # and the unfinished keys are a layer's own small blocks)
    assert _pool_copies(text, {k: v for k, v in caches.items()
                               if k in ("kv", "state")}) == []
    # (the pooled keys are written by BLOCK a slot, 33 blocks each: no rows)
    _check_pool_writes_take_the_budget(compiled, caches, ("kv",), N, W,
                                       "glm5", capsys, by_slot=("ki",))
    assert m.alias_size_in_bytes >= pools
    for name in ("kda_attention", "indexer_scores", "selection_topk",
                 "sparse_latent_attention"):
        assert name in text
    assert "f32[8,64,128,4]" not in text  # (PR 55) as in Ling's step
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 15.75 * GIB


def test_brumby_slot_step_keeps_both_state_leaves_in_place(
        one_chip, monkeypatch, capsys):
    """The one [16, 128] serving step of Brumby-14B-Base at its published
    widths and the benchmark's cut (published layers 0-7, the vocabulary
    whole) over its PAGELESS arena: the two slot leaves alone, 4.36 GB of
    expanded float32 state and its normaliser, no pool a page table indexes
    (the table is one column wide). They ride the run's scan as its carry
    and are aliased into the ``power_retention`` call, so the compiled step
    holds no copy, slice or write-back the size of a leaf or of a layer of
    one, and it fits the chip beside 8.4 GB of weights."""
    from deepspeed_tpu.models import brumby
    from deepspeed_tpu.models.decoding import init_paged_cache
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = brumby("brumby-14b", layer_ids=list(range(8)))
    cfg = model.config
    assert cfg.paged_layers == 0 and cfg.kind_count("retention") == 8
    N, W = 16, 128
    caches = jax.eval_shape(
        lambda: init_paged_cache(cfg, N, 18432 + W, BF16, max_slots=N))
    # 65 packed rows of 128 lanes: the 8,256 products of a key's symmetric
    # square and 64 lanes of zeros
    assert {k: (v.shape, v.dtype) for k, v in caches.items()} == {
        "state": ((8, N, 8, 65, 128, 128), F32),
        "norm": ((8, N, 8, 65, 1, 128), F32)}
    compiled = _compile_slot_step(model, caches, one_chip, N, W, 1)
    m = compiled.memory_analysis()
    pools = sum(a.size * a.dtype.itemsize for a in caches.values())
    with capsys.disabled():
        print(f"\nbrumby slot step, described v5e: {model.num_params():,} "
              f"parameters, arguments {m.argument_size_in_bytes / GIB:.2f} "
              f"GiB, temporaries {m.temp_size_in_bytes / GIB:.2f} GiB, "
              f"aliased {m.alias_size_in_bytes / GIB:.2f} (the two leaves "
              f"{pools / GIB:.2f})")
    text = compiled.as_text()
    assert "power_retention" in text and "tpu_custom_call" in text
    # one program a (slot, kv head): a kv head's 65 packed rows go through
    # VMEM whole, two buffers each way, under a limit the call raises itself
    # and keeps under its sibling kernels' cap
    from deepspeed_tpu.ops.pallas import power_retention as pr
    tile = pr.tile_rows(cfg.hd)
    asked = pr.vmem_limit(tile, cfg.hd, cfg.num_heads // cfg.kv_heads, W, 2)
    call = next(line for line in text.split("\n")
                if "power_retention" in line and "tpu_custom_call" in line)
    scoped = [int(b) for b in re.findall(
        r'"scoped_memory_configs":\[\{"memory_space":"1",[^}]*"size":"(\d+)"',
        call)]
    with capsys.disabled():
        print(f"power_retention: grid {(N, cfg.kv_heads, 65 // tile)}, "
              f"{tile} packed rows a program "
              f"({4 * tile * cfg.hd ** 2 * 4 / 2 ** 20:.1f} MiB of state in "
              f"four buffers), VMEM limit asked {asked / 2 ** 20:.1f} MiB, "
              f"in the compiled call {scoped}")
    assert tile == 65 and scoped == [asked]
    assert 4 * tile * cfg.hd ** 2 * 4 < asked < 96 * 2 ** 20
    assert _pool_copies(text, caches) == []
    assert m.alias_size_in_bytes >= pools
    _check_weights_are_read_as_held(compiled, model, "brumby", capsys)
    # the head runs over the 16 sampling rows, not the chunk's 2,048
    assert [dims for dims in set(re.findall(r"\bf32\[([\d,]+)\]", text))
            if np.prod([int(d) for d in dims.split(",")])
            == N * W * cfg.vocab_size] == []
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 15.75 * GIB


def test_glm_train_step_fits_the_described_chip(topo, monkeypatch, capsys):
    """The train step of the cell ``glm47flash-pretrain-4k`` (its
    configuration file's model and ds_config, micro-batch 2 x 4,096,
    706.5 M parameters with float32 AdamW state) lowered through
    ``initialize(abstract_init=True)`` for the described v5e: the chip's
    compiler takes it beside the 16 GB, the latent attention runs in the
    flash kernels and the experts in grouped matrix products."""
    import json
    import os

    import deepspeed_tpu
    from deepspeed_tpu.analysis import shardlint
    from deepspeed_tpu.comm import MeshTopology, ParallelDims
    from deepspeed_tpu.models import glm

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "glm-4.7-flash.json")) as f:
        eng = json.load(f)["engine"]
    model = glm(eng["model"]["size"], **eng["model"]["overrides"])
    batch = int(eng["micro_batch_per_chip"])
    engine, *_ = deepspeed_tpu.initialize(
        model=model, abstract_init=True,
        topology=MeshTopology(dims=ParallelDims(), devices=[topo.devices[0]]),
        config=dict(eng["ds_config"], train_batch_size=batch))
    # the kernels pick interpret mode from the backend, the CPU here
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(rn, "_interpret", lambda: False)
    _check_flash_grids(engine, batch, model.config.num_heads, 36,
                       "glm-4.7-flash", capsys)
    compiled = shardlint.lower_train_step(engine).compile()
    m = compiled.memory_analysis()
    peak = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)
    with capsys.disabled():
        print(f"\nglm-4.7-flash train step [{batch}, "
              f"{model.config.max_seq_len}], described v5e: arguments "
              f"{m.argument_size_in_bytes / GIB:.2f} GiB, temporaries "
              f"{m.temp_size_in_bytes / GIB:.2f} GiB, peak {peak / GIB:.2f} "
              f"GiB of 15.75")
    assert peak < 15.75 * GIB
    text = compiled.as_text()
    calls = set(re.findall(
        r'%([a-z_\-]+)[.\d]* = [^\n]*custom_call_target="tpu_custom_call"',
        text))
    assert {"latent_attention", "ragged-dot-none"} <= calls


def test_bloom_train_step_walks_the_causal_triangle(topo, monkeypatch,
                                                    capsys):
    """The train step of the cell ``bloom560m-pretrain-2k`` (its
    configuration file's model and ds_config, micro-batch 4 x 2,048) for the
    described v5e: every flash call of a layer runs over (4, 16, 10), the
    tiles a causal call sees of its 16, and the chip's compiler takes it."""
    import json
    import os

    import deepspeed_tpu
    from deepspeed_tpu.analysis import shardlint
    from deepspeed_tpu.comm import MeshTopology, ParallelDims
    from deepspeed_tpu.models import bloom

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "bloom-560m.json")) as f:
        eng = json.load(f)["engine"]
    model = bloom(eng["model"]["size"], **eng["model"]["overrides"])
    batch = int(eng["micro_batch_per_chip"])
    engine, *_ = deepspeed_tpu.initialize(
        model=model, abstract_init=True,
        topology=MeshTopology(dims=ParallelDims(), devices=[topo.devices[0]]),
        config=dict(eng["ds_config"], train_batch_size=batch))
    # the kernels pick interpret mode from the backend, the CPU here
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    _check_flash_grids(engine, batch, model.config.num_heads, 10,
                       "bloom-560m", capsys)
    text = shardlint.lower_train_step(engine).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') >= 4


# ----------------------------------------------------------------- norms
@pytest.mark.parametrize("D", [1024, 4096])
@pytest.mark.parametrize("kind", ["rmsnorm", "layernorm"])
def test_norm_fwd_bwd_compiles(one_chip, on_chip_norms, kind, D):
    rows = (2, 2048)  # [batch, seq] of one micro-batch

    if kind == "rmsnorm":
        def loss(x, s, b):
            return rn.rmsnorm(x, s, 1e-5).astype(F32).sum()
    else:
        def loss(x, s, b):
            return ln.layernorm(x, s, b, 1e-5).astype(F32).sum()

    # value_and_grad: the value keeps the forward kernel alive
    text = _compile(
        jax.value_and_grad(loss, argnums=(0, 1)), one_chip,
        (rows + (D,), BF16), ((D,), F32), ((D,), F32),
    )
    assert text.count("tpu_custom_call") >= 2, text[:2000]  # fwd + bwd


def test_norm_row_block_follows_width():
    """The rule itself: the row block shrinks as D grows, stays on the
    bf16 sublane tile, and never exceeds the rows there are."""
    assert rn._block_rows(4096, 1024) == 256
    assert rn._block_rows(4096, 4096) == 64
    assert rn._block_rows(4096, 1 << 20) == 16
    assert rn._block_rows(8, 1024) == 8
    for D in (128, 1024, 4096, 8192, 14336):
        assert rn._block_rows(1 << 20, D) % 16 == 0


def test_interpret_numerics_of_repaired_operands():
    """The two repaired SMEM operands, read back through interpret mode at
    a small size: per-head ALiBi slopes and per-row decode frontiers must
    pick THEIR head/row out of the whole-array operand."""
    from deepspeed_tpu.ops.attention import xla_attention

    rs = np.random.RandomState(0)
    B, S, H, hd = 2, 128, 4, 64
    q, k, v = (jnp.asarray(rs.randn(B, S, H, hd), F32) for _ in range(3))
    slopes = jnp.asarray(alibi_slopes(H), F32)
    got = fa.flash_attention(q, k, v, causal=True, alibi_slopes=slopes,
                             interpret=True)
    want = xla_attention(q, k, v, causal=True, alibi_slopes=slopes)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)

    Smax, KV = 256, 2
    qd = jnp.asarray(rs.randn(B, 1, H, hd), F32)
    kc, vc = (jnp.asarray(rs.randn(B, Smax, KV, hd), F32) for _ in range(2))
    cl = jnp.asarray([5, 200], I32)  # two different frontiers
    got = da.decode_attention_kernel(qd, kc, vc, cl, interpret=True)
    for b in range(B):
        n = int(cl[b]) + 1
        kk = jnp.repeat(kc[b, :n], H // KV, axis=1)
        vv = jnp.repeat(vc[b, :n], H // KV, axis=1)
        s = jnp.einsum("hd,shd->hs", qd[b, 0], kk) / np.sqrt(hd)
        want = jnp.einsum("hs,shd->hd", jax.nn.softmax(s, axis=-1), vv)
        np.testing.assert_allclose(np.asarray(got[b, 0]), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
