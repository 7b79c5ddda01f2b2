"""The serve step's row-by-row layers run over the plan's tokens (ISSUE 42).

``forward_with_cache(token_budget=W)`` packs the residual stream to ``W``
rows (``ChunkRows``); the cache writes and the attention calls alone see the
slot layout. The oracle is the same forward without the promise: the identity
tables, every slot's ``[B, S]`` rows, which is the step as it was.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm.topology import MeshTopology, ParallelDims
from deepspeed_tpu.models import deepseek, llama, mellum, minicpm, mixtral
from deepspeed_tpu.models.decoding import (ChunkRows, forward_with_cache,
                                           init_cache, init_paged_cache,
                                           row_layout, verify_window_rows)
from deepspeed_tpu.ops.attention import attention_impl
from deepspeed_tpu.serving import Request

F32 = jnp.float32
B, W, PS, MP = 4, 16, 16, 5   # slots, token budget, page size, pages a slot
MAX_DRAFT = 3
TOL = 1e-5

# a plan a step: ragged counts that sum to at most W, idle slots among them
PLANS = [
    dict(num_new=[W, 0, 0, 0]),                      # a slot at the budget
    dict(num_new=[3, 7, 0, 6]),                      # an idle slot between
    dict(num_new=[1, 9, 0, 1]),                      # decode rows and a chunk
    dict(num_new=[4, 1, 0, 1], spec_len=[3, 0, 0, 0]),  # a verify window
    dict(num_new=[0, 2, 5, 1]),                      # slot 0 idle, 2 begins
]


def tiny_llama(**kw):
    return llama("llama-tiny", **dict(
        vocab_size=128, max_seq_len=128, hidden_size=32, num_layers=2,
        num_heads=4, num_kv_heads=2, intermediate_size=64, **kw))


def tiny_mixtral():
    return mixtral("mixtral-tiny", vocab_size=64, max_seq_len=128,
                   hidden_size=32, num_layers=2, num_heads=4, num_kv_heads=2,
                   intermediate_size=64, num_experts=4, moe_top_k=2)


# the five shapes of cache, and the two other arenas a GQA model can have
CACHES = {
    "gqa_pages": (tiny_llama, {}),
    "window_and_full_pools": (
        lambda: mellum("mellum-tiny", initializer_range=0.05), {}),
    "latent_and_index_pools": (lambda: deepseek("deepseek-tiny"), {}),
    "slot_state_and_compressed_keys": (
        lambda: minicpm("minicpm-sala-tiny", initializer_range=0.1), {}),
    "routed_moe": (tiny_mixtral, {}),
    "gqa_int8_pages": (tiny_llama, dict(quantized=True)),
    "gqa_contiguous": (tiny_llama, dict(paged=False)),
    "gqa_pages_kernel": (tiny_llama, dict(impl="flash")),
}


def real_rows(cache, table, frontier, paged):
    """Every cache row a real token wrote, a slot at a time: what a later
    query can attend. Rows past a frontier are garbage in either layout."""
    out = {}
    for name, leaf in cache.items():
        leaf = np.asarray(leaf)
        for b, n in enumerate(frontier):
            if name == "state":           # [L, slots, H, hd, hd]
                got = leaf[:, b]
            elif not paged:               # [L, slots, Smax, ...] / scales
                got = (leaf[:, b, :, :n] if "scale" in name
                       else leaf[:, b, :n])
            elif name == "kc":            # a compressed key a whole page
                got = leaf[:, table[b, :n // PS]]
            elif "scale" in name:         # [L, P+1, KV, ps, SL]
                view = np.swapaxes(leaf[:, table[b]], 1, 2)
                got = view.reshape(*view.shape[:2], -1, view.shape[-1])[
                    :, :, :n]
            else:                         # [L, P+1, ps, ...]
                view = leaf[:, table[b]]
                got = view.reshape(view.shape[0], -1, *view.shape[3:])[:, :n]
            out[name, b] = got
    return out


@pytest.mark.parametrize("case", list(CACHES))
def test_packed_step_is_the_slot_step_on_every_real_token(case):
    """Five steps of a ragged plan, the packed forward and the slot-layout
    one each on a cache of its own: every real token's logits within 1e-5
    (whole chunks, and a verify window's rows), the routing counters equal,
    and at the end every cache row a real token wrote equal."""
    make, opts = CACHES[case]
    model = make()
    cfg = model.config
    params = model.init(jax.random.PRNGKey(3), dtype=F32)
    paged = opts.get("paged", True)
    rng = np.random.default_rng(11)
    table = None
    if paged:
        kw = {}
        if cfg.has_window:
            kw["window_pages"] = B * MP
        if cfg.mixer_types:
            kw["max_slots"] = B
        cache = init_paged_cache(cfg, B * MP, PS, F32,
                                 quantized=opts.get("quantized", False), **kw)
        table = rng.permutation(B * MP).reshape(B, MP).astype(np.int32)
    else:
        cache = init_cache(cfg, B, MP * PS, F32)
    moe = bool(cfg.is_moe)

    def forward(cache, tokens, frontier, num_new, window, budget):
        kw = dict(num_new=num_new, token_budget=budget, logit_rows=window,
                  return_moe_stats=moe)
        if paged:
            kw["page_table"] = jnp.asarray(table)
            if cfg.has_window:
                kw["page_table_win"] = jnp.asarray(table)
        if moe:
            kw["token_valid"] = jnp.arange(W)[None, :] < num_new[:, None]
        with attention_impl(opts.get("impl", "xla")):
            return forward_with_cache(cfg, params, tokens, cache, frontier,
                                      dtype=F32, **kw)

    steps = {
        (name, windowed): jax.jit(
            lambda c, t, f, n, w, budget=budget, windowed=windowed: forward(
                c, t, f, n, w if windowed else None, budget))
        for name, budget in (("packed", W), ("slots", None))
        for windowed in (False, True)}
    caches = {"packed": cache, "slots": cache}
    frontier = np.zeros(B, np.int32)
    for plan in PLANS:
        num_new = np.asarray(plan["num_new"], np.int32)
        spec_len = np.asarray(plan.get("spec_len", [0] * B), np.int32)
        windowed = "spec_len" in plan
        # (padding holds tokens too: no row is special for being zero)
        tokens = rng.integers(0, cfg.vocab_size, (B, W)).astype(np.int32)
        window = verify_window_rows(jnp.asarray(num_new),
                                    jnp.asarray(spec_len), MAX_DRAFT, W)
        outs = {}
        for name in caches:
            outs[name] = steps[name, windowed](
                caches[name], tokens, jnp.asarray(frontier),
                jnp.asarray(num_new), window)
            caches[name] = outs[name][1]
        got, want = (np.asarray(outs[n][0]) for n in ("packed", "slots"))
        assert got.shape == want.shape
        if windowed:  # a window's rows up to the slot's last draft
            real = (np.arange(MAX_DRAFT + 1)[None, :] <= spec_len[:, None]) & (
                num_new[:, None] > 0)
        else:
            real = np.arange(W)[None, :] < num_new[:, None]
        assert real.sum() == (
            (num_new > 0).sum() + spec_len.sum() if windowed
            else num_new.sum())
        np.testing.assert_allclose(got[real], want[real], rtol=0, atol=TOL)
        if moe:
            for key in ("tokens_per_expert", "drop_fraction"):
                np.testing.assert_array_equal(
                    np.asarray(outs["packed"][2][key]),
                    np.asarray(outs["slots"][2][key]), err_msg=key)
        frontier = frontier + num_new
    got, want = (real_rows(caches[n], table, frontier, paged)
                 for n in ("packed", "slots"))
    assert got.keys() == want.keys() and got
    for key in want:
        tol = 0 if want[key].dtype == np.int8 else TOL
        np.testing.assert_allclose(got[key], want[key], rtol=0, atol=tol,
                                   err_msg=str(key))


# the page pools a step writes rows into, by the model that keeps them
POOLS = {
    "gqa_pool": (tiny_llama, {}),
    "window_and_full_pools_two_tables": (
        lambda: mellum("mellum-tiny", initializer_range=0.05), {}),
    "latent_and_index_pools": (lambda: deepseek("deepseek-tiny"), {}),
    "sparse_keys_values_and_compressed_keys": (
        lambda: minicpm("minicpm-sala-tiny", initializer_range=0.1), {}),
    "gqa_int8_pool_and_scales": (tiny_llama, dict(quantized=True)),
}
# (frontiers, real tokens) a slot of one step
WRITES = {
    "an_idle_slot_between": ([5, 20, 9, 33], [3, 7, 0, 6]),
    "chunks_across_a_page_and_a_full_budget": ([10, 0, 30, 47], [9, 0, 2, 5]),
    "a_budget_not_full": ([0, 3, 17, 40], [1, 1, 2, 1]),
}


def _write_by_slot(patch):
    """The cache write as it was before the pools took the computed rows:
    every slot's ``[B, S]`` block, the packed rows unpacked into it, at the
    slot layout's places (a slot's rows past ``num_new`` past its frontier).
    Patched over the write, it is the oracle of what lands where."""
    from deepspeed_tpu.models import decoding

    seen = {}

    def places(rows, pools, tables):
        seen["rows"] = rows
        by_slot = ChunkRows(rows.B, rows.S, rows.slot_positions[:, 0])
        return {sfx: by_slot.page_rows(table, pools[
            next(n + sfx for n in ("k", "kv") if n + sfx in pools)])
            for sfx, table in tables.items()}

    def unpacked(put):
        return lambda pool, new, layer, page_rows: put(
            pool, seen["rows"].unpack(new), layer, page_rows)

    for name in ("_paged_write", "_paged_write_scale"):
        patch.setattr(decoding, name, unpacked(getattr(decoding, name)))
    patch.setattr(decoding, "page_places", places)


@functools.lru_cache(maxsize=None)
def _one_step(case):
    """(cfg, the pools filled with noise, the tables, the packed step and
    the same step writing by slot) of a pool kind, from any frontier."""
    make, opts = POOLS[case]
    model = make()
    cfg = model.config
    params = model.init(jax.random.PRNGKey(3), dtype=F32)
    rng = np.random.default_rng(17)
    kw = {}
    if cfg.has_window:
        kw["window_pages"] = B * MP
    if cfg.mixer_types:
        kw["max_slots"] = B
    zeros = init_paged_cache(cfg, B * MP, PS, F32,
                             quantized=opts.get("quantized", False), **kw)
    # no byte is special for being zero: what a write leaves alone shows
    pools = {
        name: jnp.asarray(
            rng.integers(-127, 128, leaf.shape).astype(np.int8)
            if leaf.dtype == jnp.int8
            else rng.uniform(0.5, 1.5, leaf.shape).astype(np.float32))
        for name, leaf in zeros.items()}
    tables = {"page_table": jnp.asarray(
        rng.permutation(B * MP).reshape(B, MP).astype(np.int32))}
    if cfg.has_window:  # the window layers' pool under a table of its own
        tables["page_table_win"] = jnp.asarray(
            rng.permutation(B * MP).reshape(B, MP).astype(np.int32))

    def forward(pools, tokens, frontier, num_new):
        kw = dict(num_new=num_new, token_budget=W, **tables)
        if cfg.is_moe:
            kw["token_valid"] = jnp.arange(W)[None, :] < num_new[:, None]
        return forward_with_cache(cfg, params, tokens, pools, frontier,
                                  dtype=F32, **kw)[1]

    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    shapes = (pools, i32(B, W), i32(B), i32(B))
    # (a function object each: a jit's trace is cached by the function)
    steps = {"packed": jax.jit(
        lambda *a: forward(*a)).lower(*shapes).compile()}
    with pytest.MonkeyPatch.context() as patch:
        _write_by_slot(patch)
        steps["by_slot"] = jax.jit(
            lambda *a: forward(*a)).lower(*shapes).compile()
    assert steps["packed"].as_text() != steps["by_slot"].as_text()
    return cfg, pools, tables, steps


@pytest.mark.parametrize("plan", list(WRITES))
@pytest.mark.parametrize("case", list(POOLS))
def test_packed_write_puts_real_rows_where_the_slot_write_does(case, plan):
    """One packed step from pools full of noise, its write as it is and as
    it was (every slot's block, :func:`_write_by_slot`): at every real
    position of every mapped page the step leaves bit for bit what
    the slot-layout write puts there (a compressed key: of every block the
    chunk made whole), every other byte of every page but the NULL page is
    the noise it was (the slot layout's padding does write past a frontier:
    the packed rows have no padding), and so the idle packed rows touched
    the NULL page alone."""
    cfg, pools, tables, steps = _one_step(case)
    frontier, num_new = (np.asarray(v, np.int32) for v in WRITES[plan])
    assert num_new.sum() <= W
    tokens = np.random.default_rng(5).integers(
        0, cfg.vocab_size, (B, W)).astype(np.int32)
    got, want = (
        {k: np.asarray(v) for k, v in steps[name](
            pools, tokens, jnp.asarray(frontier), jnp.asarray(num_new)
        ).items()} for name in ("packed", "by_slot"))
    before = {k: np.asarray(v) for k, v in pools.items()}
    checked = 0
    for name, leaf in got.items():
        if leaf.ndim < 3 or leaf.shape[1] != B * MP + 1:
            continue  # a leaf by slot (a state): no page
        table = np.asarray(tables[
            "page_table_win" if name.endswith("_win") else "page_table"])
        real = np.zeros(leaf.shape[1:3] if "scale" not in name
                        else (leaf.shape[1], leaf.shape[3]), bool)
        if name == "kc":  # [L, P+1, KV, hd]: a key a page, once it is whole
            for b in range(B):
                whole = range(frontier[b] // PS,
                              (frontier[b] + num_new[b]) // PS)
                np.testing.assert_array_equal(
                    leaf[:, table[b, list(whole)]],
                    want[name][:, table[b, list(whole)]])
            continue
        for b in range(B):
            pos = frontier[b] + np.arange(num_new[b])
            real[table[b, pos // PS], pos % PS] = True
        assert real.sum() == num_new.sum() and not real[-1].any()
        if "scale" in name:  # [L, P+1, KV, ps, SL]
            leaf, was, slot = (np.swapaxes(a, 2, 3) for a in (
                leaf, before[name], want[name]))
        else:
            was, slot = before[name], want[name]
        np.testing.assert_array_equal(leaf[:, real], slot[:, real], name)
        assert not np.array_equal(leaf[:, real], was[:, real])
        kept = ~real
        kept[-1] = False  # the NULL page holds whatever came last
        np.testing.assert_array_equal(leaf[:, kept], was[:, kept], name)
        # (which the slot layout's write did not: its padding landed there)
        assert not np.array_equal(slot[:, kept], was[:, kept])
        checked += 1
    assert checked >= (4 if cfg.has_window or "scale" in "".join(got) else 2)


@pytest.mark.parametrize("seed", range(6))
def test_pack_of_unpack_is_the_rows_themselves(seed):
    """Random counts that sum to at most the budget: a slot's rows are the
    contiguous run ``start[b] : start[b] + num_new[b]`` of the packed order,
    ``unpack`` hands slot ``b`` that run as its chunk's first rows, ``pack``
    brings every real row back, and a window's rows map through the same
    table."""
    rng = np.random.default_rng(seed)
    slots, width = int(rng.integers(2, 9)), int(rng.integers(4, 33))
    cuts = np.sort(rng.integers(0, width + 1, slots))
    num_new = np.diff(np.concatenate([[0], cuts])).astype(np.int32)
    num_new[rng.integers(slots)] = 0          # an idle slot, always
    if seed == 0:
        num_new[:] = 0
        num_new[-1] = width                   # one slot owns the budget
    total = int(num_new.sum())
    assert total <= width
    cache_len = rng.integers(0, 100, slots).astype(np.int32)
    rows = ChunkRows(slots, width, jnp.asarray(cache_len),
                     jnp.asarray(num_new), width)
    assert rows.packed and rows.count == width
    start = np.cumsum(num_new) - num_new
    np.testing.assert_array_equal(np.asarray(rows.valid)[0],
                                  np.arange(width) < total)
    x = rng.normal(size=(1, width, 3, 5)).astype(np.float32)
    by_slot = np.asarray(rows.unpack(jnp.asarray(x)))
    assert by_slot.shape == (slots, width, 3, 5)
    for b in range(slots):
        np.testing.assert_array_equal(by_slot[b, :num_new[b]],
                                      x[0, start[b]:start[b] + num_new[b]])
    again = np.asarray(rows.pack(jnp.asarray(by_slot)))
    np.testing.assert_array_equal(again[0, :total], x[0, :total])
    # positions a row: the slot's frontier plus the index in its chunk
    want = np.concatenate([cache_len[b] + np.arange(num_new[b])
                           for b in range(slots)])
    np.testing.assert_array_equal(np.asarray(rows.positions)[0, :total], want)
    # the rows a sampler reads: each slot's last real row
    last = verify_window_rows(jnp.asarray(num_new), jnp.zeros(slots, jnp.int32),
                              0, width)
    taken = np.asarray(rows.take(jnp.asarray(x), last))
    for b in np.flatnonzero(num_new):
        np.testing.assert_array_equal(
            taken[b, 0], x[0, start[b] + num_new[b] - 1])


def test_one_slot_and_no_promise_are_the_identity():
    x = jnp.arange(2 * 4 * 3, dtype=F32).reshape(2, 4, 3)
    for rows in (ChunkRows(2, 4, 7), ChunkRows(2, 4, jnp.asarray([1, 2])),
                 ChunkRows(1, 4, jnp.asarray([5]), jnp.asarray([2]), 4)):
        assert not rows.packed and rows.valid is None
        assert rows.pack(x) is x and rows.unpack(x) is x
        assert rows.positions is rows.slot_positions
    assert ChunkRows(2, 4, 7).count == 8


def _engine(topology=None, **serving):
    model = tiny_llama()
    eng = deepspeed_tpu.init_inference(
        model, dtype=F32, max_tokens=48, topology=topology,
        rng=jax.random.PRNGKey(1))
    return deepspeed_tpu.init_serving(
        engine=eng, serving=dict(max_slots=4, token_budget=8, max_tokens=48,
                                 **serving))


def _tokens(srv):
    prompts = [list(range(3, 3 + n)) for n in (11, 2, 7, 5, 9)]
    states = [srv.submit(Request(request_id=f"r{i}", prompt=p,
                                 max_new_tokens=5))
              for i, p in enumerate(prompts)]
    srv.run_until_idle()
    assert srv.step_traces == 1
    return [list(s.tokens) for s in states]


@pytest.mark.parametrize("paged", [False, True], ids=["contiguous", "paged"])
def test_engine_says_which_rows_it_computes(paged):
    """A slot engine packs: the gauge is the budget and the layout says so;
    on a mesh that shards the slot axis the step keeps the slot layout and
    says why; both emit the same tokens."""
    serving = dict(paged=True, page_size=8) if paged else {}
    srv = _engine(**serving)
    assert (srv.row_layout, srv.row_layout_reason) == ("packed", None)
    snap = srv.metrics.snapshot()
    assert snap["dense_rows_per_step"] == 8
    # a page pool is written from the packed rows; a contiguous arena takes
    # a slot's chunk, one slice a slot
    assert snap["cache_rows_per_step"] == (8 if paged else 4 * 8)
    want = _tokens(srv)

    topo = MeshTopology(dims=ParallelDims(dp=2), devices=jax.devices()[:2])
    assert row_layout(topo)[0] == "slots"
    sharded = _engine(topology=topo, **serving)
    assert sharded.row_layout == "slots"
    assert "dp x fsdp" in sharded.row_layout_reason
    snap = sharded.metrics.snapshot()
    assert snap["dense_rows_per_step"] == snap["cache_rows_per_step"] == 4 * 8
    assert _tokens(sharded) == want
