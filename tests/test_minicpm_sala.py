"""MiniCPM-SALA on the serving path, float32 on the CPU at a tiny size:
lightning layers whose slot state is no page beside block-sparse attention
over the paged cache, against the benchmark's plain reference
(benchmarks/families/minicpm_sala.py)."""

import functools
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.config import DeepSpeedConfigError
from deepspeed_tpu.models import minicpm
from deepspeed_tpu.models.decoding import init_paged_cache
from deepspeed_tpu.models.mixers import walk_runs
from deepspeed_tpu.ops.attention import attention_impl
from deepspeed_tpu.ops.pallas import block_sparse_attention as bsa
from deepspeed_tpu.ops.pallas import lightning_attention as la
from deepspeed_tpu.serving import Request

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from benchmarks.families import minicpm_sala as fam  # noqa: E402
from slot_program import (cached_step, chunked_logits, ids_of,  # noqa: E402
                          init_params, reference_logits)

F32 = jnp.float32
logits_of = reference_logits(fam)
TOL = 2e-5          # on logits whose spread is about 0.2
PS, W, SLOTS = 16, 16, 2
SERVING = dict(max_slots=SLOTS, token_budget=W, max_tokens=1008, paged=True,
               page_size=PS)
MIXERS = ["minicpm4", "lightning-attn", "lightning-attn", "minicpm4",
          "minicpm4", "lightning-attn"]
SPARSE = dict(kernel_size=32, kernel_stride=16, block_size=64, topk=5,
              init_blocks=1, window_size=64, dense_len=384)
CONFIG = dict(
    family="minicpm_sala", hidden_size=64, num_hidden_layers=6,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    intermediate_size=128, vocab_size=512, rms_norm_eps=1e-6,
    rope_theta=10000, scale_emb=12, scale_depth=1.4, dim_model_base=16,
    mixer_types=MIXERS, layer_ids=list(range(6)),
    published=dict(num_hidden_layers=6), assumed=dict(sparse_config=SPARSE))


@pytest.fixture(scope="module")
def model():
    # weights five times the preset's spread, so that the mixers weigh as
    # much as the residual stream and a fault in one shows in the logits
    return minicpm("minicpm-sala-tiny", initializer_range=0.1)


@pytest.fixture(scope="module")
def params(model):
    return init_params(model)


@pytest.fixture(scope="module")
def shape():
    return fam.shape_of(CONFIG)


def prefill_logits(model, params, ids, kernels=False):
    """Logits of every position of ``ids``, chunks of W rows in slot 0 of a
    two-slot arena of 64 pages a slot (the other slot idle, its row of the
    table all NULL pages)."""
    mp = 64
    table = np.full((SLOTS, mp), SLOTS * mp, np.int32)
    table[0] = np.arange(mp)
    return chunked_logits(model, params, ids, table, slot=0, chunk=W,
                          page_size=PS, kernels=kernels)


def serve(model, params, prompts, new=5, **over):
    srv = deepspeed_tpu.init_serving(
        model, serving=dict(SERVING, **over), params=params, dtype=F32)
    states = [srv.submit(Request(request_id=f"r{i}", prompt=p,
                                 max_new_tokens=new, temperature=0.0,
                                 eos_token_id=-1))
              for i, p in enumerate(prompts)]
    srv.run_until_idle()
    return srv, states


# ------------------------------------------------------- against the reference
@pytest.mark.parametrize("path,n", [("dense", 37), ("dense", 450),
                                    ("dense", 611), ("kernels", 450)])
def test_cached_logits_match_the_reference(model, params, shape, path, n):
    """Chunked prefill through pages and state (contexts inside dense_len,
    just past it and far past it; none a multiple of 16 or 64) against the
    reference's full forward pass, every position."""
    ids = ids_of(n, seed=n)
    want = np.asarray(logits_of(params, ids, shape))
    # ("kernels": the Pallas calls, in interpret mode here)
    got = prefill_logits(model, params, ids, kernels=path == "kernels")
    assert want.std() > 0.1
    assert np.abs(got - want).max() < TOL


def test_the_engine_serves_the_references_argmax(model, params, shape):
    prompts = [ids_of(n, seed=n) for n in (37, 450, 611)]
    srv, states = serve(model, params, prompts)
    assert srv.step_order == "overlapped" and srv.step_traces == 1
    assert srv.attention_paths == {"sparse": "dense", "lightning": "dense"}
    for p, st in zip(prompts, states):
        ids = np.concatenate([p, np.asarray(st.tokens, np.int32)])
        lg = np.asarray(logits_of(params, ids[:-1], shape, last=5))
        assert (lg.max(-1) - lg[np.arange(5), st.tokens]).max() < TOL
    snap = srv.metrics.snapshot()
    assert snap["state_bytes"] == 3 * SLOTS * 4 * 16 * 16 * 4
    assert snap["state_resets"] == 3 and snap["context_keys"] > 0
    assert snap["attended_keys_sparse"] < snap["context_keys"]


def test_the_engine_with_the_kernels_serves_the_dense_tokens(model, params):
    prompts = [ids_of(n, seed=n) for n in (61, 450)]
    _, want = serve(model, params, prompts, new=3)
    with attention_impl("flash"):
        kern, got = serve(model, params, prompts, new=3)
    assert [s.tokens for s in got] == [s.tokens for s in want]
    assert kern.attention_path == "block_sparse_kernel"
    assert kern.attention_paths["lightning"] == "lightning_kernel"
    snap = kern.metrics.snapshot()
    assert snap["attention_paged_kernel_sparse"] == 1.0
    assert snap["attention_paged_kernel_lightning"] == 1.0
    kern.lower_step()


# ---------------------------------------------------------------- the state
@pytest.mark.parametrize("kernel", [False, True])
def test_lightning_chunked_is_token_by_token_is_one_shot(kernel):
    """Chunk sizes that do not divide the length, against the recurrence."""
    B, S, H, hd = 1, 37, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (np.asarray(jax.random.normal(x, (S, H, hd))) for x in ks)
    ll = np.asarray([-0.4, -0.02], np.float32)
    s, want = np.zeros((H, hd, hd)), []
    for t in range(S):
        s = np.exp(ll)[:, None, None] * s + np.einsum(
            "hd,he->hde", k[t], v[t])
        want.append(np.einsum("hd,hde->he", q[t], s) * hd ** -0.5)
    want = np.asarray(want)

    # (a compile a chunk size, not one a call)
    lightning = jax.jit(functools.partial(la.lightning_attention, layer=0,
                                          scale=hd ** -0.5))
    dense = jax.jit(functools.partial(la.dense_lightning, scale=hd ** -0.5))

    def run(chunk):
        state = jnp.zeros((1, B, H, hd, hd), F32)
        outs = []
        for lo in range(0, S, chunk):
            n = min(chunk, S - lo)
            pad = lambda a: jnp.asarray(np.pad(
                a[lo:lo + n], ((0, chunk - n), (0, 0), (0, 0)),
                constant_values=7.0)[None])
            args = (pad(q), pad(k), pad(v), ll)
            at = (jnp.asarray([lo]), jnp.asarray([n]))
            if kernel:
                o, state = lightning(*args, state, *at)
            else:
                o, s1 = dense(*args, state[0], *at)
                state = s1[None]
            outs.append(np.asarray(o[0, :n]))
        return np.concatenate(outs), np.asarray(state[0, 0])

    for chunk in (5, 16, 37, 64):
        got, last = run(chunk)
        assert np.abs(got - want).max() < 1e-5, chunk
        assert np.abs(last - s).max() < 1e-5, chunk


@pytest.mark.parametrize("path", ["dense", "kernels"])
def test_padded_rows_and_idle_slots_leave_the_state_bitwise(model, params,
                                                            path):
    cfg = model.config
    mp = 64
    caches = init_paged_cache(cfg, SLOTS * mp, PS, F32, max_slots=SLOTS)
    held = jax.random.normal(jax.random.PRNGKey(3), caches["state"].shape)
    held = held.at[0, 1, 0, 0, 0].set(-0.0)
    table = jnp.asarray(np.stack([np.arange(mp), np.full(mp, SLOTS * mp)]
                                 ).astype(np.int32))

    def step(pad_token):
        tokens = np.full((SLOTS, W), pad_token, np.int32)
        tokens[0, :5] = ids_of(5, seed=9)
        _, after = cached_step(cfg, path == "kernels")(
            params, jnp.asarray(tokens), dict(caches, state=held),
            jnp.asarray([32, 0], jnp.int32), table,
            jnp.asarray([5, 0], jnp.int32))
        return np.asarray(after["state"])

    a, b = step(0), step(411)
    # the idle slot (no real row, though it stands at position 0)
    assert a[:, 1].tobytes() == np.asarray(held)[:, 1].tobytes()
    # slot 0: its 11 padded rows weigh nothing, whatever stands in them
    assert a[:, 0].tobytes() == b[:, 0].tobytes()
    assert not np.array_equal(a[:, 0], np.asarray(held)[:, 0])


def test_a_reused_slot_reads_as_a_fresh_engine(model, params):
    first, second = ids_of(450, seed=1), ids_of(85, seed=2)
    srv, _ = serve(model, params, [first], max_slots=1)
    again = srv.submit(Request(request_id="b", prompt=second,
                               max_new_tokens=6, temperature=0.0,
                               eos_token_id=-1))
    srv.run_until_idle()
    _, fresh = serve(model, params, [second], new=6, max_slots=1)
    assert again.tokens == fresh[0].tokens
    assert srv.metrics.snapshot()["state_resets"] == 2


# ------------------------------------------------------------ the selection
def brute_force_blocks(q, k, pos, g: bsa.BlockSparse):
    """The blocks position ``pos`` keeps a kv group, by loops: q [H, hd] of
    the query, k [S, KV, hd] every key at or before it."""
    H, hd = q.shape
    KV = k.shape[1]
    nk = max((pos + 1 - g.kernel_size) // g.kernel_stride + 1, 0)
    nb = pos // g.block_size + 1
    if pos + 1 <= g.dense_len:
        return [set(range(nb))] * KV
    out = []
    for kv in range(KV):
        kc = np.stack([k[j * g.kernel_stride:
                         j * g.kernel_stride + g.kernel_size, kv].mean(0)
                       for j in range(nk)])
        P = np.zeros(nk)
        for h in range(kv * H // KV, (kv + 1) * H // KV):
            s = kc @ q[h] / np.sqrt(hd)
            e = np.exp(s - s.max())
            P += e / e.sum()
        score = np.full(nb, 0.0)
        for m in range(nb):
            for j in range(nk):
                lo, hi = j * g.kernel_stride, j * g.kernel_stride + g.kernel_size
                if lo < (m + 1) * g.block_size and hi > m * g.block_size:
                    score[m] = max(score[m], P[j])
            if m < g.init_blocks or (m + 1) * g.block_size > pos + 1 - g.window_size:
                score[m] = np.inf
        out.append(set(np.argsort(-score, kind="stable")[:g.topk].tolist()))
    return out


@pytest.mark.parametrize("kernel", [False, True])
def test_the_selection_is_the_brute_force_one(kernel):
    """Contexts on both sides of dense_len, the forced blocks included: a
    chunk of 16 rows at each of several frontiers of one 700-token slot."""
    g = bsa.BlockSparse(**SPARSE)
    H, KV, hd, S, mp = 4, 2, 16, 700, 64
    rng = np.random.default_rng(5)
    k_all = rng.normal(size=(S, KV, hd)).astype(np.float32)
    k_pool = jnp.zeros((1, mp + 1, PS, KV, hd), F32)
    kc_pool = jnp.zeros((1, mp + 1, KV, hd), F32)
    table = jnp.asarray(np.arange(mp, dtype=np.int32)[None])
    from deepspeed_tpu.models.decoding import ChunkRows, _paged_write

    for lo in range(0, S, W):  # the cache as the step fills it
        n = min(W, S - lo)
        chunk = np.zeros((1, W, KV, hd), np.float32)
        chunk[0, :n] = k_all[lo:lo + n]
        at = (jnp.asarray([lo]), )
        k_pool = _paged_write(
            k_pool, jnp.asarray(chunk), 0,
            ChunkRows(1, W, at[0]).page_rows(table, k_pool))
        kc_pool = bsa.write_compressed_keys(
            kc_pool, k_pool, 0, at[0], jnp.asarray([n]), table, g, W)
    for lo in (320, 368, 380, 600, 684):
        n = min(W, S - lo)
        q = rng.normal(size=(1, W, H, hd)).astype(np.float32) * 2
        cl, nn = jnp.asarray([lo]), jnp.asarray([n])
        if kernel:
            planes = bsa.plane_view(kc_pool, 0, table, g,
                                    bsa._padded_blocks(g, mp * PS))
            kept = np.asarray(bsa.unchunked(bsa.block_select(
                jnp.asarray(q), planes, cl, nn, g, interpret=True)))
        else:
            planes = bsa.plane_view(kc_pool, 0, table, g, g.blocks(mp * PS))
            kept = np.asarray(bsa.dense_block_selection(
                jnp.asarray(q), planes, lo + jnp.arange(W)[None], g))
        for i in range(n):
            want = brute_force_blocks(q[0, i], k_all[:lo + i + 1], lo + i, g)
            for kv in range(KV):
                got = set(np.flatnonzero(kept[0, kv, i]).tolist())
                assert got == want[kv], (lo, i, kv)
        assert not kept[0, :, n:].any() or not kernel


# ---------------------------------------------------------------- the shapes
def test_published_order_and_the_32_layer_preset():
    tiny = minicpm("minicpm-sala-tiny").config
    assert [r.period for r in walk_runs(tiny)] == [
        ((kind, "dense"),) for kind in ("sparse", "lightning", "sparse",
                                        "lightning")]
    model = minicpm("minicpm-sala")
    cfg = model.config
    assert cfg.num_layers == 32 and cfg.kind_count("sparse") == 8
    assert [i for i, k in enumerate(cfg.mixer_types) if k == "sparse"] == [
        0, 9, 16, 17, 22, 29, 30, 31]
    runs = walk_runs(cfg)
    assert len(runs) == 9 and sum(r.trips for r in runs) == 32
    shapes = jax.eval_shape(lambda k: model.init(k, dtype=jnp.bfloat16),
                            jax.random.PRNGKey(0))
    assert shapes["lightning_layers"]["attn"]["wk"].shape == (24, 4096, 4096)
    assert shapes["sparse_layers"]["attn"]["wk"].shape == (8, 4096, 256)
    assert shapes["lightning_layers"]["attn"]["o_norm"]["scale"].shape == (
        24, 4096)
    assert "o_norm" not in shapes["sparse_layers"]["attn"]
    leaves = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert model.num_params() == leaves
    assert round(leaves / 1e9, 2) == 9.48
    # the benchmark's cut keeps each layer's published index and the depth
    cut = minicpm("minicpm-sala", layer_ids=list(range(16, 28))).config
    assert cut.mixer_types.count("sparse") == 3 and cut.mixer_depth == 32
    assert cut.mixer_layer_ids == tuple(range(16, 28))
    assert cut.num_params() == 3_930_000_000 + (cut.num_params() % 10**7) \
        or abs(cut.num_params() / 1e9 - 3.93) < 0.005


@pytest.mark.parametrize("fault", fam.FAULTS)
def test_every_fault_moves_the_references_logits(params, shape, fault):
    # 400 tokens: the judged rows lie just past dense_len (384), and rows
    # 320-383 before them inside it with six blocks behind, one over topk
    ids = ids_of(400, seed=400)
    sound = np.asarray(fam.logits(params, ids, shape, last=5))
    broken = np.asarray(fam.logits(
        ids=ids, shape=shape, last=5, **fam.faulted(params, fault, shape)))
    moved = np.abs(broken - sound).max()
    assert moved > TOL


# -------------------------------------------------------------- the refusals
def test_what_cannot_hold_with_state_layers_is_refused_by_name(model, params):
    for over, word in ((dict(host_pages=8), "host_pages"),
                       (dict(fleet=dict(enabled=True, replicas=2,
                                        prefill_replicas=1)),
                        "prefill_replicas"),
                       (dict(spec=dict(enabled=True, max_draft=2)),
                        "serving.spec"),
                       (dict(paged=False), "serving.paged"),
                       (dict(page_size=8), "page_size")):
        with pytest.raises(DeepSpeedConfigError, match=word) as e:
            deepspeed_tpu.init_serving(
                model, serving=dict(SERVING, **over), params=params,
                dtype=F32)
        assert "lightning" in str(e.value) or "sparse" in str(e.value)
        assert "minicpm" not in str(e.value).lower()
    with pytest.raises(DeepSpeedConfigError, match="int8 KV cache"):
        deepspeed_tpu.init_serving(model, serving=SERVING, params=params,
                                   dtype=F32, kv_cache_dtype="int8")
    # the prefix cache goes off with the reason, as for window layers
    srv, states = serve(model, params, [ids_of(20, seed=40)],
                        prefix_cache=True)
    assert srv.scheduler.prefix_cache is None
    for call in (lambda: srv.export_kv_pages([0]),
                 lambda: srv.import_kv_pages({}, [0]),
                 lambda: srv.scheduler.adopt(states[0])):
        with pytest.raises(RuntimeError, match="state"):
            call()
    srv.scheduler.assert_page_invariants()
    batch = {"input_ids": jnp.zeros((2, 8), jnp.int32),
             "labels": jnp.zeros((2, 8), jnp.int32)}
    with pytest.raises(DeepSpeedConfigError, match="mixer_types"):
        model.loss(params, batch)
