"""Keye-VL-2.0's language model (a lightning indexer that selects tokens
inside paged grouped-query K / V, one member's share of a softmax-routed
expert layer that drops nothing) at a small size on the CPU in float32,
seeded random weights, against the benchmark's plain reference
(``benchmarks/families/keye_vl2.py``): the cached forward through the paged
pools, the serving engine, the share against the uncut layer, the rotary
against the reference's M-RoPE, and the kernels against their dense twins."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from benchmarks.families import keye_vl2 as fam
from deepspeed_tpu.config import DeepSpeedConfigError
from deepspeed_tpu.models import keye, mellum
from deepspeed_tpu.models.decoding import INDEX, index_row_width
from deepspeed_tpu.models.transformer import (RopeTable, TransformerConfig,
                                              _rope)
from deepspeed_tpu.ops.attention import attention_impl
from deepspeed_tpu.ops.pallas import sparse_latent_attention as sla
from deepspeed_tpu.ops.pallas import sparse_paged_attention as spa
from deepspeed_tpu.serving import Request
from slot_program import (ids_of, init_params, paged_forward,
                          reference_logits)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32 = jnp.float32
# float32 on both sides, the same equations in another order of summation;
# the mildest fault moves the logits by thirty times the tolerance
RTOL = 1e-4
PS = 16
logits_of = reference_logits(fam)
HELD = dict(num_experts=2, moe_routed_experts=8)  # member 0 of four


def tiny_config(**over):
    """The benchmark's configuration at its rehearsal size."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "keye-vl-2.0-30b-a3b.json")) as f:
        cfg = json.load(f)
    tiny = cfg.pop("rehearse")
    tiny.pop("engine")
    nested = {k: {**cfg[k], **tiny.pop(k)}
              for k in ("rope_scaling", "sa_config", "published")}
    return {**cfg, **tiny, **nested, **over}


@pytest.fixture(scope="module")
def shape():
    return fam.shape_of(tiny_config())


def tiny(**over):
    # wide initial weights, so that attention and the experts move the
    # logits by as much as the embedding does
    return keye("keye-tiny", **{**HELD, "initializer_range": 0.2, **over})


@pytest.fixture(scope="module")
def model():
    return tiny()


@pytest.fixture(scope="module")
def params(model):
    return init_params(model, spread=0.2)


def close(got, want, rtol=RTOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want).max() <= rtol * np.abs(want).max()


def test_the_preset_is_the_published_model_and_the_tiny_one_its_shape(shape):
    c = keye("keye-vl-2.0-30b-a3b").config
    assert (c.hidden_size, c.num_layers, c.num_heads, c.kv_heads, c.hd,
            c.ffn, c.num_experts, c.moe_top_k, c.vocab_size, c.rope_theta) == (
        2048, 48, 32, 4, 128, 768, 128, 8, 151936, 1e7)
    assert (c.index_heads, c.index_dim, c.index_rope_dim, c.index_topk) == (
        16, 64, 64, 2048)
    assert (c.moe_gate, c.moe_capacity_factor, c.moe_dropless, c.qk_norm,
            c.tie_embeddings, c.moe_shared_width) == (
        "softmax", 0.0, True, True, False, 0)
    # the issue's arithmetic: a layer's attention, indexer, router, experts
    cut = keye("keye-vl-2.0-30b-a3b", num_layers=12, num_experts=32,
               moe_routed_experts=128, vocab_size=37984)
    d = 2048
    attn = 2 * d * 4096 + 2 * d * 512 + 2 * 128
    indexer = d * 1024 + d * 64 + 2 * 64 + d * 16
    layer = attn + indexer + d * 128 + 32 * 3 * d * 768 + 2 * d
    assert (attn // 10 ** 4, indexer // 10 ** 4) == (1887, 226)
    assert cut.num_params() == 12 * layer + 2 * 37984 * d + d == 2_224_347_648
    t = tiny().config
    assert (t.hidden_size, t.num_layers, t.num_heads, t.kv_heads, t.hd,
            t.ffn, t.num_experts, t.routed_experts, t.moe_top_k,
            t.vocab_size, t.index_heads, t.index_dim, t.index_topk) == (
        shape.d, shape.layers, shape.heads, shape.kv_heads, shape.hd,
        shape.ffn, shape.experts, shape.routed, shape.top_k, shape.vocab,
        shape.index_heads, shape.index_dim, shape.index_topk)
    assert sum(shape.sections) == shape.hd // 2


def test_the_tree_has_the_indexer_beside_the_attention(model, params):
    L = params["layers"]
    assert set(L) == {"ln1", "ln2", "attn", "mlp"}
    assert set(L["attn"]) == {"wq", "wk", "wv", "wo", "q_norm", "k_norm",
                              "idx"}
    idx = L["attn"]["idx"]
    assert set(idx) == {"wq", "wk", "k_norm", "w_proj"}
    assert idx["wq"].shape == (3, 64, 2 * 8)       # from the normed input
    assert idx["wk"].shape == (3, 64, 8) and idx["w_proj"].shape == (3, 64, 2)
    assert set(idx["k_norm"]) == {"scale", "bias"}
    assert set(L["mlp"]) == {"router", "wi", "wg", "wo"}
    assert L["mlp"]["router"].shape == (3, 64, 8)  # all routed experts
    assert L["mlp"]["wi"].shape == (3, 2, 64, 32)  # the two held
    assert sum(a.size for a in jax.tree.leaves(params)) == model.num_params()
    specs = model.partition_specs()
    assert jax.tree.structure(specs) == jax.tree.structure(
        jax.tree.map(lambda a: 0, params))


def test_what_is_not_computed_is_refused_by_name(model, params):
    # an indexer over a latent cache reads the query latent
    with pytest.raises(ValueError, match="q_latent_dim"):
        TransformerConfig(index_topk=4, index_heads=2, index_dim=8,
                          kv_latent_dim=16, qk_nope_dim=8, qk_rope_dim=8,
                          v_head_dim=8, num_kv_heads=1, head_dim=16)
    # ... and one inside paged K / V needs its own widths, and full layers
    with pytest.raises(ValueError, match="index_heads and index_dim"):
        TransformerConfig(index_topk=4)
    with pytest.raises(ValueError, match="window layer's selection"):
        mellum("mellum-tiny", index_topk=4, index_heads=2, index_dim=8)
    # a share under a softmax router that drops by capacity stays refused
    with pytest.raises(ValueError, match="moe_capacity_factor 0"):
        mellum("mellum-tiny", moe_routed_experts=16)
    # the uncached forward computes neither the selection nor the share
    ids = jnp.asarray(ids_of(8)[None])
    with pytest.raises(DeepSpeedConfigError, match="index_topk"):
        model.apply(params, ids, dtype=F32)
    plain = dataclasses.replace(model.config, index_topk=0, index_heads=0,
                                index_dim=0, index_rope_dim=0)
    with pytest.raises(DeepSpeedConfigError, match="moe_capacity_factor 0"):
        type(model)(plain).apply(
            {**params, "layers": {**params["layers"], "attn": {
                k: v for k, v in params["layers"]["attn"].items()
                if k != "idx"}}}, ids, dtype=F32)
    # the engine: what no test holds under a selection
    serving = dict(max_slots=2, token_budget=16, max_tokens=112, paged=True,
                   page_size=PS)
    for over, match in (
            (dict(paged=False), "serving.paged false"),
            (dict(host_pages=4), "host_pages"),
            (dict(spec=dict(enabled=True)), "serving.spec"),
            (dict(fleet=dict(prefill_replicas=1)), "prefill_replicas")):
        with pytest.raises(DeepSpeedConfigError, match=match):
            deepspeed_tpu.init_serving(
                model, serving={**serving, **over}, params=params, dtype=F32)


def test_chunks_that_cut_pages_then_decode_match_the_reference(
        model, params, shape):
    """Two slots, prompts under and three times over ``index_topk`` 24,
    chunks of 24 rows (a page is 16) packed to a budget of 32, then greedy
    decode: the logits of every position are the reference's."""
    prompts = [ids_of(19, seed=1), ids_of(75, seed=2)]
    got, seqs = paged_forward(model, params, prompts, chunk=24, page_size=PS,
                              new_tokens=3, budget=32)
    for rows, seq in zip(got, seqs):
        want = logits_of(params, np.asarray(seq[:len(rows)], np.int32), shape)
        assert close(rows, want)
    assert len(seqs[1]) == 78


@pytest.mark.parametrize("fault", fam.FAULTS)
def test_every_fault_moves_the_reference_beyond_the_tolerance(
        params, shape, fault):
    ids = ids_of(100, seed=3)
    want = fam.logits(params, ids, shape)
    broken = fam.logits(ids=ids, shape=shape,
                        **fam.faulted(params, fault, shape))
    assert not close(broken, want, rtol=30 * RTOL), fault


def test_the_plain_rotary_is_the_references_mrope_at_text_positions(shape):
    """The reference cuts the frequencies into ``mrope_section`` runs, one a
    position axis; with three equal axes that is the program's one table.
    With an image's axes apart it is not: the sections do work."""
    rng = np.random.default_rng(0)
    S, first = 37, 5
    q = rng.normal(size=(1, S, shape.heads, shape.hd)).astype(np.float32)
    k = rng.normal(size=(1, S, shape.kv_heads, shape.hd)).astype(np.float32)
    pos = jnp.arange(first, first + S)[None]
    got_q, got_k = _rope(jnp.asarray(q), jnp.asarray(k), pos,
                         RopeTable(shape.rope_theta))
    inv = jnp.asarray(fam.inv_freq(shape.rope_theta, shape.hd))
    ang = fam.mrope_angles(fam.text_positions(S, first), inv, shape.sections)
    np.testing.assert_allclose(got_q[0], fam.rotate(jnp.asarray(q[0]), ang),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got_k[0], fam.rotate(jnp.asarray(k[0]), ang),
                               rtol=1e-5, atol=1e-5)
    apart = fam.text_positions(S, first).at[1].add(3)  # the height axis
    moved = fam.rotate(jnp.asarray(q[0]), fam.mrope_angles(
        apart, inv, shape.sections))
    lo, hi = shape.sections[0], shape.sections[0] + shape.sections[1]
    same = np.isclose(moved, got_q[0], atol=1e-5).all(axis=(0, 1))
    half = shape.hd // 2
    assert same[:lo].all() and same[hi:half].all() and not same[lo:hi].any()


def test_the_four_members_parts_add_up_to_the_uncut_layer(shape):
    """Four members hold two experts each of the tiny layer's eight
    (``moe_first_expert`` 0, 2, 4, 6): their partial sums are the uncut
    layer, in the reference and in the program's serving layer."""
    from deepspeed_tpu.moe.sharded_moe import moe_serving_mlp

    whole = tiny(num_experts=8, moe_routed_experts=0)
    full = init_params(whole, seed=4, spread=0.2)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(40, 64)), F32)
    at = lambda tree, i: jax.tree.map(lambda a: a[i], tree)
    mlp = full["layers"]["mlp"]
    member = lambda j: {k: (v if k == "router" else v[:, 2 * j:2 * j + 2])
                        for k, v in mlp.items()}

    @jax.jit
    def reference(x):  # (the uncut layer's experts, each member's) at layer 1
        with fam.ref.HIGHEST():
            ln2 = at(full["layers"]["ln2"], 1)
            route = lambda first, held: fam._route(
                x, ln2, mlp["router"][1], top_k=shape.top_k, first=first,
                held=held, eps=shape.eps)
            h, w, _ = route(0, 8)
            zero = jnp.zeros_like(x)
            uncut = fam._add_experts(zero, h, w, mlp["wg"], mlp["wi"],
                                     mlp["wo"], 1)
            parts = []
            for j in range(4):
                m = member(j)
                parts.append(fam._add_experts(
                    zero, h, route(2 * j, 2)[1], m["wg"], m["wi"], m["wo"],
                    1))
        return uncut, parts, h

    uncut, parts, h = reference(x)
    assert close(sum(parts), uncut)
    assert all(float(jnp.abs(p).max()) > 0 for p in parts)

    @jax.jit
    def program(h):
        outs = []
        for j in range(4):
            cfg = tiny(moe_first_expert=2 * j).config
            out, stats = moe_serving_mlp(cfg, at(member(j), 1), h[None])
            outs.append((out[0], stats["unrouted_tokens"]))
        return outs, moe_serving_mlp(whole.config, at(mlp, 1), h[None])[0][0]

    outs, one = program(h)
    for (out, _), part in zip(outs, parts):
        assert close(out, part, rtol=3e-4)
    assert close(sum(o for o, _ in outs), uncut, rtol=3e-4)
    assert close(one, uncut, rtol=3e-4)  # every expert held: the same router
    # top-2 of 8: most tokens have no expert at a member, none has at all
    assert all(0 < int(n) < 40 for _, n in outs)


def _operands(seed, B=3, S=16, H=4, KV=2, hd=16, Hi=2, Di=8, ps=PS, mp=6,
              L=2):
    """Pools of ``L`` layers of ``B x mp`` pages (and a NULL one), a table
    that shuffles them, and one chunk's queries."""
    rng = np.random.default_rng(seed)
    P1, W = B * mp + 1, index_row_width(
        dataclasses.replace(tiny().config, index_dim=Di))
    norm = lambda *s: jnp.asarray(rng.normal(size=s), F32)
    ki = jnp.pad(norm(L, P1, ps, Di), [(0, 0)] * 3 + [(0, W - Di)])
    table = jnp.asarray(rng.permutation(B * mp).reshape(B, mp), jnp.int32)
    q_idx = jnp.pad(norm(B, S, Hi, Di), [(0, 0)] * 3 + [(0, W - Di)])
    return dict(q=norm(B, S, H, hd), k=norm(L, P1, ps, KV, hd),
                v=norm(L, P1, ps, KV, hd), ki=ki, table=table, q_idx=q_idx,
                w_idx=jnp.asarray(rng.normal(size=(B, S, Hi)), F32))


def _view(pool, layer, table):
    B, mp = table.shape
    return pool[layer][table].reshape(B, mp * pool.shape[2], *pool.shape[3:])


def test_index_scores_at_the_64_wide_key_is_its_dense_twin():
    """The scoring kernel over an index pool whose rows are padded to 128
    lanes with zeros scores the keys' own 64 values."""
    o = _operands(0, Hi=16, Di=64)
    assert o["ki"].shape[-1] == 128 and o["q_idx"].shape[-1] == 128
    cl = jnp.asarray([40, 0, 77], jnp.int32)
    nn = jnp.asarray([16, 9, 1], jnp.int32)

    @jax.jit
    def both(o):
        got = sla.unblocked(sla.index_scores(
            o["q_idx"], o["w_idx"], o["ki"], cl, o["table"], layer=1,
            num_new=nn, interpret=True))
        want = sla.dense_index_scores(
            o["q_idx"][..., :64], o["w_idx"],
            _view(o["ki"], 1, o["table"])[..., :64])
        return got, want

    got, want = map(np.asarray, both(o))
    for b in range(3):
        rows, keys = int(nn[b]), int(cl[b] + nn[b])
        np.testing.assert_allclose(got[b, :rows, :keys], want[b, :rows, :keys],
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("rows", (8, 16))
def test_sparse_paged_attention_is_its_dense_twin(rows):
    """Scores, selection and the walk in interpret mode against the plain
    lines over gathered views: a slot whose chunk crosses ``topk``, one
    inside it, a decoding slot far past it and an idle one."""
    topk = 24
    o = _operands(1, B=4)
    cl = jnp.asarray([20, 0, 77, 50], jnp.int32)
    nn = jnp.asarray([16, 9, 1, 0], jnp.int32)

    @jax.jit
    def both(o):
        kw = dict(num_new=nn, interpret=True)
        scores = sla.index_scores(o["q_idx"], o["w_idx"], o["ki"], cl,
                                  o["table"], layer=1, **kw)
        thr, tie = sla.select_topk(scores, cl, nn, topk, interpret=True)
        got = spa.sparse_paged_attention_kernel(
            o["q"], o["k"], o["v"], scores, thr, tie, cl, o["table"], layer=1,
            rows=rows, **kw)
        qpos = cl[:, None] + jnp.arange(16)[None, :]
        chosen = sla.dense_selection(sla.dense_index_scores(
            o["q_idx"], o["w_idx"], _view(o["ki"], 1, o["table"])), qpos,
            topk)
        want = spa.dense_sparse_paged_attention(
            o["q"], _view(o["k"], 1, o["table"]),
            _view(o["v"], 1, o["table"]), chosen)
        return got, want, chosen

    got, want, chosen = map(np.asarray, both(o))
    for b in range(3):
        n = int(nn[b])
        np.testing.assert_allclose(got[b, :n], want[b, :n], rtol=2e-5,
                                   atol=2e-5)
    assert np.isfinite(got).all() and not got[3].any()  # the idle slot
    # the selection bit: the decoding slot attends 24 of its 78 keys
    assert chosen[2, 0].sum() == topk and chosen[1, 8].sum() == 9


@pytest.fixture(scope="module")
def engine(model, params):
    """The one built engine of the file, with the kernel attention
    registered (interpret mode on the CPU)."""
    with attention_impl("flash"):
        return deepspeed_tpu.init_serving(
            model, serving=dict(max_slots=2, token_budget=16, max_tokens=112,
                                paged=True, page_size=PS, prefix_cache=True),
            params=params, dtype=F32)


def test_the_engine_serves_through_the_kernels_and_says_what_it_held(
        engine, params, shape):
    srv = engine
    assert srv.attention_path == "paged_sparse_kernel"
    assert "index keys" in srv.cache.rows
    assert srv.scheduler.prefix_cache is None  # off, with its reason logged
    assert set(srv._caches) == {"k", "v", INDEX}
    assert srv._caches[INDEX].shape == (3, srv.num_pages + 1, PS, 128)
    d = srv.describe()
    assert d["attention"] == {"full": {"path": "paged_sparse_kernel",
                                       "reasons": []}}
    assert d["indexer"] == dict(heads=2, dim=8, topk=24,
                                pool_bytes=srv._caches[INDEX].nbytes)
    assert d["experts"] == dict(held=2, routed=8, first=0, gate="softmax",
                                dropless=True)
    assert d["expert_path"] == "einsum"
    prompts = [ids_of(70, seed=5), ids_of(21, seed=6)]
    states = [srv.submit(Request(request_id=f"r{i}", prompt=p,
                                 max_new_tokens=5, temperature=0.0,
                                 eos_token_id=-1))
              for i, p in enumerate(prompts)]
    counts = []
    count_keys = srv._count_keys
    srv._count_keys = lambda plan: counts.append(count_keys(plan)) or counts[-1]
    try:
        srv.run_until_idle()
    finally:
        srv._count_keys = count_keys
    for p, st in zip(prompts, states):
        assert st.status.name == "DONE" and len(st.tokens) == 5
        ids = np.concatenate([p, np.asarray(st.tokens, np.int32)])
        want = logits_of(params, ids[:-1], shape, last=5)
        assert list(np.argmax(want, -1)) == list(st.tokens)
    assert srv.step_traces == 1
    snap = srv.metrics.snapshot()
    assert snap["attention_paged_kernel"] == 1.0
    assert snap["attention_paged_kernel_full"] == 1.0
    assert snap["index_pool_bytes"] == srv._caches[INDEX].nbytes
    assert snap["moe_experts_touched"] > 0
    # the step's annotation: the names the accepted readers take
    total = {k: sum(c.get(k, 0) for c in counts) for k in counts[-1]}
    assert {"rows", "context_keys", "index_keys", "attended_sparse",
            "chosen_min", "experts_touched", "experts_held"} <= set(total)
    assert total["rows"] == 70 + 21 + 2 * 4
    # the 70-token prompt passes topk 24: attention sees less than all
    assert 0 < total["attended_sparse"] < total["context_keys"]
    assert snap["attended_keys_sparse"] == total["attended_sparse"]
    assert "attended_full" not in total
