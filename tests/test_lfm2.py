"""LFM2 on the serving path, float32 on the CPU at a tiny size: gated short
convolutions (the last two rows of the gated input a slot, no state matrix)
three to one beside grouped-query attention of 64-wide heads whose pool holds
two KV heads a 128-lane row, under two leading dense layers and ALL the
experts of a sigmoid router with a selection bias, the head tied, against
the benchmark's plain reference (benchmarks/families/lfm2_moe.py)."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.config import DeepSpeedConfigError
from deepspeed_tpu.models import lfm2
from deepspeed_tpu.models.decoding import ChunkRows
from deepspeed_tpu.models.ling import carried_conv
from deepspeed_tpu.models.mixers import layer_plan, walk_runs
from deepspeed_tpu.models.transformer import TransformerConfig
from deepspeed_tpu.ops.attention import attention_impl
from deepspeed_tpu.ops.pallas import paged_attention as pa
from deepspeed_tpu.serving import Request

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from benchmarks import reference as ref  # noqa: E402
from benchmarks.families import lfm2_moe as fam  # noqa: E402
from slot_program import (drive, ids_of, init_params,  # noqa: E402
                          reference_logits, schedule)

F32 = jnp.float32
logits_of = reference_logits(fam)
# float32 against float32 on logits whose spread is about 5: what is left is
# the order of the sums
TOL = 2e-4
PS, W, SLOTS = 16, 16, 3
SERVING = dict(max_slots=SLOTS, token_budget=W, max_tokens=240, paged=True,
               page_size=PS, prefix_cache=False)
ARENA = dict(slots=SLOTS, width=W, pages_per_slot=16, page_size=PS)
IDS = list(range(8))  # c c A c c c A c of the tiny preset: two dense leads
TYPES = ["conv", "conv", "full_attention", "conv", "conv", "conv",
         "full_attention", "conv"]
CONFIG = dict(
    family="lfm2_moe", hidden_size=128, num_hidden_layers=8,
    num_attention_heads=4, num_key_value_heads=2, head_dim=64,
    intermediate_size=96, moe_intermediate_size=32, vocab_size=512,
    num_experts=8, num_experts_per_tok=2, num_dense_layers=2, conv_L_cache=3,
    conv_bias=False, norm_eps=1e-5, rope_theta=1000000, norm_topk_prob=True,
    use_expert_bias=True, routed_scaling_factor=1, layer_types=TYPES,
    layer_ids=IDS, published=dict(num_hidden_layers=8, num_experts=8))


def tiny(**over):
    # weights five times the preset's spread, so that the mixers weigh as
    # much as the residual stream and a fault in one shows in the logits
    return lfm2("lfm2-tiny", layer_ids=IDS, initializer_range=0.1, **over)


def biased(params, seed=4):
    """The selection bias in U(-0.5, 0.5): it changes most choices."""
    mlp = params["layers"]["mlp"]
    bias = jax.random.uniform(jax.random.PRNGKey(seed), mlp["sel_bias"].shape,
                              F32, -0.5, 0.5)
    return {**params, "layers": {**params["layers"],
                                 "mlp": {**mlp, "sel_bias": bias}}}


@pytest.fixture(scope="module")
def model():
    return tiny()


@pytest.fixture(scope="module")
def params(model):
    return biased(init_params(model))  # norm scales off one too


@pytest.fixture(scope="module")
def shape():
    return fam.shape_of(CONFIG)


def test_the_plan_names_every_layers_two_halves(model, params):
    """Published layers 2 and 6 are attention over their own K / V pool, the
    others convolutions over one slot leaf; layers 0 and 1 are dense at
    their own width, the rest routed over ALL the experts; each half is read
    at its own index inside its kind's stack; the head is the embedding."""
    cfg = model.config
    plan = layer_plan(cfg)
    assert [l.mixer for l in plan] == [
        "conv", "conv", "full", "conv", "conv", "conv", "full", "conv"]
    assert [l.mlp for l in plan] == ["dense"] * 2 + ["routed"] * 6
    assert [l.mixer_at for l in plan] == [0, 1, 0, 2, 3, 4, 1, 5]
    assert [l.pool_at for l in plan] == [0, 1, 0, 2, 3, 4, 1, 5]
    assert [l.mlp_at for l in plan] == [0, 1, 0, 1, 2, 3, 4, 5]
    assert [(r.stack, r.mlp_stack, r.trips) for r in walk_runs(cfg)] == [
        ("conv_layers", "lead_layers", 2), ("attn_layers", "layers", 1),
        ("conv_layers", "layers", 3), ("attn_layers", "layers", 1),
        ("conv_layers", "layers", 1)]
    assert cfg.has_state and cfg.paged_layers == 2 and not cfg.is_latent
    assert (cfg.qk_norm, cfg.tie_embeddings, cfg.conv_kernel) == (True, True, 3)
    assert (cfg.moe_gate, cfg.moe_groups, cfg.moe_norm_eps) == (
        "sigmoid_groups", 1, 1e-6)
    assert cfg.routed_experts == cfg.num_experts == 8 and cfg.moe_dropless
    assert "lm_head" not in params
    got = sum(a.size for a in jax.tree.leaves(params))
    assert model.num_params() == got
    # the published model: 24 layers, 18 of them convolutions, 2 dense
    big = lfm2("lfm2-8b-a1b").config
    assert (big.kind_count("conv"), big.kind_count("full")) == (18, 6)
    assert [i for i, k in enumerate(big.mixer_types) if k == "full"] == [
        2, 6, 10, 14, 18, 21]
    assert (big.lead_dense_layers, big.lead_dense_ffn, big.ffn) == (
        2, 7168, 1792)
    assert (big.num_heads, big.kv_heads, big.hd) == (32, 8, 64)
    # the pool holds two KV heads a 128-lane row: a page's bytes in order
    from deepspeed_tpu.models.mixers import family

    pools = jax.eval_shape(lambda: family(big).init_pools(
        big, 8, 64, 4, jnp.bfloat16))
    assert pools["k"].shape == (6, 9, 64, 4, 128)
    assert pools["conv"].shape == (18, 4, 2, 2048)


@pytest.mark.parametrize("kernels", [False, True], ids=["dense", "kernels"])
def test_slots_at_different_frontiers_match_the_reference(model, params,
                                                          shape, kernels):
    """Prefill in chunks through the one slot step with packed rows: three
    slots at different frontiers and chunk sizes (7, 5 and 1 rows: slot 2
    feeds ONE row a step from its first token, so both carried rows are old
    from its third step on), then one-row steps (decode) through the pages
    and the carried rows, then slot 1 taken by a SECOND request from position
    0 (its carried rows start from zero, whatever the first left). Logits of
    every row against the reference's full forward, with the kernel
    (interpret mode, two KV heads a lane row) and without."""
    seqs = {0: ids_of(37, 1), 1: ids_of(21, 2), 2: ids_of(9, 3)}
    feeds = schedule(seqs, {0: 7, 1: 5, 2: 1})
    more = {s: ids_of(4, 10 + s) for s in seqs}
    for j in range(4):  # decode rows, all three slots in a step
        feeds.append({s: (more[s][j:j + 1], len(seqs[s]) + j) for s in seqs})
    again = ids_of(19, 7)
    feeds += schedule({1: again}, {1: 6})
    got, _ = drive(model, params, feeds, kernels=kernels, **ARENA)
    for s in seqs:
        ids = np.concatenate([seqs[s], more[s]])
        want = np.asarray(logits_of(params, ids, shape))
        have = np.concatenate(got[s])[:len(ids)]
        assert np.abs(have - want).max() < TOL, (s, np.abs(have - want).max())
    want = np.asarray(logits_of(params, again, shape))
    have = np.concatenate(got[1])[len(seqs[1]) + 4:]
    assert np.abs(have - want).max() < TOL


def test_engine_serves_what_the_reference_predicts(model, params, shape):
    """Through init_serving (scheduler, paged arena, packed rows, overlapped
    step order): four requests over three slots, so one slot is reused; every
    served token is the reference's argmax for its context, or within TOL of
    it; the step's counters and ``describe()`` say what a slot keeps."""
    srv = deepspeed_tpu.init_serving(model, serving=SERVING, params=params,
                                     dtype=F32)
    assert srv.row_layout == "packed" and srv.step_order == "overlapped"
    assert srv.cache.rows == ("slot state",)
    assert set(srv._caches) == {"k", "v", "conv"}
    assert srv._caches["k"].shape[2:] == (PS, 1, 128)
    prompts = [ids_of(n, 20 + i) for i, n in enumerate((37, 5, 50, 21))]
    states = [srv.submit(Request(
        request_id=f"r{i}", prompt=p, max_new_tokens=6, temperature=0.0,
        eos_token_id=-1)) for i, p in enumerate(prompts)]
    srv.run_until_idle()
    assert srv.attention_paths == {"conv": "short_conv", "full": "dense"}
    assert srv.attention_path == "dense"  # the attention layers', not the
    # last layer's (a convolution)
    for p, st in zip(prompts, states):
        assert len(st.tokens) == 6
        ids = np.concatenate([p, np.asarray(st.tokens, np.int32)])
        logits = logits_of(params, ids[:-1], shape, last=6)
        assert ref.served_token_gaps(logits, st.tokens).max() < TOL
    snap = srv.metrics.snapshot()
    assert snap["state_resets"] == 4
    d = srv.describe()
    assert d["state_leaves"] == {"conv": 6 * SLOTS * 2 * 128 * 4}
    assert d["state_bytes_per_slot"] == 6 * 2 * 128 * 4
    assert snap["state_bytes"] == sum(d["state_leaves"].values())
    assert d["kv_heads_per_pool_row"] == 2 and d["paged_layers"] == 2
    assert d["experts"] == dict(held=8, routed=8, first=0,
                                gate="sigmoid_groups", dropless=True)
    assert snap["attended_keys_full"] > snap["fetched_keys_full"] > 0
    assert snap["moe_experts_touched"] > 0


def test_the_step_says_what_each_kind_did(model, params):
    """The counts a step's annotation carries for the two kinds, from the
    plan by host arithmetic: the convolution layers' real rows, live slots,
    resets and the slots that decode; the attention layers' attended and
    fetched keys and the slots on the small tile of the PAIRED stack (a lane
    pair's group is both heads' queries); an engine built on the kernels
    names both paths."""
    from deepspeed_tpu.serving.engine import _KIND_COUNTS

    cl, nn = np.array([0, 40, 7, 0, 3]), np.array([5, 1, 0, 1, 2])
    assert _KIND_COUNTS["conv"](None, cl, nn) == {
        "conv_rows": 9, "conv_state_slots": 4, "state_resets": 2,
        "decode_slots": 2}
    with attention_impl("flash"):
        srv = deepspeed_tpu.init_serving(model, serving=SERVING,
                                         params=params, dtype=F32)
    d = srv.describe()["attention"]
    assert d["conv"] == {"path": "short_conv", "reasons": []}
    assert d["full"] == {"path": "paged_kernel", "reasons": []}
    assert srv.attention_path == "paged_kernel"
    st = srv.submit(Request(request_id="r", prompt=ids_of(21, 5),
                            max_new_tokens=3, temperature=0.0,
                            eos_token_id=-1))
    srv.run_until_idle()
    assert len(st.tokens) == 3
    plan = type("P", (), dict(start_pos=np.array([16, 0, 9]),
                              num_new=np.array([5, 0, 1])))
    counts = srv._count_keys(plan)
    # 5 rows at positions 16..20 see 17 + ... + 21 keys in two 16-token pages
    # and a row at position 9 sees 10 in one; the paired stack is [16 x 4]
    # rows a KV pair, so the slot with one row (4 stacked rows) takes the
    # small tile and the chunk of 5 (20 stacked rows) does too
    assert counts == {"rows": 6, "attended_full": 105, "fetched_full": 48,
                      "small_tile_slots_full": 2, "conv_rows": 6,
                      "conv_state_slots": 2, "state_resets": 0,
                      "decode_slots": 1,
                      **{k: counts[k] for k in counts if "experts" in k}}


def _token_by_token(taps, u, prev):
    """c_t = sum_j taps[j] u[t - 2 + j] over ``prev`` [2, C] then ``u``."""
    ext = np.concatenate([prev, u])
    return np.stack([sum(taps[j] * ext[t + j] for j in range(3))
                     for t in range(len(u))])


@pytest.mark.parametrize("budget", [None, 192], ids=["by-slot", "packed"])
def test_the_carried_convolution_is_the_token_by_token_form(budget):
    """``carried_conv`` at 3 taps against the token-by-token form over a
    batch whose slots feed 1 / 2 / 17 / 128 rows (and none), from zero
    carried rows (position 0) and from non-zero ones; then a SECOND step in
    which every slot feeds one row more (a chunk of one row after a chunk of
    one row: both carried rows are old, one the step before last's). A slot
    with no real row keeps its rows bit for bit, the stack's other layer is
    untouched, and a request that begins in a slot another just left starts
    from zero."""
    C, S, layer = 8, 128, 1
    cfg = TransformerConfig(num_layers=1, conv_kernel=3)
    key = jax.random.split(jax.random.PRNGKey(0), 4)
    taps = jax.random.normal(key[0], (3, C), F32)
    cl = jnp.array([0, 9, 4, 0, 300, 7, 0])
    nn = jnp.array([128, 1, 0, 17, 2, 1, 1])
    B = len(cl)
    stack = jax.random.normal(key[1], (2, B, 2, C), F32)
    u = jax.random.normal(key[2], (B, S, C), F32)

    def step(stack, u, cl, nn):
        rows = ChunkRows(B, S, cl, nn, budget=budget)
        pre = rows.pack(u) if budget else u
        y, after = carried_conv(cfg, taps, pre, rows, stack, layer, cl, nn)
        return (rows.unpack(y) if budget else y), after

    y, after = jax.jit(step)(stack, u, cl, nn)
    assert bool((after[0] == stack[0]).all())
    held = np.asarray(stack[layer])
    for b in range(B):
        n = int(nn[b])
        if n == 0:  # bit for bit
            assert bool((after[layer, b] == stack[layer, b]).all())
            continue
        prev = np.zeros((2, C), np.float32) if int(cl[b]) == 0 else held[b]
        want = _token_by_token(np.asarray(taps), np.asarray(u[b, :n]), prev)
        assert np.abs(np.asarray(y[b, :n]) - want).max() < 1e-5, b
        carried = np.concatenate([prev, np.asarray(u[b, :n])])[-2:]
        assert np.abs(np.asarray(after[layer, b]) - carried).max() == 0, b
    # the next step: one row more a slot (slot 2 still idle; slot 3's request
    # has left and ANOTHER begins there at position 0)
    cl2 = jnp.where(jnp.arange(B) == 3, 0, cl + nn)
    nn2 = jnp.where(nn > 0, 1, 0)
    u2 = jax.random.normal(key[3], (B, S, C), F32)
    y2, after2 = jax.jit(step)(after, u2, cl2, nn2)
    mid = np.asarray(after[layer])
    for b in range(B):
        if int(nn2[b]) == 0:
            assert bool((after2[layer, b] == after[layer, b]).all())
            continue
        prev = np.zeros((2, C), np.float32) if int(cl2[b]) == 0 else mid[b]
        want = _token_by_token(np.asarray(taps), np.asarray(u2[b, :1]), prev)
        assert np.abs(np.asarray(y2[b, :1]) - want).max() < 1e-5, b
        assert np.abs(np.asarray(after2[layer, b])
                      - np.concatenate([prev[1:], u2[b, :1]])).max() == 0, b


def _pages(B=3, S=16, H=8, KV=4, hd=64, ps=16, mp=6, seed=2):
    k_ = jax.random.split(jax.random.PRNGKey(seed), 3)
    pages = B * mp
    q = jax.random.normal(k_[0], (B, S, H, hd), F32)
    k = jax.random.normal(k_[1], (2, pages + 1, ps, KV, hd), F32)
    v = jax.random.normal(k_[2], (2, pages + 1, ps, KV, hd), F32)
    table = jnp.arange(pages, dtype=jnp.int32).reshape(B, mp)
    return q, k, v, table


def _dense_lines(q, k, v, cl, table, layer):
    """Attention over a slot's gathered pages by plain lines: row i of slot
    b sees keys 0 .. cl[b] + i; query head h reads KV head h // G."""
    B, S, H, hd = q.shape
    KV = k.shape[3]
    kk = k[layer][table].reshape(B, -1, KV, hd).repeat(H // KV, axis=2)
    vv = v[layer][table].reshape(B, -1, KV, hd).repeat(H // KV, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) * hd ** -0.5
    seen = jnp.arange(kk.shape[1])[None, None, None, :] <= (
        cl[:, None, None, None] + jnp.arange(S)[None, None, :, None])
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vv)


@pytest.mark.parametrize("held", ["paired", "a-head-a-row"])
def test_the_kernel_attends_two_heads_a_lane_row_as_they_lie(held):
    """``paged_attention`` (interpret mode) at [4 KV, 64] pages against the
    dense lines: slots with a whole chunk from position 0, one row after 40
    and a ragged count after 21; the pool held two KV heads a 128-lane row
    (the family's) or a head a row (read as pairs: free here, refused on the
    chip). A query head that read the OTHER head of its lane pair
    (``kv_pair_swapped``) fails the comparison."""
    q, k, v, table = _pages()
    cl, nn = jnp.array([0, 40, 21]), jnp.array([16, 1, 5])
    want = _dense_lines(q, k, v, cl, table, 1)
    pools = (k, v) if held != "paired" else tuple(
        a.reshape(*a.shape[:3], 2, 128) for a in (k, v))
    got, why = pa.paged_attention(q, *pools, cl, table, layer=1, num_new=nn,
                                  interpret=True)
    assert got is not None, why
    swap = jnp.arange(4) ^ 1
    wrong = _dense_lines(q, k[:, :, :, swap], v[:, :, :, swap], cl, table, 1)
    for b, n in enumerate(np.asarray(nn)):
        assert float(jnp.abs(got[b, :n] - want[b, :n]).max()) < 1e-5, b
        assert float(jnp.abs(got[b, :n] - wrong[b, :n]).max()) > 0.1, b
    # a lone 64-wide KV head does not pair: one head a row, as ever
    assert pa.lane_pairs(64, 1) == 1 and pa.lane_pairs(128, 8) == 1
    assert pa.kernel_heads(32, 8, 64) == (8, 4, 128)
    assert pa.kernel_heads(32, 8, 128) == (4, 8, 128)


def test_the_bias_chooses_and_never_weighs(model, params, shape):
    """One routed layer under a selection bias in U(-0.5, 0.5): the program's
    layer is the reference's; the bias changes most tokens' choice (against
    the same layer without it) and the chosen weights stay the unbiased
    sigmoids over their sum + 1e-6."""
    from deepspeed_tpu.moe.sharded_moe import (moe_serving_mlp,
                                               sigmoid_group_gate)

    cfg, j = model.config, 2
    M = params["layers"]
    x = jax.random.normal(jax.random.PRNGKey(9), (48, 128), F32)
    load = lambda tree: jax.tree.map(lambda a: a.astype(F32), tree)
    with ref.HIGHEST():
        ln2 = load(ref.layer(M["ln2"], j))
        want, _ = fam.routed_block(x, ln2, M["mlp"], j, shape, load)
        normed = ref.rmsnorm(x, ln2, 1e-5)
        out, _ = moe_serving_mlp(cfg, ref.layer(M["mlp"], j), normed[None])
        assert float(jnp.abs(out[0] - (want - x)).max()) < 1e-5
        logits = normed @ M["mlp"]["router"][j]
        bias = M["mlp"]["sel_bias"][j]
        idx, w = sigmoid_group_gate(logits, bias, 2, 1, 1, 1.0, 1e-6)
        idx0, _ = sigmoid_group_gate(logits, 0 * bias, 2, 1, 1, 1.0, 1e-6)
    changed = (np.sort(idx, 1) != np.sort(idx0, 1)).any(1).mean()
    assert changed > 0.5
    s = jnp.take_along_axis(jax.nn.sigmoid(logits), idx, axis=1)
    assert float(jnp.abs(w - s / (s.sum(1, keepdims=True) + 1e-6)).max()) < 1e-6
    assert float(jnp.abs(w.sum(1) - 1).max()) < 1e-5
    # every other router's epsilon is the one it had
    _, w0 = sigmoid_group_gate(logits, bias, 2, 1, 1, 1.0)
    assert float(jnp.abs(w0 - s / s.sum(1, keepdims=True)).max()) < 1e-6


def test_the_members_shares_add_up_to_the_whole_layer(model, params, shape):
    """The share test: one routed layer of 8 experts (32 in the published
    model, all held in the cell) cut over 4 members of 2 each: the layer is
    told which experts it holds (first expert 0, 2, 4, 6) and the partial
    sums add up to the whole layer's output, the program's and the
    reference's."""
    from deepspeed_tpu.moe.sharded_moe import moe_serving_mlp

    j = 3
    M = params["layers"]
    x = jax.random.normal(jax.random.PRNGKey(11), (24, 128), F32)
    load = lambda tree: jax.tree.map(lambda a: a.astype(F32), tree)
    with ref.HIGHEST():
        ln2 = load(ref.layer(M["ln2"], j))
        want, _ = fam.routed_block(x, ln2, M["mlp"], j, shape, load)
        normed = ref.rmsnorm(x, ln2, 1e-5)
        whole, _ = moe_serving_mlp(model.config, ref.layer(M["mlp"], j),
                                   normed[None])
        total = 0.0
        for member in range(4):
            cfg = tiny(num_experts=2, moe_routed_experts=8,
                       moe_first_expert=2 * member).config
            bank = {k: (a[j, 2 * member:2 * member + 2]
                        if k in ("wi", "wg", "wo") else a[j])
                    for k, a in M["mlp"].items()}
            out, _ = moe_serving_mlp(cfg, bank, normed[None])
            total = total + out[0]
            held = {k: a[:, 2 * member:2 * member + 2]
                    for k, a in M["mlp"].items() if k in ("wi", "wg", "wo")}
            part, _ = fam.routed_block(x, ln2, M["mlp"], j, shape, load,
                                       first=2 * member, bank=held)
            assert float(jnp.abs(out[0] - (part - x)).max()) < 1e-5, member
    assert float(jnp.abs(total - (want - x)).max()) < 1e-5
    assert float(jnp.abs(total - whole[0]).max()) < 1e-5


@pytest.mark.parametrize("fault", fam.FAULTS)
def test_every_fault_moves_the_reference(params, shape, fault):
    """Each name in FAULTS changes the reference's logits by far more than
    the tolerance the engine is held to (300 tokens: the chunk faults bite
    at row 256)."""
    ids = ids_of(300, 31)
    sound = np.asarray(logits_of(params, ids, shape))
    broken = np.asarray(fam.logits(
        ids=ids, shape=shape, **fam.faulted(params, fault, shape)))
    assert np.abs(broken - sound).max() > 20 * TOL, fault


def test_what_cannot_be_built_is_refused_in_words(model, params):
    # a full layer has a stack of its own beside conv or gdn layers alone
    with pytest.raises(ValueError, match="share the module"):
        TransformerConfig(num_layers=2, mixer_types=("conv", "kda"),
                          mixer_layer_ids=(0, 1), mixer_depth=2)
    with pytest.raises(ValueError, match="share the module"):
        TransformerConfig(num_layers=2, mixer_types=("conv", "gdn"),
                          mixer_layer_ids=(0, 1), mixer_depth=2,
                          gdn_value_heads=2, gdn_key_heads=1, gdn_head_dim=8)
    with pytest.raises(ValueError, match="at least 2 taps"):
        TransformerConfig(num_layers=1, mixer_types=("conv",),
                          mixer_layer_ids=(0,), mixer_depth=1, conv_kernel=1)
    with pytest.raises(ValueError, match="published order"):
        lfm2("lfm2-tiny", layer_ids=[3, 1])
    with pytest.raises(DeepSpeedConfigError, match="through init_serving"):
        model.apply(params, jnp.zeros((1, 8), jnp.int32))
    serve = lambda **over: deepspeed_tpu.init_serving(
        model, serving=dict(SERVING, **over), params=params, dtype=F32)
    with pytest.raises(DeepSpeedConfigError, match="state layers"):
        serve(spec=dict(enabled=True, max_draft=2))
    with pytest.raises(DeepSpeedConfigError, match="state layers"):
        serve(host_pages=8)
    with pytest.raises(DeepSpeedConfigError, match="paged"):
        serve(paged=False)
    with pytest.raises(DeepSpeedConfigError, match="int8"):
        serve(kv_cache_dtype="int8")
    srv = serve(prefix_cache=True)  # off, with the reason logged: a prefix
    assert srv.scheduler.prefix_cache is None  # hit has no rows to resume
