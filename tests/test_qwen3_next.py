"""Qwen3-Next on the serving path, float32 on the CPU at a tiny size: Gated
DeltaNet layers (a scalar, unbounded decay a value head; a state and the
convolution's last rows a slot, no page) three to one beside gated
grouped-query attention over K / V pages, under one member's share of a
softmax-routed layer with a gated shared expert, against the benchmark's
plain reference (benchmarks/families/qwen3_next.py)."""

import functools
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.config import DeepSpeedConfigError
from deepspeed_tpu.models import qwen3_next
from deepspeed_tpu.models.decoding import ChunkRows
from deepspeed_tpu.models.mixers import layer_plan, walk_runs
from deepspeed_tpu.models.transformer import TransformerConfig
from deepspeed_tpu.ops.attention import attention_impl
from deepspeed_tpu.ops.pallas import gated_delta as gd
from deepspeed_tpu.serving import Request

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from benchmarks import reference as ref  # noqa: E402
from benchmarks.families import qwen3_next as fam  # noqa: E402
from slot_program import (drive, ids_of, init_params,  # noqa: E402
                          reference_logits, schedule)

F32 = jnp.float32
logits_of = reference_logits(fam)
# float32 against float32 on logits whose spread is about 1: what is left is
# the order of the sums (a chunk's running log-decay reaches hundreds, so a
# decay between two rows carries 1e-5 of relative rounding)
TOL = 2e-4
PS, W, SLOTS = 16, 16, 3
SERVING = dict(max_slots=SLOTS, token_budget=W, max_tokens=240, paged=True,
               page_size=PS, prefix_cache=False)
ARENA = dict(slots=SLOTS, width=W, pages_per_slot=16, page_size=PS)
IDS = list(range(8))  # two whole periods G G G A of the tiny preset
HELD = dict(num_experts=2, moe_routed_experts=8)
CONFIG = dict(
    family="qwen3_next", hidden_size=64, num_hidden_layers=8,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=16,
    linear_value_head_dim=16, linear_conv_kernel_dim=4,
    full_attention_interval=4, partial_rotary_factor=0.25,
    moe_intermediate_size=32, shared_expert_intermediate_size=32,
    vocab_size=512, num_experts=2, num_experts_per_tok=2,
    decoder_sparse_step=1, mlp_only_layers=[], norm_topk_prob=True,
    rms_norm_eps=1e-6, rope_theta=10000000, layer_ids=IDS,
    published=dict(num_hidden_layers=8, num_experts=8))


def tiny(**over):
    # weights five times the preset's spread, so that the mixers weigh as
    # much as the residual stream and a fault in one shows in the logits
    return qwen3_next("qwen3next-tiny", layer_ids=IDS, initializer_range=0.1,
                      **{**HELD, **over})


@pytest.fixture(scope="module")
def model():
    return tiny()


@pytest.fixture(scope="module")
def params(model):
    # the model's own draw: A in U(0, 16), so a head's g reaches -20 and
    # below a row; norm scales off one
    return init_params(model)


@pytest.fixture(scope="module")
def shape():
    return fam.shape_of(CONFIG)


def test_the_plan_names_every_layers_two_halves(model, params):
    """Every fourth published layer is gated attention over its own K / V
    pool, the others Gated DeltaNet over slot leaves; every layer is routed,
    and each half is read at its own index inside its kind's stack."""
    cfg = model.config
    plan = layer_plan(cfg)
    assert [l.mixer for l in plan] == ["gdn"] * 3 + ["full"] + ["gdn"] * 3 + [
        "full"]
    assert {l.mlp for l in plan} == {"routed"}
    assert [l.mixer_at for l in plan] == [0, 1, 2, 0, 3, 4, 5, 1]
    assert [l.pool_at for l in plan] == [0, 1, 2, 0, 3, 4, 5, 1]
    assert [l.mlp_at for l in plan] == list(range(8))
    assert [(r.stack, r.trips) for r in walk_runs(cfg)] == [
        ("gdn_layers", 3), ("attn_layers", 1)] * 2
    assert cfg.has_state and cfg.paged_layers == 2 and not cfg.is_latent
    assert (cfg.rotary_dim, cfg.attn_out_gate, cfg.qk_norm) == (4, True, True)
    assert cfg.moe_dropless and cfg.moe_gate == "softmax"
    got = sum(a.size for a in jax.tree.leaves(params))
    assert model.num_params() == got
    # the published model: 48 layers, 36 of them Gated DeltaNet
    big = qwen3_next("qwen3-next-80b-a3b").config
    assert (big.kind_count("gdn"), big.kind_count("full")) == (36, 12)
    assert big.mixer_types[:4] == ("gdn", "gdn", "gdn", "full")
    # the draw reaches what the bounded split cannot take
    a = params["gdn_layers"]["attn"]
    g = -jnp.exp(a["A_log"]) * jax.nn.softplus(a["dt_bias"] + 2.0)
    assert float(g.min()) < -20


@pytest.mark.parametrize("kernels", [False, True], ids=["dense", "kernels"])
def test_slots_at_different_frontiers_match_the_reference(model, params,
                                                          shape, kernels):
    """Prefill in chunks through the one slot step with packed rows: three
    slots at different frontiers and chunk sizes (7, 5 and 3 rows: every
    chunk boundary lies inside the convolution's 3-row reach of the next
    chunk's first rows), then one-row steps (decode) through the pages and
    the state, then slot 1 taken by a SECOND request from position 0 (its
    state and convolution rows start from zero, whatever the first left).
    Logits of every row against the reference's full forward, with the
    kernels (interpret mode) and without."""
    seqs = {0: ids_of(37, 1), 1: ids_of(21, 2), 2: ids_of(11, 3)}
    feeds = schedule(seqs, {0: 7, 1: 5, 2: 3})
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
    assert set(srv._caches) == {"k", "v", "state", "conv"}
    prompts = [ids_of(n, 20 + i) for i, n in enumerate((37, 5, 50, 21))]
    states = [srv.submit(Request(
        request_id=f"r{i}", prompt=p, max_new_tokens=6, temperature=0.0,
        eos_token_id=-1)) for i, p in enumerate(prompts)]
    srv.run_until_idle()
    assert srv.attention_paths == {"gdn": "dense", "full": "dense"}
    for p, st in zip(prompts, states):
        assert len(st.tokens) == 6
        ids = np.concatenate([p, np.asarray(st.tokens, np.int32)])
        logits = logits_of(params, ids[:-1], shape, last=6)
        assert ref.served_token_gaps(logits, st.tokens).max() < TOL
    snap = srv.metrics.snapshot()
    assert snap["state_resets"] == 4
    d = srv.describe()
    assert set(d["state_leaves"]) == {"state", "conv"}
    assert d["state_leaves"] == {
        "state": 6 * SLOTS * 4 * 16 * 16 * 4,
        "conv": 6 * SLOTS * 3 * (2 * 32 + 64) * 4}
    assert snap["state_bytes"] == sum(d["state_leaves"].values())
    assert d["attention"]["gdn"]["reasons"] and d["paged_layers"] == 2
    assert d["experts"] == dict(held=2, routed=8, first=0, gate="softmax",
                                dropless=True)
    assert snap["attended_keys_full"] > snap["fetched_keys_full"] > 0
    assert snap["moe_experts_touched"] > 0


def test_the_step_says_what_each_kind_did(model, params):
    """The counts a step's annotation carries for the two kinds, from the
    plan by host arithmetic: the Gated DeltaNet layers' real rows, live
    states and resets; the gated-attention layers' attended and fetched
    keys; an engine built on the kernels names both paths."""
    from deepspeed_tpu.serving.engine import _KIND_COUNTS

    cl, nn = np.array([0, 40, 7, 0, 3]), np.array([5, 1, 0, 1, 2])
    assert _KIND_COUNTS["gdn"](None, cl, nn) == {
        "gdn_rows": 9, "gdn_state_slots": 4, "state_resets": 2}
    with attention_impl("flash"):
        srv = deepspeed_tpu.init_serving(model, serving=SERVING,
                                         params=params, dtype=F32)
    d = srv.describe()["attention"]
    assert d["gdn"] == {"path": "gdn_kernel", "reasons": []}
    assert d["full"] == {"path": "paged_kernel", "reasons": []}
    st = srv.submit(Request(request_id="r", prompt=ids_of(21, 5),
                            max_new_tokens=3, temperature=0.0,
                            eos_token_id=-1))
    srv.run_until_idle()
    assert len(st.tokens) == 3
    plan = type("P", (), dict(start_pos=np.array([16, 0, 0]),
                              num_new=np.array([5, 0, 0])))
    counts = srv._count_keys(plan)
    # 5 rows at positions 16..20 see 17 + ... + 21 keys in two 16-token pages
    # (a 16-row chunk of 2 query heads a KV head is the small tile's size)
    assert counts == {"rows": 5, "attended_full": 95, "fetched_full": 32,
                      "small_tile_slots_full": 0, "gdn_rows": 5, "gdn_state_slots": 1, "state_resets": 0,
                      **{k: counts[k] for k in counts if "experts" in k}}


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5))
def _operands(B, S, Hk, r, hd, layers=2):
    k_ = jax.random.split(jax.random.PRNGKey(3), 7)
    nrm = lambda key, *s: jax.random.normal(key, s, F32)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    Hv = Hk * r
    q, k = unit(nrm(k_[0], B, S, Hk, hd)), unit(nrm(k_[1], B, S, Hk, hd))
    v = nrm(k_[2], B, S, Hv, hd)
    # the release's own draw: A in U(0, 16) a value head
    A = jax.random.uniform(k_[3], (Hv,), F32, 0.0, 16.0)
    g = -A * jax.nn.softplus(nrm(k_[4], B, S, Hv))
    beta = jax.nn.sigmoid(nrm(k_[5], B, S, Hv))
    return q, k, v, g, beta, nrm(k_[6], layers, B, Hv, hd, hd)


@functools.cache
def _calls():
    def rule(*rows):
        with jax.default_matmul_precision("highest"):
            return fam._delta_rule(*rows, 0)

    return (jax.jit(gd.gated_delta_attention,
                    static_argnames=("scale", "interpret")),
            jax.jit(gd.dense_gated_delta, static_argnames=("scale",)),
            jax.jit(rule))


def _check_against_the_recurrence(q, k, v, g, beta, stack, cl, nn, layer):
    """The kernel's call and ``dense_gated_delta`` against the reference's
    token-by-token recurrence, slot by slot; a slot with no real row keeps
    its state bit for bit and the stack's other layers are untouched."""
    B, S, Hk, hd = q.shape
    Hv = v.shape[2]
    r, scale = Hv // Hk, hd ** -0.5
    kernel, dense, rule = _calls()
    whole, first, after = kernel(q, k, v, g, beta, stack, cl, nn, layer=layer,
                                 scale=scale, interpret=True)
    o = ChunkRows(B, S, cl).pack_split(whole, first, nn > 1).reshape(v.shape)
    o2, after2 = dense(q, k, v, g, beta, stack[layer], cl, nn, scale=scale)
    for other in range(stack.shape[0]):
        if other != layer:
            assert bool((after[other] == stack[other]).all())
    for b in range(B):
        n = int(nn[b])
        if n == 0:  # no real row: bit for bit
            assert bool((after[layer, b] == stack[layer, b]).all())
            assert bool((after2[b] == stack[layer, b]).all())
            continue
        s0 = jnp.zeros((Hv, hd, hd)) if int(cl[b]) == 0 else stack[layer, b]
        o3, after3 = rule(jnp.repeat(q[b, :n], r, 1) * scale,
                          jnp.repeat(k[b, :n], r, 1), v[b, :n], g[b, :n],
                          beta[b, :n], s0)
        for have in (o[b, :n], o2[b, :n]):
            assert float(jnp.abs(have - o3).max()) < TOL, (b, n)
        for have in (after[layer, b], after2[b]):
            assert float(jnp.abs(have - after3).max()) < TOL, (b, n)
    return o, whole, first


@pytest.mark.parametrize("case", ["drawn", "g-30", "no-decay", "two-programs",
                                  "sub-chunks"])
def test_gated_delta_kernel_is_its_twin_is_the_recurrence(case, monkeypatch):
    """``gated_delta_attention`` (interpret mode) and ``dense_gated_delta``
    against the token-by-token recurrence at chunks of 1 / 17 / 128 / 256
    rows (and none), from a zero state (position 0) and a non-zero one; with
    the decays drawn as the release draws them (A in U(0, 16): a row's g
    reaches -20), with 16 CONSECUTIVE ROWS OF g = -30 (the running sum
    passes -480 inside one sub-block: the case a split bounded at 80 cannot
    take), with no decay; with two programs a slot (4 key heads, 2 a
    program: the budget is the test's); and at 512 rows, which run as two
    sub-chunks of 256 with the state carried between them."""
    B, S, Hk, r, hd = 6, 256, 2, 2, 16
    cl = jnp.array([0, 9, 4, 0, 300, 7])
    nn = jnp.array([256, 1, 0, 17, 128, 256])
    if case == "two-programs":
        Hk, hd, S = 4, 64, 64
        nn = jnp.array([64, 1, 0, 17, 33, 64])
        monkeypatch.setattr(gd, "BLOCK_VMEM_BYTES",
                            2 * gd._key_head_bytes(r, hd, hd, S, 4))
        assert gd.key_heads_per_program(Hk, r, hd, hd, S, 4) == 2
    elif case == "sub-chunks":
        B, S = 3, 512
        cl, nn = jnp.array([0, 9, 40]), jnp.array([512, 1, 300])
    else:
        assert gd.key_heads_per_program(Hk, r, hd, hd, S, 4) == Hk
    q, k, v, g, beta, stack = _operands(B, S, Hk, r, hd)
    if case == "g-30":
        g = g.at[:, 3:19].set(-30.0).at[:, 100:116, 1].set(-30.0)
    if case == "no-decay":
        g = jnp.zeros_like(g)
    _check_against_the_recurrence(q, k, v, g, beta, stack, cl, nn, 1)


# (no real row, one row, one row at position 0, a whole chunk, a ragged count)
_SLOT_KINDS = ((4, 0), (9, 1), (0, 1), (0, 32), (30, 21))


@pytest.mark.parametrize("order", [
    (0, 1, 2, 3, 4), (4, 3, 2, 1, 0), (3, 0, 4, 1, 2), (2, 4, 1, 0, 3)],
    ids=lambda o: "".join("i1fcr"[i] for i in o))
def test_the_call_moves_what_a_slot_holds_and_leaks_nothing(order,
                                                            monkeypatch):
    """A batch whose slots are (idle, one row, one row at position 0, a whole
    chunk, a ragged count) in orders in which the kinds follow and precede
    one another: a program of a slot without a chunk parks the big blocks on
    a neighbour's, and a parked block that leaked one slot's rows into the
    next would show against the recurrence. Two programs a slot. What
    ``ChunkRows.pack_split`` takes of the call's two pieces is finite for
    every idle row, whichever slot is last."""
    B, S, Hk, r, hd = 5, 32, 4, 2, 64
    monkeypatch.setattr(gd, "BLOCK_VMEM_BYTES",
                        2 * gd._key_head_bytes(r, hd, hd, S, 4))
    assert gd.key_heads_per_program(Hk, r, hd, hd, S, 4) == 2
    q, k, v, g, beta, stack = _operands(B, S, Hk, r, hd, layers=3)
    cl = jnp.array([_SLOT_KINDS[i][0] for i in order])
    nn = jnp.array([_SLOT_KINDS[i][1] for i in order])
    o, whole, first = _check_against_the_recurrence(
        q, k, v, g, beta, stack, cl, nn, 1)
    wide = Hk * r * hd
    assert whole.shape == (B + 1, S, wide) and first.shape == (B, 16, wide)
    chunk = nn > 1
    whole = jnp.where(jnp.pad(chunk, (0, 1))[:, None, None], whole, jnp.nan)
    rows = ChunkRows(B, S, cl, nn, budget=64)
    packed = rows.pack_split(whole, first, chunk)
    assert bool(jnp.isfinite(packed).all())
    real = int(nn.sum())
    o = o.reshape(B, S, wide)
    assert bool((packed[0, :real] == rows.pack(o)[0, :real]).all())


def test_the_members_shares_add_up_to_the_uncut_layer():
    """The share test: one routed layer of 8 experts cut over 4 members of 2
    each. The program's partial sums (first expert 0, 2, 4, 6; each member
    also computes the GATED shared expert), the shared expert counted once,
    add up to the reference's uncut layer."""
    from deepspeed_tpu.moe.sharded_moe import moe_serving_mlp

    full_model = tiny(num_experts=8, moe_routed_experts=8)
    full = init_params(full_model, seed=5)["layers"]
    uncut = fam.shape_of({**CONFIG, "num_experts": 8})
    x = jax.random.normal(jax.random.PRNGKey(9), (24, 64), F32)
    load = lambda tree: jax.tree.map(lambda a: a.astype(F32), tree)
    j = 3
    with ref.HIGHEST():
        ln2 = load(ref.layer(full["ln2"], j))
        want, _ = fam.routed_block(x, ln2, full["mlp"], j, uncut, load,
                                   first=0)
        normed = ref.rmsnorm(x, ln2, 1e-6)
        shared = fam._shared(normed, load(ref.layer(full["mlp"]["shared"], j)),
                             load(full["mlp"]["shared_gate"][j]))
        # the gate is no constant: an always-on shared expert is another sum
        always = fam._shared(normed, load(ref.layer(full["mlp"]["shared"], j)),
                             load(full["mlp"]["shared_gate"][j]),
                             "shared_gate_off")
        assert float(jnp.abs(always - shared).max()) > 1e-3
        total = 0.0
        for member in range(4):
            cfg = tiny(moe_first_expert=2 * member).config
            bank = {k: (a[j, 2 * member:2 * member + 2]
                        if k in ("wi", "wg", "wo") else a[j])
                    for k, a in full["mlp"].items() if k != "shared"}
            bank["shared"] = ref.layer(full["mlp"]["shared"], j)
            out, _ = moe_serving_mlp(cfg, bank, normed[None])
            total = total + out[0]
    assert float(jnp.abs(total - 3 * shared - (want - x)).max()) < 1e-5


@pytest.mark.parametrize("fault", fam.FAULTS)
def test_every_fault_moves_the_reference(params, shape, fault):
    """Each name in FAULTS changes the reference's logits by far more than
    the tolerance the engine is held to (300 tokens: the chunk faults bite
    at row 256)."""
    ids = ids_of(300, 31)
    sound = np.asarray(logits_of(params, ids, shape))
    broken = np.asarray(fam.logits(
        ids=ids, shape=shape, **fam.faulted(params, fault, shape)))
    # (a state rounded once, at row 255, fades at the pace of its decay:
    # with A drawn up to 16 most heads have forgotten it a row later)
    floor = 5 * TOL if fault == "state_bf16" else 20 * TOL
    assert np.abs(broken - sound).max() > floor, fault


def test_what_cannot_be_built_is_refused_in_words(model, params):
    with pytest.raises(ValueError, match="gdn_value_heads"):
        TransformerConfig(num_layers=1, mixer_types=("gdn",),
                          mixer_layer_ids=(0,), mixer_depth=1)
    with pytest.raises(ValueError, match="a whole number"):
        TransformerConfig(num_layers=1, mixer_types=("gdn",),
                          mixer_layer_ids=(0,), mixer_depth=1,
                          gdn_value_heads=3, gdn_key_heads=2, gdn_head_dim=8)
    # a full layer has a stack of its own beside gdn layers alone
    with pytest.raises(ValueError, match="share the module"):
        TransformerConfig(num_layers=2, mixer_types=("kda", "full"),
                          mixer_layer_ids=(0, 1), mixer_depth=2)
    with pytest.raises(ValueError, match="share the module"):
        TransformerConfig(num_layers=2, mixer_types=("gdn", "mla"),
                          mixer_layer_ids=(0, 1), mixer_depth=2,
                          gdn_value_heads=2, gdn_key_heads=1, gdn_head_dim=8,
                          kv_latent_dim=8)
    with pytest.raises(ValueError, match="published order"):
        qwen3_next("qwen3next-tiny", layer_ids=[3, 1])
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
    assert srv.scheduler.prefix_cache is None  # hit has no state to resume
