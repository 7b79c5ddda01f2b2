"""Ling-3.0-flash on the serving path, float32 on the CPU at a tiny size: KDA
layers (a state and the convolution's last rows a slot, no page) five to one
beside latent attention over the latent pool, under one member's share of a
sigmoid-routed layer, against the benchmark's plain reference
(benchmarks/families/bailing_hybrid.py)."""

import functools
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.config import DeepSpeedConfigError
from deepspeed_tpu.models import ling
from deepspeed_tpu.models.decoding import _paged_gather
from deepspeed_tpu.models.mixers import layer_plan
from deepspeed_tpu.models.transformer import TransformerConfig
from deepspeed_tpu.ops.attention import attention_impl
from deepspeed_tpu.ops.pallas import kda_attention as ka
from deepspeed_tpu.ops.pallas import sparse_latent_attention as sla
from deepspeed_tpu.serving import Request

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from benchmarks import reference as ref  # noqa: E402
from benchmarks.families import bailing_hybrid as fam  # noqa: E402
from slot_program import (drive, ids_of, init_params,  # noqa: E402
                          reference_logits, schedule)

F32 = jnp.float32
logits_of = reference_logits(fam)
# float32 against float32 on logits whose spread is about 1: what is left is
# the order of the sums (the chunk form's cumulative log-decays reach 80 a
# sub-block, so a decay carries 1e-5 of relative rounding)
TOL = 2e-4
PS, W, SLOTS = 16, 16, 3
SERVING = dict(max_slots=SLOTS, token_budget=W, max_tokens=240, paged=True,
               page_size=PS, prefix_cache=False)
ARENA = dict(slots=SLOTS, width=W, pages_per_slot=16, page_size=PS)
IDS = list(range(12))  # two whole periods K K K K K M of the tiny preset
HELD = dict(num_experts=4, moe_routed_experts=16)
CONFIG = dict(
    family="bailing_hybrid", hidden_size=64, num_hidden_layers=12,
    num_attention_heads=4, num_key_value_heads=4, head_dim=16,
    intermediate_size=128, moe_intermediate_size=32,
    moe_shared_expert_intermediate_size=32, num_shared_experts=1,
    vocab_size=512, num_experts=4, num_experts_per_tok=4, n_group=4,
    topk_group=2, routed_scaling_factor=2.5, rms_norm_eps=1e-6,
    rope_theta=6000000, layer_group_size=6, first_k_dense_replace=2,
    kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    short_conv_kernel_size=4, kda_lower_bound=-5, layer_ids=IDS,
    published=dict(num_hidden_layers=13, num_experts=16,
                   first_k_dense_replace=2))


def tiny(**over):
    # weights five times the preset's spread, so that the mixers weigh as
    # much as the residual stream and a fault in one shows in the logits
    return ling("ling-tiny", layer_ids=IDS, initializer_range=0.1,
                **{**HELD, **over})


@pytest.fixture(scope="module")
def model():
    return tiny()


@pytest.fixture(scope="module")
def params(model):
    return init_params(model)


@pytest.fixture(scope="module")
def shape():
    return fam.shape_of(CONFIG)


def test_the_plan_names_every_layers_two_halves(model):
    """Mixer kind and MLP kind are independent: the cut's first two layers
    are KDA over a dense MLP, every sixth is latent, and each half is read
    at its own index inside its kind's stack."""
    cfg = model.config
    plan = layer_plan(cfg)
    assert [l.mixer for l in plan] == ["kda"] * 5 + ["latent"] + ["kda"] * 5 + [
        "latent"]
    assert [l.mlp for l in plan] == ["dense"] * 2 + ["routed"] * 10
    assert [l.mixer_at for l in plan] == [0, 1, 2, 3, 4, 0, 5, 6, 7, 8, 9, 1]
    assert [l.mlp_at for l in plan] == [0, 1, *range(10)]
    assert cfg.has_state and cfg.paged_layers == 2 and not cfg.is_latent
    got = sum(a.size for a in jax.tree.leaves(
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))))
    assert model.num_params() == got


@pytest.mark.parametrize("kernels", [False, True], ids=["dense", "kernels"])
def test_slots_at_different_frontiers_match_the_reference(model, params,
                                                          shape, kernels):
    """Prefill in chunks through the one slot step with packed rows: three
    slots at different frontiers and chunk sizes (7, 5 and 3 rows: every
    chunk boundary lies inside the convolution's 3-row reach of the next
    chunk's first rows), then one-row steps (decode), then slot 1 taken by a
    SECOND request from position 0 (its state and convolution rows start
    from zero, whatever the first left). Logits of every row against the
    reference's full forward, with the kernels (interpret mode) and
    without."""
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
    it."""
    srv = deepspeed_tpu.init_serving(model, serving=SERVING, params=params,
                                     dtype=F32)
    assert srv.row_layout == "packed" and srv.step_order == "overlapped"
    prompts = [ids_of(n, 20 + i) for i, n in enumerate((37, 5, 50, 21))]
    states = [srv.submit(Request(
        request_id=f"r{i}", prompt=p, max_new_tokens=6, temperature=0.0,
        eos_token_id=-1)) for i, p in enumerate(prompts)]
    srv.run_until_idle()
    assert srv.attention_paths == {"kda": "dense", "latent": "dense"}
    for p, st in zip(prompts, states):
        assert len(st.tokens) == 6
        ids = np.concatenate([p, np.asarray(st.tokens, np.int32)])
        logits = logits_of(params, ids[:-1], shape, last=6)
        assert ref.served_token_gaps(logits, st.tokens).max() < TOL
    snap = srv.metrics.snapshot()
    assert snap["state_resets"] == 4
    leaves = srv.describe()["state_leaves"]
    assert set(leaves) == {"state", "conv"}
    assert snap["state_bytes"] == sum(leaves.values()) == (
        10 * SLOTS * 4 * 16 * 16 * 4 + 10 * SLOTS * 3 * 3 * 64 * 4)
    assert snap["moe_experts_touched"] > 0
    assert srv.describe()["kda_heads_per_program"] == 0  # no kernel, no program


def test_the_step_says_which_way_each_slot_takes(model, params):
    """The counts a step's annotation carries for the kda kind, from the
    plan by host arithmetic: beside the real rows and the live states, the
    slots on the one-row path and those with a chunk (the delta-rule call
    moves a row tile for the first, the chunk's blocks for the second); an
    engine built on the kernels says how many heads a program of the call
    takes (all 4 of ling-tiny's: the blocks are small), statically."""
    from deepspeed_tpu.serving.engine import _KIND_COUNTS

    cl, nn = np.array([0, 40, 7, 0, 3]), np.array([5, 1, 0, 1, 2])
    assert _KIND_COUNTS["kda"](None, cl, nn) == {
        "kda_rows": 9, "kda_state_slots": 4, "state_resets": 2,
        "kda_one_row_slots": 2, "kda_chunk_slots": 2}
    with attention_impl("flash"):
        srv = deepspeed_tpu.init_serving(model, serving=SERVING,
                                         params=params, dtype=F32)
    d = srv.describe()
    assert d["attention"]["kda"]["path"] == "kda_kernel"
    assert d["kda_heads_per_program"] == model.config.num_heads == 4


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5))
def _kda_operands(B, S, H, hd, decay=None, layers=2):
    k_ = jax.random.split(jax.random.PRNGKey(3), 6)
    nrm = lambda key, *s: jax.random.normal(key, s, F32)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q, k, v = unit(nrm(k_[0], B, S, H, hd)), unit(nrm(k_[1], B, S, H, hd)), \
        nrm(k_[2], B, S, H, hd)
    g = -5 * jax.nn.sigmoid(nrm(k_[3], B, S, H, hd)) if decay is None else (
        jnp.full((B, S, H, hd), decay, F32))
    beta = jax.nn.sigmoid(nrm(k_[4], B, S, H))
    return q, k, v, g, beta, nrm(k_[5], layers, B, H, hd, hd)


@functools.cache
def _kda_calls(budget):
    """The kernel's call (interpret mode), its dense twin and the reference's
    recurrence, each under ``jit``: once per ``BLOCK_VMEM_BYTES`` a test
    sets, which a trace of the kernel's reads."""
    def rule(*rows):
        with jax.default_matmul_precision("highest"):
            return fam._delta_rule(*rows)

    return (jax.jit(ka.kda_attention, static_argnames=("scale", "interpret")),
            jax.jit(ka.dense_kda, static_argnames=("scale",)), jax.jit(rule))


def _check_kda_against_the_recurrence(q, k, v, g, beta, stack, cl, nn, layer):
    """The kernel's call and ``dense_kda`` against the reference's row-by-row
    recurrence, slot by slot; a slot with no real row keeps its state bit
    for bit and the stack's other layers are untouched."""
    from deepspeed_tpu.models.decoding import ChunkRows

    B, S, H, hd = q.shape
    scale = hd ** -0.5
    kernel, dense, rule = _kda_calls(ka.BLOCK_VMEM_BYTES)
    whole, first, after = kernel(q, k, v, g, beta, stack, cl, nn, layer=layer,
                                 scale=scale, interpret=True)
    o = ChunkRows(B, S, cl).pack_split(whole, first, nn > 1).reshape(q.shape)
    o2, after2 = dense(q, k, v, g, beta, stack[layer], cl, nn, scale=scale)
    for other in range(stack.shape[0]):
        if other != layer:
            assert bool((after[other] == stack[other]).all())
    for b in range(B):
        n = int(nn[b])
        if n == 0:  # no real row: bit for bit
            assert bool((after[layer, b] == stack[layer, b]).all())
        s0 = jnp.zeros((H, hd, hd)) if int(cl[b]) == 0 else stack[layer, b]
        o3, after3 = rule(q[b, :n] * scale, k[b, :n], v[b, :n], g[b, :n],
                          beta[b, :n], s0)
        for have in (o[b, :n], o2[b, :n]):
            assert float(jnp.abs(have - o3).max()) < TOL if n else True
        for have in (after[layer, b], after2[b]):
            assert float(jnp.abs(have - after3).max()) < TOL
    return o, whole, first


@pytest.mark.parametrize("decay", [-5.0, 0.0, None, "heads"],
                         ids=["pinned-to-the-bound", "no-decay", "drawn",
                              "two-programs-a-slot"])
def test_kda_kernel_is_its_twin_is_the_recurrence(decay, monkeypatch):
    """``kda_attention`` (interpret mode) against ``dense_kda`` against the
    reference's row-by-row recurrence, with the log-decays pinned to -5 over
    the whole chunk (G reaches -320 over 64 rows: a quotient of powers would
    overflow), pinned to 0, and drawn; slots with a whole chunk, one row, no
    row and a ragged count; a slot at position 0 starts from zeros. The last
    case has more than one head a program and more than one program a slot
    (8 heads of 32, 4 a program: the budget is the test's)."""
    B, S, H, hd = 4, 64, 2, 16
    if decay == "heads":
        H, hd, decay = 8, 32, None
        monkeypatch.setattr(ka, "BLOCK_VMEM_BYTES", 4 * 2 * (
            2 * hd * hd * 4 + S * hd * (4 * 4 + 4)))
        assert ka.heads_per_program(H, hd, S, 4) == 4
    else:
        assert ka.heads_per_program(H, hd, S, 4) == H
    q, k, v, g, beta, stack = _kda_operands(B, S, H, hd, decay)
    cl, nn = jnp.array([0, 9, 4, 30]), jnp.array([S, 1, 0, 37])
    _check_kda_against_the_recurrence(q, k, v, g, beta, stack, cl, nn, 1)


# (no real row, one row, one row at position 0, a whole chunk, a ragged count)
_SLOT_KINDS = ((4, 0), (9, 1), (0, 1), (0, 32), (30, 21))


@pytest.mark.parametrize("order", [
    (0, 1, 2, 3, 4), (4, 3, 2, 1, 0), (3, 0, 4, 1, 2), (1, 3, 0, 2, 4),
    (2, 4, 1, 0, 3), (0, 2, 1, 4, 3), (3, 4, 0, 1, 2), (1, 0, 3, 2, 4)],
    ids=lambda o: "".join("i1fcr"[i] for i in o))
def test_kda_call_moves_what_a_slot_holds_and_leaks_nothing(order,
                                                            monkeypatch):
    """A batch whose slots are (idle, one row, one row at position 0, a whole
    chunk, a ragged count) in orders in which every kind follows and
    precedes every other: a program of a slot without a chunk parks the big
    blocks on a neighbour's, and a parked block or a skipped copy that
    leaked one slot's rows into the next would show against the recurrence.
    Two programs a slot (8 heads, 4 a program). What ``ChunkRows.pack``
    takes of the call's two pieces is the slot layout's rows for every real
    row and finite for every idle one, whichever slot is last."""
    from deepspeed_tpu.models.decoding import ChunkRows

    B, S, H, hd = 5, 32, 8, 32
    monkeypatch.setattr(ka, "BLOCK_VMEM_BYTES", 4 * 2 * (
        2 * hd * hd * 4 + S * hd * (4 * 4 + 4)))
    assert ka.heads_per_program(H, hd, S, 4) == 4
    q, k, v, g, beta, stack = _kda_operands(B, S, H, hd, layers=3)
    cl = jnp.array([_SLOT_KINDS[i][0] for i in order])
    nn = jnp.array([_SLOT_KINDS[i][1] for i in order])
    o, whole, first = _check_kda_against_the_recurrence(
        q, k, v, g, beta, stack, cl, nn, 1)
    assert whole.shape == (B + 1, S, H * hd) and first.shape == (B, 16, H * hd)
    # the pieces nobody may read are poisoned: whole's rows of a slot
    # without a chunk (the chip leaves them unwritten) and its spare block
    chunk = nn > 1
    whole = jnp.where(jnp.pad(chunk, (0, 1))[:, None, None], whole, jnp.nan)
    budget = 64
    rows = ChunkRows(B, S, cl, nn, budget=budget)
    packed = rows.pack_split(whole, first, chunk)
    assert packed.shape == (1, budget, H * hd)
    assert bool(jnp.isfinite(packed).all())
    real = int(nn.sum())
    o = o.reshape(B, S, H * hd)
    assert bool((packed[0, :real] == rows.pack(o)[0, :real]).all())
    by_slot = ChunkRows(B, S, cl).pack_split(whole, first, chunk)
    live = (jnp.arange(S)[None, :] < nn[:, None])[..., None]
    assert bool(jnp.isfinite(by_slot).all())
    assert bool((jnp.where(live, by_slot, 0) == jnp.where(live, o, 0)).all())


def test_the_members_shares_add_up_to_the_uncut_layer(params):
    """The share test: one routed layer of 16 experts in 4 groups of 4, cut
    over 4 members of one group each. The program's partial sums (first
    expert 0, 4, 8, 12; each member also computes the shared expert), the
    shared expert counted once, add up to the reference's uncut layer."""
    from deepspeed_tpu.moe.sharded_moe import moe_serving_mlp

    full_model = tiny(num_experts=16, moe_routed_experts=16)
    full = init_params(full_model, seed=5)["layers"]
    uncut = fam.shape_of({**CONFIG, "num_experts": 16})
    x = jax.random.normal(jax.random.PRNGKey(9), (24, 64), F32)
    load = lambda tree: jax.tree.map(lambda a: a.astype(F32), tree)
    j = 3
    with ref.HIGHEST():
        want, _ = fam.routed_block(x, load(ref.layer(full["ln2"], j)),
                                   full["mlp"], j, uncut, load, first=0)
        normed = ref.rmsnorm(x, load(ref.layer(full["ln2"], j)), 1e-6)
        shared = fam._gated(normed, load(ref.layer(full["mlp"]["shared"], j)))
        total = 0.0
        for member in range(4):
            cfg = tiny(moe_first_expert=4 * member).config
            bank = {k: (a[j, 4 * member:4 * member + 4]
                        if k in ("wi", "wg", "wo") else a[j])
                    for k, a in full["mlp"].items() if k != "shared"}
            bank["shared"] = ref.layer(full["mlp"]["shared"], j)
            out, _ = moe_serving_mlp(cfg, bank, normed[None])
            total = total + out[0]
    assert float(jnp.abs(total - 3 * shared - (want - x)).max()) < 1e-5


def test_the_latent_walk_without_a_selection(model):
    """``latent_attention`` (every key of each slot's context, no scores, no
    threshold) against plain attention over the gathered view, and against
    the selection walk handed ``index_topk`` >= the context: prompt chunks
    and a decode row alike."""
    B, S, H, Wd, vw, mp = 3, 16, 4, 128, 64, 8
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    pool = jax.random.normal(ks[0], (2, B * mp + 1, PS, Wd), F32)
    q = jax.random.normal(ks[1], (B, S, H, Wd), F32)
    table = jnp.arange(B * mp, dtype=jnp.int32).reshape(B, mp)
    cl, nn = jnp.array([40, 99, 0]), jnp.array([16, 1, 9])
    kw = dict(layer=1, scale=0.2, v_width=vw, num_new=nn, interpret=True)
    out, why = sla.latent_attention(q, pool, cl, table, **kw)
    assert why == []
    view = _paged_gather(pool[1], table)
    pos = cl[:, None] + jnp.arange(S)[None, :]
    causal = jnp.arange(view.shape[1])[None, None, :] <= pos[..., None]
    want = sla.dense_sparse_attention(q, view, causal, 0.2, vw)
    nb, bk = sla.score_blocks(mp, PS)
    scores = jax.random.normal(ks[2], (B, nb, S, bk), F32)
    thr, tie = sla.select_topk(scores, cl, nn, 4096, interpret=True)
    chosen = sla.sparse_attention(q, pool, scores, thr, tie, cl, table, **kw)
    for b in range(B):
        n = int(nn[b])
        assert float(jnp.abs(out[b, :n] - want[b, :n]).max()) < 1e-5
        assert float(jnp.abs(out[b, :n] - chosen[b, :n]).max()) < 1e-5


@pytest.mark.parametrize("fault", fam.FAULTS)
def test_every_fault_moves_the_reference(params, shape, fault):
    """Each name in FAULTS changes the reference's logits by far more than
    the tolerance the engine is held to (150 tokens: the chunk faults bite
    at row 128)."""
    ids = ids_of(150, 31)
    sound = np.asarray(fam.logits(params, ids, shape))
    broken = np.asarray(fam.logits(
        ids=ids, shape=shape, **fam.faulted(params, fault, shape)))
    assert np.abs(broken - sound).max() > 20 * TOL, fault


def test_what_cannot_be_built_is_refused_in_words(model, params):
    with pytest.raises(ValueError, match="no such mixer kind"):
        TransformerConfig(num_layers=1, mixer_types=("mamba",),
                          mixer_layer_ids=(0,), mixer_depth=1)
    with pytest.raises(ValueError, match="kv_latent_dim"):
        TransformerConfig(num_layers=1, mixer_types=("latent",),
                          mixer_layer_ids=(0,), mixer_depth=1)
    with pytest.raises(ValueError, match="share the module"):
        TransformerConfig(num_layers=2, mixer_types=("kda", "lightning"),
                          mixer_layer_ids=(0, 1), mixer_depth=2)
    # the clamp is one limit a model (swiglu_limit, PR 52): a cut across
    # layers the release clamps differently is refused
    with pytest.raises(ValueError, match="one limit a model"):
        ling("ling-tiny")  # published layer 12 clamps its shared expert
    with pytest.raises(ValueError, match="one limit a model"):
        ling("ling-3.0-flash", layer_ids=[0, 34])
    with pytest.raises(DeepSpeedConfigError, match="paged arena"):
        model.apply(params, jnp.zeros((1, 8), jnp.int32))
    serve = lambda **over: deepspeed_tpu.init_serving(
        model, serving=dict(SERVING, **over), params=params, dtype=F32)
    with pytest.raises(DeepSpeedConfigError, match="state layers"):
        serve(spec=dict(enabled=True, max_draft=2))
    with pytest.raises(DeepSpeedConfigError, match="state layers"):
        serve(host_pages=8)
    with pytest.raises(DeepSpeedConfigError, match="paged"):
        serve(paged=False)
    srv = serve(prefix_cache=True)  # off, with the reason logged: a prefix
    assert srv.scheduler.prefix_cache is None  # hit has no state to resume
