"""The one layer walk under ``forward_with_cache`` (models/mixers.py): every
served family's layers are runs of trips x a period of (mixer, MLP) kinds,
one ``lax.scan`` a run, and the routed layers' stats are folded in one place.
And the one description of a cache (models/decoding.py ``cache_layout``):
its pools are the arena's leaves, its bytes are theirs, and what it admits
is one table that the serving engine, ``init_paged_cache`` and ``init_cache``
read. Tiny widths, the CPU; the compiled steps are held by
tests/test_tpu_compile.py and the numbers by tests/layer_loop_oracle.py and
the family files."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import deepspeed_tpu

import deepspeed_tpu.serving.engine as engine_mod
from deepspeed_tpu.comm.topology import MeshTopology, ParallelDims
from deepspeed_tpu.config import DeepSpeedConfig, DeepSpeedConfigError
from deepspeed_tpu.models import (brumby, cohere, deepseek, glm5, keye, lfm2,
                                  ling, mellum, minicpm, mixtral, qwen3_next)
from deepspeed_tpu.models.decoding import (CACHE_ADMITS, WIN, cache_layout,
                                           forward_with_cache, init_cache,
                                           init_paged_cache)
from deepspeed_tpu.models.mixers import _step_stats, walk_runs
from deepspeed_tpu.models.transformer import MIXER_KINDS

D, R = "dense", "routed"
B, S, PS, MP = 2, 8, 16, 2  # slots, chunk, page size, pages a slot

# family -> (model, the runs expected: (stack, trips, period of kinds))
FAMILIES = {
    "mixtral": (lambda: mixtral("mixtral-tiny", num_layers=3),
                [("layers", 3, (("full", R),))]),
    "mellum": (lambda: mellum("mellum-tiny", num_layers=8),
               [("layers", 2, (("window", R),) * 3 + (("full", R),))]),
    "deepseek": (lambda: deepseek("deepseek-tiny"),
                 [("lead_layers", 1, (("mla", D),)),
                  ("layers", 3, (("mla", R),))]),
    "minicpm_sala": (lambda: minicpm("minicpm-sala-tiny"),
                     [("sparse_layers", 1, (("sparse", D),)),
                      ("lightning_layers", 2, (("lightning", D),)),
                      ("sparse_layers", 2, (("sparse", D),)),
                      ("lightning_layers", 1, (("lightning", D),))]),
    "ling": (lambda: ling("ling-tiny", layer_ids=list(range(12))),
             [("kda_layers", 2, (("kda", D),)),
              ("kda_layers", 3, (("kda", R),)),
              ("latent_layers", 1, (("latent", R),)),
              ("kda_layers", 5, (("kda", R),)),
              ("latent_layers", 1, (("latent", R),))]),
    "brumby": (lambda: brumby("brumby-tiny", layer_ids=[0, 1, 2]),
               [("retention_layers", 3, (("retention", D),))]),
    "glm5": (lambda: glm5("glm5-tiny", layer_ids=[0, 1, 2, 3, 4, 7],
                          num_experts=4, moe_routed_experts=16),
             [("kda_layers", 2, (("kda", D),)), ("kda_layers", 1, (("kda", R),)),
              ("mla_layers", 1, (("mla", R),)), ("kda_layers", 1, (("kda", R),)),
              ("mla_layers", 1, (("mla", R),))]),
    "cohere": (lambda: cohere("cohere-tiny", num_experts=4,
                              moe_routed_experts=8),
               [("layers", 1, (("window", R),) * 3 + (("full", R),))]),
    "keye": (lambda: keye("keye-tiny", num_experts=2, moe_routed_experts=8),
             [("layers", 3, (("full", R),))]),
    "qwen3_next": (lambda: qwen3_next("qwen3next-tiny", num_experts=2,
                                      moe_routed_experts=8),
                   [("gdn_layers", 3, (("gdn", R),)),
                    ("attn_layers", 1, (("full", R),))] * 2),
    "lfm2": (lambda: lfm2("lfm2-tiny"),
             [("conv_layers", 2, (("conv", D),)),
              ("attn_layers", 1, (("full", R),)),
              ("conv_layers", 3, (("conv", R),)),
              ("attn_layers", 1, (("full", R),)),
              ("conv_layers", 1, (("conv", R),))]),
}
ROUTED = ("mixtral", "mellum", "deepseek", "ling", "glm5", "cohere", "keye",
          "qwen3_next", "lfm2")


@pytest.fixture(scope="module", params=list(FAMILIES))
def family(request):
    build, want = FAMILIES[request.param]
    model = build()
    cfg = model.config
    cache = jax.eval_shape(lambda: init_paged_cache(
        cfg, B * MP, PS, jnp.float32, window_pages=B * MP, max_slots=B))
    return request.param, model, cache, want


def _call(model, cache, params, **kw):
    """``forward_with_cache`` as the slot step calls it: B slots of S new
    rows, each on MP pages of its own."""
    table = jnp.arange(B * MP, dtype=jnp.int32).reshape(B, MP)
    return forward_with_cache(
        model.config, params,
        jnp.arange(B * S, dtype=jnp.int32).reshape(B, S), cache,
        jnp.zeros((B,), jnp.int32), dtype=jnp.float32, page_table=table,
        page_table_win=table if model.config.has_window else None,
        num_new=jnp.full((B,), S, jnp.int32), token_budget=B * S, **kw)


def test_the_plan_is_runs_of_a_period_and_hits_every_pool_index_once(family):
    _, model, cache, want = family
    runs = walk_runs(model.config)
    assert [(r.stack, r.trips, r.period) for r in runs] == want
    layers = [l for r in runs for l in r.layers]
    assert [l.layer_id for l in layers] == sorted(l.layer_id for l in layers)
    # the scanned indices are the layers', a row a trip
    for r in runs:
        assert r.scanned("pool_at").shape == (r.trips, len(r.period))
        assert r.scanned("pool_at").ravel().tolist() == [
            l.pool_at for l in r.layers]
    hit = {}
    for l in layers:
        kind = MIXER_KINDS[l.mixer]
        for leaf in kind.page + kind.slot:
            leaf += WIN if l.mixer == "window" else ""
            if leaf in cache:
                hit.setdefault(leaf, []).append(l.pool_at)
    assert hit.keys() == cache.keys()
    for leaf, at in hit.items():
        assert sorted(at) == list(range(cache[leaf].shape[0])), leaf
    # one pool for all (a contiguous cache): a layer's place in the model
    if not model.config.mixer_types:
        flat = [l.pool_at for r in walk_runs(model.config, by_kind=False)
                for l in r.layers]
        assert flat == list(range(model.config.total_layers))


def test_the_forward_holds_one_scan_a_run(family):
    _, model, cache, want = family
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), dtype=jnp.float32))
    jaxpr = jax.make_jaxpr(lambda p, c: _call(model, c, p))(params, cache)
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert [e.params["length"] for e in scans] == [
        trips for _, trips, _ in want]


def test_the_routed_layers_stats_are_one_fold(family):
    """Every routed family's step stats come out of ``_step_stats``, so all
    carry ``experts_touched``; a model without routed layers has none."""
    name, model, cache, _ = family
    cfg = model.config
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), dtype=jnp.float32))
    logits, new_cache, stats = jax.eval_shape(
        lambda p, c: _call(model, c, p, return_moe_stats=True), params, cache)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert new_cache == cache
    if name not in ROUTED:
        assert stats is None
        return
    assert stats["tokens_per_expert"].shape == (cfg.num_experts,)
    assert stats["experts_touched"].shape == ()
    assert stats["experts_touched"].dtype == jnp.int32
    assert ("unrouted_tokens" in stats) == cfg.moe_dropless


def test_the_fold_sums_the_runs_and_counts_the_experts_with_a_row():
    """Two runs' stacks (2 and 1 layers, 4 experts): the step's counts are
    the sum over the layers, and an expert is touched once a layer in which
    it got a row."""
    fill = [np.array([[3, 0, 1, 0], [0, 0, 4, 0]]), np.array([[1, 1, 1, 1]])]
    stats = [{"tokens_per_expert": jnp.asarray(f, jnp.int32),
              "drop_fraction": jnp.full((len(f),), d, jnp.float32)}
             for f, d in zip(fill, (0.0, 0.3))]
    out = _step_stats(stats)
    assert out["tokens_per_expert"].tolist() == [4, 1, 6, 1]
    assert int(out["experts_touched"]) == 2 + 1 + 4
    np.testing.assert_allclose(float(out["drop_fraction"]), 0.1, rtol=1e-6)
    assert "unrouted_tokens" not in out and _step_stats([]) is None


# ----------------------------------------------- the one description of a cache
def test_the_records_pools_are_the_arena_and_its_bytes_are_theirs(family):
    """``cache_layout``'s pools are exactly the leaves ``init_paged_cache``
    builds, shape and type; ``cache_token_bytes`` x tokens x layers is the
    bytes of the page table's pools (scales left out) and ``state_bytes``
    those of the slot leaves; and the pools are the leaves ``MIXER_KINDS``
    names for the kinds the model has, a page or a slot as it says."""
    _, model, cache, _ = family
    cfg = model.config
    pools = cache_layout(cfg).pools(PS, jnp.float32)
    entries = {"page": B * MP + 1, "window": B * MP + 1, "slot": B}
    assert {p.name: ((p.layers, entries[p.table], *p.row), jnp.dtype(p.dtype))
            for p in pools} == {k: (v.shape, v.dtype) for k, v in cache.items()}
    nbytes = lambda table: sum(
        int(np.prod(cache[p.name].shape)) * cache[p.name].dtype.itemsize
        for p in pools if p.table == table)
    paged = {p.layers for p in pools if p.table == "page"}
    assert paged == ({cfg.paged_layers} if not cfg.has_window else
                     {cfg.kind_count("full")}) - {0}
    assert nbytes("page") == (
        engine_mod.cache_token_bytes(cfg, 4, False) * (B * MP + 1) * PS
        * sum(paged))
    assert nbytes("slot") == engine_mod.state_bytes(cfg, B, 4)
    kinds = dict.fromkeys(cfg.mixer_types or cfg.layer_pattern or (
        "mla" if cfg.is_latent else "full",))
    named = {(leaf + (WIN if k == "window" else ""), table)
             for k in kinds for table, leaves in (
                 ("slot", cfg.slot_leaves_of(k)),
                 ("window" if k == "window" else "page", MIXER_KINDS[k].page))
             for leaf in leaves}
    got = {(p.name, p.table) for p in pools}
    assert got <= named
    # what a kind may keep and this model does not: scales (no int8 here),
    # index keys (no indexer), the unfinished block's (keys not pooled)
    assert {n.removesuffix(WIN) for n, _ in named - got} <= {
        "k_scale", "v_scale", "ki", "ki_tail"}
    assert ("ki" in cache) == bool(cfg.index_topk)


# the operations of CACHE_ADMITS each family's cache refuses (a PR that lifts a
# refusal edits one entry of the table and one name here)
OPS = ("paged false", "int8", "host_pages", "fleet.prefill_replicas", "spec",
       "prefix_cache")
MOVES = ("host_pages", "fleet.prefill_replicas", "prefix_cache")
REFUSED = {
    "mixtral": (), "mellum": MOVES, "cohere": MOVES,
    "deepseek": ("paged false", "int8", "host_pages",
                 "fleet.prefill_replicas"),
    "keye": OPS, "minicpm_sala": OPS, "ling": OPS, "brumby": OPS, "glm5": OPS,
    "qwen3_next": OPS, "lfm2": OPS,
}
ASKED = {"paged false": dict(paged=False), "host_pages": dict(host_pages=8),
         "fleet.prefill_replicas": dict(fleet=dict(prefill_replicas=1)),
         "spec": dict(spec=dict(enabled=True, max_draft=2)),
         "prefix_cache": dict(prefix_cache=True), "int8": {}}


class _Reached(Exception):
    """The constructor is past its refusals: it builds the scheduler."""


@pytest.mark.parametrize("op", OPS)
@pytest.mark.parametrize("name", list(FAMILIES))
def test_what_a_cache_admits_is_one_table_that_every_asker_reads(
        name, op, monkeypatch):
    """family x operation: the serving engine raises the table's reason under
    the operation's name or goes on to build (its scheduler told whether a
    prefix is kept), ``init_paged_cache`` and ``init_cache`` raise it for the
    arena's storage, and ``_refuse_page_moves`` gives the reason of
    ``fleet.prefill_replicas``."""
    cfg = FAMILIES[name][0]().config
    lay = cache_layout(cfg)
    why = lay.refused(op)
    assert (why is not None) == (op in REFUSED[name])
    assert set(OPS) == {o for row in CACHE_ADMITS.values() for o in row}
    # the arena's storage: who builds it asks
    arena = {"int8": lambda: init_paged_cache(
                 cfg, 4, cfg.block_sparse.kernel_stride if cfg.block_sparse
                 else PS, quantized=True, window_pages=4, max_slots=B),
             "paged false": lambda: init_cache(cfg, B, 32)}.get(op)
    if arena and why:
        with pytest.raises(DeepSpeedConfigError) as e:
            jax.eval_shape(arena)
        assert why in str(e.value)
    elif arena:
        jax.eval_shape(arena)
    # the hand-over of pages outside the constructor
    me = types.SimpleNamespace(cache=lay)
    if op == "fleet.prefill_replicas" and why:
        with pytest.raises(RuntimeError) as e:
            engine_mod.ServingEngine._refuse_page_moves(me, "export_kv_pages")
        assert str(e.value) == "export_kv_pages: " + why
    elif op == "fleet.prefill_replicas":
        engine_mod.ServingEngine._refuse_page_moves(me, "export_kv_pages")
    if op == "int8":
        return
    # the constructor, over an engine that holds no weights: it stops where
    # it would build its scheduler
    told = {}

    def scheduler(**kw):
        told.update(kw)
        raise _Reached

    monkeypatch.setattr(engine_mod, "Scheduler", scheduler)
    held = types.SimpleNamespace(
        config=cfg, dtype=jnp.float32, max_tokens=96, params={},
        topology=MeshTopology(dims=ParallelDims(), devices=jax.devices()[:1]))
    serving = {**dict(max_slots=B, token_budget=S, max_tokens=96, paged=True,
                      page_size=PS, prefix_cache=False), **ASKED[op]}
    if why and op != "prefix_cache":
        with pytest.raises(DeepSpeedConfigError) as e:
            engine_mod.ServingEngine(serving=serving, engine=held)
        assert "serving." + op in str(e.value) and why in str(e.value)
        return
    with pytest.raises(_Reached):
        engine_mod.ServingEngine(serving=serving, engine=held)
    assert told["prefix_cache"] == (op == "prefix_cache" and why is None)
    assert told["slot_state"] == bool(cfg.has_state)
    assert told["window"] == (  # pages by kind: a paged arena's
        cfg.attn_window if cfg.has_window and op != "paged false" else 0)


def test_a_latent_cache_builds_under_drafts_and_a_kept_prefix():
    """The pairs no family file builds: a latent model (no entry under
    ``spec`` or ``prefix_cache``) is served with both on."""
    model = FAMILIES["deepseek"][0]()
    srv = deepspeed_tpu.init_serving(
        model, dtype=jnp.float32, serving=dict(
            max_slots=B, token_budget=S, max_tokens=96, paged=True,
            page_size=PS, prefix_cache=True,
            spec=dict(enabled=True, max_draft=2)))
    assert srv.max_draft == 2 and srv.scheduler.prefix_cache is not None
    assert srv.step_traces == 1 and set(srv._caches) == {"kv", "ki"}


def test_a_latent_arena_is_placed_whole_on_a_mesh(devices8):
    """deepseek-tiny over tp 2, abstractly: the latent rows and the index
    keys take no head axis, the record gives them ``P()``, and the step is
    traced with the arena's shardings named leaf by leaf (at PR 59 the
    specs knew ``k`` and ``v`` alone and this was a KeyError, the engine's
    ``device_put`` a pytree mismatch)."""
    model = FAMILIES["deepseek"][0]()
    topo = MeshTopology(dims=ParallelDims(tp=2), devices=devices8[:2])
    ds = DeepSpeedConfig({
        "tensor_parallel": {"tp_size": 2},
        "serving": {"enabled": True, "max_slots": B, "token_budget": S,
                    "max_tokens": 96, "paged": True, "page_size": PS}})
    pools = cache_layout(model.config).pools(PS, jnp.float32)
    assert {p.name: p.spec for p in pools} == {"kv": P(), "ki": P()}
    closed, shardings, _, meta = engine_mod.trace_serving_step(model, ds, topo)
    lo, hi = meta["traced_manifest"]["caches"]
    assert hi - lo == 2
    assert all(shardings[v].spec == P() for v in closed.jaxpr.invars[lo:hi])
