"""The one layer walk under ``forward_with_cache`` (models/mixers.py): every
served family's layers are runs of trips x a period of (mixer, MLP) kinds,
one ``lax.scan`` a run, and the routed layers' stats are folded in one place.
Tiny widths, the CPU; the compiled steps are held by tests/test_tpu_compile.py
and the numbers by tests/layer_loop_oracle.py, test_ling.py and
test_minicpm_sala.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import deepseek, ling, mellum, minicpm, mixtral
from deepspeed_tpu.models.decoding import (WIN, forward_with_cache,
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
}
ROUTED = ("mixtral", "mellum", "deepseek", "ling")


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
    assert ("unrouted_tokens" in stats) == (cfg.moe_gate == "sigmoid_groups")


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
