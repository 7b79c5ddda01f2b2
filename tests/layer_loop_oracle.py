"""The oracle of the layer walk under ``forward_with_cache``
(models/mixers.py ``cached_layers``: runs of a period, a scan each) for the
models of ``layer_pattern``: the same layers as a plain Python loop, every layer on a cache of its own (a stack of one, written at
index 0), so neither a carry nor an index inside a pool exists to get
wrong. Shared by tests/test_inference.py, tests/test_mellum.py and
tests/test_serving_tail.py."""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models import decoding
from deepspeed_tpu.models.transformer import _mlp, _norm, lm_head_logits
from deepspeed_tpu.moe.sharded_moe import moe_serving_mlp

LEAVES = ("k", "v", "k_scale", "v_scale")


def layer_loop_forward(cfg, params, input_ids, cache, cache_len, *,
                       page_table=None, page_table_win=None, num_new=None,
                       token_valid=None):
    """(float32 logits, cache) of ``forward_with_cache`` at dtype float32,
    layer by layer."""
    B, S = input_ids.shape
    kinds = cfg.layer_pattern or ("full",)
    split = page_table is not None and cfg.has_window
    tables = {"": page_table, decoding.WIN: page_table_win}

    # each piece is jitted with what it reads as arguments, as the scan's
    # body has them: XLA's fused norm and an op-by-op one, or a frontier
    # folded as a constant and a traced one, differ in the last bit
    @jax.jit
    def embed(input_ids, cache_len):
        positions = jnp.reshape(cache_len, (-1, 1)) + jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32), (B, S))
        x = params["embed"]["tok"][input_ids]
        if cfg.pos_embedding == "learned":
            x = x + params["embed"]["pos"][positions]
        if cfg.embed_norm:
            x = _norm(cfg, params["embed_norm"], x)
        return x

    @functools.partial(jax.jit, static_argnames=("kind",))
    def one_layer(x, layer, own_leaves, cache_len, table, num_new,
                  token_valid, kind):
        # (the slots' own rows: the oracle packs nothing)
        rows = decoding.ChunkRows(B, S, cache_len)
        a, *written = decoding._cached_attention(
            cfg, layer["attn"], _norm(cfg, layer["ln1"], x), rows, 0,
            *own_leaves[:2], cache_len, *own_leaves[2:],
            page_table=table, num_new=num_new, kind=kind,
            page_rows=(None if table is None
                       else rows.page_rows(table, own_leaves[0])))
        x = x + a
        normed = _norm(cfg, layer["ln2"], x)
        if cfg.is_moe:
            m, _ = moe_serving_mlp(
                cfg, layer["mlp"], normed, token_valid=token_valid,
                budget_tokens=S if token_valid is not None else B * S)
        else:
            m, _ = _mlp(cfg, layer["mlp"], normed, rng=None, train=False)
        return x + m, written

    cache_len = jnp.asarray(cache_len, jnp.int32)
    x = embed(input_ids, cache_len)
    own = {n: [a[i:i + 1] for i in range(a.shape[0])]
           for n, a in cache.items()}
    used = {sfx: 0 for sfx in tables}
    for i in range(cfg.num_layers):
        kind = kinds[i % len(kinds)]
        sfx = decoding.WIN if split and kind == "window" else ""
        at, used[sfx] = used[sfx], used[sfx] + 1
        names = [n + sfx for n in LEAVES if n + sfx in own]
        x, written = one_layer(
            x, jax.tree.map(lambda a: a[i], params["layers"]),
            [own[n][at] for n in names], cache_len, tables[sfx],
            num_new, token_valid, kind=kind)
        for n, leaf in zip(names, written):
            own[n][at] = leaf
    logits = jax.jit(lambda x: lm_head_logits(
        cfg, params, _norm(cfg, params["final_norm"], x)))(x)
    return logits, {n: jnp.concatenate(v) for n, v in own.items()}


def random_cache(cache, seed=0):
    """``cache`` with every leaf drawn anew: int8 over its whole range,
    scales positive, so that a read of the wrong layer, page or offset
    shows."""
    r = np.random.default_rng(seed)
    out = {}
    for n, a in cache.items():
        if a.dtype == jnp.int8:
            out[n] = jnp.asarray(r.integers(-127, 128, size=a.shape), jnp.int8)
        elif n.startswith(("k_scale", "v_scale")):
            out[n] = jnp.asarray(r.uniform(0.005, 0.02, size=a.shape), a.dtype)
        else:
            out[n] = jnp.asarray(r.normal(size=a.shape), a.dtype)
    return out


def paged_setup(cfg, B, ps, mp, quantized, seed):
    """A shuffled table over B x mp pages (unmapped tails on the NULL
    page) and a pool stack filled with noise."""
    pages = B * mp
    table = np.random.default_rng(seed).permutation(pages).reshape(B, mp)
    table[:, -1] = pages  # the NULL page
    cache = random_cache(
        decoding.init_paged_cache(cfg, pages, ps, jnp.float32,
                                  quantized=quantized),
        seed)
    return cache, jnp.asarray(table, jnp.int32)


def assert_bitwise(got, want, atol=0.0):
    """Logits and every cache leaf, bit for bit (``atol`` 0)."""
    (got_logits, got_cache), (want_logits, want_cache) = got, want
    assert got_cache.keys() == want_cache.keys()
    for n, g, w in [("logits", got_logits, want_logits)] + [
            (n, got_cache[n], want_cache[n]) for n in want_cache]:
        assert g.shape == w.shape and g.dtype == w.dtype, n
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=0.0,
                                   atol=atol, err_msg=n)
