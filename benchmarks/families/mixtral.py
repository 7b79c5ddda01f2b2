"""The ``mixtral`` family: how its configuration files spell their sizes,
and the plain reference of what they compute.

Mixtral (mistralai/Mixtral-8x7B-v0.1, modeling_mixtral.py): embedding ->
N x [RMSNorm -> grouped-query attention with rotary embeddings (theta 1e6,
rotate-half pairing) -> residual -> RMSNorm -> router logits -> softmax ->
top-2, renormalised (= softmax over the two logits) -> SwiGLU experts
w2(silu(w1 x) * w3 x), every routed token computed (no capacity, no drop)
-> residual] -> RMSNorm -> untied head.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmarks import reference as ref
from benchmarks.flops import Shape


def shape_of(config: dict) -> Shape:
    """The published keys of Mixtral's ``config.json``."""
    d, h = int(config["hidden_size"]), int(config["num_attention_heads"])
    return Shape(config["family"], d, int(config["num_hidden_layers"]), h,
                 int(config["num_key_value_heads"]),
                 int(config.get("head_dim") or d // h),
                 int(config["intermediate_size"]), int(config["vocab_size"]),
                 int(config["num_local_experts"]),
                 int(config["num_experts_per_tok"]), True,
                 bool(config.get("tie_word_embeddings", False)),
                 float(config["rms_norm_eps"]), float(config["rope_theta"]))


@partial(jax.jit, static_argnames=("heads", "kv_heads", "hd", "eps", "theta"))
def _attn(x, ln1, a, *, heads, kv_heads, hd, eps, theta):
    S = x.shape[0]
    h = ref.rmsnorm(x, ln1, eps)
    q = ref.rope((h @ a["wq"]).reshape(S, heads, hd), theta)
    k = ref.rope((h @ a["wk"]).reshape(S, kv_heads, hd), theta)
    v = (h @ a["wv"]).reshape(S, kv_heads, hd)
    o = ref.causal_attention(q, k, v).reshape(S, heads * hd)
    return x + o @ a["wo"]


@partial(jax.jit, static_argnames=("top_k", "eps"))
def _route(x, ln2, router, *, top_k, eps):
    """(normed input, routing weights [S,E] zero off the top-k, margin [S]:
    by how much router probability the last chosen expert beat the first
    one left out)."""
    h = ref.rmsnorm(x, ln2, eps)
    probs = jax.nn.softmax(h @ router, axis=-1)
    top, idx = jax.lax.top_k(probs, top_k + 1)
    margin = top[:, top_k - 1] - top[:, top_k]
    top, idx = top[:, :top_k], idx[:, :top_k]
    top = top / top.sum(-1, keepdims=True)
    w = jnp.zeros_like(probs).at[jnp.arange(h.shape[0])[:, None], idx].set(top)
    return h, w, margin


@jax.jit
def _expert(h, w_e, wg, wi, wo):
    """One SwiGLU expert on every token, weighted by its routing weight
    (zero for tokens not routed to it): plain and wasteful on purpose."""
    return ((jax.nn.silu(h @ wg) * (h @ wi)) @ wo) * w_e[:, None]


def hidden(params, ids, shape, device=None):
    """[S] token ids -> (hidden before the final norm [S,d] float32, the
    smallest routing margin of each position over the layers [S])."""
    eps, theta = shape.eps, shape.rope_theta
    margin = jnp.full((len(ids),), jnp.inf, ref.F32)
    with ref.HIGHEST():
        x = jax.device_put(params["embed"]["tok"][jnp.asarray(ids)],
                           device).astype(ref.F32)
        L = params["layers"]
        for i in range(shape.layers):
            # index leaf by leaf: slicing the whole layer would copy its
            # 2.8 GB of expert banks next to a model that fills the chip
            at = lambda sub: ref.f32(ref.layer(L[sub], i), device)
            x = _attn(
                x, at("ln1"), at("attn"),
                heads=shape.heads, kv_heads=shape.kv_heads, hd=shape.hd,
                eps=eps, theta=theta)
            m = L["mlp"]
            h, w, mg = _route(x, at("ln2"),
                                      ref.f32(m["router"][i], device),
                                      top_k=shape.top_k, eps=eps)
            margin = jnp.minimum(margin, mg)
            for e in range(shape.experts):
                x = x + _expert(h, w[:, e], ref.f32(m["wg"][i, e], device),
                                ref.f32(m["wi"][i, e], device),
                                ref.f32(m["wo"][i, e], device))
    return x, margin


def logits(params, ids, shape, device=None, last: int | None = None,
           with_margin: bool = False):
    """Logits float32 for the last ``last`` positions (all if None); with
    ``with_margin`` also each of those positions' smallest routing margin."""
    eps = shape.eps
    x, margin = hidden(params, ids, shape, device)
    if last is not None:
        x, margin = x[-last:], margin[-last:]
    with ref.HIGHEST():
        out = ref.rmsnorm(x, ref.f32(params["final_norm"], device),
                          eps) @ ref.f32(params["lm_head"], device)
    return (out, margin) if with_margin else out
