"""The ``mixtral`` family: how its configuration files spell their sizes,
and the plain reference of what they compute.

Mixtral (mistralai/Mixtral-8x7B-v0.1, modeling_mixtral.py): embedding ->
N x [RMSNorm -> grouped-query attention with rotary embeddings (theta 1e6,
rotate-half pairing) -> residual -> RMSNorm -> router logits -> softmax ->
top-2, renormalised (= softmax over the two logits) -> SwiGLU experts
w2(silu(w1 x) * w3 x), every routed token computed (no capacity, no drop)
-> residual] -> RMSNorm -> untied head.

``FAULTS`` names the ways the reference can be broken on purpose: each is
what one fault of a serving engine does to the arithmetic, so that the
serving comparison can be shown to refuse it. ``faulted`` turns a name into
what ``logits`` is handed: most are a transform of the weights, made outside
the reference; only the two that need its internals (the rotary offset, the
attention mask) are a ``fault`` it knows. Nothing sets one in a measured run.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmarks import reference as ref
from benchmarks.flops import Shape


FAULTS = (
    "rope_off_by_one",   # queries rotated for the position after their own
    "page_dropped",      # one 16-position page of the context not attended
    "chunk_dropped",     # ... one 128-position prefill chunk
    "experts_swapped",   # the middle layer routes experts 0 and 1 crosswise
    "gqa_mispaired",     # every query head reads its neighbour group's K/V
    "weights_int4",      # every matrix rounded to 4 bits a column
    "weights_int8",      # ... to 8 bits: the precision just below bf16
)
DROPPED = {"page_dropped": 16, "chunk_dropped": 128}
INNER = ("rope_off_by_one", *DROPPED)  # the faults ``_attn`` itself knows


@partial(jax.jit, static_argnames="bits")
def _rounded(w, bits: int):
    """Symmetric round-to-nearest of a matrix, one scale a column."""
    top = 2 ** (bits - 1) - 1
    scale = jnp.abs(w).max(axis=0, keepdims=True) / top
    return jnp.clip(jnp.round(w / scale), -top - 1, top) * scale


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


@partial(jax.jit, static_argnames=("heads", "kv_heads", "hd", "eps", "theta",
                                   "fault"))
def _attn(x, ln1, a, *, heads, kv_heads, hd, eps, theta, fault=None):
    S = x.shape[0]
    h = ref.rmsnorm(x, ln1, eps)
    q = ref.rope((h @ a["wq"]).reshape(S, heads, hd), theta,
                 first=int(fault == "rope_off_by_one"))
    k = ref.rope((h @ a["wk"]).reshape(S, kv_heads, hd), theta)
    v = (h @ a["wv"]).reshape(S, kv_heads, hd)
    bias_fn = None
    if fault in DROPPED:  # the aligned span in the middle of the context
        n = DROPPED[fault]
        lo = n * (S // (2 * n))
        bias_fn = lambda qpos, kpos: jnp.where(
            (kpos >= lo) & (kpos < lo + n) & (qpos[:, None] >= lo + n),
            -jnp.inf, 0.0)[None]
    o = ref.causal_attention(q, k, v, bias_fn).reshape(S, heads * hd)
    return x + o @ a["wo"]


@partial(jax.jit, static_argnames=("top_k", "eps"))
def _route(x, ln2, router, *, top_k, eps):
    """(normed input, routing weights [S,E] zero off the top-k, margin [S]:
    by how much the router's logit for the last chosen expert beat the one
    for the first left out). The margin is taken on the logits, where the
    program's bf16 activations act: on the chip it tells the positions
    where the program routed otherwise more sharply than the difference of
    the two probabilities does (PERF.md section 6, PR 27)."""
    h = ref.rmsnorm(x, ln2, eps)
    logit = h @ router
    top, idx = jax.lax.top_k(jax.nn.softmax(logit, axis=-1), top_k + 1)
    near = jnp.take_along_axis(logit, idx[:, top_k - 1:], axis=-1)
    margin = near[:, 0] - near[:, 1]
    top, idx = top[:, :top_k], idx[:, :top_k]
    top = top / top.sum(-1, keepdims=True)
    w = jnp.zeros_like(logit).at[jnp.arange(h.shape[0])[:, None], idx].set(top)
    return h, w, margin


@jax.jit
def _expert(h, w_e, wg, wi, wo):
    """One SwiGLU expert on every token, weighted by its routing weight
    (zero for tokens not routed to it): plain and wasteful on purpose."""
    return ((jax.nn.silu(h @ wg) * (h @ wi)) @ wo) * w_e[:, None]


def faulted(params, fault, shape, device=None) -> dict:
    """What ``logits`` is handed under ``fault`` (one of ``FAULTS``, or None),
    as its keywords. The faults of the weights are made here, as another
    tree or another way of loading it, so the reference's own path has no
    branch for them: swapped experts and mispaired heads are a permutation
    of two small leaves; rounding is done as each matrix is upcast, since a
    rounded copy of the expert banks would not fit beside the model."""
    if fault is None or fault in INNER:
        return dict(params=params, fault=fault)
    if fault not in FAULTS:
        raise ValueError(f"no fault {fault!r} (have {FAULTS})")
    if fault.startswith("weights_int"):
        bits = int(fault[len("weights_int"):])
        return dict(params=params, load=lambda tree: jax.tree.map(
            lambda w: _rounded(w, bits) if w.ndim == 2 else w,
            ref.f32(tree, device)))
    L = params["layers"]
    if fault == "experts_swapped":  # in the middle layer
        i, r = shape.layers // 2, L["mlp"]["router"]
        order = jnp.array([1, 0, *range(2, shape.experts)])
        sub = {"mlp": {**L["mlp"], "router": r.at[i].set(r[i][:, order])}}
    else:  # gqa_mispaired: K/V head g is computed where g + 1 is read
        def roll(w):
            heads = w.reshape(*w.shape[:-1], shape.kv_heads, shape.hd)
            return jnp.roll(heads, 1, axis=-2).reshape(w.shape)
        sub = {"attn": {**L["attn"], "wk": roll(L["attn"]["wk"]),
                        "wv": roll(L["attn"]["wv"])}}
    return dict(params={**params, "layers": {**L, **sub}})


def hidden(params, ids, shape, device=None, fault=None, load=None):
    """[S] token ids -> (hidden before the final norm [S,d] float32, the
    smallest routing margin of each position over the layers [S]).
    ``load`` is how a subtree of the weights reaches the reference: upcast
    to float32, unless the caller hands another way (``faulted``)."""
    eps, theta = shape.eps, shape.rope_theta
    load = load or partial(ref.f32, device=device)
    margin = jnp.full((len(ids),), jnp.inf, ref.F32)
    with ref.HIGHEST():
        x = jax.device_put(params["embed"]["tok"][jnp.asarray(ids)],
                           device).astype(ref.F32)
        L = params["layers"]
        for i in range(shape.layers):
            # index leaf by leaf: slicing the whole layer would copy its
            # 2.8 GB of expert banks next to a model that fills the chip
            at = lambda sub: load(ref.layer(L[sub], i))
            x = _attn(
                x, at("ln1"), at("attn"),
                heads=shape.heads, kv_heads=shape.kv_heads, hd=shape.hd,
                eps=eps, theta=theta, fault=fault)
            m = L["mlp"]
            h, w, mg = _route(x, at("ln2"), load(m["router"][i]),
                              top_k=shape.top_k, eps=eps)
            margin = jnp.minimum(margin, mg)
            for e in range(shape.experts):
                x = x + _expert(h, w[:, e], load(m["wg"][i, e]),
                                load(m["wi"][i, e]), load(m["wo"][i, e]))
    return x, margin


def logits(params, ids, shape, device=None, last: int | None = None,
           with_margin: bool = False, fault: str | None = None, load=None):
    """Logits float32 for the last ``last`` positions (all if None); with
    ``with_margin`` also each of those positions' smallest routing margin.
    ``fault`` (one of ``INNER``) and ``load`` break the reference on
    purpose: ``faulted`` makes both from a name."""
    if fault is not None and fault not in INNER:
        raise ValueError(f"no fault {fault!r} inside the reference (have "
                         f"{INNER}); faulted() makes the others")
    eps = shape.eps
    x, margin = hidden(params, ids, shape, device, fault, load)
    if last is not None:
        x, margin = x[-last:], margin[-last:]
    load = load or partial(ref.f32, device=device)
    with ref.HIGHEST():
        out = ref.rmsnorm(x, load(params["final_norm"]),
                          eps) @ load(params["lm_head"])
    return (out, margin) if with_margin else out
