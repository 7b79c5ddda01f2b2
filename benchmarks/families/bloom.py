"""The ``bloom`` family: how its configuration files spell their sizes, and
the plain reference of what they compute.

BLOOM (bigscience/bloom, modeling_bloom.py): word embedding -> LayerNorm ->
N x [LayerNorm -> multi-head attention with ALiBi and biases -> residual ->
LayerNorm -> Linear 4d -> GELU -> Linear -> residual] -> LayerNorm -> tied
head; loss = mean next-token cross-entropy.
  Departures: none in the mathematics. BLOOM's GELU is the tanh
  approximation (``bloom_gelu_forward``); the program uses the erf form, and
  the tolerance of the comparison carries that difference (it is below 5e-4
  in the activation and far below the bf16 error it is compared under).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import reference as ref
from benchmarks.flops import Shape


def shape_of(config: dict) -> Shape:
    """The published keys of BLOOM's ``config.json``."""
    d, h = int(config["hidden_size"]), int(config["n_head"])
    return Shape(config["family"], d, int(config["n_layer"]), h, h, d // h,
                 4 * d, int(config["vocab_size"]), 0, 0, False, True,
                 float(config["layer_norm_epsilon"]))


@partial(jax.jit, static_argnames=("heads", "eps"))
def _block(x, lp, slopes, *, heads, eps):
    S, d = x.shape
    hd = d // heads
    a, m = lp["attn"], lp["mlp"]
    h = ref.layernorm(x, lp["ln1"], eps)
    q = (h @ a["wq"] + a["bq"]).reshape(S, heads, hd)
    k = (h @ a["wk"] + a["bk"]).reshape(S, heads, hd)
    v = (h @ a["wv"] + a["bv"]).reshape(S, heads, hd)

    def alibi(qpos, kpos):
        # slope * key position; softmax is shift-invariant per query row,
        # so this equals the -slope * (i - j) form
        return slopes[:, None, None] * kpos[None, None, :].astype(ref.F32)

    o = ref.causal_attention(q, k, v, alibi).reshape(S, d)
    x = x + o @ a["wo"] + a["bo"]
    h = ref.layernorm(x, lp["ln2"], eps)
    h = jax.nn.gelu(h @ m["wi"] + m["bi"], approximate=True)
    return x + h @ m["wo"] + m["bo"]


@partial(jax.jit, static_argnames=("eps",))
def _nll_chunk(x, final_norm, embed, labels, *, eps):
    """Sum of next-token NLL over one chunk of positions (tied head)."""
    logits = ref.layernorm(x, final_norm, eps) @ embed.T
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return (logz - gold).sum()


def hidden(params, ids, shape, device=None):
    """[S] token ids -> (hidden before the final norm [S,d] f32, embedding
    f32 on ``device``)."""
    eps = shape.eps
    with ref.HIGHEST():
        embed = ref.f32(params["embed"]["tok"], device)
        x = ref.layernorm(embed[jnp.asarray(ids)],
                          ref.f32(params["embed_norm"], device), eps)
        slopes = jnp.asarray(ref.alibi_slopes(shape.heads))
        for i in range(shape.layers):
            lp = ref.f32(ref.layer(params["layers"], i), device)
            x = _block(x, lp, slopes, heads=shape.heads, eps=eps)
    return x, embed


def loss(params, ids, shape, device=None, chunk: int = 512) -> float:
    """Mean next-token cross-entropy of ONE sequence [S] (the last position
    has no target), float32 throughout."""
    ids = np.asarray(ids)
    S = ids.shape[0]
    eps = shape.eps
    x, embed = hidden(params, ids, shape, device)
    fn = ref.f32(params["final_norm"], device)
    total = 0.0
    with ref.HIGHEST():
        for lo in range(0, S - 1, chunk):
            hi = min(lo + chunk, S - 1)
            total += float(_nll_chunk(
                x[lo:hi], fn, embed, jnp.asarray(ids[lo + 1:hi + 1]), eps=eps))
    return total / (S - 1)


def logits(params, ids, shape, device=None, last: int | None = None,
           with_margin: bool = False):
    """[S] -> logits float32 for the last ``last`` positions (all if None).
    A dense model routes nothing: its margin is infinite."""
    x, embed = hidden(params, ids, shape, device)
    if last is not None:
        x = x[-last:]
    with ref.HIGHEST():
        out = ref.layernorm(x, ref.f32(params["final_norm"], device),
                            shape.eps) @ embed.T
    return (out, jnp.full((len(x),), jnp.inf)) if with_margin else out
