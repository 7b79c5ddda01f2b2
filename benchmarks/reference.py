"""Plain references: the helpers every family's reference shares, the
loader that finds a family by name, and the comparison that decides
``correct`` for served tokens.

A family is ``benchmarks/families/<family>.py``, named by a configuration
file's ``family`` key. It exports ``shape_of(config) -> flops.Shape`` and the
plain reference of what the configuration computes: ``loss(params, ids,
shape, device=None)`` for training cells, ``logits(params, ids, shape,
device=None, last=None, with_margin=False, ...)`` for serving cells, with
``FAULTS``, the names of the ways the reference can be broken on purpose,
and ``faulted(params, fault, shape, device)``, which turns a name into the
keywords ``logits`` is then handed; ``run.py --inject`` and the tests use
them to show that the serving comparison refuses each. A new family is a new file; nothing here names one.

A reference is written from the published description in ``jax.numpy``
float32: no kernel, no cache, no batching, no sharding, and no import from
``deepspeed_tpu``. The only thing shared with the program is the *layout of
its parameter tree* (``embed.tok``, ``layers.attn.wq`` stacked over layers,
...), because the reference has to read the weights the program serves.
Every matmul runs under ``jax.default_matmul_precision("highest")`` (a TPU
would otherwise run float32 matmuls in bf16 passes). Weights are upcast one
layer -- for routed experts one expert -- at a time, so the reference fits
beside a bf16 model that nearly fills the chip.
"""

from __future__ import annotations

import importlib
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = partial(jax.default_matmul_precision, "highest")


def f32(tree, device=None):
    """Upcast (and, for sharded leaves, bring to one device) a subtree."""
    def one(a):
        if device is not None:
            a = jax.device_put(a, device)
        return a.astype(F32)
    return jax.tree.map(one, tree)


def layer(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


def layernorm(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def rmsnorm(x, p, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * p["scale"]


def alibi_slopes(heads: int) -> np.ndarray:
    """Press et al. 2022, as BLOOM's ``build_alibi_tensor`` computes them."""
    n = 2 ** math.floor(math.log2(heads))
    base = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
    slopes = [base ** (i + 1) for i in range(n)]
    if n != heads:
        extra = 2.0 ** (-(2.0 ** -(math.log2(2 * n) - 3)))
        slopes += [extra ** (2 * i + 1) for i in range(heads - n)]
    return np.asarray(slopes, np.float32)


def causal_attention(q, k, v, bias_fn=None, block: int = 512):
    """q [S,H,hd], k/v [S,KV,hd] -> [S,H,hd]; full softmax, in query blocks
    so the score matrix of a long context never exists whole."""
    S, H, hd = q.shape
    group = H // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    kpos = jnp.arange(S)
    out = []
    for lo in range(0, S, block):
        qpos = jnp.arange(lo, min(lo + block, S))
        s = jnp.einsum("qhd,khd->hqk", q[lo:lo + block], k) / math.sqrt(hd)
        if bias_fn is not None:
            s = s + bias_fn(qpos, kpos)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v))
    return jnp.concatenate(out, axis=0)


def rope(x, theta, first: int = 0):
    """x [S,H,hd], positions first..first+S-1; rotate-half pairing
    (i, i + hd/2)."""
    S, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(first, first + S, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def family(name: str):
    """The module of a configuration's family, found by name."""
    try:
        return importlib.import_module("benchmarks.families." + name)
    except ModuleNotFoundError as e:
        raise ValueError(f"no benchmarks/families/{name}.py for the "
                         f"configuration's family {name!r}") from e


def served_token_gaps(logits, tokens) -> np.ndarray:
    """Teacher-forced check of greedy serving: ``logits[t]`` is the
    reference's prediction for ``tokens[t]``. Returns, per token, by how
    much the served token's reference logit falls short of the reference's
    maximum (0 = the served token is the reference's argmax)."""
    logits = np.asarray(logits, np.float32)
    tokens = np.asarray(tokens)
    chosen = logits[np.arange(len(tokens)), tokens]
    return logits.max(-1) - chosen


def judge_served(gaps, margins, cc: dict):
    """The serving comparison: ``gaps`` and ``margins`` per served token,
    ``cc`` a block of limits from the mix's ``correctness``. Each part is a
    count against its limit, and is judged where ``cc`` gives the limit:

    * share (``min_near_share``): of ALL served tokens, near-ties in the
      routing included, at least that share lie within ``logit_tol`` of the
      reference's maximum logit. Catches what moves most tokens; needs no
      margin.
    * outlier (``outlier_tol``): a token clear of a near-tie (the
      reference's routing margin at least ``min_margin``) lies within it.
      Catches a fault confined to a few tokens, which the share rule lets
      through. With it, enough: at least ``min_judged`` tokens are clear.
    * exact (``min_argmax_share``): at least that share are exactly the
      reference's argmax. Over the many tokens of the precision sample it
      tells the served precision from the one below it, which the two
      other parts, over 48 tokens, cannot.

    Returns (the faults found, the counts); no fault = correct."""
    gaps, margins = np.asarray(gaps, float), np.asarray(margins, float)
    n = dict(tokens=len(gaps), argmax=int((gaps == 0).sum()))
    faults = []
    if "min_argmax_share" in cc and (
            n["argmax"] < float(cc["min_argmax_share"]) * n["tokens"]):
        faults.append("too few served tokens are the reference's argmax")
    if "min_near_share" in cc:
        n["near"] = int((gaps <= float(cc["logit_tol"])).sum())
        if n["near"] < float(cc["min_near_share"]) * n["tokens"]:
            faults.append("too few served tokens are near-argmaxes of the "
                          "reference")
    if "outlier_tol" in cc:
        clear = margins >= float(cc["min_margin"])
        n.update(clear=int(clear.sum()),
                 outliers=int((gaps[clear] > float(cc["outlier_tol"])).sum()),
                 worst_clear=float(gaps[clear].max()) if clear.any() else 0.0)
        if n["outliers"]:
            faults.append("a served token clear of a near-tie is far from "
                          "the reference's argmax")
        if n["clear"] < int(cc["min_judged"]):
            faults.append(f"only {n['clear']} served tokens are clear of a "
                          "near-tie")
    return faults, n


def swap_one_token(tokens, margins, min_margin: float, clear: bool, vocab: int):
    """A fault in one token (``--inject token_clear`` / ``token_tied``): the
    first served token whose position is clear of a near-tie (or, with
    ``clear`` false, inside one) replaced by its successor in the
    vocabulary, as unrelated to the context as a token read from the wrong
    row. Only the judged token changes, not the context after it: a program
    that emits one wrong token goes on from that token, so the rest of its
    answer still follows its context."""
    tokens = np.array(tokens)
    at = np.flatnonzero((np.asarray(margins) >= min_margin) == clear)
    if len(at):
        tokens[at[0]] = (tokens[at[0]] + 1) % vocab
    return tokens
