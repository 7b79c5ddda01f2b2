"""The ``glm5_next`` family: how its configuration files spell their sizes,
the plain reference of what they compute, and what its kernels need.

GLM-5.3-Flash (zai-org/GLM-5.3-Flash, config.json; ``model_type``
``glm5_next_text``): embedding -> blocks -> RMSNorm -> untied head, over
``n`` = ``hc_mult`` residual STREAMS ``X`` [n, d] a token: the embedding
begins all of them, the final norm reads their sum.

Every half-layer ``F`` (a mixer or an MLP, each with its own leaves) stands
inside a manifold-constrained hyper-connection (arXiv:2512.24880): ``xt =
RMSNorm(vec(X))`` over all ``n d`` values (eps ``hc_eps``, no learned scale);
``raw = xt P`` (``P``: ``n d`` x (``n`` + ``n`` + ``n n``)); ``pre = sigmoid
(a_pre raw[:n] + b)``, ``post = 2 sigmoid(a_post raw[n:2n] + b)``, ``res`` =
``hc_sinkhorn_iters`` rounds of (rows to sum 1, then columns to sum 1) on
``exp(a_res raw[2n:] + b)`` as an ``n`` x ``n`` matrix; ``u = sum_j pre_j
X_j``; ``y = F(RMSNorm_l(u))`` (the half-layer's own norm, eps
``rms_norm_eps``); ``X_i <- sum_j res_ij X_j + post_i y``.

The block at PUBLISHED index ``i`` has an indexed latent mixer where ``i % 4
== 3`` and a KDA mixer elsewhere; a dense SwiGLU where ``i <
first_k_dense_replace`` and the routed layer elsewhere.

KDA (Kimi Linear, arXiv:2510.26692), ``H`` heads of ``hd``: the equations of
``families/bailing_hybrid.py`` (convolution of ``short_conv_kernel_size``
taps then SiLU; q, k L2-normed a head; a state a head token by token under
the gated delta rule) with the paper's projections: the log-decay ``g =
lower sigmoid(exp(A_log_h) (x W_a_down W_a_up + dt_bias))`` and an output
gate a CHANNEL ``sigmoid(x W_g_down W_g_up)`` times the head-normed output.
Heads are independent, so they are computed a few at a time.

Indexed latent attention (NO rotary part): ``c_q = RMSNorm(x W_qa)``, ``q =
c_q W_qb`` -> heads of ``qk_nope_head_dim``; ``c_kv = RMSNorm(x W_kva)``;
head ``h``'s ``[k | v] = c_kv W_kvb[h]`` (nothing absorbed); scores at
``qk_nope_head_dim ** -0.5``, softmax over the ALLOWED keys. The allowed
keys: ``q^I = c_q W^I_qb`` -> ``index_n_heads`` heads of ``index_head_dim``;
``k^I = LayerNorm(x W^I_k)``; the first ``index_rope_dim`` values of each
rotated at the token's own position (half-split pairs); the POOLED key of
block ``b`` is :func:`pool` of the rotated keys of tokens ``kpool b ..
kpool b + kpool - 1`` (their mean); ``w = x W^I_w * index_n_heads ** -0.5``;
``I(t, b) = sum_j w_j relu(q^I_j . kbar_b) * index_head_dim ** -0.5`` for
the blocks whose last token is at or before ``t``; token ``t`` attends the
tokens of its ``index_topk`` largest ``I(t, .)`` (all whole blocks while
there are no more), ties to the lower block, and ALWAYS the tokens after its
last whole block, ``kpool floor((t + 1) / kpool) .. t``.

MLPs: ``W_o (silu(min(x W_g, limit)) * clip(x W_i, -limit, limit))``,
dense, shared and routed alike (``swiglu_limit``). Routed layer: sigmoid
scores over all ``routed`` outputs plus a selection bias, one group, top-k,
weights the unbiased scores normalised times ``routed_scaling_factor``; only
the ``experts`` held here (``first_expert ..``) are computed and that partial
sum goes on, beside the shared expert.

``FAULTS`` names the ways the reference can be broken on purpose, each what
one fault of a serving engine does to the arithmetic. Nothing sets one in a
measured run.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from benchmarks import reference as ref
from benchmarks.families.bailing_hybrid import _delta_rule, _head_norm, route
from benchmarks.families.deepseek import _best, _blocks, _rotate, _up
from benchmarks.flops import Shape

CHUNK = 128        # rows a serving step feeds a slot (the chunk faults' unit)
ROWS = 2048        # rows a block of the row-by-row lines (MLPs, mixes)
QUERY_BLOCK = 256  # query rows a block of the indexer's scores
ATTN_BLOCK = 128   # and of the attention's
HEAD_CHUNK = 8     # heads of either mixer computed at a time
PERIOD = 4         # every fourth published layer is an indexed latent one

FAULTS = (
    "hc_res_identity",      # the streams carried over unmixed (res = I)
    "hc_post_off",          # the branch written to every stream once
    "sinkhorn_one_round",   # one round of the 20
    "pool_off",             # a block scored by its first token's key
    "tail_dropped",         # the tokens after the last whole block unseen
    "block_visible_early",  # a block scored from its first token on
    "selection_off",        # every key at or before a query attended
    "clamp_off",            # SwiGLU inputs not clamped
    "scaling_off",          # routing weights not times routed_scaling_factor
    "held_offset_off",      # the held experts read the next share's columns
    "conv_rows_dropped",    # the 3 rows before a chunk's first not carried
    "state_not_reset",      # a request starts from the state its slot held
    "weights_int8",         # every matrix rounded to 8 bits a column
)


@dataclass(frozen=True)
class Glm5Shape(Shape):
    """``flops.Shape`` (``layers`` the ROUTED layers as run, ``heads`` x
    ``hd`` the KDA layers', ``ffn`` an expert's width, ``experts`` those held
    here) plus what this family adds."""

    layer_ids: tuple = ()   # each layer's published index, as run
    first_dense: int = 3    # published layers before the routed ones
    dense_ffn: int = 0
    shared: int = 0
    routed: int = 0         # the router's outputs
    first_expert: int = 0
    groups: int = 1         # (what ``bailing_hybrid.route`` reads)
    groups_kept: int = 1
    routed_scale: float = 1.0
    limit: float = 0.0      # the SwiGLU clamp
    q_rank: int = 0
    kv_rank: int = 0
    nope: int = 0
    v_dim: int = 0
    conv: int = 4
    lower: float = -5.0
    gate_rank: int = 0      # of the KDA decay's and gate's projections
    index_heads: int = 0
    index_dim: int = 0
    index_rope: int = 0
    index_topk: int = 0     # in BLOCKS of ``kpool`` tokens
    kpool: int = 1
    streams: int = 1
    sinkhorn: int = 20
    hc_eps: float = 1e-6

    def kind(self, i: int) -> str:
        return "mla" if i % PERIOD == PERIOD - 1 else "kda"

    def count(self, kind: str) -> int:
        return sum(self.kind(i) == kind for i in self.layer_ids)

    @property
    def dense_layers(self) -> int:
        return len(self.layer_ids) - self.layers

    def mixer_matmul_params(self, kind: str) -> int:
        d, H = self.d, self.heads
        if kind == "kda":
            wide = H * self.hd
            return (4 * d * wide + 2 * self.gate_rank * (d + wide) + d * H)
        return (d * self.q_rank + self.q_rank * H * self.nope
                + d * self.kv_rank + self.kv_rank * H * (self.nope + self.v_dim)
                + H * self.v_dim * d
                + self.q_rank * self.index_heads * self.index_dim
                + d * self.index_dim + d * self.index_heads)

    def layer_matmul_params(self, active: bool = True) -> int:
        """The mean over the routed layers as run: the mixers and the two
        hyper-connection projections of every layer, a token's ``top_k``
        experts (or those stored), the shared expert and the router; the
        dense layers' MLPs are spread over them."""
        n = self.streams
        mix = sum(self.mixer_matmul_params(self.kind(i))
                  + 2 * n * self.d * (n * n + 2 * n) for i in self.layer_ids)
        dense = 3 * self.d * self.dense_ffn * self.dense_layers
        held = self.top_k if active else self.experts
        mlp = 3 * self.d * (held * self.ffn + self.shared) + self.d * self.routed
        return (mix + dense) // self.layers + mlp

    def attention_flops_per_token(self, context: float) -> float:
        """The indexed layers' scores of the pooled keys and attention over
        what the selection reaches, and the KDA layers' state decay, erase,
        write and read-out."""
        reach = min(context, self.index_topk * self.kpool + self.kpool - 1)
        mla = (2 * self.index_heads * self.index_dim * context / self.kpool
               + 2 * self.heads * 2 * self.kv_rank * reach)
        kda = 8 * self.heads * self.hd * self.hd
        return self.count("mla") * mla + self.count("kda") * kda


def shape_of(config: dict) -> Glm5Shape:
    """The published keys of GLM-5.3-Flash's ``config.json``; ``layer_ids``
    and ``published`` say which layers of the release are run, ``assumed``
    what the keys leave open."""
    pub, lin, assumed = (config["published"], config["linear_attn_config"],
                         config["assumed"])
    ids = tuple(int(i) for i in config["layer_ids"])
    assert len(ids) == int(config["num_hidden_layers"])
    first_dense = int(pub["first_k_dense_replace"])
    assert sum(i < first_dense for i in ids) == int(
        config["first_k_dense_replace"])
    assert int(config["qk_rope_head_dim"]) == 0 and config["mla_use_nope"]
    assert config["mhc"] and int(config["n_group"]) == 1
    kinds = config["layer_types"]
    assert all((kinds[i] == "deepseek_sparse_attention") == (
        i % PERIOD == PERIOD - 1) for i in ids)
    return Glm5Shape(
        config["family"], int(config["hidden_size"]),
        sum(i >= first_dense for i in ids), int(lin["num_heads"]),
        int(lin["num_heads"]), int(lin["head_dim"]),
        int(config["moe_intermediate_size"]), int(config["vocab_size"]),
        int(config["n_routed_experts"]), int(config["num_experts_per_tok"]),
        True, bool(config["tie_word_embeddings"]),
        float(config["rms_norm_eps"]), float(assumed["index_rope_theta"]),
        layer_ids=ids, first_dense=first_dense,
        dense_ffn=int(config["intermediate_size"]),
        shared=int(config["moe_intermediate_size"])
        * int(config["n_shared_experts"]),
        routed=int(pub["n_routed_experts"]),
        first_expert=int(config.get("first_expert", 0)),
        routed_scale=float(config["routed_scaling_factor"]),
        limit=float(config["swiglu_limit"]),
        q_rank=int(config["q_lora_rank"]), kv_rank=int(config["kv_lora_rank"]),
        nope=int(config["qk_nope_head_dim"]), v_dim=int(config["v_head_dim"]),
        conv=int(lin["short_conv_kernel_size"]),
        lower=float(lin["gate_lower_bound"]),
        gate_rank=int(assumed["kda_gate_rank"]),
        index_heads=int(config["index_n_heads"]),
        index_dim=int(config["index_head_dim"]),
        index_rope=int(assumed["index_rope_dim"]),
        index_topk=int(config["index_topk"]), kpool=int(config["index_kpool"]),
        streams=int(config["hc_mult"]),
        sinkhorn=int(config["hc_sinkhorn_iters"]),
        hc_eps=float(config["hc_eps"]))


def _row_blocks(fn, *per_row):
    """``fn`` over blocks of ``ROWS`` rows of every operand, one after the
    other; the outputs (a tuple) joined again."""
    S = per_row[0].shape[0]
    outs = [fn(*(a[lo:lo + ROWS] for a in per_row))
            for lo in range(0, S, ROWS)]
    return tuple(jnp.concatenate(o, axis=0) for o in zip(*outs))


# ------------------------------------------------------- hyper-connections
def sinkhorn(m, iters: int):
    """``m`` [..., n, n] positive -> doubly stochastic by ``iters`` rounds of
    (rows to sum 1, then columns to sum 1)."""
    for _ in range(iters):
        m = m / m.sum(-1, keepdims=True)
        m = m / m.sum(-2, keepdims=True)
    return m


@partial(jax.jit, static_argnames=("iters", "eps", "fault"))
def _hyper_read(X, hc, *, iters, eps, fault=None):
    """Streams ``X`` [n, S, d] -> (``u`` [S, d], ``post`` [S, n], ``res``
    [S, n, n])."""
    n, S, d = X.shape
    vec = X.transpose(1, 0, 2).reshape(S, n * d)
    xt = vec / jnp.sqrt((vec * vec).mean(-1, keepdims=True) + eps)
    raw = xt @ hc["w"]
    gain, bias = hc["gain"]["scale"], hc["bias"]
    pre = jax.nn.sigmoid(gain[0] * raw[:, :n] + bias[:n])
    post = 2.0 * jax.nn.sigmoid(gain[1] * raw[:, n:2 * n] + bias[n:2 * n])
    res = sinkhorn(
        jnp.exp(gain[2] * raw[:, 2 * n:] + bias[2 * n:]).reshape(S, n, n),
        1 if fault == "sinkhorn_one_round" else iters)
    if fault == "hc_res_identity":
        res = jnp.broadcast_to(jnp.eye(n, dtype=ref.F32), res.shape)
    if fault == "hc_post_off":
        post = jnp.ones_like(post)
    return jnp.einsum("sn,nsd->sd", pre, X), post, res


@jax.jit
def _hyper_write(X, y, post, res):
    """Streams ``X`` (a tuple of ``n`` arrays [S, d]) -> the streams after
    the half-layer's output ``y`` [S, d], as a tuple."""
    return tuple(
        sum(res[:, i, j, None] * X[j] for j in range(len(X)))
        + post[:, i, None] * y for i in range(len(X)))


def hyper(X, hc, inner, shape: Glm5Shape, fault=None):
    """One half-layer inside its hyper-connection over the streams ``X`` (a
    tuple of ``n`` arrays [S, d]: two sets of streams are the most that
    live): ``inner`` takes the mixed input ``u`` [S, d] whole (a mixer looks
    along the sequence) and returns (y, anything else); the mixing values
    are read a block of rows at a time."""
    hfault = fault if fault in ("sinkhorn_one_round", "hc_res_identity",
                                "hc_post_off") else None
    u, post, res = _row_blocks(
        lambda *Xb: _hyper_read(jnp.stack(Xb), hc, iters=shape.sinkhorn,
                                eps=shape.hc_eps, fault=hfault), *X)
    y, extra = inner(u)
    del u
    return _hyper_write(X, y, post, res), extra


# -------------------------------------------------------------------- KDA
@partial(jax.jit, static_argnames=("hd", "eps", "taps", "lower", "fault"))
def _kda_heads(h, a, *, hd, eps, taps, lower, fault=None):
    """The heads ``a`` holds (its leaves cut to them) of one KDA mixer over
    normed inputs ``h`` [S, d] -> their part of the output [S, d]."""
    S = h.shape[0]
    wide = a["wq"].shape[1]
    heads = wide // hd
    pre = jnp.concatenate([h @ a["wq"], h @ a["wk"], h @ a["wv"]], axis=-1)
    t = jnp.arange(S)
    y = 0.0
    for i in range(taps):  # y_t = sum_i c_i x_{t - (taps - 1) + i}
        back = taps - 1 - i
        rows = jnp.pad(pre, ((back, 0), (0, 0)))[:S]
        if fault == "conv_rows_dropped" and back:
            rows = jnp.where((t % CHUNK >= back)[:, None], rows, 0.0)
        y = y + rows * a["conv"][i]
    y = jax.nn.silu(y)
    q, k, v = (y[:, j * wide:(j + 1) * wide].reshape(S, heads, hd)
               for j in range(3))
    q = q / jnp.sqrt((q * q).sum(-1, keepdims=True) + 1e-6) * hd ** -0.5
    k = k / jnp.sqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    g = lower * jax.nn.sigmoid(jnp.exp(a["A_log"])[None, :, None] * (
        (h @ a["wa_down"]) @ a["wa_up"] + a["dt_bias"]).reshape(S, heads, hd))
    beta = jax.nn.sigmoid(h @ a["wbeta"])
    s0 = jnp.zeros((heads, hd, hd), ref.F32)
    if fault == "state_not_reset":  # what the slot's last request left
        _, s0 = _delta_rule(q, k, v, g, beta, s0)
    o, _ = _delta_rule(q, k, v, g, beta, s0)
    o = _head_norm(o, a["o_norm"]["scale"], eps).reshape(S, wide)
    o = o * jax.nn.sigmoid((h @ a["wg_down"]) @ a["wg_up"])
    return o @ a["wo"]


def _kda(h, a, shape: Glm5Shape, up, fault=None):
    """One KDA mixer, ``HEAD_CHUNK`` heads at a time; ``a`` the layer's
    leaves as served, each cut and upcast (``up``) where it is used."""
    H, hd = shape.heads, shape.hd
    wide = H * hd
    out = 0.0
    for lo in range(0, H, HEAD_CHUNK):
        hi = min(lo + HEAD_CHUNK, H)
        cols = slice(lo * hd, hi * hd)
        cut = {n: up(a[n])[:, cols] for n in ("wq", "wk", "wv", "wa_up",
                                              "wg_up")}
        cut.update(
            conv=jnp.concatenate([up(a["conv"])[:, j * wide + lo * hd:
                                                j * wide + hi * hd]
                                  for j in range(3)], axis=1),
            A_log=up(a["A_log"])[lo:hi], dt_bias=up(a["dt_bias"])[cols],
            wbeta=up(a["wbeta"])[:, lo:hi], wa_down=up(a["wa_down"]),
            wg_down=up(a["wg_down"]), o_norm=jax.tree.map(up, a["o_norm"]),
            wo=up(a["wo"], rows=(lo * hd, hi * hd)))
        out = out + _kda_heads(h, cut, hd=hd, eps=shape.eps, taps=shape.conv,
                               lower=shape.lower, fault=fault)
    return out


# ---------------------------------------------------------- indexed latent
def pool(keys, kpool: int):
    """ONE key a block of ``kpool`` tokens, from their rotated keys [S, Di]
    -> [ceil(S / kpool), Di]: the mean (``assumed.index_kpool``; a block the
    sequence does not finish is never scored)."""
    S = keys.shape[0]
    keys = jnp.pad(keys, ((0, -S % kpool), (0, 0)))
    return keys.reshape(-1, kpool, keys.shape[-1]).mean(1)


@partial(jax.jit, static_argnames=("heads", "dim", "rd", "topk", "kpool",
                                   "eps", "theta", "fault"))
def _allowed(h, c_q, ix, *, heads, dim, rd, topk, kpool, eps, theta,
             fault=None):
    """bool [S, S]: the keys each token attends."""
    S = h.shape[0]
    pos = jnp.arange(S)
    if fault == "selection_off":
        return pos[None, :] <= pos[:, None]
    inv = 1.0 / (theta ** (jnp.arange(0, rd, 2, dtype=ref.F32) / rd))
    k = ref.layernorm(h @ ix["wk"], ix["k_norm"], eps)
    k = jnp.concatenate([_rotate(k[..., :rd], inv), k[..., rd:]], -1)
    kbar = pool(k, kpool)
    if fault == "pool_off":  # a block's slot holds its first token's key
        kbar = jnp.pad(k, ((0, -S % kpool), (0, 0)))[::kpool]
    blocks = jnp.arange(kbar.shape[0])

    def block(first, cb, hb):
        q = (cb @ ix["wq_b"]).reshape(-1, heads, dim)
        q = jnp.concatenate([_rotate(q[..., :rd], inv, first), q[..., rd:]], -1)
        w = (hb @ ix["w_proj"]) * heads ** -0.5
        s = jnp.einsum("qhd,kd->qhk", q, kbar)
        score = jnp.sum(jnp.maximum(s, 0.0) * w[..., None], 1) * dim ** -0.5
        qpos = first + jnp.arange(q.shape[0])
        whole = (qpos + 1) // kpool  # blocks whose last token is seen
        seen = blocks[None, :] < (
            qpos // kpool + 1 if fault == "block_visible_early" else whole
        )[:, None]
        chosen = jnp.repeat(_best(score, seen, topk), kpool, axis=1)[:, :S]
        tail = pos[None, :] >= (whole * kpool)[:, None]
        if fault == "tail_dropped":  # where a whole block is seen at all
            tail &= (whole == 0)[:, None]
        return (chosen | tail) & (pos[None, :] <= qpos[:, None])

    return _blocks(block, S, QUERY_BLOCK, c_q, h)


@partial(jax.jit, static_argnames=("nope", "vd"))
def _attend(c_q, c_kv, allowed, wq_b, wkv_b, wo, *, nope, vd):
    """The heads of one chunk (``wq_b`` [q_rank, n x nope], ``wkv_b``
    [kv_rank, n x (nope + vd)], ``wo`` [n x vd, d], float32) -> their part of
    the mixer's output [S, d]."""
    S = c_q.shape[0]
    q = (c_q @ wq_b).reshape(S, -1, nope)
    kv = (c_kv @ wkv_b).reshape(S, -1, nope + vd)
    k, v = kv[..., :nope], kv[..., nope:]

    def block(first, qb, ab):
        s = jnp.einsum("qhd,khd->hqk", qb, k) * nope ** -0.5
        p = jax.nn.softmax(jnp.where(ab[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    o = _blocks(block, S, ATTN_BLOCK, q, allowed)
    return o.reshape(S, -1) @ wo


def _mla(h, a, shape: Glm5Shape, up, fault=None):
    """One indexed latent mixer over normed inputs ``h`` [S, d]; the three
    large matrices (``wq_b``, ``wkv_b``, ``wo``) a chunk of heads at a
    time."""
    H, nope, vd = shape.heads, shape.nope, shape.v_dim
    c_q = ref.rmsnorm(h @ up(a["wq_a"]), jax.tree.map(up, a["q_norm"]),
                      shape.eps)
    c_kv = ref.rmsnorm(h @ up(a["wkv_a"]), jax.tree.map(up, a["kv_norm"]),
                       shape.eps)
    allowed = _allowed(
        h, c_q, jax.tree.map(up, a["idx"]), heads=shape.index_heads,
        dim=shape.index_dim, rd=shape.index_rope, topk=shape.index_topk,
        kpool=shape.kpool, eps=shape.eps, theta=shape.rope_theta,
        fault=fault if fault in ("selection_off", "pool_off", "tail_dropped",
                                 "block_visible_early") else None)
    out = 0.0
    for lo in range(0, H, HEAD_CHUNK):
        hi = min(lo + HEAD_CHUNK, H)
        out = out + _attend(
            c_q, c_kv, allowed, up(a["wq_b"])[:, lo * nope:hi * nope],
            up(a["wkv_b"])[:, lo * (nope + vd):hi * (nope + vd)],
            up(a["wo"], rows=(lo * vd, hi * vd)), nope=nope, vd=vd)
    return out


# ------------------------------------------------------------------- MLPs
def _swiglu(gate, value, limit: float):
    if limit:
        gate, value = jnp.minimum(gate, limit), jnp.clip(value, -limit, limit)
    return jax.nn.silu(gate) * value


@partial(jax.jit, static_argnames="limit")
def _gated(h, m, limit: float = 0.0):
    return _swiglu(h @ m["wg"], h @ m["wi"], limit) @ m["wo"]


@partial(jax.jit, static_argnames=("bits", "limit"))
def _experts(h, w, wg, wi, wo, i, bits: int = 0, limit: float = 0.0):
    """Every held expert of routed layer ``i`` on EVERY token of ``h``,
    weighted by its routing weight (zero where the token was not sent to
    it), one expert after the other: plain and wasteful on purpose. The
    banks come whole ([L, E, ...], as served); one matrix at a time is cut
    out and upcast inside the loop."""
    def mat(bank, e):
        return _up(jax.lax.dynamic_slice(
            bank, (i, e, 0, 0), (1, 1, *bank.shape[2:]))[0, 0], bits=bits)

    def add(e, y):
        out = _swiglu(h @ mat(wg, e), h @ mat(wi, e), limit) @ mat(wo, e)
        return y + out * jax.lax.dynamic_index_in_dim(
            w, e, 1, keepdims=False)[:, None]

    return jax.lax.fori_loop(0, wg.shape[1], add, jnp.zeros_like(h))


def routed_mlp(h, m, j, shape: Glm5Shape, load, first=None, fault=None,
               bits: int = 0, shared: bool = True):
    """The routed layer ``j`` of the bank ``m`` (as served, stacked over
    layers) over normed rows ``h``: the partial sum of the experts held (as
    many as the bank holds, from ``first``) and, with ``shared``, the shared
    expert. Returns (y, margin [S])."""
    first = shape.first_expert if first is None else first
    limit = 0.0 if fault == "clamp_off" else shape.limit
    router, bias = load(m["router"][j]), load(m["sel_bias"][j])
    sh = load(ref.layer(m["shared"], j)) if shared else None

    def rows(hb):
        w, margin = route(hb, router, bias, shape, first, m["wi"].shape[1],
                          fault)
        y = _experts(hb, w, m["wg"], m["wi"], m["wo"], j, bits=bits,
                     limit=limit)
        if sh is not None:
            y = y + _gated(hb, sh, limit)
        return y, margin

    return _row_blocks(rows, h)


def faulted(params, fault, shape, device=None) -> dict:
    """What ``logits`` is handed under ``fault`` (one of ``FAULTS``, or None),
    as its keywords: every fault here is arithmetic of the reference itself,
    but the rounding, which is done as each matrix is upcast (``bits``)."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"no fault {fault!r} (have {FAULTS})")
    if fault is not None and fault.startswith("weights_int"):
        return dict(params=params, bits=int(fault[len("weights_int"):]))
    return dict(params=params, fault=fault)


def hidden(params, ids, shape: Glm5Shape, device=None, fault=None,
           bits: int = 0):
    """[S] token ids -> (the sum of the streams before the final norm [S, d]
    float32, the smallest routing margin of each position over the routed
    layers [S])."""
    on_device = lambda tree: jax.tree.map(
        lambda w: jax.device_put(w, device), tree)
    up = lambda w, **kw: _up(jax.device_put(w, device), bits=bits, **kw)
    load = lambda tree: jax.tree.map(up, tree)
    margin = jnp.full((len(ids),), jnp.inf, ref.F32)
    seen = {"kda": 0, "mla": 0, "dense": 0, "routed": 0}
    limit = 0.0 if fault == "clamp_off" else shape.limit
    with ref.HIGHEST():
        x = jax.device_put(params["embed"]["tok"][jnp.asarray(ids)],
                           device).astype(ref.F32)
        X = (x,) * shape.streams
        del x
        for i in shape.layer_ids:
            kind = shape.kind(i)
            mlp = "dense" if i < shape.first_dense else "routed"
            j, jm = seen[kind], seen[mlp]
            seen[kind], seen[mlp] = j + 1, jm + 1
            L = params[kind + "_layers"]
            ln1, a = load(ref.layer(L["ln1"], j)), on_device(
                ref.layer(L["attn"], j))
            mixer = _kda if kind == "kda" else _mla
            X, _ = hyper(
                X, load(ref.layer(L["hc"], j)),
                lambda u: (mixer(ref.rmsnorm(u, ln1, shape.eps), a, shape, up,
                                 fault), None), shape, fault)
            M = params["lead_layers" if mlp == "dense" else "layers"]
            ln2 = load(ref.layer(M["ln2"], jm))
            if mlp == "dense":
                m = load(ref.layer(M["mlp"], jm))
                inner = lambda u: (_row_blocks(
                    lambda ub: (_gated(ref.rmsnorm(ub, ln2, shape.eps), m,
                                       limit),), u)[0], None)
            else:
                inner = lambda u: routed_mlp(
                    ref.rmsnorm(u, ln2, shape.eps), on_device(M["mlp"]), jm,
                    shape, load, fault=fault, bits=bits)
            X, mg = hyper(X, load(ref.layer(M["hc"], jm)), inner, shape, fault)
            if mg is not None:
                margin = jnp.minimum(margin, mg)
    return sum(X), margin


def logits(params, ids, shape: Glm5Shape, device=None,
           last: int | None = None, with_margin: bool = False,
           fault: str | None = None, bits: int = 0):
    """Logits float32 over the vocabulary slice for the last ``last``
    positions (all if None); with ``with_margin`` also each of those
    positions' smallest routing margin over the layers: how near an expert
    held here was to changing sides. ``fault`` and ``bits`` break the
    reference on purpose: ``faulted`` makes both from a name."""
    x, margin = hidden(params, ids, shape, device, fault, bits)
    if last is not None:
        x, margin = x[-last:], margin[-last:]
    up = lambda w: _up(jax.device_put(w, device), bits=bits)
    with ref.HIGHEST():
        out = ref.rmsnorm(x, jax.tree.map(up, params["final_norm"]),
                          shape.eps) @ up(params["lm_head"])
    return (out, margin) if with_margin else out


# ---- kernels ---------------------------------------------------------------
def kda_cost(shape: Glm5Shape, rows: float, state_slots: float,
             itemsize: int = 2):
    """The delta rule of ONE KDA layer: (flops, bytes) the traced steps
    needed. For every real row a head's decay of the state (hd x hd), the
    erase ``k^T S``, the write ``k (..)^T`` and the read-out ``q^T S``, 2 x
    hd x hd each: the recurrence's own count, the least any form of it does.
    Bytes: every live state read and written once a slot a step, float32;
    the real rows' q, k, v in and o out, their log-decays (float32) and step
    sizes."""
    H, hd = shape.heads, shape.hd
    flops = 7 * H * hd * hd * rows
    state = 2 * H * hd * hd * 4 * state_slots
    per_row = 4 * H * hd * itemsize + H * hd * 4 + H * 4
    return flops, state + per_row * rows


def indexer_cost(shape: Glm5Shape, pooled_context_keys: float,
                 pooled_keys: float, query_rows: float, itemsize: int = 2):
    """The indexer's scoring and selection of ONE layer over POOLED keys:
    (flops, bytes) the work needs. ``pooled_context_keys``: for every real
    query token the whole blocks at or before it, summed: each pair costs a
    dot product of every index head, 2 x index_heads x index_head_dim (the
    ReLU, the weighted sum over heads and the selection itself counted as
    free). Bytes: the pooled keys of a slot's context (``pooled_keys``, the
    whole blocks at or before its last real query) once a slot, and the real
    rows' index queries and head weights in; nothing out (a selection that
    stays on the chip)."""
    flops = 2 * shape.index_heads * shape.index_dim * pooled_context_keys
    keys = shape.index_dim * itemsize * pooled_keys
    q = shape.index_heads * (shape.index_dim * itemsize + 4) * query_rows
    return flops, keys + q


def sparse_attention_cost(shape: Glm5Shape, attended_keys: float,
                          chosen_rows: float, query_rows: float,
                          itemsize: int = 2):
    """Attention of ONE indexed layer over the selection: (flops, bytes) the
    work needs, in the absorbed form. ``attended_keys``: for every real
    query ``min(context, kpool x index_topk + tail)``, summed: each pair
    costs, for every head, a dot product over the latent and a weighted sum
    of it (``kv_lora_rank`` each: a row has no rotary part). Bytes: each of
    the ``chosen_rows`` latent rows some query of a slot chose, once a slot
    (the caller gives the fewest they can be: the last query's), and the
    absorbed queries in and the attended latents out for ``query_rows``."""
    flops = 2 * shape.heads * 2 * shape.kv_rank * attended_keys
    rows = shape.kv_rank * itemsize * chosen_rows
    q_out = shape.heads * 2 * shape.kv_rank * itemsize * query_rows
    return flops, rows + q_out


def residual_mix_cost(shape: Glm5Shape, rows: float, itemsize: int = 2):
    """The hyper-connections of ONE step, all ``2 x layers`` of them: (flops,
    bytes) the work needs for ``rows`` computed rows. A boundary projects a
    row's ``n d`` values to ``n n + 2 n`` mixes, reads a mix of the streams
    (``n d`` multiply-adds) and writes ``n`` streams, each a mix of ``n``
    plus the branch (``n (n + 1) d``); the Sinkhorn rounds on ``n n`` values
    are counted as free. Bytes: the streams read once and written once a
    boundary, the branch's input out and its output in."""
    n, d = shape.streams, shape.d
    bounds = 2 * len(shape.layer_ids)
    flops = 2 * (n * d * (n * n + 2 * n) + n * d + n * (n + 1) * d)
    return (flops * rows * bounds,
            (2 * n + 2) * d * itemsize * rows * bounds)
