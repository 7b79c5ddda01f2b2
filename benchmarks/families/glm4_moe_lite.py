"""The ``glm4_moe_lite`` family: how its configuration files spell their sizes,
the plain reference of the loss they train on, and what its training kernels
need.

GLM-4.7-Flash (zai-org/GLM-4.7-Flash, config.json; ``model_type``
``glm4_moe_lite``): embedding -> ``first_k_dense_replace`` dense blocks ->
routed blocks -> RMSNorm -> untied head, and ``num_nextn_predict_layers``
multi-token-prediction module beside the head. Every block is pre-norm
(RMSNorm eps ``rms_norm_eps``, no biases); ``x`` is a block's normed input.

Latent attention, training form: ``c_q = RMSNorm(x W_qa)`` (``q_lora_rank``);
``q = c_q W_qb`` -> heads of ``[q_nope | q_pe]``; ``kv_a = x W_kva``: ``c_kv =
RMSNorm(kv_a[:kv_lora_rank])`` and ONE rotary key ``k_pe = kv_a[kv_lora_rank:]``
for all heads; ``q_pe`` and ``k_pe`` rotated over all ``qk_rope_head_dim``
values, ``rope_theta``, no scaling; head ``h``: ``[k_nope | v] = c_kv
W_kvb[h]``, ``k = [k_nope | k_pe]``. Causal softmax of ``q . k * (nope +
rope) ** -0.5``, times ``v``, heads concatenated through ``W_o``. No absorbed
form, no cache.

Leading dense layer: ``W2(silu(W1 x) * W3 x)``, width ``intermediate_size``.

Routed blocks: ``s = sigmoid(x W_g)`` in float32 over ALL ``published``
experts; ``c = s + b`` (``b`` the selection bias: it chooses, never weighs);
one group (``n_group`` 1), so the ``num_experts_per_tok`` largest ``c`` are
chosen; weights ``s_e / sum(chosen s) * routed_scaling_factor``. Output
``Shared(x) + sum over chosen e held here of w_e Expert_e(x)``. The
configuration holds ONE member's share of an expert-parallel layer: experts
``0 .. n_routed_experts - 1`` of the published count are computed here, what
the others would add is left out (as in the program), and no token is
dropped. The vocabulary is the slice the file gives.

MTP module: with ``h_i`` the main stack's output at position ``i`` before the
final norm, ``h'_i = [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(h_i)] W_eh``, one
routed block of the same kind with its own weights, its own final RMSNorm,
then the SHARED embedding and head; it predicts ``t_{i+2}``. Loss = ``CE_main
+ mtp_loss_weight * CE_mtp``; no auxiliary balance loss (``noaux_tc``).

Departures, each under ``assumed`` in the configuration file: rotary pairs are
(i, i + half) (the release interleaves; a relabelling under random weights);
the order of the two halves of ``W_eh``'s input; ``mtp_loss_weight`` 0.3 and
the bias update speed are not in the config; the sequence-wise balance term
some reports add is left out; the selection bias is drawn N(0, 0.02^2) where
the release starts it at zero. What moves the bias between steps is the
engine's, not the loss's, and is held by the CPU tests.

``FAULTS`` names the ways the reference can be broken on purpose, each what
one fault of a training step does to the arithmetic. Nothing sets one in a
measured run.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import reference as ref
from benchmarks.flops import Shape

FAULTS = (
    "mtp_off",               # the MTP module's loss left out of the total
    "mtp_shift_off_by_one",  # the module is asked for t[i+1], not t[i+2]
    "shared_expert_off",     # the shared expert left out
    "scaling_off",           # routed weights not times routed_scaling_factor
    "bias_in_weight",        # the selection bias weighs as well as chooses
    "norm_topk_off",         # routed weights not normalised over the chosen
    "latent_rope_off",       # q_pe and k_pe not rotated
    "dense_layer_as_routed",  # the leading layer's MLP is layer 1's experts
    "token_dropped",         # the fullest held expert keeps its first half
)
ATTN_BLOCK = 512  # query rows a step of attention


@dataclass(frozen=True)
class GlmShape(Shape):
    """``flops.Shape`` (``layers`` the ROUTED layers of the main stack,
    ``experts`` the experts held here, ``ffn`` their width, ``kv_heads`` 1:
    one latent a token, ``hd`` the qk width) plus what this family needs."""

    dense_layers: int = 0      # leading dense layers, beside ``layers``
    dense_ffn: int = 0
    shared_ffn: int = 0
    routed: int = 0            # experts the router chooses among
    routed_scale: float = 1.0
    q_rank: int = 0
    kv_rank: int = 0
    nope: int = 0
    rope_dim: int = 0
    v_dim: int = 0
    mtp: int = 0               # MTP modules: one routed block each
    mtp_weight: float = 0.0

    @property
    def attention_params(self) -> int:
        h = self.heads
        return (self.d * self.q_rank + self.q_rank * h * self.hd
                + self.d * (self.kv_rank + self.rope_dim)
                + self.kv_rank * h * (self.nope + self.v_dim)
                + h * self.v_dim * self.d)

    def routed_layer_params(self, active: bool) -> float:
        """Matmul parameters of one routed block; ``active``: what one token
        touches here (the shared expert and, on average, ``top_k x experts
        / routed`` of the held ones)."""
        n = self.top_k * self.experts / self.routed if active else self.experts
        return (self.attention_params + self.d * self.routed
                + 3 * self.d * self.shared_ffn + n * 3 * self.d * self.ffn)

    def layer_matmul_params(self, active: bool = True) -> int:
        """``flops.py`` multiplies this by ``layers`` and adds the head once,
        so it is everything else a token touches, a routed layer's share of
        it: the dense layer, the routed blocks of the main stack and of the
        MTP module, ``eh_proj`` and the head's second use."""
        dense = self.dense_layers * (self.attention_params
                                     + 3 * self.d * self.dense_ffn)
        routed = (self.layers + self.mtp) * self.routed_layer_params(active)
        module = self.mtp * (2 * self.d * self.d
                             + (self.d * self.vocab if active else 0))
        return int((dense + routed + module) / self.layers)

    def attention_flops_per_token(self, context: float) -> float:
        """QK^T (qk width) and PV (value width) over ``context`` keys in
        every block: dense, routed and the MTP module's."""
        depth = self.dense_layers + self.layers + self.mtp
        return depth * 2 * self.heads * (self.hd + self.v_dim) * context


def shape_of(config: dict) -> GlmShape:
    """The published keys of GLM-4.7-Flash's ``config.json``; the experts the
    router sees are the ``published`` count, those computed here
    ``n_routed_experts``."""
    dense = int(config["first_k_dense_replace"])
    nope, rope = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
    return GlmShape(
        config["family"], int(config["hidden_size"]),
        int(config["num_hidden_layers"]) - dense,
        int(config["num_attention_heads"]), 1, nope + rope,
        int(config["moe_intermediate_size"]), int(config["vocab_size"]),
        int(config["n_routed_experts"]), int(config["num_experts_per_tok"]),
        True, bool(config.get("tie_word_embeddings", False)),
        float(config["rms_norm_eps"]), float(config["rope_theta"]),
        dense_layers=dense, dense_ffn=int(config["intermediate_size"]),
        shared_ffn=int(config["n_shared_experts"])
        * int(config["moe_intermediate_size"]),
        routed=int(config["published"]["n_routed_experts"]),
        routed_scale=float(config["routed_scaling_factor"]),
        q_rank=int(config["q_lora_rank"]), kv_rank=int(config["kv_lora_rank"]),
        nope=nope, rope_dim=rope, v_dim=int(config["v_head_dim"]),
        mtp=int(config["num_nextn_predict_layers"]),
        mtp_weight=float(config["assumed"]["mtp_loss_weight"]["value"]))


# ---- the reference ---------------------------------------------------------
@partial(jax.jit, static_argnames=("heads", "nope", "rd", "vd", "kv_rank",
                                   "eps", "theta", "rotate"))
def _attn(x, ln1, a, *, heads, nope, rd, vd, kv_rank, eps, theta, rotate):
    """x + latent attention of one block; ``a`` its attention leaves,
    float32."""
    S = x.shape[0]
    h = ref.rmsnorm(x, ln1, eps)
    c_q = ref.rmsnorm(h @ a["wq_a"], a["q_norm"], eps)
    q = (c_q @ a["wq_b"]).reshape(S, heads, nope + rd)
    kv_a = h @ a["wkv_a"]
    c_kv = ref.rmsnorm(kv_a[:, :kv_rank], a["kv_norm"], eps)
    k_pe = kv_a[:, None, kv_rank:]
    q_pe = q[..., nope:]
    if rotate:
        q_pe, k_pe = ref.rope(q_pe, theta), ref.rope(k_pe, theta)
    kv = (c_kv @ a["wkv_b"]).reshape(S, heads, nope + vd)
    q = jnp.concatenate([q[..., :nope], q_pe], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (S, heads, rd))], -1)
    v = kv[..., nope:]
    pos = jnp.arange(S)
    bq = min(S, ATTN_BLOCK)
    n = -(-S // bq)

    def block(t):  # a block of query rows against every key
        first, qb = t
        s = jnp.einsum("qhd,khd->hqk", qb, k) * (nope + rd) ** -0.5
        seen = pos[None, None, :] <= (first + jnp.arange(bq))[None, :, None]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    q = jnp.pad(q, ((0, n * bq - S), (0, 0), (0, 0))).reshape(
        n, bq, heads, nope + rd)
    out = jax.lax.map(block, (jnp.arange(n) * bq, q)).reshape(
        n * bq, heads * vd)[:S]
    return x + out @ a["wo"]


@jax.jit
def _gated(h, m):
    return (jax.nn.silu(h @ m["wg"]) * (h @ m["wi"])) @ m["wo"]


@partial(jax.jit, static_argnames=("top_k", "scale", "first", "held", "fault"))
def _route(h, router, bias, *, top_k, scale, first, held, fault=None):
    """Routing weights [S, held] of the experts held here, zero where not
    chosen."""
    s = jax.nn.sigmoid(h @ router)
    c = s + bias[None, :]
    _, idx = jax.lax.top_k(c, top_k)
    w = jnp.take_along_axis(c if fault == "bias_in_weight" else s, idx, axis=1)
    if fault != "norm_topk_off":
        w = w / w.sum(-1, keepdims=True)
    if fault != "scaling_off":
        w = w * scale
    full = jnp.zeros_like(c).at[jnp.arange(c.shape[0])[:, None], idx].set(w)
    mine = full[:, first:first + held]
    if fault == "token_dropped":
        # the fullest held expert keeps the first half of its tokens
        chose = mine > 0
        e = jnp.argmax(chose.sum(0))
        rank = jnp.cumsum(chose[:, e]) - 1
        keep = rank < (chose[:, e].sum() + 1) // 2
        mine = mine.at[:, e].multiply(keep)
    return mine


def _add_experts(x, h, w, load, m, j):
    """x + every held expert of routed layer ``j`` on EVERY token, each
    weighted by its routing weight (zero for tokens not routed to it), one
    expert upcast at a time: plain and wasteful on purpose."""
    for e in range(m["wi"].shape[1]):
        one = load({k: m[k][j, e] for k in ("wg", "wi", "wo")})
        x = x + _gated(h, one) * w[:, e, None]
    return x


def _routed_mlp(x, stack, j, shape, load, fault, first_expert):
    """x + the routed MLP (shared expert and held experts) of layer ``j`` of
    ``stack``."""
    m = stack["mlp"]
    h = ref.rmsnorm(x, load(ref.layer(stack["ln2"], j)), shape.eps)
    w = _route(h, load(m["router"][j]), load(m["sel_bias"][j]),
               top_k=shape.top_k, scale=shape.routed_scale,
               first=first_expert, held=shape.experts,
               fault=fault if fault in ("bias_in_weight", "norm_topk_off",
                                        "scaling_off", "token_dropped")
               else None)
    if fault != "shared_expert_off":
        x = x + _gated(h, load(ref.layer(m["shared"], j)))
    return _add_experts(x, h, w, load, m, j)


def _stack(x, stack, n, shape, load, fault, first_expert, dense=False,
           routed_for_dense=None):
    """``n`` blocks of ``stack`` (dense: a leading dense stack)."""
    kw = dict(heads=shape.heads, nope=shape.nope, rd=shape.rope_dim,
              vd=shape.v_dim, kv_rank=shape.kv_rank, eps=shape.eps,
              theta=shape.rope_theta, rotate=fault != "latent_rope_off")
    for j in range(n):
        at = lambda sub: load(ref.layer(stack[sub], j))  # noqa: E731
        x = _attn(x, at("ln1"), at("attn"), **kw)
        if not dense:
            x = _routed_mlp(x, stack, j, shape, load, fault, first_expert)
        elif fault == "dense_layer_as_routed":
            # the dense layer's own norm, the first routed layer's MLP
            x = _routed_mlp(x, dict(routed_for_dense, ln2=stack["ln2"]), j,
                            shape, load, None, first_expert)
        else:
            x = x + _gated(ref.rmsnorm(x, at("ln2"), shape.eps), at("mlp"))
    return x


@partial(jax.jit, static_argnames=("eps", "chunk"))
def _nll(x, norm, head, labels, *, eps, chunk=512):
    """Mean cross-entropy of ``labels`` [T] under ``x`` [T, d] through the
    final norm ``norm`` and the head [d, V], a chunk of positions at a
    time."""
    total = 0.0
    for lo in range(0, x.shape[0], chunk):
        logits = ref.rmsnorm(x[lo:lo + chunk], norm, eps) @ head
        gold = jnp.take_along_axis(
            logits, labels[lo:lo + chunk, None], axis=-1)[:, 0]
        total = total + (jax.nn.logsumexp(logits, axis=-1) - gold).sum()
    return total / x.shape[0]


def losses(params, ids, shape, device=None, fault=None, first_expert: int = 0):
    """(total, CE_main, CE_mtp) of ONE sequence ``ids`` [S], float32 scalars
    (traceable: ``grads`` differentiates it). ``first_expert``: which share
    of the layer the held experts are (the configuration's member is 0; the
    shares-add-up test asks for others)."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"no fault {fault!r} (have {FAULTS})")
    load = partial(ref.f32, device=device)
    ids = jnp.asarray(ids)
    S = ids.shape[0]
    with ref.HIGHEST():
        embed = load(params["embed"]["tok"])
        head = load(params["lm_head"])
        x = embed[ids]
        x = _stack(x, params["lead_layers"], shape.dense_layers, shape, load,
                   fault, first_expert, dense=True,
                   routed_for_dense=params["layers"])
        h = _stack(x, params["layers"], shape.layers, shape, load, fault,
                   first_expert)
        main = _nll(h[:-1], load(params["final_norm"]), head, ids[1:],
                    eps=shape.eps)
        total, mtp = main, jnp.zeros((), ref.F32)
        if shape.mtp:
            m = params["mtp"]
            # position i joins the next token's embedding to h_i. The last
            # position has no next token: it is given token 0's, so that
            # every stack sees S rows (one compilation), and is never scored
            # (no earlier position attends it)
            nxt = jnp.concatenate([ids[1:], jnp.zeros((1,), ids.dtype)])
            x = jnp.concatenate([
                ref.rmsnorm(embed[nxt], load(m["enorm"]), shape.eps),
                ref.rmsnorm(h, load(m["hnorm"]), shape.eps)], -1)
            x = _stack(x @ load(m["eh_proj"]), m["layers"], shape.mtp, shape,
                       load, fault, first_expert)
            shift = 1 if fault == "mtp_shift_off_by_one" else 2
            mtp = _nll(x[:S - shift], load(m["final_norm"]), head,
                       ids[shift:], eps=shape.eps)
            if fault != "mtp_off":
                total = main + shape.mtp_weight * mtp
    return total, main, mtp


def loss(params, ids, shape, device=None, fault=None) -> float:
    """The total loss ``CE_main + mtp_loss_weight x CE_mtp`` of ONE sequence
    [S] (the last position has no target, the last two none for the MTP
    module), float32 throughout."""
    return float(losses(params, np.asarray(ids), shape, device, fault)[0])


def grads(params, ids, shape, fault=None):
    """The gradient of :func:`loss` by every leaf of ``params`` (for the CPU
    tests, at a tiny size)."""
    return jax.grad(lambda p: losses(p, ids, shape, None, fault)[0])(params)


# ---- kernels ---------------------------------------------------------------
def expected_rows(shape: GlmShape, tokens: int) -> float:
    """Rows routed to the experts held here, a routed layer, for ``tokens``
    tokens under balanced routing: ``top_k x experts / routed`` a token."""
    return tokens * shape.top_k * shape.experts / shape.routed


def expert_train_cost(shape: GlmShape, rows: float, itemsize: int = 2):
    """The grouped expert products of ONE training step on one chip, every
    routed block (the main stack's and the MTP module's): (flops, bytes) the
    algorithm needs for ``rows`` rows routed to the held experts a layer.

    Nine products a layer, each 2 x rows x d x ffn: three forward (up, gate,
    down), their three dX and their three dW; a recomputed forward is NOT
    counted (as ``flops.flash_train_cost`` has it). Bytes: every product
    reads or writes one [rows, d] and one [rows, ffn] operand. An expert's
    [d, ffn] matrix is needed only where the expert holds a row, and how
    many do is not among the program's counters, so the matrices are left
    out: the bytes are a lower bound, and the products are compute-bound
    with or without them above about 1,900 rows a layer."""
    blocks = shape.layers + shape.mtp
    flops = 9 * 2 * rows * shape.d * shape.ffn
    nbytes = 9 * itemsize * rows * (shape.d + shape.ffn)
    return blocks * flops, blocks * nbytes


def latent_flash_train_cost(shape: GlmShape, batch: int, seq: int,
                            itemsize: int = 2):
    """The flash-attention kernels of ONE training step on one chip, every
    block (dense, routed, MTP): (flops, bytes), by ``flops.flash_train_cost``'s
    rules (7 causal matmuls a layer, the remat's second forward not
    counted) at this family's shapes: after the up-projection every head has
    its own keys and values, and queries, keys and values are all
    ``max(qk width, value width)`` wide."""
    blocks = shape.dense_layers + shape.layers + shape.mtp
    width = max(shape.hd, shape.v_dim)
    flops = 7 * 2 * batch * shape.heads * width * seq * seq / 2
    tensor = batch * seq * shape.heads * width * itemsize
    return blocks * flops, blocks * 12 * tensor
