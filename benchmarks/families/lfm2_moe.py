"""The ``lfm2_moe`` family: how its configuration files spell their sizes, the
plain reference of what they compute, and what its attention call needs.

LFM2-8B-A1B (LiquidAI/LFM2-8B-A1B, config.json; ``model_type``
``lfm2_moe``): embedding -> blocks -> RMSNorm -> the embedding transposed. A
block is pre-norm, no biases: ``h <- h + Mixer(N1(h))``, ``h <- h +
MLP(N2(h))``; every norm is the plain RMSNorm ``x / rms(x) * w``, eps
``norm_eps``. The block at PUBLISHED index ``i`` has the mixer
``layer_types[i]`` and a dense MLP where ``i < num_dense_layers``, an expert
layer elsewhere.

Gated short convolution (``conv``; ``K`` = ``conv_L_cache`` taps): ``[B | C
| x~] = x W_in``, three equal runs of columns (the release's order as the
author of the issue remembers it; under drawn weights a relabelling:
DEPARTURE 1, named in the configuration's ``assumed``); ``u = B * x~``; ``c_t
= sum_j w_j u_{t - (K - 1) + j}``, depthwise and causal, as ``K`` shifted
products with ``u`` zero before the first token; NO activation; ``y = W_out
(C * c)``.

Attention (``full_attention``): ``q, k, v = x W_q, x W_k, x W_v``; q and k
RMS-normed a head (plain scale) BEFORE rotary; rotary on the whole head
(half-split pairs, ``rope_theta``); query heads ``G j .. G j + G - 1`` read
KV head ``j``; causal softmax at ``head_dim ** -0.5`` in query blocks; ``y =
W_o attn``.

Dense MLP: ``W_2 (SiLU(W_1 x) * W_3 x)`` at ``intermediate_size``. Expert
layer: ``s = sigmoid(x W_r)`` float32; the ``top_k`` largest of ``s + b`` are
chosen (ties to the lower index); their weights are ``s`` over ``sum +
1e-6`` (``norm_topk_prob``), times ``routed_scaling_factor``; only the
``experts`` held here (``first_expert ..``) are computed, each a SwiGLU of
``moe_intermediate_size``. No shared expert.

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
# the upcast that rounds under ``weights_int8``, the held experts one after
# the other and the wide dense MLP in pieces are DeepSeek's
from benchmarks.families.deepseek import _add_experts, _gated_served, _up
from benchmarks.flops import Shape

CHUNK = 256  # rows a serving step feeds a slot (the chunk faults' unit)
CONV, FULL = "conv", "full_attention"
NORM_EPS = 1e-6  # what the release adds to the sum of the chosen scores

FAULTS = (
    "gate_in_off",        # u = x~: the input gate B left out
    "gate_out_off",       # y = W_out c: the output gate C left out
    "conv_rows_dropped",  # the 2 rows before a chunk's first not carried
    "conv_taps_reversed",  # the newest row under the oldest row's tap
    "conv_silu_on",       # an activation after the convolution
    "conv_not_reset",     # a request starts from the rows its slot held
    "kv_pair_swapped",    # a query head reads the OTHER head of its lane pair
    "kv_group_off",       # a query head reads the next group's KV head
    "qk_norm_off",        # q and k heads not normed
    "rope_half_head",     # the first 32 values of a head rotated, not all 64
    "bias_weighs",        # the weights from s + b
    "bias_off",           # the choice from s
    "topk_norm_off",      # the chosen weights not normalised
    "lead_routed",        # layer 1 routed (with layer 2's experts)
    "dense_width_off",    # the dense MLPs at half their width
    "kinds_shifted",      # the mixer kinds one layer early
    "head_untied",        # a head of its own, not the embedding
    "weights_int8",       # every matrix rounded to 8 bits a column
)


@dataclass(frozen=True)
class Lfm2Shape(Shape):
    """``flops.Shape`` (``layers`` the ROUTED layers as run, ``ffn`` an
    expert's width, ``experts`` those held here) plus what the hybrid
    adds."""

    layer_ids: tuple = ()    # each layer's published index, as run
    layer_types: tuple = ()  # the PUBLISHED list, every layer's kind
    first_dense: int = 0     # published layers below it have a dense MLP
    dense_ffn: int = 0
    conv: int = 3
    routed: int = 0          # the router's outputs
    first_expert: int = 0
    routed_scale: float = 1.0

    @property
    def dense_layers(self) -> int:
        return sum(i < self.first_dense for i in self.layer_ids)

    def kind(self, i: int, shift: int = 0) -> str:
        return self.layer_types[(i + shift) % len(self.layer_types)]

    def count(self, kind: str) -> int:
        return sum(self.kind(i) == kind for i in self.layer_ids)

    kind_layers = count  # (the readers of a model with layer kinds ask so)

    def mixer_matmul_params(self, kind: str) -> int:
        if kind == CONV:
            return 4 * self.d * self.d
        return 2 * self.d * self.hd * (self.heads + self.kv_heads)

    def layer_matmul_params(self, active: bool = True) -> int:
        """The mean over the ROUTED layers as run (``layers`` of them), the
        leading dense layers' share spread over them: the mixers, the
        router and a token's ``top_k`` experts (or those stored)."""
        mix = sum(self.mixer_matmul_params(self.kind(i))
                  for i in self.layer_ids)
        dense = self.dense_layers * 3 * self.d * self.dense_ffn
        n = self.top_k * self.experts / self.routed if active else self.experts
        return int((mix + dense) / self.layers + self.d * self.routed
                   + 3 * self.d * n * self.ffn)

    def attention_flops_per_token(self, context: float) -> float:
        """The attention layers' scores and values over the context and the
        convolutions' taps and two gates."""
        full = 2 * 2 * self.heads * self.hd * context
        return self.count(FULL) * full + self.count(CONV) * (
            2 * self.conv + 2) * self.d


def shape_of(config: dict) -> Lfm2Shape:
    """The published keys of LFM2's ``config.json``; ``layer_ids`` and
    ``published`` say which layers and experts of the release are run."""
    ids = tuple(int(i) for i in config["layer_ids"])
    if len(ids) != int(config["num_hidden_layers"]):
        raise ValueError("layer_ids names num_hidden_layers layers")
    types = tuple(config["layer_types"])
    if set(types) - {CONV, FULL} or max(ids) >= len(types):
        raise ValueError(f"layer_types names {CONV} and {FULL} layers, one "
                         "a published layer")
    if config.get("conv_bias") or not config.get("norm_topk_prob", True) or (
            not config.get("use_expert_bias", True)):
        raise ValueError("the convolution has no bias, the chosen weights "
                         "are renormalised and the choice is biased "
                         "(conv_bias false, norm_topk_prob, use_expert_bias)")
    first_dense = int(config["num_dense_layers"])
    heads, d = int(config["num_attention_heads"]), int(config["hidden_size"])
    return Lfm2Shape(
        config["family"], d, sum(i >= first_dense for i in ids), heads,
        int(config["num_key_value_heads"]),
        int(config.get("head_dim", d // heads)),
        int(config["moe_intermediate_size"]), int(config["vocab_size"]),
        int(config["num_experts"]), int(config["num_experts_per_tok"]), True,
        bool(config.get("tie_word_embeddings", True)),
        float(config["norm_eps"]), float(config["rope_theta"]),
        layer_ids=ids, layer_types=types, first_dense=first_dense,
        dense_ffn=int(config["intermediate_size"]),
        conv=int(config["conv_L_cache"]),
        routed=int(config["published"]["num_experts"]),
        first_expert=int(config.get("first_expert", 0)),
        routed_scale=float(config["routed_scaling_factor"]))


# ------------------------------------------------ gated short convolution
@partial(jax.jit, static_argnames=("taps", "fault"))
def _conv(h, a, *, taps, fault=None):
    """One gated short convolution over normed inputs ``h`` [S, d] of ONE
    request from its first token."""
    S, d = h.shape
    bcx = h @ a["win"]
    gate_in, gate_out, xt = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    u = xt if fault == "gate_in_off" else gate_in * xt
    ext = jnp.concatenate([jnp.zeros((taps - 1, d), ref.F32), u])
    if fault == "conv_not_reset":  # what the slot's last request left: its
        # own last rows (the same prompt served twice in one slot)
        ext = jnp.concatenate([u[S - (taps - 1):], u])
    t = jnp.arange(S)
    w = a["conv"][::-1] if fault == "conv_taps_reversed" else a["conv"]
    c = 0.0
    for i in range(taps):  # c_t = sum_i w_i u_{t - (taps - 1) + i}
        back = taps - 1 - i
        rows = ext[i:i + S]
        if fault == "conv_rows_dropped" and back:
            rows = jnp.where((t % CHUNK >= back)[:, None], rows, 0.0)
        c = c + rows * w[i]
    if fault == "conv_silu_on":
        c = jax.nn.silu(c)
    return (c if fault == "gate_out_off" else gate_out * c) @ a["wout"]


# --------------------------------------------------------------- attention
@partial(jax.jit, static_argnames=("shape", "fault"))
def _attn(h, a, shape: Lfm2Shape, fault=None):
    """One attention mixer over normed inputs ``h`` [S, d]."""
    S = h.shape[0]
    H, KV, hd = shape.heads, shape.kv_heads, shape.hd
    q = (h @ a["wq"]).reshape(S, H, hd)
    k = (h @ a["wk"]).reshape(S, KV, hd)
    v = (h @ a["wv"]).reshape(S, KV, hd)
    if fault != "qk_norm_off":
        q = ref.rmsnorm(q, a["q_norm"], shape.eps)
        k = ref.rmsnorm(k, a["k_norm"], shape.eps)
    if fault == "rope_half_head":
        half = lambda x: jnp.concatenate(
            [ref.rope(x[..., :hd // 2], shape.rope_theta), x[..., hd // 2:]],
            -1)
        q, k = half(q), half(k)
    else:
        q, k = ref.rope(q, shape.rope_theta), ref.rope(k, shape.rope_theta)
    if fault == "kv_pair_swapped":  # KV heads 2 j and 2 j + 1 exchanged
        swap = jnp.arange(KV) ^ 1
        k, v = k[:, swap], v[:, swap]
    if fault == "kv_group_off":
        k, v = jnp.roll(k, -1, axis=1), jnp.roll(v, -1, axis=1)
    o = ref.causal_attention(q, k, v)  # at hd ** -0.5, in query blocks
    return o.reshape(S, H * hd) @ a["wo"]


# ------------------------------------------------------------------ routed
@partial(jax.jit, static_argnames=("top_k", "scale", "first", "held", "eps",
                                   "fault"))
def _route(x, ln2, router, bias, *, top_k, scale, first, held, eps,
           fault=None):
    """(normed input, routing weights [S, held] of the experts held here,
    zero where not chosen; margin [S]: the least change of a biased score
    that would move an expert held here into or out of the choice)."""
    h = ref.rmsnorm(x, ln2, eps)
    s = jax.nn.sigmoid(h @ router)
    c = s if fault == "bias_off" else s + bias[None, :]
    top, idx = jax.lax.top_k(c, top_k + 1)
    mine = c[:, first:first + held]
    last_in, first_out = top[:, top_k - 1, None], top[:, top_k, None]
    margin = jnp.where(mine >= last_in, mine - first_out, last_in - mine).min(1)
    idx = idx[:, :top_k]
    w = jnp.take_along_axis(c if fault == "bias_weighs" else s, idx, axis=1)
    if fault != "topk_norm_off":
        w = w / (w.sum(-1, keepdims=True) + NORM_EPS)
    full = jnp.zeros_like(c).at[jnp.arange(c.shape[0])[:, None], idx].set(
        w * scale)
    return h, full[:, first:first + held], margin


def routed_block(x, ln2, m, j, shape: Lfm2Shape, load, first=None, bank=None,
                 fault=None, bits: int = 0):
    """``x`` + the routed layer ``j`` of the stack ``m`` (as served): the
    partial sum of the experts held (as many as ``bank`` holds, ``m``'s own
    by default, from ``first``). Returns (x, margin [S])."""
    first = shape.first_expert if first is None else first
    bank = bank or m
    h, w, margin = _route(
        x, ln2, load(m["router"][j]), load(m["sel_bias"][j]),
        top_k=shape.top_k, scale=shape.routed_scale, first=first,
        held=bank["wi"].shape[1], eps=shape.eps, fault=fault)
    return _add_experts(x, h, w, bank["wg"], bank["wi"], bank["wo"], j,
                        bits=bits), margin


def _dense_block(x, ln2, m, j, shape: Lfm2Shape, fault, bits):
    """``x`` + the dense MLP ``j`` of the stack ``m`` (as served)."""
    m = ref.layer(m, j)
    if fault == "dense_width_off":
        half = m["wi"].shape[1] // 2
        m = {"wi": m["wi"][:, :half], "wg": m["wg"][:, :half],
             "wo": m["wo"][:half]}
    return x + _gated_served(ref.rmsnorm(x, ln2, shape.eps), m, bits)


def faulted(params, fault, shape, device=None) -> dict:
    """What ``logits`` is handed under ``fault`` (one of ``FAULTS``, or None),
    as its keywords: every fault here is arithmetic of the reference itself,
    but the rounding, which is done as each matrix is upcast (``bits``)."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"no fault {fault!r} (have {FAULTS})")
    if fault is not None and fault.startswith("weights_int"):
        return dict(params=params, bits=int(fault[len("weights_int"):]))
    return dict(params=params, fault=fault)


def hidden(params, ids, shape: Lfm2Shape, device=None, fault=None,
           bits: int = 0, first_expert=None, bank=None):
    """[S] token ids -> (hidden before the final norm [S, d] float32, the
    smallest routing margin of each position over the layers [S]).
    ``first_expert`` / ``bank``: which share of the layer the held experts
    are and the banks to read them from: the shares-add-up test asks for
    other members' and for the uncut layer."""
    def load(tree):
        return jax.tree.map(
            lambda w: _up(jax.device_put(w, device), bits=bits), tree)

    margin = jnp.full((len(ids),), jnp.inf, ref.F32)
    seen = {CONV: 0, FULL: 0, True: 0, False: 0}  # (True: dense MLPs)
    stack = {CONV: "conv_layers", FULL: "attn_layers"}
    shift = 1 if fault == "kinds_shifted" else 0
    with ref.HIGHEST():
        x = jax.device_put(params["embed"]["tok"][jnp.asarray(ids)],
                           device).astype(ref.F32)
        for i in shape.layer_ids:
            kind = shape.kind(i, shift)
            L = params[stack[kind]]
            # (shifted kinds may name one layer more than a stack holds)
            j = min(seen[kind], L["ln1"]["scale"].shape[0] - 1)
            seen[kind] += 1
            h = ref.rmsnorm(x, load(ref.layer(L["ln1"], j)), shape.eps)
            a = load(ref.layer(L["attn"], j))
            x = x + (_conv(h, a, taps=shape.conv, fault=fault)
                     if kind == CONV else _attn(h, a, shape, fault))
            dense = i < shape.first_dense
            if fault == "lead_routed" and i == shape.first_dense - 1:
                dense, j = False, 0  # the first routed layer's, not its own
            else:
                j = seen[dense]
                seen[dense] = j + 1
            M = params["lead_layers" if dense else "layers"]
            ln2 = load(ref.layer(M["ln2"], j))
            if dense:
                x = _dense_block(x, ln2, M["mlp"], j, shape, fault, bits)
            else:
                x, mg = routed_block(x, ln2, M["mlp"], j, shape, load,
                                     first=first_expert, bank=bank,
                                     fault=fault, bits=bits)
                margin = jnp.minimum(margin, mg)
    return x, margin


def logits(params, ids, shape: Lfm2Shape, device=None,
           last: int | None = None, with_margin: bool = False,
           fault: str | None = None, bits: int = 0, **share):
    """Logits float32 over the vocabulary for the last ``last`` positions
    (all if None); with ``with_margin`` also each of those positions'
    smallest routing margin over the layers: how near an expert was to
    changing sides, in biased scores. ``fault`` and ``bits`` break the
    reference on purpose (``faulted`` makes both from a name); ``share`` is
    :func:`hidden`'s ``first_expert`` / ``bank``."""
    x, margin = hidden(params, ids, shape, device, fault, bits, **share)
    if last is not None:
        x, margin = x[-last:], margin[-last:]
    up = lambda w: _up(jax.device_put(w, device), bits=bits)
    with ref.HIGHEST():
        head = up(params["embed"]["tok"]).T
        if fault == "head_untied":  # a matrix of its own: the rows reversed
            head = head[:, ::-1]
        out = ref.rmsnorm(x, jax.tree.map(up, params["final_norm"]),
                          shape.eps) @ head
    return (out, margin) if with_margin else out


# ---- kernels ---------------------------------------------------------------
def full_attention_cost(shape: Lfm2Shape, attended_keys: float,
                        fetched_keys: float, query_rows: float,
                        itemsize: int = 2):
    """The paged attention call of ONE attention layer: (flops, bytes) that
    the work needs. ``attended_keys``: for every real query token, the keys
    it sees (its whole context), summed. FLOPs: QK^T and PV at the 64-WIDE
    products every real (query, key) pair needs, 2 x 2 x heads x head_dim,
    not the 128-lane ones a lane pairing spends. Bytes: K and V of the
    ``fetched_keys``, the keys of the pages that hold a key some row of the
    slot sees, each once for all the rows and all the query heads of its
    slot, and the queries in and the outputs out for ``query_rows`` rows."""
    flops = 2 * 2 * shape.heads * shape.hd * attended_keys
    kv = 2 * shape.kv_heads * shape.hd * itemsize * fetched_keys
    q_out = 2 * shape.heads * shape.hd * itemsize * query_rows
    return flops, kv + q_out
