"""The ``bailing_hybrid`` family: how its configuration files spell their
sizes, the plain reference of what they compute, and what its two kernels
need.

Ling-3.0-flash (inclusionAI/Ling-3.0-flash, config.json; ``model_type``
``bailing_hybrid``): embedding -> blocks -> RMSNorm -> untied head. Every
block is pre-norm (RMSNorm, eps ``rms_norm_eps``, no biases): ``h <- h +
Mixer(RMSNorm(h))``, ``h <- h + MLP(RMSNorm(h))``. The block at PUBLISHED
index ``i`` has a latent-attention mixer where ``(i + 1) % layer_group_size
== 0`` and a KDA mixer elsewhere; a dense SwiGLU where ``i <
first_k_dense_replace`` and the routed layer elsewhere.

KDA (Kimi Delta Attention, arXiv:2510.26692), ``H`` heads of ``hd``: ``q~,
k~, v~ = x W``; a depthwise causal convolution over time of
``short_conv_kernel_size`` taps a channel, as that many shifted products
(rows before the first token are zero), then SiLU; q and k L2-normalised a
head (eps 1e-6), q times ``hd ** -0.5``; a log-decay a channel ``g = lower
sigmoid(exp(A_log_h) (x W_alpha + dt_bias))``, a step size a head ``beta =
sigmoid(x W_beta)``; a float32 state a head from zero, TOKEN BY TOKEN under a
scan: ``S <- Diag(exp g) S``, ``S <- S + beta k (v - S^T k)^T``, ``o = S^T
q``; ``y = W_o (sigmoid(x W_g)_h * RMSNorm_head(o))``, one gate a head.

Latent attention: ``q = x W_q`` (no query latent), 128 no-position + 64
rotary values a head; ``[c ; k_pe] = x W_kv_a``, ``c`` RMS-normed; keys and
values UP-PROJECTED by ``W_kv_b`` (not absorbed); rotary (half-split pairs,
``rope_theta``) on the 64, one ``k_pe`` for all heads; causal softmax at
``192 ** -0.5``; the same head-wise gate.

Routed layer: sigmoid scores over all ``routed`` outputs plus a selection
bias; a group scores the sum of its two best biased scores, ``topk_group``
groups kept, top-k among them, weights the unbiased scores of the chosen,
normalised, times ``routed_scaling_factor``; only the ``experts`` held here
(``first_expert ..``) are computed and that partial sum goes on, beside the
shared expert.

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
from benchmarks.flops import Shape

CHUNK = 128  # rows a serving step feeds a slot (the chunk faults' unit)

FAULTS = (
    "decay_off",          # exp(g) = 1: the state forgets nothing
    "beta_off",           # a step size of 1 at every row
    "erase_off",          # no k k^T term: the row is added, nothing erased
    "conv_rows_dropped",  # the 3 rows before a chunk's first not carried
    "state_not_reset",    # a request starts from the state its slot held
    "state_bf16",         # the state held in bf16 between chunks
    "gate_off",           # no output gate, either mixer
    "qk_l2_off",          # q and k of a KDA layer not normalised
    "latent_rope_off",    # the latent layers' rotary values not rotated
    "group_limit_off",    # top-k over every group
    "held_offset_off",    # the held experts read the next group's weights
    "shared_off",         # no shared expert
    "scaling_off",        # routing weights not times routed_scaling_factor
    "kinds_shifted",      # the mixer kinds one layer early
    "weights_int8",       # every matrix rounded to 8 bits a column
)


@dataclass(frozen=True)
class LingShape(Shape):
    """``flops.Shape`` (``layers`` the ROUTED layers as run, ``ffn`` an
    expert's width, ``experts`` those held here) plus what the hybrid adds."""

    layer_ids: tuple = ()   # each layer's published index, as run
    group: int = 6          # every group-th published layer is latent
    first_dense: int = 2    # published layers before the routed ones
    dense_ffn: int = 0
    shared: int = 0
    routed: int = 0         # the router's outputs
    first_expert: int = 0
    groups: int = 1
    groups_kept: int = 1
    routed_scale: float = 1.0
    kv_rank: int = 0
    nope: int = 0
    rope_dim: int = 0
    v_dim: int = 0
    conv: int = 4
    lower: float = -5.0

    def kind(self, i: int, shift: int = 0) -> str:
        return "latent" if (i + 1 + shift) % self.group == 0 else "kda"

    def count(self, kind: str) -> int:
        return sum(self.kind(i) == kind for i in self.layer_ids)

    def mixer_matmul_params(self, kind: str) -> int:
        wide = self.heads * self.hd
        if kind == "kda":
            return 5 * self.d * wide + 2 * self.d * self.heads
        return (self.d * self.heads * (self.nope + self.rope_dim)
                + self.d * (self.kv_rank + self.rope_dim)
                + self.kv_rank * self.heads * (self.nope + self.v_dim)
                + self.heads * self.v_dim * self.d + self.d * self.heads)

    def layer_matmul_params(self, active: bool = True) -> int:
        """The mean over the routed layers as run: the mixers of every
        layer, a token's ``top_k`` experts (or those stored), the shared
        expert and the router; the one dense layer's MLP is spread over
        them."""
        mix = sum(self.mixer_matmul_params(self.kind(i))
                  for i in self.layer_ids)
        dense = 3 * self.d * self.dense_ffn * (
            len(self.layer_ids) - self.layers)
        n = self.top_k if active else self.experts
        mlp = 3 * self.d * (n * self.ffn + self.shared) + self.d * self.routed
        return (mix + dense) // self.layers + mlp

    def attention_flops_per_token(self, context: float) -> float:
        """The latent layers' scores and values over the context (absorbed:
        a key is ``kv_rank + rope_dim`` wide, a value ``kv_rank``) and the
        KDA layers' state decay, erase, write and read-out."""
        latent = 2 * self.heads * (2 * self.kv_rank + self.rope_dim) * context
        kda = 8 * self.heads * self.hd * self.hd
        return self.count("latent") * latent + self.count("kda") * kda


def shape_of(config: dict) -> LingShape:
    """The published keys of Ling-3.0-flash's ``config.json``; ``layer_ids``
    and ``published`` say which layers of the release are run."""
    pub = config["published"]
    ids = tuple(int(i) for i in config["layer_ids"])
    assert len(ids) == int(config["num_hidden_layers"])
    first_dense = int(pub["first_k_dense_replace"])
    assert sum(i < first_dense for i in ids) == int(
        config["first_k_dense_replace"])
    return LingShape(
        config["family"], int(config["hidden_size"]),
        sum(i >= first_dense for i in ids),
        int(config["num_attention_heads"]),
        int(config["num_key_value_heads"]), int(config["head_dim"]),
        int(config["moe_intermediate_size"]), int(config["vocab_size"]),
        int(config["num_experts"]), int(config["num_experts_per_tok"]),
        True, bool(config.get("tie_word_embeddings", False)),
        float(config["rms_norm_eps"]), float(config["rope_theta"]),
        layer_ids=ids, group=int(config["layer_group_size"]),
        first_dense=first_dense, dense_ffn=int(config["intermediate_size"]),
        shared=int(config["moe_shared_expert_intermediate_size"])
        * int(config["num_shared_experts"]),
        routed=int(pub["num_experts"]),
        first_expert=int(config.get("first_expert", 0)),
        groups=int(config["n_group"]), groups_kept=int(config["topk_group"]),
        routed_scale=float(config["routed_scaling_factor"]),
        kv_rank=int(config["kv_lora_rank"]),
        nope=int(config["qk_nope_head_dim"]),
        rope_dim=int(config["qk_rope_head_dim"]),
        v_dim=int(config["v_head_dim"]),
        conv=int(config["short_conv_kernel_size"]),
        lower=float(config["kda_lower_bound"]))


@partial(jax.jit, static_argnames=("bits",))
def _up(w, bits: int = 0):
    """A served matrix (or vector) as the reference reads it: float32 and,
    with ``bits``, a matrix rounded to that many bits (symmetric, to
    nearest, one scale a column)."""
    w = w.astype(ref.F32)
    if bits and w.ndim == 2 and min(w.shape) > 8:
        top = 2 ** (bits - 1) - 1
        scale = jnp.abs(w).max(axis=0, keepdims=True) / top
        return jnp.clip(jnp.round(w / scale), -top - 1, top) * scale
    return w


def _head_norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


# -------------------------------------------------------------------- KDA
def _delta_rule(q, k, v, g, beta, s0, fault=None):
    """The recurrence a token at a time: rows q / k / v / g [S, H, hd], beta
    [S, H], state ``s0`` [H, hd, hd] (key channel, value channel) -> (o
    [S, H, hd], the state after the last row)."""
    def step(s, t):
        i, qt, kt, vt, gt, bt = t
        s = s * jnp.exp(gt)[:, :, None]
        # (sums over the key channel as plain reductions: a matrix product of
        # one row a head a token is what made 5,000 steps take minutes)
        seen = (kt[:, :, None] * s).sum(1)
        if fault == "erase_off":
            seen = jnp.zeros_like(seen)
        s = s + kt[:, :, None] * (bt[:, None] * (vt - seen))[:, None, :]
        o = (qt[:, :, None] * s).sum(1)
        if fault == "state_bf16":  # as held between a step and the next
            s = jnp.where(i % CHUNK == CHUNK - 1,
                          s.astype(jnp.bfloat16).astype(ref.F32), s)
        return s, o

    s, o = jax.lax.scan(step, s0, (jnp.arange(q.shape[0]), q, k, v, g, beta))
    return o, s


@partial(jax.jit, static_argnames=("heads", "hd", "eps", "taps", "lower",
                                   "fault"))
def _kda(h, a, *, heads, hd, eps, taps, lower, fault=None):
    """One KDA mixer over normed inputs ``h`` [S, d]."""
    S = h.shape[0]
    wide = heads * hd
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
    if fault != "qk_l2_off":
        q = q / jnp.sqrt((q * q).sum(-1, keepdims=True) + 1e-6)
        k = k / jnp.sqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    q = q * hd ** -0.5
    g = lower * jax.nn.sigmoid(jnp.exp(a["A_log"])[None, :, None] * (
        h @ a["walpha"] + a["dt_bias"]).reshape(S, heads, hd))
    if fault == "decay_off":
        g = jnp.zeros_like(g)
    beta = jax.nn.sigmoid(h @ a["wbeta"])
    if fault == "beta_off":
        beta = jnp.ones_like(beta)
    s0 = jnp.zeros((heads, hd, hd), ref.F32)
    if fault == "state_not_reset":  # what the slot's last request left
        _, s0 = _delta_rule(q, k, v, g, beta, s0)
    o, _ = _delta_rule(q, k, v, g, beta, s0, fault)
    o = _head_norm(o, a["o_norm"]["scale"], eps)
    if fault != "gate_off":
        o = o * jax.nn.sigmoid(h @ a["wgate"])[:, :, None]
    return o.reshape(S, wide) @ a["wo"]


# ----------------------------------------------------------------- latent
def _latent(h, a, shape: LingShape, fault=None):
    """One latent-attention mixer over normed inputs ``h`` [S, d], keys and
    values up-projected a head (nothing absorbed)."""
    S = h.shape[0]
    H, nope, rd, vd, kl = (shape.heads, shape.nope, shape.rope_dim,
                           shape.v_dim, shape.kv_rank)
    q = (h @ a["wq"]).reshape(S, H, nope + rd)
    kv_a = h @ a["wkv_a"]
    c = _head_norm(kv_a[:, :kl], a["kv_norm"]["scale"], shape.eps)
    kv = (c @ a["wkv_b"]).reshape(S, H, nope + vd)
    q_pe, k_pe = q[..., nope:], kv_a[:, None, kl:]
    if fault != "latent_rope_off":
        q_pe = ref.rope(q_pe, shape.rope_theta)
        k_pe = ref.rope(k_pe, shape.rope_theta)
    qf = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    kf = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (S, H, rd))], axis=-1)
    o = ref.causal_attention(qf, kf, kv[..., nope:])  # at (nope + rd) ** -.5
    if fault != "gate_off":
        o = o * jax.nn.sigmoid(h @ a["wgate"])[:, :, None]
    return o.reshape(S, H * vd) @ a["wo"]


# ----------------------------------------------------------------- routed
def route(h, router, bias, shape: LingShape, first: int, held: int,
          fault=None):
    """Normed rows ``h`` [S, d] -> (routing weights [S, held] of the experts
    ``first .. first + held``, zero where not chosen; margin [S]: the least
    change of a biased score that would move one of THOSE experts, or its
    group, into or out of the choice)."""
    s = jax.nn.sigmoid(h @ router)
    c = s + bias[None, :]
    S, E = c.shape
    G, per, K = shape.groups, E // shape.groups, shape.top_k
    open_to = c
    limited = fault != "group_limit_off" and shape.groups_kept < G
    if limited:
        two, _ = jax.lax.top_k(c.reshape(S, G, per), min(2, per))
        group_score = two.sum(-1)
        edge, kept = jax.lax.top_k(group_score, shape.groups_kept + 1)
        kept = kept[:, :shape.groups_kept]
        is_kept = (kept[:, :, None] == jnp.arange(G)).any(1)
        open_to = jnp.where(jnp.repeat(is_kept, per, axis=1), c, -jnp.inf)
    top, idx = jax.lax.top_k(open_to, K + 1)
    mine = open_to[:, first:first + held]
    last_in, first_out = top[:, K - 1, None], top[:, K, None]
    margin = jnp.where(mine >= last_in, mine - first_out,
                       last_in - mine).min(1)
    if limited:
        for g in range(first // per, (first + held - 1) // per + 1):
            margin = jnp.minimum(margin, jnp.where(
                is_kept[:, g], group_score[:, g] - edge[:, -1],
                edge[:, -2] - group_score[:, g]))
    idx = idx[:, :K]
    w = jnp.take_along_axis(s, idx, axis=1)
    w = w / w.sum(-1, keepdims=True)
    if fault != "scaling_off":
        w = w * shape.routed_scale
    full = jnp.zeros_like(c).at[jnp.arange(S)[:, None], idx].set(w)
    if fault == "held_offset_off":  # the next group's columns
        first = (first + held) % E
    return full[:, first:first + held], margin


@partial(jax.jit, static_argnames="bits")
def _add_experts(x, h, w, wg, wi, wo, i, bits: int = 0):
    """x + every held expert of routed layer ``i`` on EVERY token, weighted
    by its routing weight (zero where the token was not sent to it), one
    expert after the other: plain and wasteful on purpose. The banks come
    whole ([L, E, ...], as served); one matrix at a time is cut out and
    upcast inside the loop."""
    def mat(bank, e):
        return _up(jax.lax.dynamic_slice(
            bank, (i, e, 0, 0), (1, 1, *bank.shape[2:]))[0, 0], bits=bits)

    def add(e, x):
        y = (jax.nn.silu(h @ mat(wg, e)) * (h @ mat(wi, e))) @ mat(wo, e)
        return x + y * jax.lax.dynamic_index_in_dim(
            w, e, 1, keepdims=False)[:, None]

    return jax.lax.fori_loop(0, wg.shape[1], add, x)


@jax.jit
def _gated(h, m):
    return (jax.nn.silu(h @ m["wg"]) * (h @ m["wi"])) @ m["wo"]


def routed_block(x, ln2, m, j, shape: LingShape, load, first=None,
                 fault=None, bits: int = 0, shared: bool = True):
    """``x`` + the routed layer ``j`` of the bank ``m`` (as served, stacked
    over layers): the partial sum of the experts held (as many as the bank
    holds, from ``first``) and, with ``shared``, the shared expert. Returns
    (x, margin [S])."""
    first = shape.first_expert if first is None else first
    h = ref.rmsnorm(x, ln2, shape.eps)
    w, margin = route(h, load(m["router"][j]), load(m["sel_bias"][j]), shape,
                      first, m["wi"].shape[1], fault)
    if shared and fault != "shared_off":
        x = x + _gated(h, load(ref.layer(m["shared"], j)))
    return _add_experts(x, h, w, m["wg"], m["wi"], m["wo"], j,
                        bits=bits), margin


def faulted(params, fault, shape, device=None) -> dict:
    """What ``logits`` is handed under ``fault`` (one of ``FAULTS``, or None),
    as its keywords: every fault here is arithmetic of the reference itself,
    but the rounding, which is done as each matrix is upcast (``bits``)."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"no fault {fault!r} (have {FAULTS})")
    if fault is not None and fault.startswith("weights_int"):
        return dict(params=params, bits=int(fault[len("weights_int"):]))
    return dict(params=params, fault=fault)


def hidden(params, ids, shape: LingShape, device=None, fault=None,
           bits: int = 0):
    """[S] token ids -> (hidden before the final norm [S, d] float32, the
    smallest routing margin of each position over the routed layers [S])."""
    def load(tree):
        return jax.tree.map(
            lambda w: _up(jax.device_put(w, device), bits=bits), tree)

    on_device = lambda tree: jax.tree.map(
        lambda w: jax.device_put(w, device), tree)
    margin = jnp.full((len(ids),), jnp.inf, ref.F32)
    seen = {"kda": 0, "latent": 0, "dense": 0, "routed": 0}
    shift = 1 if fault == "kinds_shifted" else 0
    with ref.HIGHEST():
        x = jax.device_put(params["embed"]["tok"][jnp.asarray(ids)],
                           device).astype(ref.F32)
        for i in shape.layer_ids:
            kind = shape.kind(i, shift)
            mlp = "dense" if i < shape.first_dense else "routed"
            j, jm = seen[kind], seen[mlp]
            seen[kind], seen[mlp] = j + 1, jm + 1
            L = params[kind + "_layers"]
            h = ref.rmsnorm(x, load(ref.layer(L["ln1"], j)), shape.eps)
            a = load(ref.layer(L["attn"], j))
            if kind == "kda":
                x = x + _kda(h, a, heads=shape.heads, hd=shape.hd,
                             eps=shape.eps, taps=shape.conv,
                             lower=shape.lower, fault=fault)
            else:
                x = x + _latent(h, a, shape, fault)
            M = params["lead_layers" if mlp == "dense" else "layers"]
            ln2 = load(ref.layer(M["ln2"], jm))
            if mlp == "dense":
                x = x + _gated(ref.rmsnorm(x, ln2, shape.eps),
                               load(ref.layer(M["mlp"], jm)))
                continue
            x, mg = routed_block(x, ln2, on_device(M["mlp"]), jm, shape, load,
                                 fault=fault, bits=bits)
            margin = jnp.minimum(margin, mg)
    return x, margin


def logits(params, ids, shape: LingShape, device=None,
           last: int | None = None, with_margin: bool = False,
           fault: str | None = None, bits: int = 0):
    """Logits float32 over the vocabulary slice for the last ``last``
    positions (all if None); with ``with_margin`` also each of those
    positions' smallest routing margin over the layers: how near an expert
    held here (or its group) was to changing sides. ``fault`` and ``bits``
    break the reference on purpose: ``faulted`` makes both from a name."""
    x, margin = hidden(params, ids, shape, device, fault, bits)
    if last is not None:
        x, margin = x[-last:], margin[-last:]
    up = lambda w: _up(jax.device_put(w, device), bits=bits)
    with ref.HIGHEST():
        out = ref.rmsnorm(x, jax.tree.map(up, params["final_norm"]),
                          shape.eps) @ up(params["lm_head"])
    return (out, margin) if with_margin else out


# ---- kernels ---------------------------------------------------------------
def kda_cost(shape: LingShape, rows: float, state_slots: float,
             itemsize: int = 2):
    """The delta rule of ONE KDA layer: (flops, bytes) the traced steps
    needed. For every real row a head's decay of the state (hd x hd), the
    erase ``k^T S``, the write ``k (..)^T`` and the read-out ``q^T S``, 2 x
    hd x hd each: the recurrence's own count, the least any form of it does
    (the chunk form's solve is more). Bytes: every live state read and
    written once a slot a step, float32; the real rows' q, k, v in and o
    out, their log-decays (float32) and step sizes."""
    H, hd = shape.heads, shape.hd
    flops = 7 * H * hd * hd * rows
    state = 2 * H * hd * hd * 4 * state_slots
    per_row = 4 * H * hd * itemsize + H * hd * 4 + H * 4
    return flops, state + per_row * rows


def latent_walk_cost(shape: LingShape, context_keys: float,
                     keys_walked: float, rows: float, itemsize: int = 2):
    """Latent attention of ONE latent layer in the absorbed form: (flops,
    bytes) the traced steps needed. ``context_keys``: for every real query
    the cached latents at or before it, summed: each pair costs every
    head's score over the whole row (``kv_rank + rope_dim``) and its value
    over ``kv_rank``. Bytes: ``keys_walked`` latent rows, those at or before
    a slot's last real query, once a slot, and the real rows' absorbed
    queries in and attended latents out."""
    H, kl, rd = shape.heads, shape.kv_rank, shape.rope_dim
    flops = 2 * H * (2 * kl + rd) * context_keys
    row_bytes = (kl + rd) * itemsize
    return flops, row_bytes * keys_walked + H * (
        2 * kl + rd) * itemsize * rows
