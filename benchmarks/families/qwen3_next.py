"""The ``qwen3_next`` family: how its configuration files spell their sizes,
the plain reference of what they compute, and what its two kernels need.

Qwen3-Next-80B-A3B-Instruct (Qwen/Qwen3-Next-80B-A3B-Instruct, config.json;
``model_type`` ``qwen3_next``): embedding -> blocks -> RMSNorm -> untied
head. A block is pre-norm, no biases: ``h <- h + Mixer(N(h))``, ``h <- h +
MoE(N(h))``. ``N`` is RMSNorm with a ZERO-CENTRED scale, ``x / rms(x) * (1 +
w)``, eps ``rms_norm_eps``; the served tree holds ``1 + w`` under ``scale``
(a loader adds the one; the harness draws every scale one), so ``N`` reads
``* scale``: DEPARTURE 1, named in the configuration's ``assumed``. The
block at PUBLISHED index ``i`` has gated attention where ``(i + 1) %
full_attention_interval == 0`` and Gated DeltaNet elsewhere; every block is
routed (``decoder_sparse_step`` 1, ``mlp_only_layers`` []).

Gated DeltaNet (arXiv:2412.06464), ``Hk`` key heads and ``Hv`` value heads
of ``dk`` = ``dv``; value heads ``r j .. r j + r - 1`` read key head ``j``:
``[q~ | k~ | v~ | z] = x W_qkvz``, ``[b | a] = x W_ba`` (the release
interleaves the columns a key head; under drawn weights a relabelling:
DEPARTURE 2); a depthwise causal convolution over time of
``linear_conv_kernel_dim`` taps on ``[q~ | k~ | v~]``, as that many shifted
products (rows before the first token zero), then SiLU; q and k
L2-normalised a head (eps 1e-6), q times ``dk ** -0.5``; ``beta =
sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``, one a VALUE head a
token, no lower bound; a float32 state ``[dk, dv]`` a value head from zero,
TOKEN BY TOKEN under a scan: ``S <- exp(g) S``, ``u = beta (v - S^T k)``, ``S
<- S + k u^T``, ``o = S^T q``; ``y = W_o (RMSNorm_head(o) * w_norm *
SiLU(z))`` (the head norm is the plain ``* w``), a gate a CHANNEL. The rows
go through in blocks of ``ROW_BLOCK``, the state and the convolution's last
rows carried from one to the next: the projections of a long context never
exist whole.

Gated attention: ``[q | gate] = x W_q`` a head (split in halves a head),
``k, v = x W_k, x W_v``; q and k RMS-normed a head (the zero-centred scale
as above); rotary on the FIRST ``partial_rotary_factor x head_dim`` values
of a head (half-split pairs inside them, ``rope_theta``), the others
unrotated; causal softmax at ``head_dim ** -0.5`` in query blocks; ``y = W_o
(attn * sigmoid(gate))``.

Routed layer: softmax over ALL ``routed`` router outputs in float32, the
``top_k`` largest renormalised to sum to one; only the ``experts`` held
here (``first_expert ..``) are computed and that partial sum goes on;
beside it ``sigmoid(x w_sg) * SwiGLU_shared(x)``, ``w_sg`` one value a
token.

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
# the upcast that rounds under ``weights_int8`` and the held experts one after
# the other are DeepSeek's; the softmax router with its margin is Keye's
from benchmarks.families.deepseek import _add_experts, _gated, _up
from benchmarks.families.keye_vl2 import _route
from benchmarks.flops import Shape

CHUNK = 256       # rows a serving step feeds a slot (the chunk faults' unit)
ROW_BLOCK = 2048  # rows of a Gated DeltaNet layer computed at once
GDN, FULL = "gdn", "full_attention"

FAULTS = (
    "decay_off",          # exp(g) = 1: the state forgets nothing
    "beta_off",           # a step size of 1 at every row
    "erase_off",          # no S^T k term: the row is added, nothing erased
    "gate_clamped",       # g clamped at -5: the bound this model lacks
    "value_group_off",    # a value head reads its neighbour's key head
    "conv_rows_dropped",  # the 3 rows before a chunk's first not carried
    "state_not_reset",    # a request starts from the state its slot held
    "state_bf16",         # the state held in bf16 between chunks
    "z_gate_off",         # no SiLU(z) gate on a Gated DeltaNet output
    "attn_gate_off",      # no sigmoid gate on an attention output
    "rope_whole_head",    # all 256 values of a head rotated, not 64
    "qk_norm_off",        # q and k heads of an attention layer not normed
    "norm_centre_off",    # the norms read w where the model reads 1 + w
    "topk_norm_off",      # routing weights not renormalised over the top-k
    "shared_gate_off",    # the shared expert always on
    "held_offset_off",    # the held experts read the next share's weights
    "kinds_shifted",      # the mixer kinds one layer early
    "weights_int8",       # every matrix rounded to 8 bits a column
)


@dataclass(frozen=True)
class Qwen3NextShape(Shape):
    """``flops.Shape`` (``heads`` / ``kv_heads`` / ``hd`` the attention
    layers', ``ffn`` an expert's width, ``experts`` those held here) plus
    what the hybrid adds."""

    layer_ids: tuple = ()   # each layer's published index, as run
    interval: int = 4       # every interval-th published layer is attention
    key_heads: int = 0      # Gated DeltaNet: Hk, Hv, dk = dv
    value_heads: int = 0
    gdn_dim: int = 0
    conv: int = 4
    rotary: int = 0         # the leading values of a head that are rotated
    shared: int = 0
    routed: int = 0         # the router's outputs
    first_expert: int = 0
    dense_layers: int = 0   # (none: the readers of layer counts ask)

    def kind(self, i: int, shift: int = 0) -> str:
        return FULL if (i + 1 + shift) % self.interval == 0 else GDN

    def count(self, kind: str) -> int:
        return sum(self.kind(i) == kind for i in self.layer_ids)

    kind_layers = count  # (the readers of a model with layer kinds ask so)

    def mixer_matmul_params(self, kind: str) -> int:
        if kind == GDN:
            key = self.key_heads * self.gdn_dim
            val = self.value_heads * self.gdn_dim
            return self.d * (2 * key + 3 * val + 2 * self.value_heads)
        return self.d * self.hd * (3 * self.heads + 2 * self.kv_heads)

    def layer_matmul_params(self, active: bool = True) -> int:
        """The mean over the layers as run: the mixers, the router, the
        shared expert and its gate, and a token's share of the held experts
        (on average ``top_k x experts / routed``) or those stored."""
        mix = sum(self.mixer_matmul_params(self.kind(i))
                  for i in self.layer_ids) // len(self.layer_ids)
        n = self.top_k * self.experts / self.routed if active else self.experts
        return int(mix + self.d * (self.routed + 1)
                   + 3 * self.d * (n * self.ffn + self.shared))

    def attention_flops_per_token(self, context: float) -> float:
        """The attention layers' scores and values over the context and the
        Gated DeltaNet layers' state decay, erase, write and read-out."""
        full = 2 * 2 * self.heads * self.hd * context
        gdn = 8 * self.value_heads * self.gdn_dim * self.gdn_dim
        return self.count(FULL) * full + self.count(GDN) * gdn


def shape_of(config: dict) -> Qwen3NextShape:
    """The published keys of Qwen3-Next's ``config.json``; ``layer_ids`` and
    ``published`` say which layers and experts of the release are run."""
    ids = tuple(int(i) for i in config["layer_ids"])
    if len(ids) != int(config["num_hidden_layers"]):
        raise ValueError("layer_ids names num_hidden_layers layers")
    if config.get("mlp_only_layers") or not config.get(
            "norm_topk_prob", True) or int(config["decoder_sparse_step"]) != 1:
        raise ValueError("every layer is routed and the chosen weights are "
                         "renormalised (mlp_only_layers [], norm_topk_prob, "
                         "decoder_sparse_step 1)")
    dk, dv = (int(config["linear_key_head_dim"]),
              int(config["linear_value_head_dim"]))
    if dk != dv:
        raise ValueError("a Gated DeltaNet state is square here (dk = dv)")
    hd = int(config["head_dim"])
    return Qwen3NextShape(
        config["family"], int(config["hidden_size"]), len(ids),
        int(config["num_attention_heads"]),
        int(config["num_key_value_heads"]), hd,
        int(config["moe_intermediate_size"]), int(config["vocab_size"]),
        int(config["num_experts"]), int(config["num_experts_per_tok"]), True,
        bool(config.get("tie_word_embeddings", False)),
        float(config["rms_norm_eps"]), float(config["rope_theta"]),
        layer_ids=ids, interval=int(config["full_attention_interval"]),
        key_heads=int(config["linear_num_key_heads"]),
        value_heads=int(config["linear_num_value_heads"]), gdn_dim=dk,
        conv=int(config["linear_conv_kernel_dim"]),
        rotary=int(round(float(config["partial_rotary_factor"]) * hd)),
        shared=int(config["shared_expert_intermediate_size"]),
        routed=int(config["published"]["num_experts"]),
        first_expert=int(config.get("first_expert", 0)))


def _norm(x, p, eps, fault=None):
    """``x / rms(x) * (1 + w)``, the tree's ``scale`` being ``1 + w``."""
    scale = p["scale"] - 1.0 if fault == "norm_centre_off" else p["scale"]
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


# --------------------------------------------------------- Gated DeltaNet
def _delta_rule(q, k, v, g, beta, s0, first, fault=None):
    """The recurrence a token at a time: rows q / k / v [S, Hv, d] (q and k
    already a value head's), g / beta [S, Hv], state ``s0`` [Hv, dk, dv] ->
    (o [S, Hv, dv], the state after the last row). ``first``: the position
    of row 0 in its request (the chunk faults' clock)."""
    def step(s, t):
        i, qt, kt, vt, gt, bt = t
        s = s * jnp.exp(gt)[:, None, None]
        # (sums over the key channel as plain reductions: a matrix product
        # of one row a head a token is slow beyond use on the chip)
        seen = (kt[:, :, None] * s).sum(1)
        if fault == "erase_off":
            seen = jnp.zeros_like(seen)
        s = s + kt[:, :, None] * (bt[:, None] * (vt - seen))[:, None, :]
        o = (qt[:, :, None] * s).sum(1)
        if fault == "state_bf16":  # as held between a step and the next
            s = jnp.where(i % CHUNK == CHUNK - 1,
                          s.astype(jnp.bfloat16).astype(ref.F32), s)
        return s, o

    s, o = jax.lax.scan(
        step, s0, (first + jnp.arange(q.shape[0]), q, k, v, g, beta))
    return o, s


@partial(jax.jit, static_argnames=("Hk", "Hv", "hd", "eps", "taps", "fault"))
def _gdn_rows(h, a, prev, s0, first, *, Hk, Hv, hd, eps, taps, fault=None):
    """One block of rows of a Gated DeltaNet mixer over normed inputs ``h``
    [S, d], continued from the ``taps - 1`` pre-convolution rows ``prev``
    before it and the state ``s0``: (out [S, d], the rows and the state to
    carry on)."""
    S = h.shape[0]
    key, val = Hk * hd, Hv * hd
    qkvz = h @ a["wqkvz"]
    pre, z = qkvz[:, :2 * key + val], qkvz[:, 2 * key + val:]
    ext = jnp.concatenate([prev, pre])  # row t of pre is row t + taps - 1
    t = first + jnp.arange(S)
    y = 0.0
    for i in range(taps):  # y_t = sum_i c_i x_{t - (taps - 1) + i}
        back = taps - 1 - i
        rows = ext[i:i + S]
        if fault == "conv_rows_dropped" and back:
            rows = jnp.where((t % CHUNK >= back)[:, None], rows, 0.0)
        y = y + rows * a["conv"][i]
    y = jax.nn.silu(y)
    q = y[:, :key].reshape(S, Hk, hd)
    k = y[:, key:2 * key].reshape(S, Hk, hd)
    v = y[:, 2 * key:].reshape(S, Hv, hd)
    q = q / jnp.sqrt((q * q).sum(-1, keepdims=True) + 1e-6) * hd ** -0.5
    k = k / jnp.sqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    if fault == "value_group_off":  # the neighbour's key head
        q, k = jnp.roll(q, 1, axis=1), jnp.roll(k, 1, axis=1)
    r = Hv // Hk  # value heads r j .. r j + r - 1 read key head j
    q, k = jnp.repeat(q, r, axis=1), jnp.repeat(k, r, axis=1)
    ba = h @ a["wba"]
    beta = jax.nn.sigmoid(ba[:, :Hv])
    g = -jnp.exp(a["A_log"])[None, :] * jax.nn.softplus(
        ba[:, Hv:] + a["dt_bias"][None, :])
    if fault == "decay_off":
        g = jnp.zeros_like(g)
    if fault == "gate_clamped":
        g = jnp.maximum(g, -5.0)
    if fault == "beta_off":
        beta = jnp.ones_like(beta)
    o, s = _delta_rule(q, k, v, g, beta, s0, first, fault)
    o = o / jnp.sqrt((o * o).mean(-1, keepdims=True) + eps) * a[
        "o_norm"]["scale"]
    if fault != "z_gate_off":
        o = o * jax.nn.silu(z).reshape(S, Hv, hd)
    return o.reshape(S, val) @ a["wo"], ext[S:], s


def _gdn(h, a, shape: Qwen3NextShape, fault=None):
    """One Gated DeltaNet mixer over normed inputs ``h`` [S, d], a block of
    rows after the other."""
    kw = dict(Hk=shape.key_heads, Hv=shape.value_heads, hd=shape.gdn_dim,
              eps=shape.eps, taps=shape.conv)
    wide = 2 * kw["Hk"] * kw["hd"] + kw["Hv"] * kw["hd"]
    zeros = (jnp.zeros((shape.conv - 1, wide), ref.F32),
             jnp.zeros((kw["Hv"], kw["hd"], kw["hd"]), ref.F32))
    prev, s = zeros
    if fault == "state_not_reset":  # what the slot's last request left
        for lo in range(0, h.shape[0], ROW_BLOCK):
            _, _, s = _gdn_rows(h[lo:lo + ROW_BLOCK], a, prev, s, lo, **kw)
            prev = zeros[0]  # (its convolution rows are not what is at fault)
    out = []
    for lo in range(0, h.shape[0], ROW_BLOCK):
        y, prev, s = _gdn_rows(h[lo:lo + ROW_BLOCK], a, prev, s, lo,
                               fault=fault, **kw)
        out.append(y)
    return jnp.concatenate(out)


# -------------------------------------------------------- gated attention
def _rope_leading(x, theta, rd):
    """Rotary on the first ``rd`` values of a head, pairs (i, i + rd / 2)."""
    return jnp.concatenate([ref.rope(x[..., :rd], theta), x[..., rd:]], -1)


@partial(jax.jit, static_argnames=("shape", "fault"))
def _attn(h, a, shape: Qwen3NextShape, fault=None):
    """One gated-attention mixer over normed inputs ``h`` [S, d]."""
    S = h.shape[0]
    H, KV, hd = shape.heads, shape.kv_heads, shape.hd
    qg = (h @ a["wq"]).reshape(S, H, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = (h @ a["wk"]).reshape(S, KV, hd)
    v = (h @ a["wv"]).reshape(S, KV, hd)
    if fault != "qk_norm_off":
        q = _norm(q, a["q_norm"], shape.eps, fault)
        k = _norm(k, a["k_norm"], shape.eps, fault)
    rd = hd if fault == "rope_whole_head" else shape.rotary
    q = _rope_leading(q, shape.rope_theta, rd)
    k = _rope_leading(k, shape.rope_theta, rd)
    o = ref.causal_attention(q, k, v)  # at hd ** -0.5, in query blocks
    if fault != "attn_gate_off":
        o = o * jax.nn.sigmoid(gate)
    return o.reshape(S, H * hd) @ a["wo"]


# ----------------------------------------------------------------- routed
@partial(jax.jit, static_argnames="fault")
def _shared(h, m, w_sg, fault=None):
    y = _gated(h, m)
    return y if fault == "shared_gate_off" else y * jax.nn.sigmoid(h @ w_sg)


def routed_block(x, ln2, m, j, shape: Qwen3NextShape, load, first=None,
                 bank=None, fault=None, bits: int = 0, shared: bool = True):
    """``x`` + the routed layer ``j`` of the stack ``m`` (as served): the
    partial sum of the experts held (as many as ``bank`` holds, ``m``'s own
    by default, from ``first``) and, with ``shared``, the gated shared
    expert. Returns (x, margin [S])."""
    first = shape.first_expert if first is None else first
    bank = bank or m
    held = bank["wi"].shape[1]
    h, w, margin = _route(
        x, {"scale": ln2["scale"] - 1.0} if fault == "norm_centre_off"
        else ln2, load(m["router"][j]), top_k=shape.top_k,
        first=(first + held) % shape.routed if fault == "held_offset_off"
        else first, held=held, eps=shape.eps,
        fault="renorm_off" if fault == "topk_norm_off" else None)
    if shared:
        x = x + _shared(h, load(ref.layer(m["shared"], j)),
                        load(m["shared_gate"][j]), fault)
    return _add_experts(x, h, w, bank["wg"], bank["wi"], bank["wo"], j,
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


def hidden(params, ids, shape: Qwen3NextShape, device=None, fault=None,
           bits: int = 0, first_expert=None, bank=None, shared: bool = True):
    """[S] token ids -> (hidden before the final norm [S, d] float32, the
    smallest routing margin of each position over the layers [S]).
    ``first_expert`` / ``bank`` / ``shared``: which share of the layer the
    held experts are, the banks to read them from and whether this member
    adds the shared expert: the shares-add-up test asks for other members'
    and for the uncut layer."""
    def load(tree):
        return jax.tree.map(
            lambda w: _up(jax.device_put(w, device), bits=bits), tree)

    margin = jnp.full((len(ids),), jnp.inf, ref.F32)
    seen = {GDN: 0, FULL: 0}
    stack = {GDN: "gdn_layers", FULL: "attn_layers"}
    shift = 1 if fault == "kinds_shifted" else 0
    M = params["layers"]
    with ref.HIGHEST():
        x = jax.device_put(params["embed"]["tok"][jnp.asarray(ids)],
                           device).astype(ref.F32)
        for jm, i in enumerate(shape.layer_ids):
            kind = shape.kind(i, shift)
            j = seen[kind]
            seen[kind] = j + 1
            L = params[stack[kind]]
            h = _norm(x, load(ref.layer(L["ln1"], j)), shape.eps, fault)
            a = load(ref.layer(L["attn"], j))
            x = x + (_gdn(h, a, shape, fault) if kind == GDN
                     else _attn(h, a, shape, fault))
            x, mg = routed_block(
                x, load(ref.layer(M["ln2"], jm)), M["mlp"], jm, shape, load,
                first=first_expert, bank=bank, fault=fault, bits=bits,
                shared=shared)
            margin = jnp.minimum(margin, mg)
    return x, margin


def logits(params, ids, shape: Qwen3NextShape, device=None,
           last: int | None = None, with_margin: bool = False,
           fault: str | None = None, bits: int = 0, **share):
    """Logits float32 over the vocabulary slice for the last ``last``
    positions (all if None); with ``with_margin`` also each of those
    positions' smallest routing margin over the layers: how near an expert
    held here was to changing sides, in router logits. ``fault`` and
    ``bits`` break the reference on purpose (``faulted`` makes both from a
    name); ``share`` is :func:`hidden`'s ``first_expert`` / ``bank`` /
    ``shared``."""
    x, margin = hidden(params, ids, shape, device, fault, bits, **share)
    if last is not None:
        x, margin = x[-last:], margin[-last:]
    up = lambda w: _up(jax.device_put(w, device), bits=bits)
    with ref.HIGHEST():
        out = _norm(x, jax.tree.map(up, params["final_norm"]), shape.eps,
                    fault) @ up(params["lm_head"])
    return (out, margin) if with_margin else out


# ---- kernels ---------------------------------------------------------------
def gdn_cost(shape: Qwen3NextShape, rows: float, state_slots: float,
             itemsize: int = 2):
    """The delta rule of ONE Gated DeltaNet layer: (flops, bytes) the traced
    steps needed. For every real row a value head's decay of the state (dk x
    dv), the erase ``S^T k``, the write ``k u^T`` and the read-out ``S^T
    q``, 2 x dk x dv each: the recurrence's own count, the least any form
    of it does. The chunk form's ``K K^T`` and ``Q K^T`` are taken once a
    KEY head and its solve is more work than the recurrence's, so neither
    adds to what is NEEDED. Bytes: every live state read and written once a
    slot a step, float32; the real rows' q and k (a key head's), v in and o
    out (a value head's), their log-decays and step sizes (float32)."""
    Hk, Hv, hd = shape.key_heads, shape.value_heads, shape.gdn_dim
    flops = 7 * Hv * hd * hd * rows
    state = 2 * Hv * hd * hd * 4 * state_slots
    per_row = 2 * (Hk + Hv) * hd * itemsize + 2 * Hv * 4
    return flops, state + per_row * rows


def full_attention_cost(shape: Qwen3NextShape, attended_keys: float,
                        fetched_keys: float, query_rows: float,
                        itemsize: int = 2):
    """The paged attention call of ONE gated-attention layer: (flops, bytes)
    that the work needs. ``attended_keys``: for every real query token, the
    keys it sees (its whole context), summed. FLOPs: QK^T and PV, 2 x 2 x
    heads x head_dim a (query, key) pair. Bytes: K and V of the
    ``fetched_keys``, the keys of the pages that hold a key some row of the
    slot sees, each once for all the rows and all the query heads of its
    slot, and the queries in and the outputs out for ``query_rows`` rows."""
    flops = 2 * 2 * shape.heads * shape.hd * attended_keys
    kv = 2 * shape.kv_heads * shape.hd * itemsize * fetched_keys
    q_out = 2 * shape.heads * shape.hd * itemsize * query_rows
    return flops, kv + q_out
