"""The ``minicpm_sala`` family: how its configuration files spell their sizes,
the plain reference of what they compute, and what its three kernels need.

MiniCPM-SALA (openbmb/MiniCPM-SALA, config.json; ``model_type``
``minicpm_sala``): embedding x ``scale_emb`` -> blocks named one by one by
``mixer_types`` -> RMSNorm -> / (hidden_size / dim_model_base) -> untied head.
Every block is pre-norm (RMSNorm, no biases) with muP residuals: ``h <- h + a
Mixer(RMSNorm(h))``, ``h <- h + a SwiGLU(RMSNorm(h))``, ``a = scale_depth /
sqrt(published depth)``.

``lightning-attn``: ``q, k, v = W x`` as ``heads`` heads of ``hd``; RMSNorm
over each head of q and k; rotary (half-split pairs) on q and k; per head
``S_t = lambda_h S_{t-1} + k_t v_t^T`` from ``S = 0`` in float32, ``o_t =
q_t^T S_t / sqrt(hd)``; ``y = W_o (sigmoid(W_g x) * RMSNorm(o))``, the norm
over all heads' values side by side. ``lambda_h = exp(-s_h (1 - l / (L - 1)
+ 1e-5))``, ``s_h = 2 ** (-8 (h + 1) / heads)``, ``l`` the layer's PUBLISHED
index, ``L`` the published depth. Computed in blocks of 256 rows, each the
recurrence's closed form (not the program's 128-row chunks).

``minicpm4``: q ``heads`` heads, k / v ``kv_heads`` heads, RMSNorm over each
head of q and k, NO rotary. A query at position t with ``t + 1 <=
dense_len``: causal softmax attention over every key. Past it: compressed
keys ``Kc_j = mean(K[stride j : stride j + kernel_size])`` a kv head for
every j whose tokens all lie at or before t; ``p^h = softmax_j(q_h . Kc_j /
sqrt(hd))``; a group's ``P_j`` the sum of ``p^h`` over its heads; a block's
score the max of ``P_j`` over the kernels that overlap it; the first
``init_blocks`` blocks and the blocks of the last ``window_size`` tokens are
kept; the ``topk`` best blocks (the kept among them; ties to the lower
block) are attended, causally, by every head of the group. ``y = W_o
(sigmoid(W_g x) * o)``. The selection is by brute force over the whole
context, a block of queries at a time.

``FAULTS`` names the ways the reference can be broken on purpose, each what
one fault of a serving engine does to the arithmetic. Nothing sets one in a
measured run.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import reference as ref
from benchmarks.flops import Shape

PAGE = 16    # tokens a page of the served cache holds (page_dropped's unit)
CHUNK = 128  # rows a serving step feeds a slot (padded_rows_in_state's unit)
QUERY_BLOCK = 64  # query rows whose selection and attention exist at once
LIGHTNING_BLOCK = 256  # rows of the recurrence taken in one closed form

KINDS = {"minicpm4": "sparse", "lightning-attn": "lightning"}
STACK = {"sparse": "sparse_layers", "lightning": "lightning_layers"}

FAULTS = (
    "state_not_reset",       # a request starts from the state its slot held
    "decay_off",             # lambda = 1: the state forgets nothing
    "state_bf16",            # the state held in bf16 between blocks
    "padded_rows_in_state",  # the prompt's last chunk adds its padded rows
    "rope_off_lightning",    # lightning queries and keys not rotated
    "rope_on_sparse",        # sparse queries and keys rotated
    "selection_off",         # dense attention at every position
    "selection_recent",      # the window's and the first blocks alone
    "init_block_dropped",    # the first block not kept for its own sake
    "dense_len_off",         # the selection applied inside dense_len too
    "group_share_off",       # a selection a head, not a kv group
    "gate_off",              # no output gate
    "output_norm_off",       # no norm on the lightning layers' output
    "residual_scale_off",    # residual branches at weight 1
    "logit_scale_off",       # the head reads the hidden state undivided
    "kinds_shifted",         # the layers run in an order rotated by one
    "page_dropped",          # one 16-position page of the context not attended
    "weights_int8",          # every matrix rounded to 8 bits a column
)


@dataclass(frozen=True)
class SalaShape(Shape):
    """``flops.Shape`` (``kv_heads`` / ``hd`` the sparse layers') plus the
    order of the mixers and the sizes of the selection."""

    mixers: tuple = ()       # "sparse" | "lightning" a layer, as run
    layer_ids: tuple = ()    # each layer's published index
    depth: int = 0           # the published depth
    scale_emb: float = 1.0
    scale_depth: float = 1.0
    model_base: int = 0
    kernel_size: int = 32
    stride: int = 16
    block: int = 64
    topk: int = 64
    init_blocks: int = 1
    window: int = 2048
    dense_len: int = 8192

    def count(self, kind: str) -> int:
        return self.mixers.count(kind)

    def mixer_matmul_params(self, kind: str) -> int:
        wide = self.heads * self.hd
        kv = wide if kind == "lightning" else self.kv_heads * self.hd
        return 3 * self.d * wide + 2 * self.d * kv

    def layer_matmul_params(self, active: bool = True) -> int:
        """The mean over the layers as run (both kinds hold a dense MLP)."""
        mix = sum(self.mixer_matmul_params(k) for k in self.mixers)
        return mix // self.layers + 3 * self.d * self.ffn

    def attention_flops_per_token(self, context: float) -> float:
        """The sparse layers' scores over compressed keys and attention over
        the kept blocks; the lightning layers' state read and update."""
        kept = context if context <= self.dense_len else min(
            context, self.topk * self.block)
        sparse = 2 * self.heads * self.hd * (context / self.stride + 2 * kept)
        lightning = 4 * self.heads * self.hd * self.hd
        return (self.count("sparse") * sparse
                + self.count("lightning") * lightning)


def shape_of(config: dict) -> SalaShape:
    """The published keys of MiniCPM-SALA's ``config.json``; the selection's
    sizes, which the catalog row does not give, from ``assumed.sparse_config``
    (``rehearse`` may lay its own over them)."""
    sc = config["assumed"]["sparse_config"]
    mixers = tuple(KINDS[m] for m in config["mixer_types"])
    published = config["published"]
    assert len(mixers) == int(config["num_hidden_layers"])
    return SalaShape(
        config["family"], int(config["hidden_size"]), len(mixers),
        int(config["num_attention_heads"]), int(config["num_key_value_heads"]),
        int(config["head_dim"]), int(config["intermediate_size"]),
        int(config["vocab_size"]), 0, 0, True,
        bool(config.get("tie_word_embeddings", False)),
        float(config["rms_norm_eps"]), float(config["rope_theta"]),
        mixers=mixers, layer_ids=tuple(int(i) for i in config["layer_ids"]),
        depth=int(published["num_hidden_layers"]),
        scale_emb=float(config["scale_emb"]),
        scale_depth=float(config["scale_depth"]),
        model_base=int(config["dim_model_base"]),
        kernel_size=int(sc["kernel_size"]), stride=int(sc["kernel_stride"]),
        block=int(sc["block_size"]), topk=int(sc["topk"]),
        init_blocks=int(sc["init_blocks"]), window=int(sc["window_size"]),
        dense_len=int(sc["dense_len"]))


@partial(jax.jit, static_argnames=("bits",))
def _up(w, bits: int = 0):
    """A served matrix (or vector) as the reference reads it: float32 and,
    with ``bits``, rounded to that many bits (symmetric, to nearest, one
    scale a column)."""
    w = w.astype(ref.F32)
    if bits and w.ndim == 2:
        top = 2 ** (bits - 1) - 1
        scale = jnp.abs(w).max(axis=0, keepdims=True) / top
        return jnp.clip(jnp.round(w / scale), -top - 1, top) * scale
    return w


def _head_norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


# -------------------------------------------------------------- lightning
def _recurrence(q, k, v, log_lam, s0, keep):
    """``S_t = lambda S_{t-1} + k_t v_t^T``, ``o_t = q_t^T S_t`` over rows
    q / k / v [S, H, hd] from state ``s0`` [H, hd, hd]: (o [S, H, hd], the
    state after the last row). In blocks of ``LIGHTNING_BLOCK`` rows, each
    the recurrence's closed form over its rows (a token at a time, 20,000
    sequential steps a layer, took most of a run's set-up on the chip: my
    chip run, PR 41); ``keep`` is applied to the state between blocks."""
    S, H, hd = q.shape
    n = -(-S // LIGHTNING_BLOCK)
    pad = n * LIGHTNING_BLOCK - S

    def blocks(a):
        return jnp.pad(a, ((0, pad), (0, 0), (0, 0))).reshape(
            n, LIGHTNING_BLOCK, H, hd)

    i = jnp.arange(LIGHTNING_BLOCK)
    ll = log_lam[:, None, None]  # [H, 1, 1]
    below = (i[:, None] >= i[None, :])[None]
    decay = jnp.where(below, jnp.exp((i[:, None] - i[None, :]) * ll), 0.0)
    lead = jnp.exp((i + 1)[None, :, None] * ll)  # [H, B, 1]

    def block(s, t):
        qb, kb, vb, rows = t  # rows: how many of the block are real
        a = jnp.einsum("ihd,jhd->hij", qb, kb) * decay
        o = jnp.einsum("hij,jhd->ihd", a, vb) + jnp.einsum(
            "ihd,hde->ihe", qb * lead.transpose(1, 0, 2), s)
        left = (rows - 1 - i)[None, :, None]  # a padded row weighs nothing
        w = jnp.where(left >= 0, jnp.exp(left * ll), 0.0)
        s = jnp.exp(rows * ll) * s + jnp.einsum(
            "ihd,ihe->hde", kb * w.transpose(1, 0, 2), vb)
        return keep(s), o

    real = jnp.clip(S - jnp.arange(n) * LIGHTNING_BLOCK, 0, LIGHTNING_BLOCK)
    s, o = jax.lax.scan(block, s0, (blocks(q), blocks(k), blocks(v), real))
    return o.reshape(n * LIGHTNING_BLOCK, H, hd)[:S], s


@partial(jax.jit, static_argnames=("heads", "hd", "eps", "theta", "layer_id",
                                   "depth", "prompt", "fault"))
def _lightning(h, a, *, heads, hd, eps, theta, layer_id, depth, prompt,
               fault=None):
    """One lightning mixer over normed inputs ``h`` [S, d]."""
    S = h.shape[0]
    q = _head_norm((h @ a["wq"]).reshape(S, heads, hd),
                   a["q_norm"]["scale"], eps)
    k = _head_norm((h @ a["wk"]).reshape(S, heads, hd),
                   a["k_norm"]["scale"], eps)
    v = (h @ a["wv"]).reshape(S, heads, hd)
    if fault != "rope_off_lightning":
        q, k = ref.rope(q, theta), ref.rope(k, theta)
    slope = 2.0 ** (-8.0 * (np.arange(heads) + 1.0) / heads)
    log_lam = -slope * (1.0 - layer_id / max(depth - 1, 1) + 1e-5)
    log_lam = jnp.asarray(
        np.zeros(heads) if fault == "decay_off" else log_lam, ref.F32)

    def keep(s):  # the state as it is held between a step and the next
        return s.astype(jnp.bfloat16).astype(ref.F32) \
            if fault == "state_bf16" else s

    run = partial(_recurrence, log_lam=log_lam, keep=keep)
    s0 = jnp.zeros((heads, hd, hd), ref.F32)
    if fault == "state_not_reset":  # what the slot's last request left
        _, s0 = run(q, k, v, s0=s0)
    if fault == "padded_rows_in_state" and prompt % CHUNK:
        # the prompt's last chunk: its padded rows (here: its last real row,
        # again) are summed into the state before the answer's first token
        pad = CHUNK - prompt % CHUNK
        o1, s1 = run(q[:prompt], k[:prompt], v[:prompt], s0=s0)
        again = lambda t: jnp.repeat(t[prompt - 1:prompt], pad, axis=0)
        _, s1 = run(again(q), again(k), again(v), s0=s1)
        o2, _ = run(q[prompt:], k[prompt:], v[prompt:], s0=s1)
        o = jnp.concatenate([o1, o2])
    else:
        o, _ = run(q, k, v, s0=s0)
    o = (o / math.sqrt(hd)).reshape(S, heads * hd)
    if fault != "output_norm_off":
        o = _head_norm(o, a["o_norm"]["scale"], eps)
    if fault != "gate_off":
        o = o * jax.nn.sigmoid(h @ a["wgate"])
    return o @ a["wo"]


# ----------------------------------------------------------------- sparse
def _kept_blocks(score, causal, forced, topk: int):
    """bool like ``score`` [..., blocks]: the ``topk`` best blocks among
    ``causal``, the ``forced`` ones first, ties to the lower block (all of
    them where no more are causal). A sort, on purpose: brute force."""
    rank = jnp.where(causal, jnp.where(forced, jnp.inf, score), -jnp.inf)
    order = jnp.argsort(-rank, axis=-1, stable=True)
    place = jnp.argsort(order, axis=-1, stable=True)  # a block's rank
    return causal & (place < topk)


@partial(jax.jit, static_argnames=("shape", "fault"))
def _sparse(h, a, *, shape: SalaShape, fault=None):
    """One sparse mixer over normed inputs ``h`` [S, d]."""
    S = h.shape[0]
    H, KV, hd, eps = shape.heads, shape.kv_heads, shape.hd, shape.eps
    G = H // KV
    q = _head_norm((h @ a["wq"]).reshape(S, H, hd), a["q_norm"]["scale"], eps)
    k = _head_norm((h @ a["wk"]).reshape(S, KV, hd), a["k_norm"]["scale"],
                   eps)
    v = (h @ a["wv"]).reshape(S, KV, hd)
    if fault == "rope_on_sparse":
        q, k = ref.rope(q, shape.rope_theta), ref.rope(k, shape.rope_theta)
    ks, st, B_ = shape.kernel_size, shape.stride, shape.block
    nK = max((S - ks) // st + 1, 0)
    NB = -(-S // B_)
    # compressed keys [nK, KV, hd] (one row of zeros, masked, when none)
    kc = jax.vmap(
        lambda s: jax.lax.dynamic_slice_in_dim(k, s, ks).mean(0))(
            jnp.arange(nK) * st) if nK else jnp.zeros((1, KV, hd), ref.F32)
    # the kernels that overlap a block: [NB, planes]
    per, reach = B_ // st, ks // st - 1
    over = per * np.arange(NB)[:, None] - reach + np.arange(per + reach)
    kpos = jnp.arange(S)
    dense_len = 0 if fault == "dense_len_off" else shape.dense_len
    qg = q.reshape(S, KV, G, hd)

    def block(first, qb):
        n = qb.shape[0]
        pos = first + jnp.arange(n)
        seen = kpos[None, :] <= pos[:, None]  # [n, S]
        if fault == "page_dropped":  # one page in the middle, for later rows
            lo = PAGE * (S // (2 * PAGE))
            seen &= ~((kpos[None, :] >= lo) & (kpos[None, :] < lo + PAGE)
                      & (pos[:, None] >= lo + PAGE))
        m = jnp.arange(NB)
        causal = m[None, :] <= pos[:, None] // B_
        if fault == "selection_off":
            kept = jnp.broadcast_to(causal[:, None, None], (n, KV, 1, NB))
        else:
            nk = jnp.where(pos + 1 >= ks, (pos + 1 - ks) // st + 1, 0)
            ok = jnp.arange(kc.shape[0])[None, :] < nk[:, None]  # [n, nK]
            s = jnp.einsum("qghd,jgd->qghj", qb, kc) / math.sqrt(hd)
            p = jax.nn.softmax(
                jnp.where(ok[:, None, None, :], s, -jnp.inf), axis=-1)
            p = jnp.where(ok[:, None, None, :], p, 0.0)  # no key: all zero
            if fault != "group_share_off":
                p = p.sum(axis=2, keepdims=True)  # the group's heads
            inside = (over >= 0) & (over < kc.shape[0])
            score = jnp.where(
                inside, p[..., np.clip(over, 0, kc.shape[0] - 1)], 0.0
            ).max(axis=-1)  # [n, KV, 1 | G, NB]
            forced = m[None, :] >= jnp.maximum(
                pos[:, None] + 1 - shape.window, 0) // B_
            if fault != "init_block_dropped":
                forced |= m[None, :] < shape.init_blocks
            if fault == "selection_recent":
                kept = jnp.broadcast_to((causal & forced)[:, None, None],
                                        score.shape)
            else:
                kept = _kept_blocks(score, causal[:, None, None],
                                    forced[:, None, None], shape.topk)
            kept = jnp.where((pos + 1 <= dense_len)[:, None, None, None],
                             causal[:, None, None], kept)
        allowed = kept[..., kpos // B_] & seen[:, None, None, :]
        s = jnp.einsum("qghd,kgd->qghk", qb, k) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(allowed, s, -jnp.inf), axis=-1)
        return jnp.einsum("qghk,kgd->qghd", p, v).reshape(n, H * hd)

    n_blocks = -(-S // QUERY_BLOCK)
    pad = n_blocks * QUERY_BLOCK - S
    qp = jnp.pad(qg, ((0, pad), (0, 0), (0, 0), (0, 0))).reshape(
        n_blocks, QUERY_BLOCK, KV, G, hd)
    o = jax.lax.map(lambda t: block(t[0], t[1]),
                    (jnp.arange(n_blocks) * QUERY_BLOCK, qp))
    o = o.reshape(n_blocks * QUERY_BLOCK, H * hd)[:S]
    if fault != "gate_off":
        o = o * jax.nn.sigmoid(h @ a["wgate"])
    return o @ a["wo"]


@jax.jit
def _gated(h, m):
    return (jax.nn.silu(h @ m["wg"]) * (h @ m["wi"])) @ m["wo"]


def _gated_served(h, m, bits: int = 0, chunk: int = 4096):
    """:func:`_gated` of an MLP as served (``m`` not yet upcast), ``chunk``
    of its inner width at a time: three float32 matrices of a 16384-wide MLP
    are 0.8 GB at once."""
    out = 0.0
    for lo in range(0, m["wi"].shape[1], chunk):
        hi = min(lo + chunk, m["wi"].shape[1])
        out = out + _gated(h, {
            "wg": _up(m["wg"][:, lo:hi], bits=bits),
            "wi": _up(m["wi"][:, lo:hi], bits=bits),
            "wo": _up(m["wo"], bits=bits)[lo:hi] if bits
            else m["wo"][lo:hi].astype(ref.F32)})
    return out


def faulted(params, fault, shape, device=None) -> dict:
    """What ``logits`` is handed under ``fault`` (one of ``FAULTS``, or None),
    as its keywords: every fault here is arithmetic of the reference itself,
    but the rounding, which is done as each matrix is upcast (``bits``)."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"no fault {fault!r} (have {FAULTS})")
    if fault is not None and fault.startswith("weights_int"):
        return dict(params=params, bits=int(fault[len("weights_int"):]))
    return dict(params=params, fault=fault)


def hidden(params, ids, shape: SalaShape, device=None, fault=None,
           bits: int = 0, prompt: int | None = None):
    """[S] token ids -> hidden before the final norm [S, d] float32.
    ``prompt``: how many of ``ids`` were the request's prompt (all of them
    if None): only ``padded_rows_in_state`` reads it."""
    S = len(ids)
    branch = 1.0 if fault == "residual_scale_off" else (
        shape.scale_depth / math.sqrt(shape.depth))
    # (kind, index in its stack, published index) a layer, in the order run
    seen = {"sparse": 0, "lightning": 0}
    order = []
    for kind, lid in zip(shape.mixers, shape.layer_ids):
        order.append((kind, seen[kind], lid))
        seen[kind] += 1
    if fault == "kinds_shifted":
        order = order[1:] + order[:1]

    def on_device(tree):
        return jax.tree.map(lambda w: jax.device_put(w, device), tree)

    def load(tree):
        return jax.tree.map(partial(_up, bits=bits), on_device(tree))

    with ref.HIGHEST():
        x = jax.device_put(params["embed"]["tok"][jnp.asarray(ids)],
                           device).astype(ref.F32) * shape.scale_emb
        for kind, i, lid in order:
            L = params[STACK[kind]]
            at = lambda sub: ref.layer(L[sub], i)
            h = ref.rmsnorm(x, load(at("ln1")), shape.eps)
            if kind == "lightning":
                mix = _lightning(
                    h, load(at("attn")), heads=shape.heads, hd=shape.hd,
                    eps=shape.eps, theta=shape.rope_theta, layer_id=lid,
                    depth=shape.depth, prompt=S if prompt is None else prompt,
                    fault=fault)
            else:
                mix = _sparse(h, load(at("attn")), shape=shape, fault=fault)
            x = x + branch * mix
            x = x + branch * _gated_served(
                ref.rmsnorm(x, load(at("ln2")), shape.eps),
                on_device(at("mlp")), bits)
    return x


def logits(params, ids, shape: SalaShape, device=None,
           last: int | None = None, with_margin: bool = False,
           fault: str | None = None, bits: int = 0):
    """Logits float32 for the last ``last`` positions (all if None); with
    ``with_margin`` also a margin a position: infinite, this family routes
    nothing. ``fault`` and ``bits`` break the reference on purpose:
    ``faulted`` makes both from a name."""
    # the served tokens are the last ``last``; ids holds all but the newest
    prompt = None if last is None else len(ids) + 1 - last
    x = hidden(params, ids, shape, device, fault, bits, prompt)
    if last is not None:
        x = x[-last:]
    up = lambda w: _up(jax.device_put(w, device), bits=bits)
    with ref.HIGHEST():
        y = ref.rmsnorm(x, jax.tree.map(up, params["final_norm"]), shape.eps)
        if fault != "logit_scale_off" and shape.model_base:
            y = y / (shape.d / shape.model_base)
        out = y @ up(params["lm_head"])
    margin = jnp.full((out.shape[0],), jnp.inf, ref.F32)
    return (out, margin) if with_margin else out


# ---- kernels ---------------------------------------------------------------
def lightning_cost(shape: SalaShape, rows: float, state_slots: float,
                   itemsize: int = 2):
    """The lightning mixer of ONE layer: (flops, bytes) the traced steps
    needed. For every real row the state's update ``k v^T`` and its
    read-out ``q^T S``, 2 x heads x hd x hd each (the recurrence's own
    count; the decay is counted as free). Bytes: every live state read and
    written once a slot a step, float32, and the real rows' q, k, v in and
    o out."""
    H, hd = shape.heads, shape.hd
    flops = 4 * H * hd * hd * rows
    state = 2 * H * hd * hd * 4 * state_slots
    return flops, state + 4 * H * hd * itemsize * rows


def block_select_cost(shape: SalaShape, compressed_keys: float,
                      compressed_rows: float, rows: float,
                      itemsize: int = 2):
    """The block selection of ONE sparse layer: (flops, bytes) the traced
    steps needed. ``compressed_keys``: for every real query the compressed
    keys at or before it, summed: each pair costs a dot product of every
    head, 2 x heads x hd (the softmax, the pooling to blocks and the top-k
    counted as free). Bytes: ``compressed_rows`` compressed keys of every kv
    head, those at or before a slot's last real query, once a slot, and the
    real rows' queries in; the kept blocks out are counted as free."""
    flops = 2 * shape.heads * shape.hd * compressed_keys
    keys = shape.kv_heads * shape.hd * itemsize * compressed_rows
    return flops, keys + shape.heads * shape.hd * itemsize * rows


def block_sparse_attention_cost(shape: SalaShape, attended_keys: float,
                                chosen_rows: float, rows: float,
                                itemsize: int = 2):
    """Attention of ONE sparse layer over the kept blocks: (flops, bytes)
    the traced steps needed. ``attended_keys``: for every real query
    ``min(context, kept)`` keys (all of its context inside ``dense_len``),
    summed: QK^T and PV of every head. Bytes: K and V of every kv head for
    the ``chosen_rows`` tokens some query of a slot attends, once a slot
    (the caller gives a count that is certainly reached: the last query's),
    and the real rows' queries in and outputs out."""
    flops = 2 * 2 * shape.heads * shape.hd * attended_keys
    kv = 2 * shape.kv_heads * shape.hd * itemsize * chosen_rows
    return flops, kv + 2 * shape.heads * shape.hd * itemsize * rows
