"""The ``mellum`` family: how its configuration files spell their sizes, the
plain reference of what they compute, and what its attention kernels need.

Mellum2 (JetBrains/Mellum2-12B-A2.5B-Instruct, config.json): embedding ->
N x [RMSNorm -> grouped-query attention -> residual -> RMSNorm -> 64 routed
SwiGLU experts, top-8 -> residual] -> RMSNorm -> untied head. The layers
follow ``layer_types``, a period of three *sliding* layers and one *full*
layer. Attention of either kind: q, k, v projections without bias, RMSNorm
over the 128 values of every q and k head (``assumed`` in the configuration
file), rotary embedding (rotate-half) by the table of the layer's kind, scores
q k / sqrt(128), causal softmax; a sliding layer's query i sees key j only if
i - j < ``sliding_window``. The sliding layers' table is the plain one of
``rope_parameters.sliding_attention`` (theta 500000); the full layers' is
YaRN (``rope_parameters.full_attention``: factor 16 over an original length
of 8192, beta_fast 32, beta_slow 1, cos and sin times ``attention_factor``).
Routing: softmax over the 64 router logits in float32, the 8 largest,
renormalised to sum to one (``norm_topk_prob``); every routed token is
computed (no capacity, no drop); no shared expert.

``FAULTS`` names the ways the reference can be broken on purpose, each what
one fault of a serving engine does to the arithmetic (see
``families/mixtral.py``); the ones new here are faults of an engine that
keeps pages and rotary tables by layer kind. Nothing sets one in a measured
run.
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

PAGE = 16  # tokens a page of the served cache holds (the page faults' unit)

FAULTS = (
    "rope_off_by_one",      # queries rotated for the position after their own
    "page_dropped",         # one 16-position page of the context not attended
    "experts_swapped",      # the middle layer routes experts 0 and 1 crosswise
    "gqa_mispaired",        # every query head reads its neighbour group's K/V
    "window_off",           # sliding layers see the whole context
    "window_off_by_one",    # ... one key more than the window
    "yarn_off",             # full layers rotated by the sliding layers' table
    "kinds_shifted",        # the pattern starts one layer late, at the full one
    "window_page_dropped",  # the oldest page still inside a window not attended
    "weights_int4",         # every matrix rounded to 4 bits a column
    "weights_int8",         # ... to 8 bits: the precision just below bf16
)
# the faults ``_attn`` itself knows; the others are a transform of the weights
INNER = ("rope_off_by_one", "page_dropped", "window_off", "window_off_by_one",
         "yarn_off", "kinds_shifted", "window_page_dropped")
SLIDING, FULL = "sliding_attention", "full_attention"


@dataclass(frozen=True)
class MellumShape(Shape):
    """``flops.Shape`` plus what the layer kinds need: the period of kinds,
    the window, and both rotary sections as (key, value) pairs."""

    pattern: tuple = ()
    window: int = 0
    rope: tuple = ()  # ((kind, ((key, value), ...)), ...)

    def kind_layers(self, kind: str) -> int:
        return sum(1 for i in range(self.layers)
                   if self.pattern[i % len(self.pattern)] == kind)

    def attention_flops_per_token(self, context: float) -> float:
        """QK^T and PV for one query token over ``context`` keys: a sliding
        layer's query sees ``window`` keys at most."""
        per_key = 2 * 2 * self.heads * self.hd
        return per_key * (
            self.kind_layers(FULL) * context
            + self.kind_layers(SLIDING) * min(context, self.window))


def shape_of(config: dict) -> MellumShape:
    """The published keys of Mellum2's ``config.json``."""
    d, h = int(config["hidden_size"]), int(config["num_attention_heads"])
    types = list(config["layer_types"])
    period = next(p for p in range(1, len(types) + 1)
                  if all(t == types[i % p] for i, t in enumerate(types)))
    rope = tuple((kind, tuple(sorted(sec.items())))
                 for kind, sec in sorted(config["rope_parameters"].items()))
    return MellumShape(
        config["family"], d, int(config["num_hidden_layers"]), h,
        int(config["num_key_value_heads"]), int(config["head_dim"]),
        int(config["moe_intermediate_size"]), int(config["vocab_size"]),
        int(config["num_experts"]), int(config["num_experts_per_tok"]), True,
        bool(config.get("tie_word_embeddings", False)),
        float(config["rms_norm_eps"]),
        float(config["rope_parameters"][SLIDING]["rope_theta"]),
        pattern=tuple(types[:period]), window=int(config["sliding_window"]),
        rope=rope)


def rope_table(section: dict, hd: int):
    """(inverse frequencies float32 [hd/2], the factor on cos and sin) of one
    ``rope_parameters`` section: the plain table, or YaRN's (Peng et al.
    2023, eq. 23 with the linear ramp of its reference code)."""
    theta = float(section["rope_theta"])
    extra = theta ** -(np.arange(0, hd, 2, dtype=np.float64) / hd)
    if section.get("rope_type", "default") == "default":
        return extra.astype(np.float32), 1.0
    factor, length = float(section["factor"]), float(
        section["original_max_position_embeddings"])

    def dim(rotations):  # the pair that turns ``rotations`` times in ``length``
        return hd * math.log(length / (2 * math.pi * rotations)) / (
            2 * math.log(theta))

    low = max(math.floor(dim(float(section["beta_fast"]))), 0)
    high = min(math.ceil(dim(float(section["beta_slow"]))), hd - 1)
    ramp = np.clip((np.arange(hd // 2) - low) / (high - low), 0.0, 1.0)
    inv = extra / factor * ramp + extra * (1.0 - ramp)
    return inv.astype(np.float32), float(section["attention_factor"])


def _rotate(x, inv, mscale, first: int = 0):
    """x [S,H,hd] at positions first..first+S-1; rotate-half pairing."""
    S, _, hd = x.shape
    ang = jnp.arange(first, first + S, dtype=ref.F32)[:, None] * inv[None, :]
    cos = (jnp.cos(ang) * mscale)[:, None, :]
    sin = (jnp.sin(ang) * mscale)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "hd", "eps", "window",
                                   "mscale", "fault"))
def _attn(x, ln1, a, inv, *, heads, kv_heads, hd, eps, window, mscale,
          fault=None):
    """One attention block; ``window`` 0 = a full layer."""
    S = x.shape[0]
    h = ref.rmsnorm(x, ln1, eps)
    q = (h @ a["wq"]).reshape(S, heads, hd)
    k = (h @ a["wk"]).reshape(S, kv_heads, hd)
    v = (h @ a["wv"]).reshape(S, kv_heads, hd)
    q = ref.rmsnorm(q, a["q_norm"], eps)  # over each head's 128 values
    k = ref.rmsnorm(k, a["k_norm"], eps)
    q = _rotate(q, inv, mscale, first=int(fault == "rope_off_by_one"))
    k = _rotate(k, inv, mscale)
    lo = PAGE * (S // (2 * PAGE))  # page_dropped: one in the middle

    def bias_fn(qpos, kpos):
        dist = qpos[:, None] - kpos[None, :]
        hidden = jnp.zeros(dist.shape, bool)
        if window:
            hidden = dist >= window
            if fault == "window_page_dropped":
                # the page that holds the oldest key query i still sees
                oldest = jnp.maximum(qpos - (window - 1), 0)[:, None]
                hidden |= (kpos[None, :] // PAGE == oldest // PAGE) & (
                    qpos[:, None] >= window)
        if fault == "page_dropped":
            hidden |= (kpos[None, :] >= lo) & (kpos[None, :] < lo + PAGE) & (
                qpos[:, None] >= lo + PAGE)
        return jnp.where(hidden, -jnp.inf, 0.0)[None]

    o = ref.causal_attention(q, k, v, bias_fn).reshape(S, heads * hd)
    return x + o @ a["wo"]


@partial(jax.jit, static_argnames=("top_k", "eps"))
def _route(x, ln2, router, *, top_k, eps):
    """(normed input, routing weights [S,E] zero off the top-k, margin [S]:
    by how much the router's logit for the last expert chosen beat the one
    for the first left out)."""
    h = ref.rmsnorm(x, ln2, eps)
    logit = h @ router
    top, idx = jax.lax.top_k(jax.nn.softmax(logit, axis=-1), top_k + 1)
    near = jnp.take_along_axis(logit, idx[:, top_k - 1:], axis=-1)
    margin = near[:, 0] - near[:, 1]
    top, idx = top[:, :top_k], idx[:, :top_k]
    top = top / top.sum(-1, keepdims=True)
    w = jnp.zeros_like(logit).at[jnp.arange(h.shape[0])[:, None], idx].set(top)
    return h, w, margin


@partial(jax.jit, static_argnames="bits")
def _rounded(w, bits: int):
    """Symmetric round-to-nearest of a matrix, one scale a column."""
    top = 2 ** (bits - 1) - 1
    scale = jnp.abs(w).max(axis=0, keepdims=True) / top
    return jnp.clip(jnp.round(w / scale), -top - 1, top) * scale


@partial(jax.jit, static_argnames="bits")
def _add_experts(x, h, w, wg, wi, wo, i, bits: int = 0):
    """x + every expert of layer ``i`` on EVERY token, each weighted by its
    routing weight (zero for tokens not routed to it), one expert after the
    other: plain and wasteful on purpose. The banks come whole ([L, E, ...],
    as served); one matrix at a time is cut out and upcast inside the loop,
    so a layer is one call (a call an expert cost 0.9 ms each whatever the
    length, 65 s for a sample of 96 contexts: my chip run, PR 30) and the
    float32 copy of one expert is the only temporary; ``bits`` rounds each
    matrix first (the ``weights_int`` faults)."""
    def mat(bank, e):
        m = jax.lax.dynamic_slice(
            bank, (i, e, 0, 0), (1, 1, *bank.shape[2:]))[0, 0].astype(ref.F32)
        return _rounded(m, bits) if bits else m

    def add(e, x):
        y = (jax.nn.silu(h @ mat(wg, e)) * (h @ mat(wi, e))) @ mat(wo, e)
        return x + y * jax.lax.dynamic_index_in_dim(
            w, e, 1, keepdims=False)[:, None]

    return jax.lax.fori_loop(0, wg.shape[1], add, x)


def faulted(params, fault, shape, device=None) -> dict:
    """What ``logits`` is handed under ``fault`` (one of ``FAULTS``, or None),
    as its keywords. Swapped experts and mispaired heads are a permutation
    of two small leaves, made here as another tree; rounding is done as each
    matrix is upcast (``bits``), since a rounded copy of the expert banks
    would not fit beside the model."""
    if fault is None or fault in INNER:
        return dict(params=params, fault=fault)
    if fault not in FAULTS:
        raise ValueError(f"no fault {fault!r} (have {FAULTS})")
    if fault.startswith("weights_int"):
        return dict(params=params, bits=int(fault[len("weights_int"):]))
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


def _loader(device, bits: int):
    """How a subtree of the weights reaches the reference: brought to the
    device, upcast to float32 and, under a ``weights_int`` fault, every
    matrix rounded."""
    if not bits:
        return partial(ref.f32, device=device)
    return lambda tree: jax.tree.map(
        lambda w: _rounded(w, bits) if w.ndim == 2 else w,
        ref.f32(tree, device))


def _kind_of(shape, i: int, fault) -> str:
    shift = 1 if fault == "kinds_shifted" else 0
    pattern = shape.pattern
    return pattern[(i - shift) % len(pattern)]


def hidden(params, ids, shape, device=None, fault=None, bits: int = 0):
    """[S] token ids -> (hidden before the final norm [S,d] float32, the
    smallest routing margin of each position over the layers [S])."""
    eps = shape.eps
    load = _loader(device, bits)
    sections = {kind: dict(sec) for kind, sec in shape.rope}
    tables = {kind: rope_table(sec, shape.hd) for kind, sec in sections.items()}
    margin = jnp.full((len(ids),), jnp.inf, ref.F32)
    with ref.HIGHEST():
        x = jax.device_put(params["embed"]["tok"][jnp.asarray(ids)],
                           device).astype(ref.F32)
        L = params["layers"]
        for i in range(shape.layers):
            at = lambda sub: load(ref.layer(L[sub], i))
            kind = _kind_of(shape, i, fault)
            window = shape.window if kind == SLIDING else 0
            if window and fault == "window_off":
                window = 0
            if window and fault == "window_off_by_one":
                window += 1
            inv, mscale = tables[
                SLIDING if fault == "yarn_off" else kind]
            x = _attn(
                x, at("ln1"), at("attn"), jax.device_put(inv, device),
                heads=shape.heads, kv_heads=shape.kv_heads, hd=shape.hd,
                eps=eps, window=window, mscale=mscale,
                fault=fault if fault in ("rope_off_by_one", "page_dropped",
                                         "window_page_dropped") else None)
            m = L["mlp"]
            h, w, mg = _route(x, at("ln2"), load(m["router"][i]),
                              top_k=shape.top_k, eps=eps)
            margin = jnp.minimum(margin, mg)
            x = _add_experts(x, h, w, m["wg"], m["wi"], m["wo"], i, bits=bits)
    return x, margin


def logits(params, ids, shape, device=None, last: int | None = None,
           with_margin: bool = False, fault: str | None = None,
           bits: int = 0):
    """Logits float32 for the last ``last`` positions (all if None); with
    ``with_margin`` also each of those positions' smallest routing margin
    (with 64 experts top-8 over twelve layers it is near zero almost
    everywhere: the mix's ``correctness`` says what is judged instead).
    ``fault`` (one of ``INNER``) and ``bits`` break the reference on purpose:
    ``faulted`` makes both from a name."""
    if fault is not None and fault not in INNER:
        raise ValueError(f"no fault {fault!r} inside the reference (have "
                         f"{INNER}); faulted() makes the others")
    x, margin = hidden(params, ids, shape, device, fault, bits)
    if last is not None:
        x, margin = x[-last:], margin[-last:]
    load = _loader(device, bits)
    with ref.HIGHEST():
        out = ref.rmsnorm(x, load(params["final_norm"]),
                          shape.eps) @ load(params["lm_head"])
    return (out, margin) if with_margin else out


# ---- kernels ---------------------------------------------------------------
def window_attention_cost(shape: MellumShape, attended_keys: float,
                          fetched_keys: float, query_rows: float,
                          itemsize: int = 2):
    """The paged attention call of ONE sliding layer: (flops, bytes) that
    the work needs. ``attended_keys``: for every real query token, the keys
    it sees (at most the window), summed. FLOPs: QK^T and PV, 2 x 2 x heads
    x head_dim a (query, key) pair. Bytes: K and V of the ``fetched_keys``,
    the keys of the pages that hold a key some row of the slot sees (a page
    is the least a paged read can bring; the block a kernel chooses to read
    them in is its own affair and is not counted as needed), each once for
    all the rows of its slot, and the queries in and the outputs out for
    ``query_rows`` rows."""
    flops = 2 * 2 * shape.heads * shape.hd * attended_keys
    kv = 2 * shape.kv_heads * shape.hd * itemsize * fetched_keys
    q_out = 2 * shape.heads * shape.hd * itemsize * query_rows
    return flops, kv + q_out
