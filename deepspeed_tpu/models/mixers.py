"""The one layer walk under the cached forward: every served model's.

Each layer has a MIXER kind (``transformer.MIXER_KINDS``: "full" | "window" |
"mla" of models/decoding.py, "sparse" | "lightning" of models/minicpm.py,
"kda" | "latent" of models/ling.py, which also gives "mla" a stack beside
them, "retention" of models/brumby.py, "gdn" of models/qwen3_next.py and
"conv" of models/lfm2.py, which each give "full" a stack beside theirs) and,
independently, an MLP kind ("dense": a SwiGLU or GELU MLP of the layer's own
width, the leading dense layers of a routed model among them; "routed": an
expert layer or one member's share of one,
``moe/sharded_moe.moe_serving_mlp``). A layer reads its mixer and its MLP
each at its index inside a parameter stack, and its pool leaves at its index
inside its pool (:func:`layer_plan`).

A RUN is the layers that follow one another in one pair of stacks, as trips
of a PERIOD, the shortest sequence of kinds the run repeats: a model that
names its layers one by one (``TransformerConfig.mixer_types``) has runs of
one kind, period 1; a model of ``layer_pattern`` has a run for its leading
dense layers and one for its main stack, whose period is the pattern. A run
is one ``lax.scan`` whose body unrolls the period, all runs over the same
carry: the hidden rows and the pools (pages by page table, state leaves by
slot), every leaf whole and written in place at the layer's index.

The module that owns the kinds ``mixer_types`` names (``family(cfg)``) gives
``STACK`` (mixer kind -> stack), ``MLP_STACK`` (MLP kind -> stack, or None:
the MLP lies in its mixer's stack, at the mixer's index), ``init``,
``num_params``, ``init_pools`` and ``slot_leaves``.

The residual path is ``h = h + f(norm(h))`` round each half-layer (with
``cfg.parallel_block`` round the layer: ``h = h + mixer(n) + mlp(n)``, ``n``
the layer's ONE norm), or, with ``cfg.hc_mult`` streams, a hyper-connection (:func:`hyper_pre`,
:func:`hyper_post`): the rows are then ``[n, 1, T, d]``, the streams LEADING
(a tile of the chip is the last two axes: a stream axis inside them would
pad 4 to 8 or 16; leading, every stream is the ``[T, d]`` block every other
line of the walk works on).
"""

from __future__ import annotations

import collections
import importlib
import math
from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .sharding import constrain
from .transformer import Params, TransformerConfig, _mlp, _norm

DENSE, ROUTED = "dense", "routed"


def family(cfg: TransformerConfig):
    """The module of models/ that owns the kinds ``cfg.mixer_types`` names."""
    return importlib.import_module(
        f"{__package__}.{cfg.mixer_family}")


def stacks_of(cfg: TransformerConfig) -> Tuple[str, ...]:
    """The names of the parameter stacks the walk reads."""
    return tuple(dict.fromkeys(
        name for l in layer_plan(cfg) for name in (l.stack, l.mlp_stack)))


def slot_leaves(cfg: TransformerConfig, max_slots: int, dtype) -> dict:
    """The pool leaves that are indexed by slot and no page, name ->
    ``jax.ShapeDtypeStruct`` ({} for a model without state layers)."""
    if not cfg.has_state:
        return {}
    return family(cfg).slot_leaves(cfg, max_slots, dtype)


class Layer(NamedTuple):
    mixer: str       # a kind of MIXER_KINDS
    mixer_at: int    # index inside its mixer's stack
    mlp: str         # DENSE | ROUTED
    mlp_at: int      # index inside its MLP's stack
    layer_id: int    # the layer's published index
    stack: str       # the parameter stack of its mixer
    mlp_stack: str   # and of its MLP
    pool_at: int     # index inside the pool of its mixer's leaves


def layer_plan(cfg: TransformerConfig, by_kind: bool = True) -> List[Layer]:
    """Every layer in published order with where its two halves and its pool
    leaves lie. A model that names its layers keeps a stack and a pool a
    mixer kind. Any other declares ``lead_dense_layers`` layers of the
    ``lead_layers`` stack, then ``num_layers`` of the ``layers`` stack whose
    kinds are ``layer_pattern`` repeated, the MLP beside its mixer; its
    layers share one pool, except that with ``by_kind`` (a paged cache) the
    window layers have a pool of their own."""
    lead = cfg.lead_dense_layers
    mlps = [ROUTED if cfg.is_moe and i >= lead else DENSE
            for i in range(cfg.total_layers)]
    if cfg.mixer_types:
        fam = family(cfg)
        kinds, ids = cfg.mixer_types, cfg.mixer_layer_ids
        stacks = [fam.STACK[kind] for kind in kinds]
        mlp_stacks = [fam.MLP_STACK[mlp] or stack
                      for mlp, stack in zip(mlps, stacks)]
        pools = kinds
    else:
        whole = "mla" if cfg.is_latent else "full"  # attends every key
        pattern = cfg.layer_pattern or (whole,)
        kinds = (whole,) * lead + pattern * (cfg.num_layers // len(pattern))
        ids = range(cfg.total_layers)
        stacks = mlp_stacks = ["lead_layers"] * lead + [
            "layers"] * cfg.num_layers
        pools = [kind if by_kind and kind == "window" else whole
                 for kind in kinds]
    seen: collections.Counter = collections.Counter()

    def take(*key) -> int:  # how many layers took ``key`` before this one
        seen[key] += 1
        return seen[key] - 1

    plan = []
    for kind, lid, mlp, stack, mlp_stack, pool in zip(
            kinds, ids, mlps, stacks, mlp_stacks, pools):
        at = take("stack", stack)
        mlp_at = at if mlp_stack == stack else take("stack", mlp_stack)
        plan.append(Layer(kind, at, mlp, mlp_at, int(lid), stack, mlp_stack,
                          take("pool", pool)))
    return plan


class Run(NamedTuple):
    """One scan of the walk: ``layers`` are trips of ``period``."""

    stack: str
    mlp_stack: str
    period: Tuple[Tuple[str, str], ...]  # (mixer, MLP) of a trip's layers
    layers: Tuple[Layer, ...]

    @property
    def trips(self) -> int:
        return len(self.layers) // len(self.period)

    def scanned(self, field: str) -> np.ndarray:
        """``field`` of every layer, [trips, period] int32."""
        return np.asarray([getattr(l, field) for l in self.layers],
                          np.int32).reshape(self.trips, len(self.period))


def walk_runs(cfg: TransformerConfig, by_kind: bool = True) -> List[Run]:
    """The plan as the walk's runs: a run ends where a layer's stacks or its
    MLP kind change, and its period is the shortest its kinds repeat."""
    spans: List[List[Layer]] = []
    for layer in layer_plan(cfg, by_kind):
        last = spans[-1][-1] if spans else None
        if last and (last.stack, last.mlp_stack, last.mlp) == (
                layer.stack, layer.mlp_stack, layer.mlp):
            spans[-1].append(layer)
        else:
            spans.append([layer])
    out = []
    for span in spans:
        kinds = [(l.mixer, l.mlp) for l in span]
        period = next(p for p in range(1, len(span) + 1)
                      if len(span) % p == 0
                      and kinds == kinds[:p] * (len(span) // p))
        out.append(Run(span[0].stack, span[0].mlp_stack, tuple(kinds[:period]),
                       tuple(span)))
    return out


def sinkhorn(m, iters: int):
    """``exp(m)`` [n, n, ...] made doubly stochastic by ``iters`` rounds of
    (rows to sum 1, then columns to sum 1) over its two LEADING axes,
    float32. The sums are plain adds of ``n`` slices, so the rounds are one
    elementwise fusion over whatever lies behind the two axes."""
    n = m.shape[0]
    m = jnp.exp(m - jnp.max(m, axis=(0, 1), keepdims=True))
    for _ in range(iters):
        m = m / sum(m[:, j] for j in range(n))[:, None]
        m = m / sum(m[i] for i in range(n))[None]
    return m


def hyper_pre(cfg: TransformerConfig, p: Params, h):
    """The reading half of a hyper-connection (manifold-constrained,
    arXiv:2512.24880) over the streams ``h`` [n, B, S, d]: (the sub-layer's
    input ``u`` [B, S, d], a mix of the streams; ``post`` [n, B, S] and
    ``res`` [n, n, B, S] float32, what :func:`hyper_post` writes back with).
    A row's mixing values are a projection ``p["w"]`` of its ``n d`` values
    RMS-normed as one vector (eps ``hc_eps``, no learned scale: the
    projection has every weight a scale would have), times a gain a group
    plus a bias: ``pre = sigmoid(.)`` [n], ``post = 2 sigmoid(.)`` [n],
    ``res`` = :func:`sinkhorn` of the [n, n] rest (``res[i, j]``: how much
    of stream ``j`` stream ``i`` keeps). The norm is a scalar a row, so it
    is applied to the projected values, not the streams."""
    n, d = cfg.hc_mult, cfg.hidden_size
    w = p["w"].reshape(n, d, n * n + 2 * n)
    raw = jnp.einsum("nbsd,ndk->kbs", h, w,
                     preferred_element_type=jnp.float32)
    h32 = h.astype(jnp.float32)
    raw = raw * lax.rsqrt(
        jnp.mean(h32 * h32, axis=(0, 3)) + cfg.hc_eps)[None]
    gain = p["gain"]["scale"].astype(jnp.float32)
    bias = p["bias"].astype(jnp.float32)[:, None, None]
    raw = raw * jnp.repeat(gain, np.asarray([n, n, n * n]),
                           total_repeat_length=n * n + 2 * n
                           )[:, None, None] + bias
    pre = jax.nn.sigmoid(raw[:n])
    post = 2.0 * jax.nn.sigmoid(raw[n:2 * n])
    res = sinkhorn(raw[2 * n:].reshape(n, n, *raw.shape[1:]),
                   cfg.hc_sinkhorn_iters)
    u = sum(pre[j][..., None] * h32[j] for j in range(n)).astype(h.dtype)
    return u, post, res


def hyper_post(h, y, post, res):
    """The writing half: stream ``i`` becomes ``sum_j res[i, j] h[j] +
    post[i] y`` (``y`` [B, S, d] the sub-layer's output), float32 inside."""
    n = h.shape[0]
    h32, y32 = h.astype(jnp.float32), y.astype(jnp.float32)
    return jnp.stack([
        sum(res[i, j][..., None] * h32[j] for j in range(n))
        + post[i][..., None] * y32 for i in range(n)]).astype(h.dtype)


def _mix(kind: str, cfg, p, x, rows, pools, index, layer_id, cache_len,
         num_new, tables, places, note):
    """The mixer of one layer over the normed rows ``x``: (out, in x's
    layout, and the pools with the layer's leaves advanced in place).
    ``tables``: the page table of each pool's leaf-name suffix, and
    ``places`` the computed rows' places under it
    (``decoding.page_places``)."""
    if kind == "lightning":
        from .minicpm import STATE, lightning_mixer

        a, state = lightning_mixer(cfg, p, x, rows, pools[STATE], index,
                                   layer_id, cache_len, num_new, note)
        return a, {**pools, STATE: state}
    if kind == "sparse":
        from .minicpm import sparse_mixer

        return sparse_mixer(cfg, p, x, pools, index, cache_len, num_new,
                            tables[""], rows, note, places[""])
    if kind == "kda":
        from .ling import kda_mixer

        return kda_mixer(cfg, p, x, rows, pools, index, cache_len, num_new,
                         note)
    if kind == "gdn":
        from .qwen3_next import gdn_mixer

        return gdn_mixer(cfg, p, x, rows, pools, index, cache_len, num_new,
                         note)
    if kind == "conv":
        from .lfm2 import conv_mixer

        return conv_mixer(cfg, p, x, rows, pools, index, cache_len, num_new,
                          note)
    if kind == "retention":
        from .brumby import retention_mixer

        return retention_mixer(cfg, p, x, rows, pools, index, cache_len,
                               num_new, note)
    from . import decoding

    if kind in ("full", "window"):
        sfx = decoding.WIN if kind == "window" and (
            decoding.WIN in tables) else ""
        names = [n + sfx for n in ("k", "v", "k_scale", "v_scale")
                 if n + sfx in pools]
        # (an indexer's keys beside the full layers' K and V)
        indexed = [decoding.INDEX] if cfg.index_in_pages else []
        a, *written = decoding._cached_attention(
            cfg, p, x, rows, index, *(pools[n] for n in names[:2]),
            cache_len, *(pools[n] for n in names[2:]),
            page_table=tables[sfx], num_new=num_new, kind=kind,
            page_rows=places.get(sfx),
            ki_cache=pools[decoding.INDEX] if indexed else None)
        return a, {**pools, **dict(zip(names + indexed, written))}
    # "latent" | "mla" (whose path a model without mixer_types notes as a
    # "full" layer's: the one name its engine reads)
    return decoding._latent_cached_attention(
        cfg, p, x, rows, index, pools, cache_len, tables[""],
        num_new=num_new,
        kind="full" if kind == "mla" and not cfg.mixer_types else kind,
        page_rows=places[""])


def cached_layers(cfg: TransformerConfig, params: Params, x, rows, pools,
                  cache_len, page_table, num_new, token_valid=None,
                  page_table_win=None):
    """Every layer in published order over the rows ``x`` that ``rows``
    (``decoding.ChunkRows``) computes, [B,S,d] or [1,T,d] packed (with
    ``cfg.hc_mult`` the streams of them, [n,B,S,d]): (hidden in
    the same layout, the pools, the routed layers' stats summed over the
    step or None). ``params``: the stacks, already in the compute type.
    ``page_table_win``: the table of the window layers' pool, where a paged
    cache keeps one."""
    from .decoding import WIN, _note_attention_path as note, page_places

    if num_new is None:
        num_new = jnp.full((rows.B,), rows.S, jnp.int32)
    tables = {"": page_table}
    if page_table is not None and cfg.has_window:
        tables[WIN] = page_table_win
    # where a pool's write puts each computed row: the same in every layer
    places = page_places(rows, pools, tables)
    # muP: a residual branch's weight (1 for a model without it)
    branch = cfg.scale_depth / math.sqrt(cfg.mixer_depth) if (
        cfg.scale_depth != 1.0) else None
    # the most real tokens a step holds: what an expert's capacity is of
    budget = rows.count if (rows.packed or token_valid is None) else rows.S
    if cfg.hc_mult:
        def residual(h, hc, ln, f):  # f: normed rows -> (out, what it keeps)
            u, post, res = hyper_pre(cfg, hc, h)
            y, kept = f(_norm(cfg, ln, u))
            return hyper_post(h, y, post, res), kept

        shard = lambda h: constrain(h, None, ("dp", "fsdp"), None, None)
    else:
        def residual(h, hc, ln, f):
            y, kept = f(_norm(cfg, ln, h))
            return h + (y if branch is None else branch * y), kept

        shard = lambda h: constrain(h, ("dp", "fsdp"), None, None)
    stats = []
    for run in walk_runs(cfg, by_kind=WIN in tables):
        mix_stack, mlp_stack = params[run.stack], params[run.mlp_stack]

        def body(carry, scanned, run=run, mix_stack=mix_stack,
                 mlp_stack=mlp_stack):
            h, pools = carry
            lstats = []
            # one layer at a time out of the whole stack: a slice of the
            # weights a run or a period wide would be a copy
            at = lambda tree, i: jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, i, 0, False), tree)
            for j, (kind, mlp) in enumerate(run.period):
                index, mlp_index, pool_index, layer_id = (
                    s[j] for s in scanned)
                layer = at(mix_stack, index)

                def mix_of(normed, layer=layer, pools=pools):
                    return _mix(
                        kind, cfg, layer["attn"], normed, rows, pools,
                        pool_index, layer_id, cache_len, num_new, tables,
                        places, note)

                if not cfg.parallel_block:
                    h, pools = residual(h, layer.get("hc"), layer["ln1"],
                                        mix_of)
                if mlp_stack is not mix_stack:
                    layer = at(mlp_stack, mlp_index)

                def mlp_of(normed, layer=layer):
                    if mlp != ROUTED:
                        return _mlp(cfg, layer["mlp"], normed, rng=None,
                                    train=False, dense=True)[0], None
                    from ..moe.sharded_moe import moe_serving_mlp

                    # capacity from the STATIC budget, padded and idle rows
                    # to the null expert; the banks go whole, the layer's
                    # index beside them: a kernel over them takes no slice
                    # (which would be a copy)
                    return moe_serving_mlp(
                        cfg, layer["mlp"], normed, token_valid=token_valid,
                        budget_tokens=budget,
                        stack=(mlp_stack["mlp"], mlp_index))

                if cfg.parallel_block:
                    # ONE norm: the mixer and the MLP read it, one sum
                    def both(normed):
                        a, kept = mix_of(normed)
                        m, one = mlp_of(normed)
                        return a + m, (kept, one)

                    h, (pools, one) = residual(h, None, layer["ln1"], both)
                else:
                    h, one = residual(h, layer.get("hc"), layer["ln2"],
                                      mlp_of)
                if one is not None:
                    lstats.append(one)
                h = shard(h)
            return (h, pools), (jax.tree.map(
                lambda *t: jnp.stack(t), *lstats) if lstats else None)

        scanned = tuple(jnp.asarray(run.scanned(f)) for f in (
            "mixer_at", "mlp_at", "pool_at", "layer_id"))
        (x, pools), lstats = lax.scan(body, (x, pools), scanned)
        if lstats is not None:  # [trips, period, ...] -> one row a layer
            stats.append(jax.tree.map(
                lambda a: a.reshape(-1, *a.shape[2:]), lstats))
    return x, pools, _step_stats(stats)


def _step_stats(stats) -> Optional[dict]:
    """The routed layers' stats (a list of [run, ...] stacks) as one view of
    the step: what serving/metrics.on_moe books, and ``experts_touched``,
    the held experts that got at least one row, summed over the layers."""
    if not stats:
        return None
    fill = jnp.concatenate([s["tokens_per_expert"] for s in stats])  # [L, E]
    out = {
        "tokens_per_expert": jnp.sum(fill, axis=0),
        "drop_fraction": jnp.mean(
            jnp.concatenate([s["drop_fraction"] for s in stats])),
        "experts_touched": jnp.sum((fill > 0).astype(jnp.int32)),
    }
    if "unrouted_tokens" in stats[0]:
        out["unrouted_tokens"] = jnp.sum(
            jnp.concatenate([s["unrouted_tokens"] for s in stats]))
    return out
