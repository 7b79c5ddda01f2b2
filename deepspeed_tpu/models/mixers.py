"""The one layer walk under the cached forward: every served model's.

Each layer has a MIXER kind (``transformer.MIXER_KINDS``: "full" | "window" |
"mla" of models/decoding.py, "sparse" | "lightning" of models/minicpm.py,
"kda" | "latent" of models/ling.py, "retention" of models/brumby.py) and,
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


def _mix(kind: str, cfg, p, x, rows, pools, index, layer_id, cache_len,
         num_new, tables, note):
    """The mixer of one layer over the normed rows ``x``: (out, in x's
    layout, and the pools with the layer's leaves advanced in place).
    ``tables``: the page table of each pool's leaf-name suffix."""
    if kind == "lightning":
        from .minicpm import STATE, lightning_mixer

        a, state = lightning_mixer(cfg, p, x, rows, pools[STATE], index,
                                   layer_id, cache_len, num_new, note)
        return a, {**pools, STATE: state}
    if kind == "sparse":
        from .minicpm import sparse_mixer

        return sparse_mixer(cfg, p, x, pools, index, cache_len, num_new,
                            tables[""], rows, note)
    if kind == "kda":
        from .ling import kda_mixer

        return kda_mixer(cfg, p, x, rows, pools, index, cache_len, num_new,
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
        a, *written = decoding._cached_attention(
            cfg, p, x, rows, index, *(pools[n] for n in names[:2]),
            cache_len, *(pools[n] for n in names[2:]),
            page_table=tables[sfx], num_new=num_new, kind=kind)
        return a, {**pools, **dict(zip(names, written))}
    # "latent" | "mla" (whose path is noted as a "full" layer's: the one
    # name the engine of a model without mixer_types reads)
    return decoding._latent_cached_attention(
        cfg, p, x, rows, index, pools, cache_len, tables[""],
        num_new=num_new, kind="full" if kind == "mla" else kind)


def cached_layers(cfg: TransformerConfig, params: Params, x, rows, pools,
                  cache_len, page_table, num_new, token_valid=None,
                  page_table_win=None):
    """Every layer in published order over the rows ``x`` that ``rows``
    (``decoding.ChunkRows``) computes, [B,S,d] or [1,T,d] packed: (hidden in
    the same layout, the pools, the routed layers' stats summed over the
    step or None). ``params``: the stacks, already in the compute type.
    ``page_table_win``: the table of the window layers' pool, where a paged
    cache keeps one."""
    from .decoding import WIN, _note_attention_path as note

    if num_new is None:
        num_new = jnp.full((rows.B,), rows.S, jnp.int32)
    tables = {"": page_table}
    if page_table is not None and cfg.has_window:
        tables[WIN] = page_table_win
    # muP: a residual branch's weight (1 for a model without it)
    branch = cfg.scale_depth / math.sqrt(cfg.mixer_depth) if (
        cfg.scale_depth != 1.0) else None
    # the most real tokens a step holds: what an expert's capacity is of
    budget = rows.count if (rows.packed or token_valid is None) else rows.S
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
                a, pools = _mix(kind, cfg, layer["attn"],
                                _norm(cfg, layer["ln1"], h), rows, pools,
                                pool_index, layer_id, cache_len, num_new,
                                tables, note)
                h = h + (a if branch is None else branch * a)
                if mlp_stack is not mix_stack:
                    layer = at(mlp_stack, mlp_index)
                normed = _norm(cfg, layer["ln2"], h)
                if mlp == ROUTED:
                    from ..moe.sharded_moe import moe_serving_mlp

                    # capacity from the STATIC budget, padded and idle rows
                    # to the null expert; the banks go whole, the layer's
                    # index beside them: a kernel over them takes no slice
                    # (which would be a copy)
                    m, one = moe_serving_mlp(
                        cfg, layer["mlp"], normed, token_valid=token_valid,
                        budget_tokens=budget,
                        stack=(mlp_stack["mlp"], mlp_index))
                    lstats.append(one)
                else:
                    m, _ = _mlp(cfg, layer["mlp"], normed, rng=None,
                                train=False, dense=True)
                h = h + (m if branch is None else branch * m)
                h = constrain(h, ("dp", "fsdp"), None, None)
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
