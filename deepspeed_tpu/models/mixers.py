"""The one layer walk of a model whose layers are named one by one
(``TransformerConfig.mixer_types``).

Each layer has a MIXER kind (``transformer.MIXER_KINDS``: "sparse" |
"lightning" of models/minicpm.py, "kda" | "latent" of models/ling.py) and,
independently, an MLP kind ("dense": a SwiGLU of the layer's own width, the
leading dense layers of a routed model among them; "routed": one member's
share of a sigmoid-routed expert layer, ``moe/sharded_moe.moe_serving_mlp``).
A kind of either sort has a parameter stack of its own, and a layer reads its
mixer and its MLP each at its index inside the kind's stack. Runs of equal
(mixer, MLP) are one ``lax.scan`` each, all over the same carry: the hidden
rows and the pools (pages by page table, state leaves by slot), every leaf
whole and written in place at the layer's index.

The module that owns a model's kinds (``family(cfg)``) gives ``STACK`` (mixer
kind -> stack), ``MLP_STACK`` (MLP kind -> stack, or None: the MLP lies in
its mixer's stack, at the mixer's index), ``init``, ``num_params``,
``init_pools`` and ``slot_leaves``.
"""

from __future__ import annotations

import importlib
import math
from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .transformer import Params, TransformerConfig, _mlp, _norm

DENSE, ROUTED = "dense", "routed"


def family(cfg: TransformerConfig):
    """The module of models/ that owns the kinds ``cfg.mixer_types`` names."""
    return importlib.import_module(
        f"{__package__}.{cfg.mixer_family}")


def stacks_of(cfg: TransformerConfig) -> Tuple[str, ...]:
    """The names of the parameter stacks the walk reads."""
    fam = family(cfg)
    names = [fam.STACK[k] for k in dict.fromkeys(cfg.mixer_types)]
    names += [s for s in fam.MLP_STACK.values() if s]
    return tuple(dict.fromkeys(names))


def slot_leaves(cfg: TransformerConfig, max_slots: int, dtype) -> dict:
    """The pool leaves that are indexed by slot and no page, name ->
    ``jax.ShapeDtypeStruct`` ({} for a model without state layers)."""
    if not cfg.has_state:
        return {}
    return family(cfg).slot_leaves(cfg, max_slots, dtype)


class Layer(NamedTuple):
    mixer: str       # a kind of MIXER_KINDS
    mixer_at: int    # index inside the mixer kind's stack
    mlp: str         # DENSE | ROUTED
    mlp_at: int      # index inside the MLP kind's stack
    layer_id: int    # the layer's published index


def layer_plan(cfg: TransformerConfig) -> List[Layer]:
    """Every layer in published order with where its two halves lie."""
    fam = family(cfg)
    seen: dict = {}
    plan = []
    for i, (kind, lid) in enumerate(zip(cfg.mixer_types,
                                        cfg.mixer_layer_ids)):
        mlp = ROUTED if cfg.is_moe and i >= cfg.lead_dense_layers else DENSE
        at = seen.get(kind, 0)
        seen[kind] = at + 1
        if fam.MLP_STACK[mlp] is None:
            mlp_at = at
        else:
            mlp_at = seen.get(mlp, 0)
            seen[mlp] = mlp_at + 1
        plan.append(Layer(kind, at, mlp, mlp_at, int(lid)))
    return plan


def runs(cfg: TransformerConfig) -> List[Tuple[str, int, int]]:
    """The layers in published order as runs of one mixer kind: (kind, the
    run's first index inside its kind's stack, its length). (A run of the
    walk also ends where the MLP kind changes: :func:`_runs`.)"""
    out: list = []
    for layer in layer_plan(cfg):
        if out and out[-1][0] == layer.mixer:
            out[-1] = (layer.mixer, out[-1][1], out[-1][2] + 1)
        else:
            out.append((layer.mixer, layer.mixer_at, 1))
    return out


def _runs(cfg: TransformerConfig) -> List[List[Layer]]:
    out: List[List[Layer]] = []
    for layer in layer_plan(cfg):
        if out and (out[-1][-1].mixer, out[-1][-1].mlp) == (
                layer.mixer, layer.mlp):
            out[-1].append(layer)
        else:
            out.append([layer])
    return out


def _mix(kind: str, cfg, p, x, rows, pools, index, layer_id, cache_len,
         num_new, page_table, note):
    """The mixer of one layer over the normed rows ``x``: (out, in x's
    layout, and the pools with the layer's leaves advanced in place)."""
    if kind == "lightning":
        from .minicpm import STATE, lightning_mixer

        a, state = lightning_mixer(cfg, p, x, rows, pools[STATE], index,
                                   layer_id, cache_len, num_new, note)
        return a, {**pools, STATE: state}
    if kind == "sparse":
        from .minicpm import sparse_mixer

        return sparse_mixer(cfg, p, x, pools, index, cache_len, num_new,
                            page_table, rows, note)
    if kind == "kda":
        from .ling import kda_mixer

        return kda_mixer(cfg, p, x, rows, pools, index, cache_len, num_new,
                         note)
    from .decoding import _latent_cached_attention

    return _latent_cached_attention(cfg, p, x, rows, index, pools, cache_len,
                                    page_table, num_new=num_new, kind=kind)


def cached_layers(cfg: TransformerConfig, params: Params, x, rows, pools,
                  cache_len, page_table, num_new, token_valid=None):
    """Every layer in published order over the rows ``x`` that ``rows``
    (``decoding.ChunkRows``) computes, [B,S,d] or [1,T,d] packed: (hidden in
    the same layout, the pools, the routed layers' stats summed over the
    step or None). ``params``: the stacks, already in the compute type."""
    from .decoding import _note_attention_path as note

    fam = family(cfg)
    if num_new is None:
        num_new = jnp.full((rows.B,), rows.S, jnp.int32)
    # muP: a residual branch's weight (1 for a model without it)
    branch = cfg.scale_depth / math.sqrt(cfg.mixer_depth) if (
        cfg.scale_depth != 1.0) else None
    # the most real tokens a step holds: what an expert's capacity is of
    budget = rows.count if (rows.packed or token_valid is None) else rows.S
    stats = []
    for run in _runs(cfg):
        kind, mlp = run[0].mixer, run[0].mlp
        mix_stack = params[fam.STACK[kind]]
        mlp_stack = params[fam.MLP_STACK[mlp] or fam.STACK[kind]]

        def body(carry, scanned, kind=kind, mlp=mlp, mix_stack=mix_stack,
                 mlp_stack=mlp_stack):
            h, pools = carry
            index, mlp_index, layer_id = scanned
            # one layer at a time out of the whole stack: a run-sized slice
            # of the weights would be a copy
            at = lambda tree, i: jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, i, 0, False), tree)
            layer = at(mix_stack, index)
            a, pools = _mix(kind, cfg, layer["attn"],
                            _norm(cfg, layer["ln1"], h), rows, pools, index,
                            layer_id, cache_len, num_new, page_table, note)
            h = h + (a if branch is None else branch * a)
            if mlp_stack is not mix_stack:
                layer = at(mlp_stack, mlp_index)
            normed = _norm(cfg, layer["ln2"], h)
            if mlp == ROUTED:
                from ..moe.sharded_moe import moe_serving_mlp

                # the banks go whole, the layer's index beside them: a
                # kernel over them takes no slice (which would be a copy)
                m, lstats = moe_serving_mlp(
                    cfg, layer["mlp"], normed, token_valid=token_valid,
                    budget_tokens=budget, stack=(mlp_stack["mlp"], mlp_index))
            else:
                m, _ = _mlp(cfg, layer["mlp"], normed, rng=None, train=False,
                            dense=True)
                lstats = None
            return (h + (m if branch is None else branch * m), pools), lstats

        scanned = tuple(jnp.asarray([getattr(l, f) for l in run], jnp.int32)
                        for f in ("mixer_at", "mlp_at", "layer_id"))
        (x, pools), lstats = lax.scan(body, (x, pools), scanned)
        if lstats is not None:
            stats.append(lstats)
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
