"""ZeRO partitioning as sharding-spec derivation.

Parity: deepspeed/runtime/zero/stage_1_and_2.py + stage3.py. The reference
hand-implements flat-buffer partitioning, parameter all-gather and gradient
reduce-scatter over NCCL; on TPU every stage is a *rule for placing arrays on
the mesh* and XLA emits exactly those collectives:

- stage 0: params/grads/opt replicated over data axes; grad psum (DDP).
- stage 1: optimizer state + fp32 master sharded over data axes.
- stage 2: + gradients materialize sharded (psum becomes reduce-scatter).
- stage 3: + parameters sharded; all-gather-on-use, FSDP semantics.
- ZeRO++ hpZ / MiCS: params shard over the inner ``fsdp`` sub-axis only and
  replicate over ``dp`` (gathers stay inside the sub-mesh / node).

Small params (< stage3_param_persistence_threshold elements) stay replicated
in stage 3, mirroring the reference's persistence threshold.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ...comm.topology import MeshTopology
from ...config import ZeroConfig


def _axes_product(topo: MeshTopology, axes: Tuple[str, ...]) -> int:
    n = 1
    for a in axes:
        n *= topo.sizes[a]
    return n


def data_axes(topo: MeshTopology, zero_cfg: Optional[ZeroConfig] = None,
              params_level: bool = False) -> Tuple[str, ...]:
    """Mesh axes available for ZeRO sharding.

    For parameter sharding under hpZ/MiCS, only the inner ``fsdp`` sub-axis is
    used so all-gathers ride the fastest links (reference: zero_hpz_partition_size).
    """
    hpz = zero_cfg is not None and params_level and (
        zero_cfg.zero_hpz_partition_size > 1 or zero_cfg.mics_shard_size > 0
    )
    if hpz and topo.sizes["fsdp"] > 1:
        return ("fsdp",)
    return tuple(a for a in ("dp", "fsdp") if topo.sizes[a] > 1)


def add_data_axes(spec: P, shape: Tuple[int, ...], topo: MeshTopology,
                  axes: Tuple[str, ...]) -> P:
    """Shard the largest divisible, not-yet-sharded dim of ``shape`` over
    ``axes``; returns ``spec`` unchanged if nothing divides (stays replicated)."""
    if not axes or not shape:
        return spec
    n = _axes_product(topo, axes)
    entries = list(spec) + [None] * (len(shape) - len(spec))
    used = set()
    for e in entries:
        for a in (e if isinstance(e, tuple) else (e,)):
            if a:
                used.add(a)
    if any(a in used for a in axes):
        return spec
    # per-dim size after existing sharding
    best, best_size = None, 0
    for i, (dim, e) in enumerate(zip(shape, entries)):
        if e is not None:
            continue
        if dim % n == 0 and dim > best_size:
            best, best_size = i, dim
    if best is None:
        return spec
    entries[best] = axes if len(axes) > 1 else axes[0]
    return P(*entries)


def zero_specs(
    params_tree: Any,
    tp_specs: Any,
    topo: MeshTopology,
    zero_cfg: ZeroConfig,
) -> Tuple[Any, Any, Any]:
    """Derive (param_specs, grad_specs, optstate_leaf_specs) per stage.

    ``optstate_leaf_specs`` mirrors params (optax state leaves that match a
    param shape inherit its spec; scalars replicate).
    """
    stage = zero_cfg.stage
    d_axes = data_axes(topo, zero_cfg)
    p_axes = data_axes(topo, zero_cfg, params_level=True)
    threshold = zero_cfg.stage3_param_persistence_threshold

    def param_spec(x, tp_spec):
        if stage < 3 or int(np.prod(x.shape)) < threshold:
            return tp_spec
        return add_data_axes(tp_spec, x.shape, topo, p_axes)

    def grad_spec(x, tp_spec):
        if stage >= 3:
            return param_spec(x, tp_spec)
        if stage >= 2:
            return add_data_axes(tp_spec, x.shape, topo, d_axes)
        return tp_spec

    def opt_spec(x, tp_spec):
        if stage >= 1:
            return add_data_axes(tp_spec, x.shape, topo, d_axes)
        return tp_spec

    p_specs = jax.tree.map(param_spec, params_tree, tp_specs)
    g_specs = jax.tree.map(grad_spec, params_tree, tp_specs)
    o_specs = jax.tree.map(opt_spec, params_tree, tp_specs)
    return p_specs, g_specs, o_specs


def opt_state_sharding(tx, opt_state, opt_leaf_specs, topo: MeshTopology,
                       memory_kind: Optional[str] = None):
    """Shardings for an optax state: param-shaped leaves (moments, master
    copies) inherit the matching param's spec *by tree position* (via
    optax.tree_map_params); counts/scalars replicate."""
    import optax

    kwargs = {"memory_kind": memory_kind} if memory_kind else {}
    replicated = NamedSharding(topo.mesh, P())

    # a leaf the optimizer is masked from (engine state that is no
    # parameter: optax.masked keeps a MaskedNode in its place) has no state
    # to shard
    masked = lambda x: isinstance(x, optax.MaskedNode)  # noqa: E731

    return optax.tree_map_params(
        tx,
        lambda leaf, spec: leaf if masked(leaf) else NamedSharding(
            topo.mesh, spec, **kwargs),
        opt_state,
        opt_leaf_specs,
        transform_non_params=lambda leaf: replicated,
        is_leaf=masked,
    )


def make_shardings(specs_tree, topo: MeshTopology, memory_kind: Optional[str] = None):
    kwargs = {"memory_kind": memory_kind} if memory_kind else {}
    return jax.tree.map(
        lambda s: NamedSharding(topo.mesh, s, **kwargs),
        specs_tree,
        is_leaf=lambda x: isinstance(x, P),
    )
