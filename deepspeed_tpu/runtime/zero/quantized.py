"""ZeRO wire-codec collectives: qwZ/qgZ/hgZ on the shared comm layer.

Parity: deepspeed/runtime/zero/stage3.py quantized all-gather +
csrc/quantization kernels + the ZeRO++ paper (qwZ / qgZ / hgZ). The
reference quantizes NCCL payloads with hand-written CUDA; here each
stage-3-sharded parameter is gathered through an explicit ``shard_map``
collective whose wire format is a :mod:`deepspeed_tpu.comm.wires` codec
(fp32 / bf16 / int8 / int4, lane-wise scales): the forward moves
``param_wire`` bytes (qwZ at int8), and its custom backward — the
gradient reduce-scatter — moves ``grad_wire`` bytes via the qgZ
all-to-all formulation (values quantize once, the accumulate runs after
dequant, in f32). With ``hierarchical_wire`` and a factored (dp, fsdp)
leaf, both directions run the 2-hop form: full width intra-group over
the fast inner links, codec bytes inter-group (hgZ).

The legacy ``zero_quantized_weights`` / ``zero_quantized_gradients``
bools map to int8 codecs (``ZeroConfig.resolved_param_wire`` /
``resolved_grad_wire``); ``_quantize_lanewise`` survives as a re-export
of the shared :func:`comm.wires.quantize_lanewise` (bitwise identical).

hpZ composes for free: the gather axes come from the param's sharding
spec, which hpZ restricts to the ``fsdp`` sub-axis
(runtime/zero/partition.py).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ...comm import collectives, wires

# shared lane-wise int8 entry (the pre-wires private helper, kept as a
# name so existing imports — parallel/tensor_overlap among them — keep
# resolving to the ONE implementation)
_quantize_lanewise = wires.quantize_lanewise


def _spec_entries(spec: P, ndim: int) -> list:
    entries = list(spec) + [None] * (ndim - len(spec))
    return entries[:ndim]


def gather_dim_and_axes(param_spec: P, tp_spec: P, ndim: int):
    """Locate the ZeRO-sharded dim: the one entry where param_spec carries
    mesh axes that tp_spec doesn't. Returns (dim, extra_axes) or None."""
    p_entries = _spec_entries(param_spec, ndim)
    t_entries = _spec_entries(tp_spec, ndim)
    for i, (pe, te) in enumerate(zip(p_entries, t_entries)):
        p_axes = pe if isinstance(pe, tuple) else ((pe,) if pe else ())
        t_axes = te if isinstance(te, tuple) else ((te,) if te else ())
        extra = tuple(a for a in p_axes if a not in t_axes)
        if extra:
            return i, extra
    return None


@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6))
def _gather_leaf(local, axes, dim, n, param_wire, grad_wire, hier):
    """All-gather a stage-3 shard along ``dim`` over mesh ``axes`` (size
    ``n``) moving ``param_wire`` codec bytes. Backward: the gradient
    reduce-scatter in ``grad_wire`` codec bytes (qgZ). ``hier`` is the
    :func:`comm.wires.hier_axes` tuple or None."""
    x = jnp.moveaxis(local, dim, 0)
    codec = wires.get_codec(param_wire)
    # hier FIRST: with hierarchical_wire on, even fp32 wires run the
    # 2-hop form (the topology win — only 1/n_inner of the bytes cross
    # the slow outer links — exists without any quantization, and the
    # engine's analytic streams declare exactly that split)
    if hier is not None:
        o, n_o, i, n_i = hier
        full = wires.ag_wire_hier_local(x, o, i, n_o, n_i, codec,
                                        dtype=local.dtype)
    elif codec.name == "fp32":
        collectives._record("all_gather", axes, x)
        full = lax.all_gather(x, axes, axis=0, tiled=True)
    else:
        full = wires.ag_wire_local(x, axes, n, codec, dtype=local.dtype)
    return jnp.moveaxis(full, 0, dim)


def _gather_leaf_fwd(local, axes, dim, n, param_wire, grad_wire, hier):
    return (
        _gather_leaf(local, axes, dim, n, param_wire, grad_wire, hier),
        None,
    )


def _gather_leaf_bwd(axes, dim, n, param_wire, grad_wire, hier, _res, gbar):
    g = jnp.moveaxis(gbar, dim, 0)  # [d, rest...] full gradient
    codec = wires.get_codec(grad_wire)
    if hier is not None:  # hier first — see _gather_leaf
        o, n_o, i, n_i = hier
        local = wires.rs_wire_hier_local(g, o, i, n_o, n_i, codec,
                                         dtype=gbar.dtype)
    elif codec.name == "fp32":
        collectives._record("reduce_scatter", axes, g)
        local = lax.psum_scatter(g, axes, scatter_dimension=0, tiled=True)
    else:
        local = wires.rs_wire_local(g, axes, n, codec, dtype=gbar.dtype)
    return (jnp.moveaxis(local.astype(gbar.dtype), 0, dim),)


_gather_leaf.defvjp(_gather_leaf_fwd, _gather_leaf_bwd)


def make_leaf_gather(topo, pspec: P, tpspec: P, shape: Tuple[int, ...],
                     param_wire: str, grad_wire: str,
                     hierarchical: bool = False):
    """One leaf's ``shard -> full`` wire gather (partial-manual shard_map
    over just its ZeRO axes), or None when the leaf carries no ZeRO data
    axes. The building block :func:`make_quantized_gather` maps over the
    tree — exposed so the stage-3 layer prefetch can compose the SAME
    wire gather into its rotating-slot scan (runtime/zero/prefetch.py)."""
    ndim = len(shape)
    hit = gather_dim_and_axes(pspec, tpspec, ndim)
    if hit is None:
        return None
    dim, axes = hit
    n = 1
    for a in axes:
        n *= topo.sizes[a]
    # ONE eligibility predicate for the 2-hop forms (wires.hier_axes) —
    # the executed collective and the engine's priced stream share it
    hier = wires.hier_axes(topo, axes) if hierarchical else None
    # partial-manual specs mention only the manual (ZeRO) axes; the tp
    # sharding of the same array rides the automatic axes
    in_spec = P(*([None] * dim + [axes if len(axes) > 1 else axes[0]]))

    # custom_vjp takes positional args only — bind via default-arg closure
    def _bound(x, _axes=axes, _dim=dim, _n=n, _hier=hier):
        return _gather_leaf(x, _axes, _dim, _n, param_wire, grad_wire,
                            _hier)

    return jax.shard_map(
        _bound,
        mesh=topo.mesh,
        in_specs=in_spec,
        out_specs=P(),
        axis_names=set(axes),
        check_vma=False,
    )


def make_quantized_gather(topo, param_specs: Any, tp_specs: Any,
                          params_shape: Any, quant_weights: bool = False,
                          quant_grads: bool = False, *,
                          param_wire: Optional[str] = None,
                          grad_wire: Optional[str] = None,
                          hierarchical: bool = False,
                          exclude_key: Optional[str] = None):
    """Build ``gather(params) -> full params`` applying the wire codecs
    per leaf. ``quant_weights`` / ``quant_grads`` are the legacy bool
    spelling (True == int8); ``param_wire`` / ``grad_wire`` codec names
    take precedence. ``exclude_key``: a top-level tree key whose leaves
    pass through untouched — the stage-3 layer prefetch owns the stacked
    ``layers`` group's gathers when both knobs are on
    (runtime/zero/prefetch.py), and gathering it twice would both waste
    wire and defeat the prefetch.

    Leaves whose spec carries no ZeRO data axes (persistence-threshold
    survivors, pure-TP leaves) pass through untouched; XLA keeps handling
    them implicitly. The returned callable runs inside jit (each gathered
    leaf is a partial-manual ``shard_map`` over just the ZeRO axes; tp/pp
    axes stay automatic)."""
    param_wire = param_wire or ("int8" if quant_weights else "fp32")
    grad_wire = grad_wire or ("int8" if quant_grads else "fp32")
    is_spec = lambda x: isinstance(x, P)

    if exclude_key is not None and isinstance(param_specs, dict) and (
        exclude_key in param_specs
    ):
        # replacing the excluded subtree's param specs with its tp specs
        # makes gather_dim_and_axes report "no ZeRO axes" there — the
        # passthrough path, with zero special-casing downstream
        param_specs = {**param_specs, exclude_key: tp_specs[exclude_key]}

    shapes_flat, treedef = jax.tree_util.tree_flatten(params_shape)
    pspecs_flat = jax.tree_util.tree_leaves(param_specs, is_leaf=is_spec)
    tspecs_flat = jax.tree_util.tree_leaves(tp_specs, is_leaf=is_spec)
    assert len(shapes_flat) == len(pspecs_flat) == len(tspecs_flat)

    fns = [
        make_leaf_gather(topo, pspec, tpspec, shape_leaf.shape,
                         param_wire, grad_wire, hierarchical)
        for shape_leaf, pspec, tpspec in zip(
            shapes_flat, pspecs_flat, tspecs_flat
        )
    ]

    def gather(params):
        leaves = treedef.flatten_up_to(params)
        out = [w if fn is None else fn(w) for w, fn in zip(leaves, fns)]
        return jax.tree_util.tree_unflatten(treedef, out)

    return gather
