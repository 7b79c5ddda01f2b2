"""Functional collectives for use inside ``shard_map``.

Parity: deepspeed/comm/comm.py op surface (all_reduce, all_gather,
reduce_scatter, broadcast, all_to_all_single, send/recv) — rebuilt on
``jax.lax`` collectives so XLA schedules them over ICI. The reference's
NCCL process groups become mesh axis names.

Every op routes through :func:`_record` so the communication logger
(deepspeed_tpu.profiling.comm_logger) sees op name, bytes, and axis —
parity with the reference's comms_logger hooks in deepspeed/comm.
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax import lax

AxisName = Union[str, Sequence[str]]

_COMM_HOOKS = []


def register_comm_hook(fn: Callable) -> None:
    """fn(op_name, axis_name, nbytes) — used by the comms logger."""
    _COMM_HOOKS.append(fn)


def unregister_comm_hook(fn: Callable) -> None:
    """Remove one subscriber; other loggers' hooks stay registered."""
    try:
        _COMM_HOOKS.remove(fn)
    except ValueError:
        pass


def clear_comm_hooks() -> None:
    _COMM_HOOKS.clear()


def _record(op: str, axis: AxisName, x) -> None:
    if not _COMM_HOOKS:
        return
    nbytes = 0
    for leaf in jax.tree_util.tree_leaves(x):
        if hasattr(leaf, "size") and hasattr(leaf, "dtype"):
            nbytes += leaf.size * jnp.dtype(leaf.dtype).itemsize
    for hook in _COMM_HOOKS:
        hook(op, axis, nbytes)


# -- reduction ops -------------------------------------------------------------
def all_reduce(x, axis_name: AxisName, op: str = "sum"):
    """Parity: deepspeed.comm.all_reduce (inside shard_map)."""
    _record("all_reduce", axis_name, x)
    if op == "sum":
        return lax.psum(x, axis_name)
    if op == "mean":
        return lax.pmean(x, axis_name)
    if op == "max":
        return lax.pmax(x, axis_name)
    if op == "min":
        return lax.pmin(x, axis_name)
    raise ValueError(f"unsupported reduce op {op}")


def reduce_scatter(x, axis_name: AxisName, scatter_dimension: int = 0, tiled: bool = True):
    """Parity: deepspeed.comm.reduce_scatter_tensor. Sum-reduces across the
    axis and leaves each shard with its slice along ``scatter_dimension``."""
    _record("reduce_scatter", axis_name, x)
    return lax.psum_scatter(x, axis_name, scatter_dimension=scatter_dimension, tiled=tiled)


def all_gather(x, axis_name: AxisName, gather_dimension: int = 0, tiled: bool = True):
    """Parity: deepspeed.comm.all_gather_into_tensor."""
    _record("all_gather", axis_name, x)
    return lax.all_gather(x, axis_name, axis=gather_dimension, tiled=tiled)


def broadcast(x, axis_name: AxisName, src: int = 0):
    """Parity: deepspeed.comm.broadcast — select src's value on every member.

    Implemented as a masked psum (XLA lowers to an efficient broadcast)."""
    _record("broadcast", axis_name, x)
    idx = lax.axis_index(axis_name)
    mask = (idx == src).astype(x.dtype if jnp.issubdtype(x.dtype, jnp.floating) else jnp.float32)
    masked = x * mask if jnp.issubdtype(x.dtype, jnp.floating) else (x * mask.astype(x.dtype))
    return lax.psum(masked, axis_name)


def all_to_all(x, axis_name: AxisName, split_axis: int, concat_axis: int, tiled: bool = True):
    """Parity: deepspeed.comm.all_to_all_single — the MoE dispatch/combine and
    DS-Ulysses head↔sequence exchange primitive."""
    _record("all_to_all", axis_name, x)
    return lax.all_to_all(x, axis_name, split_axis=split_axis, concat_axis=concat_axis, tiled=tiled)


def check_permutation(perm, axis_size: int):
    """Problems with a ppermute permutation (empty list == well-formed).

    Re-exported from analysis/rules/topology.py — ONE implementation is
    both the static lint (shardlint R3) and the construction-time guard
    below, so "passes the hook" and "passes the lint" can never drift."""
    from ..analysis.rules.topology import check_permutation as _check

    return _check(perm, axis_size)


def permute(x, axis_name: AxisName, perm, *, validate: bool = True):
    """Parity: deepspeed.comm send/recv pairs in the pipeline engine — a
    static ring/permutation shift via collective-permute over ICI.

    Ring/chain contract (the same one shardlint R3 certifies and
    runtime/pipe/schedule.neighbor_chain states): ``perm`` must be an
    injective partial map with no self-loops whose cycle structure is
    either pure chains (the pipeline neighbor hop) or ONE full ring
    covering the whole axis — anything else (disjoint sub-rings, a ring
    plus stray edges, duplicate endpoints) is not a wrong answer on real
    ICI but a *hang*. With ``validate=True`` (default) the contract is
    enforced at construction time via
    :func:`analysis.rules.topology.check_permutation`, so callers like
    parallel/tensor_overlap's decomposed-matmul rings are lint-guaranteed
    the moment they trace, not only when shardlint later walks the jaxpr.
    Validation needs the static axis size; where it cannot be determined
    (outside any mapped context) the check is skipped and shardlint
    remains the backstop."""
    if validate:
        n = None
        try:
            n = int(jax.lax.axis_size(axis_name))
        except Exception:  # noqa: BLE001 — unbound/odd axis env: lint-only
            n = None
        if n is not None:
            problems = check_permutation(perm, n)
            if problems:
                raise ValueError(
                    f"malformed ppermute permutation over axis "
                    f"{axis_name!r} (size {n}): " + "; ".join(problems)
                    + " — this hangs or deadlocks on real ICI"
                )
    _record("ppermute", axis_name, x)
    return lax.ppermute(x, axis_name, perm=perm)


def send_forward(x, axis_name: AxisName, axis_size: int, wrap: bool = False):
    """Shift +1 along the axis (pipeline 'send to next stage').

    With ``wrap=False`` the first member receives zeros (like a recv with no
    sender); with ``wrap=True`` it is a ring rotation."""
    n = axis_size
    perm = [(i, (i + 1) % n) for i in range(n)] if wrap else [(i, i + 1) for i in range(n - 1)]
    return permute(x, axis_name, perm)


def send_backward(x, axis_name: AxisName, axis_size: int, wrap: bool = False):
    n = axis_size
    perm = [(i, (i - 1) % n) for i in range(n)] if wrap else [(i + 1, i) for i in range(n - 1)]
    return permute(x, axis_name, perm)


def axis_index(axis_name: AxisName):
    return lax.axis_index(axis_name)


def barrier(axis_name: AxisName):
    """Parity: deepspeed.comm.barrier — a no-data psum forces a sync point."""
    _record("barrier", axis_name, jnp.zeros(()))
    return lax.psum(jnp.zeros(()), axis_name)
