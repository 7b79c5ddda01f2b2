"""Activation/parameter sharding context for model code.

Models call :func:`constrain` on activations; when the engine has installed a
mesh (via :func:`use_topology`), this lowers to
``jax.lax.with_sharding_constraint`` so XLA propagates TP/SP/DP layouts and
inserts the collectives. With no mesh installed (single-device unit tests),
it is a no-op — model code never branches on distribution.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec

from ..comm.topology import MeshTopology

_local = threading.local()


def current_topology() -> Optional[MeshTopology]:
    return getattr(_local, "topology", None)


@contextlib.contextmanager
def use_topology(topology: Optional[MeshTopology]):
    prev = current_topology()
    _local.topology = topology
    try:
        yield topology
    finally:
        _local.topology = prev


def manual_axis_names() -> set:
    """Mesh axes that are Manual right now — non-empty only while tracing
    inside a ``shard_map`` (the pipeline schedule, the 1-bit wire path)."""
    am = jax.sharding.get_abstract_mesh()
    return {
        name
        for name, t in zip(am.axis_names, am.axis_types)
        if t == jax.sharding.AxisType.Manual
    }


def _filter_spec(spec: PartitionSpec, topo: MeshTopology) -> PartitionSpec:
    """Drop axes of size 1 so specs stay valid on degenerate meshes."""

    def keep(entry):
        if entry is None:
            return None
        if isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if topo.sizes.get(a, 1) > 1)
            return kept if kept else None
        return entry if topo.sizes.get(entry, 1) > 1 else None

    return PartitionSpec(*(keep(e) for e in spec))


def constrain(x, *spec_entries):
    """Constrain activation sharding; no-op outside an installed topology.

    Inside a partially-manual ``shard_map`` (the pipeline schedule: pp is
    Manual, the rest Auto), constraints must be expressed on the context's
    abstract mesh with Manual axes dropped from the spec."""
    topo = current_topology()
    if topo is None or topo.world_size == 1:
        return x
    spec = _filter_spec(PartitionSpec(*spec_entries), topo)
    manual = manual_axis_names()
    if manual:
        def drop(entry):
            if entry is None:
                return None
            if isinstance(entry, (tuple, list)):
                kept = tuple(a for a in entry if a not in manual)
                return kept if kept else None
            return None if entry in manual else entry

        spec = PartitionSpec(*(drop(e) for e in spec))
        am = jax.sharding.get_abstract_mesh()
        return jax.lax.with_sharding_constraint(x, NamedSharding(am, spec))
    return jax.lax.with_sharding_constraint(x, NamedSharding(topo.mesh, spec))


def batch_seq_spec() -> tuple:
    """Standard activation layout entries: (batch over dp+fsdp, seq over sp)."""
    return (("dp", "fsdp"), "sp")
