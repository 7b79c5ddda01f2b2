"""Sharding-aware jaxpr walk: live-set peak, FLOPs, HBM and ICI traffic.

The planner's engine room. One recursive pass over a traced program
(abstract only — nothing executes) computes, per device:

- **activation live-set high-water mark**: a last-use liveness sweep over
  each jaxpr level, descending structurally into scan/while/cond/pjit/
  remat/shard_map bodies. Buffer-reuse credit mirrors XLA's assignment
  coarsely: an output may take over a buffer freed at the same equation
  (in-place elementwise, the rotating offload/KV slots) — for top-level
  inputs only when they were donated at the jit boundary, which is the
  R4 aliasing contract made quantitative.
- **per-value bytes** via a forward "dimspec" propagation: each value
  carries one divisor per array dimension (the product of mesh-axis
  sizes sharding that dim). Seeds are the known arg shardings plus every
  ``device_put``/``sharding_constraint`` pin; transfer rules cover the
  primitives that move real bytes (dot_general drops contracted-dim
  sharding — a dp-sharded activation contracted away yields a
  *replicated* gradient, which is exactly what XLA's psum produces).
  Inside ``shard_map`` bodies avals are already per-shard, so divisors
  reset to 1 and bytes are per-device by construction.
- **MXU FLOPs** (dot_general only: 2·|out|·K, divided by the output's
  AND the contracted dims' shard counts) and **HBM traffic** for the
  materializing primitives (dots, gathers/scatters, reductions,
  collectives — elementwise chains are assumed fused away).
- **ICI traffic**: every named collective classified by mesh axis into
  per-device wire bytes and hop counts with the standard ring factors
  (psum 2(n−1)/n, all_gather/reduce_scatter (n−1)/n·full, ppermute 1
  hop), multiplied through enclosing scan lengths.

Everything here is an *estimate with stated bias*: fusion makes the
traffic figure an upper bound, GSPMD-inserted resharding collectives are
not in the traced program (only explicitly written collectives are
visible), and while-loop trip counts default to 1.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..trace import (
    Jaxpr,
    Literal,
    as_jaxpr,
    axis_names_of,
    collective_axes,
    scan_split,
    shard_map_names,
)

_CALL_KEYS = ("jaxpr", "call_jaxpr", "fun_jaxpr")

# ring collectives: (wire-bytes multiplier fn of (n, payload), hops fn)
_COLLECTIVES = {
    "psum": (lambda n, b: 2.0 * (n - 1) / n * b, lambda n: 2 * (n - 1)),
    "pmin": (lambda n, b: 2.0 * (n - 1) / n * b, lambda n: 2 * (n - 1)),
    "pmax": (lambda n, b: 2.0 * (n - 1) / n * b, lambda n: 2 * (n - 1)),
    "all_gather": (lambda n, b: float(n - 1) * b, lambda n: n - 1),
    "reduce_scatter": (lambda n, b: (n - 1) / n * b, lambda n: n - 1),
    "psum_scatter": (lambda n, b: (n - 1) / n * b, lambda n: n - 1),
    "all_to_all": (lambda n, b: (n - 1) / n * b, lambda n: 1),
    "ppermute": (lambda n, b: float(b), lambda n: 1),
    "pshuffle": (lambda n, b: float(b), lambda n: 1),
}

# primitives whose operands/results actually move through HBM in the
# fused program (elementwise chains between them are fused away)
_MATERIALIZING = {
    "dot_general", "conv_general_dilated", "gather", "scatter",
    "scatter-add", "dynamic_slice", "dynamic_update_slice", "sort",
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
    "argmax", "argmin", "cumsum", "cumlogsumexp", "concatenate",
} | set(_COLLECTIVES)

# reduction-family consumers XLA fuses INTO their producer (loop/epilogue
# fusion): a single-use intermediate between a fusable producer and one
# of these never materializes — charging both the producer's write and
# the consumer's read double-counted it (the documented PR-4 bias)
_FUSABLE_REDUCERS = {
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
    "argmax", "argmin", "cumsum", "cumlogsumexp",
}
# producers whose output an elementwise+reduce consumer fuses onto;
# collectives and scatter-family writes keep their charges (their outputs
# come out of dedicated buffers the consumer really reads back)
_FUSABLE_PRODUCERS = _MATERIALIZING - set(_COLLECTIVES) - {
    "scatter", "scatter-add", "dynamic_update_slice", "sort",
}


def _itemsize(dtype) -> float:
    try:
        return float(np.dtype(dtype).itemsize)
    except TypeError:  # extended dtypes (prng keys, int4)
        bits = getattr(dtype, "itemsize", None)
        return float(bits) if bits else 4.0


def _aval(v):
    return v.aval


def dimspec_from_sharding(s, ndim: int, mesh_sizes: Dict[str, int]
                          ) -> Tuple[int, ...]:
    """Per-dimension shard divisors of a (duck-typed) sharding."""
    spec = getattr(s, "spec", None)
    if spec is None:
        return (1,) * ndim
    try:
        sizes = dict(s.mesh.shape)
    except Exception:  # noqa: BLE001 — fall back to the context mesh
        sizes = mesh_sizes
    out = []
    for i in range(ndim):
        entry = spec[i] if i < len(spec) else None
        if entry is None:
            axes: Tuple = ()
        elif isinstance(entry, (tuple, list)):
            axes = tuple(entry)
        else:
            axes = (entry,)
        div = 1
        for a in axes:
            div *= int(sizes.get(str(a), 1))
        out.append(max(div, 1))
    return tuple(out)


def device_bytes(shape: Sequence[int], dtype, dimspec: Sequence[int]) -> float:
    """Per-device bytes of one value under its dimspec (ceil per dim; a
    short dimspec means the trailing dims are unsharded)."""
    n = _itemsize(dtype)
    for i, d in enumerate(shape):
        div = dimspec[i] if i < len(dimspec) else 1
        n *= math.ceil(d / max(div, 1))
    return n


def _ones(ndim: int) -> Tuple[int, ...]:
    return (1,) * ndim


@dataclass
class WalkStats:
    """Accumulated per-device cost counters for one walked program."""

    flops: float = 0.0                 # MXU (dot) flops
    hbm_bytes: float = 0.0             # post-fusion HBM traffic estimate
    ici_bytes: Dict[str, float] = field(default_factory=dict)
    ici_hops: Dict[str, int] = field(default_factory=dict)
    collective_scratch: float = 0.0    # largest per-device collective buffer
    peak_bytes: float = 0.0            # live-set high-water mark (device)
    host_bytes: float = 0.0            # pinned-host-resident input bytes

    def add_ici(self, axes: Tuple[str, ...], nbytes: float, hops: int,
                mult: float) -> None:
        key = "+".join(axes) if axes else "?"
        self.ici_bytes[key] = self.ici_bytes.get(key, 0.0) + nbytes * mult
        self.ici_hops[key] = self.ici_hops.get(key, 0) + int(hops * mult)

    def merge_max(self, other: "WalkStats") -> None:
        """Join a branch: costs take the max (one branch executes)."""
        self.flops = max(self.flops, other.flops)
        self.hbm_bytes = max(self.hbm_bytes, other.hbm_bytes)
        for k, v in other.ici_bytes.items():
            self.ici_bytes[k] = max(self.ici_bytes.get(k, 0.0), v)
        for k, v in other.ici_hops.items():
            self.ici_hops[k] = max(self.ici_hops.get(k, 0), v)
        self.collective_scratch = max(
            self.collective_scratch, other.collective_scratch
        )


@dataclass
class _Fusion:
    """Per-level producer-consumer coalescing evidence.

    ``reads[v]`` — v is a reducer operand whose read is fused with its
    producer chain: charge the chain root instead (or nothing when the
    root itself fuses away). ``outs`` — values a fusable producer never
    writes back to HBM (their only consumer is a fused reducer)."""

    reads: Dict[Any, Any] = field(default_factory=dict)
    outs: set = field(default_factory=set)


def _chain_link(eqn) -> bool:
    """True when ``eqn`` is a pure elementwise link a fused reducer reads
    *through*: exactly one non-literal input, no nested jaxpr, and not a
    primitive that materializes on its own."""
    if eqn.primitive.name in _MATERIALIZING:
        return False
    if any(k in eqn.params for k in _CALL_KEYS) or eqn.primitive.name in (
        "scan", "while", "cond", "shard_map"
    ):
        return False
    return sum(1 for a in eqn.invars if not isinstance(a, Literal)) == 1


def analyze_fusion(jaxpr: Jaxpr) -> _Fusion:
    """Coalesce producer→elementwise-chain→reducer triples at one jaxpr
    level. XLA fuses a reduction-family consumer into its producer when
    the intermediate is single-use, so the bytes between them never move
    through HBM; without this credit the walk charged the producer's
    write AND the consumer's read of the same value."""
    use_count: Dict[Any, int] = {}
    producer: Dict[Any, Any] = {}
    for eqn in jaxpr.eqns:
        for a in eqn.invars:
            if not isinstance(a, Literal):
                use_count[a] = use_count.get(a, 0) + 1
        for ov in eqn.outvars:
            producer[ov] = eqn
    for a in jaxpr.outvars:
        if not isinstance(a, Literal):
            # a level output materializes for the caller regardless
            use_count[a] = use_count.get(a, 0) + 1

    fusion = _Fusion()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name not in _FUSABLE_REDUCERS:
            continue
        for v in eqn.invars:
            if isinstance(v, Literal) or use_count.get(v, 0) != 1:
                continue
            # walk back through single-use elementwise links to the root
            root = v
            while True:
                p = producer.get(root)
                if p is None or not _chain_link(p):
                    break
                root = next(a for a in p.invars
                            if not isinstance(a, Literal))
                if use_count.get(root, 0) != 1:
                    break  # multi-use root still materializes; it is the
                    # redirect target, not another link to walk through
            p = producer.get(root)
            if (p is not None and p.primitive.name in _FUSABLE_PRODUCERS
                    and use_count.get(root, 0) == 1):
                # the whole triple fuses: producer write + reducer read
                # of this value both vanish
                fusion.outs.add(root)
                fusion.reads[v] = None
            elif root is not v:
                # chain collapses onto a materialized root: the fused
                # kernel reads the root once, not the intermediate
                fusion.reads[v] = root
    return fusion


class JaxprWalker:
    """One pass: dimspec propagation + liveness peak + cost counters."""

    def __init__(self, mesh_sizes: Dict[str, int], while_trips: int = 1,
                 probe: bool = False):
        self.mesh_sizes = dict(mesh_sizes or {})
        self.while_trips = max(int(while_trips), 1)
        # a probe walker only settles dimspecs — its nested scans skip
        # their own settling pre-pass, keeping the total walk count
        # linear (not 2^depth) in scan-nesting depth
        self.probe = probe
        self.stats = WalkStats()

    # ------------------------------------------------------------ dimspecs
    def _pinned_sharding_spec(self, eqn, idx: int):
        """The sharding an eqn pins its output to (device_put/constraint)."""
        name = eqn.primitive.name
        if name == "sharding_constraint":
            return eqn.params.get("sharding")
        if name == "device_put":
            devices = eqn.params.get("devices") or ()
            if idx < len(devices):
                d = devices[idx]
                if getattr(d, "spec", None) is not None:
                    return d
        return None

    def _elementwise_spec(self, eqn, in_specs, out_aval) -> Tuple[int, ...]:
        """Right-aligned broadcast join: per out dim, max divisor among
        inputs whose matching dim has the same size."""
        out_shape = out_aval.shape
        nd = len(out_shape)
        spec = [1] * nd
        for v, s in zip(eqn.invars, in_specs):
            ish = _aval(v).shape
            off = nd - len(ish)
            if off < 0:
                continue
            for j, (d, dv) in enumerate(zip(ish, s)):
                if d == out_shape[off + j]:
                    spec[off + j] = max(spec[off + j], dv)
        return tuple(spec)

    def _dot_spec(self, eqn, in_specs) -> Tuple[int, ...]:
        (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
        ls, rs = in_specs[0], in_specs[1]
        l_free = [i for i in range(len(ls)) if i not in lc and i not in lb]
        r_free = [i for i in range(len(rs)) if i not in rc and i not in rb]
        out = [max(ls[i], rs[j]) for i, j in zip(lb, rb)]
        out += [ls[i] for i in l_free]
        out += [rs[j] for j in r_free]
        return tuple(out)

    def _reshape_spec(self, in_shape, in_spec, out_shape) -> Tuple[int, ...]:
        """Keep sharding on the untouched leading/trailing dims."""
        nd = len(out_shape)
        spec = [1] * nd
        i = 0
        while (i < nd and i < len(in_shape)
               and in_shape[i] == out_shape[i]):
            spec[i] = in_spec[i]
            i += 1
        j = 0
        while (j < nd - i and j < len(in_shape) - i
               and in_shape[-1 - j] == out_shape[-1 - j]):
            spec[-1 - j] = in_spec[-1 - j]
            j += 1
        return tuple(spec)

    def _gather_spec(self, eqn, in_specs, out_aval) -> Tuple[int, ...]:
        """Output batch dims (from the indices) inherit the indices'
        sharding; operand-sliced dims stay conservative (1)."""
        dn = eqn.params.get("dimension_numbers")
        if dn is None or len(eqn.invars) < 2:
            return _ones(len(out_aval.shape))
        offset = set(getattr(dn, "offset_dims", ()))
        idx_spec = in_specs[1]
        idx_shape = _aval(eqn.invars[1]).shape
        # indices' last dim is the index vector — not a batch dim
        batch_src = list(idx_spec[:len(idx_shape) - 1]) or []
        spec = []
        k = 0
        for d in range(len(out_aval.shape)):
            if d in offset:
                spec.append(1)
            else:
                spec.append(batch_src[k] if k < len(batch_src) else 1)
                k += 1
        return tuple(spec)

    def _out_specs_plain(self, eqn, in_specs) -> List[Tuple[int, ...]]:
        name = eqn.primitive.name
        outs = []
        for idx, ov in enumerate(eqn.outvars):
            aval = _aval(ov)
            nd = len(getattr(aval, "shape", ()))
            pinned = self._pinned_sharding_spec(eqn, idx)
            if pinned is not None:
                outs.append(dimspec_from_sharding(pinned, nd, self.mesh_sizes))
                continue
            if name == "dot_general":
                outs.append(self._dot_spec(eqn, in_specs))
            elif name == "transpose":
                perm = eqn.params["permutation"]
                outs.append(tuple(in_specs[0][p] for p in perm))
            elif name == "reshape":
                outs.append(self._reshape_spec(
                    _aval(eqn.invars[0]).shape, in_specs[0], aval.shape
                ))
            elif name == "broadcast_in_dim":
                bd = eqn.params["broadcast_dimensions"]
                in_shape = _aval(eqn.invars[0]).shape
                spec = [1] * nd
                for src, dst in enumerate(bd):
                    if (src < len(in_specs[0])
                            and in_shape[src] == aval.shape[dst]):
                        spec[dst] = in_specs[0][src]
                outs.append(tuple(spec))
            elif name in ("reduce_sum", "reduce_max", "reduce_min",
                          "reduce_prod", "reduce_and", "reduce_or",
                          "argmax", "argmin"):
                axes = set(eqn.params.get("axes", ()))
                outs.append(tuple(
                    dv for i, dv in enumerate(in_specs[0]) if i not in axes
                ))
            elif name == "squeeze":
                dims = set(eqn.params.get("dimensions", ()))
                outs.append(tuple(
                    dv for i, dv in enumerate(in_specs[0]) if i not in dims
                ))
            elif name in ("slice", "dynamic_slice", "pad"):
                in_shape = _aval(eqn.invars[0]).shape
                outs.append(tuple(
                    dv if i < len(in_shape) and in_shape[i] == aval.shape[i]
                    else 1
                    for i, dv in enumerate(in_specs[0])
                ))
            elif name in ("dynamic_update_slice", "scatter", "scatter-add"):
                outs.append(in_specs[0])
            elif name == "gather":
                outs.append(self._gather_spec(eqn, in_specs, aval))
            elif name == "concatenate":
                dim = eqn.params.get("dimension", 0)
                base = [min(s[i] if i < len(s) else 1 for s in in_specs)
                        for i in range(nd)]
                if dim < nd:
                    base[dim] = 1
                outs.append(tuple(base))
            elif name in _COLLECTIVES:
                # shard_map-internal collectives: stay per-shard (ones)
                outs.append(_ones(nd))
            elif nd == 0:
                outs.append(())
            else:
                outs.append(self._elementwise_spec(eqn, in_specs, aval))
        return outs

    # ------------------------------------------------------------- costing
    def _eqn_costs(self, eqn, in_specs, out_specs, mult: float,
                   fusion: Optional["_Fusion"] = None,
                   nbytes=None) -> None:
        name = eqn.primitive.name
        if name == "dot_general":
            (lc, rc), _ = eqn.params["dimension_numbers"]
            lhs = _aval(eqn.invars[0])
            k = 1
            for i in lc:
                k *= lhs.shape[i]
            out = _aval(eqn.outvars[0])
            # per-device flops: global work over BOTH the output's shard
            # count and the contracted dims' (a weight-grad dot contracts
            # the dp-sharded batch away — each device computes 1/dp of
            # the reduction and psums partials)
            shards = 1
            for dv in out_specs[0]:
                shards *= dv
            ls, rs = in_specs[0], in_specs[1]
            for i, j in zip(lc, rc):
                li = ls[i] if i < len(ls) else 1
                rj = rs[j] if j < len(rs) else 1
                shards *= max(li, rj)
            self.stats.flops += mult * 2.0 * out.size * k / max(shards, 1)
        if name in _COLLECTIVES:
            axes = collective_axes(eqn)
            if not axes:
                axes = axis_names_of(eqn.params.get("axis_name"))
            n = 1
            for a in axes:
                n *= int(self.mesh_sizes.get(a, 1))
            payload = sum(
                device_bytes(_aval(v).shape, _aval(v).dtype, s)
                for v, s in zip(eqn.invars, in_specs)
                if not isinstance(v, Literal)
            )
            if n > 1:
                wire_fn, hops_fn = _COLLECTIVES[name]
                self.stats.add_ici(axes, wire_fn(n, payload), hops_fn(n), mult)
                out_b = sum(
                    device_bytes(_aval(v).shape, _aval(v).dtype, s)
                    for v, s in zip(eqn.outvars, out_specs)
                )
                self.stats.collective_scratch = max(
                    self.stats.collective_scratch, max(payload, out_b)
                )
        if name in _MATERIALIZING:
            io = 0.0
            for v, s in zip(eqn.invars, in_specs):
                if isinstance(v, Literal):
                    continue
                if fusion is not None and v in fusion.reads:
                    root = fusion.reads[v]
                    if root is not None:
                        io += nbytes(root)  # the fused kernel reads the
                        # chain's root, not the elementwise intermediate
                    continue  # root fused away with its producer: 0 bytes
                io += device_bytes(_aval(v).shape, _aval(v).dtype, s)
            for v, s in zip(eqn.outvars, out_specs):
                if fusion is not None and v in fusion.outs:
                    continue  # consumed only by a fused reducer: never
                    # written back to HBM
                io += device_bytes(_aval(v).shape, _aval(v).dtype, s)
            self.stats.hbm_bytes += mult * io


    # ---------------------------------------------------------------- walk
    def walk(
        self,
        jaxpr: Jaxpr,
        in_specs: Sequence[Tuple[int, ...]],
        *,
        mult: float = 1.0,
        donated: Optional[Sequence[bool]] = None,
        host_resident: Optional[Sequence[bool]] = None,
    ) -> Tuple[float, List[Tuple[int, ...]]]:
        """Walk one jaxpr level. Returns (peak device bytes incl. live
        inputs, out dimspecs). ``donated[i]`` marks invars whose buffer
        may be reused once dead (jit-boundary donation); non-donated
        invars stay live to the end (the caller owns them).
        ``host_resident[i]`` marks pinned-host invars (0 HBM bytes)."""
        n_in = len(jaxpr.invars)
        donated = list(donated) if donated is not None else [True] * n_in
        host = list(host_resident) if host_resident is not None \
            else [False] * n_in
        specs: Dict[Any, Tuple[int, ...]] = {}
        for v, s in zip(jaxpr.invars, in_specs):
            specs[v] = tuple(s)[:len(_aval(v).shape)] or _ones(
                len(_aval(v).shape)
            )
        for cv in jaxpr.constvars:
            specs[cv] = _ones(len(_aval(cv).shape))

        def nbytes(v) -> float:
            if isinstance(v, Literal):
                return 0.0
            return device_bytes(
                _aval(v).shape, _aval(v).dtype,
                specs.get(v, _ones(len(_aval(v).shape))),
            )

        fusion = analyze_fusion(jaxpr)

        # ---- liveness: last equation index using each var ----------------
        last_use: Dict[Any, int] = {}
        for i, eqn in enumerate(jaxpr.eqns):
            for a in eqn.invars:
                if not isinstance(a, Literal):
                    last_use[a] = i
        INF = len(jaxpr.eqns) + 1
        for a in jaxpr.outvars:
            if not isinstance(a, Literal):
                last_use[a] = INF
        for v, don, hst in zip(jaxpr.invars, donated, host):
            if not don and not hst:
                last_use[v] = INF  # caller-owned buffer, live throughout

        live: Dict[Any, float] = {}
        for v, hst in zip(jaxpr.invars, host):
            if hst:
                self.stats.host_bytes += device_bytes(
                    _aval(v).shape, _aval(v).dtype, specs[v]
                )
                live[v] = 0.0
            else:
                live[v] = nbytes(v)
        for cv in jaxpr.constvars:
            live[cv] = nbytes(cv)
        live_sum = sum(live.values())
        peak = live_sum

        for i, eqn in enumerate(jaxpr.eqns):
            e_in_specs = [
                specs.get(a, _ones(len(_aval(a).shape)))
                if not isinstance(a, Literal) else ()
                for a in eqn.invars
            ]
            inner_extra, out_specs = self._descend(
                eqn, e_in_specs, mult
            )
            if out_specs is None:
                out_specs = self._out_specs_plain(eqn, e_in_specs)
            for ov, s in zip(eqn.outvars, out_specs):
                specs[ov] = s
            self._eqn_costs(eqn, e_in_specs, out_specs, mult,
                            fusion=fusion, nbytes=nbytes)

            freed = [
                a for a in {id(a): a for a in eqn.invars
                            if not isinstance(a, Literal)}.values()
                if last_use.get(a) == i and a in live
            ]
            freed_pool = sorted((live[a] for a in freed))
            out_bytes = [nbytes(ov) for ov in eqn.outvars]
            new_alloc = 0.0
            for b in sorted(out_bytes, reverse=True):
                taken = None
                for k, fb in enumerate(freed_pool):
                    if fb >= b:
                        taken = k
                        break
                if taken is not None:
                    freed_pool.pop(taken)  # reuse the freed buffer
                else:
                    new_alloc += b
            peak = max(peak, live_sum + new_alloc + inner_extra)
            for a in freed:
                live_sum -= live.pop(a)
            for ov, b in zip(eqn.outvars, out_bytes):
                live[ov] = b
                live_sum += b
            # drop outputs that are never used (dead code in the trace)
            for ov in list(eqn.outvars):
                if last_use.get(ov) is None and ov in live:
                    live_sum -= live.pop(ov)
            peak = max(peak, live_sum)

        out_specs = [
            specs.get(a, _ones(len(_aval(a).shape)))
            if not isinstance(a, Literal) else ()
            for a in jaxpr.outvars
        ]
        return peak, out_specs

    # ------------------------------------------------- structural descent
    def _descend(self, eqn, in_specs, mult: float):
        """(inner_extra_peak, out_specs|None) for control-flow equations.
        Returns (0, None) for plain primitives."""
        name = eqn.primitive.name
        if name == "scan":
            body = as_jaxpr(eqn.params["jaxpr"])
            nc, ncar = scan_split(eqn)
            length = max(int(eqn.params.get("length", 1)), 1)
            consts = in_specs[:nc]
            carry = list(in_specs[nc:nc + ncar])
            xs = [tuple(s[1:]) for s in in_specs[nc + ncar:]]
            # one settling pass for carry specs, then the costed pass
            # (skipped inside a probe — the outer costed walk re-settles)
            if not self.probe:
                probe = JaxprWalker(self.mesh_sizes, self.while_trips,
                                    probe=True)
                _, probe_out = probe.walk(body, consts + carry + xs,
                                          mult=0.0)
                carry = [
                    tuple(min(a, b) for a, b in zip(ci, bo))
                    for ci, bo in zip(carry, probe_out[:ncar])
                ]
            body_peak, body_out = self.walk(
                body, consts + carry + xs, mult=mult * length
            )
            in_bytes = self._specs_bytes(body.invars, consts + carry + xs)
            outs = list(body_out[:ncar]) + [
                (1,) + tuple(s) for s in body_out[ncar:]
            ]
            return max(body_peak - in_bytes, 0.0), outs
        if name == "while":
            body = as_jaxpr(eqn.params["body_jaxpr"])
            cn = eqn.params["cond_nconsts"]
            bn = eqn.params["body_nconsts"]
            bconsts = in_specs[cn:cn + bn]
            carry = in_specs[cn + bn:]
            body_peak, body_out = self.walk(
                body, list(bconsts) + list(carry),
                mult=mult * self.while_trips,
            )
            in_bytes = self._specs_bytes(
                body.invars, list(bconsts) + list(carry)
            )
            return max(body_peak - in_bytes, 0.0), list(body_out)
        if name == "cond":
            operands = in_specs[1:]
            extra, outs = 0.0, None
            base = self.stats
            best: Optional[WalkStats] = None
            for br in eqn.params["branches"]:
                self.stats = WalkStats()
                b = as_jaxpr(br)
                p, o = self.walk(b, operands, mult=mult)
                in_b = self._specs_bytes(b.invars, operands)
                extra = max(extra, p - in_b)
                outs = o if outs is None else [
                    tuple(min(x, y) for x, y in zip(a, bo))
                    for a, bo in zip(outs, o)
                ]
                if best is None:
                    best = self.stats
                else:
                    best.merge_max(self.stats)
            self.stats = base
            if best is not None:
                self.stats.flops += best.flops
                self.stats.hbm_bytes += best.hbm_bytes
                for k, v in best.ici_bytes.items():
                    self.stats.ici_bytes[k] = (
                        self.stats.ici_bytes.get(k, 0.0) + v
                    )
                for k, v in best.ici_hops.items():
                    self.stats.ici_hops[k] = (
                        self.stats.ici_hops.get(k, 0) + v
                    )
                self.stats.collective_scratch = max(
                    self.stats.collective_scratch, best.collective_scratch
                )
            return max(extra, 0.0), outs
        if name == "shard_map":
            body = as_jaxpr(eqn.params["jaxpr"])
            # body avals are per-shard — divisors reset to 1
            body_peak, _ = self.walk(
                body, [_ones(len(_aval(v).shape)) for v in body.invars],
                mult=mult,
            )
            in_bytes = self._specs_bytes(
                body.invars,
                [_ones(len(_aval(v).shape)) for v in body.invars],
            )
            outs = []
            for ov, names in zip(eqn.outvars,
                                 shard_map_names(eqn, "out_specs")):
                nd = len(_aval(ov).shape)
                spec = [1] * nd
                for dim, axes in names.items():
                    if dim < nd:
                        div = 1
                        for a in axes:
                            div *= int(self.mesh_sizes.get(str(a), 1))
                        spec[dim] = div
                outs.append(tuple(spec))
            return max(body_peak - in_bytes, 0.0), outs
        for key in _CALL_KEYS:
            sub = eqn.params.get(key)
            if sub is None or not isinstance(sub, (Jaxpr,)) and not hasattr(
                sub, "jaxpr"
            ):
                continue
            body = as_jaxpr(sub)
            if len(body.invars) == len(in_specs):
                aligned = list(in_specs)
            elif len(body.invars) < len(in_specs):
                aligned = list(in_specs[-len(body.invars):])
            else:
                aligned = list(in_specs) + [
                    _ones(len(_aval(v).shape))
                    for v in body.invars[len(in_specs):]
                ]
            body_peak, body_out = self.walk(body, aligned, mult=mult)
            in_bytes = self._specs_bytes(body.invars, aligned)
            return max(body_peak - in_bytes, 0.0), list(body_out)
        return 0.0, None

    def _specs_bytes(self, vs, specs) -> float:
        return sum(
            device_bytes(_aval(v).shape, _aval(v).dtype, s)
            for v, s in zip(vs, specs)
        )
