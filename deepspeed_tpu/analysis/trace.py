"""Jaxpr traversal + forward dataflow for shardlint rules.

Everything here is abstract: programs are walked as jaxprs (the IR
``jax.make_jaxpr`` returns), never executed. The dataflow engine is a
boolean forward may-analysis with structural handling of the control-flow
primitives (scan/while/cond/pjit/remat/shard_map/custom_*): loop carries
iterate to a fixpoint, branches join with OR. Rules subclass
:class:`DataflowAnalysis` and override the per-primitive transfer.
"""

from __future__ import annotations

from typing import Any, Dict, Iterator, List, Tuple

import jax
from jax.extend.core import ClosedJaxpr, Jaxpr, Literal

# primitives that wrap exactly one jaxpr consuming the eqn inputs 1:1
_CALL_LIKE_KEYS = ("jaxpr", "call_jaxpr", "fun_jaxpr")


def as_jaxpr(j) -> Jaxpr:
    return j.jaxpr if isinstance(j, ClosedJaxpr) else j


def _param_jaxprs(value) -> Iterator[Jaxpr]:
    if isinstance(value, (Jaxpr, ClosedJaxpr)):
        yield as_jaxpr(value)
    elif isinstance(value, (list, tuple)):
        for v in value:
            yield from _param_jaxprs(v)


def eqn_subjaxprs(eqn) -> List[Tuple[str, Jaxpr]]:
    """All (param_name, jaxpr) sub-programs an equation carries."""
    out = []
    for k, v in eqn.params.items():
        for j in _param_jaxprs(v):
            out.append((k, j))
    return out


def iter_jaxprs(root, path: str = "") -> Iterator[Tuple[Jaxpr, str]]:
    """Yield (jaxpr, path) for the program and every nested sub-program."""
    j = as_jaxpr(root)
    yield j, path
    for eqn in j.eqns:
        for k, sub in eqn_subjaxprs(eqn):
            sub_path = f"{path}/{eqn.primitive.name}"
            if k not in ("jaxpr",):
                sub_path += f".{k}"
            yield from iter_jaxprs(sub, sub_path)


def producers(jaxpr: Jaxpr) -> Dict[Any, Any]:
    """Var → producing eqn map for one jaxpr level."""
    out = {}
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            out[v] = eqn
    return out


def scan_split(eqn):
    """(consts, carries, xs) operand index ranges of a scan eqn."""
    nc = eqn.params["num_consts"]
    ncar = eqn.params["num_carry"]
    return nc, ncar


def axis_names_of(param) -> Tuple[str, ...]:
    """Normalize a collective's axis-name param (str | tuple) to a tuple."""
    if param is None:
        return ()
    if isinstance(param, (tuple, list)):
        return tuple(str(a) for a in param)
    return (str(param),)


def collective_axes(eqn) -> Tuple[str, ...]:
    """Mesh axis names a named collective operates over (else ())."""
    p = eqn.params
    return axis_names_of(p.get("axes") or p.get("axis_name"))


def shard_map_manual_axes(eqn) -> Dict[str, int]:
    """{axis: size} the shard_map body is Manual over (the eqn's
    ``manual_axes``, sized from its mesh)."""
    mesh = eqn.params.get("mesh")
    manual = eqn.params.get("manual_axes") or frozenset()
    if mesh is None:
        return {}
    try:
        shape = dict(mesh.shape)
    except Exception:  # noqa: BLE001 — AbstractMesh without concrete shape
        return {}
    return {a: n for a, n in shape.items() if a in manual}


def shard_map_names(eqn, key: str) -> List[Dict[int, Tuple[str, ...]]]:
    """A shard_map eqn's ``in_specs``/``out_specs`` (PartitionSpecs, one per
    operand/result) as {dim: (axes,)} maps of the partitioned dims only."""
    out = []
    for spec in eqn.params.get(key) or ():
        names = {}
        for dim, entry in enumerate(spec):
            if entry is None:
                continue
            names[dim] = (
                tuple(entry) if isinstance(entry, (tuple, list)) else (entry,)
            )
        out.append(names)
    return out


def names_spec_axes(names_entry) -> Tuple[str, ...]:
    """Flatten one :func:`shard_map_names` entry ({dim: (axes,)}) to the
    set of mesh axes the value is partitioned over."""
    axes: List[str] = []
    for dim_axes in (names_entry or {}).values():
        axes.extend(str(a) for a in dim_axes)
    return tuple(axes)


class DataflowAnalysis:
    """Boolean forward may-analysis over a jaxpr.

    Subclasses override :meth:`transfer` (plain primitives) and optionally
    :meth:`visit` (called for every eqn with its in/out values — the spot
    to emit findings). Control flow is handled structurally here.
    """

    MAX_FIXPOINT_ITERS = 16

    # -- overridables -------------------------------------------------------
    def transfer(self, eqn, in_vals: List[bool]) -> List[bool]:
        return [any(in_vals)] * len(eqn.outvars)

    def visit(self, eqn, in_vals: List[bool], out_vals: List[bool],
              path: str) -> None:
        pass

    # -- engine -------------------------------------------------------------
    def run(self, jaxpr: Jaxpr, in_vals: List[bool], path: str = "") -> List[bool]:
        env: Dict[Any, bool] = {}

        def read(a) -> bool:
            if isinstance(a, Literal):
                return False
            return env.get(a, False)

        for var, val in zip(jaxpr.invars, in_vals):
            env[var] = bool(val)
        for cv in jaxpr.constvars:
            env[cv] = False
        for eqn in jaxpr.eqns:
            ivals = [read(a) for a in eqn.invars]
            ovals = self._eqn_out(eqn, ivals, path)
            self.visit(eqn, ivals, ovals, path)
            for v, val in zip(eqn.outvars, ovals):
                env[v] = bool(val)
        return [read(v) for v in jaxpr.outvars]

    def _eqn_out(self, eqn, ivals: List[bool], path: str) -> List[bool]:
        name = eqn.primitive.name
        sub = f"{path}/{name}"
        if name == "scan":
            body = as_jaxpr(eqn.params["jaxpr"])
            nc, ncar = scan_split(eqn)
            consts, carry = ivals[:nc], ivals[nc:nc + ncar]
            xs = ivals[nc + ncar:]
            outs = carry + [False] * (len(eqn.outvars) - ncar)
            for _ in range(self.MAX_FIXPOINT_ITERS):
                outs = self.run(body, consts + carry + xs, sub)
                new_carry = [c or o for c, o in zip(carry, outs[:ncar])]
                if new_carry == carry:
                    break
                carry = new_carry
            return [c or o for c, o in zip(carry, outs[:ncar])] + outs[ncar:]
        if name == "while":
            body = as_jaxpr(eqn.params["body_jaxpr"])
            cn = eqn.params["cond_nconsts"]
            bn = eqn.params["body_nconsts"]
            bconsts = ivals[cn:cn + bn]
            carry = ivals[cn + bn:]
            for _ in range(self.MAX_FIXPOINT_ITERS):
                outs = self.run(body, bconsts + carry, sub)
                new_carry = [c or o for c, o in zip(carry, outs)]
                if new_carry == carry:
                    break
                carry = new_carry
            return carry
        if name == "cond":
            branches = eqn.params["branches"]
            operands = ivals[1:]
            outs = None
            for br in branches:
                o = self.run(as_jaxpr(br), operands, sub)
                outs = o if outs is None else [a or b for a, b in zip(outs, o)]
            return outs if outs is not None else []
        if name == "shard_map":
            return self.run(as_jaxpr(eqn.params["jaxpr"]), ivals, sub)
        for key in _CALL_LIKE_KEYS:
            if key in eqn.params and isinstance(
                eqn.params[key], (Jaxpr, ClosedJaxpr)
            ):
                body = as_jaxpr(eqn.params[key])
                if len(body.invars) == len(ivals):
                    return self.run(body, ivals, sub)
                if len(body.invars) < len(ivals):
                    # call-like wrappers that prepend consts (custom_vjp):
                    # align the trailing operands
                    outs = self.run(body, ivals[-len(body.invars):], sub)
                    return outs
                break  # structure unknown — fall through to transfer
        return self.transfer(eqn, ivals)
