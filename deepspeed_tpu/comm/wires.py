"""Wire codecs + wire-collective forms — the first-class comm layer.

PRs 3 and 10 proved int8 quantized-hop wires and decomposed rings inside
individual call sites (the TP projection rings, the MoE a2a, the stage-3
prefetch); the codec logic lived buried in those modules and could not
reach the biggest remaining wires — the ZeRO gradient reduce-scatter and
the stage-3 parameter all-gathers. This module factors it out (ZeRO++
qgZ/hgZ, arXiv 2306.10209; EQuARX's topology-aware split):

**Codecs** (:data:`CODECS`): fp32 / bf16 / int8 / int4, each a
:class:`WireCodec` declaring its wire bytes per element and a documented,
property-tested error bound. Quantized codecs use symmetric lane-wise
scales — ONE fp32 scale per lane, quantizing over the row axis — the
exact scheme the TP rings and ZeRO++ gather shipped with (bitwise
compatible: ``quantize_lanewise`` here IS the old
``runtime/zero/quantized._quantize_lanewise``). Canonical payload shape
is ``[blocks, rows, lanes]``; scales are ``[blocks, 1, lanes]``.

===== ====================== ==========================================
codec wire bytes / element   |decode(encode(x)) - x| bound (per lane)
===== ====================== ==========================================
fp32  itemsize (identity)    0 (bitwise)
bf16  2                      ``|x| * 2**-8`` (bitwise for bf16 inputs)
int8  1 (+ 4 per lane scale) ``scale / 2``, scale = max(amax,1e-12)/127
int4  0.5 (+ 4 per lane)     ``scale / 2``, scale = max(amax,1e-12)/7
===== ====================== ==========================================

Zero and denormal lanes are covered by the ``max(amax, 1e-12)`` floor:
a lane whose magnitudes all sit below the floor rounds to zero codes and
the bound still holds (|x| <= 1e-12/254 is false only when |x| <= bound
anyway — tests/test_wires.py pins this on actual denormals).

**Wire collectives**: composable forms built on the qgZ all-to-all
formulation — values quantize at most ONCE, the reduction runs AFTER
dequant, in f32, in pinned member order (so the fp32-codec wire is the
bitwise full-width baseline the oracles compare against):

- :func:`rs_wire_local` — reduce-scatter: split the local array into one
  block per member, encode per block (per-(block, lane) scales), one
  all-to-all, dequant, f32 member-order accumulate.
- :func:`ag_wire_local` — all-gather: encode the local shard once, one
  all-gather of payload + scales, dequant on arrival (error is one
  fake-quant round trip, hop-count independent).
- :func:`rs_wire_hier_local` / :func:`ag_wire_hier_local` — the 2-hop
  hierarchical variants over a FACTORED mesh axis pair (outer, inner),
  e.g. ``("dp", "fsdp")``: the intra-group hop runs full width over the
  fast inner links, the inter-group hop moves codec bytes over the slow
  outer links (ZeRO++ hgZ / EQuARX). Block ordering is outer-major —
  exactly the layout ``PartitionSpec((outer, inner))`` assigns — so the
  hierarchical form drops into any sharding the single-hop form serves.

The ``*_local`` forms run INSIDE an existing ``shard_map`` (the ZeRO
runtimes' partial-manual per-leaf maps, the rings' full-manual maps);
:func:`all_gather_wire` / :func:`reduce_scatter_wire` are global-array
wrappers (full-manual shard_map over the whole mesh) — the CPU-mesh
oracle surface and the documented reference semantics.

Every payload that crosses the wire routes through
``collectives._record`` so the comms logger sees the REAL (encoded)
bytes, and the engine prices each wire statically through
``analytic_streams()`` (:func:`rs_wire_nbytes` / :func:`ag_wire_nbytes`)
so shardplan R8 sees the win before anything compiles. shardlint R5
keeps the f32 master path honest: codec decode is ALWAYS to f32 before
any accumulate — the master update never consumes sub-32-bit data
directly (docs/wires.md).
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from . import collectives

AxisName = Union[str, Tuple[str, ...]]

__all__ = [
    "WireCodec",
    "CODECS",
    "WIRE_NAMES",
    "get_codec",
    "quantize_lanewise",
    "dequantize_lanewise",
    "ag_wire_local",
    "rs_wire_local",
    "ag_wire_hier_local",
    "rs_wire_hier_local",
    "all_gather_wire",
    "reduce_scatter_wire",
    "ag_wire_nbytes",
    "rs_wire_nbytes",
    "hier_rs_nbytes",
    "hier_ag_nbytes",
    "hier_axes",
]


# ------------------------------------------------------------------- codecs
class WireCodec:
    """One wire format. Canonical operand shape is ``[B, R, L]`` (blocks,
    rows, lanes); quantized codecs reduce over R with one fp32 scale per
    (block, lane). ``wire_bits`` is the payload width per element
    (scales priced separately by :meth:`payload_nbytes`)."""

    name: str = "?"
    wire_bits: int = 32
    lossless: bool = False

    def encode(self, x3: jax.Array) -> Dict[str, jax.Array]:
        raise NotImplementedError

    def decode(self, payload: Dict[str, jax.Array], rows: int,
               dtype) -> jax.Array:
        raise NotImplementedError

    def bound(self, x3: jax.Array) -> jax.Array:
        """Per-element upper bound on ``|decode(encode(x)) - x|`` (f32,
        broadcastable against x3) — the documented, property-tested
        contract of the codec."""
        raise NotImplementedError

    def payload_nbytes(self, blocks: int, rows: int, lanes: int,
                       itemsize: int = 4) -> int:
        """Wire bytes of one encoded ``[blocks, rows, lanes]`` operand,
        INCLUDING the fp32 lane scales quantized codecs ride with.
        Polymorphic — a codec that doesn't declare its bytes cannot be
        priced and must not silently inherit another codec's formula."""
        raise NotImplementedError

    def __repr__(self) -> str:  # pragma: no cover — debugging aid
        return f"WireCodec({self.name})"


class _Fp32(WireCodec):
    """Identity wire — the full-width baseline. Bitwise for any input
    dtype (a bf16 compute array stays bf16 on the wire: 'fp32' names the
    POLICY — never truncate — not a cast)."""

    name = "fp32"
    wire_bits = 32
    lossless = True

    def encode(self, x3):
        return {"x": x3}

    def decode(self, payload, rows, dtype):
        return payload["x"].astype(dtype)

    def bound(self, x3):
        return jnp.zeros((), jnp.float32)

    def payload_nbytes(self, blocks, rows, lanes, itemsize=4):
        return blocks * rows * lanes * itemsize


class _Bf16(WireCodec):
    """Truncate-to-bf16 wire. Round-to-nearest-even: error <= |x| * 2**-8
    for normal f32 inputs (+1e-38 absolute slack for the denormal tail);
    bitwise identity when the input is already bf16."""

    name = "bf16"
    wire_bits = 16

    def encode(self, x3):
        return {"x": x3.astype(jnp.bfloat16)}

    def decode(self, payload, rows, dtype):
        return payload["x"].astype(jnp.float32).astype(dtype)

    def bound(self, x3):
        # the absolute slack covers the denormal tail and must itself be
        # a NORMAL f32 (1.2e-38 > min normal ~1.175e-38): a denormal
        # literal would flush to zero under XLA FTZ and the bound would
        # read 0 exactly where it needs the slack
        return jnp.abs(x3.astype(jnp.float32)) * (2.0 ** -8) + 1.2e-38

    def payload_nbytes(self, blocks, rows, lanes, itemsize=4):
        return blocks * rows * lanes * 2


def _lane_scale(x3: jax.Array, levels: float) -> jax.Array:
    """[B, 1, L] symmetric scale over the row axis — the csrc/quantization
    layout the repo has shipped since PR 3 (amax/levels with a 1e-12
    floor so all-zero lanes stay finite)."""
    amax = jnp.max(jnp.abs(x3.astype(jnp.float32)), axis=1, keepdims=True)
    return jnp.maximum(amax, 1e-12) / levels


class _Int8(WireCodec):
    """int8 symmetric lane-wise wire (ZeRO++ qwZ/qgZ). amax maps to
    exactly +/-127 so clipping never adds error: the bound is pure
    rounding, scale/2 per element."""

    name = "int8"
    wire_bits = 8

    def encode(self, x3):
        scale = _lane_scale(x3, 127.0)
        q = jnp.clip(
            jnp.round(x3.astype(jnp.float32) / scale), -127, 127
        ).astype(jnp.int8)
        return {"q": q, "scale": scale}

    def decode(self, payload, rows, dtype):
        return (
            payload["q"].astype(jnp.float32) * payload["scale"]
        ).astype(dtype)

    def bound(self, x3):
        return _lane_scale(x3, 127.0) * 0.5

    def payload_nbytes(self, blocks, rows, lanes, itemsize=4):
        return blocks * rows * lanes + blocks * lanes * 4

    def quantize(self, x3):
        """(q, scale) without the dict wrapper — the 2-D lanewise entry
        the TP rings and ZeRO++ gather use directly."""
        p = self.encode(x3)
        return p["q"], p["scale"]


class _Int4(WireCodec):
    """int4 symmetric lane-wise wire, genuinely bit-packed: two [-7, 7]
    codes per int8 byte along the row axis (odd row counts pad one zero
    row — decode slices it back off). Half the int8 wire at double the
    rounding step."""

    name = "int4"
    wire_bits = 4

    def encode(self, x3):
        scale = _lane_scale(x3, 7.0)
        q = jnp.clip(
            jnp.round(x3.astype(jnp.float32) / scale), -7, 7
        ).astype(jnp.int8)
        r = q.shape[1]
        if r % 2:
            q = jnp.pad(q, ((0, 0), (0, 1), (0, 0)))
        lo = q[:, 0::2]
        hi = q[:, 1::2]
        packed = (lo & jnp.int8(0x0F)) | (hi << 4)
        return {"q": packed.astype(jnp.int8), "scale": scale}

    def decode(self, payload, rows, dtype):
        p = payload["q"]
        # arithmetic shifts sign-extend the two's-complement nibbles
        lo = (p << 4).astype(jnp.int8) >> 4
        hi = p >> 4
        q = jnp.stack([lo, hi], axis=2).reshape(
            p.shape[0], 2 * p.shape[1], p.shape[2]
        )[:, :rows]
        return (q.astype(jnp.float32) * payload["scale"]).astype(dtype)

    def bound(self, x3):
        return _lane_scale(x3, 7.0) * 0.5

    def payload_nbytes(self, blocks, rows, lanes, itemsize=4):
        # two codes per byte, rows padded to even, fp32 lane scales
        return blocks * (-(-rows // 2)) * lanes + blocks * lanes * 4


CODECS: Dict[str, WireCodec] = {
    "fp32": _Fp32(),
    "bf16": _Bf16(),
    "int8": _Int8(),
    "int4": _Int4(),
}
WIRE_NAMES: Tuple[str, ...] = tuple(CODECS)


def get_codec(codec: Union[str, WireCodec]) -> WireCodec:
    if isinstance(codec, WireCodec):
        return codec
    try:
        return CODECS[codec]
    except KeyError:
        raise ValueError(
            f"unknown wire codec {codec!r} (known: {WIRE_NAMES})"
        ) from None


# ------------------------------------------------- legacy lanewise entries
def quantize_lanewise(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """int8 symmetric quant over axis 0, one fp32 scale per remaining
    lane — THE shared implementation the TP-overlap rings and the ZeRO++
    gather both used privately before this module existed (bitwise
    identical to both)."""
    x3 = x.reshape((1, x.shape[0], -1))
    q, scale = CODECS["int8"].quantize(x3)
    return q.reshape(x.shape), scale.reshape((1,) + x.shape[1:])


def dequantize_lanewise(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (q.astype(jnp.float32) * scale).astype(dtype)


# ------------------------------------------------------------ shape helpers
def _to_blocks(x: jax.Array, n: int, dim: int) -> Tuple[jax.Array, Tuple]:
    """[..., d, ...] -> ([n, d//n, lanes], restore-shape) splitting ``dim``
    into n member blocks; lanes collapse every trailing element (the
    per-(block, lane) scale granularity of the qgZ exchange)."""
    xm = jnp.moveaxis(x, dim, 0)
    d = xm.shape[0]
    if d % n:
        raise ValueError(
            f"wire reduce-scatter: dim {dim} (size {d}) does not divide "
            f"the {n} members"
        )
    return xm.reshape(n, d // n, -1), xm.shape


def _from_block(blk: jax.Array, full_shape: Tuple, n: int,
                dim: int) -> jax.Array:
    """[chunk, lanes] -> the caller's layout with ``dim`` shrunk n-fold."""
    out = blk.reshape((full_shape[0] // n,) + tuple(full_shape[1:]))
    return jnp.moveaxis(out, 0, dim)


def _ordered_sum(dec: jax.Array) -> jax.Array:
    """f32 accumulate over axis 0 in pinned member order — the ONE
    reduction-order definition every wire form shares, so fp32-codec
    wires stay bitwise comparable across forms."""
    acc = dec[0].astype(jnp.float32)
    for s in range(1, dec.shape[0]):
        acc = acc + dec[s].astype(jnp.float32)
    return acc


# ------------------------------------------------------- local (in-map) ops
def ag_wire_local(x: jax.Array, axis: AxisName, n: int,
                  codec: Union[str, WireCodec], *, dim: int = 0,
                  dtype=None) -> jax.Array:
    """All-gather the local shard ``x`` along ``dim`` over mesh ``axis``
    (total size ``n``) moving codec bytes. Runs inside a shard_map.
    Error: one encode/decode round trip per element, independent of n."""
    codec = get_codec(codec)
    dtype = dtype or x.dtype
    xm = jnp.moveaxis(x, dim, 0)
    r = xm.shape[0]
    p = codec.encode(xm.reshape(1, r, -1))
    collectives._record("all_gather", axis, p)
    g = {
        k: lax.all_gather(v, axis, axis=0, tiled=False) for k, v in p.items()
    }
    # [n, 1, ...] -> [n, ...]: each member's block decodes against its
    # own gathered scales
    g = {k: v.reshape((n,) + v.shape[2:]) for k, v in g.items()}
    full3 = codec.decode(g, r, dtype)
    full = full3.reshape((n * r,) + tuple(xm.shape[1:]))
    return jnp.moveaxis(full, 0, dim)


def rs_wire_local(x: jax.Array, axis: AxisName, n: int,
                  codec: Union[str, WireCodec], *, dim: int = 0,
                  dtype=None) -> jax.Array:
    """Reduce-scatter the local contribution ``x`` along ``dim`` over
    ``axis`` (size ``n``), qgZ form: one encode per member block, one
    all-to-all, dequant, f32 member-order accumulate (dequant-accumulate
    in master precision — never a quantized sum). Error <= the sum of
    the n contributors' per-block bounds."""
    codec = get_codec(codec)
    dtype = dtype or x.dtype
    x3, full_shape = _to_blocks(x, n, dim)
    p = codec.encode(x3)
    collectives._record("all_to_all", axis, p)
    ex = {
        k: lax.all_to_all(v, axis, split_axis=0, concat_axis=0, tiled=False)
        for k, v in p.items()
    }
    dec = codec.decode(ex, x3.shape[1], jnp.float32)
    return _from_block(_ordered_sum(dec).astype(dtype), full_shape, n, dim)


def ag_wire_hier_local(x: jax.Array, outer: str, inner: str, n_o: int,
                       n_i: int, codec: Union[str, WireCodec], *,
                       inner_codec: Union[str, WireCodec] = "fp32",
                       dim: int = 0, dtype=None) -> jax.Array:
    """Hierarchical 2-hop all-gather over the factored axis pair
    ``(outer, inner)``: hop 1 gathers full width (``inner_codec``,
    default fp32) over the fast intra-group links; hop 2 encodes the
    group's gathered block ONCE and moves codec bytes over the slow
    inter-group links. Result ordering is outer-major — identical to a
    single-hop gather over ``(outer, inner)``."""
    codec = get_codec(codec)
    dtype = dtype or x.dtype
    # hop 1 (intra): the group's n_i shards, full width on fast links
    intra = ag_wire_local(x, inner, n_i, inner_codec, dim=dim, dtype=dtype)
    # hop 2 (inter): one encode of the group block, codec bytes on the wire
    return ag_wire_local(intra, outer, n_o, codec, dim=dim, dtype=dtype)


def rs_wire_hier_local(x: jax.Array, outer: str, inner: str, n_o: int,
                       n_i: int, codec: Union[str, WireCodec], *,
                       inner_codec: Union[str, WireCodec] = "fp32",
                       dim: int = 0, dtype=None) -> jax.Array:
    """Hierarchical 2-hop reduce-scatter (hgZ): hop 1 reduce-scatters
    full width within each group (fast links — and it SHRINKS what the
    slow hop must move n_i-fold); hop 2 reduce-scatters the group
    partials over the outer axis in codec bytes. Member (o, i) ends with
    global block ``o * n_i + i`` — the outer-major layout
    ``PartitionSpec((outer, inner))`` expects. Quantization still
    happens at most once per value (only the inter hop encodes; the
    intra hop is full width), so the error bound is the single-hop
    bound over the n_o inter-group contributors."""
    codec = get_codec(codec)
    dtype = dtype or x.dtype
    n = n_o * n_i
    x3, full_shape = _to_blocks(x, n, dim)  # [n_o * n_i, chunk, L]
    chunk = x3.shape[1]
    # regroup [n_o, n_i, chunk, L] -> inner blocks [n_i, n_o * chunk, L]:
    # hop 1 scatters the inner-block axis within the group (full width)
    xb = x3.reshape(n_o, n_i, chunk, x3.shape[2])
    inner_blocks = jnp.moveaxis(xb, 1, 0).reshape(
        n_i, n_o * chunk, x3.shape[2]
    )
    ic = get_codec(inner_codec)
    p1 = ic.encode(inner_blocks)
    collectives._record("all_to_all", inner, p1)
    ex1 = {
        k: lax.all_to_all(v, inner, split_axis=0, concat_axis=0,
                          tiled=False)
        for k, v in p1.items()
    }
    dec1 = ic.decode(ex1, inner_blocks.shape[1], jnp.float32)
    y = _ordered_sum(dec1).reshape(n_o, chunk, x3.shape[2])
    # hop 2 (inter): member (o, i) holds inner block i reduced over its
    # group; scatter its n_o outer blocks in codec bytes, f32 accumulate
    p2 = codec.encode(y)
    collectives._record("all_to_all", outer, p2)
    ex2 = {
        k: lax.all_to_all(v, outer, split_axis=0, concat_axis=0,
                          tiled=False)
        for k, v in p2.items()
    }
    dec2 = codec.decode(ex2, chunk, jnp.float32)
    return _from_block(_ordered_sum(dec2).astype(dtype), full_shape, n, dim)


# -------------------------------------------------------- global wrappers
def _shard_map_full(body, topo, in_specs, out_specs):
    return jax.shard_map(
        body,
        mesh=topo.mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        axis_names=set(topo.mesh.axis_names),
        check_vma=False,
    )


def _axes_tuple(axes) -> Tuple[str, ...]:
    return tuple(axes) if isinstance(axes, (tuple, list)) else (axes,)


def hier_axes(topo, axes) -> Optional[Tuple[str, int, str, int]]:
    """(outer, n_outer, inner, n_inner) when ``axes`` is a live factored
    pair this topology can run the 2-hop forms over (outer first — the
    slower, outermost mesh axis); None otherwise (single-hop territory:
    one live axis, or a pair with a dead member)."""
    axes = _axes_tuple(axes)
    if len(axes) != 2:
        return None
    n_o, n_i = topo.sizes[axes[0]], topo.sizes[axes[1]]
    if n_o <= 1 or n_i <= 1:
        return None
    return axes[0], n_o, axes[1], n_i


def all_gather_wire(shards: jax.Array, topo, axes=("dp",),
                    codec: Union[str, WireCodec] = "int8", *,
                    hierarchical: bool = False) -> jax.Array:
    """Global-array all-gather wire: ``shards`` is the stacked
    ``[n, chunk, ...]`` per-member shard array (sharded over ``axes`` on
    dim 0); returns the gathered ``[n * chunk, ...]`` array, replicated
    over ``axes``. The oracle surface: fp32 codec == ``jnp.concatenate``
    of the shards, bitwise; every other codec within its stated bound."""
    axes = _axes_tuple(axes)
    n = int(np.prod([topo.sizes[a] for a in axes]))
    hier = hier_axes(topo, axes) if hierarchical else None

    def body(s):
        local = s[0]  # [chunk, ...]
        if hier is not None:
            o, n_o, i, n_i = hier
            return ag_wire_hier_local(local, o, i, n_o, n_i, codec)
        return ag_wire_local(local, axes if len(axes) > 1 else axes[0], n,
                             codec)

    ax_entry = axes if len(axes) > 1 else axes[0]
    return _shard_map_full(body, topo, (P(ax_entry),), P())(shards)


def reduce_scatter_wire(contribs: jax.Array, topo, axes=("dp",),
                        codec: Union[str, WireCodec] = "int8", *,
                        hierarchical: bool = False) -> jax.Array:
    """Global-array reduce-scatter wire: ``contribs`` is the stacked
    ``[n, d, ...]`` per-member contribution array (sharded over ``axes``
    on dim 0); returns the stacked scattered sums ``[n, d // n, ...]``
    (member m's row is block m of the f32 member-order sum). fp32 codec
    == the serial blocked sum, bitwise; every other codec within n x its
    per-block bound."""
    axes = _axes_tuple(axes)
    n = int(np.prod([topo.sizes[a] for a in axes]))
    hier = hier_axes(topo, axes) if hierarchical else None

    def body(c):
        local = c[0]  # [d, ...]
        if hier is not None:
            o, n_o, i, n_i = hier
            out = rs_wire_hier_local(local, o, i, n_o, n_i, codec)
        else:
            out = rs_wire_local(local, axes if len(axes) > 1 else axes[0],
                                n, codec)
        return out[None]

    ax_entry = axes if len(axes) > 1 else axes[0]
    return _shard_map_full(body, topo, (P(ax_entry),), P(ax_entry))(contribs)


# ---------------------------------------------------------- byte accounting
def ag_wire_nbytes(shard_shape: Sequence[int], n: int,
                   codec: Union[str, WireCodec], itemsize: int = 2,
                   *, dim: int = 0) -> int:
    """Per-device wire bytes of ONE codec all-gather of a ``shard_shape``
    local shard over ``n`` members: each device receives the other n-1
    members' encoded shards (ring/tree topologies move the same total)."""
    codec = get_codec(codec)
    shape = tuple(int(d) for d in shard_shape)
    rows = shape[dim]
    lanes = int(np.prod(shape)) // max(rows, 1)
    per_member = codec.payload_nbytes(1, rows, lanes, itemsize)
    return per_member * (n - 1)


def rs_wire_nbytes(full_shape: Sequence[int], n: int,
                   codec: Union[str, WireCodec], itemsize: int = 4,
                   *, dim: int = 0) -> int:
    """Per-device wire bytes of ONE codec reduce-scatter of a
    ``full_shape`` contribution over ``n`` members: the all-to-all sends
    n-1 of each member's n encoded blocks."""
    codec = get_codec(codec)
    shape = tuple(int(d) for d in full_shape)
    rows = shape[dim] // max(n, 1)
    lanes = int(np.prod(shape)) // max(shape[dim], 1)
    per_block = codec.payload_nbytes(1, max(rows, 1), lanes, itemsize)
    return per_block * (n - 1)


def hier_rs_nbytes(full_shape: Sequence[int], n_o: int, n_i: int,
                   codec: Union[str, WireCodec], itemsize: int = 4,
                   *, dim: int = 0,
                   inner_codec: Union[str, WireCodec] = "fp32",
                   ) -> Tuple[int, int]:
    """(inter, intra) per-device wire bytes of one 2-hop reduce-scatter
    (:func:`rs_wire_hier_local`): the intra hop scatters the full
    contribution over the n_i group members at ``inner_codec`` (full
    width by default), the inter hop scatters the 1/n_i group partial
    over the n_o groups at ``codec`` — ONE pricing of the split rule,
    shared by every analytic stream that declares a 2-hop wire."""
    intra = rs_wire_nbytes(full_shape, n_i, inner_codec, itemsize, dim=dim)
    shrunk = list(int(d) for d in full_shape)
    shrunk[dim] //= n_i
    inter = rs_wire_nbytes(shrunk, n_o, codec, itemsize, dim=dim)
    return inter, intra


def hier_ag_nbytes(full_shape: Sequence[int], n_o: int, n_i: int,
                   codec: Union[str, WireCodec], itemsize: int = 4,
                   *, dim: int = 0,
                   inner_codec: Union[str, WireCodec] = "fp32",
                   ) -> Tuple[int, int]:
    """(inter, intra) per-device wire bytes of one 2-hop all-gather
    (:func:`ag_wire_hier_local`): the intra hop gathers the n_i member
    shards at ``inner_codec``, the inter hop moves each group's
    1/n_o block once at ``codec``."""
    shard = list(int(d) for d in full_shape)
    shard[dim] //= n_o * n_i
    intra = ag_wire_nbytes(shard, n_i, inner_codec, itemsize, dim=dim)
    group = list(int(d) for d in full_shape)
    group[dim] //= n_o
    inter = ag_wire_nbytes(group, n_o, codec, itemsize, dim=dim)
    return inter, intra
