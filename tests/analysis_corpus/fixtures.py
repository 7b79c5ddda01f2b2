"""Hazard fixture programs for the shardlint corpus.

Every builder returns ``(closed_jaxpr, lint_kwargs, expect_rule)`` —
trace-ready evidence of one statically-visible bug class:

- ``stacked_dim0_drift``    R2: the PR-1 bucketed-opt carry drift
- ``slot_cache_carry_drift`` R2: a serving slot-KV arena whose step
  carry re-puts the head partition onto the slot dim
- ``paged_pool_carry_drift`` R2: the block-paged pool carry (gather/
  scatter through a page table) whose write-back sharding drifts
- ``spec_frontier_mask_drift`` R2: the speculative verify step's
  multi-token frontier writes (a k+1-wide window per slot at its own
  frontier) whose arena carry-out sharding drifts
- ``missing_psum_grads``    R1: dp-local grads applied as if reduced
- ``broken_ppermute_ring``  R3: a pipeline ring with a stray edge
- ``moe_a2a_malformed_ring`` R3: a hand-rolled MoE dispatch-reduce ring
  whose ep cycle closes on the wrong member (the a2a-overlap hazard;
  the clean twin traces the real parallel/a2a_overlap.py program)
- ``moe_decode_ring_malformed`` R3: the serving engine's decode-shaped
  expert combine ride hand-rolled with a duplicate-destination ep perm
  (the clean twin traces the real moe_decode_a2a ring)
- ``read_after_donate``     R4: a rotating slot read after overwrite
- ``zero3_prefetch_stale_slot`` R4: a hand-rolled two-slot param-gather
  prefetch whose layer compute reads the pre-overwrite slot generation
  (the staleness the functional prefetch carry avoids by construction)
- ``truncated_master``      R5: f32 master rebuilt through bf16
- ``pinned_host_compute``   R5: host-resident bytes fed to compute
- ``grad_wire_truncates_master`` R5: an int8 grad wire whose dequantized
  blocks accumulate into the master through bf16 instead of f32 (the
  qgZ dequant-accumulate contract of comm/wires.py)
- ``hier_wire_bad_split``   R3: a hand-rolled hierarchical 2-hop wire
  whose intra-group ring permutation maps two members onto one (the
  clean twin traces the real comm/wires.py 2-hop reduce-scatter)
- ``hbm_over_budget``       R6: estimated peak exceeds the HBM budget
- ``autotuner_rung_oom``    R6: a fat-micro autotuner rung statically
  over the shared budget (the planner-search prune; the clean twin is
  the thin-micro rung under the SAME budget)
- ``reshard_transpose_pair`` R7: transpose∘reshard∘transpose identity
- ``unhideable_offload_stream`` R8: declared-overlapped stream bigger
  than the compute window
- ``rng_key_reuse``         R9: one per-slot key consumed by two
  sampling sites (the clean twin splits first — the serving chain rule)
- ``reassoc_accum_drift``   R10: a hand-rolled wire ring accumulating
  dequantized chunks in bf16 (the clean twin dequant-accumulates in
  f32, the qgZ contract)
- ``static_arg_per_tick``   R11: a slot step whose ``spec_len`` was
  baked as a python constant at trace time (the clean twin traces it)
- ``dcn_flat_ring``         R12: the flat joint-(dp, fsdp) wire ring on
  a hybrid mesh whose dp axis is DCN-tagged (the clean twin traces the
  hierarchical 2-hop form of the same wire)
- ``dcn_unbudgeted_stream`` R13: a declared-overlapped stream whose
  payload only fits the compute window at ICI speed, not on the
  DCN-tagged axis it crosses (the clean twin splits hierarchically and
  declares the shrunk inter hop)
- ``kv_spill_unbudgeted``   R8: the tiered serving step's kv_spill
  host-paging stream with a page too large for the staging window to
  hide on the host link (the clean twin is the shipped two-slot
  double-buffer over a real KiB-scale page)
- ``restore_drops_sharding`` R2: a checkpoint-restore writeback that
  rebuilds the optimizer carry from host arrays without re-putting to
  the donated carry's resting shardings (the clean twin is
  runtime/ckpt/reshard.py's explicit device_put to the destination
  sharding)

Each has a ``*_clean`` twin proving the rules don't fire on the fixed
form. All fixtures trace on the 8-device CPU mesh (no execution).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def corpus_mesh() -> Mesh:
    devs = np.array(jax.devices()[:8]).reshape(4, 2)
    return Mesh(devs, ("dp", "tp"))


# --------------------------------------------------------------------- R2
def _drift_scan(mesh, drift: bool):
    resting = NamedSharding(mesh, P("dp", None))
    # the drifted writeback loses the dim-0 partition — exactly what the
    # bucketed layer scan's drop-lead slice hooks did to a dp-sharded
    # stacked dim before the PR-2 resting re-put
    writeback = NamedSharding(mesh, P(None, "tp") if drift else P("dp", None))

    def step(x):
        x = lax.with_sharding_constraint(x, resting)

        def body(c, _):
            c = jax.device_put(c * 0.5 + 1.0, writeback)
            return c, ()

        y, _ = lax.scan(body, x, None, length=4)
        return y

    sds = jax.ShapeDtypeStruct((8, 2), jnp.float32)
    return jax.make_jaxpr(step)(sds)


def stacked_dim0_drift():
    mesh = corpus_mesh()
    return _drift_scan(mesh, True), {"mesh": mesh}, "R2"


def stacked_dim0_drift_clean():
    mesh = corpus_mesh()
    return _drift_scan(mesh, False), {"mesh": mesh}, "R2"


# ------------------------------------------------------------------ R2 bis
def _slot_cache_scan(mesh, drift: bool):
    """The serving engine's slot-KV-arena carry: the arena
    [slots, capacity, kv*hd] rests with cache heads over tp and is
    carried through the step loop (frontier writes via
    dynamic_update_slice). The drifted form re-puts the carry with the
    head partition swapped onto the slot dim — exactly the bug a serving
    step whose cache write loses its sharding constraint would compile
    to (per-step reshard of the whole arena on real ICI)."""
    resting = NamedSharding(mesh, P(None, None, "tp"))
    writeback = NamedSharding(
        mesh, P("dp", None, None) if drift else P(None, None, "tp")
    )

    def step(arena):
        arena = lax.with_sharding_constraint(arena, resting)

        def body(c, _):
            chunk = jnp.ones((4, 2, 16), c.dtype)  # one step's KV writes
            c = lax.dynamic_update_slice(c, chunk, (0, 0, 0))
            c = jax.device_put(c, writeback)  # the step's carry-out
            return c, ()

        y, _ = lax.scan(body, arena, None, length=3)
        return y

    sds = jax.ShapeDtypeStruct((4, 8, 16), jnp.float32)
    return jax.make_jaxpr(step)(sds)


def slot_cache_carry_drift():
    mesh = corpus_mesh()
    return _slot_cache_scan(mesh, True), {"mesh": mesh}, "R2"


def slot_cache_carry_drift_clean():
    mesh = corpus_mesh()
    return _slot_cache_scan(mesh, False), {"mesh": mesh}, "R2"


# ------------------------------------------------------------------ R2 ter
def _paged_pool_scan(mesh, drift: bool):
    """The PAGED serving arena's pool carry: a global page pool
    [num_pages, page_size, kv*hd] resting with cache heads over tp,
    addressed through a traced per-slot page table (gather for the
    per-slot views, scatter for the chunk write — the block-paged form of
    the slot arena). The drifted form re-puts the carried pool with the
    head partition moved onto the PAGE dim — the bug a paged step whose
    pool write-back loses its sharding constraint compiles to: the whole
    pool reshards over ICI every serving step."""
    resting = NamedSharding(mesh, P(None, None, "tp"))
    writeback = NamedSharding(
        mesh, P("dp", None, None) if drift else P(None, None, "tp")
    )

    def step(pool, page_table):
        pool = lax.with_sharding_constraint(pool, resting)

        def body(c, _):
            view = c[page_table]          # [slots, pages/slot, ps, kv*hd]
            chunk = view[:, 0, :2] + 1.0  # one step's per-slot writes
            c = c.at[page_table[:, 0], :2].set(chunk)
            c = jax.device_put(c, writeback)  # the step's carry-out
            return c, ()

        y, _ = lax.scan(body, pool, None, length=3)
        return y

    pool = jax.ShapeDtypeStruct((8, 4, 16), jnp.float32)
    pt = jnp.zeros((2, 3), jnp.int32)
    return jax.make_jaxpr(step)(pool, pt)


def paged_pool_carry_drift():
    mesh = corpus_mesh()
    return _paged_pool_scan(mesh, True), {"mesh": mesh}, "R2"


def paged_pool_carry_drift_clean():
    mesh = corpus_mesh()
    return _paged_pool_scan(mesh, False), {"mesh": mesh}, "R2"


# ---------------------------------------------------------------- R2 quater
def _spec_frontier_scan(mesh, drift: bool):
    """The SPECULATIVE serving step's arena carry: each slot writes a
    k+1-wide verify window (committed token + k drafts) at its own
    frontier — a vmapped per-row dynamic_update_slice, the multi-token
    form of the slot engine's frontier write — and the arena must keep
    its head partition through the carry. The drifted form re-puts the
    carry with the partition moved onto the slot dim: the bug a spec
    step whose masked window write-back loses its sharding constraint
    compiles to (the whole arena reshards over ICI every verify)."""
    resting = NamedSharding(mesh, P(None, None, "tp"))
    writeback = NamedSharding(
        mesh, P("dp", None, None) if drift else P(None, None, "tp")
    )

    def step(arena, frontier):
        arena = lax.with_sharding_constraint(arena, resting)

        def body(c, _):
            win = jnp.ones((4, 3, 16), c.dtype)  # k+1 = 3 verify rows/slot
            c = jax.vmap(
                lambda a, w, off: lax.dynamic_update_slice(a, w, (off, 0))
            )(c, win, frontier)
            c = jax.device_put(c, writeback)  # the step's carry-out
            return c, ()

        y, _ = lax.scan(body, arena, None, length=3)
        return y

    arena = jax.ShapeDtypeStruct((4, 8, 16), jnp.float32)
    frontier = jnp.zeros((4,), jnp.int32)
    return jax.make_jaxpr(step)(arena, frontier)


def spec_frontier_mask_drift():
    mesh = corpus_mesh()
    return _spec_frontier_scan(mesh, True), {"mesh": mesh}, "R2"


def spec_frontier_mask_drift_clean():
    mesh = corpus_mesh()
    return _spec_frontier_scan(mesh, False), {"mesh": mesh}, "R2"


# --------------------------------------------------------------------- R1
def _grad_step(mesh, reduce_grads: bool):
    def body(g, p):
        if reduce_grads:
            g = lax.pmean(g, "dp")
        return p - 0.1 * g  # claimed-replicated "updated params"

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P("dp"), P()),
        out_specs=P(),
        axis_names={"dp", "tp"},
        check_vma=False,
    )
    g = jax.ShapeDtypeStruct((8, 4), jnp.float32)
    p = jax.ShapeDtypeStruct((2, 4), jnp.float32)
    return jax.make_jaxpr(lambda a, b: fn(a, b))(g, p)


def missing_psum_grads():
    mesh = corpus_mesh()
    return _grad_step(mesh, False), {"mesh": mesh}, "R1"


def missing_psum_grads_clean():
    mesh = corpus_mesh()
    return _grad_step(mesh, True), {"mesh": mesh}, "R1"


# --------------------------------------------------------------------- R3
def _pp_ring(mesh, perm):
    def body(x):
        return lax.psum(lax.ppermute(x, "dp", perm), "dp")

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=P("dp"),
        out_specs=P(),
        axis_names={"dp", "tp"},
        check_vma=False,
    )
    x = jax.ShapeDtypeStruct((8, 2), jnp.float32)
    return jax.make_jaxpr(fn)(x)


def broken_ppermute_ring():
    mesh = corpus_mesh()
    # ring 1→2→3→1 plus a stray 0→1 edge: duplicate destination — the
    # schedule hangs members on real ICI
    perm = [(1, 2), (2, 3), (3, 1), (0, 1)]
    return _pp_ring(mesh, perm), {"mesh": mesh}, "R3"


def broken_ppermute_ring_clean():
    mesh = corpus_mesh()
    perm = [(i, (i + 1) % 4) for i in range(4)]  # full single ring
    return _pp_ring(mesh, perm), {"mesh": mesh}, "R3"


# --------------------------------------------------------------------- R4
def _rotating_slot(stale_read: bool):
    def prog(slots, xs):
        def body(carry, x):
            buf = carry
            new = lax.dynamic_update_slice(buf, x[None], (0, 0))
            if stale_read:
                # reads the PRE-overwrite generation: the rotating slot
                # already holds the new bytes
                out = buf[0] + x
            else:
                out = new[0] + x
            return new, out

        return lax.scan(body, slots, xs)

    slots = jax.ShapeDtypeStruct((2, 4), jnp.float32)
    xs = jax.ShapeDtypeStruct((3, 4), jnp.float32)
    return jax.make_jaxpr(prog)(slots, xs)


def read_after_donate():
    return _rotating_slot(True), {}, "R4"


def read_after_donate_clean():
    return _rotating_slot(False), {}, "R4"


# --------------------------------------------------------------------- R5
def _master_update(truncate: bool):
    def prog(p, g):
        u = g.astype(jnp.float32) * -0.1
        if truncate:
            p = p.astype(jnp.bfloat16).astype(jnp.float32)
        return p + u

    p = jax.ShapeDtypeStruct((4, 4), jnp.float32)
    g = jax.ShapeDtypeStruct((4, 4), jnp.bfloat16)
    closed = jax.make_jaxpr(prog)(p, g)
    return closed, {"master_pairs": [(0, 0, "params")]}


def truncated_master():
    closed, kw = _master_update(True)
    return closed, kw, "R5"


def truncated_master_clean():
    closed, kw = _master_update(False)
    return closed, kw, "R5"


class _FakePinnedSharding:
    """Duck-typed pinned-host sharding: CPU devices expose no pinned_host
    memory space, so the corpus seeds the placement evidence directly —
    rules only read ``.spec`` / ``.memory_kind``."""

    memory_kind = "pinned_host"
    spec = P()


def _pinned_host(copy_first: bool):
    mesh = corpus_mesh()

    def prog(m):
        if copy_first:
            m = jax.device_put(m, NamedSharding(mesh, P()))
        return m * 2.0 + 1.0

    m = jax.ShapeDtypeStruct((4, 4), jnp.float32)
    closed = jax.make_jaxpr(prog)(m)
    # both twins start from a pinned-host master; the clean one copies to
    # device memory before any math touches it
    kw = {
        "mesh": mesh,
        "arg_shardings": {closed.jaxpr.invars[0]: _FakePinnedSharding()},
    }
    return closed, kw


def pinned_host_compute():
    closed, kw = _pinned_host(False)
    return closed, kw, "R5"


def pinned_host_compute_clean():
    closed, kw = _pinned_host(True)
    return closed, kw, "R5"


# --------------------------------------------------------------------- R3
# decomposed collective matmul (parallel/tensor_overlap.py): the clean twin
# traces the REAL ring program; the hazard is the same shape hand-rolled
# with a raw lax.ppermute and a malformed ring (bypassing the
# comm.collectives.permute construction-time contract — the exact mistake
# the hook exists to prevent, kept detectable at lint time)
def _overlap_topo():
    from deepspeed_tpu.comm.topology import MeshTopology, ParallelDims

    return MeshTopology(dims=ParallelDims(dp=2, tp=4))


def tp_overlap_malformed_ring():
    topo = _overlap_topo()
    tp = 4
    # ring 0→1→2→3 closed back to 1 instead of 0: duplicate destination —
    # two members send to one, the ring hangs on real ICI
    perm = [(0, 1), (1, 2), (2, 3), (3, 1)]

    def body(x, w):
        i = lax.axis_index("tp")
        m = x.shape[1]
        out = jnp.zeros((x.shape[0], m * tp, w.shape[1]), x.dtype)
        chunk, src = x, i
        for s in range(tp):
            out = lax.dynamic_update_slice(
                out, jnp.einsum("bsk,kn->bsn", chunk, w), (0, src * m, 0)
            )
            if s < tp - 1:
                chunk = lax.ppermute(chunk, "tp", perm)
                src = (src - 1) % tp
        return out

    fn = jax.shard_map(
        body,
        mesh=topo.mesh,
        in_specs=(P(("dp",), "tp", None), P(None, "tp")),
        out_specs=P("dp", None, "tp"),
        axis_names=set(topo.mesh.axis_names),
        check_vma=False,
    )
    x = jax.ShapeDtypeStruct((2, 8, 16), jnp.float32)
    w = jax.ShapeDtypeStruct((16, 8), jnp.float32)
    return jax.make_jaxpr(fn)(x, w), {"mesh": topo.mesh}, "R3"


def tp_overlap_ring_clean():
    from deepspeed_tpu.parallel.tensor_overlap import allgather_matmul

    topo = _overlap_topo()

    def prog(x, w):
        return allgather_matmul(x, w, topo, chunks=2, bidirectional=True)

    x = jax.ShapeDtypeStruct((2, 8, 16), jnp.float32)
    w = jax.ShapeDtypeStruct((16, 8), jnp.float32)
    return jax.make_jaxpr(prog)(x, w), {"mesh": topo.mesh}, "R3"


# ------------------------------------------------------------------ R3 bis
# decomposed MoE all-to-all (parallel/a2a_overlap.py): the clean twin
# traces the REAL overlapped expert layer; the hazard is the same dispatch-
# reduce ring hand-rolled with a raw lax.ppermute whose ep ring closes on
# the wrong member (bypassing comm.collectives.permute's construction-time
# contract — the exact mistake the hook exists to prevent)
def _moe_topo():
    from deepspeed_tpu.comm.topology import MeshTopology, ParallelDims

    return MeshTopology(dims=ParallelDims(dp=2, ep=4))


def moe_a2a_malformed_ring():
    topo = _moe_topo()
    ep, E_loc, C, D = 4, 1, 8, 16
    # ring 0→1→2→3 closed back to 1 instead of 0: duplicate destination —
    # two members send to one, the exchange hangs on real ICI
    perm = [(0, 1), (1, 2), (2, 3), (3, 1)]

    def body(disp, tok):
        i = lax.axis_index("ep")
        n = tok.shape[0]

        def part(blk):
            d = lax.dynamic_slice(disp, (0, blk * E_loc, 0), (n, E_loc, C))
            return jnp.einsum("nec,nd->ecd", d, tok)

        acc = part((i - 1) % ep)
        for s in range(1, ep):
            acc = lax.ppermute(acc, "ep", perm)
            acc = acc + part((i - 1 - s) % ep)
        return lax.psum(acc, ("dp",))

    fn = jax.shard_map(
        body,
        mesh=topo.mesh,
        in_specs=(P(("dp", "ep"), None, None), P(("dp", "ep"), None)),
        out_specs=P(None, None, None),
        axis_names=set(topo.mesh.axis_names),
        check_vma=False,
    )
    disp = jax.ShapeDtypeStruct((16, ep * E_loc, C), jnp.float32)
    tok = jax.ShapeDtypeStruct((16, D), jnp.float32)
    return jax.make_jaxpr(fn)(disp, tok), {"mesh": topo.mesh}, "R3"


def moe_a2a_ring_clean():
    from deepspeed_tpu.parallel.a2a_overlap import moe_a2a_ffn

    topo = _moe_topo()
    B, S, D, F, E, C = 2, 8, 16, 32, 4, 8

    def prog(x, disp, comb, wi, wg, wo):
        return moe_a2a_ffn(
            x, ("einsum", disp, comb), (wi, wg, wo), topo,
            chunks=2, bidirectional=True,
        )

    x = jax.ShapeDtypeStruct((B, S, D), jnp.float32)
    disp = jax.ShapeDtypeStruct((B, S, E, C), jnp.float32)
    comb = jax.ShapeDtypeStruct((B, S, E, C), jnp.float32)
    wi = jax.ShapeDtypeStruct((E, D, F), jnp.float32)
    wg = jax.ShapeDtypeStruct((E, D, F), jnp.float32)
    wo = jax.ShapeDtypeStruct((E, F, D), jnp.float32)
    return (
        jax.make_jaxpr(prog)(x, disp, comb, wi, wg, wo),
        {"mesh": topo.mesh},
        "R3",
    )


# ------------------------------------------------------------------ R3 ter
# decode-shaped MoE exchange (ISSUE 14, parallel/a2a_overlap.moe_decode_a2a
# — the serving engine's expert-parallel combine ride): the hazard is the
# same ride hand-rolled with a raw lax.ppermute whose ep cycle maps two
# members onto one destination (the exchange hangs on real ICI); the clean
# twin traces the REAL decode ring, whose every hop goes through
# comm.collectives.permute's construction-time R3 contract
def _moe_decode_topo():
    from deepspeed_tpu.comm.topology import MeshTopology, ParallelDims

    return MeshTopology(dims=ParallelDims(ep=4), devices=jax.devices()[:4])


def moe_decode_ring_malformed():
    topo = _moe_decode_topo()
    ep, E_loc, C, D = 4, 1, 8, 16
    # ring 0→1→2→3 closed back to 1 instead of 0: duplicate destination —
    # two members send their expert-output block to one, the combine ride
    # hangs on real ICI
    perm = [(0, 1), (1, 2), (2, 3), (3, 1)]

    def body(eo_local):
        i = lax.axis_index("ep")
        full = jnp.zeros((ep * E_loc, C, D), eo_local.dtype)
        buf = eo_local
        for s in range(ep):
            blk = (i - s) % ep
            full = lax.dynamic_update_slice(full, buf, (blk * E_loc, 0, 0))
            if s < ep - 1:
                buf = lax.ppermute(buf, "ep", perm)
        return full

    fn = jax.shard_map(
        body,
        mesh=topo.mesh,
        in_specs=(P("ep", None, None),),
        out_specs=P(None, None, None),
        axis_names=set(topo.mesh.axis_names),
        check_vma=False,
    )
    eo = jax.ShapeDtypeStruct((ep * E_loc, C, D), jnp.float32)
    return jax.make_jaxpr(fn)(eo), {"mesh": topo.mesh}, "R3"


def moe_decode_ring_clean():
    from deepspeed_tpu.parallel.a2a_overlap import moe_decode_a2a

    topo = _moe_decode_topo()
    N, D, F, E, C, K = 12, 16, 32, 4, 8, 2

    def prog(tokens, tok_of_slot, slot_valid, slot_of_tok, w_of_tok,
             wi, wg, wo):
        return moe_decode_a2a(
            tokens, tok_of_slot, slot_valid, slot_of_tok, w_of_tok,
            (wi, wg, wo), topo, chunks=2, bidirectional=True,
        )

    tokens = jax.ShapeDtypeStruct((N, D), jnp.float32)
    tof = jax.ShapeDtypeStruct((E, C), jnp.int32)
    sv = jax.ShapeDtypeStruct((E, C), jnp.bool_)
    sot = jax.ShapeDtypeStruct((N, K), jnp.int32)
    wt = jax.ShapeDtypeStruct((N, K), jnp.float32)
    wi = jax.ShapeDtypeStruct((E, D, F), jnp.float32)
    wg = jax.ShapeDtypeStruct((E, D, F), jnp.float32)
    wo = jax.ShapeDtypeStruct((E, F, D), jnp.float32)
    return (
        jax.make_jaxpr(prog)(tokens, tof, sv, sot, wt, wi, wg, wo),
        {"mesh": topo.mesh},
        "R3",
    )


# ------------------------------------------------------------------ R4 bis
def _prefetch_slots(stale_read: bool):
    """A hand-rolled two-slot ZeRO-3 gather prefetch: the rotating slot
    buffer [2, d, d] is overwritten with the next layer's gathered params
    via dynamic_update_slice each tick; the hazard reads the PRE-overwrite
    generation — the layer computes with layer i-2's weights (exactly the
    staleness the functional carry in runtime/zero/prefetch.py avoids by
    construction)."""

    def prog(slots, gathered):
        def body(carry, layer_w):
            buf = carry
            new = lax.dynamic_update_slice(buf, layer_w[None], (0, 0, 0))
            src = buf if stale_read else new
            out = jnp.tanh(src[0]) * 0.5
            return new, out

        return lax.scan(body, slots, gathered)

    slots = jax.ShapeDtypeStruct((2, 4, 4), jnp.float32)
    gathered = jax.ShapeDtypeStruct((3, 4, 4), jnp.float32)
    return jax.make_jaxpr(prog)(slots, gathered)


def zero3_prefetch_stale_slot():
    return _prefetch_slots(True), {}, "R4"


def zero3_prefetch_stale_slot_clean():
    return _prefetch_slots(False), {}, "R4"


# ------------------------------------------------------------------ R5 ter
def _grad_wire_update(truncate: bool):
    """The qgZ contract at the master update: an int8 grad wire is only
    sound when the dequantized blocks ACCUMULATE INTO THE MASTER IN F32
    (comm/wires.py decodes to f32 before any sum). The hazard books the
    wire-decoded gradient into the master through a bf16 accumulate —
    every path from the f32 master input to the f32 master output passes
    through a sub-32-bit float, the exact bf16-in-f32-clothing drift R5
    exists to catch. The clean twin is the dequant-accumulate-in-f32
    path the engine's wired reduction ships."""

    def prog(master, g):
        # the int8 wire leg (shared lane-wise scheme, fake-quant form)
        amax = jnp.max(jnp.abs(g), axis=0, keepdims=True)
        scale = jnp.maximum(amax, 1e-12) / 127.0
        q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
        deq = q.astype(jnp.float32) * scale
        if truncate:
            new = (
                master.astype(jnp.bfloat16)
                - 0.1 * deq.astype(jnp.bfloat16)
            ).astype(jnp.float32)
        else:
            new = master - 0.1 * deq
        return new

    m = jax.ShapeDtypeStruct((8, 4), jnp.float32)
    g = jax.ShapeDtypeStruct((8, 4), jnp.float32)
    closed = jax.make_jaxpr(prog)(m, g)
    return closed, {"master_pairs": [(0, 0, "master")]}


def grad_wire_truncates_master():
    closed, kw = _grad_wire_update(True)
    return closed, kw, "R5"


def grad_wire_truncates_master_clean():
    closed, kw = _grad_wire_update(False)
    return closed, kw, "R5"


# ------------------------------------------------------------------ R3 ter
# hierarchical 2-hop wire (comm/wires.py): the clean twin traces the REAL
# reduce_scatter_wire(hierarchical=True) program over a factored dp x fsdp
# mesh; the hazard is the same 2-hop shape hand-rolled with a raw
# lax.ppermute whose intra-group ring permutation maps two members onto
# one — a malformed group split that hangs the inner hop on real ICI
# (bypassing comm.collectives.permute's construction-time contract)
def _hier_topo():
    from deepspeed_tpu.comm.topology import MeshTopology, ParallelDims

    return MeshTopology(dims=ParallelDims(dp=2, fsdp=4))


def hier_wire_bad_split():
    topo = _hier_topo()
    n_i = 4
    # inner "ring" 0→1→2→3 closed back to 1: duplicate destination — the
    # intra-group exchange desynchronizes and hangs members on real ICI
    perm = [(0, 1), (1, 2), (2, 3), (3, 1)]

    def body(x):
        # hand-rolled hop 1: ride-the-ring partial accumulation over fsdp
        i = lax.axis_index("fsdp")
        chunk = x.shape[0] // n_i

        def part(blk):
            return lax.dynamic_slice(
                x, (blk * chunk, 0), (chunk, x.shape[1])
            ).astype(jnp.float32)

        acc = part((i - 1) % n_i)
        for s in range(1, n_i):
            acc = lax.ppermute(acc, "fsdp", perm)
            acc = acc + part((i - 1 - s) % n_i)
        # hop 2: the inter-group reduction over dp
        return lax.psum(acc, "dp")

    fn = jax.shard_map(
        body,
        mesh=topo.mesh,
        in_specs=P(("dp", "fsdp")),
        out_specs=P("fsdp"),
        axis_names=set(topo.mesh.axis_names),
        check_vma=False,
    )
    x = jax.ShapeDtypeStruct((32, 8), jnp.float32)
    return jax.make_jaxpr(fn)(x), {"mesh": topo.mesh}, "R3"


def hier_wire_bad_split_clean():
    from deepspeed_tpu.comm.wires import reduce_scatter_wire

    topo = _hier_topo()

    def prog(contribs):
        return reduce_scatter_wire(
            contribs, topo, ("dp", "fsdp"), "int8", hierarchical=True
        )

    contribs = jax.ShapeDtypeStruct((8, 32, 8), jnp.float32)
    return jax.make_jaxpr(prog)(contribs), {"mesh": topo.mesh}, "R3"


# --------------------------------------------------------------------- R6
def _budget_prog():
    mesh = corpus_mesh()

    def prog(x, w):
        h = jnp.einsum("bk,kn->bn", x, w)
        return (h * 2.0).sum()

    x = jax.ShapeDtypeStruct((256, 512), jnp.float32)
    w = jax.ShapeDtypeStruct((512, 512), jnp.float32)
    return jax.make_jaxpr(prog)(x, w), mesh


def hbm_over_budget():
    # x+w+h ≈ 1.8 MiB live — a 64 KiB per-device budget cannot hold it
    closed, mesh = _budget_prog()
    return closed, {"mesh": mesh, "hbm_budget_bytes": 64 * 1024}, "R6"


def hbm_over_budget_clean():
    closed, mesh = _budget_prog()
    return closed, {"mesh": mesh, "hbm_budget_bytes": 1 << 30}, "R6"


# ------------------------------------------------------------------ R6 bis
def _autotune_rung(micro: int):
    """An autotuner rung's shape: a per-device [micro, S, H] activation
    batch through a two-matmul block to a loss. The planner-driven
    search prices exactly this kind of program per (stage, remat, micro)
    rung; the hazard is the fat-micro rung whose activation live set
    statically exceeds the budget BOTH twins share — R6 prunes it before
    any compile, the thin rung passes (the prune-before-compile
    contract, docs/memory_planner.md)."""
    mesh = corpus_mesh()

    def prog(x, w1, w2):
        h = jnp.tanh(jnp.einsum("bsh,hk->bsk", x, w1))
        y = jnp.einsum("bsk,kh->bsh", h, w2)
        return ((y - x) ** 2).sum()

    x = jax.ShapeDtypeStruct((micro, 128, 256), jnp.float32)
    w1 = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    w2 = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    closed = jax.make_jaxpr(prog)(x, w1, w2)
    # 3 MiB/device: holds weights + the mb=1 rung's live set (~0.9 MiB)
    # with room, and is crossed by mb=16 (x alone is 2 MiB, h/y double it)
    kw = {"mesh": mesh, "hbm_budget_bytes": 3 * (1 << 20)}
    return closed, kw


def autotuner_rung_oom():
    closed, kw = _autotune_rung(16)
    return closed, kw, "R6"


def autotuner_rung_oom_clean():
    closed, kw = _autotune_rung(1)
    return closed, kw, "R6"


# --------------------------------------------------------------------- R7
def _reshard_pair(mesh, roundtrip: bool):
    # the hazard: transpose → reshard → transpose⁻¹, all single-use —
    # the placement cast pins both copies, so XLA cannot cancel the
    # pair; resharding the ORIGINAL value costs half the copies. The
    # clean twin does exactly that.
    cast = NamedSharding(mesh, P(None, "dp"))

    def prog(x):
        if roundtrip:
            y = jnp.transpose(x)
            y = lax.with_sharding_constraint(y, cast)
            z = jnp.transpose(y)
        else:
            z = lax.with_sharding_constraint(
                x, NamedSharding(mesh, P("dp", None))
            )
        return z * 1.5

    x = jax.ShapeDtypeStruct((8, 4), jnp.float32)
    return jax.make_jaxpr(prog)(x)


def reshard_transpose_pair():
    mesh = corpus_mesh()
    return _reshard_pair(mesh, True), {"mesh": mesh}, "R7"


def reshard_transpose_pair_clean():
    mesh = corpus_mesh()
    return _reshard_pair(mesh, False), {"mesh": mesh}, "R7"


# --------------------------------------------------------------------- R8
def _declared_stream(nbytes: float):
    mesh = corpus_mesh()

    def prog(x, w):
        return jnp.einsum("bk,kn->bn", x, w).sum()

    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    closed = jax.make_jaxpr(prog)(x, w)
    kw = {
        "mesh": mesh,
        "streams": {
            "offload": {
                "kind": "offload",
                "bytes_per_step": nbytes,
                "per_device_bytes_per_step": nbytes,
                "overlapped": True,
            }
        },
    }
    return closed, kw


def unhideable_offload_stream():
    # 64 GiB/step over a 32 GB/s host link is ~2 s of DMA; the tiny
    # matmul's compute window is microseconds — the overlap claim is
    # statically false
    closed, kw = _declared_stream(64 * (1 << 30))
    return closed, kw, "R8"


def unhideable_offload_stream_clean():
    closed, kw = _declared_stream(4 * 1024)  # 4 KiB hides under anything
    return closed, kw, "R8"


# --------------------------------------------------------------------- R9
def _slot_sampling(reuse: bool):
    """The serving sampler's key discipline: each slot's chain key is
    split, one subkey per draw. The hazard consumes ONE key at two
    sampling sites (the categorical draw and the top-p uniform) — the
    draws are correlated and the replay chain desynchronizes from the
    lockstep reference. The clean twin is the chain rule the slot
    engine ships: split first, consume each subkey once."""

    def prog(logits, key):
        if reuse:
            tok = jax.random.categorical(key, logits)
            u = jax.random.uniform(key, (logits.shape[0],))
        else:
            k1, k2 = jax.random.split(key)
            tok = jax.random.categorical(k1, logits)
            u = jax.random.uniform(k2, (logits.shape[0],))
        return tok, u

    logits = jax.ShapeDtypeStruct((4, 16), jnp.float32)
    return jax.make_jaxpr(prog)(logits, jax.random.PRNGKey(0))


def rng_key_reuse():
    return _slot_sampling(True), {}, "R9"


def rng_key_reuse_clean():
    return _slot_sampling(False), {}, "R9"


# ------------------------------------------------------------------ R10 bis
def _wire_ring_accum(narrow: bool):
    """A hand-rolled qgZ-style wire accumulate: int8 chunk payloads are
    dequantized (decode + lane-scale) and folded into a running
    accumulator chunk by chunk. The hazard runs the accumulator in
    bf16 — every grouping of the adds lands different rounding, so the
    declared-bitwise wire pair cannot hold. The clean twin accumulates
    in f32 and casts once at the end (comm/wires.py's contract)."""
    acc_dtype = jnp.bfloat16 if narrow else jnp.float32

    def prog(q, scales):
        acc = q[0].astype(acc_dtype) * scales[0].astype(acc_dtype)
        for s in range(1, 4):
            acc = acc + q[s].astype(acc_dtype) * scales[s].astype(acc_dtype)
        return acc.astype(jnp.bfloat16)

    q = jax.ShapeDtypeStruct((4, 8, 16), jnp.int8)
    scales = jax.ShapeDtypeStruct((4, 1, 16), jnp.float32)
    return jax.make_jaxpr(prog)(q, scales)


def reassoc_accum_drift():
    return _wire_ring_accum(True), {}, "R10"


def reassoc_accum_drift_clean():
    return _wire_ring_accum(False), {}, "R10"


# --------------------------------------------------------------------- R11
def _per_tick_step(baked: bool):
    """The slot step's trace-stability contract: per-tick scheduler
    state (here ``spec_len``) must be a TRACED input. The hazard bakes
    it as a python constant — the compiled program is specialized on
    one tick's value and every later tick retraces (or silently runs
    with the first tick's state). The lint kwargs carry the traced-args
    manifest exactly like serving.trace_serving_step supplies it."""
    BAKED_SPEC_LEN = 2

    def step_baked(tokens, num_new):
        window = tokens[:, :1 + BAKED_SPEC_LEN]
        return window.sum(axis=1) + num_new

    def step_traced(tokens, num_new, spec_len):
        mask = jnp.arange(tokens.shape[1])[None, :] <= spec_len[:, None]
        return (tokens * mask).sum(axis=1) + num_new

    tokens = jax.ShapeDtypeStruct((4, 8), jnp.int32)
    num_new = jax.ShapeDtypeStruct((4,), jnp.int32)
    spec_len = jax.ShapeDtypeStruct((4,), jnp.int32)
    if baked:
        closed = jax.make_jaxpr(step_baked)(tokens, num_new)
        manifest = {"tokens": (0, 1), "num_new": (1, 2)}
    else:
        closed = jax.make_jaxpr(step_traced)(tokens, num_new, spec_len)
        manifest = {"tokens": (0, 1), "num_new": (1, 2),
                    "spec_len": (2, 3)}
    kw = {
        "required_traced": ("num_new", "spec_len"),
        "traced_manifest": manifest,
    }
    return closed, kw


def static_arg_per_tick():
    closed, kw = _per_tick_step(True)
    return closed, kw, "R11"


def static_arg_per_tick_clean():
    closed, kw = _per_tick_step(False)
    return closed, kw, "R11"


# --------------------------------------------------------------------- R12
# flat vs 2-hop grad reduce-scatter on a HYBRID mesh (ISSUE 17): the
# hazard traces the real comm/wires.py FLAT form — one joint ring over
# ("dp", "fsdp") — on a mesh whose dp axis is DCN-tagged, so every hop of
# the full payload synchronizes on the slow inter-pod link; the clean
# twin traces the SAME wire hierarchical (intra-fsdp ring on ICI, then
# the 1/n_fsdp-sized inter hop over dp), the decomposition R12 names
def _dcn_topo():
    from deepspeed_tpu.comm.topology import MeshTopology, ParallelDims

    return MeshTopology.hybrid(dims=ParallelDims(dp=2, fsdp=4))


def _dcn_ring(hierarchical: bool):
    from deepspeed_tpu.comm.wires import reduce_scatter_wire

    topo = _dcn_topo()

    def prog(contribs):
        return reduce_scatter_wire(
            contribs, topo, ("dp", "fsdp"), "int8",
            hierarchical=hierarchical,
        )

    # a wire-bucket-sized payload: past R12's latency-bound materiality
    # floor, so the joint flat ring flags on bandwidth grounds
    contribs = jax.ShapeDtypeStruct((8, 2048, 64), jnp.float32)
    kw = {"mesh": topo.mesh, "link_kinds": topo.link_kinds}
    return jax.make_jaxpr(prog)(contribs), kw


def dcn_flat_ring():
    closed, kw = _dcn_ring(hierarchical=False)
    return closed, kw, "R12"


def dcn_flat_ring_clean():
    closed, kw = _dcn_ring(hierarchical=True)
    return closed, kw, "R12"


# --------------------------------------------------------------------- R13
# overlap claims must hold at DCN bandwidth: the hazard declares an
# overlapped grad-wire stream over a DCN-tagged dp axis whose payload
# fits the compute window at ICI speed (R8 stays silent — its one wire
# speed IS the ICI figure) but takes ~80x the window on the inter-pod
# link; the clean twin is the hierarchical split of the same stream,
# whose declared inter_bytes_per_step hop is all that rides DCN
def _dcn_stream(hierarchical: bool):
    from deepspeed_tpu.analysis.cost import HardwareModel

    mesh = corpus_mesh()

    def prog(x, w):
        return jnp.einsum("bk,kn->bn", x, w).sum()

    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    closed = jax.make_jaxpr(prog)(x, w)
    # ~21 ms compute window; 16 MiB/step fits it at 1 GB/s ICI (~17 ms)
    # but not at the 0.01 GB/s DCN share (~1.7 s)
    stream = {
        "kind": "ici",
        "axes": ("dp",),
        "bytes_per_step": 16 * (1 << 20),
        "per_device_bytes_per_step": 16 * (1 << 20),
        "overlapped": True,
    }
    if hierarchical:
        stream["hierarchical"] = True
        stream["inter_bytes_per_step"] = 64 * 1024
    kw = {
        "mesh": mesh,
        "link_kinds": {"dp": "dcn"},
        "streams": {"grad_wire": stream},
        "hardware": HardwareModel(
            gen="test", peak_flops=1e8, hbm_bytes=1 << 30, hbm_bw=1e9,
            ici_bw=1e9, host_bw=1e9, dcn_bw=1e7,
        ),
    }
    return closed, kw


def dcn_unbudgeted_stream():
    closed, kw = _dcn_stream(hierarchical=False)
    return closed, kw, "R13"


def dcn_unbudgeted_stream_clean():
    closed, kw = _dcn_stream(hierarchical=True)
    return closed, kw, "R13"


# ------------------------------------------------------- R8 (kv tiering)
def _kv_spill_stream(page_bytes: float, stage_slots: int):
    """The tiered serving step's host-spill stream (serving/engine.py
    ``kv_spill_stream``): ``stage_slots`` pages in + ``stage_slots``
    pages out per step, declared overlapped because the staged-gather
    hides the page-in under decode. The hazard sizes a page so large
    the double-buffer window can never hide it on the host link; the
    clean twin is the shipped two-slot staging buffer over a real page."""
    mesh = corpus_mesh()

    def prog(x, w):
        return jnp.einsum("bk,kn->bn", x, w).sum()

    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    closed = jax.make_jaxpr(prog)(x, w)
    nbytes = float(page_bytes) * stage_slots * 2  # demote + promote
    kw = {
        "mesh": mesh,
        "streams": {
            "kv_spill": {
                "kind": "offload",
                "bytes_per_step": nbytes,
                "per_device_bytes_per_step": nbytes,
                "overlapped": True,
                "stage_slots": stage_slots,
                "page_bytes_at_rest": float(page_bytes),
                "codec": "fp32",
            }
        },
    }
    return closed, kw


def kv_spill_unbudgeted():
    # an 8 GiB page x 2 staging slots x 2 directions is ~1 s of host
    # DMA per step — no decode window hides it; the overlap claim is
    # statically false
    closed, kw = _kv_spill_stream(8 * (1 << 30), stage_slots=2)
    return closed, kw, "R8"


def kv_spill_unbudgeted_clean():
    # a real page (2 layers x 16 tok x 4 kv-heads x 8 hd x 4 B k+v) is
    # KiB-scale — the double-buffered window hides it under anything
    closed, kw = _kv_spill_stream(32 * 1024, stage_slots=2)
    return closed, kw, "R8"


# ------------------------------------------------------------- R2 (ckpt)
def _restore_scan(mesh, drift: bool):
    """runtime/ckpt restore discipline as a carry fixture: the optimizer
    pair (m, v) rests dp-sharded on dim 0 and is rebuilt from host
    rectangles at restore time. The hazard's writeback re-puts the
    rebuilt tree WITHOUT the resting partition — what a loader that
    skips reshard.py's final ``device_put(arr, sharding)`` compiles to —
    so the donated carry re-enters the step loop de-sharded. The clean
    twin re-puts to the resting sharding (reshard._resharded_leaf's
    last line)."""
    resting = NamedSharding(mesh, P("dp", None))
    restored = NamedSharding(mesh, P(None, "tp") if drift else P("dp", None))

    def step(m, v):
        m = lax.with_sharding_constraint(m, resting)
        v = lax.with_sharding_constraint(v, resting)

        def body(carry, _):
            cm, cv = carry
            # the restore writeback: the carry rebuilt from host shards
            cm = jax.device_put(cm * 0.9 + 0.1, restored)
            cv = jax.device_put(cv * 0.99 + 0.01, restored)
            return (cm, cv), ()

        (m, v), _ = lax.scan(body, (m, v), None, length=4)
        return m, v

    sds = jax.ShapeDtypeStruct((8, 4), jnp.float32)
    return jax.make_jaxpr(step)(sds, sds)


def restore_drops_sharding():
    mesh = corpus_mesh()
    return _restore_scan(mesh, True), {"mesh": mesh}, "R2"


def restore_drops_sharding_clean():
    mesh = corpus_mesh()
    return _restore_scan(mesh, False), {"mesh": mesh}, "R2"


HAZARDS = [
    stacked_dim0_drift,
    slot_cache_carry_drift,
    paged_pool_carry_drift,
    spec_frontier_mask_drift,
    missing_psum_grads,
    broken_ppermute_ring,
    read_after_donate,
    truncated_master,
    pinned_host_compute,
    tp_overlap_malformed_ring,
    moe_a2a_malformed_ring,
    moe_decode_ring_malformed,
    zero3_prefetch_stale_slot,
    grad_wire_truncates_master,
    hier_wire_bad_split,
    hbm_over_budget,
    autotuner_rung_oom,
    reshard_transpose_pair,
    unhideable_offload_stream,
    rng_key_reuse,
    reassoc_accum_drift,
    static_arg_per_tick,
    dcn_flat_ring,
    dcn_unbudgeted_stream,
    kv_spill_unbudgeted,
    restore_drops_sharding,
]

CLEAN_TWINS = [
    stacked_dim0_drift_clean,
    slot_cache_carry_drift_clean,
    paged_pool_carry_drift_clean,
    spec_frontier_mask_drift_clean,
    missing_psum_grads_clean,
    broken_ppermute_ring_clean,
    read_after_donate_clean,
    truncated_master_clean,
    pinned_host_compute_clean,
    tp_overlap_ring_clean,
    moe_a2a_ring_clean,
    moe_decode_ring_clean,
    zero3_prefetch_stale_slot_clean,
    grad_wire_truncates_master_clean,
    hier_wire_bad_split_clean,
    hbm_over_budget_clean,
    autotuner_rung_oom_clean,
    reshard_transpose_pair_clean,
    unhideable_offload_stream_clean,
    rng_key_reuse_clean,
    reassoc_accum_drift_clean,
    static_arg_per_tick_clean,
    dcn_flat_ring_clean,
    dcn_unbudgeted_stream_clean,
    kv_spill_unbudgeted_clean,
    restore_drops_sharding_clean,
]
