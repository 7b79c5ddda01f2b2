"""The SPMD pipeline schedule.

Parity: deepspeed/runtime/pipe/schedule.py + engine.py (TrainSchedule,
InferenceSchedule, P2P send/recv). The reference runs an imperative 1F1B
instruction list per rank over NCCL p2p; the TPU-native schedule is one
``shard_map`` over the ``pp`` mesh axis (other axes stay auto, so dp/tp/sp
shardings keep flowing through XLA):

- Stacked layer params [L, ...] are sharded over pp on dim 0: each stage
  holds L/pp contiguous layers.
- A ``lax.scan`` over M + pp - 1 ticks implements GPipe filling/draining;
  stage outputs move to the next stage via ``lax.ppermute`` (ICI neighbor
  hop, the p2p send/recv pair).
- ``jax.grad`` through the scan+ppermute yields the reverse pipeline for
  backward automatically — with per-tick rematerialisation this is
  1F1B-equivalent activation memory (stash one activation per in-flight
  microbatch, recompute inside the tick's vjp).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ...comm.topology import MeshTopology
from ...models.transformer import TransformerConfig, apply_layer_stack


def neighbor_chain(n_stages: int):
    """The schedule's p2p fabric: stage i → i+1, NO wraparound edge.

    This is the exact shape shardlint R3 certifies as hang-free (a pure
    chain: injective, no self-loops, zero cycles —
    analysis/rules/topology.check_permutation); a ring or a stray extra
    edge here deadlocks real ICI, which the static check catches before a
    multi-chip run does."""
    return [(i, i + 1) for i in range(n_stages - 1)]


def pipelined_stack(
    cfg: TransformerConfig,
    layers,
    x: jax.Array,
    positions: jax.Array,
    segment_ids,
    topo: MeshTopology,
    train: bool,
    rng: Optional[jax.Array] = None,
    remat_policy: Optional[str] = None,
    tick_chunk: Optional[int] = None,
):
    """Run the block stack as a pp-stage pipeline over microbatches.

    layers: stacked block params [L, ...] (dim 0 sharded over pp).
    x: embedded microbatch stream [M, mb, S, D]; positions: [M, mb, S];
    segment_ids: [M, mb, S] or None. Returns (y [M, mb, S, D], moe_aux_mean).

    tick_chunk: checkpoint the schedule in chunks of this many ticks —
    grad-of-scan otherwise stashes one residual set per tick, i.e.
    O(num_microbatches) activations (measured: tools/pipe_memory.py),
    where the reference's 1F1B holds at most pp in-flight stashes
    (deepspeed/runtime/pipe/engine.py). Chunking stores only chunk-boundary
    carries and recomputes one chunk at a time during backward: peak stash
    is O(T/C + C) boundary activations (T = M + pp - 1) at ~2x forward
    compute — the scan-schedule equivalent of 1F1B's memory bound.
    """
    n_stages = topo.pp_size
    M = x.shape[0]
    num_layers = jax.tree_util.tree_leaves(layers)[0].shape[0]
    assert num_layers % n_stages == 0, (
        f"num_layers {num_layers} must divide pipeline stages {n_stages}"
    )
    # segment_ids stream alongside activations; a zeros stream when unused
    has_seg = segment_ids is not None
    seg = segment_ids if has_seg else jnp.zeros(positions.shape, jnp.int32)

    if n_stages == 1:
        def per_mb(args):
            xm, pm, sm, idx = args
            key = jax.random.fold_in(rng, idx) if rng is not None else None
            return apply_layer_stack(
                cfg, layers, xm, pm, sm if has_seg else None, key, train,
                remat_policy,
            )
        ys, auxs = lax.map(per_mb, (x, positions, seg, jnp.arange(M)))
        return ys, jnp.mean(auxs)

    fwd_perm = neighbor_chain(n_stages)

    ticks = M + n_stages - 1
    chunk = 0
    if tick_chunk:
        chunk = min(int(tick_chunk), ticks)
    padded_ticks = (
        ((ticks + chunk - 1) // chunk) * chunk if chunk else ticks
    )

    def body(local_layers, x_stream, pos_stream, seg_stream):
        stage = lax.axis_index("pp")

        def pad_stream(s):
            return jnp.pad(
                s, [(0, padded_ticks - M)] + [(0, 0)] * (s.ndim - 1)
            )

        x_pad, p_pad, s_pad = map(pad_stream, (x_stream, pos_stream, seg_stream))

        def tick(carry, inp):
            state, pstate, sstate, t = carry
            x_in, p_in, s_in = inp
            cur = jnp.where(stage == 0, x_in, state)
            pos = jnp.where(stage == 0, p_in, pstate)
            sg = jnp.where(stage == 0, s_in, sstate)
            # distinct randomness per (tick, stage): the in-flight microbatch
            # is t - stage, so fold both in (dense path splits per microbatch)
            key = (
                jax.random.fold_in(jax.random.fold_in(rng, t), stage)
                if rng is not None
                else None
            )
            out, aux = apply_layer_stack(
                cfg, local_layers, cur, pos, sg if has_seg else None, key,
                train, remat_policy,
            )
            # microbatch (t - stage) is in flight here; mask bubble ticks
            valid = (t >= stage) & (t < stage + M)
            aux = jnp.where(valid, aux, 0.0)
            y = jnp.where(stage == n_stages - 1, out, jnp.zeros_like(out))
            nxt = lax.ppermute(out, "pp", fwd_perm)
            pnxt = lax.ppermute(pos, "pp", fwd_perm)
            snxt = lax.ppermute(sg, "pp", fwd_perm)
            return (nxt, pnxt, snxt, t + 1), (y, aux)

        carry0 = (
            jnp.zeros(x_stream.shape[1:], x_stream.dtype),
            jnp.zeros(pos_stream.shape[1:], pos_stream.dtype),
            jnp.zeros(seg_stream.shape[1:], seg_stream.dtype),
            jnp.zeros((), jnp.int32),
        )
        if chunk:
            # checkpointed chunks: backward stores only the chunk-boundary
            # carries (one boundary activation each) and replays one chunk
            # of ticks at a time; ticks beyond `ticks` are bubble work the
            # valid-mask zeroes and the output slice drops
            def run_chunk(carry, inp):
                return lax.scan(tick, carry, inp)

            xs = tuple(
                a.reshape(padded_ticks // chunk, chunk, *a.shape[1:])
                for a in (x_pad, p_pad, s_pad)
            )
            _, (ys, auxs) = lax.scan(jax.checkpoint(run_chunk), carry0, xs)
            ys = ys.reshape(padded_ticks, *ys.shape[2:])
            auxs = auxs.reshape(padded_ticks)
        else:
            _, (ys, auxs) = lax.scan(tick, carry0, (x_pad, p_pad, s_pad))
        # valid outputs live on the last stage at ticks [pp-1, pp-1+M);
        # broadcast them to every stage (head/loss then run replicated-on-pp).
        # fp32 psum: XLA's CPU AllReducePromotion pass crashes on bf16
        # all-reduce under partial-manual shard_map (workaround; fp32 is
        # also the dtype the head consumes anyway).
        ys = lax.psum(
            ys[n_stages - 1:n_stages - 1 + M].astype(jnp.float32), "pp"
        ).astype(x_stream.dtype)
        aux_total = lax.psum(jnp.sum(auxs), "pp")  # sum over stages+ticks
        return ys, aux_total / M

    run = jax.shard_map(
        body,
        mesh=topo.mesh,
        in_specs=(P("pp"), P(), P(), P()),
        out_specs=(P(), P()),
        axis_names={"pp"},
        check_vma=False,
    )
    return run(layers, x, positions, seg)
