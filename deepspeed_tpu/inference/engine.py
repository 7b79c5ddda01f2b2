"""Inference engine: TP-sharded serving with a static KV cache.

Parity: deepspeed/inference/engine.py (InferenceEngine) + deepspeed
__init__.init_inference. The reference swaps torch modules for fused CUDA
blocks ("kernel injection") and walks an eager token loop; TPU-native:

- one jitted prefill (full-prompt forward that fills the cache) and one
  jitted ``lax.while_loop`` decode program — every step identical shapes,
  compiled once, KV cache donated through the loop;
- tensor parallelism is the model's partition_specs placed on the mesh
  (weights sharded column/row over tp); XLA inserts the serving
  collectives;
- ``replace_with_kernel_inject`` maps to selecting the Pallas flash
  attention path for prefill (the decode matvec is already MXU-shaped);
- ``dtype=int8`` / quantize flags use ops/quantizer.py weight-only block
  quantization; decode-shaped projections run the Pallas streaming kernel
  (ops/pallas/quantized_matmul.py) so HBM reads int8/int4 bytes — the
  dequantize-then-dot alternative materializes full-width weights every
  decode step (measured 3x slower at 410M).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..comm.topology import MeshTopology, ParallelDims
from ..models.decoding import forward_with_cache, init_cache
from ..models.sharding import use_topology
from ..ops.quantizer import (PackedWeight, pack_quantize_blockwise,
                             packed_partition_specs, packed_sharding_ok,
                             quantize_dequantize)
from ..utils.logging import log_dist


def _align_cache(n: int, mult: int = 128) -> int:
    """KV-cache capacity rounded up so the Pallas decode kernel always has
    an aligned block divisor (a 132-row cache has none and silently fell
    back to the XLA path — observed in the r4 decode bench logs). Capacity
    padding rows are position-masked by cache_len, so results are
    unchanged; the cost is a few KB of HBM per layer."""
    return max(-(-n // mult) * mult, mult)


def _bucket_prompt(n: int, mult: int = 32) -> int:
    """Prompt-width bucket for the compile cache. The KV cache itself
    keeps the 128 alignment (_align_cache — the Pallas block contract);
    the PREFILL WIDTH has no such constraint, so a finer granule wastes
    less padded prefill compute on short prompts while still collapsing
    the ragged-length neighborhood onto a handful of programs."""
    return _align_cache(n, mult)


def apply_repetition_penalty(logits, seen, penalty, active=None):
    """HF-convention repetition penalty: for tokens in ``seen`` [B, V],
    positive logits divide by the penalty, negative multiply.

    ``active`` ([B] or [B, 1] bool, optional) masks ragged-batch rows:
    padded/inactive slots keep their logits untouched instead of
    attending whatever stale ``seen`` garbage their row holds.

    ``logits`` may also be a [B, S, V] verify WINDOW (the serving step's
    speculative form): the one [B, V] ``seen`` matrix then applies to
    every window position — same elementwise math, so the S = 1 window
    is bitwise the 2-D path."""
    penalized = jnp.where(logits > 0, logits / penalty, logits * penalty)
    mask = seen if logits.ndim == 2 else seen[:, None, :]
    if active is not None:
        shape = (-1, 1) if logits.ndim == 2 else (-1, 1, 1)
        mask = mask & jnp.reshape(active, shape)
    return jnp.where(mask, penalized, logits)


def init_inference(
    model,
    tensor_parallel: Optional[Dict[str, Any]] = None,
    tp_size: int = 1,
    ep_size: int = 1,
    dtype=jnp.bfloat16,
    replace_with_kernel_inject: bool = False,
    quantize_bits: Optional[int] = None,
    max_tokens: int = 1024,
    kv_cache_dtype: str = "auto",
    draft_model=None,
    draft_params=None,
    checkpoint=None,
    topology: Optional[MeshTopology] = None,
    params=None,
    rng: Optional[jax.Array] = None,
    matvec_max_rows: Optional[int] = None,
    config: Optional[Dict[str, Any]] = None,
    **kwargs,
) -> "InferenceEngine":
    """Parity: deepspeed.init_inference(model, tp_size, dtype, ...).

    ``matvec_max_rows`` (also accepted as ``config={"matvec_max_rows": N}``
    — the "inference.matvec_max_rows" knob) widens the row threshold under
    which packed int8/int4 projections take the Pallas streaming matvec:
    e.g. the k=9 speculative verify window is 10 rows and needs ≥ 10.

    ``ep_size`` > 1 serves a MoE model EXPERT-PARALLEL: the mesh grows an
    ``ep`` axis (tp_size · ep_size devices), expert banks shard E over it
    per the model's partition specs, and the decode MLP's expert exchange
    runs over that axis (docs/serving.md "MoE serving").
    """
    if config:
        if matvec_max_rows is None and "matvec_max_rows" in config:
            matvec_max_rows = int(config["matvec_max_rows"])
        extras = sorted(set(config) - {"matvec_max_rows"})
        if extras:
            log_dist(
                f"init_inference: ignoring unsupported config keys {extras}"
            )
    if kwargs:
        log_dist(
            f"init_inference: ignoring unsupported arguments {sorted(kwargs)} "
            f"(reference-surface kwargs with no TPU equivalent)"
        )
    overlap_comm = None
    if tensor_parallel:
        tp_size = tensor_parallel.get("tp_size", tp_size)
        if tensor_parallel.get("overlap_comm"):
            # same section schema as the training config's
            # tensor_parallel.overlap_comm (decomposed collective matmul);
            # a bare boolean means {"enabled": bool}
            from ..config import OverlapCommConfig, _parse_dc

            oc = tensor_parallel["overlap_comm"]
            if isinstance(oc, bool):
                oc = {"enabled": oc}
            overlap_comm = _parse_dc(OverlapCommConfig, oc)
            overlap_comm.validate()
    if checkpoint is not None:
        if params is not None:
            raise ValueError("pass either checkpoint= or params=, not both")
        from ..runtime.checkpointing import load_params

        template = jax.eval_shape(
            lambda k: model.init(k), jax.random.PRNGKey(0)
        )
        params = load_params(checkpoint, template)
    if dtype in ("int8", jnp.int8):
        dtype = jnp.bfloat16
        quantize_bits = quantize_bits or 8
    elif dtype == "int4":  # weight-only 4-bit (reference: quantize_bits=4)
        dtype = jnp.bfloat16
        quantize_bits = quantize_bits or 4
    if topology is None:
        ep_size = max(int(ep_size), 1)
        n = max(tp_size, 1) * ep_size
        topology = MeshTopology(
            dims=ParallelDims(tp=tp_size, ep=ep_size),
            devices=jax.devices()[:n],
        )
    return InferenceEngine(
        model,
        topology=topology,
        dtype=dtype,
        kernel_inject=replace_with_kernel_inject,
        quantize_bits=quantize_bits,
        max_tokens=max_tokens,
        kv_cache_dtype=kv_cache_dtype,
        draft_model=draft_model,
        draft_params=draft_params,
        params=params,
        rng=rng,
        matvec_max_rows=matvec_max_rows,
        overlap_comm=overlap_comm,
    )


class InferenceEngine:
    def __init__(
        self,
        model,
        topology: MeshTopology,
        dtype=jnp.bfloat16,
        kernel_inject: bool = False,
        quantize_bits: Optional[int] = None,
        max_tokens: int = 1024,
        kv_cache_dtype: str = "auto",
        draft_model=None,
        draft_params=None,
        params=None,
        rng: Optional[jax.Array] = None,
        matvec_max_rows: Optional[int] = None,
        overlap_comm=None,
    ):
        self.model = model
        self.config = model.config
        self.topology = topology
        self.dtype = dtype
        self.max_tokens = min(max_tokens, self.config.max_seq_len)
        self.kernel_inject = kernel_inject
        # int8 KV cache: halves KV HBM for long-context serving; per-token
        # scales dequantize at read (in-kernel on the Pallas decode path)
        if kv_cache_dtype not in ("auto", "int8", "bf16", "bfloat16"):
            raise ValueError(
                f"kv_cache_dtype must be auto|bf16|bfloat16|int8, got "
                f"{kv_cache_dtype!r}"
            )
        self.kv_cache_quantized = kv_cache_dtype == "int8"
        self.kv_cache_storage_dtype = (
            jnp.bfloat16 if kv_cache_dtype in ("bf16", "bfloat16") else dtype
        )
        # "kernel injection" parity (reference: replace_with_kernel_inject
        # swaps torch blocks for fused CUDA blocks, csrc/transformer/
        # inference). The TPU translation is a fused *composition*, not one
        # mega-kernel: Pallas flash prefill + Pallas cached-KV decode
        # attention (models/decoding.py) + Pallas rmsnorm, with XLA fusing
        # the matmul/elementwise chains between them. Scoped via context
        # managers so other engines' kernel choices are untouched.
        on_tpu = topology.mesh.devices.flat[0].platform == "tpu"
        # inference.matvec_max_rows: per-engine streaming-matvec threshold
        # (None → kernel default). Applied as a trace-time scope below so
        # engines with different settings in one process don't fight.
        self.matvec_max_rows = (
            int(matvec_max_rows) if matvec_max_rows is not None else None
        )
        # decomposed TP collective matmul for the serving projections
        # (tensor_parallel.overlap_comm — parallel/tensor_overlap.py): the
        # decode out-projections take the feature-scatter ring (S=1 cannot
        # seq-shard), prefill takes the Megatron-SP pair when shapes divide
        self.tp_overlap = (
            overlap_comm
            if (
                overlap_comm is not None
                and getattr(overlap_comm, "enabled", False)
                and topology.tp_size > 1
            )
            else None
        )
        if self.tp_overlap is not None:
            from ..parallel.tensor_overlap import static_widths_divide

            reason = None
            if quantize_bits:
                # every big projection is a PackedWeight — the ring
                # dispatchers always fall back for packed leaves, so the
                # scope would only buy residual-layout churn
                reason = f"packed int{quantize_bits} weights take the " \
                         "streaming-matvec path, not the rings"
            elif not static_widths_divide(self.config, topology.tp_size):
                reason = (
                    "a projection width does not divide "
                    f"tp={topology.tp_size}"
                )
            if reason:
                log_dist(
                    f"tensor_parallel.overlap_comm disabled: {reason}"
                )
                self.tp_overlap = None

        def _impl_scopes():
            from contextlib import ExitStack

            from ..ops.pallas.quantized_matmul import matvec_max_rows_scope
            from ..parallel.tensor_overlap import overlap_scope

            stack = ExitStack()
            stack.enter_context(matvec_max_rows_scope(self.matvec_max_rows))
            stack.enter_context(overlap_scope(self.tp_overlap))
            if kernel_inject:
                from ..ops.attention import attention_impl
                from ..ops.normalization import pallas_rmsnorm_scope

                stack.enter_context(attention_impl("auto"))  # flash on TPU
                stack.enter_context(pallas_rmsnorm_scope(on_tpu))
            return stack

        self._impl_ctx = _impl_scopes

        tp_specs = (
            model.partition_specs(topology)
            if hasattr(model, "partition_specs")
            else None
        )
        if params is None:
            params = model.init(
                rng if rng is not None else jax.random.PRNGKey(0), dtype=dtype
            )
        cast = lambda a: (
            a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating) else a
        )
        params = jax.tree.map(cast, params)
        if quantize_bits:
            params = self._quantize_weights(params, quantize_bits, tp_specs)
        if tp_specs is not None and topology.world_size > 1:
            mesh = topology.mesh

            def to_sharding(spec, leaf):
                if isinstance(leaf, PackedWeight):
                    qs, ss = packed_partition_specs(spec, len(leaf.shape))
                    return PackedWeight(
                        NamedSharding(mesh, qs), NamedSharding(mesh, ss),
                        leaf.shape, leaf.bits, leaf.dtype, leaf.nibbles,
                        leaf.pspec,
                    )
                return NamedSharding(mesh, spec)

            shardings = jax.tree.map(
                to_sharding,
                tp_specs,
                params,
                is_leaf=lambda x: isinstance(x, P),
            )
            params = jax.device_put(params, shardings)
        else:
            # commit to the serving device: params= may arrive as host
            # numpy arrays (e.g. exported from a training engine), and an
            # uncommitted tree re-uploads per jitted call
            params = jax.device_put(params, topology.devices[0])
        self.params = params
        # speculative decoding (greedy, B=1): a draft proposes, the main
        # model verifies a whole window per forward. draft_model="ngram"
        # self-drafts by n-gram lookup in the token buffer (prompt-lookup
        # decoding) — zero extra parameters, zero extra HBM streams
        self.draft_model = draft_model
        self.draft_params = None
        self.spec_ngram_n = 3  # context length for the "ngram" draft
        if isinstance(draft_model, str):
            if draft_model != "ngram":
                raise ValueError(
                    f"draft_model={draft_model!r}: the only string draft is "
                    '"ngram" (prompt-lookup self-drafting); otherwise pass '
                    "a model"
                )
        elif draft_model is not None:
            if draft_model.config.vocab_size != self.config.vocab_size:
                raise ValueError(
                    "draft model must share the main model's vocabulary "
                    f"({draft_model.config.vocab_size} != "
                    f"{self.config.vocab_size})"
                )
            if draft_params is None:
                draft_params = draft_model.init(
                    jax.random.PRNGKey(1), dtype=dtype
                )
            self.draft_params = jax.tree.map(cast, draft_params)
        self._decode_fns: Dict[Any, Any] = {}
        # recompile observability (serving warmup): programs are keyed on
        # bucketed (B, prompt, total) shapes (prompt at 32, total at the
        # cache's 128), so this counts one compile per shape bucket — a
        # replayed ragged trace stays flat after warmup instead of
        # growing per exact length
        self.num_compiles = 0
        n_params = sum(
            int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params)
        )
        log_dist(
            f"InferenceEngine: {n_params / 1e6:.1f}M params, dtype="
            f"{jnp.dtype(dtype).name}, tp={topology.tp_size}, "
            f"quant={quantize_bits or 'off'}, kernel_inject={kernel_inject}"
        )

    def _quantize_weights(self, params, bits: int, tp_specs=None):
        """Weight-only block quantization of the big matmul weights.

        PACKED storage (ops/quantizer.PackedWeight) — HBM holds int8/int4
        + scales; the PackedWeight leaves flow into the jitted decode
        loop intact, where each projection runs the Pallas streaming
        kernel (ops/pallas/quantized_matmul.packed_proj) that dequantizes
        in VMEM — HBM traffic stays at the quantized byte count instead
        of a per-step full-width dequant temp. Under tp>1 the packed pair
        shards along
        the weight's own partition spec (packed_partition_specs: blocks
        stay whole — the contraction dim is stored (G, B) and only G
        shards), and the leaf remembers that spec (PackedWeight.pspec) so
        packed_proj's full-manual shard_map wrapper can run the streaming
        kernel PER SHARD — under tp>1 the decode matvec streams quantized
        bytes instead of dequantizing full-width weights every step (a
        bare pallas_call has no GSPMD partitioning rule, which is why the
        wrapper exists; leaves without a usable pspec still fall back to
        dequantize-then-dot). A
        leaf whose block/nibble geometry does not divide over the mesh
        falls back to the fake-quant roundtrip (numerics identical either
        way — same q/dq values), logged by name."""
        big = {"wq", "wk", "wv", "wo", "wi", "wg"}
        sharded = tp_specs is not None and self.topology.world_size > 1

        def q(path, leaf, spec=None):
            name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
            if name not in big or leaf.ndim < 2:
                return leaf
            if leaf.ndim > 3:
                # MoE expert banks [L, E, d, f] PACK since ISSUE 14: the
                # decode dispatch path consumes PackedWeight natively
                # (moe/sharded_moe._expert_proj → the per-expert Pallas
                # streaming matvec, per-shard under ep/tp meshes) and the
                # training/apply path dequantizes once (bitwise the old
                # fake-quant roundtrip — same q/dq values)
                if leaf.ndim != 4 or (
                    sharded and not self._expert_bank_sharding_ok(
                        leaf.shape, spec, bits
                    )
                ):
                    log_dist(
                        f"quantize: expert bank {name} falls back to "
                        f"fake-quant (geometry {leaf.shape} does not pack "
                        f"over mesh spec {spec})"
                    )
                    return quantize_dequantize(leaf, block=128, bits=bits)
                pw = pack_quantize_blockwise(leaf, block=128, bits=bits)
                if sharded:
                    pw.pspec = spec
                return pw
            if sharded and not packed_sharding_ok(
                leaf.shape, spec, self.topology.mesh, block=128, bits=bits
            ):
                log_dist(
                    f"quantize: {name} falls back to fake-quant (packed "
                    f"geometry {leaf.shape} does not divide over mesh "
                    f"spec {spec})"
                )
                return quantize_dequantize(leaf, block=128, bits=bits)
            pw = pack_quantize_blockwise(leaf, block=128, bits=bits)
            if sharded:
                pw.pspec = spec  # trace-time spec for the shard_map wrapper
            return pw

        if sharded:
            return jax.tree_util.tree_map_with_path(q, params, tp_specs)
        return jax.tree_util.tree_map_with_path(q, params)

    def _expert_bank_sharding_ok(self, shape, spec, bits: int) -> bool:
        """Whether a stacked expert bank [L, E, d, f] packs under this
        mesh spec: the trailing (d, f) dims obey the shared
        packed_sharding_ok block/nibble rules, expert shards keep whole
        experts (E divides the dim -3 extent), and the stacked layer dim
        stays unsharded (a scanned per-layer slice must be a whole
        bank)."""
        from ..ops.quantizer import _axis_size

        if spec is None:
            return True
        if not packed_sharding_ok(
            shape, spec, self.topology.mesh, block=128, bits=bits
        ):
            return False
        s = tuple(spec) + (None,) * (len(shape) - len(tuple(spec)))
        if any(e is not None for e in s[:-3]):
            return False
        try:
            e_extent = _axis_size(self.topology.mesh, s[-3])
        except KeyError:
            return False
        return shape[-3] % max(e_extent, 1) == 0

    # ------------------------------------------------- planner metadata
    def analytic_streams(self, batch: int = 1, seq: Optional[int] = None,
                         include_potential: bool = False):
        """Declared analytic streams, same schema as the training
        engine's (the shared planner / comms-logger / R8 contract). The
        serving engine has one: the decomposed-TP ring hops of the
        forward projections (no backward — the fwd wire figure)."""
        streams = {}
        if self.tp_overlap is not None:
            from ..parallel.tensor_overlap import ring_wire_bytes_per_step

            ring = ring_wire_bytes_per_step(
                self.config,
                self.topology,
                self.tp_overlap,
                batch=batch,
                seq=seq if seq is not None else self.config.max_seq_len,
                itemsize=jnp.dtype(self.dtype).itemsize,
            )
            if ring:
                # ring carries a fwd+bwd "bytes_per_step"; the serving
                # stream is fwd-only, so the overrides come AFTER the
                # spread
                streams["tp_ring"] = {
                    **ring,
                    "kind": "ici",
                    "bytes_per_step": ring["fwd_bytes_per_step"],
                    "per_device_bytes_per_step": ring["fwd_bytes_per_step"],
                    "overlapped": True,
                }
        return streams

    # -------------------------------------------------------------- forward
    def forward(self, input_ids):
        """Plain logits forward (no cache) — reference engine __call__."""
        if not hasattr(self, "_jit_forward"):  # jit once, not per call
            self._jit_forward = jax.jit(
                lambda p, ids: self.model.apply(
                    p, ids, dtype=self.dtype
                )
            )
        with use_topology(self.topology), self._impl_ctx():
            logits, _ = self._jit_forward(self.params, jnp.asarray(input_ids))
        return logits

    __call__ = forward

    # -------------------------------------------------- speculative decode
    def _build_spec_decode(self, prompt_bucket: int, total_bucket: int,
                           k: int):
        """Greedy speculative decoding, B=1 (the latency-bound serving case).

        Reference-era DeepSpeed ships this in its serving stack; TPU-native
        form: ONE jitted program — a small draft model proposes k-1 tokens
        autoregressively, the main model scores the whole window in a single
        cached forward, and the longest matching prefix (+1 "bonus" token
        from the verifier) is accepted. Greedy acceptance makes the output
        token-for-token IDENTICAL to plain greedy decoding of the main
        model — the oracle the tests assert — while the main model runs
        ~new_tokens/(accepted+1) times instead of new_tokens times.

        Cache discipline: every verify writes its full k-token window at the
        accepted position, so entries from rejected drafts are always
        overwritten before any later query can attend them (windows are
        contiguous and advance by >= 1 per round).

        draft_model="ngram" replaces the draft forward with a vectorized
        n-gram lookup over the token buffer (prompt-lookup decoding): the
        most recent earlier occurrence of the last n tokens supplies the
        proposed continuation, falling back to the buffer's stale verifier
        predictions past ``pos``. Proposal cost is a few VPU ops — and
        since batch-1 decode is HBM-bound, verifying k tokens streams the
        same weight bytes as decoding one, so every accepted draft token
        is nearly free throughput.

        Since ISSUE 9 the draft lookup and the acceptance math live in
        ``serving/spec.py`` (ngram_propose / longest_accepted_prefix /
        clamp_advance_at_eos) — ONE implementation shared with the slot
        engine's batched verify; this builder is the thin lockstep
        caller.

        Shapes are BUCKETED (``prompt_bucket`` at 32, ``total_bucket`` at
        the cache's 128); the actual ``prompt_len``/``total_len`` ride as
        traced operands, so every request whose lengths round to the same
        buckets reuses one compiled program. Padding beyond the real prompt holds the eos
        fill; its cache writes sit beyond the frontier and are rewritten
        before any query can attend them.
        """
        from ..serving.spec import (clamp_advance_at_eos,
                                    longest_accepted_prefix, ngram_propose)

        cfg = self.config
        ngram = isinstance(self.draft_model, str)
        m = int(self.spec_ngram_n)
        dcfg = None if ngram else self.draft_model.config
        # margin so last-round writes stay in-bounds
        total_alloc = total_bucket + k

        def spec_generate(params, dparams, tokens_buf, prompt_len, total_len,
                          eos_id):
            main_cache = init_cache(
                cfg, 1, _align_cache(total_alloc),
                self.kv_cache_storage_dtype,
                quantized=self.kv_cache_quantized,
            )
            draft_cache = (
                jnp.zeros((), jnp.int32) if ngram
                else init_cache(dcfg, 1, _align_cache(total_alloc), self.dtype)
            )
            prompt = tokens_buf[:, :prompt_bucket]
            logits, main_cache = forward_with_cache(
                cfg, params, prompt,
                main_cache, 0, dtype=self.dtype
            )
            # last REAL prompt position (the bucket tail is padding)
            last = lax.dynamic_slice_in_dim(logits, prompt_len - 1, 1, 1)
            n0 = jnp.argmax(last[:, 0], axis=-1)  # token at position P
            tokens_buf = lax.dynamic_update_slice(
                tokens_buf, n0[:, None], (0, prompt_len)
            )
            if not ngram:
                _, draft_cache = forward_with_cache(
                    dcfg, dparams, prompt, draft_cache, 0, dtype=self.dtype
                )

            def cond(state):
                _, _, _, pos, done, _ = state
                return (pos < total_len - 1) & ~done

            def body(state):
                tokens_buf, main_cache, draft_cache, pos, done, rounds = state
                start_tok = lax.dynamic_slice(tokens_buf, (0, pos), (1, 1))
                if ngram:
                    # shared prompt-lookup draft (serving/spec.py): the
                    # no-match fallback slice past ``pos`` reads the
                    # previous rejected window's stale verifier
                    # predictions — free, plausible proposals
                    cand = jnp.concatenate(
                        [start_tok.astype(jnp.int32),
                         ngram_propose(tokens_buf[0], pos, k - 1, m)[None, :]],
                        axis=1,
                    )
                else:
                    # --- draft k-1 tokens autoregressively --------------
                    # the loop runs k steps (one past the last proposal):
                    # the extra step's token is discarded but its forward
                    # writes the draft-cache row at pos+k-1, which a fully-
                    # accepting round (adv = k) would otherwise leave as
                    # zeros forever — collapsing acceptance for the rest
                    # of the generation
                    cand0 = jnp.zeros((1, k + 1), jnp.int32)
                    cand0 = lax.dynamic_update_slice(cand0, start_tok, (0, 0))

                    def dstep(i, carry):
                        cand, dcache = carry
                        tok = lax.dynamic_slice(cand, (0, i), (1, 1))
                        dlog, dcache = forward_with_cache(
                            dcfg, dparams, tok, dcache, pos + i,
                            dtype=self.dtype
                        )
                        nxt = jnp.argmax(dlog[:, -1], axis=-1).astype(jnp.int32)
                        cand = lax.dynamic_update_slice(
                            cand, nxt[:, None], (0, i + 1)
                        )
                        return cand, dcache

                    cand, draft_cache = lax.fori_loop(
                        0, k, dstep, (cand0, draft_cache)
                    )
                    cand = cand[:, :k]  # the k-th draft is never proposed
                # --- verify the whole window in one main forward --------
                # packed weights stream via the Pallas matvec kernel only
                # while the verify window fits the engine's matvec row
                # threshold (default 8; inference.matvec_max_rows): the
                # banked k=9 sweep's 10-row verify takes the
                # dequantize-then-MXU path at the default — same numerics,
                # but full-width HBM traffic for that forward. Set
                # matvec_max_rows >= k+1 to keep it streaming; making that
                # the default needs an on-chip win at 10+ rows first
                # (unmeasured).
                vlog, main_cache = forward_with_cache(
                    cfg, params, cand,
                    main_cache, pos, dtype=self.dtype
                )
                targets = jnp.argmax(vlog, axis=-1).astype(jnp.int32)  # [1,k]
                # shared acceptance math (serving/spec.py): longest
                # matching draft prefix + the verifier bonus token, the
                # advance clamped at an emitted eos
                n_acc = longest_accepted_prefix(
                    cand[0, 1:] == targets[0, : k - 1]
                )
                adv, has_eos = clamp_advance_at_eos(
                    targets[0], n_acc + 1, eos_id
                )
                tokens_buf = lax.dynamic_update_slice(
                    tokens_buf, targets, (0, pos + 1)
                )
                return (
                    tokens_buf, main_cache, draft_cache, pos + adv,
                    done | has_eos, rounds + 1,
                )

            done0 = (n0 == eos_id)[0]
            tokens_buf, _, _, pos, _, rounds = lax.while_loop(
                cond,
                body,
                (tokens_buf, main_cache, draft_cache,
                 jnp.asarray(prompt_len), done0, jnp.asarray(0)),
            )
            # positions past the last accepted token hold rejected-window
            # garbage: restore the eos fill the buffer started with
            fill = jnp.where(eos_id >= 0, eos_id, 0)
            idx = jnp.arange(total_alloc)[None, :]
            tokens_buf = jnp.where(idx <= pos, tokens_buf, fill)
            # rounds = verifier forwards: acceptance observability (a perfect
            # draft needs ceil((new_tokens-1)/k) rounds). The caller trims
            # the bucketed buffer to the real total_len.
            return tokens_buf, rounds

        return jax.jit(spec_generate)

    # ------------------------------------------------------------- generate
    def _build_decode(self, B: int, prompt_bucket: int, total_bucket: int):
        """One decode program per BUCKETED (B, prompt, total) shape
        (prompt at 32, total at the cache's 128): the exact
        ``prompt_len``/``total_len`` are traced operands, so the whole
        ragged-length neighborhood shares a compile (the serving warmup
        stops scaling with distinct request lengths)."""
        cfg = self.config

        def prefill(params, tokens_buf, prompt_len):
            cache = init_cache(
                cfg, B, _align_cache(total_bucket),
                self.kv_cache_storage_dtype,
                quantized=self.kv_cache_quantized,
            )
            prompt = tokens_buf[:, :prompt_bucket]
            logits, cache = forward_with_cache(
                cfg, params, prompt, cache,
                0, dtype=self.dtype
            )
            # last REAL prompt position (the bucket tail is eos padding)
            last = lax.dynamic_slice_in_dim(logits, prompt_len - 1, 1, 1)
            return last[:, 0], cache

        def sample(logits, key, temperature, top_k, top_p):
            logits = logits / jnp.maximum(temperature, 1e-6)
            if top_k > 0:
                kth = lax.top_k(logits, top_k)[0][:, -1][:, None]
                logits = jnp.where(logits < kth, -1e30, logits)
            if top_p < 1.0:
                # nucleus: keep the smallest prefix of the sorted distribution
                # whose mass reaches top_p (the top-1 token always survives)
                sorted_desc = jnp.sort(logits, axis=-1)[:, ::-1]
                probs = jax.nn.softmax(sorted_desc, axis=-1)
                cum = jnp.cumsum(probs, axis=-1)
                keep = (cum - probs) < top_p
                keep = keep.at[:, 0].set(True)  # top-1 survives even top_p=0
                kth = jnp.min(
                    jnp.where(keep, sorted_desc, jnp.inf), axis=-1, keepdims=True
                )
                logits = jnp.where(logits < kth, -1e30, logits)
            greedy = jnp.argmax(logits, axis=-1)
            sampled = jax.random.categorical(key, logits, axis=-1)
            return jnp.where(temperature == 0.0, greedy, sampled)

        def generate(params, tokens_buf, prompt_len, total_len, rng,
                     temperature, top_k, top_p, rep_penalty, use_penalty,
                     eos_id):
            V = cfg.vocab_size
            rows = jnp.arange(B)

            def step_sample(logits, seen, key, live=None):
                if use_penalty:
                    logits = apply_repetition_penalty(
                        logits, seen, rep_penalty, active=live
                    )
                return sample(logits, key, temperature, top_k, top_p)

            # seen-token mask carried through the loop: built once from the
            # prompt, then one O(B) scatter per generated token (not a full
            # (B,V) rebuild per step)
            if use_penalty:
                prompt_live = jnp.arange(total_bucket)[None, :] < prompt_len
                seen = jnp.zeros((B, V), jnp.bool_).at[
                    rows[:, None], tokens_buf
                ].max(prompt_live)
            else:
                seen = jnp.zeros((B, 1), jnp.bool_)  # unused placeholder

            last_logits, cache = prefill(params, tokens_buf, prompt_len)
            key, rng = jax.random.split(rng)
            nxt = step_sample(last_logits, seen, key)
            if use_penalty:
                seen = seen.at[rows, nxt].set(True)
            tokens_buf = lax.dynamic_update_slice(
                tokens_buf, nxt[:, None], (0, prompt_len)
            )
            done = nxt == eos_id

            def cond(state):
                _, _, pos, _, done, _ = state
                return (pos < total_len - 1) & ~jnp.all(done)

            def body(state):
                tokens_buf, cache, pos, rng, done, seen = state
                tok = lax.dynamic_slice(tokens_buf, (0, pos), (B, 1))
                # packed weights stay packed: each projection streams
                # int8/int4 from HBM through the Pallas matvec kernel
                logits, cache = forward_with_cache(
                    self.config, params,
                    tok, cache, pos, dtype=self.dtype
                )
                key, rng = jax.random.split(rng)
                nxt = step_sample(logits[:, -1], seen, key, live=~done)
                nxt = jnp.where(done, jnp.full_like(nxt, eos_id), nxt)
                if use_penalty:
                    # ragged-batch hazard fix: rows already done emit
                    # forced eos padding — never book it as "seen" (and
                    # never scatter a negative eos sentinel)
                    seen = seen.at[rows, jnp.clip(nxt, 0, V - 1)].max(~done)
                tokens_buf = lax.dynamic_update_slice(
                    tokens_buf, nxt[:, None], (0, pos + 1)
                )
                done = done | (nxt == eos_id)
                return (tokens_buf, cache, pos + 1, rng, done, seen)

            tokens_buf, _, _, _, _, _ = lax.while_loop(
                cond, body,
                (tokens_buf, cache, jnp.asarray(prompt_len), rng, done, seen),
            )
            return tokens_buf

        # top_k/top_p/use_penalty static (each gates a sort/scatter); the
        # penalty VALUE and the real lengths stay traced so sweeping them
        # doesn't recompile
        return jax.jit(generate, static_argnums=(6, 7, 9))

    def generate(
        self,
        input_ids,
        max_new_tokens: int = 32,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 1.0,
        repetition_penalty: float = 1.0,
        eos_token_id: int = -1,
        num_draft_tokens: int = 4,
        rng: Optional[jax.Array] = None,
    ):
        """Greedy (temperature=0) or top-k / top-p sampled decoding, with
        an optional HF-convention repetition penalty. With a draft model
        attached (init_inference(draft_model=...)), greedy B=1 generation
        runs speculatively: ``num_draft_tokens`` proposals per verifier
        forward, output identical to plain greedy.

        Returns [B, prompt + max_new_tokens] token ids (eos-padded).
        """
        ids = np.asarray(input_ids)
        B, prompt_len = ids.shape
        if max_new_tokens <= 0:
            # nothing to generate: echo the prompt (the decode program would
            # otherwise clamp its first write onto the last prompt token)
            return ids.astype(np.int32)
        if prompt_len >= self.max_tokens:
            raise ValueError(
                f"prompt length {prompt_len} leaves no room to generate under "
                f"max_tokens={self.max_tokens} (model max_seq_len="
                f"{self.config.max_seq_len}); truncate the prompt or raise "
                f"max_tokens"
            )
        total_len = min(prompt_len + max_new_tokens, self.max_tokens)
        # bucketed program shapes (prompt at 32, total at the cache's 128):
        # the exact lengths ride as traced operands, so a ragged arrival
        # trace compiles once per bucket
        pb, tb = _bucket_prompt(prompt_len), _align_cache(total_len)
        fill = eos_token_id if eos_token_id >= 0 else 0
        speculative = (
            self.draft_model is not None
            and temperature == 0.0
            and B == 1
            and repetition_penalty == 1.0
            and num_draft_tokens >= 1
        )
        if speculative:
            k = int(num_draft_tokens) + 1  # window = drafts + bonus slot
            key = ("spec", pb, tb, k)
            if key not in self._decode_fns:
                self.num_compiles += 1
                log_dist(
                    f"inference compile #{self.num_compiles}: spec decode "
                    f"bucket (prompt<={pb}, total<={tb}, k={k})"
                )
                self._decode_fns[key] = self._build_spec_decode(pb, tb, k)
            buf = np.full((1, tb + k), fill, dtype=np.int32)
            buf[:, :prompt_len] = ids
            with use_topology(self.topology), self._impl_ctx():
                out, rounds = self._decode_fns[key](
                    self.params, self.draft_params, jnp.asarray(buf),
                    prompt_len, total_len, eos_token_id,
                )
            self.last_spec_rounds = int(rounds)  # verifier calls this generate
            return np.asarray(out)[:, :total_len]
        statics = (top_k, float(top_p), float(repetition_penalty) != 1.0)
        key = (B, pb, tb) + statics
        if key not in self._decode_fns:
            self.num_compiles += 1
            log_dist(
                f"inference compile #{self.num_compiles}: decode bucket "
                f"(B={B}, prompt<={pb}, total<={tb}, "
                f"top_k={statics[0]}, top_p={statics[1]}, "
                f"penalty={statics[2]})"
            )
            self._decode_fns[key] = self._build_decode(B, pb, tb)
        buf = np.full((B, tb), fill, dtype=np.int32)
        buf[:, :prompt_len] = ids
        with use_topology(self.topology), self._impl_ctx():
            out = self._decode_fns[key](
                self.params,
                jnp.asarray(buf),
                prompt_len,
                total_len,
                rng if rng is not None else jax.random.PRNGKey(0),
                jnp.asarray(temperature, jnp.float32),
                statics[0],
                statics[1],
                jnp.asarray(repetition_penalty, jnp.float32),
                statics[2],
                eos_token_id,
            )
        return np.asarray(out)[:, :total_len]
