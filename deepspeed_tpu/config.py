"""DeepSpeed-compatible configuration.

Parity: deepspeed/runtime/config.py (DeepSpeedConfig) and the per-section
config dataclasses under deepspeed/runtime/*/config.py. Accepts the same
``ds_config.json`` schema (a dict or a path), validates the batch-size
triangle, and exposes typed sections.

TPU-first notes: ``train_micro_batch_size_per_gpu`` keeps its reference name
but means per-*dp-shard* micro batch; ``"auto"`` values are resolved at
``initialize()`` time like the HF integration does in the reference.
"""

from __future__ import annotations

import copy
import json
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

AUTO = "auto"


class DeepSpeedConfigError(ValueError):
    pass


def _get(d: Dict[str, Any], key: str, default=None):
    v = d.get(key, default)
    return default if v == AUTO else v


def _tristate(v):
    """Normalize a bool-or-"auto" knob: "auto" (and any other string)
    survives parsing — strings are judged by ``_check_tristate`` at
    validation so a typo like "ture" raises instead of silently
    coercing to True; non-strings collapse to bool (JSON 0/1)."""
    return v if isinstance(v, str) else bool(v)


def _check_tristate(name: str, v) -> None:
    if not (isinstance(v, bool) or v == AUTO):
        raise DeepSpeedConfigError(
            f"{name} must be true, false or \"auto\", got {v!r}"
        )


@dataclass
class OptimizerConfig:
    """Parity: "optimizer" section (deepspeed/runtime/config.py)."""

    type: str = "adamw"
    params: Dict[str, Any] = field(default_factory=dict)

    @property
    def lr(self) -> float:
        return float(self.params.get("lr", 1e-3))

    @property
    def betas(self) -> Tuple[float, float]:
        betas = self.params.get("betas", (0.9, 0.999))
        return (float(betas[0]), float(betas[1]))

    @property
    def eps(self) -> float:
        return float(self.params.get("eps", 1e-8))

    @property
    def weight_decay(self) -> float:
        return float(self.params.get("weight_decay", 0.0))


@dataclass
class SchedulerConfig:
    type: Optional[str] = None
    params: Dict[str, Any] = field(default_factory=dict)


@dataclass
class FP16Config:
    """Parity: "fp16" section incl. dynamic loss scaling knobs."""

    enabled: bool = False
    loss_scale: float = 0.0  # 0 => dynamic
    initial_scale_power: int = 16
    loss_scale_window: int = 1000
    hysteresis: int = 2
    consecutive_hysteresis: bool = False
    min_loss_scale: float = 1.0

    @property
    def dynamic(self) -> bool:
        return self.loss_scale == 0.0

    @property
    def initial_scale(self) -> float:
        if not self.dynamic:
            return float(self.loss_scale)
        return float(2.0 ** self.initial_scale_power)


@dataclass
class BF16Config:
    enabled: bool = False
    # reference: bf16 grad accumulation dtype option (accumulate_grads_in_fp32)
    accumulate_grads_in_fp32: bool = True


@dataclass
class OffloadConfig:
    """Parity: "offload_optimizer"/"offload_param" subsections."""

    device: str = "none"  # none | cpu | nvme
    nvme_path: Optional[str] = None
    pin_memory: bool = True
    buffer_count: int = 4
    buffer_size: int = 100 * 2**20
    max_in_cpu: int = 10**9

    @property
    def enabled(self) -> bool:
        return self.device not in ("none", None)


@dataclass
class ZeroConfig:
    """Parity: deepspeed/runtime/zero/config.py (DeepSpeedZeroConfig)."""

    stage: int = 0
    allgather_partitions: bool = True
    overlap_comm: bool = True
    reduce_scatter: bool = True
    contiguous_gradients: bool = True
    reduce_bucket_size: int = 5 * 10**8
    allgather_bucket_size: int = 5 * 10**8
    sub_group_size: int = 10**9
    # double-buffer the bucketed per-layer offload update: prefetch layer
    # i+1's pinned-host optimizer state while layer i's math runs, write
    # layer i-1's result back concurrently (runtime/bucketed_opt.py).
    # Costs one extra layer slice of HBM; off until on-chip parity + A/B
    # land. "sub_group_prefetch" is accepted as an alias. "auto" defers to
    # the measured knob-default table (resolve_auto_knobs).
    offload_double_buffer: Any = False  # bool | "auto"
    # one-layer-ahead stage-3 parameter all-gather prefetch: the layer
    # scan carries a rotating two-slot gathered-params buffer (the PR-1
    # offload_double_buffer pattern applied to the fwd/bwd scan), so
    # layer i+1's all-gather is issued under layer i's math instead of
    # stalling layer i+1's compute on its own fetch
    # (runtime/zero/prefetch.py). Persistence-threshold (replicated)
    # params are excluded automatically — their "gather" is a no-op.
    # Off by default pending an on-chip A/B; "zero3_prefetch" is
    # accepted as an alias. Ignored (with a log line) when stage != 3.
    # "auto" defers to the measured knob-default table.
    stage3_layer_prefetch: Any = False  # bool | "auto"
    offload_optimizer: OffloadConfig = field(default_factory=OffloadConfig)
    offload_param: OffloadConfig = field(default_factory=OffloadConfig)
    stage3_max_live_parameters: int = 10**9
    stage3_max_reuse_distance: int = 10**9
    stage3_prefetch_bucket_size: int = 5 * 10**7
    stage3_param_persistence_threshold: int = 10**5
    stage3_gather_16bit_weights_on_model_save: bool = False
    # ZeRO++ knobs (reference: zero_quantized_* / zero_hpz_partition_size)
    zero_quantized_weights: bool = False
    zero_quantized_gradients: bool = False
    zero_hpz_partition_size: int = 1
    # MiCS-style sub-partitioning
    mics_shard_size: int = -1
    mics_hierarchical_params_gather: bool = False
    # ---- wire codecs (comm/wires.py, docs/wires.md) ----------------------
    # grad_wire: codec for the ZeRO gradient reduce-scatter on the data
    # axes (qgZ — blocks quantize ONCE before the exchange, the
    # accumulate runs after dequant in f32 master precision). Applies at
    # stages 1/2 (explicit wire reduction replaces the GSPMD-implicit
    # one) and stage 3 (the gather's backward). "auto" resolves from the
    # legacy bool: int8 when zero_quantized_gradients, else fp32.
    grad_wire: str = "auto"   # auto | fp32 | bf16 | int8 | int4
    # param_wire: codec for the stage-3 parameter all-gathers (qwZ),
    # composing with stage3_layer_prefetch (the prefetched gather then
    # moves codec bytes). "auto": int8 when zero_quantized_weights.
    param_wire: str = "auto"  # auto | fp32 | bf16 | int8 | int4
    # hierarchical_wire: 2-hop collectives over a factored (dp, fsdp)
    # mesh — intra-group (fsdp) hops run full width on the fast links,
    # inter-group (dp) hops move codec bytes (ZeRO++ hgZ / EQuARX).
    # Ignored (with a log line) when dp or fsdp is not live.
    hierarchical_wire: bool = False

    _WIRE_CODECS = ("auto", "fp32", "bf16", "int8", "int4")

    def resolved_grad_wire(self) -> str:
        if self.grad_wire != "auto":
            return self.grad_wire
        return "int8" if self.zero_quantized_gradients else "fp32"

    def resolved_param_wire(self) -> str:
        if self.param_wire != "auto":
            return self.param_wire
        return "int8" if self.zero_quantized_weights else "fp32"

    def validate(self) -> None:
        if self.stage not in (0, 1, 2, 3):
            raise DeepSpeedConfigError(f"zero_optimization.stage must be 0-3, got {self.stage}")
        for knob in ("offload_double_buffer", "stage3_layer_prefetch"):
            _check_tristate(f"zero_optimization.{knob}", getattr(self, knob))
        for off in (self.offload_optimizer, self.offload_param):
            if off.device not in ("none", "cpu", "nvme", None):
                raise DeepSpeedConfigError(f"offload device must be none|cpu|nvme, got {off.device}")
            if off.device == "nvme" and not off.nvme_path:
                raise DeepSpeedConfigError("nvme offload requires nvme_path")
        if self.offload_param.enabled and self.stage != 3:
            raise DeepSpeedConfigError("offload_param requires ZeRO stage 3")
        if (self.zero_quantized_weights or self.zero_quantized_gradients) and (
            self.stage != 3
        ):
            raise DeepSpeedConfigError(
                "zero_quantized_weights/gradients (ZeRO++) require stage 3"
            )
        for knob in ("grad_wire", "param_wire"):
            v = getattr(self, knob)
            if v not in self._WIRE_CODECS:
                raise DeepSpeedConfigError(
                    f"zero_optimization.{knob} must be one of "
                    f"{self._WIRE_CODECS}, got {v!r}"
                )
        if self.resolved_grad_wire() != "fp32" and self.stage < 1:
            raise DeepSpeedConfigError(
                "zero_optimization.grad_wire requires ZeRO stage >= 1 "
                "(stage 0 has no data-axis gradient reduce-scatter to "
                "compress — the DDP psum stays full width)"
            )
        if self.resolved_param_wire() != "fp32" and self.stage != 3:
            raise DeepSpeedConfigError(
                "zero_optimization.param_wire requires ZeRO stage 3 "
                "(below it parameters are never gathered over a wire)"
            )
        if self.hierarchical_wire and self.stage < 1:
            raise DeepSpeedConfigError(
                "zero_optimization.hierarchical_wire requires ZeRO stage "
                ">= 1 (stage 0 has no data-axis wire collectives to run "
                "the 2-hop forms over)"
            )


@dataclass
class ActivationCheckpointingConfig:
    """Parity: "activation_checkpointing" section; `policy` is TPU-native
    (maps to jax.checkpoint policies) replacing partition_activations et al."""

    partition_activations: bool = False
    cpu_checkpointing: bool = False
    contiguous_memory_optimization: bool = False
    number_checkpoints: Optional[int] = None
    synchronize_checkpoint_boundary: bool = False
    profile: bool = False
    policy: str = "none"  # none | full | dots_saveable | dots_flash | attn_only | offload_host

    def validate(self) -> None:
        # reject unknown policies at construction — otherwise the typo only
        # surfaces as a KeyError deep inside the traced train step
        from .runtime.activation_checkpointing import _POLICIES

        if self.policy not in (None, "none") and self.policy not in _POLICIES:
            raise DeepSpeedConfigError(
                f"activation_checkpointing.policy {self.policy!r} is unknown; "
                f"have none, {', '.join(sorted(_POLICIES))}"
            )


@dataclass
class PipelineConfig:
    """Parity: PipelineEngine config (runtime/pipe/engine.py kwargs)."""

    stages: int = 1
    partition_method: str = "parameters"  # parameters | uniform | type:<regex>
    seed_layers: bool = False
    activation_checkpoint_interval: int = 0
    pipe_schedule: str = "1f1b"  # 1f1b | gpipe (memory policy; grads identical)
    tick_chunk: int = 0  # 1f1b ckpt-chunk size in ticks; 0 = auto (~sqrt)


@dataclass
class TopologyConfig:
    """"topology" section — the physical fabric under the mesh.

    ``dcn_dp``: the data-parallel axis rides the inter-pod DCN fabric
    with this many pods (0/1 = flat single-pod ICI mesh). When > 1 the
    engine builds a two-level hybrid mesh (``MeshTopology.hybrid``: the
    DCN-tagged dp axis outermost, ICI axes inside), the cost planner
    prices dp-crossing collectives at ``hardware.dcn_bw`` and rules
    R12/R13 arm. This describes the fabric, not a tuning choice: the
    2-hop hierarchical split is the planner's job to pick
    (docs/memory_planner.md "Per-link pricing").
    """

    dcn_dp: int = 0

    def validate(self) -> None:
        if self.dcn_dp < 0:
            raise DeepSpeedConfigError(
                f"topology.dcn_dp must be >= 0, got {self.dcn_dp}"
            )

    def dcn_axes(self) -> tuple:
        return ("dp",) if self.dcn_dp > 1 else ()


@dataclass
class MoEOverlapA2AConfig:
    """"moe.overlap_a2a" — decomposed MoE all-to-all
    (parallel/a2a_overlap.py): the GSPMD dispatch/combine exchanges at the
    expert boundary decompose into chunked ppermute hops on the ep-axis
    ring whose wire time hides under the per-chunk expert FFN matmuls —
    each expert shard starts computing as soon as a capacity chunk lands
    instead of waiting for the whole [E, C, D] exchange. Default OFF until
    an on-chip A/B lands (the same protocol as
    tensor_parallel.overlap_comm / zero_optimization.offload_double_buffer);
    numerics of the rings are oracle-verified BITWISE against the module's
    pure-XLA reference path on CPU meshes for both dispatch modes
    (tests/test_moe_a2a_overlap.py)."""

    enabled: Any = False  # bool | "auto" (measured knob-default table)
    # capacity chunks per exchange (the ring/FFN pipelining granularity:
    # chunk k+1's hops fly while chunk k's expert matmuls run); uneven
    # splits allowed, never changes numerics for top_k <= 2
    chunks: int = 1
    # halves of each capacity chunk ride both ring directions at once
    # (full-duplex ICI halves per-hop wire time, same hop count)
    bidirectional: bool = False

    def validate(self) -> None:
        _check_tristate("moe.overlap_a2a.enabled", self.enabled)
        if int(self.chunks) < 1:
            raise DeepSpeedConfigError(
                f"moe.overlap_a2a.chunks must be >= 1, got {self.chunks}"
            )


@dataclass
class MoEConfig:
    enabled: bool = False
    ep_size: int = 1
    num_experts: int = 1
    top_k: int = 2
    capacity_factor: float = 1.25
    eval_capacity_factor: float = 2.0
    min_capacity: int = 4
    aux_loss_coef: float = 0.01
    z_loss_coef: float = 1e-3
    drop_tokens: bool = True
    use_residual: bool = False
    overlap_a2a: MoEOverlapA2AConfig = field(
        default_factory=MoEOverlapA2AConfig
    )

    def __post_init__(self):
        # _parse_dc is shallow: the nested section arrives as a dict (or a
        # bare bool / "auto", the overlap_comm spelling) — normalize here
        if isinstance(self.overlap_a2a, bool) or self.overlap_a2a == AUTO:
            self.overlap_a2a = MoEOverlapA2AConfig(enabled=self.overlap_a2a)
        elif isinstance(self.overlap_a2a, dict):
            self.overlap_a2a = _parse_dc(MoEOverlapA2AConfig,
                                         self.overlap_a2a)


@dataclass
class OverlapCommConfig:
    """"tensor_parallel.overlap_comm" — decomposed (ring) collective
    matmul at the TP projection boundaries (parallel/tensor_overlap.py):
    the Megatron all-gather/reduce-scatter pair decomposes into ppermute
    rings whose hops hide under the per-chunk matmuls (T3, arXiv
    2401.16677). Default OFF until an on-chip A/B lands (the same
    protocol as zero_optimization.offload_double_buffer); numerics of the
    unquantized rings are oracle-verified bitwise against the XLA
    reference path on a CPU mesh (tests/test_tp_overlap.py)."""

    enabled: Any = False  # bool | "auto" (measured knob-default table)
    # matmul sub-chunks per ring step (scheduling granularity for the
    # DMA/MXU overlap; never changes numerics — uneven splits allowed)
    chunks: int = 1
    # send half the payload around each ring direction simultaneously:
    # full-duplex ICI halves per-hop wire time at the same hop count
    bidirectional: bool = False
    # int8 + fp32 lane-scale hop wire (ZeRO++ qwZ composition). Gather
    # wires quantize once at the source; scatter accumulators re-quantize
    # per hop (error O(tp) — see docs/collective_matmul.md). Forward-only:
    # in training the backward runs the unquantized transpose
    # (straight-through — int8 casts would otherwise zero the activation
    # cotangents), mirroring ZeRO++'s qwZ/qgZ split.
    quantized_hops: bool = False

    def validate(self) -> None:
        _check_tristate("tensor_parallel.overlap_comm.enabled", self.enabled)
        if int(self.chunks) < 1:
            raise DeepSpeedConfigError(
                f"tensor_parallel.overlap_comm.chunks must be >= 1, got "
                f"{self.chunks}"
            )


@dataclass
class TensorParallelConfig:
    """Parity: autotp / "tensor_parallel" section."""

    tp_size: int = 1
    overlap_comm: OverlapCommConfig = field(default_factory=OverlapCommConfig)


@dataclass
class SpecDecodeConfig:
    """"serving.spec" section — speculative decoding inside the slot
    engine (deepspeed_tpu/serving/spec.py, docs/serving.md). Each active
    decode slot proposes up to ``max_draft`` draft tokens host-side
    (n-gram/prompt-lookup over its own token buffer); the ONE jitted
    step verifies every slot's window at once. A spec decode slot
    consumes ``max_draft + 1`` budget rows; the SplitFuse planner
    shrinks the draft count toward 0 under budget pressure, so the step
    shape — and the zero-recompiles contract — never changes. Lossless:
    spec-on reproduces spec-off token-for-token (greedy AND
    sampled-with-shared-keys)."""

    enabled: Any = False  # bool | "auto" (measured knob-default table)
    max_draft: int = 4     # k: draft tokens per decode slot per step (the
                           # verify window is k+1 rows of the slot's chunk)
    draft: str = "ngram"   # draft source; "ngram" = host-side n-gram /
                           # prompt-lookup over the slot's token buffer
    ngram_n: int = 3       # context length of the n-gram match

    def validate(self) -> None:
        if int(self.max_draft) < 1:
            raise DeepSpeedConfigError(
                f"serving.spec.max_draft must be >= 1, got {self.max_draft}"
            )
        if self.draft != "ngram":
            raise DeepSpeedConfigError(
                'serving.spec.draft must be "ngram" (host-side n-gram / '
                f"prompt-lookup), got {self.draft!r}"
            )
        if int(self.ngram_n) < 1:
            raise DeepSpeedConfigError(
                f"serving.spec.ngram_n must be >= 1, got {self.ngram_n}"
            )


@dataclass
class FleetConfig:
    """"serving.fleet" section — the disaggregated, replicated serving
    tier (deepspeed_tpu/serving/fleet/, docs/serving.md "Fleet"). A
    :class:`~deepspeed_tpu.serving.fleet.Router` owns a fleet-level
    bounded admission gate and dispatches requests across ``replicas``
    data-parallel ServingEngine replicas (one process, shared params),
    with prefix-cache-aware routing over the chained-crc32 block keys,
    optional DistServe-style prefill/decode disaggregation (dedicated
    prefill replicas hand finished prefills' KV to decode replicas as a
    page transfer), session affinity and load shedding. Correctness
    anchor: ANY routing of a trace replays token-for-token equal to a
    single-replica serial replay (the deterministic per-request RNG
    chain), including across a prefill→decode handoff."""

    enabled: bool = False
    replicas: int = 2            # data-parallel ServingEngine replicas
    prefill_replicas: int = 0    # of those, dedicated prefill replicas
                                 # (0 = every replica serves mixed
                                 # prefill+decode; > 0 needs serving.paged
                                 # — the KV handoff is a page transfer)
    routing: str = "prefix"      # prefix | least_loaded | round_robin
                                 # ("prefix" routes to the replica whose
                                 # PrefixCache holds the longest matching
                                 # block chain, falling back to load)
    affinity: bool = True        # session_id -> replica stickiness (a
                                 # session's KV reuse stays local)
    queue_limit: int = 0         # fleet-wide shed threshold: total queued
                                 # across replicas at admission; 0 = only
                                 # the per-replica bounds shed
    shed_ttft_p95_s: float = 0.0  # shed new arrivals while the fleet's
                                 # recent p95 TTFT exceeds this; 0 = off
    prefix_balance_slack: int = -1  # cache-locality vs load-balance
                                 # trade: a prefix match only wins while
                                 # the matched replica's load exceeds the
                                 # idlest replica's by at most this many
                                 # requests (a fully-shared system prompt
                                 # must not pile the whole fleet's
                                 # traffic on one replica); -1 = auto
                                 # (max(1, max_slots // 2))

    ROUTING_POLICIES = ("prefix", "least_loaded", "round_robin")

    def validate(self) -> None:
        if int(self.replicas) < 1:
            raise DeepSpeedConfigError(
                f"serving.fleet.replicas must be >= 1, got {self.replicas}"
            )
        if int(self.prefill_replicas) < 0:
            raise DeepSpeedConfigError(
                "serving.fleet.prefill_replicas must be >= 0, got "
                f"{self.prefill_replicas}"
            )
        if int(self.prefill_replicas) >= int(self.replicas):
            raise DeepSpeedConfigError(
                f"serving.fleet.prefill_replicas {self.prefill_replicas} "
                f"must be < replicas {self.replicas}: every prefill "
                "replica hands its KV to a decode replica, so at least "
                "one decode replica must exist"
            )
        if self.routing not in self.ROUTING_POLICIES:
            raise DeepSpeedConfigError(
                "serving.fleet.routing must be one of "
                f"{'|'.join(self.ROUTING_POLICIES)}, got {self.routing!r}"
            )
        if int(self.queue_limit) < 0:
            raise DeepSpeedConfigError(
                "serving.fleet.queue_limit must be >= 0 (0 = per-replica "
                f"bounds only), got {self.queue_limit}"
            )
        if float(self.shed_ttft_p95_s) < 0:
            raise DeepSpeedConfigError(
                "serving.fleet.shed_ttft_p95_s must be >= 0 (0 = off), "
                f"got {self.shed_ttft_p95_s}"
            )
        if int(self.prefix_balance_slack) < -1:
            raise DeepSpeedConfigError(
                "serving.fleet.prefix_balance_slack must be >= -1 "
                f"(-1 = auto), got {self.prefix_balance_slack}"
            )


@dataclass
class ServingConfig:
    """"serving" section — the continuous-batching runtime
    (deepspeed_tpu/serving/). Parity: DeepSpeed-MII / FastGen's
    continuous batching + Dynamic SplitFuse scheduling, TPU-native: one
    jitted step of fixed shape [max_slots, token_budget] serves arbitrary
    arrival patterns with zero recompiles after warmup."""

    enabled: bool = False
    max_slots: int = 8           # concurrent in-flight requests (KV slots)
    token_budget: int = 64       # tokens processed per engine step (the
                                 # SplitFuse chunk width; prompts longer
                                 # than this prefill across steps)
    queue_limit: int = 64        # bounded admission queue; 0 = unbounded
    request_timeout_s: float = 60.0   # queued longer than this → EVICTED
    eviction_backoff_s: float = 1.0   # retry-after hint: backoff * 2**attempts
    max_tokens: int = 1024       # per-request prompt+output cap (slot KV
                                 # capacity; clamped to model max_seq_len)
    kv_cache_dtype: str = "auto"  # auto | bf16 | bfloat16 | int8
    paged: Any = False           # block-paged KV arena (vLLM / FastGen
                                 # blocked-KV): a global page pool + per-slot
                                 # page tables replaces the contiguous
                                 # [max_slots, capacity] regions. bool |
                                 # "auto" (measured knob-default table;
                                 # forced True under fleet disaggregation)
    page_size: int = 16          # tokens per KV page (paged mode)
    num_pages: int = 0           # physical pages in the pool; 0 = auto
                                 # (max_slots * pages_per_slot — no
                                 # overcommit). Lower it to overcommit HBM;
                                 # shardplan prices the pool (R6)
    prefix_cache: bool = True    # hash-of-prefix → shared read-only pages
                                 # with refcounts + copy-on-write (paged
                                 # mode only; off for a model with window
                                 # layers, whose windows a hit would lack)
    host_pages: int = 0          # tiered KV (ISSUE 18): pinned-host page
                                 # capacity behind the HBM pool. 0 = off;
                                 # > 0 demotes cold/evicted pages to host
                                 # (codec-compressed at rest) and promotes
                                 # them back through the in-step staging
                                 # buffer — paged mode only
    spill_codec: str = "fp32"    # at-rest codec for demoted pages
                                 # (comm/wires.py): fp32 = bitwise spill,
                                 # int8 = 4x smaller within the codec's
                                 # lane-wise bound; int8-quantized pools
                                 # spill their q arrays raw either way
    spill_dir: Optional[str] = None  # optional NVMe third tier: host-
                                 # overflowed pages stream to .bin files
                                 # here through ops/aio (same interface)
    moe_a2a: str = "auto"        # decode-shaped expert-exchange form for
                                 # MoE models served expert-parallel
                                 # (ep > 1): "stock" = GSPMD collectives
                                 # (the latency-bound small-step default),
                                 # "chunked" = the a2a_overlap chunked-
                                 # ppermute ring (hops hide under per-
                                 # chunk expert FFNs), "auto" = stock
                                 # below a per-hop payload threshold,
                                 # chunked above it. Bitwise-equal forms;
                                 # planner_search enumerates the axis.
    spec: SpecDecodeConfig = field(default_factory=SpecDecodeConfig)
                                 # speculative decoding (draft-then-verify
                                 # per decode slot); see SpecDecodeConfig
    fleet: FleetConfig = field(default_factory=FleetConfig)
                                 # replicated serving tier behind a
                                 # prefix-aware router; see FleetConfig

    def __post_init__(self):
        # _parse_dc is shallow: the nested "spec"/"fleet" sections arrive
        # as dicts both from DeepSpeedConfig and from ServingEngine(
        # serving={...}) — normalize here so every consumer sees the
        # dataclasses
        if isinstance(self.spec, bool) or self.spec == AUTO:
            # bare bool / "auto" spelling, like overlap_comm
            self.spec = SpecDecodeConfig(enabled=self.spec)
        if isinstance(self.spec, dict):
            self.spec = _parse_dc(SpecDecodeConfig, self.spec)
        if isinstance(self.fleet, dict):
            self.fleet = _parse_dc(FleetConfig, self.fleet)

    def pages_per_slot(self, max_tokens: Optional[int] = None) -> int:
        """Logical pages per slot: covers the per-request token cap plus
        the token_budget write margin (padded chunk tails never leave the
        mapped range). The ENGINE passes its clamped
        ``min(serving.max_tokens, model max)`` — that value is
        authoritative; without it this is the config-level upper bound."""
        span = int(max_tokens if max_tokens is not None
                   else self.max_tokens) + int(self.token_budget)
        return -(-span // int(self.page_size))

    def validate(self) -> None:
        if int(self.max_slots) < 1:
            raise DeepSpeedConfigError(
                f"serving.max_slots must be >= 1, got {self.max_slots}"
            )
        if int(self.token_budget) < 1:
            raise DeepSpeedConfigError(
                f"serving.token_budget must be >= 1, got {self.token_budget}"
            )
        if int(self.queue_limit) < 0:
            raise DeepSpeedConfigError(
                f"serving.queue_limit must be >= 0, got {self.queue_limit}"
            )
        if float(self.request_timeout_s) <= 0:
            raise DeepSpeedConfigError(
                "serving.request_timeout_s must be > 0, got "
                f"{self.request_timeout_s}"
            )
        if self.kv_cache_dtype not in ("auto", "int8", "bf16", "bfloat16"):
            raise DeepSpeedConfigError(
                "serving.kv_cache_dtype must be auto|bf16|bfloat16|int8, "
                f"got {self.kv_cache_dtype!r}"
            )
        if int(self.page_size) < 1:
            raise DeepSpeedConfigError(
                f"serving.page_size must be >= 1, got {self.page_size}"
            )
        if int(self.num_pages) < 0:
            raise DeepSpeedConfigError(
                f"serving.num_pages must be >= 0 (0 = auto), got "
                f"{self.num_pages}"
            )
        if self.moe_a2a not in ("auto", "stock", "chunked"):
            raise DeepSpeedConfigError(
                "serving.moe_a2a must be auto|stock|chunked, got "
                f"{self.moe_a2a!r}"
            )
        if int(self.host_pages) < 0:
            raise DeepSpeedConfigError(
                f"serving.host_pages must be >= 0 (0 = untiered), got "
                f"{self.host_pages}"
            )
        if int(self.host_pages) > 0 and self.paged is False:
            # "auto" is fine: resolve_auto_knobs runs before the engine
            # reads paged, and a tiered config forces it on there
            raise DeepSpeedConfigError(
                "serving.host_pages > 0 requires serving.paged: the host "
                "tier demotes/promotes PAGES of the block-paged arena "
                "(docs/serving.md \"KV tiering\")"
            )
        from .comm.wires import WIRE_NAMES
        if self.spill_codec not in WIRE_NAMES:
            raise DeepSpeedConfigError(
                f"serving.spill_codec must be one of "
                f"{'|'.join(WIRE_NAMES)}, got {self.spill_codec!r}"
            )
        _check_tristate("serving.spec.enabled", self.spec.enabled)
        _check_tristate("serving.paged", self.paged)
        if self.spec.enabled is True:
            # a disabled (or still-"auto") spec section is inert (the
            # engine maps it to max_draft = 0; "auto" only resolves on
            # when the budget fits), so its field ranges only matter on
            self.spec.validate()
            if int(self.spec.max_draft) + 1 > int(self.token_budget):
                raise DeepSpeedConfigError(
                    f"serving.spec.max_draft {self.spec.max_draft} needs "
                    f"max_draft + 1 <= token_budget {self.token_budget}: a "
                    "spec decode slot's verify window is max_draft + 1 rows "
                    "of the one fixed-shape step"
                )
        if self.fleet.enabled:
            self.fleet.validate()
            if int(self.fleet.prefill_replicas) > 0 and self.paged is False:
                # "auto" is fine here: resolve_auto_knobs forces paged on
                # under prefill/decode disaggregation before the engine
                # reads it
                raise DeepSpeedConfigError(
                    "serving.fleet.prefill_replicas > 0 requires "
                    "serving.paged: the prefill→decode KV handoff is a "
                    "page-table + page-payload transfer through the "
                    "block-paged arena (docs/serving.md)"
                )
        # NOTE: the num_pages liveness floor (num_pages >= pages_per_slot)
        # depends on the ENGINE-clamped max_tokens (min with the model's
        # max_seq_len), so ServingEngine.__init__ / trace_serving_step
        # enforce it — config validation alone cannot know the model.


@dataclass
class SteptraceConfig:
    """"steptrace" section — structured span tracing + the process-global
    metrics registry (profiling/steptrace.py, docs/observability.md).
    Host-side only: spans bracket dispatches; nothing is traced inside
    jitted programs. The section gates the REGISTRY (and the one span
    that fences, ``train/device``): disabled, engines keep
    ``tracer = None``, no registry exists and nothing is stored. The span
    sites themselves always feed the profiler's trace
    (``steptrace.Phase``), which costs about a microsecond a span while
    no profile is being taken."""

    enabled: bool = False
    max_spans: int = 100_000   # registry bound (spans / async events /
                               # metric samples each); beyond it entries
                               # are counted in ``dropped``, not stored
    export_path: Optional[str] = None  # default target of
                               # ``engine.trace_export()`` (Chrome
                               # trace-event JSON)

    def validate(self) -> None:
        if int(self.max_spans) < 1:
            raise DeepSpeedConfigError(
                f"steptrace.max_spans must be >= 1, got {self.max_spans}"
            )


@dataclass
class HealthwatchConfig:
    """"healthwatch" section — always-on goodput accounting, anomaly
    watchdogs and the flight-recorder postmortem
    (profiling/healthwatch.py, docs/observability.md "healthwatch").
    Enabling healthwatch implies steptrace (the goodput buckets are
    classified off the engine's own spans). MUST be zero-overhead when
    disabled: engines keep ``healthwatch = None``, no ring buffer is
    allocated, no span is added and no device scalar is read — the loss
    trajectory is bitwise identical to a no-healthwatch engine."""

    enabled: bool = False
    ring_steps: int = 64       # flight-recorder depth: last K steps of
                               # spans/metrics/watchdog evaluations
    rules: Dict[str, Any] = field(default_factory=dict)
                               # per-rule overrides merged over
                               # healthwatch.DEFAULT_RULES, e.g.
                               # {"queue_depth_breach": {"threshold": 32,
                               #                         "action": "dump"}}
    export_path: Optional[str] = None  # metrics export target; "*.prom"
                               # writes Prometheus textfile format,
                               # anything else appends JSON-lines
    export_interval_s: float = 10.0    # min seconds between flushes
                               # (0 = flush every step)
    postmortem_path: Optional[str] = None  # default dump target
                               # (healthwatch_postmortem_<source>.json)
    install_signal_handler: bool = True  # chain SIGTERM + excepthook so
                               # preemption/crash still dumps evidence

    def validate(self) -> None:
        if int(self.ring_steps) < 1:
            raise DeepSpeedConfigError(
                f"healthwatch.ring_steps must be >= 1, got "
                f"{self.ring_steps}"
            )
        if float(self.export_interval_s) < 0:
            raise DeepSpeedConfigError(
                "healthwatch.export_interval_s must be >= 0, got "
                f"{self.export_interval_s}"
            )
        if not isinstance(self.rules, dict):
            raise DeepSpeedConfigError(
                f"healthwatch.rules must be a dict, got "
                f"{type(self.rules).__name__}"
            )
        from .profiling.healthwatch import (ACTIONS, DEFAULT_RULES,
                                            SEVERITIES)

        for name, params in self.rules.items():
            if name not in DEFAULT_RULES:
                raise DeepSpeedConfigError(
                    f"healthwatch.rules: unknown rule {name!r} "
                    f"(known: {sorted(DEFAULT_RULES)})"
                )
            if isinstance(params, bool):
                continue
            if not isinstance(params, dict):
                raise DeepSpeedConfigError(
                    f"healthwatch.rules.{name} must be a dict or bool, "
                    f"got {type(params).__name__}"
                )
            action = params.get("action")
            if action is not None and action not in ACTIONS:
                raise DeepSpeedConfigError(
                    f"healthwatch.rules.{name}.action must be one of "
                    f"{ACTIONS}, got {action!r}"
                )
            sev = params.get("severity")
            if sev is not None and sev not in SEVERITIES:
                raise DeepSpeedConfigError(
                    f"healthwatch.rules.{name}.severity must be one of "
                    f"{SEVERITIES}, got {sev!r}"
                )


@dataclass
class FlopsProfilerConfig:
    enabled: bool = False
    profile_step: int = 1
    module_depth: int = -1
    top_modules: int = 1
    detailed: bool = True
    output_file: Optional[str] = None


@dataclass
class CommsLoggerConfig:
    enabled: bool = False
    verbose: bool = False
    prof_all: bool = True
    debug: bool = False
    prof_ops: List[str] = field(default_factory=list)


@dataclass
class MonitorConfig:
    tensorboard: Dict[str, Any] = field(default_factory=dict)
    wandb: Dict[str, Any] = field(default_factory=dict)
    csv_monitor: Dict[str, Any] = field(default_factory=dict)

    @property
    def enabled(self) -> bool:
        return any(
            bool(sec.get("enabled", False))
            for sec in (self.tensorboard, self.wandb, self.csv_monitor)
        )


@dataclass
class CurriculumConfig:
    enabled: bool = False
    curriculum_type: str = "seqlen"
    min_difficulty: int = 8
    max_difficulty: int = 1024
    schedule_type: str = "fixed_linear"
    schedule_config: Dict[str, Any] = field(default_factory=dict)


@dataclass
class RandomLTDConfig:
    enabled: bool = False
    total_layer_num: int = 0
    random_ltd_layer_num: int = 0
    random_ltd_layer_id: List[int] = field(default_factory=list)
    model_mask_name: Optional[str] = None
    model_type: str = "decoder"
    hidden_state_order: str = "batch_seq_dim"
    random_ltd_schedule: Dict[str, Any] = field(default_factory=dict)


@dataclass
class DataEfficiencyConfig:
    enabled: bool = False
    seed: int = 1234
    curriculum_learning: CurriculumConfig = field(default_factory=CurriculumConfig)
    random_ltd: RandomLTDConfig = field(default_factory=RandomLTDConfig)


@dataclass
class CompressionConfig:
    weight_quantization: Dict[str, Any] = field(default_factory=dict)
    activation_quantization: Dict[str, Any] = field(default_factory=dict)
    sparse_pruning: Dict[str, Any] = field(default_factory=dict)
    head_pruning: Dict[str, Any] = field(default_factory=dict)
    row_pruning: Dict[str, Any] = field(default_factory=dict)
    channel_pruning: Dict[str, Any] = field(default_factory=dict)
    layer_reduction: Dict[str, Any] = field(default_factory=dict)


@dataclass
class AutotuningConfig:
    enabled: bool = False
    fast: bool = True
    metric: str = "throughput"
    start_profile_step: int = 3
    end_profile_step: int = 5
    max_train_micro_batch_size_per_gpu: int = 64
    tuner_type: str = "gridsearch"


@dataclass
class ElasticityConfig:
    enabled: bool = False
    max_train_batch_size: int = 2000
    micro_batch_sizes: List[int] = field(default_factory=lambda: [2, 4, 6])
    min_gpus: int = 1
    max_gpus: int = 10000
    min_time: int = 20
    prefer_larger_batch: bool = True
    ignore_non_elastic_batch_info: bool = False
    version: float = 0.1


@dataclass
class ProgressiveLayerDropConfig:
    """Parity: "progressive_layer_drop" section (PLD paper schedule)."""

    enabled: bool = False
    theta: float = 0.5
    gamma: float = 0.001


@dataclass
class SequenceParallelConfig:
    sp_size: int = 1
    mode: str = "ulysses"  # ulysses | ring


@dataclass
class CheckpointConfig:
    """Parity: the "checkpoint" section + the reference's pluggable
    checkpoint_engine (torch-native / nebula → native shard files / Orbax)."""

    engine: str = "native"  # native (shard .npy files) | orbax
    # async snapshot pipeline (runtime/ckpt): overlap the shard write with
    # the next step's math; the snapshot fence is the only synchronous cost
    async_save: bool = False
    # keep only the newest N committed tags (0 = keep everything)
    keep_last: int = 0
    # declared save cadence (every N global steps, 0 = no periodic saves):
    # the train loop's contract, and the amortization window the
    # ckpt_snapshot analytic stream prices against the roofline
    save_interval_steps: int = 0
    # SIGTERM (preemption) behavior once a save_dir is known:
    # "save" chains a final sync save in front of healthwatch's postmortem
    on_preempt: str = "save"  # save | none

    def validate(self) -> None:
        if self.engine not in ("native", "orbax"):
            raise DeepSpeedConfigError(
                f"checkpoint.engine must be 'native' or 'orbax', got {self.engine!r}"
            )
        if self.keep_last < 0:
            raise DeepSpeedConfigError(
                f"checkpoint.keep_last must be >= 0, got {self.keep_last}"
            )
        if self.save_interval_steps < 0:
            raise DeepSpeedConfigError(
                f"checkpoint.save_interval_steps must be >= 0, got "
                f"{self.save_interval_steps}"
            )
        if self.on_preempt not in ("save", "none"):
            raise DeepSpeedConfigError(
                f"checkpoint.on_preempt must be 'save' or 'none', got "
                f"{self.on_preempt!r}"
            )
        if self.async_save and self.engine == "orbax":
            raise DeepSpeedConfigError(
                "checkpoint.async_save requires the native engine (orbax "
                "keeps its own sync path)"
            )


@dataclass
class SparseAttentionConfig:
    """Parity: the "sparse_attention" ds_config section
    (deepspeed/ops/sparse_attention/sparsity_config.py schemas)."""

    mode: str = "none"  # none | dense | fixed | bigbird | bslongformer | variable
    block: int = 128  # TPU tile granularity (reference default 16 is GPU)
    num_local_blocks: int = 4
    num_global_blocks: int = 1
    num_sliding_window_blocks: int = 3
    num_random_blocks: int = 1
    global_block_indices: List[int] = field(default_factory=lambda: [0])

    def validate(self) -> None:
        modes = ("none", "dense", "fixed", "bigbird", "bslongformer", "variable")
        if self.mode not in modes:
            raise DeepSpeedConfigError(
                f"sparse_attention.mode must be one of {modes}, got {self.mode!r}"
            )


@dataclass
class TpuKernelsConfig:
    """TPU-native section: which Pallas kernels replace the XLA defaults.

    Parity: the reference's builder/op toggles (deepspeed/ops/op_builder) —
    where it JIT-compiles CUDA extensions, we flip registered Pallas kernels.
    "auto" resolves to on for TPU backends, off elsewhere (kernels still run
    under interpret=True in tests that force them on).
    """

    flash_attention: Any = AUTO  # auto | True | False
    fused_rmsnorm: Any = False  # covers rmsnorm AND layernorm; opt-in
    flash_block_q: int = 0  # 0 => kernel default
    flash_block_k: int = 0
    flash_block_q_bwd: int = 0  # 0 => inherit the fwd tile (dq/dkv kernels)
    flash_block_k_bwd: int = 0
    # vocab-chunked cross-entropy (ops/cross_entropy.py): the [B,S,V] logit
    # tensor never materializes. auto => on for TPU (tp=1 meshes only; the
    # vocab-parallel dense path handles tp>1)
    fused_ce: Any = AUTO
    ce_chunk: int = 4096

    def resolve(self, on_tpu: bool) -> "TpuKernelsConfig":
        def res(v):
            return on_tpu if v == AUTO else bool(v)

        return TpuKernelsConfig(
            flash_attention=res(self.flash_attention),
            fused_rmsnorm=res(self.fused_rmsnorm),
            flash_block_q=int(self.flash_block_q),
            flash_block_k=int(self.flash_block_k),
            flash_block_q_bwd=int(self.flash_block_q_bwd),
            flash_block_k_bwd=int(self.flash_block_k_bwd),
            fused_ce=res(self.fused_ce),
            ce_chunk=int(self.ce_chunk),
        )


class DeepSpeedConfig:
    """Parsed + validated ds_config. Accepts dict or json path.

    Parity: deepspeed.runtime.config.DeepSpeedConfig — including the
    batch-triangle resolution: train_batch_size =
    micro_batch_per_gpu * gradient_accumulation_steps * dp_world_size.
    """

    def __init__(self, config, dp_world_size: Optional[int] = None):
        if isinstance(config, (str, os.PathLike)):
            with open(config, "r") as f:
                config = json.load(f)
        if not isinstance(config, dict):
            raise DeepSpeedConfigError(f"config must be dict or path, got {type(config)}")
        self.raw: Dict[str, Any] = copy.deepcopy(config)
        d = self.raw

        # ---- batch triangle -------------------------------------------------
        self.train_batch_size = _get(d, "train_batch_size")
        self.train_micro_batch_size_per_gpu = _get(d, "train_micro_batch_size_per_gpu")
        self.gradient_accumulation_steps = _get(d, "gradient_accumulation_steps")
        self._dp_world_size = dp_world_size
        if dp_world_size is not None:
            self._resolve_batch_triangle(dp_world_size)

        self.steps_per_print = int(_get(d, "steps_per_print", 10) or 10)
        self.wall_clock_breakdown = bool(_get(d, "wall_clock_breakdown", False))
        self.dump_state = bool(_get(d, "dump_state", False))
        self.prescale_gradients = bool(_get(d, "prescale_gradients", False))
        self.gradient_predivide_factor = float(_get(d, "gradient_predivide_factor", 1.0) or 1.0)
        self.gradient_clipping = float(_get(d, "gradient_clipping", 0.0) or 0.0)
        self.communication_data_type = _get(d, "communication_data_type")
        self.seed = int(_get(d, "seed", 1234) or 1234)
        self.memory_breakdown = bool(_get(d, "memory_breakdown", False))
        self.zero_allow_untested_optimizer = bool(_get(d, "zero_allow_untested_optimizer", True))

        # ---- sections -------------------------------------------------------
        opt = d.get("optimizer") or {}
        self.optimizer = OptimizerConfig(
            type=str(opt.get("type", "adamw")).lower(), params=dict(opt.get("params", {}))
        )
        sched = d.get("scheduler") or {}
        self.scheduler = SchedulerConfig(
            type=(sched.get("type") or None), params=dict(sched.get("params", {}))
        )
        self.fp16 = _parse_dc(FP16Config, d.get("fp16"))
        self.bf16 = _parse_dc(BF16Config, d.get("bf16"))
        zo = dict(d.get("zero_optimization") or {})
        if "sub_group_prefetch" in zo:  # alias (sub_group_size kin)
            zo.setdefault("offload_double_buffer", zo["sub_group_prefetch"])
        zo["offload_double_buffer"] = _tristate(
            zo.get("offload_double_buffer", False)
        )
        if "zero3_prefetch" in zo:  # alias (the ROADMAP/ISSUE spelling)
            zo.setdefault("stage3_layer_prefetch", zo.pop("zero3_prefetch"))
        zo["stage3_layer_prefetch"] = _tristate(
            zo.get("stage3_layer_prefetch", False)
        )
        zo["offload_optimizer"] = _parse_dc(OffloadConfig, zo.get("offload_optimizer"))
        zo["offload_param"] = _parse_dc(OffloadConfig, zo.get("offload_param"))
        self.zero_config = _parse_dc(ZeroConfig, zo)
        self.activation_checkpointing = _parse_dc(
            ActivationCheckpointingConfig, d.get("activation_checkpointing")
        )
        pipe = dict(d.get("pipeline") or {})
        if "stages" not in pipe and "num_stages" in pipe:
            pipe["stages"] = pipe.pop("num_stages")
        self.pipeline = _parse_dc(PipelineConfig, pipe)
        self.topology = _parse_dc(TopologyConfig, d.get("topology"))
        self.moe = _parse_dc(MoEConfig, d.get("moe"))
        tp = dict(d.get("tensor_parallel") or {})
        if "autotp_size" in tp and "tp_size" not in tp:
            # alias only — the rest of the section (overlap_comm) survives
            tp["tp_size"] = tp.pop("autotp_size")
        oc = tp.get("overlap_comm")
        if isinstance(oc, bool) or oc == AUTO:
            # the spelling zero_optimization.overlap_comm users expect
            # ("auto" rides the same shorthand)
            oc = {"enabled": oc}
        tp["overlap_comm"] = _parse_dc(OverlapCommConfig, oc)
        self.tensor_parallel = _parse_dc(TensorParallelConfig, tp)
        self.serving = _parse_dc(ServingConfig, d.get("serving"))
        sp = d.get("sequence_parallel") or {}
        if "sequence_parallel_size" in d:
            sp.setdefault("sp_size", d["sequence_parallel_size"])
        self.sequence_parallel = _parse_dc(SequenceParallelConfig, sp)
        self.tpu_kernels = _parse_dc(TpuKernelsConfig, d.get("tpu_kernels"))
        self.sparse_attention = _parse_dc(
            SparseAttentionConfig, d.get("sparse_attention")
        )
        self.checkpoint = _parse_dc(CheckpointConfig, d.get("checkpoint"))
        self.steptrace = _parse_dc(SteptraceConfig, d.get("steptrace"))
        self.healthwatch = _parse_dc(HealthwatchConfig, d.get("healthwatch"))
        self.flops_profiler = _parse_dc(FlopsProfilerConfig, d.get("flops_profiler"))
        self.comms_logger = _parse_dc(CommsLoggerConfig, d.get("comms_logger"))
        self.monitor = MonitorConfig(
            tensorboard=dict(d.get("tensorboard") or {}),
            wandb=dict(d.get("wandb") or {}),
            csv_monitor=dict(d.get("csv_monitor") or {}),
        )
        de = dict(d.get("data_efficiency") or {})
        de_types = dict(de.get("data_routing") or {})
        cl = dict((de.get("data_sampling") or {}).get("curriculum_learning") or {})
        self.data_efficiency = DataEfficiencyConfig(
            enabled=bool(de.get("enabled", False)),
            seed=int(de.get("seed", 1234)),
            curriculum_learning=_parse_dc(CurriculumConfig, cl or d.get("curriculum_learning")),
            random_ltd=_parse_dc(RandomLTDConfig, de_types.get("random_ltd")),
        )
        self.compression = _parse_dc(CompressionConfig, d.get("compression_training"))
        self.autotuning = _parse_dc(AutotuningConfig, d.get("autotuning"))
        self.elasticity = _parse_dc(ElasticityConfig, d.get("elasticity"))
        self.progressive_layer_drop = _parse_dc(
            ProgressiveLayerDropConfig, d.get("progressive_layer_drop")
        )

        self._validate()

    # -- helpers --------------------------------------------------------------
    def _resolve_batch_triangle(self, dp_world_size: int) -> None:
        tb, mb, ga = (
            self.train_batch_size,
            self.train_micro_batch_size_per_gpu,
            self.gradient_accumulation_steps,
        )
        if tb is not None and mb is not None and ga is not None:
            if tb != mb * ga * dp_world_size:
                raise DeepSpeedConfigError(
                    f"train_batch_size {tb} != micro_batch {mb} * grad_accum {ga} * dp {dp_world_size}"
                )
        elif tb is not None and mb is not None:
            if tb % (mb * dp_world_size) != 0:
                raise DeepSpeedConfigError(
                    f"train_batch_size {tb} not divisible by micro_batch {mb} * dp {dp_world_size}"
                )
            ga = tb // (mb * dp_world_size)
        elif tb is not None and ga is not None:
            if tb % (ga * dp_world_size) != 0:
                raise DeepSpeedConfigError(
                    f"train_batch_size {tb} not divisible by grad_accum {ga} * dp {dp_world_size}"
                )
            mb = tb // (ga * dp_world_size)
        elif mb is not None:
            ga = ga or 1
            tb = mb * ga * dp_world_size
        elif tb is not None:
            ga = 1
            if tb % dp_world_size != 0:
                raise DeepSpeedConfigError(
                    f"train_batch_size {tb} not divisible by dp world size {dp_world_size}"
                )
            mb = tb // dp_world_size
        else:
            tb, mb, ga = dp_world_size, 1, 1
        self.train_batch_size, self.train_micro_batch_size_per_gpu = int(tb), int(mb)
        self.gradient_accumulation_steps = int(ga)

    def resolve_batch_sizes(self, dp_world_size: int) -> None:
        self._dp_world_size = dp_world_size
        self._resolve_batch_triangle(dp_world_size)

    def _validate(self) -> None:
        if self.fp16.enabled and self.bf16.enabled:
            raise DeepSpeedConfigError("fp16 and bf16 cannot both be enabled")
        self.zero_config.validate()
        if self.gradient_clipping < 0:
            raise DeepSpeedConfigError("gradient_clipping must be >= 0")
        if self.pipeline.stages < 1:
            raise DeepSpeedConfigError("pipeline.stages must be >= 1")
        if self.pipeline.pipe_schedule not in ("1f1b", "gpipe"):
            raise DeepSpeedConfigError(
                "pipeline.pipe_schedule must be 1f1b or gpipe, got "
                f"{self.pipeline.pipe_schedule!r}"
            )
        if self.zero_config.stage >= 2 and self.pipeline.stages > 1:
            # reference: PipelineEngine asserts ZeRO-2/3 unsupported with pipeline
            raise DeepSpeedConfigError(
                "ZeRO stages 2/3 are incompatible with pipeline parallelism (reference parity)"
            )
        if self.progressive_layer_drop.enabled and self.pipeline.stages > 1:
            raise DeepSpeedConfigError(
                "progressive_layer_drop is not supported with pipeline "
                "parallelism (the stochastic layer gate would have to cross "
                "pp stage boundaries)"
            )
        self.tensor_parallel.overlap_comm.validate()
        self.moe.overlap_a2a.validate()
        self.serving.validate()
        if (
            self.tensor_parallel.overlap_comm.enabled is True
            and self.pipeline.stages > 1
        ):
            # "auto" is exempt: resolve_auto_knobs gates the flip on
            # pp <= 1, so an auto knob can never resolve into this state
            raise DeepSpeedConfigError(
                "tensor_parallel.overlap_comm is not supported with pipeline "
                "parallelism (the decomposed matmul is a full-manual "
                "shard_map and cannot nest inside the pipeline's manual "
                "schedule); the runtime also falls back per call site"
            )
        if self.moe.overlap_a2a.enabled is True and self.pipeline.stages > 1:
            raise DeepSpeedConfigError(
                "moe.overlap_a2a is not supported with pipeline parallelism "
                "(the decomposed all-to-all is a full-manual shard_map and "
                "cannot nest inside the pipeline's manual schedule); the "
                "runtime also falls back per call site"
            )
        if self.data_efficiency.random_ltd.enabled and self.pipeline.stages > 1:
            raise DeepSpeedConfigError(
                "random_ltd is not supported with pipeline parallelism (the "
                "token-subset gather would cross pp stage boundaries)"
            )
        self.activation_checkpointing.validate()
        self.sparse_attention.validate()
        self.topology.validate()
        self.checkpoint.validate()
        self.steptrace.validate()
        self.healthwatch.validate()
        if self.sparse_attention.mode not in ("none", "dense") and (
            self.sequence_parallel.sp_size > 1
        ):
            raise DeepSpeedConfigError(
                "sparse_attention is not supported together with sequence "
                "parallelism (the block layout assumes full-sequence tiles)"
            )
        if self.sparse_attention.mode not in ("none", "dense") and (
            self.data_efficiency.random_ltd.enabled
        ):
            raise DeepSpeedConfigError(
                "sparse_attention is not supported together with random_ltd "
                "(LTD layers attend over gathered token subsets whose length "
                "is not block-aligned with the sparse layout)"
            )
        if self.sequence_parallel.mode not in ("ulysses", "ring"):
            raise DeepSpeedConfigError(
                f"sequence_parallel.mode must be 'ulysses' or 'ring', got "
                f"{self.sequence_parallel.mode!r}"
            )

    # dtype policy ------------------------------------------------------------
    @property
    def compute_dtype(self):
        import jax.numpy as jnp

        if self.bf16.enabled:
            return jnp.bfloat16
        if self.fp16.enabled:
            return jnp.float16
        return jnp.float32

    @property
    def zero_enabled(self) -> bool:
        return self.zero_config.stage > 0

    def to_dict(self) -> Dict[str, Any]:
        return copy.deepcopy(self.raw)


def _parse_dc(cls, section):
    """Build dataclass ``cls`` from dict ``section``, ignoring unknown keys."""
    section = dict(section or {})
    names = {f.name for f in cls.__dataclass_fields__.values()} if hasattr(cls, "__dataclass_fields__") else set()
    known = {}
    for k, v in section.items():
        if k in names:
            known[k] = v
    try:
        return cls(**known)
    except TypeError as e:  # pragma: no cover
        raise DeepSpeedConfigError(f"bad config section for {cls.__name__}: {e}")


# ---------------------------------------------------------------------------
# "auto" knob resolution against the measured per-topology default table
# (analysis/cost/knob_defaults.json, emitted by tools/autoplan.py
# --campaign). ONE resolver for every overlap/wire/spec/paged knob,
# generalizing the point solutions that grew one at a time
# (resolved_grad_wire, kv_cache_dtype-"auto", resolve_moe_a2a_form):
# initialize() and ServingEngine.__init__ call it once, before any
# engine code reads the knobs, so a knob is either a concrete value or
# a deliberate downstream "auto" (wires / kv dtype / serving moe_a2a
# keep their existing late resolution when the table has no fresh row).
#
# Trust model: a table value only applies when (a) the knob is
# applicable to this config (an inapplicable flip silently stays off —
# a dp-only mesh can't use tp overlap no matter what a row says),
# (b) the row's recorded evidence is FRESH — its (predicted, measured)
# pair still sits inside the generation's drift band (drift.check_pair)
# and its recorded jax major.minor matches the running one. Stale rows
# resolve to the conservative off default with a one-time named
# warning, never a crash.
# ---------------------------------------------------------------------------

#: every knob path resolve_auto_knobs() owns (docs/autotuning.md
#: "Campaign mode" documents the schema these dotted paths key into)
AUTO_KNOB_PATHS = (
    "tensor_parallel.overlap_comm",
    "zero_optimization.offload_double_buffer",
    "zero_optimization.stage3_layer_prefetch",
    "zero_optimization.grad_wire",
    "zero_optimization.param_wire",
    "moe.overlap_a2a",
    "serving.spec",
    "serving.paged",
    "serving.moe_a2a",
    "serving.kv_cache_dtype",
)

_AUTO_WARNED: set = set()


def _jax_major_minor() -> Optional[str]:
    try:
        import jax

        return ".".join(str(jax.__version__).split(".")[:2])
    except Exception:  # noqa: BLE001
        return None


def _warn_once(key: str, msg: str) -> None:
    if key in _AUTO_WARNED:
        return
    _AUTO_WARNED.add(key)
    try:
        from .utils.logging import logger

        logger.warning(msg)
    except Exception:  # noqa: BLE001 — never block resolution on logging
        pass


def _fresh_table_value(row, provenance: str, path: str, gen: str):
    """(value, source) for one knob path out of a table row, applying the
    staleness gate; (None, reason) when the row has nothing fresh."""
    from .analysis.cost import drift

    if row is None or path not in (row.get("knobs") or {}):
        return None, "miss"
    value = row["knobs"][path]
    jx = row.get("jax")
    now = _jax_major_minor()
    if jx and now and jx != now:
        _warn_once(
            f"{path}:{provenance}:jax",
            f"auto knob {path}: {provenance} was measured on jax {jx} but "
            f"this is jax {now} — using the conservative off default "
            "(re-run tools/autoplan.py --campaign to refresh the table)",
        )
        return None, f"stale-jax:{provenance}"
    ev = (row.get("evidence") or {}).get(path) or {}
    pred = ev.get("predicted_step_s")
    meas = ev.get("measured_step_s")
    if meas is not None:
        verdict = drift.check_pair(pred, meas, row.get("gen", gen))
        if not verdict["ok"]:
            _warn_once(
                f"{path}:{provenance}:band",
                f"auto knob {path}: {provenance} evidence is outside the "
                f"{verdict['gen']} drift band {verdict['band']} (ratio "
                f"{verdict['ratio']}) — using the conservative off default "
                "(re-run tools/autoplan.py --campaign to refresh the table)",
            )
            return None, f"stale-band:{provenance}"
    return value, provenance


def resolve_auto_knobs(cfg, hardware=None, model_config=None,
                       topology=None, table=None) -> Dict[str, Dict[str, Any]]:
    """Resolve every ``"auto"`` knob on ``cfg`` in place from the measured
    knob-default table; returns (and attaches as ``cfg.auto_resolution``)
    a ``{path: {"value", "source"}}`` report.

    ``cfg`` is a :class:`DeepSpeedConfig` (training + serving knobs) or a
    bare :class:`ServingConfig` (serving knobs only). Explicit values are
    never touched — only knobs spelled ``"auto"`` resolve, and only to a
    table value that is applicable AND fresh (see the module comment);
    everything else lands on the conservative off default. Idempotent:
    a second call is a no-op because nothing is "auto" anymore (except
    the deliberately-deferred wire/kv/moe_a2a autos, whose downstream
    resolution is itself deterministic).
    """
    report: Dict[str, Dict[str, Any]] = {}
    full = isinstance(cfg, DeepSpeedConfig)
    srv = cfg.serving if full else (cfg if isinstance(cfg, ServingConfig)
                                    else None)

    def pending() -> List[str]:
        p = []
        if full:
            if cfg.tensor_parallel.overlap_comm.enabled == AUTO:
                p.append("tensor_parallel.overlap_comm")
            zc = cfg.zero_config
            if zc.offload_double_buffer == AUTO:
                p.append("zero_optimization.offload_double_buffer")
            if zc.stage3_layer_prefetch == AUTO:
                p.append("zero_optimization.stage3_layer_prefetch")
            if zc.grad_wire == AUTO:
                p.append("zero_optimization.grad_wire")
            if zc.param_wire == AUTO:
                p.append("zero_optimization.param_wire")
            if cfg.moe.overlap_a2a.enabled == AUTO:
                p.append("moe.overlap_a2a")
        if srv is not None:
            if srv.spec.enabled == AUTO:
                p.append("serving.spec")
            if srv.paged == AUTO:
                p.append("serving.paged")
            if srv.moe_a2a == AUTO:
                p.append("serving.moe_a2a")
            if srv.kv_cache_dtype == AUTO:
                p.append("serving.kv_cache_dtype")
        return p

    pend = pending()
    if not pend:
        if full:
            cfg.auto_resolution = report
        return report

    from .analysis.cost import hardware as hwmod

    hw = hardware if hardware is not None else hwmod.HardwareModel.detect()
    tab = table if table is not None else hwmod.load_knob_table()
    row, provenance = hwmod.lookup_knob_row(
        tab, hw.gen, hwmod.topology_key(topology), hwmod.model_class(model_config)
    )

    def fresh(path):
        return _fresh_table_value(row, provenance, path, hw.gen)

    def resolve_bool(path: str, applicable: bool, apply) -> None:
        value, source = fresh(path)
        if not applicable:
            apply(False)
            report[path] = {"value": False, "source": "inapplicable"}
            return
        if isinstance(value, bool):
            apply(value)
            report[path] = {"value": value, "source": source}
        else:
            apply(False)
            report[path] = {"value": False, "source": f"off-default:{source}"}

    if full:
        tp_live = int(cfg.tensor_parallel.tp_size) > 1
        pp_live = int(cfg.pipeline.stages) > 1
        zc = cfg.zero_config
        moe = cfg.moe
        if "tensor_parallel.overlap_comm" in pend:
            resolve_bool(
                "tensor_parallel.overlap_comm",
                tp_live and not pp_live,
                lambda v: setattr(cfg.tensor_parallel.overlap_comm,
                                  "enabled", v),
            )
        if "zero_optimization.offload_double_buffer" in pend:
            resolve_bool(
                "zero_optimization.offload_double_buffer",
                bool(zc.offload_optimizer.enabled),
                lambda v: setattr(zc, "offload_double_buffer", v),
            )
        if "zero_optimization.stage3_layer_prefetch" in pend:
            resolve_bool(
                "zero_optimization.stage3_layer_prefetch",
                int(zc.stage) == 3,
                lambda v: setattr(zc, "stage3_layer_prefetch", v),
            )
        if "moe.overlap_a2a" in pend:
            resolve_bool(
                "moe.overlap_a2a",
                bool(moe.enabled) and int(moe.ep_size) > 1 and not pp_live,
                lambda v: setattr(moe.overlap_a2a, "enabled", v),
            )
        # wire codecs: a fresh measured codec wins; otherwise "auto"
        # survives for the legacy resolution (resolved_grad_wire /
        # resolved_param_wire — zero_quantized_* spellings), which is
        # already deterministic and fp32-conservative
        for path, attr, applicable in (
            ("zero_optimization.grad_wire", "grad_wire", int(zc.stage) >= 1),
            ("zero_optimization.param_wire", "param_wire",
             int(zc.stage) == 3),
        ):
            if path not in pend:
                continue
            value, source = fresh(path)
            if (applicable and isinstance(value, str)
                    and value in ZeroConfig._WIRE_CODECS and value != AUTO):
                setattr(zc, attr, value)
                report[path] = {"value": value, "source": source}
            else:
                report[path] = {
                    "value": getattr(zc, f"resolved_{attr}")(),
                    "source": "legacy-auto" if applicable
                    else "inapplicable",
                }

    if srv is not None:
        if "serving.spec" in pend:
            budget_fits = (int(srv.spec.max_draft) + 1
                           <= int(srv.token_budget))
            resolve_bool(
                "serving.spec",
                budget_fits,
                lambda v: setattr(srv.spec, "enabled", v),
            )
        if "serving.paged" in pend:
            if srv.fleet.enabled and int(srv.fleet.prefill_replicas) > 0:
                # prefill/decode disaggregation REQUIRES the paged arena
                # (the KV handoff is a page-table transfer) — forced on
                # regardless of the table
                srv.paged = True
                report["serving.paged"] = {
                    "value": True, "source": "forced:fleet-disaggregation"
                }
            elif int(srv.host_pages) > 0:
                # KV tiering demotes/promotes PAGES of the block-paged
                # arena; a host tier without a paged pool is meaningless
                # — forced on regardless of the table
                srv.paged = True
                report["serving.paged"] = {
                    "value": True, "source": "forced:kv-tiering"
                }
            else:
                resolve_bool("serving.paged", True,
                             lambda v: setattr(srv, "paged", v))
        if "serving.moe_a2a" in pend:
            value, source = fresh("serving.moe_a2a")
            if value in ("stock", "chunked"):
                srv.moe_a2a = value
                report["serving.moe_a2a"] = {"value": value, "source": source}
            else:
                # the payload-threshold resolution in serving/engine.py
                # (resolve_moe_a2a_form) stays authoritative
                report["serving.moe_a2a"] = {"value": AUTO,
                                             "source": "threshold-auto"}
        if "serving.kv_cache_dtype" in pend:
            value, source = fresh("serving.kv_cache_dtype")
            if value in ("int8", "bf16", "bfloat16"):
                srv.kv_cache_dtype = value
                report["serving.kv_cache_dtype"] = {"value": value,
                                                    "source": source}
            else:
                # engine default (bf16 KV) stays authoritative
                report["serving.kv_cache_dtype"] = {"value": AUTO,
                                                    "source": "engine-auto"}

    if full:
        cfg.auto_resolution = report
    return report
