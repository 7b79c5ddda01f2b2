"""The training engine.

Parity: deepspeed/runtime/engine.py (DeepSpeedEngine) + deepspeed.initialize
(deepspeed/__init__.py). One jitted SPMD train step replaces the reference's
imperative forward/backward/step machinery:

- ZeRO stages are sharding rules (runtime/zero/partition.py); XLA inserts the
  all-gathers/reduce-scatters the reference hand-codes over NCCL.
- Gradient accumulation is a ``lax.scan`` over microbatches.
- fp16 dynamic loss scaling runs inside the step (no host sync); overflow
  skips the update exactly like the reference's optimizer wrapper.
- fp32 master weights live sharded (ZeRO-1+); compute casts to bf16/fp16.
- The reference's engine.forward/backward/step call protocol is emulated on
  top (micro-batch buffer, update applied at the accumulation boundary).
"""

from __future__ import annotations

import os
from contextlib import contextmanager
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import comm
from ..comm.topology import MeshTopology, ParallelDims
from ..config import DeepSpeedConfig
from ..models.sharding import use_topology
from ..profiling.steptrace import Phase
from ..utils.logging import log_dist
from ..utils.timer import SynchronizedWallClockTimer, ThroughputTimer
from ..utils.tree import global_norm, tree_cast
from .dataloader import DeepSpeedDataLoader
from .lr_schedules import build_schedule
from .optimizers import build_optimizer
from .precision import (
    LossScaleState,
    grads_finite,
    init_loss_scale,
    update_loss_scale,
)
from .zero.partition import make_shardings, opt_state_sharding, zero_specs


# model metrics of a step that go to the monitor (``train/<name>``) and onto
# the ``train/device`` span beside the loss: the MTP module's own loss and
# the sigmoid_groups router's load (models/transformer.loss_fn)
_SPAN_METRICS = ("mtp_loss", "moe_rows_held", "moe_rows_max_over_mean")


class TrainState:
    """Params (fp32 master), optax state, loss-scale state, step counter."""

    def __init__(self, params, opt_state, loss_scale, step):
        self.params = params
        self.opt_state = opt_state
        self.loss_scale = loss_scale
        self.step = step

    def astuple(self):
        return (self.params, self.opt_state, self.loss_scale, self.step)


def initialize(
    args=None,
    model=None,
    optimizer=None,
    model_parameters=None,
    training_data=None,
    lr_scheduler=None,
    dist_init_required=None,
    config=None,
    config_params=None,
    mpu=None,
    topology: Optional[MeshTopology] = None,
    rng: Optional[jax.Array] = None,
    abstract_init: bool = False,
):
    """Parity: deepspeed.initialize → (engine, optimizer, dataloader, lr_scheduler).

    ``model`` follows the model protocol (init/loss/partition_specs — see
    models/transformer.TransformerModel). ``optimizer`` may be an optax
    GradientTransformation to override the config-built one. ``mpu``
    (reference: Megatron model-parallel unit) is accepted as an alternate
    spelling of the mesh shape: its get_*_parallel_world_size() methods
    seed ParallelDims when no explicit ``topology`` is given.

    ``abstract_init=True`` builds the engine WITHOUT materializing any
    state: params/optimizer leaves are ShapeDtypeStructs carrying the
    exact shardings training would use. Such an engine cannot step — it
    exists so deepspeed_tpu.analysis (shardlint) can trace and lint the
    step program of arbitrarily large configs in seconds on CPU.
    """
    if config is None:
        config = config_params
    if config is None and args is not None:
        config = getattr(args, "deepspeed_config", None)
    if config is None:
        raise ValueError("initialize() requires config (dict or ds_config.json path)")
    if model is None:
        raise ValueError("initialize() requires model")

    cfg = config if isinstance(config, DeepSpeedConfig) else DeepSpeedConfig(config)

    def _mpu_reported(*names):
        for n in names:
            fn = getattr(mpu, n, None)
            if callable(fn):
                return int(fn())
        return 1

    mpu_consumed = False
    if topology is None and mpu is not None and not comm.is_initialized():
        # mpu protocol: the reference reads tensor/pipeline sizes off the
        # Megatron mpu. mpu overrides the config's tp/pp; the other mesh
        # axes (sp/ep/fsdp) still come from the config exactly like the
        # no-mpu branch below, and a pp the config can't run (no pipeline
        # section → no stage layers → TpuEngine) is an error, not a
        # silently replicated mesh axis.
        _mpu_size = _mpu_reported
        mpu_consumed = True
        mpu_pp = _mpu_size("get_pipe_parallel_world_size",
                           "get_pipeline_model_parallel_world_size")
        if mpu_pp > 1 and cfg.pipeline.stages <= 1:
            raise ValueError(
                f"mpu reports pipeline world size {mpu_pp} but the config "
                "has no pipeline section (pipeline.stages) — the engine "
                "cannot place stage layers it doesn't know about"
            )
        topology = comm.init_distributed(dims=ParallelDims(
            dp=cfg.topology.dcn_dp if cfg.topology.dcn_dp > 1 else 0,
            tp=_mpu_size("get_tensor_model_parallel_world_size",
                         "get_model_parallel_world_size"),
            pp=mpu_pp if mpu_pp > 1 else cfg.pipeline.stages,
            sp=cfg.sequence_parallel.sp_size,
            ep=cfg.moe.ep_size if cfg.moe.enabled else 1,
            fsdp=(cfg.zero_config.zero_hpz_partition_size
                  if cfg.zero_config.zero_hpz_partition_size > 1
                  else (cfg.zero_config.mics_shard_size
                        if cfg.zero_config.mics_shard_size > 0 else 1)),
        ), dcn_axes=cfg.topology.dcn_axes())
    if topology is None:
        if comm.is_initialized():
            topology = comm.get_topology()
        else:
            tp = cfg.tensor_parallel.tp_size
            pp = cfg.pipeline.stages
            sp = cfg.sequence_parallel.sp_size
            ep = cfg.moe.ep_size if cfg.moe.enabled else 1
            fsdp = 1
            if cfg.zero_config.zero_hpz_partition_size > 1:
                fsdp = cfg.zero_config.zero_hpz_partition_size
            elif cfg.zero_config.mics_shard_size > 0:
                fsdp = cfg.zero_config.mics_shard_size
            topology = comm.init_distributed(
                dims=ParallelDims(
                    dp=cfg.topology.dcn_dp if cfg.topology.dcn_dp > 1 else 0,
                    fsdp=fsdp, pp=pp, ep=ep, sp=sp, tp=tp,
                ),
                dcn_axes=cfg.topology.dcn_axes(),
            )
    else:
        comm.set_topology(topology)

    if mpu is not None and not mpu_consumed:
        # mpu arrived too late to shape the mesh (comm already initialized
        # or an explicit topology was passed); a disagreeing mpu must not
        # proceed silently — the caller's Megatron groups and this mesh
        # would split tensors differently
        mpu_tp = _mpu_reported("get_tensor_model_parallel_world_size",
                               "get_model_parallel_world_size")
        mpu_pp = _mpu_reported("get_pipe_parallel_world_size",
                               "get_pipeline_model_parallel_world_size")
        top_tp, top_pp = topology.get_dim("tp"), topology.get_dim("pp")
        # same convention as the consume branch: an mpu size of 1 (incl.
        # absent getters) defers to the config/topology — only a size the
        # mpu actively reports as parallel can conflict
        mismatch = [
            f"{name} {got} != {have}"
            for name, got, have in (("tp", mpu_tp, top_tp),
                                    ("pp", mpu_pp, top_pp))
            if got > 1 and got != have
        ]
        if mismatch:
            raise ValueError(
                f"initialize(mpu=...): mpu reports {', '.join(mismatch)} "
                f"vs the active topology (tp={top_tp}, pp={top_pp}); "
                "initialize comm from the mpu (or pass a matching topology)"
            )
        log_dist(
            "initialize(mpu=...): mesh already initialized; verified mpu "
            f"sizes match (tp={top_tp}, pp={top_pp})"
        )

    cfg.resolve_batch_sizes(topology.data_shard_size)

    # resolve every "auto" overlap/wire/spec/paged knob from the measured
    # knob-default table (config.resolve_auto_knobs) BEFORE any engine
    # code reads them — engines see concrete values only (the
    # deliberately-deferred wire/kv autos keep their downstream
    # resolution when the table has no fresh row)
    from ..config import resolve_auto_knobs

    resolve_auto_knobs(
        cfg, model_config=getattr(model, "config", None), topology=topology
    )

    if cfg.pipeline.stages > 1 or getattr(model, "is_pipeline_module", False):
        from .pipe.engine import PipelineEngine

        engine_cls = PipelineEngine
    else:
        engine_cls = TpuEngine
    engine = engine_cls(
        model=model,
        config=cfg,
        topology=topology,
        optimizer=optimizer,
        model_parameters=model_parameters,
        rng=rng,
        abstract_init=abstract_init,
    )

    dataloader = None
    if training_data is not None:
        dataloader = DeepSpeedDataLoader(
            training_data, cfg.train_batch_size, seed=cfg.seed
        )
    return engine, engine, dataloader, engine.lr_scheduler


class TpuEngine:
    """Parity surface: DeepSpeedEngine (train_batch/eval_batch/forward/
    backward/step/lr/global_steps/save_checkpoint/load_checkpoint)."""

    def __init__(
        self,
        model,
        config: DeepSpeedConfig,
        topology: MeshTopology,
        optimizer=None,
        model_parameters=None,
        rng: Optional[jax.Array] = None,
        abstract_init: bool = False,
    ):
        self.model = model
        self.config = config
        self.topology = topology
        # lint-only shell: state stays ShapeDtypeStructs (see initialize())
        self.abstract = bool(abstract_init)
        self.timers = SynchronizedWallClockTimer()
        # steady-state samples/sec: async dispatch makes per-call host time
        # track device time once the queue fills; the first steps are skipped
        self.tput = ThroughputTimer(batch_size=config.train_batch_size)
        self.global_steps = 0
        self.micro_steps = 0
        self.skipped_steps = 0
        self.training = True
        self._micro_buffer = []
        self._metrics = {}
        self._chain_fns: Dict[Any, Any] = {}
        self.last_chain_metrics = None
        self.monitor = None
        if config.monitor.enabled:
            from ..monitor.monitor import MonitorMaster

            self.monitor = MonitorMaster(config.monitor)
        self.comm_logger = None
        # steptrace's registry (config-gated; docs/observability.md).
        # None: no registry exists and nothing is stored; the span sites
        # still feed the profiler's trace (steptrace.Phase). Abstract
        # (lint) shells never trace.
        self.tracer = None
        self._steptrace_export_path = None
        if config.steptrace.enabled and not self.abstract:
            from ..profiling import steptrace as _steptrace

            self.tracer = _steptrace.configure(
                max_spans=config.steptrace.max_spans
            )
            self._steptrace_export_path = config.steptrace.export_path
        # healthwatch (config-gated; docs/observability.md "healthwatch").
        # None is the zero-overhead path: no ring buffer, no device-scalar
        # taps, no extra spans — constructed below AFTER the analytic
        # streams exist (its comm-exposed goodput bucket prices them).
        self.healthwatch = None
        if config.comms_logger.enabled:
            from ..profiling.comm_logger import CommsLogger

            self.comm_logger = CommsLogger(config.comms_logger,
                                           registry=self.tracer)

        self.fp16_enabled = config.fp16.enabled
        self.compute_dtype = config.compute_dtype
        self.remat_policy = config.activation_checkpointing.policy
        on_tpu = topology.mesh.devices.flat[0].platform == "tpu"
        # ---- TPU kernel selection (reference: op_builder CUDA-extension
        # toggles become Pallas kernel switches). Applied as *scoped*
        # overrides while tracing this engine's steps (_kernel_scope), so
        # engines with different configs in one process don't fight. --------
        tk = config.tpu_kernels.resolve(on_tpu)
        self.tpu_kernels = tk
        self._sparse_impl = None
        if config.sparse_attention.mode != "none":
            # training-time block-sparse attention (reference:
            # SparseSelfAttention driven by the "sparse_attention" section)
            from ..ops.sparse_attention import from_ds_config, make_attention_impl

            if topology.sp_size > 1 and config.sparse_attention.mode != "dense":
                # config validation only sees the config's sp_size; an
                # explicitly passed sp>1 topology must fail here, not apply
                # a chunk-local block layout silently inside the ring path
                from ..config import DeepSpeedConfigError

                raise DeepSpeedConfigError(
                    "sparse_attention is not supported on a sequence-"
                    "parallel topology (the block layout assumes full-"
                    "sequence tiles)"
                )
            sp_cfg = from_ds_config(config.sparse_attention)
            if sp_cfg is not None:
                self._sparse_impl = make_attention_impl(sp_cfg)
        # ---- decomposed TP collective matmul (tensor_parallel.overlap_comm:
        # parallel/tensor_overlap.py). Scoped at trace time like the kernel
        # selectors; the knob defaults off pending an on-chip A/B. ----------
        ov = config.tensor_parallel.overlap_comm
        self.tp_overlap = ov if (ov.enabled and topology.tp_size > 1) else None
        if ov.enabled and topology.tp_size <= 1:
            log_dist(
                "tensor_parallel.overlap_comm: tp_size == 1 on this "
                "topology — nothing to decompose, knob ignored"
            )
        if self.tp_overlap is not None:
            from ..parallel.tensor_overlap import static_widths_divide

            mc = getattr(model, "config", None)
            if mc is not None and not static_widths_divide(
                mc, topology.tp_size
            ):
                log_dist(
                    "tensor_parallel.overlap_comm: a projection width does "
                    f"not divide tp={topology.tp_size} — the rings could "
                    "never engage, so the knob is disabled (the residual "
                    "stream would otherwise pay the (sp, tp) layout for "
                    "nothing)"
                )
                self.tp_overlap = None
        # ---- decomposed MoE all-to-all (moe.overlap_a2a:
        # parallel/a2a_overlap.py). Same trace-time-scope protocol; the
        # knob defaults off pending an on-chip A/B. ----------------------
        mo = config.moe.overlap_a2a
        _model_is_moe = bool(
            getattr(getattr(model, "config", None), "is_moe", False)
        )
        self.moe_a2a = (
            mo if (mo.enabled and topology.ep_size > 1 and _model_is_moe)
            else None
        )
        if mo.enabled and self.moe_a2a is None:
            log_dist(
                "moe.overlap_a2a: "
                + ("ep_size == 1 on this topology"
                   if topology.ep_size <= 1 else "model is not MoE")
                + " — no expert exchange to decompose, knob ignored"
            )
        self.pld = None
        if config.progressive_layer_drop.enabled:
            from .progressive_layer_drop import ProgressiveLayerDrop

            self.pld = ProgressiveLayerDrop(
                theta=config.progressive_layer_drop.theta,
                gamma=config.progressive_layer_drop.gamma,
            )
        self.compression_masks = None
        self._compression_cfg = None
        self._qat = None
        cc = config.compression
        if any(
            (getattr(cc, f) or {}).get("shared_parameters", {}).get("enabled")
            for f in ("weight_quantization", "sparse_pruning", "head_pruning",
                      "row_pruning")
        ):
            self._compression_cfg = cc
        if (cc.layer_reduction or {}).get("enabled"):
            from ..config import DeepSpeedConfigError

            raise DeepSpeedConfigError(
                "compression.layer_reduction changes the model architecture; "
                "apply compression.compress.apply_layer_reduction to the "
                "params (and shrink the model config) before initialize()"
            )
        self.curriculum = None
        if config.data_efficiency.curriculum_learning.enabled:
            from ..data_pipeline.curriculum_scheduler import CurriculumScheduler

            self.curriculum = CurriculumScheduler(
                config.data_efficiency.curriculum_learning
            )
        self.random_ltd = None
        self._ltd_layers = None
        rl = config.data_efficiency.random_ltd
        if rl.enabled:
            # random-LTD (reference: data_pipeline/data_routing) — the
            # scheduler quantizes the kept-token count (one compiled program
            # per distinct value); the layer range must be contiguous because
            # the layer scan is split pre/ltd/post (models/transformer.py)
            from ..data_pipeline.random_ltd import RandomLTDScheduler

            n_layers = getattr(getattr(model, "config", None), "num_layers", 0)
            L = rl.total_layer_num or n_layers
            self.random_ltd = RandomLTDScheduler(rl, total_layers=L)
            ids = sorted(rl.random_ltd_layer_id)
            if ids:
                if ids != list(range(ids[0], ids[-1] + 1)):
                    from ..config import DeepSpeedConfigError

                    raise DeepSpeedConfigError(
                        "random_ltd_layer_id must be a contiguous range on "
                        "TPU (the layer scan is split around it); got "
                        f"{rl.random_ltd_layer_id}"
                    )
                self._ltd_layers = (ids[0], ids[-1] + 1)
            else:
                # explicit layer_num is honored exactly (lo may be 0); the
                # derived default keeps the first layer out of the drop set
                if rl.random_ltd_layer_num:
                    n_ltd = min(rl.random_ltd_layer_num, L)
                    lo = (L - n_ltd) // 2
                else:
                    n_ltd = max(L - 2, 0)
                    lo = max((L - n_ltd) // 2, 1)
                self._ltd_layers = (lo, min(lo + n_ltd, L))
            if self._ltd_layers[0] >= self._ltd_layers[1]:
                self.random_ltd = None
                self._ltd_layers = None
        if topology.sp_size > 1:
            # per-topology, so two engines with different modes don't fight
            topology.sp_mode = config.sequence_parallel.mode

        # ---- schedule + optimizer ------------------------------------------
        self.lr_schedule = build_schedule(
            config.scheduler.type, config.scheduler.params, config.optimizer.lr
        )
        self.lr_scheduler = self.lr_schedule
        self._stacked_grads_axes = None
        opt_name = (config.optimizer.type or "").lower().replace("_", "")
        data_axes_live = tuple(
            a for a in ("dp", "fsdp") if topology.sizes[a] > 1
        )
        if (
            opt_name in ("onebitadam", "onebitlamb")
            and optimizer is None
            and data_axes_live
            and config.zero_config.stage <= 1
            and config.pipeline.stages <= 1
            and not getattr(model, "is_pipeline_module", False)
        ):
            # wire-compressed 1-bit path (reference: compressed_allreduce):
            # the engine hands the optimizer stacked per-member local grads
            # and the momentum crosses the wire bit-packed
            from ..ops.onebit import build_onebit_wire_optimizer

            self._stacked_grads_axes = data_axes_live
            self.optimizer_tx = build_onebit_wire_optimizer(
                opt_name, config.optimizer, self.lr_schedule, topology,
                data_axes_live,
            )
            msg = (
                f"1-bit wire compression active over {data_axes_live} "
                f"(warmup={config.optimizer.params.get('freeze_step', 100)} "
                f"steps, then bit-packed momentum all-reduce)"
            )
            if config.gradient_clipping > 0:
                msg += "; gradient_clipping is not applied in this mode"
            log_dist(msg)
        else:
            if opt_name in ("onebitadam", "onebitlamb") and optimizer is None:
                # make the semantics fork audible (r2 verdict: silent):
                # the numerics-only variant compresses nothing on the wire
                why = (
                    "no >1-size data axis" if not data_axes_live
                    else "ZeRO stage > 1" if config.zero_config.stage > 1
                    else "pipeline parallelism"
                )
                log_dist(
                    f"{config.optimizer.type}: wire compression DISABLED "
                    f"({why}); running the numerics-only variant — momentum "
                    f"is NOT bit-packed on the network"
                )
            self.optimizer_tx = (
                optimizer
                if isinstance(optimizer, optax.GradientTransformation)
                else build_optimizer(config.optimizer, self.lr_schedule)
            )

        # ---- sharding specs -------------------------------------------------
        tp_specs = (
            model.partition_specs(topology)
            if hasattr(model, "partition_specs")
            else None
        )
        self._rng = rng if rng is not None else jax.random.PRNGKey(config.seed)
        params_shape = jax.eval_shape(
            lambda k: model.init(k, dtype=jnp.float32), self._rng
        )
        if tp_specs is None:
            tp_specs = jax.tree.map(lambda x: P(), params_shape)
        # ---- state that is no parameter (a model's ``buffer_mask``: True at
        # a leaf of the tree that takes no gradient, e.g. a router's
        # selection bias). The optimizer never sees such a leaf (no moments,
        # no weight decay); after each update the model's ``update_buffers``
        # moves it from the step's own metrics. It stays a leaf of
        # ``state.params``, so a checkpoint saves and restores it.
        mask_of = getattr(model, "buffer_mask", None)
        self._buffer_mask = mask_of(params_shape) if mask_of else None
        if self._buffer_mask is not None:
            self.optimizer_tx = optax.masked(
                self.optimizer_tx,
                jax.tree.map(lambda b: not b, self._buffer_mask))
        self.param_specs, self.grad_specs, self.opt_leaf_specs = zero_specs(
            params_shape, tp_specs, topology, config.zero_config
        )
        self._tp_specs = tp_specs
        self._params_shape = params_shape
        # ---- ZeRO-3 one-layer-ahead parameter prefetch
        # (zero_optimization.stage3_layer_prefetch: runtime/zero/prefetch.py).
        # The puts tree is one layer slice's gathered (tp-only) shardings;
        # persistence-threshold leaves come back as identity puts. --------
        # ---- wire codecs (comm/wires.py, docs/wires.md): the grad
        # reduce-scatter / param all-gather wire formats. Legacy
        # zero_quantized_* bools resolve to int8 codecs. ----------------
        zc = config.zero_config
        self._grad_wire = zc.resolved_grad_wire()
        self._param_wire = zc.resolved_param_wire()
        self._hier_wire = bool(zc.hierarchical_wire)
        if self._hier_wire and not (
            topology.sizes["dp"] > 1 and topology.sizes["fsdp"] > 1
        ):
            log_dist(
                "zero_optimization.hierarchical_wire: needs a live "
                f"factored dp x fsdp mesh (this one is {topology}); the "
                "2-hop forms have no groups to split — knob ignored, "
                "single-hop wires run"
            )
            self._hier_wire = False
        self._z3_prefetch_puts = None
        self._z3_prefetch_shapes = None
        if config.zero_config.stage3_layer_prefetch:
            if config.zero_config.stage != 3:
                log_dist(
                    "zero_optimization.stage3_layer_prefetch: stage "
                    f"{config.zero_config.stage} has no parameter gathers "
                    "to prefetch, knob ignored"
                )
            else:
                from .zero.prefetch import build_layer_puts

                self._z3_prefetch_puts = build_layer_puts(
                    params_shape, tp_specs, self.param_specs, topology,
                    param_wire=self._param_wire,
                    grad_wire=self._grad_wire,
                    hierarchical=self._hier_wire,
                )
                if self._z3_prefetch_puts is None:
                    log_dist(
                        "stage3_layer_prefetch: no data-sharded stacked "
                        "'layers' leaf on this mesh (everything persistent "
                        "or replicated) — nothing to prefetch, knob ignored"
                    )
                else:
                    self._z3_prefetch_shapes = (params_shape, tp_specs)
        self._qgather = None
        if zc.stage == 3 and (
            self._param_wire != "fp32"
            or self._grad_wire != "fp32"
            or self._hier_wire
        ):
            # ZeRO++ qwZ/qgZ/hgZ: explicit wire-codec gather replaces
            # XLA's implicit one; its custom backward is the codec grad
            # reduce-scatter (runtime/zero/quantized.py). When the layer
            # prefetch owns the stacked group's gathers, exclude it here
            # — its WirePut callables run the same per-leaf program
            # inside the scan (runtime/zero/prefetch.py).
            from .zero.quantized import make_quantized_gather

            self._qgather = make_quantized_gather(
                topology,
                self.param_specs,
                tp_specs,
                params_shape,
                param_wire=self._param_wire,
                grad_wire=self._grad_wire,
                hierarchical=self._hier_wire,
                exclude_key=(
                    "layers" if self._z3_prefetch_puts is not None else None
                ),
            )
        # stage-1/2 grad wire (qgZ at the dp reduction itself): the grad
        # computation runs per data-shard inside a shard_map and the
        # cross-member reduction becomes the explicit codec
        # reduce-scatter (stage 3's grad wire rides the gather's custom
        # backward instead — see the _qgather block above)
        self._wired_grad_axes = None
        # the wired reduction also engages for fp32 + hierarchical_wire:
        # the 2-hop topology win (only 1/n_fsdp of the bytes cross the
        # slow dp links) exists without any quantization
        _wire_wanted = self._grad_wire != "fp32" or self._hier_wire
        if _wire_wanted and zc.stage in (1, 2) and (
            config.pipeline.stages > 1
            or getattr(model, "is_pipeline_module", False)
            or self._stacked_grads_axes is not None
        ):
            log_dist(
                "zero_optimization.grad_wire: the wired reduction cannot "
                "run under pipeline parallelism / the 1-bit wire path; "
                "the full-width reduction runs"
            )
        elif _wire_wanted and zc.stage in (1, 2):
            if not data_axes_live:
                log_dist(
                    "zero_optimization.grad_wire: no >1-size data axis on "
                    "this mesh — nothing to compress, the full-width "
                    "reduction runs"
                )
            else:
                self._wired_grad_axes = data_axes_live
                log_dist(
                    f"grad wire active: {self._grad_wire} reduce-scatter "
                    f"over {data_axes_live}"
                    + (" (hierarchical 2-hop)" if self._hier_wire else "")
                )
        # ---- offload (reference: zero offload_optimizer / offload_param +
        # swap_tensor/partitioned_optimizer_swapper) --------------------------
        off_opt = zc.offload_optimizer
        off_par = zc.offload_param
        self._nvme_swapper = None
        self._checkpoint_guard = None  # lazy (runtime/ckpt CheckpointGuard)
        self._opt_memory_kind = None
        if off_opt.device == "cpu":
            # XLA's CPU SPMD partitioner can't annotate memory kinds, so the
            # host-memory path is TPU-only; CPU test meshes run unoffloaded
            self._opt_memory_kind = "pinned_host" if on_tpu else None
        elif off_opt.device == "nvme":
            from .swap_tensor import TensorSwapper

            self._nvme_swapper = TensorSwapper(
                os.path.join(off_opt.nvme_path, "zero_opt_swap"),
                # host buffer reuse is only safe when device_put really
                # copies (TPU HBM); the CPU client can zero-copy alias
                reuse_buffers=on_tpu,
                buffer_count=off_opt.buffer_count,
            )
        self._param_memory_kind = (
            "pinned_host" if (off_par.enabled and on_tpu) else None
        )
        # CPU-offloaded optimizer state steps per-layer (sub_group_size
        # semantics — see runtime/bucketed_opt.py): one layer's m/v/master
        # streams through HBM per scan tick instead of the whole tree's
        # f32 update temps at once (the 1.4B config OOM'd otherwise)
        from .bucketed_opt import BucketedOptimizer, bucketed_applicable

        bucketable = (
            off_opt.device == "cpu"
            and not self._stacked_grads_axes
            # fp16's overflow skip selects over the WHOLE old/new
            # state, which would force full-width compute on the
            # pinned-host layer leaves the scan keeps resident there;
            # bf16/fp32 (the TPU-native paths) never take that select
            and not self.fp16_enabled
            and bucketed_applicable(params_shape)
        )
        # NOTE: a stacked leaf sharding its leading (layer) dim no longer
        # disables bucketing (the PR-1 gate): _apply_update re-puts the
        # scanned groups to their resting shardings after the layer scan,
        # restoring the carry-in == carry-out closure the slice hooks
        # alone cannot (shardlint rule R2 checks the invariant statically)
        self._bucketed_opt = (
            BucketedOptimizer(
                self.optimizer_tx,
                double_buffer=zc.offload_double_buffer,
            )
            if bucketable
            else None
        )
        if off_opt.device == "cpu" and self.fp16_enabled:
            log_dist(
                "offload_optimizer + fp16: per-layer bucketed stepping is "
                "disabled (the overflow-skip select needs the full state "
                "on device); prefer bf16 on TPU for large offloaded models"
            )
        if off_par.enabled and not on_tpu:
            log_dist(
                "offload_param: pinned_host memory kinds need the TPU "
                "backend; this CPU mesh runs without param offload"
            )
        if off_par.device == "nvme":
            log_dist(
                "offload_param.device=nvme: params stage in pinned host "
                "memory (disk swap applies to optimizer state via "
                "offload_optimizer.device=nvme)"
            )
        self.param_shardings = make_shardings(
            self.param_specs, topology, self._param_memory_kind
        )
        self._param_dev_shardings = (
            make_shardings(self.param_specs, topology)
            if self._param_memory_kind
            else None
        )
        self.grad_shardings = make_shardings(self.grad_specs, topology)

        # ---- materialize state (zero.Init parity: params born sharded) -----
        with use_topology(topology):
            if self.abstract:
                # shardlint tracing shell: leaves are ShapeDtypeStructs
                # carrying the exact shardings the real engine would
                # materialize — nothing executes on any device
                if self._compression_cfg is not None:
                    raise NotImplementedError(
                        "abstract_init does not support compression_training "
                        "(mask computation needs real params)"
                    )
                if model_parameters is not None:
                    raise NotImplementedError(
                        "abstract_init ignores model_parameters; pass none"
                    )
                params = jax.tree.map(
                    lambda a, s: jax.ShapeDtypeStruct(
                        a.shape, a.dtype, sharding=s
                    ),
                    params_shape,
                    self.param_shardings,
                )
            elif model_parameters is not None:
                params = jax.device_put(
                    tree_cast(model_parameters, jnp.float32), self.param_shardings
                )
            else:
                params = jax.jit(
                    lambda k: model.init(k, dtype=jnp.float32),
                    out_shardings=self.param_shardings,
                )(self._rng)
            if self._compression_cfg is not None:
                # Engine hook (reference: init_compression on module wrap):
                # pruning masks computed once here and re-imposed after every
                # optimizer step; weight QAT runs as STE fake-quant inside
                # each forward (_loss_for), masters stay full precision.
                from ..compression.compress import (
                    init_compression,
                    quantization_settings,
                )

                params, masks = init_compression(
                    params,
                    self._compression_cfg,
                    getattr(model, "config", None),
                    qat_in_forward=True,
                )
                params = jax.device_put(params, self.param_shardings)
                self.compression_masks = masks or None
                self._qat = quantization_settings(self._compression_cfg)
            if self._stacked_grads_axes:
                from ..ops.onebit import onebit_wire_state_shardings

                opt_out_shardings = onebit_wire_state_shardings(
                    jax.eval_shape(self.optimizer_tx.init, params_shape),
                    topology,
                    self._stacked_grads_axes,
                    self._opt_memory_kind,
                )
            elif self._bucketed_opt is not None:
                bshape = jax.eval_shape(self._bucketed_opt.init, params_shape)
                rest_specs = {
                    k: v for k, v in self.opt_leaf_specs.items()
                    if k != self._bucketed_opt.key
                }
                opt_out_shardings = {
                    "rest": opt_state_sharding(
                        self.optimizer_tx, bshape["rest"], rest_specs,
                        topology, self._opt_memory_kind,
                    ),
                    # vmapped per-layer state: param-shaped leaves are
                    # stacked like the params, so the stacked specs apply
                    "layers": opt_state_sharding(
                        self.optimizer_tx, bshape["layers"],
                        self.opt_leaf_specs[self._bucketed_opt.key],
                        topology, self._opt_memory_kind,
                    ),
                }
            else:
                opt_out_shardings = opt_state_sharding(
                    self.optimizer_tx,
                    jax.eval_shape(self.optimizer_tx.init, params_shape),
                    self.opt_leaf_specs,
                    topology,
                    self._opt_memory_kind,
                )
            init_fn = (
                self._bucketed_opt.init
                if self._bucketed_opt is not None
                else self.optimizer_tx.init
            )
            if self.abstract:
                opt_state = jax.tree.map(
                    lambda a, s: jax.ShapeDtypeStruct(
                        a.shape, a.dtype, sharding=s
                    ),
                    jax.eval_shape(init_fn, params),
                    opt_out_shardings,
                )
            else:
                opt_state = jax.jit(init_fn, out_shardings=opt_out_shardings)(
                    params
                )
        self.opt_shardings = jax.tree.map(lambda x: x.sharding, opt_state)
        self._opt_dev_shardings = (
            jax.tree.map(
                lambda s: NamedSharding(s.mesh, s.spec), self.opt_shardings
            )
            if self._opt_memory_kind
            else None
        )
        self._opt_treedef = jax.tree_util.tree_structure(opt_state)
        loss_scale = init_loss_scale(config.fp16, self.fp16_enabled)
        step0 = jnp.zeros((), jnp.int32)
        if not self.abstract:
            # commit the scalar state to its replicated resting sharding
            # NOW: the step's out_shardings put the new scale/step there,
            # so uncommitted host scalars here would make the SECOND
            # train_batch retrace the whole step program (fresh vs
            # donated-state shardings) — one wasted full compile per
            # engine, and the dryrun/serving "one steady trace" gates
            # would always read 2
            rep = NamedSharding(topology.mesh, P())
            loss_scale, step0 = jax.device_put((loss_scale, step0), rep)
        self.state = TrainState(params, opt_state, loss_scale, step0)
        self.offload_stream = self._compute_offload_stream()
        self._tp_overlap_streams = {}
        self.tp_overlap_stream = self._compute_tp_overlap_stream()
        self._moe_a2a_streams = {}
        self.moe_a2a_stream = self._compute_moe_a2a_stream()
        self.z3_prefetch_stream = self._compute_z3_prefetch_stream()
        self.grad_wire_stream = self._compute_grad_wire_stream()
        self.param_wire_stream = self._compute_param_wire_stream()
        if config.healthwatch.enabled and not self.abstract:
            self._build_healthwatch(config.healthwatch)
        if self._nvme_swapper is not None and not self.abstract:
            # optimizer state lives on disk between steps (reference:
            # partitioned_optimizer_swapper); swapped in around each update
            self._swap_out_opt()

        self._replicated = NamedSharding(topology.mesh, P())
        self._data_iters: Dict[int, Any] = {}
        # retrace counter (the serving engine's step_traces discipline):
        # a trace-time side effect fires once per XLA compile of the
        # jitted step programs — healthwatch's recompile watchdog and the
        # goodput compile bucket read the per-step delta
        self.step_traces = 0
        self._last_seq: Optional[int] = None
        self._mfu_cache: Dict[str, Any] = {}
        self._compile_step_fns()
        n_params = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(params_shape))
        log_dist(
            f"TpuEngine: {n_params/1e6:.1f}M params, zero_stage={config.zero_config.stage}, "
            f"dtype={self.compute_dtype.__name__}, topology={topology}, "
            f"micro_batch={config.train_micro_batch_size_per_gpu}, "
            f"accum={config.gradient_accumulation_steps}"
        )
        if config.memory_breakdown:
            # reference: memory_breakdown prints see_memory_usage around the
            # step; here at init + every steps_per_print (train_batch)
            from ..utils.memory import print_zero_memory_estimates, see_memory_usage

            print_zero_memory_estimates(
                model, topology, stages=(config.zero_config.stage,),
                compute_dtype_bytes=jnp.dtype(self.compute_dtype).itemsize,
                offload_optimizer=config.zero_config.offload_optimizer.enabled,
                offload_params=config.zero_config.offload_param.enabled,
            )
            see_memory_usage("after engine init")

    # --------------------------------------------------- offload accounting
    def _compute_offload_stream(self, assume_offload: bool = False):
        """Static per-step host↔HBM DMA byte counts for the bucketed
        offload stream (None when no pinned-host leaves stream). Every
        pinned-host stacked leaf is read in and written back once per
        optimizer step, so the counts come straight from the resting
        shardings; ``slot_bytes`` is one layer slice (the scan's in-flight
        unit — double buffering keeps ``slots`` of them resident).

        ``assume_offload=True`` prices the stream the *config declares*
        even where the mesh has no memory kinds (the CPU lint mesh):
        every stacked leaf the TPU run would pin to host counts, so the
        planner and rule R8 can budget the 1.5B offload leg without a
        chip. Per-device figures come from each leaf's shard shape."""
        if self._bucketed_opt is None or self.state is None:
            return None
        kind = self._opt_memory_kind or self._param_memory_kind
        zc = self.config.zero_config
        opt_declared = zc.offload_optimizer.device in ("cpu", "nvme")
        par_declared = zc.offload_param.enabled
        if kind is None and not (
            assume_offload and (opt_declared or par_declared)
        ):
            return None  # CPU mesh: no memory kinds, nothing streams
        key = self._bucketed_opt.key

        def stream_bytes(tree):
            total = dev = 0
            for leaf in jax.tree_util.tree_leaves(tree):
                streams_leaf = (
                    getattr(leaf.sharding, "memory_kind", None) == kind
                    if kind is not None
                    else True  # assumed: the whole stacked group would pin
                )
                if not streams_leaf:
                    continue
                nbytes = leaf.size * leaf.dtype.itemsize
                total += nbytes
                try:
                    shard = leaf.sharding.shard_shape(leaf.shape)
                    dev += int(np.prod(shard)) * leaf.dtype.itemsize
                except Exception:  # noqa: BLE001 — no sharding evidence
                    dev += nbytes
            return total, dev

        state_b, state_dev = (
            stream_bytes(self.state.opt_state[key])
            if self._opt_memory_kind or (kind is None and opt_declared)
            else (0, 0)
        )
        param_b, param_dev = (
            stream_bytes(self.state.params[key])
            if self._param_memory_kind or (kind is None and par_declared)
            else (0, 0)
        )
        total = state_b + param_b
        per_dev = state_dev + param_dev
        if total == 0:
            return None
        n_layers = jax.tree_util.tree_leaves(self.state.params[key])[0].shape[0]
        slots = 2 if self._bucketed_opt.double_buffer else 1
        return {
            "bytes_in": total,
            "bytes_out": total,
            "per_device_bytes_in": per_dev,
            "per_device_bytes_out": per_dev,
            "slot_bytes": total // max(n_layers, 1),
            "slots": slots,
            "layers": int(n_layers),
            "double_buffer": self._bucketed_opt.double_buffer,
            "assumed": kind is None,
        }

    def analytic_streams(self, seq=None, include_potential: bool = False):
        """The engine's declared analytic streams, normalized for the
        cost planner / rule R8 and the comms logger (ONE schema for every
        hidden-stream subsystem): name → ``{"kind", "bytes_per_step",
        "per_device_bytes_per_step", "overlapped", ...}``.

        ``include_potential=True`` also prices streams the config
        declares but this mesh cannot pin (the CPU lint mesh has no
        memory kinds) — what the planner budgets; the comms logger only
        ever records the actual (default) set.

        Every mesh stream carries ``axes``: the mesh axes its collective
        runs over, so per-link pricing (hybrid DCN meshes, rule R13) can
        tell which bytes cross the slow fabric."""
        streams = {}
        data_axes = tuple(
            a for a in ("dp", "fsdp") if self.topology.sizes[a] > 1
        )
        off = self.offload_stream
        if off is None and include_potential:
            off = self._compute_offload_stream(assume_offload=True)
        if off:
            total = off["bytes_in"] + off["bytes_out"]
            per_dev = (
                off.get("per_device_bytes_in", off["bytes_in"])
                + off.get("per_device_bytes_out", off["bytes_out"])
            )
            streams["offload"] = {
                "kind": "offload",
                "bytes_per_step": total,
                "per_device_bytes_per_step": per_dev,
                "per_device_inflight_bytes": off["slots"] * off["slot_bytes"]
                // max(self.topology.world_size, 1),
                "overlapped": bool(off["double_buffer"]),
                **off,
            }
        if self.tp_overlap is not None:
            ring = self._tp_overlap_stream_for(seq)
            if ring:
                streams["tp_ring"] = {
                    **ring,
                    "kind": "ici",
                    "axes": ("tp",),
                    # ring_wire_bytes_per_step is already per device
                    "bytes_per_step": ring["bytes_per_step"],
                    "per_device_bytes_per_step": ring["bytes_per_step"],
                    "overlapped": True,
                }
        # MoE dispatch/combine traffic is declared whether or not the
        # overlap knob is on (ISSUE-10 fix: the serial GSPMD path moves
        # the same logical bytes, R8/shardplan must see them either way);
        # overlapped only when the decomposed rings actually ENGAGE —
        # the knob being on with undividable shapes falls back to the
        # serial path at trace time (moe_a2a_applicable), and claiming
        # overlap for it would let R8 hide wire that runs serialized
        # (same honesty rule as ring_wire_bytes_per_step's predicates)
        a2a = self._moe_a2a_stream_for(seq)
        if a2a:
            streams["moe_a2a"] = {
                **a2a,
                "kind": "ici",
                "axes": ("ep",),
                # moe_a2a_bytes_per_step is already per device
                "bytes_per_step": a2a["bytes_per_step"],
                "per_device_bytes_per_step": a2a["bytes_per_step"],
                "overlapped": bool(
                    self.moe_a2a is not None and a2a.get("ring_engages")
                ),
            }
        z3 = self.z3_prefetch_stream
        if z3:
            streams["zero3_prefetch"] = {
                **z3,
                "kind": "ici",
                "axes": data_axes,
                "bytes_per_step": z3["bytes_per_step"],
                "per_device_bytes_per_step": z3["bytes_per_step"],
                "overlapped": True,
            }
        # wire-codec streams (comm/wires.py): the grad reduce-scatter and
        # stage-3 param gathers in codec bytes. Declared NOT overlapped —
        # they are serial collectives (the win is fewer bytes, not hidden
        # ones), except where the prefetch already owns (and overlaps)
        # the stacked layers' share via zero3_prefetch above. shardplan
        # prices them; R8 sees the codec-shrunk zero3_prefetch stream.
        gw = self.grad_wire_stream
        if gw:
            streams["grad_wire"] = {
                **gw,
                "kind": "ici",
                "axes": data_axes,
                "bytes_per_step": gw["bytes_per_step"],
                "per_device_bytes_per_step": gw["bytes_per_step"],
                "overlapped": False,
            }
        pw = self.param_wire_stream
        if pw:
            streams["param_wire"] = {
                **pw,
                "kind": "ici",
                "axes": data_axes,
                "bytes_per_step": pw["bytes_per_step"],
                "per_device_bytes_per_step": pw["bytes_per_step"],
                "overlapped": False,
            }
        # periodic checkpoint snapshots (runtime/ckpt): device→host bytes
        # amortized over the declared save cadence, so R8/shardplan price
        # the async pipeline against the roofline window like any other
        # offload stream. goodput_bucket marks its synchronous cost as
        # already charged to the `checkpoint` bucket — healthwatch must
        # not carve it out of compute spans a second time.
        ckpt_cfg = getattr(self.config, "checkpoint", None)
        interval = int(getattr(ckpt_cfg, "save_interval_steps", 0) or 0)
        if interval > 0:
            try:
                snap_total, snap_dev = self._ckpt_snapshot_bytes()
            except Exception:  # noqa: BLE001 — abstract/odd state trees
                snap_total = snap_dev = 0.0
            if snap_total > 0:
                streams["ckpt_snapshot"] = {
                    "kind": "offload",
                    "bytes_per_step": snap_total / interval,
                    "per_device_bytes_per_step": snap_dev / interval,
                    "overlapped": bool(
                        getattr(ckpt_cfg, "async_save", False)
                    ),
                    "goodput_bucket": "checkpoint",
                    "interval_steps": interval,
                    "snapshot_bytes": snap_total,
                    "per_device_snapshot_bytes": snap_dev,
                }
        return streams

    def _ckpt_snapshot_bytes(self):
        """(global, per-device) bytes of one checkpoint snapshot — the
        params + optimizer-state + loss-scale trees the ckpt writer
        serializes. Per-device uses each leaf's sharding dimspec (the
        same analysis/cost pricing reshard's overlap reads report)."""
        from ..analysis.cost.walk import device_bytes, dimspec_from_sharding

        state = self.state
        if state is None:
            return 0.0, 0.0
        world = max(self.topology.world_size, 1)
        total = per_dev = 0.0
        for tree, sh in (
            (state.params, self.param_shardings),
            (state.opt_state, self.opt_shardings),
            (state.loss_scale, None),
        ):
            leaves = jax.tree_util.tree_leaves(tree)
            shardings = (
                jax.tree_util.tree_leaves(sh)
                if sh is not None
                else [None] * len(leaves)
            )
            for i, leaf in enumerate(leaves):
                shape = tuple(getattr(leaf, "shape", ()) or ())
                dtype = np.dtype(getattr(leaf, "dtype", np.float32))
                n = float(dtype.itemsize)
                for d in shape:
                    n *= int(d)
                total += n
                s = shardings[i] if i < len(shardings) else None
                if s is not None and shape:
                    try:
                        per_dev += device_bytes(
                            shape, dtype,
                            dimspec_from_sharding(s, len(shape), {}),
                        )
                    except Exception:  # noqa: BLE001 — duck-typed shardings
                        per_dev += n / world
                else:
                    per_dev += n
        return total, per_dev

    def parity_pairs(self):
        """The declared-bitwise form pairs of this engine's train step
        (analysis/parity.py — TP ring vs XLA reference when
        overlap_comm serves, moe_a2a chunked vs stock, wire codec vs
        full-width). Each pair re-traces the step abstractly from a
        knob-flipped twin of this config; ``tools/paritycheck.py``
        proves them all statically."""
        from ..analysis.parity import config_parity_pairs

        return config_parity_pairs(self.config.raw, self.model)

    def _record_offload_stream(self, steps: int = 1, batch=None):
        if self.comm_logger is None:
            return
        # ring bytes scale with the ACTUAL batch sequence length (and
        # vanish when it stops dividing the ring) — derive it from the
        # prepared batch rather than trusting model max_seq_len
        seq = None
        if isinstance(batch, dict):
            ids = batch.get("input_ids")
            if ids is not None and getattr(ids, "shape", None):
                seq = int(ids.shape[-1])
        self.comm_logger.record_streams(
            self.analytic_streams(seq=seq), steps=steps
        )

    def _tp_overlap_stream_for(self, seq):
        """The analytic ring stream at one sequence length (cached)."""
        if seq is None:
            return self.tp_overlap_stream
        if seq not in self._tp_overlap_streams:
            self._tp_overlap_streams[seq] = self._compute_tp_overlap_stream(
                seq=seq
            )
        return self._tp_overlap_streams[seq]

    def _compute_tp_overlap_stream(self, seq=None):
        """Static per-step decomposed-ring wire bytes (None when overlap is
        off, shapes keep the rings from engaging, or the model isn't
        transformer-shaped). Reported to the comms logger per step — the
        trace-time hook bus under-counts scanned layers (a scan body
        traces once), so the analytic figure is the honest per-step
        number. ``seq`` defaults to the model's max_seq_len (the static
        estimate); recording passes the actual batch length."""
        if self.tp_overlap is None:
            return None
        from ..parallel.tensor_overlap import ring_wire_bytes_per_step

        model_cfg = getattr(self.model, "config", None)
        if model_cfg is None:
            return None
        return ring_wire_bytes_per_step(
            model_cfg,
            self.topology,
            self.tp_overlap,
            batch=self.config.train_micro_batch_size_per_gpu
            * self.topology.data_shard_size,
            seq=seq if seq is not None
            else getattr(model_cfg, "max_seq_len", 0),
            itemsize=jnp.dtype(self.compute_dtype).itemsize,
            accum_steps=self.config.gradient_accumulation_steps,
        )

    def _moe_a2a_stream_for(self, seq):
        """The analytic MoE exchange stream at one sequence length
        (cached, the _tp_overlap_stream_for discipline)."""
        if seq is None:
            return self.moe_a2a_stream
        if seq not in self._moe_a2a_streams:
            self._moe_a2a_streams[seq] = self._compute_moe_a2a_stream(
                seq=seq
            )
        return self._moe_a2a_streams[seq]

    def _compute_moe_a2a_stream(self, seq=None):
        """Static per-step MoE dispatch/combine exchange bytes (None for
        non-MoE models or ep == 1). Declared for BOTH the serial and the
        decomposed path — capacity scales with the batch, so recording
        passes the actual sequence length like the TP ring stream."""
        model_cfg = getattr(self.model, "config", None)
        if model_cfg is None or self.topology.ep_size <= 1:
            return None
        from ..parallel.a2a_overlap import (
            moe_a2a_applicable,
            moe_a2a_bytes_per_step,
        )

        batch = (self.config.train_micro_batch_size_per_gpu
                 * self.topology.data_shard_size)
        seq = seq if seq is not None else getattr(
            model_cfg, "max_seq_len", 0
        )
        stream = moe_a2a_bytes_per_step(
            model_cfg,
            self.topology,
            batch=batch,
            seq=seq,
            itemsize=jnp.dtype(self.compute_dtype).itemsize,
            accum_steps=self.config.gradient_accumulation_steps,
        )
        if stream is not None:
            # whether the decomposed rings would ENGAGE at these shapes —
            # the moe_layer dispatch predicate evaluated statically
            stream["ring_engages"] = moe_a2a_applicable(
                self.topology, B=batch, S=seq,
                E=int(getattr(model_cfg, "num_experts", 0) or 0),
                F=int(getattr(model_cfg, "ffn", 0) or 0),
            )
        return stream

    def _compute_z3_prefetch_stream(self):
        """Static per-step all-gather wire for the prefetched layer scan
        (None when the knob/mesh leaves nothing to prefetch). Shapes, not
        batch, set this stream — no per-seq cache needed. Wire codecs
        shrink it: with ``param_wire`` / ``grad_wire`` set the prefetched
        gather moves codec bytes and R8 prices the smaller stream."""
        if self._z3_prefetch_puts is None:
            return None
        from .zero.prefetch import prefetch_wire_bytes_per_step

        params_shape, tp_specs = self._z3_prefetch_shapes
        return prefetch_wire_bytes_per_step(
            params_shape,
            tp_specs,
            self.param_specs,
            self.topology,
            itemsize=jnp.dtype(self.compute_dtype).itemsize,
            accum_steps=self.config.gradient_accumulation_steps,
            remat=bool(self.remat_policy and self.remat_policy != "none"),
            param_wire=self._param_wire,
            grad_wire=self._grad_wire,
            hierarchical=self._hier_wire,
        )

    # --------------------------------------------------- wire accounting
    def _wire_leaf_iter(self, specs_a, specs_b, exclude_key=None):
        """Yield (shape, dim, axes, n) for every leaf whose ``specs_a``
        entry carries mesh axes its ``specs_b`` entry doesn't — the
        leaves a wire collective actually touches. ``exclude_key``
        masks a top-level subtree (the stacked ``layers`` group when the
        prefetch stream already prices it)."""
        from .zero.quantized import gather_dim_and_axes

        if exclude_key is not None and isinstance(specs_a, dict) and (
            exclude_key in specs_a
        ):
            specs_a = {**specs_a, exclude_key: specs_b[exclude_key]}
        is_spec = lambda s: isinstance(s, P)
        shapes = jax.tree_util.tree_leaves(self._params_shape)
        a_flat = jax.tree_util.tree_leaves(specs_a, is_leaf=is_spec)
        b_flat = jax.tree_util.tree_leaves(specs_b, is_leaf=is_spec)
        for sh, sa, sb in zip(shapes, a_flat, b_flat):
            hit = gather_dim_and_axes(sa, sb, len(sh.shape))
            if hit is None:
                continue
            dim, axes = hit
            n = 1
            for a in axes:
                n *= self.topology.sizes[a]
            if n > 1:
                yield tuple(int(d) for d in sh.shape), dim, axes, n

    def _leaf_hier(self, axes):
        """(n_outer, n_inner) when this leaf's wire runs the 2-hop form —
        the SAME wires.hier_axes predicate the executed collective uses
        (runtime/zero/quantized.make_leaf_gather), so the priced stream
        and the traced program can never disagree on eligibility."""
        from ..comm import wires

        if not self._hier_wire:
            return None
        hier = wires.hier_axes(self.topology, axes)
        if hier is None:
            return None
        return hier[1], hier[3]

    def _compute_grad_wire_stream(self):
        """Static per-device wire bytes of the codec gradient
        reduce-scatter (qgZ/hgZ; None when no codec wire engages).
        Stage 1/2: the explicit wired reduction, once per optimizer step
        (after the accumulation scan) — stage-1 leaves add the f32
        gather-back half of the decomposed all-reduce, non-dividing
        leaves stay full-width psum and are reported as such. Stage 3:
        the gather backward's reduce-scatter, once per microbatch;
        stacked layers under the prefetch are priced by the
        zero3_prefetch stream instead (never double-counted)."""
        from ..comm import wires

        codec = self._grad_wire
        if codec == "fp32" and not self._hier_wire:
            return None
        inter = intra = fullwidth = 0.0
        hops = 1
        if self._wired_grad_axes:
            plan, _ = self._wired_grad_plan()
            shapes = jax.tree_util.tree_leaves(self._params_shape)
            axes = self._wired_grad_axes
            n = 1
            for a in axes:
                n *= self.topology.sizes[a]
            hier = self._leaf_hier(axes)
            for sh, (kind, dim) in zip(shapes, plan):
                shape = tuple(int(d) for d in sh.shape)
                if kind == "psum":
                    nb = 1
                    for d in shape:
                        nb *= d
                    fullwidth += 2.0 * nb * 4 * (n - 1) / n
                    continue
                if hier is not None:
                    n_o, n_i = hier
                    hops = 2
                    leaf_inter, leaf_intra = wires.hier_rs_nbytes(
                        shape, n_o, n_i, codec, 4, dim=dim
                    )
                    inter += leaf_inter
                    intra += leaf_intra
                else:
                    inter += wires.rs_wire_nbytes(shape, n, codec, 4,
                                                  dim=dim)
                if kind == "rs_ag":
                    fullwidth += wires.rs_wire_nbytes(shape, n, "fp32", 4,
                                                      dim=dim)
        elif self._qgather is not None:
            # stage 3: _qgather exists iff a codec or the 2-hop form
            # engages (the same disjunction the early return tested)
            accum = max(self.config.gradient_accumulation_steps, 1)
            exclude = (
                "layers" if self._z3_prefetch_puts is not None else None
            )
            for shape, dim, axes, n in self._wire_leaf_iter(
                self.param_specs, self._tp_specs, exclude
            ):
                hier = self._leaf_hier(axes)
                if hier is not None:
                    n_o, n_i = hier
                    hops = 2
                    leaf_inter, leaf_intra = wires.hier_rs_nbytes(
                        shape, n_o, n_i, codec, 4, dim=dim
                    )
                    inter += accum * leaf_inter
                    intra += accum * leaf_intra
                else:
                    inter += accum * wires.rs_wire_nbytes(
                        shape, n, codec, 4, dim=dim
                    )
        total = inter + intra + fullwidth
        if total <= 0:
            return None
        return {
            "codec": codec,
            "bytes_per_step": int(total),
            "inter_bytes_per_step": int(inter),
            "intra_bytes_per_step": int(intra),
            "fullwidth_bytes_per_step": int(fullwidth),
            "hierarchical": hops == 2,
        }

    def _compute_param_wire_stream(self):
        """Static per-device wire bytes of the codec stage-3 parameter
        all-gathers (qwZ; None when no codec gather engages). One gather
        per microbatch forward, plus the remat re-gather; stacked layers
        under the prefetch are priced by the zero3_prefetch stream."""
        from ..comm import wires

        codec = self._param_wire
        if self._qgather is None or (codec == "fp32"
                                     and not self._hier_wire):
            return None
        accum = max(self.config.gradient_accumulation_steps, 1)
        remat = bool(self.remat_policy and self.remat_policy != "none")
        passes = accum * (2 if remat else 1)
        inter = intra = 0.0
        hops = 1
        exclude = "layers" if self._z3_prefetch_puts is not None else None
        for shape, dim, axes, n in self._wire_leaf_iter(
            self.param_specs, self._tp_specs, exclude
        ):
            hier = self._leaf_hier(axes)
            if hier is not None:
                n_o, n_i = hier
                hops = 2
                leaf_inter, leaf_intra = wires.hier_ag_nbytes(
                    shape, n_o, n_i, codec, 4, dim=dim
                )
                inter += passes * leaf_inter
                intra += passes * leaf_intra
            else:
                shard = list(shape)
                shard[dim] //= n
                inter += passes * wires.ag_wire_nbytes(
                    shard, n, codec, 4, dim=dim
                )
        total = inter + intra
        if total <= 0:
            return None
        return {
            "codec": codec,
            "bytes_per_step": int(total),
            "inter_bytes_per_step": int(inter),
            "intra_bytes_per_step": int(intra),
            "hierarchical": hops == 2,
            "passes": passes,
        }

    # ------------------------------------------------------------------ step
    def _device_params(self, params):
        """Memory staging: copy offloaded (pinned_host) params to device."""
        if self._param_memory_kind:
            params = jax.tree.map(
                jax.device_put, params, self._param_dev_shardings
            )
        return params

    @staticmethod
    def _put_except(tree, shardings, key):
        """device_put every entry of ``tree`` except ``key`` (the bucketed
        stacked-layers group, which streams per-slice in the update scan
        and must keep its resting placement)."""
        return {
            **jax.tree.map(
                jax.device_put,
                {k: v for k, v in tree.items() if k != key},
                {k: v for k, v in shardings.items() if k != key},
            ),
            key: tree[key],
        }

    def _bucketed_slice_put(self, shardings_tree):
        """(to_device, to_host) placement hooks for one layer-slice of an
        offloaded stacked tree (see BucketedOptimizer.step). The slice
        shardings are the stacked leaves' with the leading (layer) spec
        entry dropped; None on meshes without memory kinds (CPU tests run
        the same scan, just without the DMA pinning)."""
        kind = self._opt_memory_kind or self._param_memory_kind
        if kind is None:
            return None
        mesh = self.topology.mesh
        stacked = shardings_tree[self._bucketed_opt.key]

        def drop_lead(ns, memory_kind=None):
            spec = tuple(ns.spec)
            spec = spec[1:] if spec else ()
            kwargs = {"memory_kind": memory_kind} if memory_kind else {}
            return NamedSharding(mesh, P(*spec), **kwargs)

        dev = jax.tree.map(drop_lead, stacked)
        # writeback respects each leaf's OWN final placement: the big
        # param-shaped leaves (m/v/masters) return to pinned host, but
        # small non-param leaves (e.g. adam's count) stay on device — a
        # host-space s32 lane-update is also unsupported by the compiler
        hst = jax.tree.map(
            lambda ns: drop_lead(
                ns, kind if getattr(ns, "memory_kind", None) == kind else None
            ),
            stacked,
        )
        return (
            lambda t: jax.device_put(t, dev),
            lambda t: jax.device_put(t, hst),
        )

    def _effective_params(self, params):
        """Differentiable staging — must run *inside* the differentiated
        function so the ZeRO++ gather's custom VJP (gradient reduce-scatter)
        and the QAT straight-through estimator shape the backward pass."""
        if self._qgather is not None:
            params = self._qgather(params)
        if self._qat is not None:
            from ..compression.compress import ste_fake_quant

            params = ste_fake_quant(params, *self._qat)
        return params

    def _kernel_scope(self):
        """Trace-time kernel selection for this engine's tpu_kernels config
        (scoped: no process-global mutation)."""
        from contextlib import ExitStack

        from ..ops.attention import attention_impl
        from ..ops.normalization import pallas_rmsnorm_scope
        from ..ops.pallas.flash_attention import block_sizes_scope

        tk = self.tpu_kernels
        stack = ExitStack()
        stack.enter_context(
            attention_impl(
                self._sparse_impl
                if self._sparse_impl is not None
                else ("flash" if tk.flash_attention else "xla")
            )
        )
        stack.enter_context(pallas_rmsnorm_scope(tk.fused_rmsnorm))
        stack.enter_context(
            block_sizes_scope(tk.flash_block_q, tk.flash_block_k,
                              tk.flash_block_q_bwd, tk.flash_block_k_bwd)
        )
        from ..ops.cross_entropy import fused_ce_scope

        stack.enter_context(fused_ce_scope(tk.fused_ce, tk.ce_chunk))
        from ..parallel.tensor_overlap import overlap_scope

        stack.enter_context(overlap_scope(self.tp_overlap))
        from ..parallel.a2a_overlap import a2a_scope

        stack.enter_context(a2a_scope(self.moe_a2a))
        from .zero.prefetch import prefetch_scope

        stack.enter_context(prefetch_scope(self._z3_prefetch_puts))
        return stack

    def _loss_for(self, params, mb, key, scale, pld_keep=None, ltd_keep=None):
        params = self._effective_params(params)
        kw = {}
        if pld_keep is not None:
            kw["pld_keep"] = pld_keep
        if ltd_keep is not None and self._ltd_layers is not None:
            kw["ltd_keep"] = ltd_keep
            kw["ltd_layers"] = self._ltd_layers
        with self._kernel_scope():
            loss, metrics = self.model.loss(
                params,
                mb,
                dtype=self.compute_dtype,
                train=True,
                rng=key,
                remat_policy=self.remat_policy,
                **kw,
            )
        return loss * scale, (loss, metrics)

    def _pld_keep(self, step):
        """[L] per-layer keep probs when progressive layer drop is on."""
        if self.pld is None:
            return None
        from .progressive_layer_drop import layer_keep_probs

        return layer_keep_probs(
            self.pld.get_theta(step), self.model.config.num_layers
        )

    def _compute_grads(self, params, batch, rng, scale, step=None, ltd_keep=None):
        """(grads fp32 mean-over-microbatches, mean loss, model metrics).
        ``batch`` has a leading grad-accum dim. Overridden by PipelineEngine
        (the pipeline schedule consumes all microbatches in one pass).

        Model metrics (lm_loss, moe_aux_loss, tokens) ride through so the
        engine can log them (reference: MoE aux loss in the step log);
        scalars are microbatch means, token counts sum."""
        accum = self.config.gradient_accumulation_steps
        grad_fn = jax.value_and_grad(self._loss_for, has_aux=True)
        pld_keep = self._pld_keep(step)
        if accum == 1:
            # fast path: no scan, no zeros-init accumulator HBM traffic
            key = jax.random.fold_in(rng, 0)
            (_, (loss, m)), grads = grad_fn(
                params, jax.tree.map(lambda x: x[0], batch), key, scale,
                pld_keep, ltd_keep,
            )
            inv = 1.0 / scale
            grads = jax.tree.map(lambda g: g.astype(jnp.float32) * inv, grads)
            return grads, loss, m

        zero_grads = jax.tree.map(
            lambda x: jnp.zeros(x.shape, jnp.float32), params
        )

        def accum_body(carry, xs):
            g_acc, loss_acc, m_acc = carry
            mb, key = xs
            (_, (loss, m)), grads = grad_fn(
                params, mb, key, scale, pld_keep, ltd_keep
            )
            g_acc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32), g_acc, grads)
            m_acc = jax.tree.map(lambda a, v: a + v, m_acc, m)
            return (g_acc, loss_acc + loss, m_acc), None

        keys = jax.random.split(rng, accum)
        # zero scan-carry derived from the model's actual metric tree (shape
        # eval only — no compute), so custom models with their own metric
        # structure accumulate fine
        m_shape = jax.eval_shape(
            lambda p, mb, k: self._loss_for(p, mb, k, scale, pld_keep, ltd_keep),
            params, jax.tree.map(lambda x: x[0], batch), keys[0],
        )[1][1]
        zero_m = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), m_shape)
        (grads, loss_sum, m_sum), _ = jax.lax.scan(
            accum_body,
            (zero_grads, jnp.zeros((), jnp.float32), zero_m),
            (batch, keys),
        )
        inv = 1.0 / (accum * scale)
        grads = jax.tree.map(lambda g: g * inv, grads)
        if isinstance(m_sum, dict):
            # counts ("tokens") stay sums; everything else reports the mean
            mmetrics = {
                k: (v if k == "tokens" else v / accum) for k, v in m_sum.items()
            }
        else:
            mmetrics = jax.tree.map(lambda v: v / accum, m_sum)
        return grads, loss_sum / accum, mmetrics

    def _compute_grads_stacked(self, params, batch, rng, scale, step,
                               ltd_keep=None):
        """Per-dp-member local grads stacked on a new leading axis [n, ...]
        (sharded over the data axes) — NO cross-member reduction. Feeds the
        wire-compressed 1-bit optimizers, which own the (compressed)
        reduction (ops/onebit.py build_onebit_wire_optimizer)."""
        topo = self.topology
        axes = self._stacked_grads_axes
        ax_entry = axes if len(axes) > 1 else axes[0]
        accum = self.config.gradient_accumulation_steps
        grad_fn = jax.value_and_grad(self._loss_for, has_aux=True)
        pld = self._pld_keep(step)
        has_pld = pld is not None

        def local_fn(params, batch, key, scale, pld_keep):
            pk = pld_keep if has_pld else None
            if accum == 1:
                (_, (loss, _m)), grads = grad_fn(
                    params,
                    jax.tree.map(lambda x: x[0], batch),
                    jax.random.fold_in(key, 0),
                    scale,
                    pk,
                    ltd_keep,
                )
                inv = 1.0 / scale
                grads = jax.tree.map(
                    lambda g: g.astype(jnp.float32) * inv, grads
                )
            else:
                zero_grads = jax.tree.map(
                    lambda x: jnp.zeros(x.shape, jnp.float32), params
                )

                def accum_body(carry, xs):
                    g_acc, loss_acc = carry
                    mb, k = xs
                    (_, (loss, _m)), grads = grad_fn(
                        params, mb, k, scale, pk, ltd_keep
                    )
                    g_acc = jax.tree.map(
                        lambda a, g: a + g.astype(jnp.float32), g_acc, grads
                    )
                    return (g_acc, loss_acc + loss), None

                keys = jax.random.split(key, accum)
                (grads, loss_sum), _ = jax.lax.scan(
                    accum_body,
                    (zero_grads, jnp.zeros((), jnp.float32)),
                    (batch, keys),
                )
                inv = 1.0 / (accum * scale)
                grads = jax.tree.map(lambda g: g * inv, grads)
                loss = loss_sum / accum
            loss = jax.lax.pmean(loss, axes)
            return jax.tree.map(lambda g: g[None], grads), loss

        run = jax.shard_map(
            local_fn,
            mesh=topo.mesh,
            in_specs=(P(), P(None, ax_entry), P(), P(), P()),
            out_specs=(P(ax_entry), P()),
            axis_names=set(axes),
            check_vma=False,
        )
        return run(
            params,
            batch,
            rng,
            scale,
            pld if has_pld else jnp.zeros((), jnp.float32),
        )

    def _wired_grad_plan(self):
        """Per-leaf reduction plan for the stage-1/2 grad wire, aligned
        with the flattened param tree: ``("rs", dim)`` — the leaf's grad
        spec carries the data axes (stage 2: reduce-scatter straight
        into its resting layout); ``("rs_ag", dim)`` — replicated-grad
        leaf with a dividable dim (stage 1: the decomposed all-reduce —
        codec reduce-scatter + full-width f32 gather of the reduced
        shards, the qgZ split of an all-reduce); ``("psum", None)`` —
        nothing divides, full-width psum (honest: no wire saving there).
        Second return: the shard_map out_specs tree (manual data axes
        only — tp sharding rides the automatic axes)."""
        from .zero.partition import add_data_axes
        from .zero.quantized import gather_dim_and_axes

        axes = self._wired_grad_axes
        is_spec = lambda s: isinstance(s, P)
        shapes_flat, treedef = jax.tree_util.tree_flatten(self._params_shape)
        gspecs = jax.tree_util.tree_leaves(self.grad_specs, is_leaf=is_spec)
        tspecs = jax.tree_util.tree_leaves(self._tp_specs, is_leaf=is_spec)
        plan, out_flat = [], []
        for sh, gs, ts in zip(shapes_flat, gspecs, tspecs):
            ndim = len(sh.shape)
            hit = gather_dim_and_axes(gs, ts, ndim)
            if hit is not None and set(hit[1]) == set(axes):
                dim = hit[0]
                plan.append(("rs", dim))
                entries = list(gs) + [None] * (ndim - len(gs))
                proj = []
                for e in entries:
                    es = e if isinstance(e, tuple) else ((e,) if e else ())
                    kept = tuple(a for a in es if a in axes)
                    proj.append(
                        kept if len(kept) > 1
                        else (kept[0] if kept else None)
                    )
                out_flat.append(P(*proj))
                continue
            cand = add_data_axes(ts, sh.shape, self.topology, axes)
            hit2 = gather_dim_and_axes(cand, ts, ndim)
            plan.append(
                ("rs_ag", hit2[0]) if hit2 is not None else ("psum", None)
            )
            out_flat.append(P())
        return plan, jax.tree_util.tree_unflatten(treedef, out_flat)

    def _compute_grads_wired(self, params, batch, rng, scale, step,
                             ltd_keep=None):
        """(grads fp32 in their resting layout, mean loss) with the
        cross-member gradient reduction run as the explicit wire-codec
        reduce-scatter (qgZ): member-local grads compute inside a
        shard_map over the data axes, each leaf's blocks quantize ONCE,
        the accumulate runs after dequant in f32 (master precision), and
        the f32 mean lands in the leaf's grad_specs layout. Like the
        1-bit wire path, model metrics don't ride (loss only)."""
        from ..comm import wires

        topo = self.topology
        axes = self._wired_grad_axes
        ax_entry = axes if len(axes) > 1 else axes[0]
        accum = self.config.gradient_accumulation_steps
        grad_fn = jax.value_and_grad(self._loss_for, has_aux=True)
        pld = self._pld_keep(step)
        has_pld = pld is not None
        n_members = 1
        for a in axes:
            n_members *= topo.sizes[a]
        hier = wires.hier_axes(topo, axes) if self._hier_wire else None
        plan, grads_out_specs = self._wired_grad_plan()
        codec = self._grad_wire
        inv_members = 1.0 / float(n_members)

        def reduce_leaf(g, kind, dim):
            if kind == "psum":
                return lax.psum(g, axes) * inv_members
            if hier is not None:
                o, n_o, i_ax, n_i = hier
                red = wires.rs_wire_hier_local(
                    g, o, i_ax, n_o, n_i, codec, dim=dim,
                    dtype=jnp.float32,
                )
            else:
                red = wires.rs_wire_local(
                    g, ax_entry, n_members, codec, dim=dim,
                    dtype=jnp.float32,
                )
            red = red * inv_members
            if kind == "rs_ag":
                red = jnp.moveaxis(
                    lax.all_gather(
                        jnp.moveaxis(red, dim, 0), axes, axis=0, tiled=True
                    ),
                    0, dim,
                )
            return red

        def local_fn(params, batch, key, scale, pld_keep):
            pk = pld_keep if has_pld else None
            if accum == 1:
                (_, (loss, _m)), grads = grad_fn(
                    params,
                    jax.tree.map(lambda x: x[0], batch),
                    jax.random.fold_in(key, 0),
                    scale,
                    pk,
                    ltd_keep,
                )
                inv = 1.0 / scale
                grads = jax.tree.map(
                    lambda g: g.astype(jnp.float32) * inv, grads
                )
            else:
                zero_grads = jax.tree.map(
                    lambda x: jnp.zeros(x.shape, jnp.float32), params
                )

                def accum_body(carry, xs):
                    g_acc, loss_acc = carry
                    mb, k = xs
                    (_, (loss, _m)), grads = grad_fn(
                        params, mb, k, scale, pk, ltd_keep
                    )
                    g_acc = jax.tree.map(
                        lambda a, g: a + g.astype(jnp.float32), g_acc, grads
                    )
                    return (g_acc, loss_acc + loss), None

                keys = jax.random.split(key, accum)
                (grads, loss_sum), _ = jax.lax.scan(
                    accum_body,
                    (zero_grads, jnp.zeros((), jnp.float32)),
                    (batch, keys),
                )
                inv = 1.0 / (accum * scale)
                grads = jax.tree.map(lambda g: g * inv, grads)
                loss = loss_sum / accum
            leaves = jax.tree_util.tree_structure(params).flatten_up_to(
                grads
            )
            reduced = [
                reduce_leaf(g, kind, dim)
                for g, (kind, dim) in zip(leaves, plan)
            ]
            grads = jax.tree_util.tree_structure(params).unflatten(reduced)
            return grads, jax.lax.pmean(loss, axes)

        run = jax.shard_map(
            local_fn,
            mesh=topo.mesh,
            in_specs=(P(), P(None, ax_entry), P(), P(), P()),
            out_specs=(grads_out_specs, P()),
            axis_names=set(axes),
            check_vma=False,
        )
        return run(
            params,
            batch,
            rng,
            scale,
            pld if has_pld else jnp.zeros((), jnp.float32),
        )

    def _grads_and_loss(self, params, loss_scale, step, batch, rng,
                        ltd_keep=None):
        """The fwd+bwd half of the step: (grads fp32, loss). Compiled
        standalone for the NVMe-offload path so disk swap-in of the optimizer
        state overlaps with this program's device time."""
        cfg = self.config
        params = self._device_params(params)
        scale = loss_scale.scale if self.fp16_enabled else jnp.ones((), jnp.float32)
        if self._stacked_grads_axes:
            grads, loss = self._compute_grads_stacked(
                params, batch, rng, scale, step, ltd_keep
            )
            mmetrics = {}  # 1-bit wire path: loss only (local stacked grads)
        elif self._wired_grad_axes:
            grads, loss = self._compute_grads_wired(
                params, batch, rng, scale, step, ltd_keep
            )
            mmetrics = {}  # wire path: loss only (like the 1-bit path)
        else:
            grads, loss, mmetrics = self._compute_grads(
                params, batch, rng, scale, step, ltd_keep
            )

        # ZeRO>=2: materialize grads sharded (psum → reduce-scatter)
        if cfg.zero_config.stage >= 2 and self.topology.world_size > 1:
            grads = jax.tree.map(
                lambda g, s: jax.lax.with_sharding_constraint(g, s),
                grads,
                self.grad_shardings,
            )
        return grads, loss, mmetrics

    def _apply_update(self, params, opt_state, loss_scale, step, grads, loss,
                      mmetrics=None):
        """The optimizer half of the step (overflow skip, clip, update)."""
        cfg = self.config
        # offloaded state: explicit copies host→device for compute; the step's
        # out_shardings put the new state back in pinned host memory, so XLA
        # schedules the DMA both ways around the math
        if self._bucketed_opt is not None and self._param_memory_kind:
            # host-resident LAYER masters stream per layer inside the
            # bucketed scan (a whole-tree copy here would defeat it); the
            # non-layer leaves update as one group and need device copies
            params = self._put_except(
                params, self._param_dev_shardings, self._bucketed_opt.key
            )
        else:
            params = self._device_params(params)
        if self._opt_memory_kind:
            if self._bucketed_opt is not None:
                opt_state = {
                    "rest": jax.tree.map(
                        jax.device_put,
                        opt_state["rest"],
                        self._opt_dev_shardings["rest"],
                    ),
                    # layer state stays pinned_host; the scan's state_put
                    # hooks move one layer per tick
                    "layers": opt_state["layers"],
                }
            else:
                opt_state = jax.tree.map(
                    jax.device_put, opt_state, self._opt_dev_shardings
                )
        overflow = (
            ~grads_finite(grads) if self.fp16_enabled else jnp.asarray(False)
        )
        if self._stacked_grads_axes:
            # stacked locals: report sqrt(Σ_i ||g_i||²/n) ≈ mean-grad norm;
            # clipping is not applied (reference 1-bit limitation)
            n_members = 1
            for a in self._stacked_grads_axes:
                n_members *= self.topology.sizes[a]
            gnorm = global_norm(grads) / jnp.sqrt(float(n_members))
        else:
            gnorm = global_norm(grads)
            if cfg.gradient_clipping > 0:
                factor = jnp.minimum(
                    1.0, cfg.gradient_clipping / (gnorm + 1e-6)
                )
                grads = jax.tree.map(lambda g: g * factor, grads)

        if self._bucketed_opt is not None:
            new_params, new_opt = self._bucketed_opt.step(
                grads,
                opt_state,
                params,
                state_put=self._bucketed_slice_put(self.opt_shardings),
                param_put=(
                    self._bucketed_slice_put(self.param_shardings)
                    if self._param_memory_kind
                    else None
                ),
            )
        else:
            updates, new_opt = self.optimizer_tx.update(
                grads, opt_state, params
            )
            new_params = optax.apply_updates(params, updates)
        mmetrics = mmetrics or {}
        if self._buffer_mask is not None:
            # buffers move by the model's own rule, from this step's
            # metrics; whatever the masked optimizer passed through is void
            moved = self.model.update_buffers(params, mmetrics)
            new_params = jax.tree.map(
                lambda b, new, buf: buf if b else new,
                self._buffer_mask, new_params, moved)
        # a model metric that is no scalar fed the update and is not reported
        mmetrics = {k: v for k, v in mmetrics.items() if jnp.ndim(v) == 0}

        if self.fp16_enabled:
            # overflow → keep old state (skip step); bf16/fp32 never overflow
            # this way, so skip the full-state select (HBM traffic)
            def sel(new, old):
                return jax.tree.map(lambda a, b: jnp.where(overflow, b, a), new, old)

            new_params = sel(new_params, params)
            new_opt = sel(new_opt, opt_state)
        if self.compression_masks:
            # re-impose pruning masks the optimizer update just violated
            # (reference: masks enforced in every compressed forward)
            from ..compression.compress import redundancy_clean

            new_params = redundancy_clean(new_params, self.compression_masks)
        if self._bucketed_opt is not None:
            # the step must be memory-space-closed (train_batch_chain scans
            # it: carry in == carry out): the rest-group state/params were
            # device_put up top, so return them to their resting placement
            key = self._bucketed_opt.key
            if self._opt_memory_kind:
                new_opt = self._put_except(
                    new_opt, self.opt_shardings, "layers"
                )
            if self._param_memory_kind:
                new_params = self._put_except(
                    new_params, self.param_shardings, key
                )
            # the stacked groups come back with whatever sharding the layer
            # scan stacked (the slice hooks drop the leading spec entry, so
            # a dim-0 partition — L as the largest dp-divisible dim — would
            # be lost); re-put them to their resting shardings so the carry
            # closure holds for EVERY spec shape. A no-op re-put compiles
            # away; this replaced the PR-1 "disable bucketing" gate
            # (shardlint R2 proves the closure statically).
            new_params = {
                **new_params,
                key: jax.tree.map(
                    jax.device_put, new_params[key], self.param_shardings[key]
                ),
            }
            new_opt = {
                **new_opt,
                "layers": jax.tree.map(
                    jax.device_put, new_opt["layers"],
                    self.opt_shardings["layers"],
                ),
            }
        new_scale = update_loss_scale(loss_scale, overflow, cfg.fp16, self.fp16_enabled)
        # skipped steps don't advance the schedule (reference scheduler parity)
        new_step = step + jnp.where(overflow, 0, 1).astype(step.dtype)
        metrics = {
            "loss": loss,
            "grad_norm": gnorm,
            "overflow": overflow,
            "loss_scale": new_scale.scale,
            "lr": self.lr_schedule(step),
            **mmetrics,  # lm_loss / moe_aux_loss / tokens
        }
        return new_params, new_opt, new_scale, new_step, metrics

    def _train_step(self, params, opt_state, loss_scale, step, batch, rng,
                    ltd_keep=None):
        grads, loss, mmetrics = self._grads_and_loss(
            params, loss_scale, step, batch, rng, ltd_keep
        )
        return self._apply_update(
            params, opt_state, loss_scale, step, grads, loss, mmetrics
        )

    def _eval_step(self, params, batch, rng, train: bool = False):
        # eval sees the same weights the train step optimizes
        params = self._effective_params(self._device_params(params))
        with self._kernel_scope():
            loss, metrics = self.model.loss(
                params, batch, dtype=self.compute_dtype, train=train, rng=rng,
            )
        return loss, metrics

    def _compile_step_fns(self):
        state_shardings = (
            self.param_shardings,
            self.opt_shardings,
            jax.tree.map(lambda _: self._replicated, self.state.loss_scale),
            self._replicated,
        )
        self._state_shardings = state_shardings

        def _counted(fn):
            # trace-time side effect: fires once per XLA compile, so the
            # per-step delta of self.step_traces is the retrace count
            # (healthwatch recompile watchdog + goodput compile bucket).
            # wraps() keeps the compiled program's name (HLO dumps and
            # profiler traces must not all read "jit_wrapped").
            import functools

            @functools.wraps(fn)
            def wrapped(*args):
                self.step_traces += 1
                return fn(*args)

            return wrapped

        self._jit_train = jax.jit(
            _counted(self._train_step),
            donate_argnums=(0, 1, 2, 3),
            static_argnums=(6,),  # random-LTD kept-token count
            out_shardings=(*state_shardings, None),
        )
        self._jit_eval = jax.jit(self._eval_step, static_argnums=(3,))
        if self._nvme_swapper is not None:
            # NVMe overlap (reference: partitioned_optimizer_swapper's
            # async_swapper): the step splits into a grads program and an
            # update program; train_batch dispatches grads, then does the
            # disk swap-in while the device computes, then dispatches the
            # update. Swap-out writes overlap the next step.
            self._jit_grads = jax.jit(
                _counted(self._grads_and_loss), static_argnums=(5,)
            )
            self._jit_update = jax.jit(
                _counted(self._apply_update),
                donate_argnums=(0, 1, 2, 3),
                out_shardings=(*state_shardings, None),
            )

    # ------------------------------------------------------------- batching
    def _batch_sharding(self, accum_leading: bool):
        spec = self.topology.batch_spec()
        entries = ((None,) if accum_leading else ()) + tuple(spec)
        return NamedSharding(self.topology.mesh, P(*entries))

    def _prepare_batch(self, batch) -> Dict[str, jax.Array]:
        """Global batch dict → [accum, per_step_batch, ...] device arrays.

        Fields that already arrived staged (device arrays in the prepared
        [accum, micro, ...] layout with the right sharding — see
        :meth:`prepare_batch`) pass through untouched: no np.asarray
        readback, no re-upload: a steady-state loop re-feeding one staged
        batch skips the upload before each dispatch entirely."""
        accum = self.config.gradient_accumulation_steps
        expect = self.config.train_batch_size
        out = {}
        sharding = self._batch_sharding(accum_leading=True)
        for k, v in batch.items():
            if (
                isinstance(v, jax.Array)
                and v.ndim >= 2
                and v.shape[0] == accum
                and v.shape[1] == expect // accum
                and v.sharding == sharding
            ):
                out[k] = v  # already staged
                continue
            arr = np.asarray(v)
            b = arr.shape[0]
            if b != expect:
                raise ValueError(
                    f"batch field {k!r} has batch {b}, config train_batch_size={expect}"
                )
            arr = arr.reshape(accum, b // accum, *arr.shape[1:])
            out[k] = jax.device_put(arr, sharding)
        return out

    def prepare_batch(self, batch) -> Dict[str, jax.Array]:
        """Pre-stage a global batch on device; feeding the result back to
        :meth:`train_batch` skips the per-step host→device upload.

        For steady-state loops over a fixed batch (benchmarks, overfit
        sanity runs) or a prefetching input pipeline that stages batch N+1
        while N computes. Not for the seqlen-curriculum path (it reshapes
        the batch on host each step)."""
        if "labels" not in batch:
            from ..models.transformer import make_lm_batch

            batch = make_lm_batch(jnp.asarray(batch["input_ids"]))
        return self._prepare_batch(batch)

    def next_rng(self) -> jax.Array:
        self._rng, key = jax.random.split(self._rng)
        return key

    def _check_concrete(self, op: str) -> None:
        if self.abstract:
            raise RuntimeError(
                f"{op}: this engine was built with abstract_init=True — a "
                "shardlint tracing shell whose state is ShapeDtypeStructs; "
                "rebuild without abstract_init to run real steps"
            )

    # ---------------------------------------------------------------- API
    def train_batch(self, data_iter=None, batch=None):
        """Parity: PipelineEngine.train_batch / typical engine step loop.

        Accepts either a global-batch dict (``batch=``) or an iterator
        yielding them (``data_iter=``).
        """
        self._check_concrete("train_batch")
        hw = self.healthwatch
        tr = self.tracer
        if hw is not None:
            hw.on_step_start()
        self.tput.start()
        if batch is None:
            if data_iter is None:
                raise ValueError("train_batch needs data_iter or batch")
            # input-wait instrumentation (ISSUE 11): the iterator pull is
            # the data stall — healthwatch's stall_on_data goodput bucket
            with Phase(tr, "train/input_wait"):
                batch = self._next_batch(data_iter)
        if "labels" not in batch:
            from ..models.transformer import make_lm_batch

            batch = make_lm_batch(jnp.asarray(batch["input_ids"]))
        if self.curriculum is not None and self.curriculum.curriculum_type == "seqlen":
            # seqlen curriculum: truncate before upload (reference parity:
            # curriculum_scheduler + the engine's seqlen reshape). Each
            # distinct difficulty compiles one program (rounding bounds it).
            # Staged (prepare_batch) inputs are [accum, micro, seq] device
            # arrays — the host-side truncate below would slice the micro
            # axis and force a device readback; fail loudly instead.
            if any(
                isinstance(v, jax.Array)
                and v.ndim >= 2
                and v.shape[0] == self.config.gradient_accumulation_steps
                for v in batch.values()
            ):
                raise ValueError(
                    "seqlen curriculum reshapes the batch on host each "
                    "step; pass the raw host batch, not prepare_batch() "
                    "output"
                )
            difficulty = self.curriculum.update_difficulty(self.global_steps)
            batch = {
                k: (np.asarray(v)[:, :difficulty] if np.asarray(v).ndim >= 2 else v)
                for k, v in batch.items()
            }
        traces_before = self.step_traces
        with Phase(tr, "train/step", step=self.global_steps + 1):
            metrics = self._host_step(batch)
        if hw is not None:
            # healthwatch tick AFTER the step span closed: the device
            # fence already ran, so the loss/grad taps read finished
            # values (exactly 2 host scalar transfers per step)
            hw.on_train_step(
                step=self.global_steps,
                loss=metrics["loss"],
                grad_norm=metrics["grad_norm"],
                compiled=self.step_traces - traces_before,
            )
        return metrics["loss"]

    def _host_step(self, batch):
        """The host's side of one step, inside ``train/step``: lay the
        batch out, hand the jitted step over, commit what it returned.
        Every phase is a ``with`` block, so a body that raises (a failed
        compile, an OOM) still closes its span in both sinks."""
        tr = self.tracer
        breakdown = self.config.wall_clock_breakdown
        if breakdown:
            self.timers("batch_prep").start()
        with Phase(tr, "train/batch_prep"):
            prepared = self._prepare_batch(batch)
            self._last_seq = int(prepared["input_ids"].shape[-1])
        if breakdown:
            self.timers("batch_prep").stop()
        ltd_keep = None
        if self.random_ltd is not None:
            # skipped (fp16-overflow) steps must not advance the anneal —
            # same invariant the in-step counter enforces for lr/PLD
            ltd_keep = self.random_ltd.get_seq_len(
                self.global_steps - self.skipped_steps
            )
            seq = prepared["input_ids"].shape[-1]
            if ltd_keep >= seq:
                ltd_keep = None  # schedule annealed past full length
        if breakdown:
            self.timers("step_dispatch").start()
        traces_before = self.step_traces
        with use_topology(self.topology):
            if self._nvme_swapper is not None:
                # dispatch grads async, then overlap the NVMe swap-in with
                # the device's fwd+bwd time; the update program follows.
                # Span discipline: the fwd_bwd dispatch span does NOT
                # fence (a fence here would serialize the swap-in against
                # the device work — the very overlap being traced); the
                # train/device span at the bottom owns the blocking wait.
                # A retrace inside a dispatch: healthwatch books the span
                # as compile time.
                with Phase(tr, "train/fwd_bwd_dispatch") as sp:
                    grads, loss, mmetrics = self._jit_grads(
                        self.state.params, self.state.loss_scale,
                        self.state.step, prepared, self.next_rng(), ltd_keep,
                    )
                    sp.annotate(traced=self.step_traces - traces_before)
                with Phase(tr, "train/offload_swap_in"):
                    self._swap_in_opt()
                with Phase(tr, "train/optimizer_dispatch") as sp:
                    traces_mid = self.step_traces
                    p, o, s, st, metrics = self._jit_update(
                        *self.state.astuple(), grads, loss, mmetrics
                    )
                    sp.annotate(traced=self.step_traces - traces_mid)
            else:
                with Phase(tr, "train/dispatch") as sp:
                    p, o, s, st, metrics = self._jit_train(
                        *self.state.astuple(), prepared, self.next_rng(),
                        ltd_keep,
                    )
                    # a retrace inside the dispatch: healthwatch books the
                    # span as compile time
                    sp.annotate(traced=self.step_traces - traces_before)
        if tr is not None:
            # fence at close: the async-dispatched fwd/bwd/optimizer work
            # is charged to this span (utils/timer.py block_on
            # discipline). This runs BEFORE the state assignment below —
            # replacing the old (donated) state while the step is still
            # in flight blocks inside the assignment, which would
            # silently attribute the whole device time to host work.
            # A fence changes the run, so this span exists only where
            # the registry was asked for (and then in both sinks, like any
            # other): no fence on a path without one.
            with Phase(tr, "train/device") as sp:
                jax.block_until_ready(metrics["loss"])
                # the step's model metrics ride on the span that timed it
                extra = {k: float(metrics[k]) for k in _SPAN_METRICS
                         if k in metrics}
                if extra:
                    sp.annotate(**extra)
        # what follows the dispatch's return: the donated state's swap,
        # the counters, the step log (0.2-0.4 ms on the chip: the swap
        # does not wait for the step in flight there, PERF.md PR 39)
        with Phase(tr, "train/commit"):
            self.state = TrainState(p, o, s, st)
            if breakdown:
                # dispatch returns immediately; a second timer blocks on the
                # device so the pair splits host time from device time
                self.timers("step_dispatch").stop()
                self.timers("step_device").start()
                self.timers("step_device").stop(block_on=metrics["loss"])
                if (self.global_steps + 1) % self.config.steps_per_print == 0:
                    self.timers.log(
                        ["batch_prep", "step_dispatch", "step_device"])
            if self._nvme_swapper is not None:
                with Phase(tr, "train/offload_swap_out"):
                    # the writes overlap the next step
                    self._swap_out_opt(blocking=False)
            self.global_steps += 1
            self.micro_steps += self.config.gradient_accumulation_steps
            self._record_offload_stream(batch=prepared)
            self._metrics = {k: v for k, v in metrics.items()}
            # only the fp16 path reads overflow on host — a host read here
            # forces a device sync every step and kills async dispatch overlap
            if self.fp16_enabled and bool(metrics["overflow"]):
                self.skipped_steps += 1
                log_dist(
                    f"step {self.global_steps}: fp16 overflow, skipping update "
                    f"(new scale {float(metrics['loss_scale'])})"
                )
            if (
                self.config.memory_breakdown
                and self.global_steps % self.config.steps_per_print == 0
            ):
                from ..utils.memory import see_memory_usage

                see_memory_usage(f"step {self.global_steps}")
            self._emit_step_log(metrics, self.global_steps)
            self.tput.stop()
        return metrics

    def _emit_step_log(self, metrics, step_no: int):
        """Monitor events + steps_per_print log line for one step's metrics
        (no-op off the print boundary). Shared by train_batch and the
        scanned chain, which replays it for every boundary it crossed."""
        if step_no % self.config.steps_per_print != 0:
            return
        show_moe = "moe_aux_loss" in metrics and getattr(
            getattr(self.model, "config", None), "is_moe", False
        )
        from ..profiling.steptrace import get_registry, write_events

        if self.monitor or get_registry() is not None:
            # the documented train/* namespace, routed through the
            # steptrace registry's single monitor bridge (one coherent
            # scheme with serve/* / comm/* / plan/* / health/*); a traced
            # run records the events as registry samples even with no
            # monitor backend, so MFU/goodput land in the health export
            events = [
                ("train/loss", float(metrics["loss"]), step_no),
                ("train/lr", float(metrics["lr"]), step_no),
                ("train/grad_norm", float(metrics["grad_norm"]), step_no),
            ]
            if show_moe:
                events.append((
                    "train/moe_aux_loss", float(metrics["moe_aux_loss"]),
                    step_no,
                ))
            events += [("train/" + k, float(metrics[k]), step_no)
                       for k in _SPAN_METRICS if k in metrics]
            if self.tput.avg_samples_per_sec > 0:
                events.append((
                    "train/samples_per_sec", self.tput.avg_samples_per_sec,
                    step_no,
                ))
            mfu = self._train_mfu()
            if mfu is not None:
                # flops_profiler MFU wired through the one registry
                # (ISSUE 11 satellite): MFU, goodput and drift appear
                # side-by-side in one export
                events.append(("train/mfu", float(mfu), step_no))
            if self.healthwatch is not None:
                events.append((
                    "train/goodput",
                    self.healthwatch.goodput_fraction(), step_no,
                ))
            write_events(self.monitor, events)
            if self.comm_logger is not None and self.monitor is not None:
                self.comm_logger.write_to(self.monitor, step_no)
        if self.monitor is None:
            aux = (
                f" moe_aux={float(metrics['moe_aux_loss']):.4f}" if show_moe else ""
            )
            sps = self.tput.avg_samples_per_sec
            tput = f" samples/sec={sps:.1f}" if sps > 0 else ""
            log_dist(
                f"step {step_no}: loss={float(metrics['loss']):.4f} "
                f"lr={float(metrics['lr']):.3e} gnorm={float(metrics['grad_norm']):.3f}"
                f"{aux}{tput}"
            )

    def _chain_eligible(self):
        """Host logic that must run BETWEEN steps disqualifies the scanned
        chain; everything else (lr schedule, PLD keep-probs, fp16 scale
        updates, overflow skip) is traced from the step carry and scans
        fine."""
        reasons = []
        if self.random_ltd is not None:
            reasons.append("random-LTD anneal picks a static keep per step")
        if self.curriculum is not None and self.curriculum.curriculum_type == "seqlen":
            reasons.append("seqlen curriculum reshapes the batch on host")
        if self._nvme_swapper is not None:
            reasons.append("NVMe offload swaps optimizer shards between "
                           "the grads and update programs")
        return reasons

    def _jit_chain(self, steps: int, stacked: bool):
        key = (steps, stacked)
        fn = self._chain_fns.get(key)
        if fn is not None:
            return fn

        def chain(params, opt_state, loss_scale, step, data, rng):
            def body(carry, x):
                p, o, s, st, r = carry
                mb = x if stacked else data
                # split exactly as next_rng() does, so a chain is
                # bit-identical to the same steps dispatched one by one
                r, key = jax.random.split(r)
                p, o, s, st, m = self._train_step(p, o, s, st, mb, key, None)
                return (p, o, s, st, r), m

            xs = data if stacked else None
            (p, o, s, st, r), ms = jax.lax.scan(
                body, (params, opt_state, loss_scale, step, rng), xs,
                length=None if stacked else steps,
            )
            return p, o, s, st, r, ms

        fn = jax.jit(
            chain,
            donate_argnums=(0, 1, 2, 3),
            out_shardings=(*self._state_shardings, None, None),
        )
        self._chain_fns[key] = fn
        return fn

    def train_batch_chain(self, batch=None, data_iter=None, steps: int = 1):
        """Run ``steps`` optimizer steps as ONE jitted program: a
        ``lax.scan`` over the train step, so the whole chain costs a single
        host dispatch.

        The reference amortizes per-step launch overhead with CUDA graphs
        and fused multi-tensor ops; on TPU the native equivalent is
        compiling the loop itself. With ``batch=`` the same (optionally
        pre-staged) global batch feeds every step — the steady-state shape
        benchmarks measure. With ``data_iter=`` the next ``steps`` host
        batches upload as one stacked transfer and scan through.

        Features that need host logic between steps (random-LTD anneal,
        seqlen curriculum, NVMe swap windows) fall back to per-step
        ``train_batch`` calls transparently. Returns the stacked per-step
        loss array ([steps]); full stacked metrics land in
        ``engine.last_chain_metrics``.
        """
        if steps < 1:
            raise ValueError(f"steps must be >= 1, got {steps}")
        self._check_concrete("train_batch_chain")
        reasons = self._chain_eligible()
        if reasons or steps == 1:
            if reasons:
                log_dist(
                    "train_batch_chain: per-step fallback: "
                    + "; ".join(reasons)
                )
            losses = [
                self.train_batch(batch=batch, data_iter=data_iter)
                for _ in range(steps)
            ]
            self.last_chain_metrics = None
            return jnp.stack([jnp.asarray(ls) for ls in losses])

        from ..models.transformer import make_lm_batch

        stacked = data_iter is not None
        if stacked:
            # stack the N host batches FIRST and upload each field once as
            # one [steps, accum, micro, ...] transfer — per-batch device_put
            # is exactly the blocking-RPC-per-step cost the chain removes.
            # Labels shift on host for the same reason.
            accum = self.config.gradient_accumulation_steps
            expect = self.config.train_batch_size
            host_steps = []
            for _ in range(steps):
                b = {k: np.asarray(v) for k, v in
                     self._next_batch(data_iter).items()}
                if "labels" not in b:
                    ids = b["input_ids"]
                    b["labels"] = np.concatenate(
                        [ids[:, 1:],
                         np.full((ids.shape[0], 1), -1, ids.dtype)], axis=1
                    )
                host_steps.append(b)
            sharding = NamedSharding(
                self.topology.mesh, P(None, None, *tuple(self.topology.batch_spec()))
            )
            data = {}
            for k in host_steps[0]:
                arrs = [b[k] for b in host_steps]
                for a in arrs:
                    if a.shape[0] != expect:
                        raise ValueError(
                            f"batch field {k!r} has batch {a.shape[0]}, "
                            f"config train_batch_size={expect}"
                        )
                data[k] = jax.device_put(
                    np.stack([
                        a.reshape(accum, expect // accum, *a.shape[1:])
                        for a in arrs
                    ]),
                    sharding,
                )
        else:
            if batch is None:
                raise ValueError("train_batch_chain needs batch or data_iter")
            if "labels" not in batch:
                batch = make_lm_batch(jnp.asarray(batch["input_ids"]))
            data = self._prepare_batch(batch)

        self.tput.start()
        with use_topology(self.topology):
            p, o, s, st, self._rng, ms = self._jit_chain(steps, stacked)(
                *self.state.astuple(), data, self._rng
            )
        start = self.global_steps
        self.state = TrainState(p, o, s, st)
        self.global_steps += steps
        self.micro_steps += steps * self.config.gradient_accumulation_steps
        self._record_offload_stream(steps=steps, batch=data)
        self.last_chain_metrics = ms
        # expose the final step's metrics where train_batch puts them
        self._metrics = {k: v[-1] for k, v in ms.items()}
        if self.fp16_enabled:
            skipped = int(np.sum(np.asarray(ms["overflow"])))
            if skipped:
                self.skipped_steps += skipped
                log_dist(
                    f"chain of {steps}: {skipped} fp16-overflow steps skipped"
                )
        self.tput.stop(steps=steps)
        # replay monitor/print output for every boundary inside the chain
        for i in range(steps):
            if (start + i + 1) % self.config.steps_per_print == 0:
                self._emit_step_log(
                    {k: v[i] for k, v in ms.items()}, start + i + 1
                )
        return ms["loss"]

    def _next_batch(self, data_iter):
        """Pull the next batch: accepts a batch dict, an iterator, or an
        iterable (e.g. the DeepSpeedDataLoader returned by initialize();
        its iterator is cached so repeated calls advance it)."""
        if isinstance(data_iter, dict):
            return data_iter
        if hasattr(data_iter, "__next__"):
            return next(data_iter)
        if hasattr(data_iter, "__iter__"):
            key = id(data_iter)
            if key not in self._data_iters:
                self._data_iters[key] = iter(data_iter)
            try:
                return next(self._data_iters[key])
            except StopIteration:
                self._data_iters[key] = iter(data_iter)
                return next(self._data_iters[key])
        return data_iter

    def eval_batch(self, data_iter=None, batch=None):
        self._check_concrete("eval_batch")
        if batch is None:
            batch = self._next_batch(data_iter)
        if "labels" not in batch:
            from ..models.transformer import make_lm_batch

            batch = make_lm_batch(jnp.asarray(batch["input_ids"]))
        sharding = self._batch_sharding(accum_leading=False)
        prepared = {
            k: jax.device_put(np.asarray(v), sharding) for k, v in batch.items()
        }
        with use_topology(self.topology):
            loss, _ = self._jit_eval(self.state.params, prepared, self.next_rng())
        return loss

    def profile_step(self, data_iter=None, batch=None,
                     trace_dir: str = "xprof_trace"):
        """Run one train step under ``jax.profiler.trace`` and dump an xprof
        trace to ``trace_dir`` (open with xprof/tensorboard, or feed to the
        autotuner). Returns (loss, trace_dir).

        Parity: the reference's flops-profiler/wall-clock breakdown hooks —
        here the XLA profiler captures per-op device timelines instead of
        python-side module timers (the step is one fused program)."""
        os.makedirs(trace_dir, exist_ok=True)
        with jax.profiler.trace(trace_dir):
            loss = self.train_batch(data_iter=data_iter, batch=batch)
            # host-read so the device work lands inside the trace window
            jax.block_until_ready(self.state.params)
        log_dist(f"profile_step: xprof trace written to {trace_dir}")
        return loss, trace_dir

    # --------------------------------------------------------- steptrace
    def trace_export(self, path: Optional[str] = None) -> str:
        """Write the Chrome trace-event JSON (Perfetto-loadable; see
        docs/observability.md). Every declared ``analytic_streams()``
        stream is added as a ``plan/<name>`` span annotated with its
        shardplan-predicted bytes/seconds next to the measured average
        ``train/step`` wall clock — the per-component drift view."""
        if self.tracer is None:
            raise RuntimeError(
                "steptrace is not enabled on this engine — set "
                '{"steptrace": {"enabled": true}} in the config'
            )
        measured = self.tracer.mean_dur("train/step")
        try:
            streams = self.analytic_streams(include_potential=True)
        except Exception:  # noqa: BLE001 — a trace export must not die
            # on the analytic annotation (e.g. half-built lint shells)
            streams = {}
        for name, stream in streams.items():
            args = {}
            if name == "offload" and self._bucketed_opt is not None:
                # bucketed_opt's stream annotation: rotating-slot depth
                # rides along so Perfetto shows the prefetch structure
                args = self._bucketed_opt.stream_annotation()
            self.tracer.plan_span(
                name, {**stream, **args}, measured_step_s=measured
            )
        path = path or self._steptrace_export_path or "steptrace_train.json"
        out = self.tracer.export(path)
        log_dist(f"steptrace: wrote {out}")
        return out

    # -------------------------------------------------------- healthwatch
    def _build_healthwatch(self, hw_cfg):
        """Construct the health layer (profiling/healthwatch.py). It
        rides the steptrace registry — enabling healthwatch turns
        tracing on so the goodput buckets can be classified off this
        engine's own spans."""
        from ..profiling import healthwatch as _healthwatch
        from ..profiling import steptrace as _steptrace

        if self.tracer is None:
            self.tracer = _steptrace.configure(
                max_spans=self.config.steptrace.max_spans
            )
            if self.comm_logger is not None:
                self.comm_logger.registry = self.tracer
        self.healthwatch = _healthwatch.HealthWatch(
            hw_cfg, self.tracer, source="train",
            context={"config": self.config.to_dict()},
        )
        streams = self.analytic_streams()
        self.healthwatch.set_comm_estimate_from_streams(streams)
        snap = streams.get("ckpt_snapshot")
        if snap:
            # arm the checkpoint_stall watchdog: fence budget = snapshot
            # bytes over the host link (same static pricing as R8)
            try:
                from ..analysis.cost.hardware import HardwareModel

                host_bw = float(HardwareModel.detect().host_bw)
                if host_bw > 0:
                    self.healthwatch.set_ckpt_budget(
                        float(snap["per_device_snapshot_bytes"]) / host_bw
                    )
            except Exception as e:  # noqa: BLE001 — telemetry only
                log_dist(f"healthwatch: ckpt budget skipped: {e}")
        return self.healthwatch

    def dump_postmortem(self, path: Optional[str] = None,
                        reason: str = "explicit") -> Optional[str]:
        """Write the flight-recorder postmortem JSON (render/validate
        with tools/healthwatch.py; docs/observability.md)."""
        if self.healthwatch is None:
            raise RuntimeError(
                "healthwatch is not enabled on this engine — set "
                '{"healthwatch": {"enabled": true}} in the config'
            )
        return self.healthwatch.dump_postmortem(path=path, reason=reason)

    def _train_mfu(self) -> Optional[float]:
        """Model-flops utilization from the throughput timer plus the
        flops profiler's analytic per-step flops (fwd+bwd = 3x fwd),
        priced against the hardware table's peak — the ISSUE-11
        satellite that puts MFU next to goodput and drift in one
        export. None until the timer warms up or when the model has no
        TransformerConfig-shaped config."""
        sps = self.tput.avg_samples_per_sec
        mc = getattr(self.model, "config", None)
        if sps <= 0 or mc is None or self._last_seq is None:
            return None
        key = (self.config.train_batch_size, self._last_seq)
        if key not in self._mfu_cache:
            # dict cache per (batch, seq): bucketed-seqlen runs must not
            # re-profile the model at every print boundary
            try:
                from ..analysis.cost.hardware import HardwareModel
                from ..profiling.flops_profiler import get_model_profile

                flops, _macs, _params = get_model_profile(
                    self.model, key[0], key[1], fwd_only=False
                )
                self._mfu_cache[key] = (
                    float(flops),
                    float(HardwareModel.detect().peak_flops),
                )
            except Exception:  # noqa: BLE001 — telemetry must not
                # crash the step loop on an exotic model shape
                self._mfu_cache[key] = (0.0, 0.0)
        flops, peak = self._mfu_cache[key]
        if flops <= 0 or peak <= 0:
            return None
        step_s = self.config.train_batch_size / sps
        return flops / step_s / peak

    # -- reference imperative protocol ---------------------------------------
    def forward(self, batch):
        """Parity: engine(batch) → loss in the engine's current train/eval
        mode (engine.train()/engine.eval(); train mode also buffers the
        batch for backward/step).

        Note: the SPMD fast path is train_batch() — this protocol re-runs the
        forward inside the fused train step at the accumulation boundary, so
        it costs one extra forward per microbatch versus train_batch().
        """
        self._check_concrete("forward")
        if self.training:
            self._pending_batch = batch
        if "labels" not in batch:
            from ..models.transformer import make_lm_batch

            batch = make_lm_batch(jnp.asarray(batch["input_ids"]))
        sharding = self._batch_sharding(accum_leading=False)
        prepared = {k: jax.device_put(np.asarray(v), sharding) for k, v in batch.items()}
        with use_topology(self.topology):
            loss, _ = self._jit_eval(
                self.state.params, prepared, self.next_rng(), self.training
            )
        return loss

    def backward(self, loss=None, batch=None):
        """Parity: engine.backward(loss) — buffers the microbatch; the real
        fused fwd+bwd runs at the accumulation boundary inside step()."""
        mb = batch if batch is not None else getattr(self, "_pending_batch", None)
        if mb is None:
            raise ValueError("backward() without a pending forward batch")
        self._micro_buffer.append(mb)
        self._pending_batch = None
        return loss

    def is_gradient_accumulation_boundary(self) -> bool:
        return len(self._micro_buffer) >= self.config.gradient_accumulation_steps

    def step(self):
        """Parity: engine.step() — applies the update at the boundary."""
        if not self.is_gradient_accumulation_boundary():
            return None
        merged = {}
        for k in self._micro_buffer[0]:
            merged[k] = np.concatenate([np.asarray(mb[k]) for mb in self._micro_buffer])
        self._micro_buffer = []
        return self.train_batch(batch=merged)

    __call__ = forward

    # ------------------------------------------------- nn.Module-ish parity
    # (DeepSpeedEngine subclasses torch.nn.Module; user loops call these)
    @property
    def module(self):
        """Parity: engine.module — the wrapped model object."""
        return self.model

    def train(self, mode: bool = True):
        """Parity: engine.train() — records the mode flag. Train/eval
        behavior here is selected per call (train_batch vs eval_batch);
        the flag only answers engine.training queries."""
        self.training = bool(mode)
        return self

    def eval(self):
        return self.train(False)

    def zero_grad(self, set_to_none: bool = True):
        """Parity no-op: grads are functional values produced inside the
        jitted step, never accumulated into persistent buffers."""

    # ----------------------------------------------------------- properties
    @property
    def lr(self) -> float:
        return float(self.lr_schedule(self.state.step))

    def get_lr(self):
        return [self.lr]

    @property
    def loss_scale(self) -> float:
        return float(self.state.loss_scale.scale)

    def get_global_grad_norm(self) -> float:
        g = self._metrics.get("grad_norm")
        return float(g) if g is not None else 0.0

    @property
    def train_micro_batch_size_per_gpu(self) -> int:
        return self.config.train_micro_batch_size_per_gpu

    @property
    def gradient_accumulation_steps(self) -> int:
        return self.config.gradient_accumulation_steps

    # ------------------------------------------------------------ NVMe swap
    def _swap_in_opt(self):
        """Read optimizer state back from NVMe (no-op if already resident)."""
        if self.state.opt_state is None:
            self.state.opt_state = self._nvme_swapper.swap_in(
                "opt_state", self._opt_treedef, self.opt_shardings
            )

    def _swap_out_opt(self, blocking: bool = True):
        """Stream optimizer state to NVMe and release its device memory.

        blocking=False leaves the disk writes in flight (the swapper blocks
        the next swap_in on them), overlapping write I/O with host-side batch
        prep and the next step's dispatch."""
        self._nvme_swapper.swap_out(
            "opt_state", self.state.opt_state, blocking=blocking
        )
        self.state.opt_state = None

    def save_16bit_model(self, save_dir, save_filename="model.safetensors"):
        """Parity: DeepSpeedEngine.save_16bit_model (deepspeed/runtime/
        engine.py) — consolidate the (possibly ZeRO-sharded) weights into
        ONE bf16 safetensors file, no optimizer state. For the recognized
        model families (llama/mistral/gpt2/bloom/mixtral) the keys are the
        HF state_dict names, so transformers can load the file directly
        (the reference's stated use for a consolidated 16-bit export);
        other models fall back to the checkpoint's internal keystr names
        for same-framework reload. Every process participates in the
        gather; the writer process writes and everyone barriers so no
        process races ahead of the file."""
        from ..integrations.hf import export_hf_state_dict, write_safetensors
        from .checkpointing import _barrier, _is_writer, _leaf_paths, _to_host

        host = jax.tree.map(_to_host, self.state.params)
        fam = str(getattr(self.model.config, "name", "")).split("-")[0].lower()
        hf_families = ("llama", "mistral", "gpt2", "bloom", "mixtral")
        if fam in hf_families:
            # a recognized family must export HF names; an exporter bug
            # here should surface, not silently degrade the file
            flat = export_hf_state_dict(host, self.model.config, fam)
            log_dist(f"save_16bit_model: HF state_dict names ({fam})")
        else:
            flat = dict(zip(_leaf_paths(host),
                            jax.tree_util.tree_leaves(host)))
            log_dist(
                f"save_16bit_model: family {fam!r} has no HF exporter; "
                "writing internal keystr names (same-framework reload only)"
            )
        flat = {
            k: (np.asarray(v).astype(jnp.bfloat16)  # ml_dtypes scalar type
                if np.issubdtype(np.asarray(v).dtype, np.floating)
                else np.asarray(v))
            for k, v in flat.items()
        }
        path = os.path.join(save_dir, save_filename)
        if _is_writer():
            os.makedirs(save_dir, exist_ok=True)
            write_safetensors(path, flat)
        _barrier("save_16bit_model")
        return path

    @contextmanager
    def no_sync(self):
        """Parity shim: DeepSpeedEngine.no_sync. Gradient sync here is not
        a hook to suppress — accumulation is a jitted scan and the data-
        parallel mean happens once at the boundary inside the compiled
        step, so there is nothing to skip; micro-steps never pay a sync.
        Kept for train-loop portability. Like the reference, it refuses
        under ZeRO >= 2 (there the reduce IS the partitioning and a user
        expecting deferred sync would silently get wrong semantics)."""
        if self.config.zero_config.stage >= 2:
            raise RuntimeError(
                "no_sync is not supported with ZeRO stage >= 2 "
                "(gradient reduce-scatter is the partitioning step)"
            )
        yield

    # --------------------------------------------------------- checkpointing
    def _ckpt_guard(self):
        """Lazy per-engine CheckpointGuard: fences async saves and routes
        background write seconds to healthwatch (out-of-band, never the
        goodput buckets — the write overlaps training)."""
        if self._checkpoint_guard is None:
            from .ckpt import CheckpointGuard

            def on_write_done(seconds):
                hw = self.healthwatch
                if hw is not None:
                    hw.add_ckpt_write_s(seconds)

            self._checkpoint_guard = CheckpointGuard(
                on_write_done=on_write_done
            )
        return self._checkpoint_guard

    def save_checkpoint(self, save_dir, tag=None, client_state=None,
                        async_save=None):
        self._check_concrete("save_checkpoint")
        from .ckpt import save_checkpoint as _save
        from .ckpt.async_writer import install_preempt_handler

        ckpt_cfg = self.config.checkpoint
        if async_save is None:
            async_save = bool(getattr(ckpt_cfg, "async_save", False))
        if getattr(ckpt_cfg, "on_preempt", "save") == "save":
            # first save teaches SIGTERM where restore points live: a
            # preemption now triggers a final sync save ahead of
            # healthwatch's postmortem chain
            install_preempt_handler(self, save_dir)
        # checkpoint time is its own goodput bucket (ISSUE 11). The span
        # covers only the SYNCHRONOUS cost: swap-in, the snapshot fence
        # (device→pinned-host copy), and the swap-out. An async save's
        # shard write lands in the background and is reported separately
        # as ckpt_write_s — charging it here would bill overlap as stall.
        with Phase(self.tracer, "train/checkpoint"):
            if self._nvme_swapper is not None:
                self._swap_in_opt()
            try:
                return _save(
                    self, save_dir, tag=tag, client_state=client_state or {},
                    async_save=async_save, guard=self._ckpt_guard(),
                )
            finally:
                if self._nvme_swapper is not None:
                    # keep the "on disk between steps" invariant
                    self._swap_out_opt()

    def load_checkpoint(self, load_dir, tag=None, strict=True):
        from .ckpt import load_checkpoint as _load

        guard = self._checkpoint_guard
        if guard is not None:
            guard.fence()  # never read a tag the writer is still landing
        if self._nvme_swapper is not None:
            self._swap_in_opt()  # loader needs a resident template tree
        out = _load(self, load_dir, tag=tag, strict=strict)
        if self._nvme_swapper is not None:
            self._swap_out_opt()
        return out

    def destroy(self):
        """Parity: DeepSpeedEngine.destroy — release global hooks/writers so
        engines created in a loop don't accumulate loggers."""
        if self._checkpoint_guard is not None:
            # land the in-flight async save before the state it snapshotted
            # is torn down (drain logs a writer failure instead of raising:
            # teardown must complete)
            self._checkpoint_guard.drain()
            self._checkpoint_guard = None
        if self.healthwatch is not None:
            self.healthwatch.close()  # final exporter flush + unregister
            self.healthwatch = None
        if self.comm_logger is not None:
            self.comm_logger.stop()
            self.comm_logger = None
        if self.monitor is not None:
            for m in self.monitor.monitors:
                if hasattr(m, "close"):
                    m.close()
            self.monitor = None
        if self._nvme_swapper is not None:
            self._nvme_swapper.close()
            self._nvme_swapper = None
        # Free device buffers NOW rather than at the GC's leisure: an engine
        # holds params + optimizer state (~6x param bytes at fp32 master),
        # and tuner loops that build engines back-to-back on a 16GB chip OOM
        # on the *next* candidate when the previous state lingers. Deleting
        # is safe — the engine is defunct after destroy().
        state, self.state = self.state, None
        if state is not None:
            # TrainState is not a registered pytree — walk its tuple form
            for leaf in jax.tree_util.tree_leaves(state.astuple()):
                if isinstance(leaf, jax.Array):
                    try:
                        leaf.delete()
                    except Exception:  # noqa: BLE001 — already-deleted/donated
                        pass
