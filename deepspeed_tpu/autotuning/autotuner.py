"""Autotuner: micro-batch-size / remat-policy search.

Parity: deepspeed/autotuning/autotuner.py (+ the "autotuning" config
section). The reference launches separate ranked experiments; on TPU one
process owns the chips, so each candidate is a fresh engine in-process:
compile → run measured steps → throughput; OOM (XLA RESOURCE_EXHAUSTED)
prunes the candidate and, in fast mode, everything larger.

Search space: micro-batch sizes (powers of two up to
max_train_micro_batch_size_per_gpu) × remat policies (none is tried first
at each batch — cheapest when it fits, per the memory/compute tradeoff),
then a flash-attention tile sweep (block_q × block_k) refines the winner —
the "tpu_kernels" knob the engine exposes for exactly this loop.

Planner mode (ISSUE 7, default whenever an HBM budget is resolvable —
``autotuning.hbm_gb``, ``SHARDPLAN_HBM_GB``, or a detected TPU
generation's capacity; ``autotuning.planner`` forces it either way):
instead of walking the ladder by compiling, the whole candidate space is
priced through analysis/cost abstract traces (planner_search.py), rule
R6 statically prunes what cannot fit, survivors are ranked by roofline
throughput, and only a top-k (``autotuning.top_k``, default 3) is
compiled and measured. Each measured survivor banks its
(predicted, measured) step pair into the drift ledger
(analysis/cost/drift.py; ``autotuning.drift_ledger`` overrides the
path) so systematic cost-model drift surfaces as a recalibration
suggestion instead of silently rotting the ranking. The runtime
RESOURCE_EXHAUSTED catch in ``_measure`` stays as the backstop for what
the static estimate misses.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..utils.logging import log_dist

REMAT_POLICIES = ("none", "dots_flash", "attn_mlp", "full")
# phase-0 memory ladder (reference: the DeepSpeed autotuner's core job is
# picking the ZeRO stage — deepspeed/autotuning/autotuner.py tuning space
# z0→z3+offload): escalate until the model fits, then tune micro/remat at
# that stage. Lower stages go first — less collective traffic when they fit.
ZERO_LADDER = (
    {"stage": 0},
    {"stage": 1},
    {"stage": 2},
    {"stage": 3},
    {"stage": 3, "offload_optimizer": {"device": "cpu"}},
)
# (512, 512) is NOT a candidate: it equals the kernel defaults (see
# flash_attention.DEFAULT_BLOCK_*) so phase 2 would re-measure the (0, 0)
# phase-1 winner; 512x1024 is the measured v5e S=2048 winner
FLASH_BLOCKS = ((0, 0), (512, 1024), (512, 256), (256, 512), (128, 128))
# phase-3 backward-tile candidates (dq/dkv kernels); fwd tiles stay at the
# phase-2 winner. Excludes (0, 0): that IS the phase-2 result (inherit).
FLASH_BLOCKS_BWD = ((512, 512), (256, 512), (512, 256))


def _is_oom(err: Exception) -> bool:
    # match XLA's OOM signatures only — a generic "hbm" substring would also
    # swallow unrelated compiler diagnostics that merely mention the memory
    # space, hiding the real failure from the user
    s = str(err)
    return "RESOURCE_EXHAUSTED" in s or "Ran out of memory" in s


class Autotuner:
    def __init__(self, model, base_config: Dict[str, Any], topology=None,
                 sample_batch_fn=None):
        self.model = model
        self.base_config = dict(base_config)
        self.topology = topology
        self.sample_batch_fn = sample_batch_fn
        at = dict(self.base_config.get("autotuning") or {})
        self.metric = at.get("metric", "throughput")
        self.fast = bool(at.get("fast", True))
        self.start_step = int(at.get("start_profile_step", 3))
        self.end_step = int(at.get("end_profile_step", 5))
        self.max_micro = int(at.get("max_train_micro_batch_size_per_gpu", 64))
        self.trials = int(at.get("trials", 3))  # medians beat noisy pools
        self.fixed_global_batch = bool(at.get("fixed_global_batch", False))
        # phase 0 (ZeRO ladder) runs by default only when the user left the
        # zero_optimization section unset — an explicit stage is a pin the
        # tuner must respect; "tune_zero_stage" overrides either way
        self.tune_zero = bool(
            at.get("tune_zero_stage",
                   "zero_optimization" not in self.base_config)
        )
        # planner mode (planner_search.py): None → auto (on when an HBM
        # budget is resolvable), True/False forces it
        self.planner: Optional[bool] = at.get("planner")
        self.top_k = int(at.get("top_k", 3))
        self.hbm_gb = at.get("hbm_gb")
        self.drift_ledger_path = at.get("drift_ledger")
        self._zero_patch: Optional[Dict[str, Any]] = None
        self.results: List[Dict[str, Any]] = []
        self.last_search = None      # SearchResult of the planner phase
        self.n_compiles = 0          # engines actually built + compiled

    def _candidates(self) -> List[Tuple[int, str]]:
        mbs = []
        m = 1
        while m <= self.max_micro:
            mbs.append(m)
            m *= 2
        return [(mb, pol) for mb in mbs for pol in REMAT_POLICIES]

    def _settled_zero(self, rung) -> Dict[str, Any]:
        """The zero section phases 1+ measure once the ladder settles:
        the winning rung plus the user's non-conflicting zero keys.
        stage and the offload subsections come from the rung — they ARE
        what phase 0 decided."""
        user = dict(self.base_config.get("zero_optimization") or {})
        for k in ("stage", "offload_optimizer", "offload_param"):
            user.pop(k, None)
        return {**user, **dict(rung)}

    def _candidate_config(self, micro_batch: int, remat: str,
                          blocks: Tuple[int, ...] = (0, 0)) -> Dict[str, Any]:
        """The exact ds_config one candidate measures (split out so tests
        can assert what a probe runs without spinning an engine)."""
        cfg = dict(self.base_config)
        cfg.pop("autotuning", None)
        if self._zero_patch is not None:
            # the ladder rung REPLACES the section wholesale: merging the
            # base config's keys in (dict.update) leaked user settings
            # like offload_optimizer into lower-stage probes — stage 0 +
            # cpu offload is a config the ladder never intends to measure
            cfg["zero_optimization"] = dict(self._zero_patch)
        if self.topology is not None:
            dp = self.topology.data_shard_size
        else:
            # initialize() will build a pure-dp topology over every visible
            # device; the batch triangle must be computed against that same
            # dp or every candidate fails config validation
            import jax

            dp = max(len(jax.devices()), 1)
        cfg["train_micro_batch_size_per_gpu"] = micro_batch
        if self.fixed_global_batch:
            # hold the global batch constant and let accumulation absorb
            # the micro change (operator-sweep semantics: every point sees
            # identical data and optimizer dynamics)
            tbs = int(cfg["train_batch_size"])
            cfg["gradient_accumulation_steps"] = max(tbs // (micro_batch * dp), 1)
        else:
            accum = int(cfg.get("gradient_accumulation_steps", 1))
            cfg["train_batch_size"] = micro_batch * dp * accum
        cfg["activation_checkpointing"] = {"policy": remat}
        blocks = tuple(blocks) + (0,) * (4 - len(blocks))  # (bq,bk[,bqb,bkb])
        if any(blocks):
            tk = dict(cfg.get("tpu_kernels") or {})
            # fwd keys only when the candidate names them: a bwd-only
            # candidate (0,0,bqb,bkb) must keep the base config's fwd
            # tiles, or the measurement and the emitted patch describe
            # different configurations. bwd keys assigned whenever the
            # candidate is non-default: its 0 means "inherit fwd" and
            # must overwrite a stale base-config bwd override.
            if blocks[0] or blocks[1]:
                tk["flash_block_q"], tk["flash_block_k"] = blocks[:2]
            tk["flash_block_q_bwd"], tk["flash_block_k_bwd"] = blocks[2:]
            cfg["tpu_kernels"] = tk
        cfg.setdefault("steps_per_print", 10**9)
        return cfg

    def _measure(self, micro_batch: int, remat: str,
                 blocks: Tuple[int, int] = (0, 0),
                 cfg: Optional[Dict[str, Any]] = None) -> Optional[float]:
        """One candidate: fresh engine → compile+warmup → chained-dispatch
        timing → tokens/sec. This is THE compile+measure loop: the campaign
        (autotuning/campaign.py) measures its survivors through it too.

        Timing: each trial dispatches a chain of steps with ONE blocking
        read at the end, and trials are reduced by median (a one-chip
        machine shares its host's cores, so host clocks are noisy)."""
        import deepspeed_tpu

        # planner mode passes the candidate's FULL config (extra axes
        # like tp_overlap differ from what (micro, remat) alone rebuilds)
        cfg = cfg or self._candidate_config(micro_batch, remat, blocks)
        engine = None
        self.n_compiles += 1  # the planner-mode contract: ≤ top-k of these
        try:
            engine, *_ = deepspeed_tpu.initialize(
                model=self.model, config=cfg, topology=self.topology
            )
            batch = self.sample_batch_fn(cfg["train_batch_size"])
            # stage once: no upload before each dispatch
            staged = engine.prepare_batch(dict(batch))
            # the scanned chain: one dispatch and one readback per trial,
            # and only ONE compile per candidate (the single-step program
            # never compiles)
            chain = max(self.end_step - self.start_step, 1)
            engine.train_batch_chain(batch=staged, steps=chain)  # compile
            float(engine.state.step)  # settle before the timed region
            trials = []
            for _ in range(self.trials):
                t0 = time.perf_counter()
                engine.train_batch_chain(batch=staged, steps=chain)
                float(engine.state.step)  # one readback per chain
                trials.append((time.perf_counter() - t0) / chain)
            dt = float(np.median(trials))
            tokens = np.asarray(batch["input_ids"]).size
            return tokens / dt
        except Exception as e:  # noqa: BLE001 — OOM pruning is the point
            if _is_oom(e):
                log_dist(f"autotune: mb={micro_batch} remat={remat} OOM, pruned")
                return None
            raise
        finally:
            if engine is not None:
                engine.destroy()  # release logger hooks even on failure

    def measure_grid(self, grid) -> List[Dict[str, Any]]:
        """Measure an explicit [(micro, remat_policy, (bq, bk)), ...] grid
        through the same engine as :meth:`tune`. Returns one record per
        point ({micro_batch, remat_policy, flash_block_*, throughput} or
        {... , error}); OOM points record throughput None. Non-OOM failures
        are recorded, not raised — an operator grid survives bad rungs."""
        records = []
        for micro, pol, blocks in grid:
            rec: Dict[str, Any] = {
                "micro_batch": int(micro), "remat_policy": pol,
                "flash_block_q": int(blocks[0]), "flash_block_k": int(blocks[1]),
            }
            if len(blocks) > 2 and (blocks[2] or blocks[3]):
                rec["flash_block_q_bwd"] = int(blocks[2])
                rec["flash_block_k_bwd"] = int(blocks[3])
            try:
                rec["throughput"] = self._measure(micro, pol, tuple(blocks))
            except Exception as e:  # noqa: BLE001
                rec["error"] = (str(e).splitlines() or [repr(e)])[0][:160]
            records.append(rec)
            if rec.get("throughput") is not None:
                self.results.append(rec)
        return records

    def _flash_tunable(self) -> bool:
        """Phase 2 only makes sense when the flash tile knobs are live."""
        import jax

        if jax.default_backend() != "tpu":
            return False  # interpret-mode tiles all time the same
        tk = dict(self.base_config.get("tpu_kernels") or {})
        if tk.get("flash_attention") is False:
            return False  # xla impl never reads the tile scope
        sa = dict(self.base_config.get("sparse_attention") or {})
        if sa.get("mode", "none") != "none":
            return False  # sparse pins block_q/block_k to its layout block
        return True

    def _pick_zero_stage(self) -> Optional[Dict[str, Any]]:
        """Phase 0: walk ZERO_LADDER until a probe fits (micro_batch=1 at
        max remat — if THAT OOMs, nothing at the stage will run), leaving
        the winning patch active in self._zero_patch for every later
        measurement. Answers the reference autotuner's core question: which
        ZeRO stage do I need for this model to fit at all."""
        if not self.tune_zero:
            return None
        pipe = dict(self.base_config.get("pipeline") or {})
        ladder = ZERO_LADDER
        if int(pipe.get("stages", 1)) > 1:
            # grads must persist across the pipeline schedule: config
            # validation rejects ZeRO>=2 + pp, so the ladder stops at 1
            ladder = tuple(z for z in ladder if z["stage"] <= 1)
        self._probe_tput = None
        for z in ladder:
            self._zero_patch = dict(z)  # probes measure the rung EXACTLY
            tput = self._measure(1, REMAT_POLICIES[-1])
            if tput is not None:
                log_dist(f"autotune: zero ladder settled on {z}")
                self._probe_tput = tput
                # later phases (micro/remat/tiles) measure the winning
                # rung ENRICHED with the user's non-conflicting zero keys
                # (bucket sizes etc.) — stage/offload stay the ladder's
                # decision, but dropping e.g. reduce_bucket_size would
                # rank candidates on a config the user won't run
                settled = self._settled_zero(z)
                if settled != dict(z):
                    # the probe ran the BARE rung; its tput must not be
                    # recorded against the enriched section — phase 1
                    # re-measures the (mb=1, max-remat) point
                    self._probe_tput = None
                self._zero_patch = settled
                return dict(settled)
            log_dist(f"autotune: zero={z} OOM at mb=1/full; escalating")
        self._zero_patch = None
        raise RuntimeError(
            "autotuning: no ZeRO stage (0-3, +cpu offload) fits even at "
            "micro_batch=1 with full rematerialisation"
        )

    # ------------------------------------------------------- planner mode
    def _resolved_budget(self) -> Optional[float]:
        """The per-device HBM budget planner mode prunes against:
        explicit ``autotuning.hbm_gb``, then the ``SHARDPLAN_HBM_GB``
        env, then — only when the chips are real — the detected
        generation's capacity. On a CPU mesh with nothing armed there is
        no budget (R6's never-guess-the-machine contract) and the tuner
        stays on the runtime ladder unless ``planner`` forces it."""
        import os

        if self.hbm_gb is not None:
            return float(self.hbm_gb) * float(1 << 30)
        env = os.environ.get("SHARDPLAN_HBM_GB")
        if env:
            return float(env) * float(1 << 30)
        import jax

        if jax.default_backend() == "tpu":
            from ..analysis.cost import HardwareModel

            return HardwareModel.detect().hbm_bytes
        return None

    def _planner_mode(self) -> bool:
        if self.planner is not None:
            return bool(self.planner)
        return self._resolved_budget() is not None

    def _tune_planner(self) -> Dict[str, Any]:
        """Phase 0+1, planner-driven: enumerate the whole (zero × remat
        × micro) space through analysis.cost, R6-prune statically, rank
        by roofline, compile + measure only the top-k. Banks one drift
        pair per measured survivor."""
        from ..analysis.cost import drift
        from ..config import DeepSpeedConfig
        from .planner_search import PlannerSearch

        if DeepSpeedConfig(dict(self.base_config)).serving.enabled:
            # the measurement loop below times a TRAIN step; a serving
            # config's token_budget axis is static-only for now
            raise NotImplementedError(
                "planner-mode measurement covers training candidates; "
                "the serving token_budget search is static-only — rank "
                "it with tools/autoplan.py and A/B the survivors with "
                "tools/bench_serve.py"
            )
        search = PlannerSearch(
            self.model, self.base_config, self.topology,
            top_k=self.top_k, hbm_budget_bytes=self._resolved_budget(),
            tuner=self,
        )
        self.last_search = result = search.search()
        if not result.survivors:
            raise RuntimeError(
                "autotuning: every candidate is statically over the HBM "
                "budget (planner_search R6) — shard further, offload, or "
                "raise autotuning.hbm_gb\n" + result.explain()
            )
        ledger = drift.DriftLedger(self.drift_ledger_path)
        best = None
        for pc in result.top_k:
            self._zero_patch = pc.cand.zero_dict
            # the EXACT planned config (incl. axes _candidate_config
            # alone cannot rebuild, e.g. tp_overlap) is what measures —
            # the drift pair must compare prediction and wall clock of
            # the same program
            cfg = search._candidate_config(pc.cand)
            tput = self._measure(pc.cand.micro, pc.cand.remat, cfg=cfg)
            if tput is None:
                # the static estimate missed: the runtime OOM catch is
                # still the backstop, the rung just loses its slot
                log_dist(f"autotune: planner survivor {pc.cand.label()} "
                         "OOMed at runtime (backstop prune)")
                continue
            rec = {
                "micro_batch": pc.cand.micro,
                "remat_policy": pc.cand.remat,
                "throughput": tput,
                "predicted_step_s": pc.predicted_step_s,
                "predicted_tokens_per_s": pc.predicted_tput,
            }
            if pc.cand.zero_dict is not None:
                rec["zero_optimization"] = pc.cand.zero_dict
            if pc.cand.tp_overlap is not None:
                # carry the full resolved section: result_to_config_patch
                # replaces sections wholesale, so a bare flag would wipe
                # tp_size on merge
                rec["tensor_parallel"] = cfg["tensor_parallel"]
            if pc.cand.moe_a2a is not None:
                rec["moe"] = cfg["moe"]  # same wholesale-section rule
            if pc.cand.z3_prefetch is not None:
                rec["zero_optimization"] = cfg["zero_optimization"]
            self.results.append(rec)
            log_dist(f"autotune: planner top-k {pc.cand.label()}: "
                     f"{tput:.0f} tok/s (predicted "
                     f"{pc.predicted_tput or 0:.0f})")
            if best is None or tput > best["throughput"]:
                best = rec
            try:  # the ledger is evidence, never a point of failure
                measured_step_s = pc.tokens_per_step / tput
                ledger.append(drift.make_entry(
                    pc.plan, measured_step_s,
                    source=f"autotune:{pc.cand.label()}",
                    extra={"throughput": round(tput, 1)},
                ))
            except Exception as e:  # noqa: BLE001
                log_dist(f"autotune: drift ledger append failed: {e}")
        if best is None:
            raise RuntimeError(
                "autotuning: all planner-ranked top-k candidates failed "
                "at runtime; re-run with a lower autotuning.hbm_gb or "
                "planner=false\n" + result.explain()
            )
        # later phases (tile sweep) must measure the winner's sections:
        # zero via the patch mechanism, tensor_parallel by pinning the
        # winning section into the base config _candidate_config copies
        self._zero_patch = best.get("zero_optimization")
        if "tensor_parallel" in best:
            self.base_config["tensor_parallel"] = dict(
                best["tensor_parallel"]
            )
        return best

    def tune(self) -> Dict[str, Any]:
        """Returns the best config patch: {micro_batch, remat_policy,
        throughput} plus, when the flash tile sweep improved on it,
        tpu_kernels-style {flash_block_q, flash_block_k} keys, and the
        zero_optimization section phase 0 settled on (when it ran).
        Planner mode (see module docstring) replaces the
        compile-and-time ladder with a static search + top-k measure."""
        if self._planner_mode():
            best = self._tune_planner()
            return self._sweep_tiles(best)
        return self._sweep_tiles(self._tune_ladder())

    def _tune_ladder(self) -> Dict[str, Any]:
        """Phases 0+1, classic: walk the ZeRO ladder and the (micro,
        remat) grid by compiling, pruning on runtime OOM."""
        best = None
        oom_at = None
        zero = self._pick_zero_stage()
        # every record carries the phase-0 section so best == the max
        # record and each rec round-trips through result_to_config_patch
        zrec = {} if zero is None else {"zero_optimization": zero}
        for mb, pol in self._candidates():
            if (zero is not None and (mb, pol) == (1, REMAT_POLICIES[-1])
                    and self._probe_tput is not None):
                # the phase-0 probe already measured this exact point
                tput = self._probe_tput
                rec = {"micro_batch": mb, "remat_policy": pol,
                       "throughput": tput, **zrec}
                self.results.append(rec)
                if best is None or tput > best["throughput"]:
                    best = rec
                continue
            if oom_at is not None and self.fast and mb >= oom_at:
                continue
            tput = self._measure(mb, pol)
            if tput is None:
                if pol == REMAT_POLICIES[-1]:  # OOM even at max remat
                    oom_at = mb
                continue
            rec = {"micro_batch": mb, "remat_policy": pol,
                   "throughput": tput, **zrec}
            self.results.append(rec)
            log_dist(f"autotune: mb={mb} remat={pol}: {tput:.0f} tok/s")
            if best is None or tput > best["throughput"]:
                best = rec
        if best is None:
            raise RuntimeError("autotuning found no runnable configuration")
        return best

    def _sweep_tiles(self, best: Dict[str, Any]) -> Dict[str, Any]:
        """Phases 2+3: the flash tile sweep on the winning (mb, remat).
        Tile shapes are plan-invariant (the traced program does not
        change with kernel block sizes), so this stays a measured
        refinement in planner mode too."""
        # records carry the winner's zero section so every rec keeps
        # round-tripping through result_to_config_patch
        zrec = (
            {"zero_optimization": best["zero_optimization"]}
            if "zero_optimization" in best else {}
        )
        # phase 2: flash tile sweep on the winning (mb, remat)
        if self._flash_tunable():
            for blocks in FLASH_BLOCKS[1:]:
                tput = self._measure(
                    best["micro_batch"], best["remat_policy"], blocks
                )
                if tput is None:
                    continue
                rec = {
                    "micro_batch": best["micro_batch"],
                    "remat_policy": best["remat_policy"],
                    "flash_block_q": blocks[0],
                    "flash_block_k": blocks[1],
                    "throughput": tput,
                    **zrec,
                }
                self.results.append(rec)
                log_dist(
                    f"autotune: blocks={blocks}: {tput:.0f} tok/s"
                )
                if tput > best["throughput"]:
                    best = rec
            # phase 3: backward tiles on the winner — the dq/dkv kernels'
            # operand mix differs from the fwd's, so their best shape is
            # its own small search (0,0 = inherit fwd, the phase-2 result)
            fwd = (best.get("flash_block_q", 0), best.get("flash_block_k", 0))
            for bwd in FLASH_BLOCKS_BWD:
                blocks = (*fwd, *bwd)
                tput = self._measure(
                    best["micro_batch"], best["remat_policy"], blocks
                )
                if tput is None:
                    continue
                rec = {
                    "micro_batch": best["micro_batch"],
                    "remat_policy": best["remat_policy"],
                    "flash_block_q": fwd[0], "flash_block_k": fwd[1],
                    "flash_block_q_bwd": bwd[0], "flash_block_k_bwd": bwd[1],
                    "throughput": tput,
                    **zrec,
                }
                self.results.append(rec)
                log_dist(f"autotune: bwd blocks={bwd}: {tput:.0f} tok/s")
                if tput > best["throughput"]:
                    best = rec
        return best


def result_to_config_patch(rec: Dict[str, Any]) -> Dict[str, Any]:
    """A tuner record → ds_config fragment, mergeable into any base config
    (the round-trip contract: sweep/tune output feeds straight back into
    `deepspeed_tpu.initialize(config=...)`)."""
    patch: Dict[str, Any] = {
        "train_micro_batch_size_per_gpu": int(rec["micro_batch"]),
        "activation_checkpointing": {"policy": rec["remat_policy"]},
    }
    bq, bk = rec.get("flash_block_q", 0), rec.get("flash_block_k", 0)
    if bq or bk:
        patch["tpu_kernels"] = {"flash_block_q": int(bq),
                                "flash_block_k": int(bk)}
    bqb = rec.get("flash_block_q_bwd", 0)
    bkb = rec.get("flash_block_k_bwd", 0)
    if bqb or bkb:
        patch.setdefault("tpu_kernels", {}).update(
            flash_block_q_bwd=int(bqb), flash_block_k_bwd=int(bkb)
        )
    if "zero_optimization" in rec:
        patch["zero_optimization"] = dict(rec["zero_optimization"])
    if "tensor_parallel" in rec:
        # planner-mode records carry the full section the candidate
        # measured (tp_size + the decided overlap_comm), so the
        # wholesale-replace merge semantics stay lossless
        patch["tensor_parallel"] = dict(rec["tensor_parallel"])
    return patch


def autotune(model, base_config, topology=None, sample_batch_fn=None):
    return Autotuner(model, base_config, topology, sample_batch_fn).tune()
