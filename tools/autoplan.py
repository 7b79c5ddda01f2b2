#!/usr/bin/env python
"""autoplan CLI: planner-driven autotuning + the drift-regression gate.

    python tools/autoplan.py examples/ds_config_zero3.json --hbm-gb 16
    python tools/autoplan.py --leg 410m --hbm-gb 16 --explain
    python tools/autoplan.py --leg 410m --dryrun-mesh 8x1,4x2,2x4
    python tools/autoplan.py --check --leg 410m-lite --hbm-gb 1 --top-k 2
    python tools/autoplan.py --campaign --gen cpu --leg 410m-lite --tp 2

Default mode is **static**: enumerate the config's full candidate space
(zero stage × offload × remat × micro-batch, tp-overlap and serving
token_budget when the config has those axes, mesh shapes with
``--dryrun-mesh``) through analysis/cost abstract traces, R6-prune
everything statically over the ``--hbm-gb`` budget, and print the
ranked survivors — seconds on CPU, nothing compiles. ``--explain``
prints the full table including WHY each pruned rung lost (the R6
breakdown, or the memoized derivation that skipped its trace).

``--check`` is the drift-regression gate (ISSUE 7 satellite, wired into
CI): run the planner-driven Autotuner on the chosen leg — compile and
measure only the top-k — bank every (predicted, measured) pair into the
drift ledger, cross-check the winner's predicted HBM peak against XLA's
``memory_analysis()``, and exit 1 when any pair leaves the documented
band (docs/autotuning.md "Drift bands"). Legs:

- ``410m``      the 410M target of analysis/targets.py (full size —
                minutes per measured step on CPU; meant for TPU hosts
                or patient operators)
- ``410m-lite`` the same llama family scaled to hidden 512 / 4 layers /
                seq 256: the CPU-mesh CI leg (a couple of minutes total)
- ``1b``        the 1.4B ZeRO-3 offload leg (static modes only)

``--campaign`` is the knob-lattice measurement campaign (docs/
autotuning.md "Campaign mode"): enumerate every overlap/wire/prefetch
knob combination through the same R6-pruned, roofline-ranked search,
compile+measure only the top-k, bank every pair into the drift ledger
tagged ``campaign``, and emit a default-table row keyed by (gen, mesh
topology, model class) that ``config.py`` consults whenever one of
those knobs is spelled ``"auto"``. The run closes its own loop: a
fresh all-"auto" config must re-resolve onto the emitted winner or the
exit code is 1. Runs end-to-end on a CPU host with ``--gen cpu``.
"""

import argparse
import json
import os
import sys
import time

REPO_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS_DIR = os.path.dirname(os.path.abspath(__file__))
for p in (REPO_DIR, TOOLS_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)

# ONE copy of the CPU-backend dance (JAX_PLATFORMS + XLA_FLAGS before jax
# loads) — the shardlint CLI owns it
import shardlint as shardlint_cli  # noqa: E402


def leg_model(leg: str, seq: int = None):
    """(model, base_seq) for a named leg. ``410m-lite`` is the
    CPU-gate proxy: same llama family, scaled so a measured step is
    seconds, not minutes."""
    from deepspeed_tpu.models import llama

    if leg == "410m-lite":
        S = seq or 256
        return llama(
            "llama-tiny", vocab_size=8192, max_seq_len=S, hidden_size=512,
            num_layers=4, num_heads=8, num_kv_heads=4, head_dim=64,
            intermediate_size=2048,
        ), S
    from deepspeed_tpu.analysis import targets

    return targets.target_model("1b" if leg == "1b" else "410m"), targets.SEQ


def leg_base_config(args) -> dict:
    """The base ds_config the search enumerates over for a --leg run: no
    zero section (so the ladder is an axis), bf16, the tuner knobs from
    the CLI."""
    return {
        "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
        "bf16": {"enabled": True},
        "gradient_clipping": 1.0,
        "steps_per_print": 10**9,
        "autotuning": {
            "max_train_micro_batch_size_per_gpu": args.max_micro,
            "top_k": args.top_k,
            "trials": args.trials,
            "start_profile_step": 1,
            "end_profile_step": 1 + args.steps,
            "planner": True,
            **({"hbm_gb": args.hbm_gb} if args.hbm_gb is not None else {}),
            **({"drift_ledger": args.ledger} if args.ledger else {}),
        },
    }


def parse_meshes(spec: str):
    """"8x1,4x2" → [(8, 1), (4, 2)] (dp x tp factorizations).

    A ``*`` factors the data axis across the DCN boundary (ISSUE 17):
    "2*2x2" → (2, 2, 2), a hybrid dcn_dp=2 x fsdp=2 x tp=2 mesh whose
    outer dp hop prices at DCN bandwidth."""
    out = []
    for part in spec.split(","):
        part = part.strip().lower()
        if not part:
            continue
        data, tp = part.split("x")
        if "*" in data:
            dcn_dp, fsdp = data.split("*")
            out.append((int(dcn_dp), int(fsdp), int(tp)))
        else:
            out.append((int(data), int(tp)))
    return out


def static_search(args, model, base_config):
    from deepspeed_tpu.autotuning import PlannerSearch

    budget = args.hbm_gb * (1 << 30) if args.hbm_gb is not None else None
    search = PlannerSearch(
        model, base_config, topology=None, top_k=args.top_k,
        hbm_budget_bytes=budget,
        mesh_shapes=parse_meshes(args.dryrun_mesh)
        if args.dryrun_mesh else None,
    )
    return search.search()


def peak_ratio_vs_xla(model, cfg):
    """Predicted peak / XLA ``memory_analysis()`` peak for one config
    (the ISSUE-4 cross-check, run on the gate's anchor program). None
    when the backend does not report memory analysis."""
    import jax

    import deepspeed_tpu
    import deepspeed_tpu.comm as comm
    from deepspeed_tpu.analysis import plan_engine
    from deepspeed_tpu.analysis.shardlint import compiled_train_memory_peak

    comm.destroy_process_group()
    cfg = dict(cfg)
    cfg.pop("autotuning", None)
    engine, *_ = deepspeed_tpu.initialize(
        model=model, config=cfg, abstract_init=True
    )
    try:
        plan = plan_engine(engine, source="anchor")
        xla_peak, _ma = compiled_train_memory_peak(engine)
        if xla_peak is None:
            return None, None
        plan_peak = plan.peak_hbm_bytes
        if jax.default_backend() == "cpu":
            # the CPU lint mesh has no pinned-host memory space, so
            # XLA's accounting keeps offloaded state in its argument
            # column — add the plan's host column back for a
            # like-for-like comparison (0 for non-offload configs)
            plan_peak += plan.host_state_bytes
        return plan_peak / xla_peak, xla_peak
    finally:
        engine.destroy()


def run_check(args, model, base_config) -> int:
    """The drift-regression gate: planner-tune the leg, bank pairs,
    enforce the documented bands. Exit 1 on any violation."""
    import numpy as np

    from deepspeed_tpu.analysis.cost import drift
    from deepspeed_tpu.autotuning import Autotuner

    S = model.config.max_seq_len
    vocab = model.config.vocab_size
    rng = np.random.RandomState(0)

    def sample_batch(global_batch):
        return {"input_ids": rng.randint(0, vocab, size=(global_batch, S))}

    ledger_path = args.ledger or os.path.join(REPO_DIR, "perf",
                                              "drift.jsonl")
    base_config = dict(base_config)
    base_config["autotuning"] = dict(base_config["autotuning"],
                                     drift_ledger=ledger_path)
    t_start = time.time()
    tuner = Autotuner(model, base_config, sample_batch_fn=sample_batch)
    best = tuner.tune()
    assert tuner.last_search is not None, "planner mode did not engage"
    print(tuner.last_search.explain())
    problems = []
    if tuner.n_compiles > args.top_k:
        problems.append(
            f"compiled {tuner.n_compiles} candidates > top-k {args.top_k} "
            "(the prune-before-compile contract broke)"
        )

    ledger = drift.DriftLedger(ledger_path)
    fresh = [e for e in ledger.load()
             if e.get("ts", 0) >= t_start - 1
             and str(e.get("source", "")).startswith("autotune:")]
    if not fresh:
        problems.append("no drift entries banked — measured survivors "
                        "did not reach the ledger")
    ok, issues = drift.check(fresh)
    problems.extend(issues)

    # predicted peak vs XLA's own accounting, on the leg's CALIBRATED
    # anchor program (stage 0, no remat, micro 1 — the program the ±10%
    # tier-1 band was measured on; remat/offload winners have a looser,
    # documented liveness model and their drift is covered by the step
    # pairs above)
    anchor_cfg = dict(base_config)
    anchor_cfg.update({
        "train_micro_batch_size_per_gpu": 1,
        "activation_checkpointing": {"policy": "none"},
        "zero_optimization": {"stage": 0},
    })
    ratio, xla_peak = peak_ratio_vs_xla(model, anchor_cfg)
    if ratio is not None and not (
        drift.GATE_PEAK_BAND[0] <= ratio <= drift.GATE_PEAK_BAND[1]
    ):
        problems.append(
            f"anchor predicted/XLA HBM peak ratio {ratio:.3f} outside "
            f"{list(drift.GATE_PEAK_BAND)}"
        )

    summary = {
        "leg": args.leg or (args.configs[0] if args.configs else "?"),
        "winner": {k: best[k] for k in
                   ("micro_batch", "remat_policy", "throughput")
                   if k in best},
        "n_compiles": tuner.n_compiles,
        "top_k": args.top_k,
        "drift": drift.summarize(fresh),
        "anchor_peak_ratio_vs_xla": round(ratio, 4) if ratio else None,
        "ledger": ledger_path,
        "ok": not problems,
        "problems": problems,
    }
    # campaign-tagged pairs live in the same ledger but never mix into
    # the ad-hoc medians above (drift.check groups spread per tag) —
    # report them as their own section so table provenance is auditable
    campaign_rows = ledger.load(tag="campaign")
    if campaign_rows:
        summary["campaign_drift"] = drift.summarize(campaign_rows)
    recal = drift.recalibration_suggestion(ledger.load())
    if recal:
        summary["recalibration"] = recal
    print(json.dumps(summary))
    if problems:
        for p in problems:
            print(f"autoplan --check FAIL: {p}", file=sys.stderr)
        return 1
    return 0


def run_campaign_mode(args, model, base_config) -> int:
    """--campaign: enumerate the knob lattice, measure the top-k, bank
    campaign-tagged drift pairs, emit the default-table row, then prove
    the loop closes — a FRESH all-"auto" config resolved against the
    emitted table must land on the winner's settings. Exit 1 when the
    re-resolution misses or disagrees."""
    import numpy as np

    from deepspeed_tpu.autotuning import (
        emit_table,
        run_campaign,
        serving_ab,
        verify_roundtrip,
    )

    S = model.config.max_seq_len
    vocab = model.config.vocab_size
    rng = np.random.RandomState(0)

    def sample_batch(global_batch):
        return {"input_ids": rng.randint(0, vocab, size=(global_batch, S))}

    ledger_path = args.ledger or os.path.join(REPO_DIR, "perf",
                                              "drift.jsonl")
    table_path = args.table or os.path.join(
        REPO_DIR, "deepspeed_tpu", "analysis", "cost", "knob_defaults.json"
    )
    base_config = dict(base_config)
    if args.tp > 1:
        # arm the tp_overlap lattice axis (and the dpXxtpY topology the
        # row is keyed on)
        base_config["tensor_parallel"] = dict(
            base_config.get("tensor_parallel") or {}, tp_size=args.tp
        )
    budget = args.hbm_gb * (1 << 30) if args.hbm_gb is not None else None
    out = run_campaign(
        model, base_config,
        sample_batch_fn=sample_batch, top_k=args.top_k,
        hbm_budget_bytes=budget, drift_ledger_path=ledger_path,
    )
    print(out["search"].explain())
    problems = []
    row = out["row"]
    if row is None:
        problems.append("no lattice rung survived measurement — no table "
                        "row emitted")
    else:
        emit_table([row], table_path)
        rt = verify_roundtrip(base_config, table_path, model=model)
        resolved = rt["resolved"]
        for path, want in row["knobs"].items():
            if not isinstance(want, bool):
                continue  # wire codecs resolve downstream ("legacy-auto")
            got = resolved.get(path)
            if got is not want:
                problems.append(
                    f"re-resolution mismatch: {path} resolved to {got!r}, "
                    f"campaign shipped {want!r}"
                )
    serve = None
    if args.serve:
        # the serving half of the lattice: off-vs-on A/B per knob through
        # the same loop tools/bench_serve.py --campaign-ab uses; arms must
        # emit identical tokens (the knobs are layout/scheduling, never
        # numerics)
        serve = {}
        section = {"max_slots": 4, "token_budget": 16, "max_tokens": 32,
                   "queue_limit": 64, "request_timeout_s": 1e9}
        for knob in ("paged", "spec"):
            res = serving_ab(model, section, knob, requests=4, new_tokens=4)
            serve[knob] = res
            if not res.get("tokens_equal", False):
                problems.append(
                    f"serving A/B arms for {knob!r} emitted different "
                    "tokens — knob is not numerics-neutral"
                )
    summary = {
        "leg": args.leg or (args.configs[0] if args.configs else "?"),
        "row": ({k: row[k] for k in ("gen", "topology", "model_class",
                                     "knobs", "winner", "throughput")}
                if row else None),
        "skipped": out["skipped"],
        "banked": out["banked"],
        "table": table_path,
        "ledger": ledger_path,
        **({"serve": serve} if serve is not None else {}),
        "ok": not problems,
        "problems": problems,
    }
    print(json.dumps(summary))
    if problems:
        for p in problems:
            print(f"autoplan --campaign FAIL: {p}", file=sys.stderr)
        return 1
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="autoplan", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("configs", nargs="*", help="ds_config.json paths")
    ap.add_argument("--leg", choices=["410m", "410m-lite", "1b"],
                    help="search a named leg instead of a config")
    ap.add_argument("--top-k", type=int, default=3, metavar="K",
                    help="survivors to compile+measure (default 3)")
    ap.add_argument("--hbm-gb", type=float, metavar="N",
                    help="per-device HBM budget; arms the R6 static "
                         "pruner (unset: rank-only, nothing prunes)")
    ap.add_argument("--max-micro", type=int, default=8,
                    help="micro-batch axis upper bound (default 8)")
    ap.add_argument("--gen", metavar="GEN",
                    help="price a specific hardware generation "
                         "(v4/v5e/v5p/v6e/cpu) instead of detecting — "
                         "ask a CPU host what the v5e would do")
    ap.add_argument("--explain", action="store_true",
                    help="print the full table incl. why each pruned "
                         "rung lost")
    ap.add_argument("--dryrun-mesh", metavar="SHAPES",
                    help="comma list of dpxtp mesh shapes to enumerate "
                         "statically (e.g. 8x1,4x2,2x4); dcn_dp*fsdp "
                         "spellings (e.g. 2*2x2) build hybrid meshes "
                         "whose outer dp hop prices at DCN bandwidth")
    ap.add_argument("--check", action="store_true",
                    help="drift-regression gate: compile+measure top-k, "
                         "bank (predicted, measured) pairs, exit 1 when "
                         "any pair leaves the documented band")
    ap.add_argument("--campaign", action="store_true",
                    help="knob-lattice campaign: enumerate, measure "
                         "top-k, bank campaign-tagged drift pairs, emit "
                         "the per-(gen, topology, model-class) default "
                         "table row and prove a fresh all-\"auto\" config "
                         "re-resolves onto the winner (exit 1 otherwise)")
    ap.add_argument("--table", metavar="PATH",
                    help="--campaign: default-table target (default: the "
                         "packaged deepspeed_tpu/analysis/cost/"
                         "knob_defaults.json)")
    ap.add_argument("--tp", type=int, default=1, metavar="N",
                    help="--campaign: tensor-parallel degree; N>1 arms "
                         "the tp_overlap lattice axis on a dp x tp CPU "
                         "host mesh")
    ap.add_argument("--serve", action="store_true",
                    help="--campaign: also A/B the serving knobs (paged, "
                         "spec) through autotuning.serving_ab")
    ap.add_argument("--steps", type=int, default=1,
                    help="--check: measured steps per trial (default 1)")
    ap.add_argument("--trials", type=int, default=1,
                    help="--check: timing trials per candidate")
    ap.add_argument("--ledger", metavar="PATH",
                    help="drift ledger path (default perf/drift.jsonl "
                         "next to the repo, or SHARDPLAN_DRIFT_LEDGER)")
    ap.add_argument("--json", metavar="PATH",
                    help="write the machine-readable search result "
                         "('-' for stdout)")
    args = ap.parse_args(argv)
    if not args.configs and not args.leg:
        ap.error("no target: pass a ds_config.json or --leg")
    if (args.check or args.campaign) and not args.leg:
        ap.error(f"--{'check' if args.check else 'campaign'} needs a "
                 "--leg (it must build a runnable model + batch)")
    if args.gen:
        # the planner's HardwareModel.detect() honors this env pin
        os.environ["DSTPU_TPU_GEN"] = args.gen

    from deepspeed_tpu.config import DeepSpeedConfig

    if args.leg:
        model, _S = leg_model(args.leg)
        base_config = leg_base_config(args)
    else:
        with open(args.configs[0]) as f:
            base_config = json.load(f)
        base_config.setdefault("autotuning", {})
        base_config["autotuning"].setdefault("max_train_micro_batch_size_per_gpu",
                                             args.max_micro)
        model = shardlint_cli.default_model_for(DeepSpeedConfig(base_config))

    if args.campaign:
        return run_campaign_mode(args, model, base_config)
    if args.check:
        return run_check(args, model, base_config)

    result = static_search(args, model, base_config)
    if args.explain:
        print(result.explain())
    else:
        lines = result.explain().splitlines()
        # terse default: header + ranked survivors + the tail summary
        keep = [ln for ln in lines if not ln.lstrip().startswith("-")]
        print("\n".join(keep))
    if args.json:
        payload = json.dumps(result.to_dict(), indent=2)
        if args.json == "-":
            print(payload)
        else:
            with open(args.json, "w") as f:
                f.write(payload)
    return 0


if __name__ == "__main__":
    sys.exit(main())
