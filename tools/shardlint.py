#!/usr/bin/env python
"""shardlint CLI: statically lint engine configs for sharding hazards.

    python tools/shardlint.py examples/ds_config_zero3.json
    python tools/shardlint.py --all-examples --json /tmp/shardlint.json
    python tools/shardlint.py cfg.json --rules R2,R3
    python tools/shardlint.py --all-examples --report [--hbm-gb 16]

Each config builds an *abstract* engine (abstract_init — state is
ShapeDtypeStructs, nothing materializes), traces the jitted train step to
a jaxpr on a CPU mesh, and runs the R1–R11 rule registry
(docs/shardlint.md; e.g. ``--rules R9,R10,R11`` for the paritylint
subset). Exit code 1 on any error-severity finding — wire
``--all-examples`` into the tier-1 flow as the pre-TPU correctness gate
(it covers every shipped examples/*.json plus the 410M and 1.5B targets
of deepspeed_tpu/analysis/targets.py, including the double-buffered
offload stream).

``--report`` additionally prints the analysis/cost planner table per
config (docs/memory_planner.md); ``--hbm-gb N`` arms rule R6 so a
config whose estimated peak exceeds the budget exits 1 before anything
compiles. ``tools/shardplan.py`` is the planner-first spelling of the
same flow.
"""

import argparse
import json
import os
import sys
import time

# force the CPU backend BEFORE jax loads: the lint is abstract and must
# not take the chip, and the lint mesh wants the 8 virtual host devices
# the test suite uses
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    )

REPO_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_DIR not in sys.path:
    sys.path.insert(0, REPO_DIR)


def default_model_for(cfg):
    """A tiny model shaped to satisfy the config's structural demands
    (layer count divisible by pipeline stages; a routed-expert MLP with
    ep-divisible experts when the config enables MoE — a dense model
    would trace no expert exchange and the moe lint would be vacuous).
    Lint findings are about the *step program structure*, which the
    config — not the model size — determines."""
    stages = max(1, cfg.pipeline.stages)
    layers = max(4, stages * 2)
    if layers % stages:
        layers = stages * ((layers // stages) + 1)
    if cfg.moe.enabled:
        from deepspeed_tpu.models import mixtral

        return mixtral(
            "mixtral-tiny",
            vocab_size=512,
            max_seq_len=64,
            num_layers=layers,
            num_experts=max(2, cfg.moe.ep_size, cfg.moe.num_experts),
        )
    from deepspeed_tpu.models import gpt2

    return gpt2(
        "gpt2-tiny",
        vocab_size=512,
        max_seq_len=64,
        num_layers=layers,
        num_heads=4,
        hidden_size=64,
        intermediate_size=128,
    )


def iter_targets(args):
    """Yield (name, model_or_None, config_dict) lint targets."""
    for path in args.configs:
        with open(path) as f:
            yield os.path.basename(path), None, json.load(f)
    if args.all_examples:
        ex_dir = os.path.join(REPO_DIR, "examples")
        for fn in sorted(os.listdir(ex_dir)):
            if fn.endswith(".json"):
                with open(os.path.join(ex_dir, fn)) as f:
                    yield f"examples/{fn}", None, json.load(f)
        import jax

        from deepspeed_tpu.analysis import targets

        for name, model, cfg in targets.lint_targets(len(jax.devices())):
            yield name, model, cfg
        # the autotuner's ladder rungs are configs too (ISSUE 7): the
        # planner-driven search only measures rungs that lint clean
        for name, model, cfg in targets.autotune_rung_targets(
            len(jax.devices())
        ):
            yield name, model, cfg


def run_lint(args, collect_plan=False):
    """One definition of the per-target lint loop (shardplan delegates
    here): normalize the shared --rules/--hbm-gb flags, build each
    target's abstract engine, lint it, aggregate into a Report;
    NotImplementedError targets (what abstract_init refuses) are recorded
    as skipped, not silently passed."""
    only = (
        [r.strip().upper() for r in args.rules.split(",") if r.strip()]
        if args.rules
        else None
    )
    budget = (
        args.hbm_gb * (1 << 30) if args.hbm_gb is not None else None
    )

    import deepspeed_tpu.comm as comm
    from deepspeed_tpu.analysis import Report, lint_config
    from deepspeed_tpu.config import DeepSpeedConfig

    report = Report()
    for name, model, cfg_dict in iter_targets(args):
        t0 = time.time()
        try:
            comm.destroy_process_group()  # each target shapes its own mesh
            cfg = DeepSpeedConfig(cfg_dict)
            if model is None:
                model = default_model_for(cfg)
            sub = lint_config(
                cfg_dict, model=model, source=name, only=only,
                hbm_budget_bytes=budget, collect_plan=collect_plan,
            )
            report.extend(sub.findings)
            report.sources.extend(sub.sources)
            report.plans.extend(sub.plans)
        except NotImplementedError as e:
            report.add_source(name, time.time() - t0, 0,
                              skipped=str(e).splitlines()[0][:120])
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="shardlint", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("configs", nargs="*", help="ds_config.json paths")
    ap.add_argument("--all-examples", action="store_true",
                    help="lint every shipped examples/*.json plus the "
                         "analysis/targets.py 410M/1.5B targets")
    ap.add_argument("--json", metavar="PATH",
                    help="write the machine-readable report here "
                         "('-' for stdout)")
    ap.add_argument("--rules", metavar="IDS",
                    help="comma-separated rule subset (e.g. R2,R3)")
    ap.add_argument("--report", action="store_true",
                    help="print the cost-planner table per config "
                         "(params / opt / activations / peak GiB, ICI "
                         "GiB/step, est. step_s — analysis/cost)")
    ap.add_argument("--hbm-gb", type=float, metavar="N",
                    help="per-device HBM budget in GiB; arms rule R6 "
                         "(exit 1 when a config's estimated peak exceeds "
                         "it)")
    args = ap.parse_args(argv)
    if not args.configs and not args.all_examples:
        ap.error("no targets: pass config paths and/or --all-examples")

    report = run_lint(args, collect_plan=args.report)
    print(report.format())
    if args.json:
        payload = report.to_json(indent=2)
        if args.json == "-":
            print(payload)
        else:
            with open(args.json, "w") as f:
                f.write(payload)
    return 0 if report.ok else 1


if __name__ == "__main__":
    sys.exit(main())
