#!/usr/bin/env python
"""shardplan CLI: static HBM-capacity + collective-cost plans per config.

    python tools/shardplan.py examples/ds_config_zero3.json
    python tools/shardplan.py cfg.json --hbm-gb 16
    python tools/shardplan.py --all-examples --json -

Every config builds an *abstract* engine (state is ShapeDtypeStructs,
nothing materializes), traces the jitted train step to a jaxpr on a CPU
mesh, and budgets it with analysis/cost (docs/memory_planner.md): per
device, parameter / optimizer / master-weight bytes from the state
shardings, the activation live-set high-water mark through
scan/remat/donation, collective scratch and offload double-buffer slots,
ICI wire bytes per mesh axis, and the analytic roofline step time. The
full R1–R8 shardlint registry runs on the same trace — ``--hbm-gb N``
arms rule R6, so a config whose estimated peak exceeds the budget exits
1 *before anything compiles* (the static OOM check).

Seconds per config on CPU; the 1.5B offload leg plans without
allocating a byte of state.
"""

import argparse
import os
import sys

REPO_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS_DIR = os.path.dirname(os.path.abspath(__file__))
for p in (REPO_DIR, TOOLS_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)

# importing the shardlint CLI forces the CPU backend (JAX_PLATFORMS +
# XLA_FLAGS) at module import, BEFORE jax can load — ONE copy of the dance
import shardlint as shardlint_cli


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="shardplan", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("configs", nargs="*", help="ds_config.json paths")
    ap.add_argument("--all-examples", action="store_true",
                    help="plan every shipped examples/*.json plus the "
                         "analysis/targets.py 410M/1.5B targets")
    ap.add_argument("--hbm-gb", type=float, metavar="N",
                    help="per-device HBM budget in GiB; arms rule R6 — "
                         "exit 1 when a config's estimated peak exceeds "
                         "it (unset: R6 stays silent; the table's budget "
                         "column shows the detected generation's "
                         "capacity for reference only)")
    ap.add_argument("--json", metavar="PATH",
                    help="write the machine-readable report here "
                         "('-' for stdout)")
    ap.add_argument("--rules", metavar="IDS",
                    help="comma-separated rule subset to lint alongside "
                         "the plan (default: all)")
    args = ap.parse_args(argv)
    if not args.configs and not args.all_examples:
        ap.error("no targets: pass config paths and/or --all-examples")

    # delegate to the shardlint CLI's shared lint loop (target iteration,
    # flag normalization, default model shaping, skip handling) — one
    # definition of "every shipped config and standing target", planner table
    # always on
    report = shardlint_cli.run_lint(args, collect_plan=True)
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
