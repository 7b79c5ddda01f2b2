"""Training-config sweep on the real chip: micro-batch x remat x flash tiles.

A thin CLI over the in-framework Autotuner (autotuning/autotuner.py) — ONE
compile+measure engine for both tuners, so they cannot drift. The grid runs
on the bench model (bench.py's definition), prints one JSON line per point,
and writes the winner to SWEEP_BEST.json at the repo root in TWO shapes:
the raw record, and a ds_config `config_patch` that merges straight into
`deepspeed_tpu.initialize(config=...)`. bench.py seeds its OOM ladder from
this file, so a committed sweep means the bench never burns a known-doomed
compile again.

Each grid point runs in its OWN child process (the reference autotuner also
launches every experiment as a separate ranked process): on a 16GB chip an
OOM can leave the in-process backend client wedged, after which every later
candidate fails instantly with the same RESOURCE_EXHAUSTED — observed as a
whole sweep of spurious "OOM, pruned" rows. A fresh process per point makes
candidates independent; a hung child costs its own timeout, not the sweep.
The parent never imports jax, so each child in turn can take the chip.

Usage:    python tools/sweep_train.py            # default grid
          python tools/sweep_train.py --quick    # 3 configs
          python tools/sweep_train.py --no-write # don't update SWEEP_BEST
          python tools/sweep_train.py --in-process  # old single-process mode
CPU smoke: BENCH_SMOKE=1 (tiny model, interpret kernels).
"""

import argparse
import itertools
import json
import os
import subprocess
import sys

REPO_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_DIR)

SWEEP_BEST = os.path.join(REPO_DIR, "SWEEP_BEST.json")
POINT_TIMEOUT_S = 600  # compile + trials for one candidate
PROBE_TIMEOUT_S = 120  # tiny device-count child; a wedged chip fails fast


def build_tuner():
    from bench import bench_model_and_data, smoke_mode
    from deepspeed_tpu.autotuning.autotuner import Autotuner
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    smoke = smoke_mode()
    enable_compile_cache()
    model, data, B, S = bench_model_and_data(smoke)

    def sample_batch(train_batch_size):
        # grid micros divide B: accum = B // (micro * dp) keeps the global
        # batch (and the data dict) identical across every point
        assert train_batch_size == B, (train_batch_size, B)
        return dict(data)

    tuner = Autotuner(
        model,
        base_config={
            "train_batch_size": B,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": 0},
            "gradient_clipping": 1.0,
            "autotuning": {"start_profile_step": 1, "end_profile_step": 6,
                           "fixed_global_batch": True},
        },
        sample_batch_fn=sample_batch,
    )
    return tuner, B, S, smoke


def device_count_subprocess() -> int:
    """Device count via a throwaway child: the parent must never hold the
    TPU client itself — a local chip is process-exclusive and the children
    are the ones that need it. A failed probe aborts the sweep: guessing
    dp=1 on a multi-device machine would fail the batch triangle in every
    child and record a full grid of spurious error rows."""
    try:
        proc = subprocess.run(
            [sys.executable, "-c",
             "import jax; print(len(jax.devices()), jax.default_backend())"],
            capture_output=True, text=True, timeout=PROBE_TIMEOUT_S,
        )
        n, backend = (proc.stdout or "").strip().splitlines()[-1].split()
        if backend == "cpu" and "cpu" not in os.environ.get("JAX_PLATFORMS", ""):
            # jax fell back to CPU (e.g. the accelerator is transiently
            # held) — trusting its device count would hand the children a
            # wrong dp and fail the batch triangle on every point
            raise SystemExit(
                "sweep: device probe landed on the CPU backend but "
                "JAX_PLATFORMS does not request cpu; refusing to guess dp"
            )
        return max(int(n), 1)
    except SystemExit:
        raise
    except Exception as e:
        tail = ""
        if isinstance(e, subprocess.TimeoutExpired):
            tail = f"probe timed out after {PROBE_TIMEOUT_S}s"
        elif "proc" in locals():
            tail = (proc.stderr or "").strip().splitlines()[-1:]
            tail = tail[0] if tail else repr(e)
        else:
            tail = repr(e)
        raise SystemExit(f"sweep: device probe failed ({tail}); "
                         "is the accelerator pool up?")


def default_grid(B, dp):
    # batch triangle: B == micro * accum * dp, so micro tops out at B // dp
    mb_full = max(B // dp, 1)
    micros = [mb_full, max(mb_full // 2, 1)]
    policies = ["none", "dots_flash", "dots_saveable"]
    # (0,0) = kernel defaults (512x512 as of the v5e tile measurement);
    # 512x1024 is the measured S=2048 winner; 256x256 guards against a
    # shape where the bigger defaults regress
    tiles = [(0, 0), (512, 1024), (256, 256)]
    grid = list(itertools.product(micros, policies, tiles))
    # the committed winner's neighborhood measures FIRST: the pool drops
    # without warning, and the incremental SWEEP_BEST write means a partial
    # window still refreshes a good seed instead of a pile of OOM rows
    try:
        with open(SWEEP_BEST) as f:
            seed = (json.load(f) or {}).get("best") or {}
        s_mb, s_pol = int(seed["micro_batch"]), str(seed["remat_policy"])

        def rank(point):
            mb, pol, _ = point
            return (mb != s_mb, pol != s_pol)

        grid.sort(key=rank)
    except Exception:
        pass
    return grid


def parse_point(spec: str):
    """MICRO,POLICY,BQ,BK[,BQ_BWD,BK_BWD] → (micro, policy, blocks)."""
    parts = spec.split(",")
    if len(parts) not in (4, 6):
        raise SystemExit(
            f"sweep: bad point spec {spec!r} "
            "(want MICRO,POLICY,BQ,BK[,BQ_BWD,BK_BWD])")
    try:
        return (int(parts[0]), parts[1], tuple(int(x) for x in parts[2:]))
    except ValueError:
        raise SystemExit(f"sweep: non-integer field in point spec {spec!r}")


def run_one(point_csv: str) -> None:
    """Child mode: measure exactly one point and print its record as the
    final JSON line."""
    tuner, _, _, _ = build_tuner()
    [rec] = tuner.measure_grid([parse_point(point_csv)])
    print("SWEEP_POINT " + json.dumps(rec), flush=True)


def measure_point_subprocess(point):
    micro, pol, blocks = point
    csv = ",".join([str(micro), pol, *map(str, blocks)])
    cmd = [sys.executable, os.path.abspath(__file__), "--one", csv]
    rec = {"micro_batch": int(micro), "remat_policy": pol,
           "flash_block_q": int(blocks[0]), "flash_block_k": int(blocks[1])}
    if len(blocks) > 2 and (blocks[2] or blocks[3]):
        rec["flash_block_q_bwd"] = int(blocks[2])
        rec["flash_block_k_bwd"] = int(blocks[3])
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, cwd=REPO_DIR,
            timeout=POINT_TIMEOUT_S,
        )
    except subprocess.TimeoutExpired:
        rec.update(throughput=None, error=f"timeout {POINT_TIMEOUT_S}s")
        return rec
    for line in reversed((proc.stdout or "").splitlines()):
        if line.startswith("SWEEP_POINT "):
            return json.loads(line[len("SWEEP_POINT "):])
    tail = ((proc.stderr or "") + (proc.stdout or "")).strip().splitlines()
    rec.update(throughput=None,
               error=f"child rc={proc.returncode}: "
                     + (tail[-1][:160] if tail else "no output"))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--no-write", action="store_true",
                    help="don't update SWEEP_BEST.json")
    ap.add_argument("--in-process", action="store_true",
                    help="measure every point in this process (no isolation)")
    ap.add_argument("--one", default=None, metavar="MICRO,POLICY,BQ,BK",
                    help="child mode: measure one point and exit")
    ap.add_argument("--points", default=None,
                    metavar="MICRO,POLICY,BQ,BK[;...]",
                    help="measure exactly these points instead of the "
                         "default grid (SWEEP_BEST still updates if one "
                         "of them wins)")
    ap.add_argument("--fresh", action="store_true",
                    help="ignore the committed SWEEP_BEST record: this "
                         "run's own best wins even if slower (use after a "
                         "hardware/code change makes the old record "
                         "unreproducible)")
    args = ap.parse_args()

    if args.one:
        run_one(args.one)
        return

    from bench import smoke_mode

    smoke = smoke_mode()
    in_process = args.in_process or smoke  # smoke: child spawn is overhead
    if args.points:
        # explicit points: no device probe (the children discover the
        # backend themselves), no --quick/smoke truncation — "exactly
        # these points" means exactly these points
        grid = [parse_point(spec)
                for spec in filter(None, args.points.split(";"))]
        if not grid:
            raise SystemExit("sweep: --points named no points")
        if in_process:
            tuner, B, S, smoke = build_tuner()
        else:
            from bench import bench_dims

            B, S = bench_dims(smoke)
    elif in_process:
        tuner, B, S, smoke = build_tuner()
        import jax

        grid = default_grid(B, max(len(jax.devices()), 1))
        if args.quick or smoke:
            grid = grid[:3]
    else:
        # the parent only needs the grid geometry; the model compiles in
        # the children. B/S come from the bench definition without jax.
        from bench import bench_dims

        B, S = bench_dims(smoke)
        grid = default_grid(B, device_count_subprocess())
        if args.quick:
            grid = grid[:3]

    from deepspeed_tpu.autotuning.autotuner import result_to_config_patch

    write = not args.no_write and not smoke

    def build_out(best):
        out = {"best": best}
        if best is not None:
            out["config_patch"] = result_to_config_patch(best)
        return out

    def save_best(best):
        out = build_out(best)
        if best is not None and write:
            # incremental: a stage-level kill (campaign timeout, pool drop)
            # must not discard points already measured
            with open(SWEEP_BEST, "w") as f:
                json.dump(out, f, indent=1)
        return out

    # SWEEP_BEST is a high-water mark: a focused --points run (or a noisy
    # re-measure of the committed winner) must not replace the record with
    # a slower point, so the incumbent competes as this run's baseline.
    # --fresh drops the incumbent when the old record is unreproducible
    # (hardware/topology/code change).
    best = None
    if not args.fresh:
        try:
            with open(SWEEP_BEST) as f:
                incumbent = (json.load(f) or {}).get("best") or None
            if incumbent and incumbent.get("tok_s"):
                best = incumbent
        except Exception:
            pass
    measured = 0
    for point in grid:
        if in_process:
            [rec] = tuner.measure_grid([point])
        else:
            rec = measure_point_subprocess(point)
        if rec.get("throughput"):
            measured += 1
            rec = dict(rec, step_s=round(B * S / rec["throughput"], 4),
                       tok_s=round(rec["throughput"], 1))
            if best is None or rec["tok_s"] > best["tok_s"]:
                best = rec
                save_best(best)
        print(json.dumps(rec), flush=True)

    # final line reports the standing record; the file was already written
    # incrementally on every improvement, so a no-improvement run leaves
    # SWEEP_BEST untouched (a slower re-measure must not regenerate the
    # record or strip fields save_best doesn't produce)
    print(json.dumps(build_out(best)))
    if not measured:
        # every point errored/OOMed/timed out — callers (rebench watcher,
        # campaign) must see this as a failed run, not a quiet no-op
        raise SystemExit(1)


if __name__ == "__main__":
    main()
