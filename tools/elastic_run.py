#!/usr/bin/env python
"""elastic_run: the reference elastic worker + the preemption oracle.

    # supervise an elastic CPU job: 2 workers x 2 devices, save every 2
    python tools/elastic_run.py --workdir /tmp/el --num-workers 2 --steps 8

    # resume an interrupted job from its latest committed tag
    python tools/elastic_run.py --workdir /tmp/el --resume --num-workers 1

    # the CI preemption oracle (ci.yml `preemption` job)
    python tools/elastic_run.py --oracle --workdir /tmp/el

Three modes over ``launcher/elastic.ElasticSupervisor`` +
``runtime/ckpt``:

- default (supervisor): spawn ``--num-workers`` ranks of this script's
  ``--worker`` mode as one ``jax.distributed`` CPU job; on a worker
  death, shrink the world to the survivors and relaunch. Workers always
  resume from the latest *committed* tag, resharding onto the new
  process layout. Survivors absorb the dead ranks' CPU devices
  (``total/nprocs`` each), so the GLOBAL mesh — and the loss
  all-reduce tree, the thing that makes "bitwise" a fair claim — is
  identical across rounds; what changes (and what restore regroups) is
  which process owns which shards.
- ``--worker`` (internal): one rank — tiny deterministic train loop,
  periodic (async) saves, rank 0 appends ``{round, step, loss}`` lines
  to ``losses.jsonl``. ``--die round:rank:step`` self-SIGTERMs at an
  exact step, which runs the runtime/ckpt preemption chain for real:
  final sync save (single-process rounds) then healthwatch's postmortem
  dump.
- ``--oracle``: the ISSUE-20 acceptance gate. Runs the uninterrupted
  baseline (1 worker, all devices), then an elastic run that is killed
  TWICE (round 0: one of two ranks dies mid-interval; round 1: the lone
  survivor dies → exercises the final preemption save), then asserts
  the per-step loss trajectory is BITWISE identical to the baseline
  across every mesh the job lived on, that the round-2 resume started
  exactly at the preemption save's step, and that every death left a
  postmortem that passes ``tools/healthwatch.py --validate``.

CPU-only, stdlib + repo imports; jax is imported only inside ``--worker``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_DIR not in sys.path:
    sys.path.insert(0, REPO_DIR)

SEED = 0
VOCAB, SEQ, BATCH = 256, 16, 8


def _die_specs(specs):
    out = []
    for s in specs or []:
        rnd, rank, step = (int(x) for x in s.split(":"))
        out.append((rnd, rank, step))
    return out


# ------------------------------------------------------------- worker
def run_worker(args) -> int:
    # Survivors absorb the dead ranks' devices: with --total-devices the
    # per-rank share is total/nprocs, so the GLOBAL mesh (and with it
    # the loss all-reduce tree — the thing that makes "bitwise" a fair
    # claim) is identical across rounds; only the process→shard mapping
    # changes, which is exactly what resharding-on-restore regroups.
    nprocs = int(os.environ.get("DSTPU_NUM_PROCESSES", "1"))
    devices_per_proc = (
        args.total_devices // nprocs if args.total_devices
        else args.devices_per_proc
    )
    # fresh interpreter: claim the rank's CPU devices BEFORE backend init
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    flags.append(
        f"--xla_force_host_platform_device_count={devices_per_proc}"
    )
    os.environ["XLA_FLAGS"] = " ".join(flags)
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", devices_per_proc)

    import numpy as np

    import deepspeed_tpu
    import deepspeed_tpu.comm as comm
    from deepspeed_tpu.comm import ParallelDims
    from deepspeed_tpu.launcher.elastic import ROUND_ENV
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.runtime.ckpt import install_preempt_handler

    rnd = int(os.environ.get(ROUND_ENV, "0"))
    world = devices_per_proc * nprocs
    topo = comm.init_distributed(dims=ParallelDims(dp=world))
    pid = jax.process_index()
    workdir = os.path.abspath(args.workdir)
    save_dir = os.path.join(workdir, "ckpt")

    model = gpt2("gpt2-tiny", vocab_size=VOCAB, max_seq_len=SEQ,
                 hidden_size=32, num_layers=1, num_heads=2)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, topology=topo, config={
            "train_batch_size": BATCH,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": args.zero_stage},
            "seed": SEED,
            "checkpoint": {
                "async_save": bool(args.async_save),
                "save_interval_steps": int(args.save_interval),
            },
            "healthwatch": {
                "enabled": True,
                "postmortem_path": os.path.join(
                    workdir, f"postmortem_round{rnd}_rank{pid}.json"
                ),
            },
        },
    )
    # resume from the latest committed tag (torn saves are invisible);
    # a fresh job finds nothing and starts at step 0
    engine.load_checkpoint(save_dir)
    start = engine.global_steps
    # arm the preemption chain before the first interval save too
    install_preempt_handler(engine, save_dir)
    dies = _die_specs(args.die)
    losses = os.path.join(workdir, "losses.jsonl")

    def batch(step):
        return {"input_ids": np.random.RandomState(1000 + step).randint(
            0, VOCAB, size=(BATCH, SEQ))}

    print(f"WORKER {pid} round {rnd}: world={world} start_step={start}",
          flush=True)
    for step in range(start, args.steps):
        loss = float(engine.train_batch(batch=batch(step)))
        if pid == 0:
            with open(losses, "a") as f:
                f.write(json.dumps(
                    {"round": rnd, "world": world, "step": step,
                     "loss": loss}) + "\n")
        if args.save_interval and (step + 1) % args.save_interval == 0:
            engine.save_checkpoint(save_dir)
        if (rnd, pid, step) in dies:
            import signal
            import time

            print(f"WORKER {pid} round {rnd}: SIGTERM self at step {step}",
                  flush=True)
            os.kill(os.getpid(), signal.SIGTERM)
            time.sleep(60)  # the ckpt/healthwatch chain exits; never reached
    engine.destroy()  # drains the async writer before exit
    print(f"WORKER {pid} round {rnd}: DONE at step {args.steps}", flush=True)
    return 0


# --------------------------------------------------------- supervisor
def run_supervisor(args) -> int:
    from deepspeed_tpu.launcher.elastic import ElasticSupervisor

    os.makedirs(os.path.abspath(args.workdir), exist_ok=True)
    worker_argv = [
        sys.executable, os.path.abspath(__file__), "--worker",
        "--workdir", os.path.abspath(args.workdir),
        "--steps", str(args.steps),
        "--save-interval", str(args.save_interval),
        "--zero-stage", str(args.zero_stage),
        "--devices-per-proc", str(args.devices_per_proc),
        "--total-devices", str(args.devices_per_proc * args.num_workers),
    ]
    if args.async_save:
        worker_argv.append("--async-save")
    for d in args.die or []:
        worker_argv += ["--die", d]
    sup = ElasticSupervisor(
        worker_argv,
        num_workers=args.num_workers,
        min_workers=args.min_workers,
        env={"PYTHONPATH": REPO_DIR + os.pathsep
             + os.environ.get("PYTHONPATH", "")},
    )
    rc = sup.run()
    print(f"elastic_run: supervisor rc={rc} rounds={sup.rounds}", flush=True)
    return rc


# ------------------------------------------------------------- oracle
def _read_losses(path):
    entries = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                entries.append(json.loads(line))
    return entries


def run_oracle(args) -> int:
    import copy
    import glob
    import subprocess

    workdir = os.path.abspath(args.workdir)
    os.makedirs(workdir, exist_ok=True)
    # the supervisor stays off jax: workers are fresh interpreters, and a
    # parent that has touched jax holds the chip they need
    num_workers, dpp = args.num_workers, args.devices_per_proc
    total_devices = dpp * num_workers
    die_mid = args.steps // 2          # inside an interval, after a commit
    die_late = args.steps - 2          # lone survivor: final preempt save

    def leg(subdir, num_workers, devices_per_proc, dies):
        a = copy.copy(args)
        a.workdir = os.path.join(workdir, subdir)
        a.num_workers = num_workers
        a.devices_per_proc = devices_per_proc
        a.die = dies
        rc = run_supervisor(a)
        if rc != 0:
            raise SystemExit(f"oracle: {subdir} leg failed rc={rc}")
        return _read_losses(os.path.join(a.workdir, "losses.jsonl"))

    # 1) uninterrupted baseline: one process owning every device, async
    #    saves ON (their overlap must not perturb the trajectory)
    base = leg("baseline", 1, total_devices, [])
    ref = {}
    for e in base:
        assert e["step"] not in ref, f"baseline logged step {e['step']} twice"
        ref[e["step"]] = e["loss"]
    assert sorted(ref) == list(range(args.steps)), sorted(ref)

    # 2) elastic run killed twice: round 0 loses its last rank
    #    mid-interval (multi-worker: resume reshards onto the survivor
    #    mesh); round 1's lone survivor is preempted -> final sync save
    #    -> round 2 resumes at that exact step
    elas = leg(
        "elastic", num_workers, dpp,
        [f"0:{num_workers - 1}:{die_mid}", f"1:0:{die_late}"],
    )

    # 3) bitwise loss-trajectory oracle, across every mesh the job used
    seen = {}
    rounds = set()
    for e in elas:
        rounds.add(e["round"])
        step, loss = e["step"], e["loss"]
        if step in seen and seen[step] != loss:
            raise SystemExit(
                f"oracle: step {step} re-ran with a different loss: "
                f"{seen[step]} != {loss} (resume is not deterministic)"
            )
        seen[step] = loss
        if ref[step] != loss:
            raise SystemExit(
                f"oracle: step {step} loss {loss!r} != baseline "
                f"{ref[step]!r} (world={e['world']}, round={e['round']})"
            )
    assert sorted(seen) == list(range(args.steps)), (
        f"oracle: elastic run missed steps: {sorted(set(ref) - set(seen))}"
    )
    assert rounds == {0, 1, 2}, f"expected 3 rounds, saw {sorted(rounds)}"
    # round 1 resumes from round 0's death: multi-worker rounds restart
    # at the last committed interval tag (die_mid sits right on one)
    r1_start = min(e["step"] for e in elas if e["round"] == 1)
    assert r1_start == die_mid, (
        f"oracle: round 1 resumed at {r1_start}, expected {die_mid}"
    )
    # round 1's lone survivor completes step die_late, then SIGTERMs:
    # the preemption save commits die_late+1 steps, so round 2 must
    # resume one past the kill — resuming AT die_late would mean it fell
    # back to the last interval tag, i.e. the final sync save was lost
    r2_steps = [e["step"] for e in elas if e["round"] == 2]
    assert r2_steps and min(r2_steps) == die_late + 1, (
        f"oracle: round 2 resumed at {min(r2_steps) if r2_steps else None}, "
        f"expected {die_late + 1} (preemption save missing?)"
    )

    # 4) every death dumped a postmortem that validates green
    pms = sorted(glob.glob(os.path.join(workdir, "elastic", "postmortem_*")))
    assert pms, "oracle: no postmortem dumped by the preempted workers"
    for pm in pms:
        rc = subprocess.run(
            [sys.executable, os.path.join(REPO_DIR, "tools", "healthwatch.py"),
             "--validate", pm],
            capture_output=True, text=True,
        )
        if rc.returncode != 0:
            raise SystemExit(
                f"oracle: postmortem {pm} failed --validate:\n{rc.stdout}"
                f"{rc.stderr}"
            )
    mode = (
        f"resumed rounds resharded {num_workers}x{dpp}dev -> "
        f"1x{total_devices}dev at constant dp={total_devices}"
    )
    print(
        f"ORACLE OK: {args.steps} steps bitwise across dp={total_devices} "
        f"baseline + {len(rounds)} elastic rounds ({mode}); preemption "
        f"save committed step {die_late + 1}; "
        f"{len(pms)} postmortem(s) validated",
        flush=True,
    )
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="elastic_run", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--workdir", required=True,
                    help="job directory: ckpt/, losses.jsonl, postmortems")
    ap.add_argument("--worker", action="store_true",
                    help="internal: run one rank (spawned by the supervisor)")
    ap.add_argument("--oracle", action="store_true",
                    help="run the CI preemption oracle end to end")
    ap.add_argument("--resume", action="store_true",
                    help="alias documenting intent; workers always resume "
                    "from the latest committed tag when one exists")
    ap.add_argument("--num-workers", type=int, default=2)
    ap.add_argument("--min-workers", type=int, default=1)
    ap.add_argument("--devices-per-proc", type=int, default=2)
    ap.add_argument("--total-devices", type=int, default=0,
                    help="internal: fix the job's global device count; "
                    "each rank claims total/nprocs so shrunken rounds "
                    "keep the same mesh (survivors absorb the dead "
                    "ranks' devices)")
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--save-interval", type=int, default=2)
    ap.add_argument("--zero-stage", type=int, default=3)
    ap.add_argument("--async-save", action="store_true", default=True)
    ap.add_argument("--sync-save", dest="async_save", action="store_false")
    ap.add_argument("--die", action="append", default=[],
                    metavar="ROUND:RANK:STEP",
                    help="fault injection: that rank SIGTERMs itself at "
                    "that step of that round (repeatable)")
    args = ap.parse_args(argv)
    if args.worker:
        return run_worker(args)
    if args.oracle:
        return run_oracle(args)
    return run_supervisor(args)


if __name__ == "__main__":
    raise SystemExit(main())
