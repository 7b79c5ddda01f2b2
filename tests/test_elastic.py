"""launcher/elastic.py: the preemption-recovery supervisor.

The unit tests drive the REAL supervisor over trivial python workers
(no jax, no collectives) — round accounting, shrink-to-survivors, the
min_workers floor, max_rounds exhaustion. The end-to-end preemption
oracle (kill a jax.distributed worker mid-step, resume resharded,
bitwise loss trajectory) is tools/elastic_run.py --oracle: the `slow`
test here runs it in-process-count-degraded form locally and ci.yml's
``preemption`` job runs it on every push.
"""

import os
import subprocess
import sys

import pytest

from deepspeed_tpu.launcher.elastic import (
    ROUND_ENV,
    ElasticSupervisor,
    _rc,
    free_port,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def worker_argv(body: str):
    """A tiny rank script: sees DSTPU_PROCESS_ID + the round env."""
    return [
        sys.executable, "-c",
        "import os, sys\n"
        f"rank = int(os.environ['DSTPU_PROCESS_ID'])\n"
        f"rnd = int(os.environ['{ROUND_ENV}'])\n" + body,
    ]


def test_rc_maps_signals_to_128_plus():
    assert _rc(-15) == 143  # SIGTERM
    assert _rc(-9) == 137   # SIGKILL
    assert _rc(1) == 1
    assert _rc(0) == 0


def test_free_port_is_bindable_int():
    p = free_port()
    assert isinstance(p, int) and 0 < p < 65536


def test_clean_round_exits_zero():
    sup = ElasticSupervisor(worker_argv("sys.exit(0)"), num_workers=2)
    assert sup.run() == 0
    assert sup.rounds == [{"round": 0, "world": 2, "rc": 0, "dead": 0}]


def test_one_death_shrinks_world_and_resumes():
    """Rank 1 dies in round 0 only; round 1 runs the lone survivor."""
    sup = ElasticSupervisor(
        worker_argv("sys.exit(143 if rnd == 0 and rank == 1 else 0)"),
        num_workers=2,
    )
    assert sup.run() == 0
    assert [r["world"] for r in sup.rounds] == [2, 1]
    assert sup.rounds[0]["rc"] != 0 and sup.rounds[1]["rc"] == 0


def test_whole_job_preemption_respawns_at_floor():
    """Every rank dying at once must not end the job: the next round
    restarts at the min_workers floor."""
    sup = ElasticSupervisor(
        worker_argv("sys.exit(143 if rnd == 0 else 0)"), num_workers=2,
    )
    assert sup.run() == 0
    assert [r["world"] for r in sup.rounds] == [2, 1]


def test_max_rounds_exhaustion_propagates_failure():
    sup = ElasticSupervisor(
        worker_argv("sys.exit(7)"), num_workers=1, max_rounds=2,
    )
    assert sup.run() == 7
    assert len(sup.rounds) == 3  # initial + 2 recoveries
    assert all(r["rc"] == 7 for r in sup.rounds)


def test_round_env_reaches_workers(tmp_path):
    marker = os.path.join(str(tmp_path), "round_r{}.txt")
    sup = ElasticSupervisor(
        worker_argv(
            f"open({marker!r}.format(rnd), 'a').write(str(rank))\n"
            "sys.exit(143 if rnd == 0 and rank == 0 else 0)"
        ),
        num_workers=2,
    )
    assert sup.run() == 0
    assert os.path.exists(marker.format(0))
    assert os.path.exists(marker.format(1))


@pytest.mark.slow
def test_preemption_oracle_end_to_end(tmp_path):
    """The full oracle: baseline vs twice-preempted elastic run, bitwise
    loss trajectory, preemption-save resume point, validated
    postmortems, two workers dropping to one."""
    env = {**os.environ, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu"}
    rc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "elastic_run.py"),
         "--oracle", "--workdir", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=560,
    )
    assert rc.returncode == 0, rc.stdout + rc.stderr
    assert "ORACLE OK" in rc.stdout
