"""Pipeline-schedule activation-memory measurement (VERDICT r4 item #6).

Thin CLI over ``deepspeed_tpu.analysis.cost.pipeline`` — the estimator
(auto_chunk, boundary bytes, per-policy stash growth laws) lives there
now, shared with the shardplan cost planner; this tool *measures* the
same quantity with XLA's own accounting and prints both columns, so
drift between the analytic law and the compiled buffer assignment is
visible the day it appears.

The reference's 1F1B schedule (deepspeed/runtime/pipe/engine.py) bounds
in-flight activation stashes at pp per stage BY CONSTRUCTION; our
scan+ppermute schedule (runtime/pipe/schedule.py) relies on jax.grad of
the scan, which stores one residual set per tick — so the claim
"1F1B-equivalent memory via remat" needs a measurement, not an assertion.

This tool compiles grad(pipelined loss) on a virtual CPU mesh at pp=2/4
across microbatch counts M and reads XLA's own accounting
(jax.stages.Compiled.memory_analysis().temp_size_in_bytes = peak scratch,
which is where the scan's stacked residuals live). The fit against M tells
whether stashed state grows O(M) (GPipe-like) or stays bounded; the
committed table lives in docs/pipe_memory.md.

Run: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
       python tools/pipe_memory.py
"""

import json
import os
import sys

import jax

# a CPU-mesh measurement by design: force the config flags (same recipe as
# tests/conftest.py). Under pytest an 8-device backend already exists and
# the device count can no longer be set — it is already what is wanted.
jax.config.update("jax_platforms", "cpu")
try:
    jax.config.update("jax_num_cpu_devices", 8)
except RuntimeError:
    pass

import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from deepspeed_tpu.analysis.cost.pipeline import (
    auto_chunk,
    boundary_bytes,
    growth_per_microbatch,
    pipeline_temp_bytes,
)
from deepspeed_tpu.comm.topology import MeshTopology, ParallelDims
from deepspeed_tpu.models import gpt2
from deepspeed_tpu.runtime.pipe import pipelined_stack


def measure(pp: int, M: int, remat_policy, mb=2, S=128, D=64, L=None,
            tick_chunk=None):
    """Peak temp bytes of one compiled fwd+bwd pipeline pass."""
    L = L or pp  # one layer per stage keeps the per-tick compute term flat
    model = gpt2("gpt2-tiny", vocab_size=128, max_seq_len=S, hidden_size=D,
                 num_layers=L, num_heads=2)
    cfg = model.config
    params = model.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    topo = MeshTopology(dims=ParallelDims(pp=pp))
    r = np.random.RandomState(0)
    x = jnp.asarray(r.randn(M, mb, S, D), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (M, mb, S))

    def loss(layers):
        y, _ = pipelined_stack(cfg, layers, x, positions, None, topo, True,
                               jax.random.PRNGKey(1), remat_policy,
                               tick_chunk=tick_chunk)
        return (y.astype(jnp.float32) ** 2).mean()

    compiled = jax.jit(jax.grad(loss)).lower(params["layers"]).compile()
    ma = compiled.memory_analysis()
    return int(ma.temp_size_in_bytes)


def main():
    mb, S, D = 2, 128, 64
    act_bytes = boundary_bytes(mb, S, D)  # one fp32 boundary activation
    rows = []
    # legs: (remat policy, chunked?, estimator policy key) — "full+1f1b" is
    # what the engine runs by default at pp>1; "full" alone is gpipe
    legs = ((None, False, "none"), ("full", False, "gpipe"),
            ("full", True, "1f1b"))
    for pp in (2, 4):
        for policy, chunked, law in legs:
            for M in (2, 4, 8, 16, 32):
                tc = auto_chunk(pp, M) if chunked else None
                t = measure(pp, M, policy, mb=mb, S=S, D=D, tick_chunk=tc)
                pred = pipeline_temp_bytes(pp, M, mb, S, D, policy=law,
                                           tick_chunk=tc)
                rows.append({"pp": pp, "policy": law, "M": M,
                             "tick_chunk": tc, "temp_bytes": t,
                             "predicted_bytes": int(pred)})
                print(f"pp={pp} policy={law:6s} M={M:3d} "
                      f"chunk={tc or '-':>2} temp={t/1e6:8.2f} MB "
                      f"(= {t/act_bytes:6.1f} boundary activations, "
                      f"est {pred/act_bytes:6.1f})",
                      flush=True)
    # per-(pp,policy) growth: bytes added per extra microbatch, in units of
    # one boundary activation — the scan schedule's stash rate
    print()
    for pp in (2, 4):
        for _, _, law in legs:
            pts = [(r["M"], r["temp_bytes"]) for r in rows
                   if r["pp"] == pp and r["policy"] == law]
            slope = growth_per_microbatch(pts, act_bytes)
            print(f"pp={pp} policy={law:6s}: "
                  f"+{slope:.2f} boundary-activations per microbatch")
    out = {"mb": mb, "seq": S, "hidden": D, "act_bytes": act_bytes,
           "rows": rows}
    path = os.path.join(os.path.dirname(__file__), "..", "perf",
                        "pipe_memory.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {os.path.normpath(path)}")


if __name__ == "__main__":
    main()
