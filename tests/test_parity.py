"""Differential parity prover (ISSUE 15): form pairs certify statically,
seeded mutations diverge with the offending op named, and the engines
declare their pairs through the ``parity_pairs()`` protocol.

The heavy CLI subprocess legs are marked slow (the 1-core tier-1 box);
the in-process proofs are seconds.
"""

import copy
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import deepspeed_tpu
import deepspeed_tpu.comm as comm
from deepspeed_tpu.analysis import config_parity_pairs, prove_parity
from deepspeed_tpu.analysis.parity import (FormPair, extract_anchors,
                                           _serving_trace_thunk)
from deepspeed_tpu.models import gpt2, llama

pytestmark = pytest.mark.shardlint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny_llama():
    return llama("llama-tiny", vocab_size=64, max_seq_len=64,
                 hidden_size=32, num_layers=2, num_heads=4,
                 num_kv_heads=2, intermediate_size=64)


SERVING_CFG = {
    "serving": {"enabled": True, "max_slots": 2, "token_budget": 4,
                "max_tokens": 16, "paged": True, "page_size": 8},
}


# ---------------------------------------------------------------- proving
def test_paged_vs_contiguous_certifies(devices8):
    pairs = config_parity_pairs(copy.deepcopy(SERVING_CFG), tiny_llama())
    assert [p.name for p in pairs] == ["serving/paged-vs-contiguous"]
    cert = prove_parity(pairs[0])
    assert cert.ok, cert.format()
    assert cert.anchors_a and cert.anchors_b
    assert cert.seconds < 5.0, "ISSUE 15 acceptance: <5s per pair"
    d = cert.to_dict()
    assert d["ok"] and d["pair"] == "serving/paged-vs-contiguous"
    assert d["divergences"] == []


def test_mutated_form_diverges_with_named_op(devices8):
    """Seeded divergence: silently enabling spec on one form changes the
    verify window's sampling/RNG anchors — the prover must name them,
    and reduction-bucket divergences must carry rule R10."""
    model = tiny_llama()
    pairs = config_parity_pairs(copy.deepcopy(SERVING_CFG), model)
    pair = pairs[0]
    mut = copy.deepcopy(SERVING_CFG)
    mut["serving"]["spec"] = {"enabled": True, "max_draft": 2}
    mut["serving"].pop("paged")
    mut["serving"].pop("page_size")
    pair.trace_b = _serving_trace_thunk(mut, model)
    cert = prove_parity(pair)
    assert not cert.ok
    first = cert.first_divergence
    assert first is not None and first.op
    ops = {d.op for d in cert.divergences}
    assert ops & {"random_bits", "random_split", "sort", "argmax",
                  "reduce_sum", "cumsum"}, ops
    # both provenances named (a path or an explicit absence)
    assert first.where_a and first.where_b
    # a reduce-bucket divergence is a reduction-order (R10) finding
    for d in cert.divergences:
        if d.kind in ("reduce", "collective", "accum"):
            assert d.rule == "R10", d.format()
        else:
            assert d.rule == "parity", d.format()


def test_missing_reduction_is_r10(devices8):
    """A pair whose form B drops a psum: the divergent bucket is a
    collective and must be labeled R10 (the reassociation half)."""
    def with_psum(x):
        return jax.lax.psum(jnp.tanh(x).sum(axis=0, keepdims=True), "dp")

    def without(x):
        return jnp.tanh(x).sum(axis=0, keepdims=True)

    from jax.sharding import Mesh, PartitionSpec as P
    import numpy as np
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(4, 2), ("dp", "tp"))
    x = jax.ShapeDtypeStruct((8, 4), jnp.float32)
    fa = jax.shard_map(with_psum, mesh=mesh, in_specs=P("dp"), out_specs=P(),
                   axis_names={"dp", "tp"}, check_vma=False)
    fb = jax.shard_map(without, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
                   axis_names={"dp", "tp"}, check_vma=False)
    pair = FormPair(
        name="unit/psum-dropped", contract="unit", form_a="a", form_b="b",
        trace_a=lambda: jax.make_jaxpr(fa)(x),
        trace_b=lambda: jax.make_jaxpr(fb)(x),
    )
    cert = prove_parity(pair)
    assert not cert.ok
    assert any(d.kind == "collective" and d.rule == "R10"
               for d in cert.divergences), cert.format()


def test_chunking_fold_unifies_split_dots(devices8):
    """Two half-width dots == one full dot under the chunking rewrite
    (mass-exact), and WITHOUT the rewrite they diverge."""
    def chunked(x, w):
        h1 = x @ w[:, :8]
        h2 = x @ w[:, 8:]
        return jnp.concatenate([h1, h2], axis=1).sum()

    def whole(x, w):
        return (x @ w).sum()

    x = jax.ShapeDtypeStruct((4, 16), jnp.float32)
    w = jax.ShapeDtypeStruct((16, 16), jnp.float32)

    def mk(rewrites):
        return FormPair(
            name="unit/chunked-dot", contract="unit", form_a="chunked",
            form_b="whole",
            trace_a=lambda: jax.make_jaxpr(chunked)(x, w),
            trace_b=lambda: jax.make_jaxpr(whole)(x, w),
            rewrites=frozenset(rewrites),
        )

    assert prove_parity(mk({"chunking"})).ok
    strict = prove_parity(mk(set()))
    assert not strict.ok
    assert strict.first_divergence.op == "dot_general"


def test_dim_alias_unifies_form_specific_extents(devices8):
    """The paged view extent vs the contiguous capacity are the same
    logical extent: aliasing both to one symbol matches the attention
    dots without smearing over unrelated dims that happen to match."""
    def attn(q, k):
        return jnp.einsum("bd,btd->bt", q, k).sum()

    q = jax.ShapeDtypeStruct((2, 8), jnp.float32)
    ka = jax.ShapeDtypeStruct((2, 24, 8), jnp.float32)
    kb = jax.ShapeDtypeStruct((2, 32, 8), jnp.float32)
    pair = FormPair(
        name="unit/aliased-extent", contract="unit", form_a="a",
        form_b="b",
        trace_a=lambda: jax.make_jaxpr(attn)(q, ka),
        trace_b=lambda: jax.make_jaxpr(attn)(q, kb),
        dim_aliases_a={24: "KV_EXT"},
        dim_aliases_b={32: "KV_EXT"},
    )
    assert prove_parity(pair).ok
    bare = FormPair(
        name="unit/unaliased", contract="unit", form_a="a", form_b="b",
        trace_a=lambda: jax.make_jaxpr(attn)(q, ka),
        trace_b=lambda: jax.make_jaxpr(attn)(q, kb),
    )
    assert not prove_parity(bare).ok


def test_extract_anchors_elides_layout_keeps_compute(devices8):
    def prog(x, w):
        h = jnp.transpose(x) @ w
        return jax.nn.softmax(h.reshape(-1, 4), axis=-1)

    closed = jax.make_jaxpr(prog)(
        jax.ShapeDtypeStruct((16, 8), jnp.float32),
        jax.ShapeDtypeStruct((16, 4), jnp.float32),
    )
    anchors = extract_anchors(closed, frozenset())
    ops = [a.op for a in anchors]
    assert "dot_general" in ops
    assert "transpose" not in ops and "reshape" not in ops


# --------------------------------------------------------------- protocol
def test_tpu_engine_declares_parity_pairs(devices8):
    comm.destroy_process_group()
    engine, *_ = deepspeed_tpu.initialize(
        model=gpt2("gpt2-tiny", vocab_size=128, max_seq_len=16),
        config={
            "train_batch_size": 16,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "bf16": {"enabled": True},
            "tensor_parallel": {"tp_size": 2, "overlap_comm": True},
            "zero_optimization": {"stage": 1, "grad_wire": "int8"},
        },
        abstract_init=True,
    )
    try:
        names = [p.name for p in engine.parity_pairs()]
    finally:
        engine.destroy()
    assert "train/tp-ring-vs-xla" in names
    assert "train/wire-codec-vs-full-width" in names


def test_serving_engine_declares_parity_pairs(devices8):
    comm.destroy_process_group()
    eng = deepspeed_tpu.init_inference(
        tiny_llama(), dtype=jnp.float32, max_tokens=16,
        rng=jax.random.PRNGKey(0),
    )
    from deepspeed_tpu.serving import ServingEngine

    srv = ServingEngine(engine=eng, serving=dict(SERVING_CFG["serving"],
                                                 enabled=True))
    pairs = srv.parity_pairs()
    assert [p.name for p in pairs] == ["serving/paged-vs-contiguous"]
    cert = prove_parity(pairs[0])
    assert cert.ok, cert.format()


# -------------------------------------------------------------------- CLI
@pytest.mark.slow
def test_cli_all_pairs_certifies(tmp_path, devices8):
    out = tmp_path / "parity.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "paritycheck.py"),
         "--all-pairs", "--json", str(out)],
        capture_output=True, text=True, timeout=540, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    import json

    payload = json.loads(out.read_text())
    assert payload["ok"] and payload["pairs"]
    names = {p["pair"] for p in payload["pairs"]}
    assert "serving/paged-vs-contiguous" in names
    assert "train/tp-ring-vs-xla" in names
    assert "train/wire-codec-vs-full-width" in names
    for p in payload["pairs"]:
        assert p["seconds"] < 5.0, p  # ISSUE 15 acceptance


@pytest.mark.slow
def test_cli_seeded_divergence_exits_1(devices8):
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "paritycheck.py"),
         "--mutate", os.path.join(REPO, "examples",
                                  "ds_config_serving.json")],
        capture_output=True, text=True, timeout=300, cwd=REPO,
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "DIVERGENT" in proc.stdout
    # the prover names the divergent sampling/rng ops
    assert any(op in proc.stdout for op in
               ("random_bits", "sort", "argmax")), proc.stdout
