"""Inference engine (SURVEY §2.6): cached decode == full re-forward greedy;
TP-sharded serving; weight-only quantization sanity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from layer_loop_oracle import (assert_bitwise, layer_loop_forward,
                               paged_setup, random_cache)

from deepspeed_tpu import init_inference
from deepspeed_tpu.comm.topology import MeshTopology, ParallelDims
from deepspeed_tpu.models import bloom, gpt2, llama
from deepspeed_tpu.models.decoding import (forward_with_cache, init_cache,
                                           init_paged_cache)
from deepspeed_tpu.ops.quantizer import (
    dequantize_blockwise,
    quantize_blockwise,
    quantize_dequantize,
)


def tiny_llama(**kw):
    d = dict(vocab_size=128, max_seq_len=64, hidden_size=32, num_layers=2,
             num_heads=4, num_kv_heads=2, intermediate_size=64)
    d.update(kw)
    return llama("llama-tiny", **d)


def greedy_reference(model, params, prompt, n_new):
    """Decode by full re-forward each step (no cache) — the oracle."""
    ids = jnp.asarray(prompt)
    for _ in range(n_new):
        logits, _ = model.apply(params, ids, dtype=jnp.float32)
        nxt = jnp.argmax(logits[:, -1], axis=-1)
        ids = jnp.concatenate([ids, nxt[:, None]], axis=1)
    return np.asarray(ids)


@pytest.mark.parametrize("family", ["llama", "gpt2", "bloom"])
def test_cached_decode_matches_full_forward(family):
    if family == "llama":
        model = tiny_llama()
    elif family == "gpt2":
        model = gpt2("gpt2-tiny", vocab_size=128, max_seq_len=64,
                     hidden_size=32, num_layers=2, num_heads=4)
    else:
        model = bloom("bloom-tiny", vocab_size=128, max_seq_len=64,
                      hidden_size=32, num_layers=2, num_heads=4)
    params = model.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    cfg = model.config
    B, S = 2, 8
    ids = np.random.RandomState(0).randint(0, 128, size=(B, S))

    # full forward logits
    full_logits, _ = model.apply(params, jnp.asarray(ids), dtype=jnp.float32)

    # prefill in two chunks through the cache: same logits
    cache = init_cache(cfg, B, 16, jnp.float32)
    l1, cache = forward_with_cache(cfg, params, jnp.asarray(ids[:, :5]), cache, 0,
                                   dtype=jnp.float32)
    l2, cache = forward_with_cache(cfg, params, jnp.asarray(ids[:, 5:]), cache, 5,
                                   dtype=jnp.float32)
    got = np.concatenate([np.asarray(l1), np.asarray(l2)], axis=1)
    np.testing.assert_allclose(got, np.asarray(full_logits), rtol=2e-4, atol=2e-4)


def test_generate_greedy_matches_reference():
    model = tiny_llama()
    engine = init_inference(model, dtype=jnp.float32, max_tokens=64,
                            rng=jax.random.PRNGKey(1))
    prompt = np.random.RandomState(1).randint(0, 128, size=(2, 6))
    out = engine.generate(prompt, max_new_tokens=6, temperature=0.0)
    ref = greedy_reference(model, engine.params, prompt, 6)
    np.testing.assert_array_equal(out, ref)


def test_generate_eos_stops():
    model = tiny_llama()
    engine = init_inference(model, dtype=jnp.float32, max_tokens=64,
                            rng=jax.random.PRNGKey(2))
    prompt = np.random.RandomState(2).randint(0, 128, size=(1, 4))
    ref = greedy_reference(model, engine.params, prompt, 8)
    eos = int(ref[0, 5])  # force eos at the 2nd generated token
    out = engine.generate(prompt, max_new_tokens=8, temperature=0.0,
                          eos_token_id=eos)
    # after eos, everything is eos-padded
    assert (out[0, 6:] == eos).all()


def test_tp_sharded_serving():
    model = tiny_llama(num_heads=4, num_kv_heads=4)
    topo = MeshTopology(dims=ParallelDims(tp=4, dp=2))
    engine = init_inference(model, topology=topo, dtype=jnp.float32,
                            rng=jax.random.PRNGKey(3))
    single = init_inference(model, dtype=jnp.float32, rng=jax.random.PRNGKey(3),
                            topology=MeshTopology(devices=jax.devices()[:1]))
    prompt = np.random.RandomState(3).randint(0, 128, size=(2, 5))
    out_tp = engine.generate(prompt, max_new_tokens=5)
    out_1 = single.generate(prompt, max_new_tokens=5)
    np.testing.assert_array_equal(out_tp, out_1)


def test_tp_packed_decode_streams_per_shard():
    """ADVICE r5 fix: tp>1 int8 decode must run the Pallas streaming
    matvec PER SHARD (packed_proj's shard_map wrapper), not dequantize
    full-width weights every step. Asserts STREAMING (the sharded kernel
    path traced), not just packed HBM residency — plus token parity with
    the unsharded packed engine."""
    from deepspeed_tpu.ops.pallas import quantized_matmul as qm
    from deepspeed_tpu.ops.quantizer import PackedWeight

    # hidden 256 so each tp=2 column shard keeps whole 128-lane tiles and
    # d = 2 quantization blocks so the row-parallel wo shards G evenly
    model = tiny_llama(hidden_size=256, num_heads=4, num_kv_heads=4,
                       intermediate_size=512, num_layers=1)
    params = model.init(jax.random.PRNGKey(5), dtype=jnp.float32)
    prompt = np.array([[5, 9, 11, 3]])
    ref = init_inference(model, dtype="int8", params=params)
    out_ref = ref.generate(prompt, max_new_tokens=4)
    topo = MeshTopology(dims=ParallelDims(tp=2, dp=1),
                        devices=jax.devices()[:2])
    qm.reset_streaming_trace_counts()
    eng = init_inference(model, dtype="int8", params=params, topology=topo,
                         tp_size=2)
    # HBM residency stays packed per shard (the old guarantee)…
    leaves = jax.tree_util.tree_leaves(
        eng.params, is_leaf=lambda x: isinstance(x, PackedWeight)
    )
    packed = [l for l in leaves if isinstance(l, PackedWeight)]
    assert packed and all(p.pspec is not None for p in packed)
    out_tp = eng.generate(prompt, max_new_tokens=4)
    # …and the decode matvec now actually STREAMS under tp (new): the
    # sharded kernel path traced at least once per packed projection
    counts = qm.streaming_trace_counts()
    assert counts["sharded"] > 0, (
        "tp>1 packed decode took the dequantize-then-dot fallback "
        f"(trace counts {counts})"
    )
    np.testing.assert_array_equal(out_ref, out_tp)


def test_sampling_modes_run():
    model = tiny_llama()
    engine = init_inference(model, dtype=jnp.float32, rng=jax.random.PRNGKey(4))
    prompt = np.random.RandomState(4).randint(0, 128, size=(2, 4))
    out = engine.generate(prompt, max_new_tokens=4, temperature=0.8, top_k=10,
                          rng=jax.random.PRNGKey(9))
    assert out.shape == (2, 8)
    assert (out >= 0).all() and (out < 128).all()


def test_quantizer_roundtrip():
    r = np.random.RandomState(0)
    w = jnp.asarray(r.randn(256, 64).astype(np.float32))
    qt = quantize_blockwise(w, block=128, bits=8)
    deq = dequantize_blockwise(qt, jnp.float32)
    # int8 symmetric: ~0.5 LSB error relative to per-block amax
    err = np.abs(np.asarray(deq) - np.asarray(w))
    scale = np.asarray(qt.scale)
    assert err.max() <= scale.max() * 0.51 + 1e-6
    # int4 coarser but bounded
    qt4 = quantize_blockwise(w, block=128, bits=4)
    deq4 = dequantize_blockwise(qt4, jnp.float32)
    assert np.abs(np.asarray(deq4) - np.asarray(w)).max() <= np.asarray(qt4.scale).max() * 0.51 + 1e-6


def test_quantized_inference_close_to_fp():
    model = tiny_llama(hidden_size=64, intermediate_size=128)
    eng_fp = init_inference(model, dtype=jnp.float32, rng=jax.random.PRNGKey(5),
                            topology=MeshTopology(devices=jax.devices()[:1]))
    eng_q = init_inference(model, dtype=jnp.float32, quantize_bits=8,
                           rng=jax.random.PRNGKey(5),
                           topology=MeshTopology(devices=jax.devices()[:1]))
    ids = np.random.RandomState(5).randint(0, 128, size=(1, 8))
    lf = np.asarray(eng_fp(ids))
    lq = np.asarray(eng_q(ids))
    # weight-only int8 keeps logits close
    assert np.abs(lf - lq).mean() < 0.15


def test_init_inference_loads_checkpoint(tmp_path):
    """init_inference(checkpoint=dir) serves the trained engine weights
    (ADVICE r1: the argument was silently discarded)."""
    import deepspeed_tpu
    import deepspeed_tpu.comm as comm

    comm.destroy_process_group()
    model = tiny_llama()
    engine, *_ = deepspeed_tpu.initialize(
        model=model,
        config={
            "train_batch_size": 8,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        },
    )
    engine.train_batch(
        batch={"input_ids": np.random.RandomState(0).randint(0, 64, size=(8, 16))}
    )
    engine.save_checkpoint(str(tmp_path))
    comm.destroy_process_group()

    eng = init_inference(model, dtype=jnp.float32, checkpoint=str(tmp_path))
    ids = np.random.RandomState(1).randint(0, 64, size=(2, 8))
    got = np.asarray(eng.forward(ids))
    want = np.asarray(
        model.apply(
            jax.tree.map(lambda x: np.asarray(x, np.float32), engine.state.params),
            jnp.asarray(ids),
            dtype=jnp.float32,
        )[0]
    )
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_init_inference_checkpoint_errors(tmp_path):
    model = tiny_llama()
    with pytest.raises(FileNotFoundError):
        init_inference(model, checkpoint=str(tmp_path / "nope"))
    with pytest.raises(ValueError, match="not both"):
        init_inference(
            model, checkpoint=str(tmp_path), params=model.init(jax.random.PRNGKey(0))
        )


# ---------------------------------------------------------------------------
# r3: fused decode attention kernel + int4 weight-only path
# ---------------------------------------------------------------------------
def test_decode_attention_kernel_matches_matvec():
    """Pallas cached-KV decode == masked fp32 matvec, incl. GQA + short cache
    in a long buffer (the predication case)."""
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas.decode_attention import decode_attention_kernel

    B, Smax, H, KV, hd = 2, 512, 4, 2, 64
    r = np.random.RandomState(0)
    q = jnp.asarray(r.randn(B, 1, H, hd), jnp.float32)
    kc = jnp.asarray(r.randn(B, Smax, KV, hd), jnp.float32)
    vc = jnp.asarray(r.randn(B, Smax, KV, hd), jnp.float32)
    for cache_len in (0, 5, 130, 511):
        out = decode_attention_kernel(q, kc, vc, jnp.asarray(cache_len))
        # reference: expand GQA, mask beyond cache_len, fp32 softmax
        kf = jnp.repeat(kc, H // KV, axis=2).astype(jnp.float32)
        vf = jnp.repeat(vc, H // KV, axis=2).astype(jnp.float32)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), kf)
        logits = logits / np.sqrt(hd)
        kpos = jnp.arange(Smax)[None, None, None, :]
        logits = jnp.where(kpos <= cache_len, logits, -1e30)
        ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, -1), vf)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5,
            err_msg=f"cache_len={cache_len}",
        )


def test_generate_uses_decode_kernel(monkeypatch):
    """With kernel injection on, the while_loop decode must trace the Pallas
    decode kernel and produce the same tokens as the XLA matvec."""
    import deepspeed_tpu
    import deepspeed_tpu.ops.pallas.decode_attention as da
    from deepspeed_tpu.models import llama
    from deepspeed_tpu.ops.attention import attention_impl

    model = llama("llama-tiny", vocab_size=128, max_seq_len=128,
                  hidden_size=64, num_layers=2, num_heads=4, num_kv_heads=2,
                  intermediate_size=128)
    eng_ref = deepspeed_tpu.init_inference(model, max_tokens=128)
    prompt = np.arange(8).reshape(1, 8) % 128
    ref_tokens = eng_ref.generate(prompt, max_new_tokens=8)

    called = {}
    orig = da.decode_attention_kernel

    def spy(*a, **kw):
        called["yes"] = True
        return orig(*a, **kw)

    monkeypatch.setattr(da, "decode_attention_kernel", spy)
    # kernel_inject pins "auto" (xla on the CPU suite), which would shadow
    # the forced scope — build a plain engine and force "flash" around the
    # trace instead, which is what injection resolves to on a real TPU
    eng = deepspeed_tpu.init_inference(
        model, max_tokens=128, params=eng_ref.params,
    )
    with attention_impl("flash"):  # force the kernel path on the CPU suite
        tokens = eng.generate(prompt, max_new_tokens=8)
    assert called.get("yes"), "decode kernel never traced"
    np.testing.assert_array_equal(tokens, ref_tokens)


def test_int4_weight_only_inference():
    """dtype="int4" → weight-only 4-bit quant; close to fp output (parity
    bound loose: 4-bit), and strictly coarser than int8."""
    import deepspeed_tpu
    from deepspeed_tpu.models import llama

    model = llama("llama-tiny", vocab_size=128, max_seq_len=64,
                  hidden_size=64, num_layers=2, num_heads=4, num_kv_heads=2,
                  intermediate_size=128)
    rng = jax.random.PRNGKey(3)
    eng_fp = deepspeed_tpu.init_inference(model, dtype=jnp.float32, rng=rng)
    eng_i4 = deepspeed_tpu.init_inference(model, dtype="int4", rng=rng)
    assert eng_i4.dtype == jnp.bfloat16  # compute dtype, weights int4-qdq

    ids = np.arange(16).reshape(1, 16) % 128
    lf = np.asarray(eng_fp(ids), np.float32)
    l4 = np.asarray(eng_i4(ids), np.float32)
    # same argmax on most positions; logits within a loose bound
    agree = (lf.argmax(-1) == l4.argmax(-1)).mean()
    assert agree > 0.7, agree
    assert np.max(np.abs(lf - l4)) < 2.0


def test_generate_top_p_and_repetition_penalty():
    """top-p keeps outputs in-vocab and deterministic seeds reproduce;
    repetition_penalty discourages repeats vs the unpenalized run."""
    import deepspeed_tpu

    model = tiny_llama()
    engine = deepspeed_tpu.init_inference(model, max_tokens=64)
    prompt = np.random.RandomState(0).randint(0, model.config.vocab_size,
                                              size=(2, 8))
    out1 = engine.generate(prompt, max_new_tokens=8, temperature=0.8,
                           top_p=0.9, rng=jax.random.PRNGKey(1))
    out2 = engine.generate(prompt, max_new_tokens=8, temperature=0.8,
                           top_p=0.9, rng=jax.random.PRNGKey(1))
    assert (out1 == out2).all()  # same seed, same nucleus
    assert out1.shape == (2, 16)
    assert (out1 >= 0).all() and (out1 < model.config.vocab_size).all()

    pen = engine.generate(prompt, max_new_tokens=8, temperature=0.0,
                          repetition_penalty=5.0)
    pen2 = engine.generate(prompt, max_new_tokens=8, temperature=0.0,
                           repetition_penalty=5.0)
    assert (pen == pen2).all()  # penalized greedy is deterministic
    assert (pen >= 0).all() and (pen < model.config.vocab_size).all()


def test_apply_repetition_penalty_math():
    """Unit math (HF convention): seen+positive divides, seen+negative
    multiplies, unseen untouched."""
    from deepspeed_tpu.inference.engine import apply_repetition_penalty

    logits = jnp.asarray([[2.0, -2.0, 1.0, -1.0]])
    seen = jnp.asarray([[True, True, False, False]])
    out = np.asarray(apply_repetition_penalty(logits, seen, 2.0))
    np.testing.assert_allclose(out, [[1.0, -4.0, 1.0, -1.0]])


def test_generate_max_new_tokens_zero_echoes_prompt():
    import deepspeed_tpu

    model = tiny_llama()
    engine = deepspeed_tpu.init_inference(model, max_tokens=32)
    prompt = np.random.RandomState(2).randint(0, model.config.vocab_size,
                                              size=(1, 8))
    out = engine.generate(prompt, max_new_tokens=0)
    assert (out == prompt).all()


def test_generate_top_p_zero_still_greedyish():
    """top_p=0 must keep the top-1 token (no silent uniform sampling)."""
    import deepspeed_tpu

    model = tiny_llama()
    engine = deepspeed_tpu.init_inference(model, max_tokens=32)
    prompt = np.random.RandomState(1).randint(0, model.config.vocab_size,
                                              size=(1, 8))
    greedy = engine.generate(prompt, max_new_tokens=6, temperature=0.0)
    nucleus0 = engine.generate(prompt, max_new_tokens=6, temperature=0.5,
                               top_p=0.0, rng=jax.random.PRNGKey(0))
    # with only the top-1 token surviving, sampling == greedy
    assert (nucleus0 == greedy).all()


def test_int8_kv_cache_decode_close_to_bf16():
    """int8 KV cache (kv_cache_dtype="int8"): generate runs end-to-end and
    per-step decode logits stay close to the full-precision cache."""
    import deepspeed_tpu
    from deepspeed_tpu.models.decoding import forward_with_cache, init_cache

    model = tiny_llama()
    cfg = model.config
    params = model.init(jax.random.PRNGKey(0))
    prompt = jnp.asarray(
        np.random.RandomState(3).randint(0, cfg.vocab_size, size=(2, 12))
    )

    # prefill + one decode step on both cache flavors
    def run(quantized):
        cache = init_cache(cfg, 2, 32, jnp.float32, quantized=quantized)
        logits, cache = forward_with_cache(
            cfg, params, prompt, cache, 0, dtype=jnp.float32
        )
        nxt = logits[:, -1].argmax(-1)[:, None]
        step_logits, cache = forward_with_cache(
            cfg, params, nxt, cache, 12, dtype=jnp.float32
        )
        return np.asarray(logits[:, -1]), np.asarray(step_logits[:, -1])

    pre_f, dec_f = run(False)
    pre_q, dec_q = run(True)
    # prefill attends with exact new k/v: identical
    np.testing.assert_allclose(pre_q, pre_f, rtol=1e-5, atol=1e-5)
    # decode reads the quantized cache: close, and top-1 agrees
    np.testing.assert_allclose(dec_q, dec_f, rtol=0.2, atol=0.15)
    assert (dec_q.argmax(-1) == dec_f.argmax(-1)).mean() >= 0.5

    # engine-level: int8 cache generates in-vocab tokens deterministically
    engine = deepspeed_tpu.init_inference(
        model, max_tokens=32, kv_cache_dtype="int8",
        replace_with_kernel_inject=True,
    )
    out = engine.generate(np.asarray(prompt), max_new_tokens=6)
    out2 = engine.generate(np.asarray(prompt), max_new_tokens=6)
    assert (out == out2).all()
    assert out.shape == (2, 18) and (out < cfg.vocab_size).all()


def test_int8_kv_cache_halves_cache_bytes():
    from deepspeed_tpu.models.decoding import init_cache

    from deepspeed_tpu.models import llama

    cfg = llama(
        "llama-tiny", vocab_size=256, max_seq_len=128, hidden_size=256,
        num_layers=2, num_heads=2, num_kv_heads=2, head_dim=128,
        intermediate_size=256,
    ).config
    full = init_cache(cfg, 1, 128, jnp.bfloat16, quantized=False)
    quant = init_cache(cfg, 1, 128, jnp.bfloat16, quantized=True)
    data_bytes = lambda c: c["k"].nbytes + c["v"].nbytes
    assert data_bytes(quant) == data_bytes(full) // 2
    # scale overhead (32B/token-head) stays small next to hd=128 int8 data
    scale_bytes = quant["k_scale"].nbytes + quant["v_scale"].nbytes
    assert scale_bytes == data_bytes(quant) // 4


def test_kv_cache_dtype_bf16_honored():
    """kv_cache_dtype="bf16" on an fp32 engine must actually store bf16."""
    import deepspeed_tpu
    from deepspeed_tpu.models.decoding import init_cache

    model = tiny_llama()
    engine = deepspeed_tpu.init_inference(
        model, dtype=jnp.float32, kv_cache_dtype="bf16", max_tokens=32
    )
    assert engine.kv_cache_storage_dtype == jnp.bfloat16
    prompt = np.random.RandomState(4).randint(0, model.config.vocab_size,
                                              size=(1, 8))
    out = engine.generate(prompt, max_new_tokens=4)
    assert out.shape == (1, 12)
    with pytest.raises(ValueError):
        deepspeed_tpu.init_inference(model, kv_cache_dtype="fp8")


def test_decode_attention_kernel_int8_scales_in_kernel():
    """The in-kernel dequant path (has_scales): Pallas output must match the
    dequantize-then-matvec reference, incl. GQA and cache predication."""
    from deepspeed_tpu.models.decoding import SCALE_LANES, _quantize_kv
    from deepspeed_tpu.ops.pallas.decode_attention import decode_attention_kernel

    B, Smax, H, KV, hd = 2, 512, 4, 2, 64
    r = np.random.RandomState(1)
    q = jnp.asarray(r.randn(B, 1, H, hd), jnp.float32)
    k_raw = jnp.asarray(r.randn(B, Smax, KV, hd), jnp.float32)
    v_raw = jnp.asarray(r.randn(B, Smax, KV, hd), jnp.float32)
    kq, ks = _quantize_kv(k_raw)
    vq, vs = _quantize_kv(v_raw)
    assert kq.dtype == jnp.int8 and ks.shape == (B, Smax, KV, SCALE_LANES)

    for cache_len in (5, 130, 511):
        # the kernel consumes scales in the cache's storage layout
        # [B, KV, Smax, SL] (models/decoding.init_cache)
        out = decode_attention_kernel(
            q, kq, vq, jnp.asarray(cache_len),
            k_scale=jnp.swapaxes(ks, 1, 2), v_scale=jnp.swapaxes(vs, 1, 2),
        )
        kf = kq.astype(jnp.float32) * ks[..., :1]
        vf = vq.astype(jnp.float32) * vs[..., :1]
        kf = jnp.repeat(kf, H // KV, axis=2)
        vf = jnp.repeat(vf, H // KV, axis=2)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, kf) / np.sqrt(hd)
        kpos = jnp.arange(Smax)[None, None, None, :]
        logits = jnp.where(kpos <= cache_len, logits, -1e30)
        ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, -1), vf)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_decode_attention_kernel_mixed_storage_dtype():
    """bf16 cache vs fp32 queries (kv_cache_dtype="bf16" on an fp32 engine):
    the kernel casts storage to the query dtype before the matmul."""
    from deepspeed_tpu.ops.pallas.decode_attention import decode_attention_kernel

    B, Smax, H, KV, hd = 1, 256, 2, 2, 32
    r = np.random.RandomState(2)
    q = jnp.asarray(r.randn(B, 1, H, hd), jnp.float32)
    kc = jnp.asarray(r.randn(B, Smax, KV, hd), jnp.float32).astype(jnp.bfloat16)
    vc = jnp.asarray(r.randn(B, Smax, KV, hd), jnp.float32).astype(jnp.bfloat16)
    out = decode_attention_kernel(q, kc, vc, jnp.asarray(64))
    kf = kc.astype(jnp.float32)
    vf = vc.astype(jnp.float32)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kf) / np.sqrt(hd)
    kpos = jnp.arange(Smax)[None, None, None, :]
    logits = jnp.where(kpos <= 64, logits, -1e30)
    ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, -1), vf)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-3)


def test_speculative_decode_matches_plain_greedy():
    """Greedy speculative decoding is exact: with ANY draft model, the
    output must be token-for-token identical to plain greedy decoding of
    the main model (acceptance only keeps verifier-approved tokens)."""
    import deepspeed_tpu

    main = tiny_llama()
    draft = llama(
        "llama-tiny", vocab_size=main.config.vocab_size, max_seq_len=64,
        hidden_size=32, num_layers=1, num_heads=2, num_kv_heads=2,
        head_dim=16, intermediate_size=64,
    )
    plain = deepspeed_tpu.init_inference(main, dtype=jnp.float32,
                                         max_tokens=64)
    spec = deepspeed_tpu.init_inference(main, dtype=jnp.float32,
                                        max_tokens=64, draft_model=draft)
    prompt = np.random.RandomState(5).randint(0, main.config.vocab_size,
                                              size=(1, 8))
    want = plain.generate(prompt, max_new_tokens=20)
    for k in (1, 3, 6):
        got = spec.generate(prompt, max_new_tokens=20, num_draft_tokens=k)
        assert (got == want).all(), (k, got.tolist(), want.tolist())


def test_speculative_ngram_matches_plain_greedy():
    """The "ngram" self-draft (prompt-lookup decoding) needs no draft
    model at all; the acceptance rule still makes the output token-exact
    vs plain greedy, whatever the lookup proposes."""
    import deepspeed_tpu

    main = tiny_llama()
    plain = deepspeed_tpu.init_inference(main, dtype=jnp.float32,
                                         max_tokens=64)
    spec = deepspeed_tpu.init_inference(main, dtype=jnp.float32,
                                        max_tokens=64, draft_model="ngram")
    for seed in (5, 11):
        prompt = np.random.RandomState(seed).randint(
            0, main.config.vocab_size, size=(1, 8))
        want = plain.generate(prompt, max_new_tokens=20)
        for k in (1, 3, 6):
            got = spec.generate(prompt, max_new_tokens=20,
                                num_draft_tokens=k)
            assert (got == want).all(), (seed, k, got.tolist(), want.tolist())


def test_speculative_ngram_repetitive_prompt_accepts():
    """On a repetitive prompt the n-gram lookup should land real
    acceptances: the verifier round count must come in well under the
    one-round-per-token worst case."""
    import deepspeed_tpu

    main = tiny_llama()
    spec = deepspeed_tpu.init_inference(main, dtype=jnp.float32,
                                        max_tokens=64, draft_model="ngram")
    plain = deepspeed_tpu.init_inference(main, dtype=jnp.float32,
                                         max_tokens=64)
    # an untrained model decoded greedily settles into a cycle quickly;
    # the lookup finds it. Seeded prompt with a repeated motif helps the
    # first rounds along.
    prompt = np.tile(np.asarray([[7, 3, 9, 7, 3, 9, 7, 3]]), (1, 1))
    new = 24
    want = plain.generate(prompt, max_new_tokens=new)
    got = spec.generate(prompt, max_new_tokens=new, num_draft_tokens=5)
    assert (got == want).all()
    assert spec.last_spec_rounds < new - 1, spec.last_spec_rounds


def test_speculative_decode_eos_and_fallback():
    """eos inside an accepted window stops generation; sampled/batched
    requests fall back to the normal decode loop."""
    import deepspeed_tpu

    main = tiny_llama()
    draft = tiny_llama()
    spec = deepspeed_tpu.init_inference(main, dtype=jnp.float32,
                                        max_tokens=64, draft_model=draft)
    plain = deepspeed_tpu.init_inference(main, dtype=jnp.float32,
                                         max_tokens=64)
    prompt = np.random.RandomState(6).randint(0, main.config.vocab_size,
                                              size=(1, 8))
    want = plain.generate(prompt, max_new_tokens=16, eos_token_id=3)
    got = spec.generate(prompt, max_new_tokens=16, eos_token_id=3,
                        num_draft_tokens=3)
    assert (got == want).all()

    # batched (B=2) silently takes the plain path and still works
    p2 = np.random.RandomState(7).randint(0, main.config.vocab_size,
                                          size=(2, 8))
    out = spec.generate(p2, max_new_tokens=4)
    assert out.shape == (2, 12)

    # vocab mismatch is rejected up front
    import pytest as _pytest

    bad = llama("llama-tiny", vocab_size=main.config.vocab_size * 2,
                max_seq_len=64, hidden_size=32, num_layers=1, num_heads=2,
                num_kv_heads=2, head_dim=16, intermediate_size=64)
    with _pytest.raises(ValueError):
        deepspeed_tpu.init_inference(main, draft_model=bad)


def test_speculative_full_acceptance_round_count():
    """With draft params == main params, every proposal is accepted: the
    verifier must run only ceil((new-1)/k) rounds. Catches the draft-cache
    hole regression (an unwritten row after a fully-accepting round would
    desync the draft and inflate the round count)."""
    import math

    import deepspeed_tpu

    main = tiny_llama()
    params = main.init(jax.random.PRNGKey(0))
    spec = deepspeed_tpu.init_inference(
        main, dtype=jnp.float32, max_tokens=64, params=params,
        draft_model=main, draft_params=params,
    )
    prompt = np.random.RandomState(8).randint(0, main.config.vocab_size,
                                              size=(1, 8))
    new = 24
    for nd in (2, 4):
        k = nd + 1
        out = spec.generate(prompt, max_new_tokens=new, num_draft_tokens=nd)
        assert out.shape == (1, 8 + new)
        assert spec.last_spec_rounds == math.ceil((new - 1) / k), (
            nd, spec.last_spec_rounds
        )


def test_packed_int8_storage_and_token_parity():
    """Single-device int8 serving stores PACKED weights (int8 qdata lives
    in the params tree — the HBM stream the decode loop reads) and decodes
    the same tokens as the fake-quant roundtrip (identical q/dq values by
    construction)."""
    from deepspeed_tpu.ops.quantizer import PackedWeight, quantize_dequantize

    model = tiny_llama(hidden_size=64, intermediate_size=128)
    topo = MeshTopology(devices=jax.devices()[:1])
    eng_q = init_inference(model, dtype=jnp.float32, quantize_bits=8,
                           rng=jax.random.PRNGKey(7), topology=topo,
                           max_tokens=24)
    packed = [
        leaf for leaf in jax.tree_util.tree_leaves(
            eng_q.params,
            is_leaf=lambda x: isinstance(x, PackedWeight))
        if isinstance(leaf, PackedWeight)
    ]
    assert packed, "no PackedWeight leaves — int8 storage is not packed"
    assert all(leaf.qdata.dtype == jnp.int8 for leaf in packed)

    # reference: same weights through the fake-quant roundtrip (the same
    # name rule _quantize_weights uses)
    big = {"wq", "wk", "wv", "wo", "wi", "wg"}

    def fake_q(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if name in big and leaf.ndim >= 2:
            return quantize_dequantize(leaf, block=128, bits=8)
        return leaf

    ref_params = jax.tree_util.tree_map_with_path(
        fake_q, model.init(jax.random.PRNGKey(7), dtype=jnp.float32)
    )
    eng_ref = init_inference(model, dtype=jnp.float32, params=ref_params,
                             topology=topo, max_tokens=24)
    ids = np.random.RandomState(7).randint(0, 128, size=(1, 8))
    out_q = np.asarray(eng_q.generate(ids, max_new_tokens=8, temperature=0.0))
    out_r = np.asarray(eng_ref.generate(ids, max_new_tokens=8,
                                        temperature=0.0))
    np.testing.assert_array_equal(out_q, out_r)


@pytest.mark.parametrize("bits", [8, 4])
def test_tp_packed_quantized_serving(bits):
    """tp>1 + weight quantization stores PACKED shards (VERDICT r4 #4):
    each device's HBM holds int8 (or nibble-packed int4) qdata sharded
    along the weight's own TP spec — not a bf16 fake-quant stream — and
    decode matches the single-device packed engine token-for-token."""
    from deepspeed_tpu.ops.quantizer import PackedWeight

    model = tiny_llama(hidden_size=256, intermediate_size=256,
                       num_heads=4, num_kv_heads=4)
    topo = MeshTopology(dims=ParallelDims(tp=2))
    eng_tp = init_inference(model, dtype=jnp.float32, quantize_bits=bits,
                            rng=jax.random.PRNGKey(5), topology=topo,
                            max_tokens=16)
    packed = {
        jax.tree_util.keystr(path): leaf
        for path, leaf in jax.tree_util.tree_flatten_with_path(
            eng_tp.params,
            is_leaf=lambda x: isinstance(x, PackedWeight))[0]
        if isinstance(leaf, PackedWeight)
    }
    assert packed, "tp=2 quantized serving fell back to fake-quant"
    assert all(pw.qdata.dtype == jnp.int8 for pw in packed.values())
    # the device buffers themselves are int8 shards: a tp-sharded qdata's
    # per-device shard is half the global array (these params are the jit
    # inputs, so this IS what streams from HBM during decode)
    def spec_names(spec):
        names = []
        for e in tuple(spec):
            if e is not None:
                names.extend(e if isinstance(e, tuple) else (e,))
        return names

    tp_sharded = [
        pw for pw in packed.values()
        if "tp" in spec_names(pw.qdata.sharding.spec)
    ]
    assert tp_sharded, "no qdata leaf is sharded over tp"
    for pw in tp_sharded:
        shard = pw.qdata.addressable_shards[0].data
        assert shard.dtype == jnp.int8
        assert shard.size == pw.qdata.size // 2
        # scales shard along with their blocks
        assert pw.scale.addressable_shards[0].data.size == pw.scale.size // 2
    if bits == 4:
        assert any(pw.nibbles for pw in packed.values()), (
            "int4 under tp lost nibble packing"
        )
    # token parity vs the single-device packed engine (same rng → same
    # q/dq values)
    eng_1 = init_inference(model, dtype=jnp.float32, quantize_bits=bits,
                           rng=jax.random.PRNGKey(5), max_tokens=16,
                           topology=MeshTopology(devices=jax.devices()[:1]))
    prompt = np.random.RandomState(5).randint(0, 128, size=(1, 6))
    out_tp = np.asarray(eng_tp.generate(prompt, max_new_tokens=6,
                                        temperature=0.0))
    out_1 = np.asarray(eng_1.generate(prompt, max_new_tokens=6,
                                      temperature=0.0))
    np.testing.assert_array_equal(out_tp, out_1)


def test_tp_packed_fallback_when_geometry_does_not_divide():
    """A weight whose quant-block geometry can't shard over the mesh
    (hidden 32 → one block per contraction dim, G=1 < tp) falls back to
    the fake-quant roundtrip instead of failing — and still serves."""
    from deepspeed_tpu.ops.quantizer import PackedWeight

    model = tiny_llama()  # hidden 32: row-parallel wo/wo-mlp have G=1
    topo = MeshTopology(dims=ParallelDims(tp=2))
    eng = init_inference(model, dtype=jnp.float32, quantize_bits=8,
                         rng=jax.random.PRNGKey(6), topology=topo,
                         max_tokens=16)
    leaves = jax.tree_util.tree_leaves(
        eng.params, is_leaf=lambda x: isinstance(x, PackedWeight))
    # row-parallel leaves (wo) must have fallen back; column-parallel ones
    # (wq: shards the last dim, blocks untouched) still pack
    assert any(isinstance(l, PackedWeight) for l in leaves)
    prompt = np.random.RandomState(6).randint(0, 128, size=(1, 5))
    out = eng.generate(prompt, max_new_tokens=4, temperature=0.0)
    assert out.shape == (1, 9)


@pytest.mark.parametrize("cols", [16, 15])
def test_int4_nibble_packing_roundtrip(cols):
    """int4 packed storage nibble-packs blocks g and g+G/2 per byte plane
    (half the int8 bytes, column and in-block row layout untouched — the
    split-half pairing keeps the Pallas unpack a block-dim concat) and
    dequantizes bit-identically to the unpacked quantizer."""
    from deepspeed_tpu.ops.quantizer import (
        dequantize_blockwise, pack_quantize_blockwise, quantize_blockwise,
    )

    w = jnp.asarray(np.random.RandomState(11).randn(32, cols), jnp.float32)
    pw = pack_quantize_blockwise(w, block=16, bits=4)
    ref = dequantize_blockwise(quantize_blockwise(w, block=16, bits=4),
                               jnp.float32)
    np.testing.assert_array_equal(np.asarray(pw.dequantize()),
                                  np.asarray(ref))
    # 2 blocks of 16 rows → one byte plane [1, 16, cols]
    assert pw.nibbles
    assert pw.qdata.shape[-3:] == (1, 16, cols)


def test_int4_odd_block_falls_back_to_bytewise():
    """An odd block COUNT can't pair split-halves: one int4 per byte."""
    from deepspeed_tpu.ops.quantizer import (
        dequantize_blockwise, pack_quantize_blockwise, quantize_blockwise,
    )

    w = jnp.asarray(np.random.RandomState(3).randn(15, 8), jnp.float32)
    pw = pack_quantize_blockwise(w, block=16, bits=4)  # 15 % 16 → block 15
    assert not pw.nibbles and pw.qdata.shape[-2] == 15
    ref = dequantize_blockwise(quantize_blockwise(w, block=16, bits=4),
                               jnp.float32)
    np.testing.assert_array_equal(np.asarray(pw.dequantize()),
                                  np.asarray(ref))


def test_moe_quantized_serving_runs():
    """MoE + weight quantization: expert banks [L, E, d, f] PACK since
    ISSUE 14 (the decode dispatch path consumes PackedWeight through the
    per-expert Pallas matvec / dequantize-once fallback) — serving runs
    end-to-end with the banks resident as int8 bytes."""
    from deepspeed_tpu.models import mixtral
    from deepspeed_tpu.ops.quantizer import PackedWeight

    model = mixtral("mixtral-tiny", vocab_size=128, max_seq_len=64,
                    hidden_size=64, num_layers=2, num_heads=4,
                    num_kv_heads=2, intermediate_size=128, num_experts=4,
                    moe_top_k=2)
    eng = init_inference(model, dtype=jnp.float32, quantize_bits=8,
                         rng=jax.random.PRNGKey(9), max_tokens=24,
                         topology=MeshTopology(devices=jax.devices()[:1]))
    leaves = jax.tree_util.tree_leaves(
        eng.params, is_leaf=lambda x: isinstance(x, PackedWeight))
    packed = [l for l in leaves if isinstance(l, PackedWeight)]
    assert packed  # attention projections pack
    assert any(len(pw.shape) == 4 for pw in packed)  # expert banks too
    prompt = np.random.RandomState(9).randint(0, 128, size=(1, 6))
    out = eng.generate(prompt, max_new_tokens=6, temperature=0.0)
    assert out.shape == (1, 12)
    assert (np.asarray(out) < 128).all()


def test_matvec_max_rows_scope_switches_kernel_path(monkeypatch):
    """inference.matvec_max_rows (ADVICE r5 #2 follow-up): a 10-row
    projection — the k=9 speculative verify window — takes the dequantize
    path at the default threshold (8) and the Pallas streaming matvec
    once the threshold covers it."""
    from deepspeed_tpu.ops.pallas import quantized_matmul as qm
    from deepspeed_tpu.ops.quantizer import pack_quantize_blockwise

    w = np.random.RandomState(0).randn(128, 128).astype(np.float32)
    packed = pack_quantize_blockwise(jnp.asarray(w), block=128, bits=8)
    x = jnp.asarray(np.random.RandomState(1).randn(10, 128), jnp.float32)

    calls = []
    real = qm._packed_matvec

    def spy(x2d, qdata, scale, **kw):
        calls.append(x2d.shape)
        return real(x2d, qdata, scale, **kw)

    monkeypatch.setattr(qm, "_packed_matvec", spy)

    y_deq = qm.packed_proj(x, packed)  # default threshold 8 < 10 rows
    assert calls == []
    with qm.matvec_max_rows_scope(16):
        assert qm.matvec_max_rows() == 16
        y_stream = qm.packed_proj(x, packed)
    assert calls == [(10, 128)]
    assert qm.matvec_max_rows() == qm._MATVEC_MAX_ROWS  # scope restored
    # same numerics either path (fp32 kernel pins HIGHEST dot precision)
    np.testing.assert_allclose(np.asarray(y_stream), np.asarray(y_deq),
                               rtol=2e-5, atol=2e-5)


def test_speculative_verify_window_streams_with_configured_threshold(
    monkeypatch,
):
    """CPU-path end-to-end: with inference.matvec_max_rows=16 the k=9
    speculative verify forward (10 rows) engages the streaming kernel at
    trace time; at the default threshold it never does. Tokens match the
    unconfigured engine either way."""
    import deepspeed_tpu
    from deepspeed_tpu.ops.pallas import quantized_matmul as qm

    model = tiny_llama(hidden_size=128, intermediate_size=256)
    prompt = np.random.RandomState(3).randint(
        0, model.config.vocab_size, size=(1, 8))

    rows_seen = []
    real = qm._packed_matvec

    def spy(x2d, qdata, scale, **kw):
        rows_seen.append(x2d.shape[0])
        return real(x2d, qdata, scale, **kw)

    monkeypatch.setattr(qm, "_packed_matvec", spy)

    def run(**engine_kw):
        rows_seen.clear()
        eng = deepspeed_tpu.init_inference(
            model, dtype=jnp.float32, quantize_bits=8, max_tokens=64,
            draft_model="ngram", rng=jax.random.PRNGKey(0), **engine_kw,
        )
        out = eng.generate(prompt, max_new_tokens=12, num_draft_tokens=9)
        return eng, np.asarray(out), list(rows_seen)

    base_eng, base_out, base_rows = run()
    assert base_eng.matvec_max_rows is None
    assert 10 not in base_rows  # default threshold 8: verify dequantizes
    cfg_eng, cfg_out, cfg_rows = run(config={"matvec_max_rows": 16})
    assert cfg_eng.matvec_max_rows == 16  # the "inference." config spelling
    assert 10 in cfg_rows  # the verify window streams now
    np.testing.assert_array_equal(base_out, cfg_out)


# ---------------------------------------------- the cache rides the scan
@pytest.mark.parametrize(
    "family,layout,quantized,impl",
    [
        ("llama", "paged", False, "xla"),
        ("llama", "paged", False, "flash"),  # the kernel on stack + layer
        ("llama", "paged", True, "xla"),
        ("llama", "contiguous", False, "xla"),
        ("llama", "contiguous", True, "xla"),
        ("llama", "scalar", False, "xla"),   # the lockstep engine's form
        ("llama", "scalar", True, "xla"),
        ("alibi", "paged", False, "flash"),  # ALiBi: dense lines on a slice
        ("alibi", "contiguous", False, "xla"),
        ("bloom", "paged", False, "flash"),
        ("bloom", "contiguous", False, "xla"),
    ],
)
def test_carried_cache_is_bitwise_the_layer_loop(family, layout, quantized,
                                                 impl):
    """forward_with_cache carries the cache stacks through its layer scan
    and writes each layer in place at its index. Against a plain loop over
    layers, every layer on a cache of its own: logits and every cache leaf
    bit for bit, over two chunks (the second attends what the first
    wrote), ragged frontiers, the other layers' bytes noise."""
    from deepspeed_tpu.ops.attention import attention_impl

    if family == "bloom":
        model = bloom("bloom-tiny", vocab_size=128, max_seq_len=64,
                      hidden_size=32, num_layers=3, num_heads=4)
    else:
        model = tiny_llama(num_layers=3, **(
            dict(pos_embedding="alibi") if family == "alibi" else {}))
    # BLOOM's LayerNorm is the one piece XLA's CPU backend sums in another
    # order inside a scan's body than outside one: a few last bits there,
    # where a wrong layer, page or offset reads noise of order one
    exact = family != "bloom"
    cfg = model.config
    params = model.init(jax.random.PRNGKey(1), dtype=jnp.float32)
    B, S, ps, mp = 3, 8, 4, 8
    ids = np.random.RandomState(2).randint(0, 128, size=(2, B, S))
    kw = {}
    if layout == "paged":
        cache, table = paged_setup(cfg, B, ps, mp, quantized, seed=3)
        kw = dict(page_table=table)
    else:
        cache = random_cache(
            init_cache(cfg, B, 32, jnp.float32, quantized=quantized), 3)
    frontier = 5 if layout == "scalar" else jnp.asarray([0, 5, 11], jnp.int32)
    if layout != "scalar":
        kw["num_new"] = jnp.asarray([S, 3, S], jnp.int32)
    got = want = (None, cache)
    fwd = jax.jit(lambda ids, c, cl: forward_with_cache(
        cfg, params, ids, c, cl, dtype=jnp.float32, **kw))
    with attention_impl(impl):
        for chunk in ids:
            args = (cfg, params, jnp.asarray(chunk))
            got = fwd(args[2], got[1], frontier)
            want = layer_loop_forward(*args, want[1], frontier, **kw)
            assert_bitwise(got, want, atol=0.0 if exact else 1e-6)
            frontier = frontier + S
    for n in cache:  # and the step did write: no leaf is what it was
        assert not np.array_equal(np.asarray(got[1][n]), np.asarray(cache[n]))


def test_donated_caches_are_consumed_by_the_step():
    """Jitted with the caches donated (as both serving steps are), the
    step takes the input buffers for its outputs: the inputs are deleted,
    and XLA does not say a donated buffer went unused."""
    import warnings

    model = tiny_llama(num_layers=3)
    cfg = model.config
    params = model.init(jax.random.PRNGKey(1), dtype=jnp.float32)
    cache, table = paged_setup(cfg, 2, 4, 8, False, seed=0)
    step = jax.jit(
        lambda c, ids, cl: forward_with_cache(
            cfg, params, ids, c, cl, dtype=jnp.float32, page_table=table),
        donate_argnums=(0,))
    ids = jnp.zeros((2, 8), jnp.int32)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        _, new = step(cache, ids, jnp.asarray([0, 3], jnp.int32))
    assert all(a.is_deleted() for a in cache.values())
    assert not any(a.is_deleted() for a in new.values())
