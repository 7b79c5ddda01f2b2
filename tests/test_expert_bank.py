"""The bank matmul that reads the experts a step reached
(ops/pallas/expert_bank.py), in interpret mode on the CPU: against the einsum
over every expert on a stacked bank at a chosen layer, and
``moe_serving_mlp`` through it against itself through ``_expert_ffn`` on a
Ling-shaped layer (one member's 64 experts of a 512-expert router, top-8)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import ling
from deepspeed_tpu.models.decoding import record_attention_path
from deepspeed_tpu.moe import sharded_moe as sm
from deepspeed_tpu.ops.pallas import expert_bank as eb

F32, BF16 = jnp.float32, jnp.bfloat16
L, E, C, K, N = 3, 12, 8, 128, 384
LAYER = 1

FILLS = {
    "every_expert_touched": [1, 3, 8, 2, 1, 1, 5, 2, 8, 1, 4, 2],
    "a_third_touched": [0, 2, 0, 0, 1, 0, 0, 8, 0, 3, 0, 0],
    "expert_0_untouched": [0, 1, 1, 2, 0, 0, 3, 1, 1, 0, 2, 1],
    "none_touched": [0] * E,
}
FORMS = {
    "plain": dict(),
    "swiglu": dict(gated=True),
    "gelu": dict(gelu=True),
}


@pytest.fixture(scope="module")
def operands():
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(k[0], (E, C, K), F32)
    bank = jax.random.normal(k[1], (L, E, K, N), F32) * K ** -0.5
    gate = jax.random.normal(k[2], (L, E, K, N), F32) * K ** -0.5
    return x, bank, gate


def test_touched_first_lists_the_touched_in_order_then_the_rest():
    order, n = eb.touched_first(jnp.asarray(FILLS["a_third_touched"]))
    assert order.tolist() == [1, 4, 7, 9, 0, 2, 3, 5, 6, 8, 10, 11]
    assert n.tolist() == [4]
    order, n = eb.touched_first(jnp.zeros((E,), jnp.int32))
    assert order.tolist() == list(range(E)) and n.tolist() == [0]


@pytest.mark.parametrize("K_, N_, itemsize, want", [
    (2560, 768, 2, 768),     # Ling's bank in bf16: one block
    (768, 2560, 2, 2560),
    (4096, 14336, 2, 512),   # Mixtral's: 4 MiB a tile
    (128, 384, 4, 384),
    (4096, 200, 4, 200),     # no lane multiple: whole
])
def test_a_weight_block_is_the_widest_tile_under_the_limit(K_, N_, itemsize,
                                                           want):
    assert eb.tile_of(K_, N_, itemsize) == want
    assert N_ % want == 0


@pytest.mark.parametrize("tiles", [1, 3], ids=["one_tile", "three_tiles"])
@pytest.mark.parametrize("form", list(FORMS))
@pytest.mark.parametrize("fill", list(FILLS))
def test_kernel_matches_the_einsum_over_every_expert(operands, fill, form,
                                                     tiles, monkeypatch):
    """Rows of a touched expert are the einsum's; an untouched expert's are
    exact zeros (written, not left), expert 0's among them."""
    x, bank, gate = operands
    if tiles > 1:
        monkeypatch.setattr(eb, "BLOCK_BYTES", K * (N // tiles) * 4)
        assert eb.tile_of(K, N, 4) == N // tiles
    kw = dict(FORMS[form])
    g = gate if kw.pop("gated", False) else None
    rows = jnp.asarray(FILLS[fill], jnp.int32)
    # the layer feeds zeros where no pair sits
    x = x * (jnp.arange(C)[None, :, None] < rows[:, None, None])
    got = jax.jit(lambda x, w, g, at, f: eb.expert_bank(
        x, w, at, f, gate=g, **kw))(x, bank, g, LAYER, rows)
    want = eb.dense_bank(x, bank, LAYER, gate=g, **kw)
    assert got.shape == (E, C, N) and got.dtype == x.dtype
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    untouched = np.asarray(rows) == 0
    assert not np.asarray(got)[untouched].any()
    # another layer's bank gives another answer: the index is read
    if fill == "every_expert_touched":
        other = eb.dense_bank(x, bank, LAYER + 1, gate=g, **kw)
        assert float(jnp.abs(got - other).max()) > 0.1


def test_output_is_in_the_rows_type_from_float32_sums(operands):
    x, bank, gate = (a.astype(BF16) for a in operands)
    rows = jnp.asarray(FILLS["expert_0_untouched"], jnp.int32)
    got = eb.expert_bank(x, bank, LAYER, rows, gate=gate)
    assert got.dtype == BF16
    want = eb.dense_bank(x.astype(F32), bank.astype(F32), LAYER,
                         gate=gate.astype(F32))
    live = np.asarray(rows) > 0
    np.testing.assert_allclose(np.asarray(got.astype(F32))[live],
                               np.asarray(want)[live], rtol=2e-2, atol=2e-2)
    assert not np.asarray(got.astype(F32))[~live].any()


# ------------------------------------------------ through moe_serving_mlp
@pytest.fixture(scope="module")
def ling_layer():
    """One member's share of a Ling-shaped routed layer: 64 held of 512
    experts in 8 groups (4 kept), top-8, lane-wide sides."""
    model = ling("ling-tiny", layer_ids=[0, 1, 2, 3], num_experts=64,
                 moe_routed_experts=512, moe_top_k=8, moe_groups=8,
                 moe_groups_kept=4, hidden_size=128, intermediate_size=128,
                 num_heads=8, initializer_range=0.1)
    stack = model.init(jax.random.PRNGKey(3), dtype=F32)["layers"]["mlp"]
    assert stack["wi"].shape == (2, 64, 128, 128)
    return model.config, stack


def _both_ways(cfg, stack, x, valid, budget):
    """``moe_serving_mlp`` of layer 1 handed the stack (the kernel) and
    handed the layer's slice alone (``_expert_ffn``), with what each noted."""
    layer = jax.tree.map(lambda a: a[1], stack)
    outs = []
    for handed in (True, False):
        with record_attention_path() as rec:
            out, stats = jax.jit(lambda x, layer, stack: sm.moe_serving_mlp(
                cfg, layer, x, token_valid=valid, budget_tokens=budget,
                stack=(stack, jnp.int32(1)) if handed else None))(
                    x, layer, stack)
        outs.append((out, stats, rec["expert_path"],
                     rec["expert_path_reason"]))
    return outs


@pytest.mark.parametrize("dtype, tol", [(F32, 1e-5), (BF16, 3e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("tokens", [16, 128])
def test_serving_layer_through_the_kernel_is_the_einsum_layer(
        ling_layer, tokens, dtype, tol):
    cfg, stack = ling_layer
    stack = jax.tree.map(lambda a: a.astype(dtype), stack)
    x = jax.random.normal(jax.random.PRNGKey(tokens), (1, tokens, 128), F32)
    valid = (jnp.arange(tokens) < tokens - 3)[None]
    (a, sa, path_a, why_a), (b, sb, path_b, why_b) = _both_ways(
        cfg, stack, x.astype(dtype), valid, tokens)
    assert (path_a, why_a) == ("touched_kernel", None)
    assert path_b == "einsum" and "not the stack" in why_b
    fill = np.asarray(sa["tokens_per_expert"])
    assert (fill == np.asarray(sb["tokens_per_expert"])).all()
    # a step of this size leaves held experts without a row
    assert 0 < (fill > 0).sum() < 64
    scale = float(jnp.abs(b.astype(F32)).max())
    assert scale > 0
    assert float(jnp.abs(a.astype(F32) - b.astype(F32)).max()) <= tol * max(
        scale, 1.0)


def test_an_invalid_row_beside_an_untouched_expert_0_stays_finite(ling_layer):
    """A pair held nowhere gathers slot 0 of expert 0 and weighs it 0: with
    expert 0 untouched those rows are the kernel's zeros, so the layer's
    output is finite, and an invalid row's is exactly the shared expert's."""
    cfg, stack = ling_layer
    # the selection bias keeps every token off expert 0
    stack = {**stack, "sel_bias": stack["sel_bias"].at[:, 0].set(-100.0)}
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 16, 128), F32)
    valid = (jnp.arange(16) < 12)[None]
    (a, sa, path, _), (b, _, _, _) = _both_ways(cfg, stack, x, valid, 16)
    assert path == "touched_kernel"
    assert int(sa["tokens_per_expert"][0]) == 0
    assert bool(jnp.isfinite(a).all())
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
