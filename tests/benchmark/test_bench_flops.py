"""flops.py against arithmetic done by hand from the published sizes."""

import json
import os

import pytest

from benchmarks import flops, reference

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def config(name):
    with open(os.path.join(ROOT, "benchmarks", "configs", name + ".json")) as f:
        return json.load(f)


def shape_of(cfg):
    return reference.family(cfg["family"]).shape_of(cfg)


def test_bloom_560m_by_hand():
    s = shape_of(config("bloom-560m"))
    assert (s.d, s.layers, s.heads, s.hd, s.ffn, s.vocab) == (
        1024, 24, 16, 64, 4096, 250880)
    # a layer: q, k, v, o = 4 d^2; MLP = 8 d^2
    assert s.layer_matmul_params() == 12 * 1024 ** 2 == 12_582_912
    assert flops.head_params(s) == 250880 * 1024 == 256_901_120
    # forward per token at 2048: 2 x (24 layers + head) + attention over
    # 1024 keys on average: 2 matmuls x 2 x 24 layers x 16 heads x 64
    fwd = 2 * (24 * 12_582_912 + 256_901_120) + 2 * 2 * 24 * 16 * 64 * 1024
    assert fwd == 1_218_445_312
    assert flops.forward_flops_per_token(s, 2048) == fwd
    assert flops.train_flops_per_token(s, 2048) == 3 * fwd == 3_655_335_936
    # tied: the embedding is stored once
    assert flops.stored_params(s) == 24 * 12_582_912 + 256_901_120


def test_mixtral_8x7b_by_hand():
    c = dict(config("mixtral-8x7b"))
    c["num_hidden_layers"] = c["published"]["num_hidden_layers"]  # 32
    s = shape_of(c)
    assert (s.d, s.heads, s.kv_heads, s.hd, s.ffn, s.experts, s.top_k) == (
        4096, 32, 8, 128, 14336, 8, 2)
    attn = 2 * 4096 * 4096 + 2 * 4096 * 1024
    expert = 3 * 4096 * 14336
    assert s.layer_matmul_params() == attn + 2 * expert + 4096 * 8 \
        == 394_297_344
    assert s.layer_matmul_params(active=False) == \
        attn + 8 * expert + 4096 * 8 == 1_451_261_952
    # the published 46.7 B parameters (norm vectors left out)
    assert flops.stored_params(s) == 32 * 1_451_261_952 + 2 * 32000 * 4096 \
        == 46_702_526_464
    assert flops.forward_flops_per_token(s, 4096) == \
        2 * (32 * 394_297_344 + 32000 * 4096) + 2 * 2 * 32 * 32 * 128 * 2048


def test_flash_cost_and_roofline():
    s = shape_of(config("bloom-560m"))
    fl, by = flops.flash_train_cost(s, batch=4, seq=2048)
    one = 2 * 4 * 16 * 64 * 2048 * 2048 / 2   # one causal S x S matmul
    assert fl == 7 * one * 24
    tensor = 4 * 2048 * 16 * 64 * 2           # Q (= K = V = O) in bf16
    assert by == (4 + 8) * tensor * 24
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = flops.roofline_seconds(fl, by, peak)
    assert bound == "compute" and t == pytest.approx(fl / 197e12)
    assert flops.roofline_seconds(1.0, 819e9, peak) == (1.0, "memory")


def test_unknown_family_is_an_error():
    with pytest.raises(ValueError, match="benchmarks/families/nope.py"):
        reference.family("nope")


def test_a_family_that_counts_differently_brings_its_own_arithmetic():
    # what a later family with, say, a shared expert does: subclass, override
    class Shared(flops.Shape):
        def layer_matmul_params(self, active=True):
            return super().layer_matmul_params(active) + 3 * self.d * self.ffn

    base = shape_of(dict(config("mixtral-8x7b")))
    s = Shared(**vars(base))
    extra = 3 * 4096 * 14336
    assert s.layer_matmul_params() == base.layer_matmul_params() + extra
    assert flops.forward_flops_per_token(s, 4096) == \
        flops.forward_flops_per_token(base, 4096) + 2 * s.layers * extra
    assert flops.stored_params(s) == flops.stored_params(base) \
        + s.layers * extra
