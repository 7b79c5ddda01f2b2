"""Command A+ presets (CohereLabs/command-a-plus-05-2026, ``cohere2_moe``):
a parallel block under one bias-free LayerNorm (attention and the expert
layer read the same norm, one residual sum), periods of three window layers
(4,096 keys, plain rotary) and one full layer WITHOUT positions (NoPE), 128
query heads on 8 KV heads, and 128 experts top-8 by a plain sigmoid router
beside 4 shared experts whose outputs are averaged; tied head
(``logit_scale`` 1: the program has no scale on its logits).

Rotary pairing. The published model rotates interleaved pairs ``(2i, 2i +
1)`` of a head (``rope_gptj``); the program's ``_rope`` rotates the halves
``(i, i + hd/2)``. They are the same model under a fixed permutation of the
columns of ``W_q`` and ``W_k`` inside every head, made once at load
(:func:`half_split_columns`): q and k are permuted alike, so every score is
unchanged.

Shared experts. The mean of ``n`` SwiGLU experts of width ``f`` is ONE SwiGLU
of width ``n x f`` whose down projection holds ``W_d / n``: the program
serves that one bank (``moe_shared_width``, the path of every other
configuration's shared expert), made once at load
(:func:`averaged_shared_bank`).
"""

import numpy as np

from .transformer import TransformerConfig, TransformerModel

_PATTERN = ("window", "window", "window", "full")

_COHERE_SIZES = {
    "cohere-tiny": dict(
        hidden_size=64, num_layers=4, num_heads=8, num_kv_heads=2,
        head_dim=16, intermediate_size=32, num_experts=8, moe_top_k=2,
        moe_shared_width=2 * 32, vocab_size=512, max_seq_len=512,
        attn_window=24,
    ),
    "command-a-plus-05-2026": dict(
        hidden_size=4096, num_layers=32, num_heads=128, num_kv_heads=8,
        head_dim=128, intermediate_size=4096, num_experts=128, moe_top_k=8,
        moe_shared_width=4 * 4096,
    ),
}


def half_split_columns(w, heads: int, head_dim: int):
    """``W_q`` or ``W_k`` [..., d, heads x head_dim] in the published
    (interleaved-pair) layout -> the layout the program rotates: inside
    every head the even columns first, then the odd ones."""
    order = np.concatenate([np.arange(0, head_dim, 2),
                            np.arange(1, head_dim, 2)])
    cols = (np.arange(heads)[:, None] * head_dim + order[None, :]).reshape(-1)
    return w[..., cols]


def averaged_shared_bank(wg, wi, wo):
    """The published shared experts (``wg``, ``wi`` [..., n, d, f], ``wo``
    [..., n, f, d]), whose outputs are averaged -> the program's one bank
    ``{wg, wi [..., d, n x f], wo [..., n x f, d]}``: the experts side by
    side, ``1 / n`` folded into the down projection."""
    n, d, f = wg.shape[-3:]
    wide = lambda w: np.moveaxis(w, -3, -2).reshape(*w.shape[:-3], d, n * f)
    return {"wg": wide(wg), "wi": wide(wi),
            "wo": wo.reshape(*wo.shape[:-3], n * f, d) / n}


def cohere_config(size: str = "command-a-plus-05-2026",
                  **overrides) -> TransformerConfig:
    base = dict(
        vocab_size=262144,
        max_seq_len=200000,
        pos_embedding="rope",
        rope_theta=50000.0,
        norm="layernorm",
        norm_bias=False,
        norm_eps=1e-5,
        activation="swiglu",
        use_bias=False,
        tie_embeddings=True,
        layer_pattern=_PATTERN,
        nope_kinds=("full",),
        attn_window=4096,
        parallel_block=True,
        moe_gate="sigmoid",
        name=size,
    )
    base.update(_COHERE_SIZES[size])
    base.update(overrides)
    return TransformerConfig(**base)


def cohere(size: str = "command-a-plus-05-2026",
           **overrides) -> TransformerModel:
    return TransformerModel(cohere_config(size, **overrides))
