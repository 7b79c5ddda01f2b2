"""Block-sparse attention.

Parity: csrc/sparse_attention/ + deepspeed/ops/sparse_attention/ (SparseSelfAttention,
sparsity_config.py). The reference builds triton/CUDA block-sparse matmuls
from a layout tensor; here the same block layout feeds the Pallas flash
kernel's flat grid (ops/pallas/flash_attention.py `block_mask`): the
layout becomes one scalar-prefetch list of its live (q-block, k-block)
tiles, the kernel grid walks that list and nothing else, and masked tiles
are neither computed NOR fetched from HBM NOR a grid step — the MXU work,
the DMA bandwidth and the grid's length all scale with the layout's
density, like the reference's triton lut-driven sdd/dsd kernels.
No separate sdd/dsd/dds matmul trio needed; XLA/Mosaic fuse the rest.

Patterns mirror the reference's sparsity_config classes: Fixed (local +
periodic global), BigBird (window + global + random), BSLongformer (sliding
window + global blocks), Dense. Layouts are per-model static numpy tables:
one [nq, nk] 0/1 mask at kernel-block granularity.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np


@dataclass
class SparsityConfig:
    """Base: block size must equal the flash kernel's tile size."""

    block: int = 128

    def make_layout(self, seq_len: int) -> np.ndarray:  # pragma: no cover
        raise NotImplementedError

    def _n(self, seq_len: int) -> int:
        if seq_len % self.block != 0:
            raise ValueError(
                f"seq_len {seq_len} not divisible by sparsity block {self.block}"
            )
        return seq_len // self.block


@dataclass
class DenseSparsityConfig(SparsityConfig):
    """Parity: DenseSparsityConfig — all blocks visible (debug/reference)."""

    def make_layout(self, seq_len: int) -> np.ndarray:
        n = self._n(seq_len)
        return np.ones((n, n), np.int32)


@dataclass
class FixedSparsityConfig(SparsityConfig):
    """Parity: FixedSparsityConfig — each block attends to its local window
    of ``num_local_blocks`` and to the last ``num_global_blocks`` of every
    preceding window (the "summary" blocks other windows expose)."""

    num_local_blocks: int = 4
    num_global_blocks: int = 1

    def make_layout(self, seq_len: int) -> np.ndarray:
        n = self._n(seq_len)
        nl, ng = self.num_local_blocks, self.num_global_blocks
        layout = np.zeros((n, n), np.int32)
        for qi in range(n):
            window = qi // nl
            layout[qi, window * nl : (window + 1) * nl] = 1  # local window
            for w in range(window):  # global summary blocks of prior windows
                lo = (w + 1) * nl - ng
                layout[qi, max(lo, 0) : (w + 1) * nl] = 1
        return layout


@dataclass
class BigBirdSparsityConfig(SparsityConfig):
    """Parity: BigBirdSparsityConfig — sliding window + global + random."""

    num_sliding_window_blocks: int = 3
    num_global_blocks: int = 1
    num_random_blocks: int = 1
    seed: int = 0

    def make_layout(self, seq_len: int) -> np.ndarray:
        n = self._n(seq_len)
        w = self.num_sliding_window_blocks // 2
        layout = np.zeros((n, n), np.int32)
        for qi in range(n):
            layout[qi, max(0, qi - w) : min(n, qi + w + 1)] = 1  # window
        layout[:, : self.num_global_blocks] = 1  # global cols
        layout[: self.num_global_blocks, :] = 1  # global rows
        rng = np.random.RandomState(self.seed)
        for qi in range(n):
            for ki in rng.choice(n, size=min(self.num_random_blocks, n), replace=False):
                layout[qi, ki] = 1
        return layout


@dataclass
class BSLongformerSparsityConfig(SparsityConfig):
    """Parity: BSLongformerSparsityConfig — sliding window + chosen global
    block indices that everyone attends to (and that attend to everyone)."""

    num_sliding_window_blocks: int = 3
    global_block_indices: List[int] = field(default_factory=lambda: [0])

    def make_layout(self, seq_len: int) -> np.ndarray:
        n = self._n(seq_len)
        w = self.num_sliding_window_blocks // 2
        layout = np.zeros((n, n), np.int32)
        for qi in range(n):
            layout[qi, max(0, qi - w) : min(n, qi + w + 1)] = 1
        for g in self.global_block_indices:
            if g < n:
                layout[:, g] = 1
                layout[g, :] = 1
        return layout


@dataclass
class VariableSparsityConfig(SparsityConfig):
    """Parity: VariableSparsityConfig — local windows of varying width
    (``local_window_blocks``, last entry repeats), chosen global block
    indices, plus random blocks."""

    num_random_blocks: int = 0
    local_window_blocks: List[int] = field(default_factory=lambda: [4])
    global_block_indices: List[int] = field(default_factory=lambda: [0])
    seed: int = 0

    def make_layout(self, seq_len: int) -> np.ndarray:
        n = self._n(seq_len)
        layout = np.zeros((n, n), np.int32)
        # tile variable-width local windows over the block axis
        start = 0
        widths = list(self.local_window_blocks) or [1]
        wi = 0
        while start < n:
            w = widths[min(wi, len(widths) - 1)]
            end = min(start + w, n)
            layout[start:end, start:end] = 1
            start = end
            wi += 1
        for g in self.global_block_indices:
            if g < n:
                layout[:, g] = 1
                layout[g, :] = 1
        rng = np.random.RandomState(self.seed)
        for qi in range(n):
            if self.num_random_blocks:
                for ki in rng.choice(
                    n, size=min(self.num_random_blocks, n), replace=False
                ):
                    layout[qi, ki] = 1
        return layout


def causal_trim(layout: np.ndarray) -> np.ndarray:
    """Zero strictly-upper block diagonals (the kernel also causal-masks
    inside diagonal blocks; this just documents the block-level layout)."""
    return np.asarray(np.tril(np.ones_like(layout)) * layout, np.int32)


def sparse_attention(q, k, v, config: SparsityConfig, *, causal: bool = True,
                     segment_ids=None, alibi_slopes=None,
                     interpret: Optional[bool] = None):
    """Block-sparse attention in model layout q[B,S,H,D] → [B,S,H,D].

    Parity surface: SparseSelfAttention.forward. The layout is built once
    per (config, seq_len) and drives tile predication in the flash kernel.
    """
    from .pallas.flash_attention import flash_attention

    S = q.shape[1]
    layout = config.make_layout(S)
    if causal:
        layout = causal_trim(layout)
    return flash_attention(
        q, k, v, causal=causal, segment_ids=segment_ids,
        alibi_slopes=alibi_slopes, block_mask=layout,
        block_q=config.block, block_k=config.block, interpret=interpret,
    )


def dense_blocksparse_reference(q, k, v, layout, block, *, causal=True):
    """Oracle: dense attention with the block mask expanded to tokens."""
    import jax.numpy as jnp

    from .attention import xla_attention

    S = q.shape[1]
    n = S // block
    tok_mask = np.kron(np.asarray(layout)[:n, :n], np.ones((block, block)))
    bias = jnp.where(jnp.asarray(tok_mask) > 0, 0.0, -1e30)[None, None]
    return xla_attention(q, k, v, causal=causal, bias=bias)


def from_ds_config(sa_cfg) -> Optional[SparsityConfig]:
    """ds_config "sparse_attention" section → SparsityConfig (None = off).

    Parity: deepspeed/ops/sparse_attention get_sparse_attention_config."""
    mode = getattr(sa_cfg, "mode", "none")
    if mode in ("none", None):
        return None
    if mode == "dense":
        return DenseSparsityConfig(block=sa_cfg.block)
    if mode == "fixed":
        return FixedSparsityConfig(
            block=sa_cfg.block,
            num_local_blocks=sa_cfg.num_local_blocks,
            num_global_blocks=sa_cfg.num_global_blocks,
        )
    if mode == "bigbird":
        return BigBirdSparsityConfig(
            block=sa_cfg.block,
            num_sliding_window_blocks=sa_cfg.num_sliding_window_blocks,
            num_global_blocks=sa_cfg.num_global_blocks,
            num_random_blocks=sa_cfg.num_random_blocks,
        )
    if mode == "bslongformer":
        return BSLongformerSparsityConfig(
            block=sa_cfg.block,
            num_sliding_window_blocks=sa_cfg.num_sliding_window_blocks,
            global_block_indices=list(sa_cfg.global_block_indices),
        )
    if mode == "variable":
        return VariableSparsityConfig(
            block=sa_cfg.block,
            num_random_blocks=sa_cfg.num_random_blocks,
            local_window_blocks=[sa_cfg.num_local_blocks],
            global_block_indices=list(sa_cfg.global_block_indices),
        )
    raise ValueError(f"unknown sparse_attention mode {mode!r}")


def make_attention_impl(config: SparsityConfig):
    """An attention-signature callable for the engine's scoped impl stack."""

    def impl(q, k, v, *, causal=True, bias=None, segment_ids=None,
             alibi_slopes=None):
        if bias is not None:
            raise ValueError(
                "sparse_attention cannot compose with a dense attention bias"
            )
        return sparse_attention(
            q, k, v, config, causal=causal, segment_ids=segment_ids,
            alibi_slopes=alibi_slopes,
        )

    return impl
