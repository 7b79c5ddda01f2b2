"""Operations and bytes from shapes: the benchmark's own arithmetic.

Everything here is computed from the published sizes in a configuration
file (``benchmarks/configs/<name>.json``), never measured and never taken
from the program. The file's ``family`` names the module under
``benchmarks/families/`` that turns those sizes into a :class:`Shape`; no
family is named here. Conventions:

* a multiply-add is 2 FLOPs;
* model FLOPs per trained token = 3 x forward (forward + backward), the
  recompute of activation checkpointing NOT counted;
* forward per token = 2 x (matmul parameters a token really touches) +
  causal attention, counted once: 2 matmuls (QK^T and PV) x 2 FLOPs x
  heads x head_dim x S/2 keys on average;
* the embedding lookup is a gather, not a matmul; the output head is a
  matmul whether or not its weight is tied to the embedding;
* for a routed-expert layer a token touches ``top_k`` experts' matrices and
  the router.

``bench.py`` uses the same convention (3 x forward, remat not counted); the
arithmetic is copied here, not the file.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Shape:
    """The sizes the arithmetic here needs, under one spelling. A family
    (``benchmarks/families/<family>.py``) builds it from its configuration
    file's published keys; one whose layers count differently (latent
    attention, shared experts, state-space layers) returns a subclass that
    overrides the two methods, and everything below follows."""

    family: str
    d: int          # hidden size
    layers: int
    heads: int
    kv_heads: int
    hd: int         # head size
    ffn: int        # MLP / expert inner width
    vocab: int
    experts: int    # 0 = dense MLP
    top_k: int
    gated: bool     # SwiGLU (three matrices) or a plain two-matrix MLP
    tied: bool      # output head shares the embedding
    eps: float = 1e-5        # norm epsilon (the reference reads it)
    rope_theta: float = 0.0  # 0 = no rotary embedding

    def layer_matmul_params(self, active: bool = True) -> int:
        """Matmul parameters of one layer; ``active`` counts what ONE token
        touches (top_k experts), otherwise what is stored (all experts)."""
        attn = self.d * self.heads * self.hd * 2 \
            + self.d * self.kv_heads * self.hd * 2
        mlp = (3 if self.gated else 2) * self.d * self.ffn
        if self.experts:
            n = self.top_k if active else self.experts
            mlp = n * mlp + self.d * self.experts  # + router
        return attn + mlp

    def attention_flops_per_token(self, context: float) -> float:
        """QK^T and PV for one query token over ``context`` keys, all
        layers."""
        return 2 * 2 * self.layers * self.heads * self.hd * context


def head_params(s: Shape) -> int:
    return s.d * s.vocab


def stored_params(s: Shape) -> int:
    """All weights held in memory (biases and norm vectors left out:
    under 0.1 % in these families)."""
    embed = s.vocab * s.d * (1 if s.tied else 2)
    return s.layers * s.layer_matmul_params(active=False) + embed


def forward_flops_per_token(s: Shape, seq: int) -> float:
    """Forward pass for one token of a causal sequence of length ``seq``
    (average context seq/2: causal attention counted once)."""
    matmul = 2 * (s.layers * s.layer_matmul_params() + head_params(s))
    return matmul + s.attention_flops_per_token(seq / 2)


def train_flops_per_token(s: Shape, seq: int) -> float:
    """Forward + backward; the remat's recompute is not counted."""
    return 3 * forward_flops_per_token(s, seq)


# ---- kernels ---------------------------------------------------------------
def flash_train_cost(s: Shape, batch: int, seq: int, itemsize: int = 2):
    """The flash-attention kernels of ONE training step on ONE chip, all
    layers: (flops, bytes). ``batch`` is the chip's micro-batch.

    FLOPs the algorithm needs, causal: forward 2 matmuls, backward 4
    (dS = dO V^T, dQ = dS K, dK = dS^T Q, dV = P^T dO) plus the backward's
    own recompute of QK^T, which every flash backward does by construction
    and is therefore part of the kernel's necessary work: 7 matmuls of
    2 x hd x S^2/2 each. A remat'd second forward is NOT counted (it is
    recomputation the kernel does not need), so a step that runs it shows a
    lower share.

    Bytes the algorithm needs: forward reads Q, K, V and writes O; backward
    reads Q, K, V, O, dO and writes dQ, dK, dV (the per-row logsumexp is
    1/hd of a tensor and is left out).
    """
    per_matmul = 2 * batch * s.heads * s.hd * seq * seq / 2
    flops = 7 * per_matmul * s.layers
    q = batch * seq * s.heads * s.hd * itemsize
    kv = batch * seq * s.kv_heads * s.hd * itemsize
    fwd = 2 * q + 2 * kv
    bwd = 4 * q + 4 * kv
    return flops, (fwd + bwd) * s.layers


def roofline_seconds(flops: float, nbytes: float, peak: dict):
    """Least time the chip could take and which bound sets it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
