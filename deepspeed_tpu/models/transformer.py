"""TPU-native decoder transformer core.

One configurable functional decoder covers the reference's benchmark model
families (GPT-2, Llama, BLOOM, Mixtral — see models/{gpt2,llama,bloom,
mixtral}.py presets). Where the reference wraps torch nn.Modules, here a
model is (init, apply, loss, partition_specs) over an explicit parameter
pytree:

- layers are *stacked* along a leading L dim and applied with ``lax.scan``
  (fast XLA compiles at depth; the pipeline engine re-slices the same stack
  across pp stages)
- activations carry sharding constraints (models/sharding.py) so TP/SP/DP
  layouts propagate and XLA inserts the collectives
- attention is pluggable (ops.attention registry) so the Pallas flash kernel
  and ring/Ulysses sequence-parallel variants drop in without model changes
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from .sharding import constrain, current_topology

Params = Dict[str, Any]


@dataclass(frozen=True)
class MixerKind:
    """One kind of mixer a layer can have (named by ``mixer_types``, or
    declared by ``layer_pattern`` / ``kv_latent_dim`` for the kinds of
    models/decoding.py): the pool leaves it keeps a SLOT (indexed
    by slot, no page: begun at zero with a request, never shared), those it
    keeps a PAGE (through the page table), the module of ``models/`` that
    owns its parameters and pools, what it needs of the configuration
    beside its name (a field that must be set, and why) and, for a kind of
    models/decoding.py, the modules that give it a stack of its own."""

    slot: Tuple[str, ...]
    page: Tuple[str, ...]
    family: str
    needs: Tuple[str, str] = ("", "")
    # a kind of models/decoding.py that ``mixer_types`` may name: the
    # modules that give it a stack beside their own kinds
    stacked_by: Tuple[str, ...] = ()


MIXER_KINDS: Dict[str, MixerKind] = {
    # attention over every key of the K / V pool (int8 with its scales) or,
    # with an indexer (``index_topk``), over the best by its keys, which the
    # page keeps beside K and V (``ki``); named by ``mixer_types`` it lies in
    # the stack models/qwen3_next.py or models/lfm2.py gives it beside its
    # state layers
    "full": MixerKind((), ("k", "v", "k_scale", "v_scale", "ki"), "decoding",
                      stacked_by=("qwen3_next", "lfm2")),
    # attention over the last ``attn_window`` keys; a paged cache keeps the
    # window layers a pool and a page table of their own (``k_win`` ...)
    "window": MixerKind((), ("k", "v", "k_scale", "v_scale"), "decoding"),
    # latent attention as every layer of a ``kv_latent_dim`` model has it:
    # over every key of the latent pool or, with an indexer
    # (``index_topk``), over the best by its keys; named by ``mixer_types``
    # it lies in the stack models/ling.py gives it and, with ``index_kpool``
    # > 1, keeps the unfinished block's index keys a slot
    "mla": MixerKind(("ki_tail",), ("kv", "ki"), "decoding",
                     ("kv_latent_dim", "the width of its cached latent"),
                     stacked_by=("ling",)),
    # grouped-query attention over a learned selection of blocks of pages
    "sparse": MixerKind((), ("k", "v", "kc"), "minicpm",
                        ("block_sparse", "the geometry of its selection")),
    # linear attention with a decay a head: a float32 state a slot
    "lightning": MixerKind(("state",), (), "minicpm"),
    # the gated delta rule with a decay a channel: a float32 state and the
    # short convolution's last rows a slot
    "kda": MixerKind(("state", "conv"), (), "ling"),
    # latent attention over every key of the latent pool
    "latent": MixerKind((), ("kv",), "ling",
                        ("kv_latent_dim", "the width of its cached latent")),
    # power retention (degree 2) with a gate a kv head: a float32 state of
    # the symmetric square of the keys and its normaliser a slot
    "retention": MixerKind(("state", "norm"), (), "brumby"),
    # the gated delta rule with ONE decay a value head, unbounded (Gated
    # DeltaNet): a float32 state a value head and the short convolution's
    # last rows a slot
    "gdn": MixerKind(("state", "conv"), (), "qwen3_next",
                     ("gdn_value_heads", "the heads of its state")),
    # a gated short convolution as the whole mixer (LFM2): the last rows of
    # its gated input a slot, no state matrix
    "conv": MixerKind(("conv",), (), "lfm2"),
}

# the kinds ``layer_pattern`` may name: those of models/decoding.py that
# keep K / V pages (a contiguous cache holds a pattern's layers in one pool)
LAYER_KINDS = tuple(k for k, kind in MIXER_KINDS.items()
                    if kind.family == "decoding" and "k" in kind.page)


@dataclass(frozen=True)
class RopeTable:
    """One rotary table: plain (``theta`` alone) or YaRN-scaled (Peng et
    al. 2023, as HF's ``_compute_yarn_parameters``): frequencies above the
    ``beta_fast`` rotations of the original length are kept, those below
    ``beta_slow`` divided by ``factor``, a linear ramp between; cos and
    sin are both multiplied by ``attention_factor``."""

    theta: float = 10000.0
    factor: float = 1.0           # 1.0 = plain rotary
    original_len: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    def inv_freq(self, hd: int) -> np.ndarray:
        extra = self.theta ** -(np.arange(0, hd, 2, dtype=np.float64) / hd)
        if self.factor == 1.0:
            return extra.astype(np.float32)

        def dim(rotations):
            return hd * math.log(self.original_len / (
                rotations * 2 * math.pi)) / (2 * math.log(self.theta))

        low = max(math.floor(dim(self.beta_fast)), 0)
        high = min(math.ceil(dim(self.beta_slow)), hd - 1)
        ramp = np.clip((np.arange(hd // 2) - low) / max(high - low, 1e-3),
                       0.0, 1.0)
        return (extra / self.factor * ramp + extra * (1 - ramp)
                ).astype(np.float32)


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    hidden_size: int = 512
    num_layers: int = 4
    num_heads: int = 8
    num_kv_heads: Optional[int] = None  # None => MHA
    head_dim: Optional[int] = None
    intermediate_size: Optional[int] = None
    max_seq_len: int = 2048
    pos_embedding: str = "rope"  # rope | learned | alibi | none
    rope_theta: float = 10000.0
    norm: str = "rmsnorm"  # rmsnorm | layernorm
    norm_eps: float = 1e-5
    norm_bias: bool = True  # a layernorm's bias (False: mean-centred, a scale)
    activation: str = "swiglu"  # swiglu | gelu | gelu_new
    use_bias: bool = False
    tie_embeddings: bool = False
    embed_norm: bool = False  # BLOOM's word-embedding layernorm
    initializer_range: float = 0.02
    # MoE (Mixtral): >0 experts turns the MLP into a routed expert layer.
    num_experts: int = 0
    moe_top_k: int = 2
    moe_dispatch: str = "einsum"  # einsum (one-hot dots) | gather (indexed)
    moe_capacity_factor: float = 2.0
    moe_aux_loss_coef: float = 0.01
    moe_z_loss_coef: float = 1e-3
    # Residual-MoE (reference: deepspeed/moe/layer.py use_residual — the
    # PR-MoE paper): a dense MLP runs alongside the routed experts and a
    # learned 2-way per-token coefficient mixes the two outputs.
    moe_use_residual: bool = False
    # Layer kinds: a repeating period of "window" | "full" attention layers
    # (() = every layer full). A window layer's query i sees key j iff
    # 0 <= i - j < attn_window. ``rope_tables`` gives a kind its own rotary
    # table (absent = the plain table of ``rope_theta``); a kind named by
    # ``nope_kinds`` has none: its q and k are not rotated.
    layer_pattern: Tuple[str, ...] = ()
    attn_window: int = 0
    rope_tables: Tuple[Tuple[str, "RopeTable"], ...] = ()
    nope_kinds: Tuple[str, ...] = ()
    # The parallel block: ONE norm a layer (``ln1``), the mixer and the MLP
    # both read it, and ``h = h + mixer(n) + mlp(n)`` in one sum.
    parallel_block: bool = False
    qk_norm: bool = False  # RMSNorm over each head of q and k, before rotary
    # ``rotary_dim`` > 0: the leading values of a head that are rotated
    # (half-split pairs inside them; 0 = the whole head). ``attn_out_gate``:
    # ``W_q`` is twice as wide, a head's second half the gate of its output,
    # ``y = W_o (attn * sigmoid(gate))``, a gate a channel.
    rotary_dim: int = 0
    attn_out_gate: bool = False
    # Latent attention (``kv_latent_dim`` > 0): queries through a
    # ``q_latent_dim``-wide latent, every head's keys and values
    # up-projected from ONE ``kv_latent_dim``-wide latent a token, beside
    # which one ``qk_rope_dim``-wide rotary key serves all heads. The cache
    # holds that latent and that key alone; ``head_dim`` is the qk width
    # (``qk_nope_dim + qk_rope_dim``) and ``num_kv_heads`` 1.
    # ``attn_scale_mult`` multiplies the score scale ``head_dim ** -0.5``.
    q_latent_dim: int = 0
    kv_latent_dim: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    attn_scale_mult: float = 1.0
    # Learned sparse attention (``index_topk`` > 0): an indexer of
    # ``index_heads`` heads of ``index_dim`` scores every cached token for
    # every query from its own cached key, and attention sees the
    # ``index_topk`` best alone. Over a latent cache its queries come from
    # the query latent; without one (grouped-query attention over paged K
    # and V) from the layer's normed input, and every head of a query
    # attends the query's selection inside its own KV group.
    index_heads: int = 0
    index_dim: int = 0
    index_topk: int = 0
    # ``index_rope_dim``: the leading values of an index query and key that
    # are rotated (0 = ``qk_rope_dim``, the attention's own). ``index_kpool``
    # > 1: ONE index key a block of that many tokens, the mean of their
    # rotated keys (``decoding.pool_index_keys``); a query scores the blocks
    # whose last token is at or before it, attends the tokens of its
    # ``index_topk`` best BLOCKS and always the tokens after its last whole
    # block.
    index_rope_dim: int = 0
    index_kpool: int = 1
    # Router: "softmax" (top-k of a softmax, with a capacity; with
    # ``moe_capacity_factor`` 0 the top-k of a softmax over every routed
    # expert renormalised over the chosen, no token dropped, served only), or
    # "sigmoid_groups": sigmoid scores plus a learned selection bias, the
    # ``moe_groups_kept`` best of ``moe_groups`` groups by the sum of their
    # two best, the top-k inside them, weights from the unbiased scores
    # normalised and times ``moe_routed_scale``; no token dropped. "sigmoid":
    # the top-k of the sigmoid scores, normalised; no bias, group or scale.
    moe_gate: str = "softmax"
    moe_groups: int = 1
    moe_groups_kept: int = 1
    moe_routed_scale: float = 1.0
    # what the sigmoid_groups router adds to the sum of the chosen scores it
    # normalises by (0: the sum itself, guarded from zero)
    moe_norm_eps: float = 0.0
    # a shared expert of this width beside the routed ones: always on, or
    # times ``sigmoid(x w_sg)`` where the tree carries ``shared_gate``
    moe_shared_width: int = 0
    # One member's share of an expert-parallel layer: the router's width
    # (0 = ``num_experts``, all held) and the first expert of the
    # ``num_experts`` held here; the layer returns its partial sum.
    moe_routed_experts: int = 0
    moe_first_expert: int = 0
    # Leading dense layers: ``lead_dense_layers`` more layers BEFORE the
    # ``num_layers`` of the main stack, with a dense MLP of width
    # ``lead_dense_ffn``, in a stack of their own (``lead_layers``).
    lead_dense_layers: int = 0
    lead_dense_ffn: int = 0
    # The selection bias of the sigmoid_groups router is no parameter: it
    # takes no gradient, and after each step the engine moves every entry by
    # ``moe_bias_update_rate`` towards balance, by the sign of the mean
    # count less the expert's own count over the step (``noaux_tc``).
    moe_bias_update_rate: float = 0.0
    # Multi-token prediction: ``mtp_layers`` (0 or 1) module after the main
    # stack, one more block of the main stack's kind over
    # ``[norm(embed(t[i+1])) ; norm(h[i])] W_eh``, its own final norm, the
    # SHARED embedding and head; it predicts t[i+2] and its cross-entropy
    # joins the loss times ``mtp_loss_weight``.
    mtp_layers: int = 0
    mtp_loss_weight: float = 0.3
    # Mixers by layer (models/mixers.py): ``mixer_types`` names every layer
    # (the leading dense ones first), in an order that need be no period, by
    # a kind of ``MIXER_KINDS``: "sparse" (grouped-query attention over a
    # learned selection of blocks of the paged cache: ``block_sparse``, an
    # ops/pallas/block_sparse_attention.BlockSparse) and "lightning" (linear
    # attention whose cache is a state a slot, no page) of models/minicpm.py;
    # "kda" (the gated delta rule: a state and the last ``conv_kernel - 1``
    # pre-convolution rows a slot, log-decays bounded by ``kda_lower_bound``)
    # and "latent" (latent attention over every key of the latent pool) of
    # models/ling.py; "retention" (power retention of degree 2: a state a kv
    # head and its normaliser a slot, the denominator plus ``retention_eps``)
    # of models/brumby.py; "gdn" (the gated delta rule with one unbounded
    # decay a value head: ``gdn_value_heads`` value heads over
    # ``gdn_key_heads`` key heads of ``gdn_head_dim``, state and convolution
    # rows a slot) beside "full" (gated grouped-query attention over K / V
    # pages) of models/qwen3_next.py; "conv" (a gated short convolution of
    # ``conv_kernel`` taps, the last rows of its gated input a slot) beside
    # "full" of models/lfm2.py. Each kind has a parameter stack of its
    # own; the
    # MLP of a layer (dense lead | routed) is independent of its mixer.
    # ``mixer_layer_ids`` gives each layer its index in the published model
    # of ``mixer_depth`` layers (a cut keeps both: the decay of a lightning
    # layer and the residual scale follow them). muP: the embedding times ``scale_emb``, a residual
    # branch times ``scale_depth / sqrt(mixer_depth)``, the hidden state
    # over ``hidden_size / dim_model_base`` before the head.
    mixer_types: Tuple[str, ...] = ()
    mixer_layer_ids: Tuple[int, ...] = ()
    mixer_depth: int = 0
    block_sparse: Optional[Any] = None
    conv_kernel: int = 4
    kda_lower_bound: float = -5.0
    # Kimi Linear's published kda projections (0 = Ling's: a full-rank decay
    # and one output gate a head): the decay through ``kda_gate_rank``
    # values, and an output gate a CHANNEL through as many
    kda_gate_rank: int = 0
    gdn_key_heads: int = 0
    gdn_value_heads: int = 0
    gdn_head_dim: int = 0
    retention_eps: float = 1e-6
    # Hyper-connections (``hc_mult`` > 1, manifold-constrained: models/
    # mixers.py ``hyper_pre`` / ``hyper_post``): that many residual streams,
    # each sub-layer reading a learned mix of them and writing back through
    # a doubly-stochastic mix (``hc_sinkhorn_iters`` Sinkhorn rounds) of the
    # streams; 0 = the one stream and ``h = h + f(norm(h))``.
    hc_mult: int = 0
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    # SwiGLU inputs clamped: ``silu(min(gate, limit)) * clip(up, -limit,
    # limit)`` in dense, shared and routed MLPs alike (0 = no clamp)
    swiglu_limit: float = 0.0
    scale_emb: float = 1.0
    scale_depth: float = 1.0
    dim_model_base: int = 0
    name: str = "transformer"

    def __post_init__(self):
        bad = set(self.layer_pattern) - set(LAYER_KINDS)
        if bad:
            raise ValueError(
                f"layer_pattern kinds {sorted(bad)} (must be of {LAYER_KINDS})"
            )
        if self.layer_pattern and self.num_layers % len(self.layer_pattern):
            raise ValueError(
                f"num_layers {self.num_layers} is not whole periods of the "
                f"{len(self.layer_pattern)}-layer pattern"
            )
        if "window" in self.layer_pattern and self.attn_window < 1:
            raise ValueError("a window layer needs attn_window >= 1")
        if self.moe_gate not in ("softmax", "sigmoid_groups", "sigmoid"):
            raise ValueError(
                f"moe_gate {self.moe_gate!r} (softmax, sigmoid_groups or "
                "sigmoid)")
        if self.moe_gate == "sigmoid" and (
                self.moe_groups != 1 or self.moe_routed_scale != 1.0):
            raise ValueError(
                "the plain sigmoid router has no groups and no routed scale "
                "(moe_gate sigmoid_groups has both)")
        if set(self.nope_kinds) - set(self.layer_pattern):
            raise ValueError(
                f"nope_kinds {self.nope_kinds} names kinds of layer_pattern "
                f"{self.layer_pattern}")
        if self.parallel_block and (self.hc_mult or self.mixer_types):
            raise ValueError(
                "parallel_block: one norm and one sum a layer of "
                "layer_pattern's kinds; the layers mixer_types names and the "
                "residual streams of hc_mult norm each half-layer")
        if self.index_topk and self.kv_latent_dim and not self.q_latent_dim:
            raise ValueError("over a latent cache the indexer's queries come "
                             "from the query latent: index_topk with "
                             "kv_latent_dim needs q_latent_dim")
        if self.index_in_pages and (
                self.layer_pattern or self.mixer_types
                or self.pos_embedding != "rope"
                or not (self.index_heads and self.index_dim)):
            raise ValueError(
                "an indexer without a latent cache (index_topk, no "
                "kv_latent_dim) selects inside the paged K and V of full "
                "rotary layers: it needs index_heads and index_dim, and a "
                "window layer's selection (layer_pattern) or a named "
                "mixer's (mixer_types) is not computed")
        if self.is_latent and (
                self.layer_pattern or self.qk_nope_dim + self.qk_rope_dim
                != self.hd or self.kv_heads != 1):
            raise ValueError(
                "latent attention: head_dim is qk_nope_dim + qk_rope_dim, "
                "num_kv_heads 1 (one latent a token), one layer kind")
        if self.index_kpool > 1 and not (
                self.index_topk and "mla" in self.mixer_types):
            raise ValueError(
                "index_kpool pools the keys of an indexer (index_topk) whose "
                "layers mixer_types names mla: the unfinished block's keys "
                "are a slot's leaf")
        if self.hc_mult == 1 or (self.hc_mult and not self.mixer_types):
            raise ValueError(
                f"hc_mult {self.hc_mult}: several residual streams round "
                "the layers mixer_types names, or 0 for the one stream")
        if self.mtp_layers not in (0, 1):
            raise ValueError(
                f"mtp_layers {self.mtp_layers}: one module (it predicts the "
                "token after next) or none")
        if (self.moe_gate == "softmax" and self.moe_capacity_factor
                and self.moe_routed_experts not in (0, self.num_experts)):
            raise ValueError(
                "one member's share of an expert-parallel layer "
                "(moe_routed_experts) has room for every token: it is "
                "computed under the sigmoid routers, or under a softmax "
                "router that drops nothing (moe_capacity_factor 0); what a "
                "capacity drops is decided by every member's fill")
        if self.mixer_types:
            self._check_mixers()
        if self.routed_experts % self.moe_groups or not (
                0 <= self.moe_first_expert
                <= self.routed_experts - self.num_experts):
            raise ValueError(
                f"experts {self.moe_first_expert}..+{self.num_experts} of "
                f"{self.routed_experts} in {self.moe_groups} groups")

    def _check_mixers(self) -> None:
        """``mixer_types`` against the table of kinds: what each kind keeps
        and needs decides what the configuration must bring."""
        # (models/decoding.py's kinds are declared by layer_pattern and
        # kv_latent_dim: they have no stack a kind, but where the module
        # that owns the model's other kinds gives one, ``stacked_by``)
        named = sorted(k for k, kind in MIXER_KINDS.items()
                       if kind.family != "decoding" or kind.stacked_by)
        unknown = sorted(set(self.mixer_types) - set(named))
        if unknown:
            raise ValueError(
                f"mixer_types names {unknown}: no such mixer kind (have "
                f"{named}, see MIXER_KINDS)")
        names = list(dict.fromkeys(self.mixer_types))
        families = sorted({MIXER_KINDS[n].family for n in names}
                          - {"decoding"})
        if len(families) != 1 or any(
                families[0] not in MIXER_KINDS[n].stacked_by for n in names
                if MIXER_KINDS[n].family == "decoding"):
            raise ValueError(
                f"mixer_types mixes kinds of models/{families}: the kinds of "
                "one model share the module that owns their parameter "
                "stacks and pools (a kind of models/decoding.py has a stack "
                "beside the kinds of the modules MIXER_KINDS names for it: "
                "mla of models/ling.py, full of models/qwen3_next.py and "
                "models/lfm2.py)")
        for name in names:
            field, why = MIXER_KINDS[name].needs
            if field and not getattr(self, field):
                raise ValueError(f"a {name!r} mixer needs {field}: {why}")
        if (len(self.mixer_types) != self.total_layers
                or len(self.mixer_layer_ids) != self.total_layers
                or self.mixer_depth <= max(self.mixer_layer_ids)
                or self.layer_pattern):
            raise ValueError(
                "mixer_types names every layer (leading dense ones first), "
                "each with its published index under mixer_depth, and is "
                "the only list of layer kinds (no layer_pattern)")
        if "gdn" in names and (
                not self.gdn_key_heads or not self.gdn_head_dim
                or self.gdn_value_heads % self.gdn_key_heads):
            raise ValueError(
                "a gdn mixer's gdn_value_heads value heads read "
                "gdn_key_heads key heads of gdn_head_dim, a whole number "
                "each")
        if self.kda_lower_bound > 0 or self.conv_kernel < 2:
            raise ValueError(
                "kda_lower_bound bounds a log-decay (at most 0) and a short "
                "convolution has at least 2 taps")

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def hd(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def ffn(self) -> int:
        return self.intermediate_size or 4 * self.hidden_size

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def index_in_pages(self) -> bool:
        """An indexer without a latent cache: it selects inside the paged K
        and V of the full layers, its keys a pool of their own (``ki``)
        beside them on their page table."""
        return bool(self.index_topk) and not self.kv_latent_dim

    @property
    def moe_dropless(self) -> bool:
        """The router drops no token (every router but a softmax one under a
        capacity): the serving layer gives each held expert room for every
        token, and may hold one member's share of the layer."""
        return self.moe_gate != "softmax" or not self.moe_capacity_factor

    @property
    def has_window(self) -> bool:
        return "window" in self.layer_pattern

    @property
    def has_state(self) -> bool:
        """A layer keeps leaves a slot (a recurrent state), which are no
        page."""
        return any(self.slot_leaves_of(k) for k in set(self.mixer_types))

    def slot_leaves_of(self, kind: str) -> Tuple[str, ...]:
        """The leaves a layer of ``kind`` keeps a slot (an mla layer its
        unfinished block's index keys, where they are pooled)."""
        if kind == "mla" and self.index_kpool <= 1:
            return ()
        return MIXER_KINDS[kind].slot

    @property
    def mixer_family(self) -> str:
        """The module of models/ that owns the mixers' stacks and pools:
        its kinds' own (mla lies in the stack that module gives it)."""
        return next(MIXER_KINDS[k].family for k in self.mixer_types
                    if MIXER_KINDS[k].family != "decoding")

    @property
    def paged_layers(self) -> int:
        """Layers that keep pages (a state layer keeps none)."""
        if not self.mixer_types:
            return self.total_layers
        return sum(bool(MIXER_KINDS[k].page) for k in self.mixer_types)

    @property
    def is_latent(self) -> bool:
        """EVERY layer is latent attention (a model with mixers names its
        latent layers in ``mixer_types``)."""
        return self.kv_latent_dim > 0 and not self.mixer_types

    @property
    def latent_width(self) -> int:
        """Values a token keeps in a latent cache."""
        return self.kv_latent_dim + self.qk_rope_dim

    @property
    def routed_experts(self) -> int:
        """Experts the router chooses among (those held: ``num_experts``)."""
        return self.moe_routed_experts or self.num_experts

    @property
    def total_layers(self) -> int:
        return self.lead_dense_layers + self.num_layers

    def kind_count(self, kind: str) -> int:
        """Layers of ``kind`` in the whole stack."""
        if self.mixer_types:
            return self.mixer_types.count(kind)
        if not self.layer_pattern:
            return self.num_layers if kind == "full" else 0
        return self.layer_pattern.count(kind) * (
            self.num_layers // len(self.layer_pattern))

    def window_of(self, kind: str) -> Optional[int]:
        return self.attn_window if kind == "window" else None

    def rope_of(self, kind: str) -> Optional["RopeTable"]:
        """The rotary table of a layer kind, None for one of ``nope_kinds``."""
        if kind in self.nope_kinds:
            return None
        return dict(self.rope_tables).get(kind) or RopeTable(self.rope_theta)

    @property
    def ln_bias(self) -> bool:
        """The hidden-size norms carry a bias leaf."""
        return self.norm == "layernorm" and self.norm_bias

    @property
    def norms_per_layer(self) -> int:
        return 1 if self.parallel_block else 2

    def num_params(self) -> int:
        """Analytic parameter count (for flops profiler / partition planner)."""
        if self.mixer_types:
            from .mixers import family

            return family(self).num_params(self)
        d, v, L = self.hidden_size, self.vocab_size, self.num_layers
        ln_width = 2 * d if self.ln_bias else d  # scale (+bias)
        qkvo = d * self.num_heads * self.hd * 2 + d * self.kv_heads * self.hd * 2
        if self.is_latent:
            nh, ql, kl = self.num_heads, self.q_latent_dim, self.kv_latent_dim
            # (no query latent: one W_q, no norm)
            wq = d * ql + ql + ql * nh * self.hd if ql else d * nh * self.hd
            qkvo = (wq + d * self.latent_width
                    + kl + kl * nh * (self.qk_nope_dim + self.v_head_dim)
                    + nh * self.v_head_dim * d)
        if self.index_topk:  # queries from the query latent, or from d
            qkvo += ((self.q_latent_dim or d) * self.index_heads
                     * self.index_dim + d * self.index_dim
                     + 2 * self.index_dim + d * self.index_heads)
        if self.activation == "swiglu":
            mlp = 3 * d * self.ffn
        else:
            mlp = 2 * d * self.ffn
        if self.is_moe:
            dense_mlp = mlp
            mlp *= self.num_experts
            mlp += d * self.routed_experts  # router
            if self.moe_gate == "sigmoid_groups":
                mlp += self.routed_experts  # selection bias
            mlp += 3 * d * self.moe_shared_width
            if self.moe_use_residual:
                mlp += dense_mlp + 2 * d  # residual dense branch + coef
        biases = 0
        if self.use_bias:
            biases += self.num_heads * self.hd + 2 * self.kv_heads * self.hd + d
            if not self.is_moe and self.activation != "swiglu":
                biases += self.ffn + d
        if self.qk_norm:
            biases += 2 * self.hd
        per_layer = qkvo + mlp + biases + self.norms_per_layer * ln_width
        lead = self.lead_dense_layers * (
            qkvo + 3 * d * self.lead_dense_ffn
            + self.norms_per_layer * ln_width)
        embed = v * d + (self.max_seq_len * d if self.pos_embedding == "learned" else 0)
        if self.embed_norm:
            embed += ln_width
        head = 0 if self.tie_embeddings else v * d
        # an MTP module: a block of the main stack's kind, eh_proj, the two
        # norms before it and its own final norm
        mtp = self.mtp_layers * (per_layer + 2 * d * d + 3 * ln_width)
        return L * per_layer + lead + embed + head + ln_width + mtp


def _latent_attn_params(cfg: "TransformerConfig", nrm, lk, L: int,
                        out_scale: float, dtype) -> Params:
    """Attention leaves of ``L`` latent-attention layers (and their
    indexer's, under ``idx``), stacked."""
    d, nh = cfg.hidden_size, cfg.num_heads
    ql, kl = cfg.q_latent_dim, cfg.kv_latent_dim
    qk = cfg.qk_nope_dim + cfg.qk_rope_dim
    if ql:
        attn = {"wq_a": nrm(lk[0], L, d, ql),
                "q_norm": {"scale": jnp.ones((L, ql), dtype)},
                "wq_b": nrm(lk[1], L, ql, nh * qk)}
    else:  # no query latent: one W_q
        attn = {"wq": nrm(lk[0], L, d, nh * qk)}
    attn.update({
        "wkv_a": nrm(lk[2], L, d, cfg.latent_width),
        "kv_norm": {"scale": jnp.ones((L, kl), dtype)},
        "wkv_b": nrm(lk[10], L, kl, nh * (cfg.qk_nope_dim + cfg.v_head_dim)),
        "wo": nrm(lk[3], L, nh * cfg.v_head_dim, d, scale=out_scale),
    })
    if cfg.index_topk:
        attn["idx"] = _indexer_params(cfg, nrm, lk[11], L, dtype)
    return attn


def _indexer_params(cfg: "TransformerConfig", nrm, key, L: int,
                    dtype) -> Params:
    """The indexer's leaves of ``L`` layers, stacked: its queries from the
    query latent (``wq_b``) or, without one, from the layer's normed input
    (``wq``); one key a token (``wk``, LayerNorm ``k_norm``) and a weight a
    head (``w_proj``) from the normed input."""
    d, ql = cfg.hidden_size, cfg.q_latent_dim
    ik = jax.random.split(key, 3)
    return {
        ("wq_b" if ql else "wq"): nrm(ik[0], L, ql or d,
                                      cfg.index_heads * cfg.index_dim),
        "wk": nrm(ik[1], L, d, cfg.index_dim),
        "k_norm": {"scale": jnp.ones((L, cfg.index_dim), dtype),
                   "bias": jnp.zeros((L, cfg.index_dim), dtype)},
        "w_proj": nrm(ik[2], L, d, cfg.index_heads),
    }


# -----------------------------------------------------------------------------
# init
# -----------------------------------------------------------------------------
def init(cfg: TransformerConfig, rng: jax.Array, dtype=jnp.float32) -> Params:
    if cfg.mixer_types:
        from .mixers import family

        return family(cfg).init(cfg, rng, dtype)
    std = cfg.initializer_range
    keys = jax.random.split(rng, 16)
    d, hd, nh, nkv, f = cfg.hidden_size, cfg.hd, cfg.num_heads, cfg.kv_heads, cfg.ffn

    def nrm(key, *shape, scale=std):
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)

    def norm_params(with_bias: bool, lead=()):
        p = {"scale": jnp.ones((*lead, d), dtype)}
        if with_bias:
            p["bias"] = jnp.zeros((*lead, d), dtype)
        return p

    ln_bias = cfg.ln_bias
    params: Params = {
        "embed": {"tok": nrm(keys[0], cfg.vocab_size, d)},
        "final_norm": norm_params(ln_bias),
    }
    if cfg.pos_embedding == "learned":
        params["embed"]["pos"] = nrm(keys[1], cfg.max_seq_len, d)
    if cfg.embed_norm:
        params["embed_norm"] = norm_params(ln_bias)
    if not cfg.tie_embeddings:
        params["lm_head"] = nrm(keys[2], d, cfg.vocab_size)

    # residual-branch output projections get depth-scaled init (GPT-2 paper)
    out_scale = std / math.sqrt(2 * cfg.total_layers)

    def attn_params(lk, L):
        if cfg.is_latent:
            return _latent_attn_params(cfg, nrm, lk, L, out_scale, dtype)
        attn = {
            "wq": nrm(lk[0], L, d, nh * hd),
            "wk": nrm(lk[1], L, d, nkv * hd),
            "wv": nrm(lk[2], L, d, nkv * hd),
            "wo": nrm(lk[3], L, nh * hd, d, scale=out_scale),
        }
        if cfg.use_bias:
            for nm, width in (("bq", nh * hd), ("bk", nkv * hd), ("bv", nkv * hd), ("bo", d)):
                attn[nm] = jnp.zeros((L, width), dtype)
        if cfg.qk_norm:
            attn["q_norm"] = {"scale": jnp.ones((L, hd), dtype)}
            attn["k_norm"] = {"scale": jnp.ones((L, hd), dtype)}
        if cfg.index_topk:
            attn["idx"] = _indexer_params(cfg, nrm, lk[11], L, dtype)
        return attn

    def dense_mlp(lk, L, f):
        mlp = {"wi": nrm(lk[5], L, d, f), "wo": nrm(lk[6], L, f, d, scale=out_scale)}
        if cfg.activation == "swiglu":
            mlp["wg"] = nrm(lk[7], L, d, f)
        if cfg.use_bias:
            mlp["bi"] = jnp.zeros((L, f), dtype)
            mlp["bo"] = jnp.zeros((L, d), dtype)
        return mlp

    def norms(L):  # a parallel block has the one norm
        return {name: norm_params(ln_bias, (L,))
                for name in ("ln1", "ln2")[:cfg.norms_per_layer]}

    def main_stack(lk, L):
        """``L`` layers of the main stack's kind, stacked."""
        if cfg.is_moe:
            E = cfg.num_experts
            mlp = {
                "router": nrm(lk[4], L, d, cfg.routed_experts),
                "wi": nrm(lk[5], L, E, d, f),
                "wo": nrm(lk[6], L, E, f, d, scale=out_scale),
            }
            if cfg.activation == "swiglu":
                mlp["wg"] = nrm(lk[7], L, E, d, f)
            if cfg.moe_use_residual:
                mlp["res_wi"] = nrm(lk[8], L, d, f)
                mlp["res_wo"] = nrm(lk[9], L, f, d, scale=out_scale)
                if cfg.activation == "swiglu":
                    mlp["res_wg"] = nrm(lk[10], L, d, f)
                mlp["coef"] = nrm(lk[11], L, d, 2)
            if cfg.moe_gate == "sigmoid_groups":
                # the selection bias: added to the scores to choose, never to
                # weigh ("sel_": a leaf named b... is a bias drawn as zero)
                mlp["sel_bias"] = nrm(lk[8], L, cfg.routed_experts)
            if cfg.moe_shared_width:
                sk = jax.random.split(lk[9], 8)
                mlp["shared"] = dense_mlp(sk, L, cfg.moe_shared_width)
        else:
            mlp = dense_mlp(lk, L, f)
        return {**norms(L), "attn": attn_params(lk, L), "mlp": mlp}

    params["layers"] = main_stack(jax.random.split(keys[3], 12),
                                  cfg.num_layers)
    if cfg.lead_dense_layers:
        dk = jax.random.split(keys[4], 12)
        Ld = cfg.lead_dense_layers
        params["lead_layers"] = {
            **norms(Ld),
            "attn": attn_params(dk, Ld),
            "mlp": dense_mlp(dk, Ld, cfg.lead_dense_ffn),
        }
    if cfg.mtp_layers:
        # the embedding and the head are the main model's: no leaf here
        params["mtp"] = {
            "enorm": norm_params(ln_bias),
            "hnorm": norm_params(ln_bias),
            "eh_proj": nrm(keys[5], 2 * d, d),
            "layers": main_stack(jax.random.split(keys[6], 12),
                                 cfg.mtp_layers),
            "final_norm": norm_params(ln_bias),
        }
    return params


# -----------------------------------------------------------------------------
# building blocks
# -----------------------------------------------------------------------------
def _norm(cfg: TransformerConfig, p: Params, x: jax.Array) -> jax.Array:
    x32 = x.astype(jnp.float32)
    if cfg.norm == "rmsnorm":
        from ..ops.normalization import rmsnorm

        return rmsnorm(x32, p["scale"].astype(jnp.float32), cfg.norm_eps).astype(x.dtype)
    from ..ops.normalization import layernorm

    scale = p["scale"].astype(jnp.float32)
    bias = p["bias"].astype(jnp.float32) if "bias" in p else (
        jnp.zeros_like(scale))  # norm_bias False: centred and scaled alone
    return layernorm(x32, scale, bias, cfg.norm_eps).astype(x.dtype)


def _rope(q: jax.Array, k: jax.Array, positions: jax.Array,
          table: RopeTable):
    """Rotary embeddings by ``table`` (a layer kind's own, see
    :class:`RopeTable`); q/k: [B, S, H, hd], positions: [B, S]."""
    hd = q.shape[-1]
    mscale = table.attention_factor
    if table.factor == 1.0:
        # the plain table stays the float32 expression it was before tables
        # had kinds (``inv_freq`` works in float64 and differs in the last
        # bit), so a one-kind model's compiled program is unchanged
        freqs = 1.0 / (
            table.theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    else:
        freqs = jnp.asarray(table.inv_freq(hd))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, hd/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale

    def rot(x):
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)

    return rot(q), rot(k)


def _rms_last(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """RMSNorm over the last dim by plain lines (a head's values, a latent:
    widths the hidden-size norm kernel is not for)."""
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(ms + eps) * scale.astype(jnp.float32)
            ).astype(x.dtype)


def _qk_norm(cfg: TransformerConfig, p: Params, q: jax.Array, k: jax.Array):
    """RMSNorm over the head dim of every q and k head (``cfg.qk_norm``),
    each with its learned [hd] vector, before the rotary embedding."""
    return (_rms_last(q, p["q_norm"]["scale"], cfg.norm_eps),
            _rms_last(k, p["k_norm"]["scale"], cfg.norm_eps))


def alibi_slopes(num_heads: int) -> np.ndarray:
    """BLOOM's ALiBi head slopes (power-of-2 interpolation)."""
    closest = 2 ** math.floor(math.log2(num_heads))
    base = 2.0 ** (-(2.0 ** -(math.log2(closest) - 3)))
    slopes = [base**(i + 1) for i in range(closest)]
    if closest != num_heads:
        extra_base = 2.0 ** (-(2.0 ** -(math.log2(2 * closest) - 3)))
        slopes += [extra_base**(2 * i + 1) for i in range(num_heads - closest)]
    return np.asarray(slopes, dtype=np.float32)


def _latent_projections(cfg: TransformerConfig, p: Params, x: jax.Array,
                        positions: jax.Array):
    """What both forms of latent attention (this file's training form, the
    cached one of models/decoding.py) start from: x [B,S,d] -> (the query
    latent c_q [B,S,ql], None without one (``q_latent_dim`` 0: one ``wq``),
    q_nope [B,S,H,nope], q_pe [B,S,H,rd] rotated, the normed kv latent c_kv
    [B,S,kl], ONE rotated key k_pe [B,S,1,rd])."""
    B, S, _ = x.shape
    kl, nope, eps = cfg.kv_latent_dim, cfg.qk_nope_dim, cfg.norm_eps
    c_q = None
    if cfg.q_latent_dim:
        c_q = _rms_last(x @ p["wq_a"], p["q_norm"]["scale"], eps)
    q = (x @ p["wq"] if c_q is None else c_q @ p["wq_b"]).reshape(
        B, S, cfg.num_heads, nope + cfg.qk_rope_dim)
    kv_a = x @ p["wkv_a"]
    c_kv = _rms_last(kv_a[..., :kl], p["kv_norm"]["scale"], eps)
    q_pe, k_pe = q[..., nope:], kv_a[:, :, None, kl:]
    if cfg.qk_rope_dim:  # (0: rows without a rotary part, both empty)
        q_pe, k_pe = _rope(q_pe, k_pe, positions, cfg.rope_of("full"))
    return c_q, q[..., :nope], q_pe, c_kv, k_pe


def _latent_attention(cfg: TransformerConfig, p: Params, x: jax.Array,
                      positions: jax.Array,
                      segment_ids: Optional[jax.Array]) -> jax.Array:
    """Latent attention in its training form: every head's keys and values
    are up-projected from the token's latent and attended as plain heads (no
    cache, nothing absorbed), so the registered attention op (the flash
    kernels on the chip) takes them. Queries, keys and values are padded
    with zeros to the widest of the qk and the value widths, which changes
    neither a score nor an output; the op's own ``width ** -0.5`` is set
    right on the queries."""
    from ..ops.attention import attention as attn_op

    B, S, _ = x.shape
    H, rd = cfg.num_heads, cfg.qk_rope_dim
    nope, vd = cfg.qk_nope_dim, cfg.v_head_dim
    _, q_nope, q_pe, c_kv, k_pe = _latent_projections(cfg, p, x, positions)
    kv = (c_kv @ p["wkv_b"]).reshape(B, S, H, nope + vd)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (B, S, H, rd))], axis=-1)
    v = kv[..., nope:]
    width = max(cfg.hd, vd)
    mult = cfg.attn_scale_mult * (width / cfg.hd) ** 0.5
    if mult != 1.0:
        q = q * jnp.asarray(mult, q.dtype)

    def widen(t):
        gap = width - t.shape[-1]
        return jnp.pad(t, ((0, 0),) * 3 + ((0, gap),)) if gap else t

    q, k, v = (constrain(widen(t), ("dp", "fsdp"), "sp", None, None)
               for t in (q, k, v))
    out = attn_op(q, k, v, causal=True, bias=None, segment_ids=segment_ids,
                  alibi_slopes=None)[..., :vd]
    return out.reshape(B, S, H * vd) @ p["wo"]


def _attention(cfg: TransformerConfig, p: Params, x: jax.Array, positions: jax.Array,
               segment_ids: Optional[jax.Array],
               pos_default: bool = True, kind: str = "full") -> jax.Array:
    if cfg.is_latent:
        with jax.named_scope("latent_attention"):
            return _latent_attention(cfg, p, x, positions, segment_ids)
    from ..ops.attention import attention as attn_op
    from ..parallel.tensor_overlap import tp_in_proj, tp_out_proj

    B, S, d = x.shape
    nh, nkv, hd = cfg.num_heads, cfg.kv_heads, cfg.hd
    # qkv share ONE decomposed gather ring when overlap_comm is active
    # (plain einsums otherwise — tp_in_proj falls back per weight)
    qp, kp, vp = tp_in_proj(x, (p["wq"], p["wk"], p["wv"]))
    q = qp.reshape(B, S, nh, hd)
    k = kp.reshape(B, S, nkv, hd)
    v = vp.reshape(B, S, nkv, hd)
    if cfg.use_bias:
        q = q + p["bq"].reshape(1, 1, nh, hd)
        k = k + p["bk"].reshape(1, 1, nkv, hd)
        v = v + p["bv"].reshape(1, 1, nkv, hd)
    if cfg.qk_norm:
        q, k = _qk_norm(cfg, p, q, k)
    if cfg.pos_embedding == "rope" and cfg.rope_of(kind) is not None:
        q, k = _rope(q, k, positions, cfg.rope_of(kind))

    # ALiBi rides as per-head slopes: the flash kernel and the ring path
    # build -slope*|Δpos| from sequence indices in-kernel, so the [B,H,S,S]
    # bias tensor is never materialized. That is only faithful when
    # positions ARE the sequence indices (the default arange); custom or
    # gathered positions (left padding, random-LTD subsets) take the exact
    # dense bias computed from the real positions instead.
    slopes = bias = None
    if cfg.pos_embedding == "alibi":
        if pos_default:
            slopes = jnp.asarray(alibi_slopes(nh))
        else:
            rel = positions[:, None, :].astype(jnp.float32) - positions[:, :, None].astype(jnp.float32)
            bias = jnp.asarray(alibi_slopes(nh))[None, :, None, None] * (
                -jnp.abs(rel)
            )[:, None, :, :]  # [B,H,S,S]
    if kind == "window":
        # the lower edge of a window layer as an additive mask (the causal
        # upper edge stays the attention op's): this path is apply's and
        # training's; serving masks inside the paged kernel
        dist = positions[:, :, None] - positions[:, None, :]  # query - key
        edge = jnp.where(dist < cfg.attn_window, 0.0, -1e30)[:, None]
        bias = edge if bias is None else bias + edge

    topo = current_topology()
    if topo is not None and topo.sp_size > 1:
        # sequence parallel: Ulysses all-to-all or KV ring (parallel/sequence.py)
        from ..parallel.sequence import sp_attention

        out = sp_attention(
            q, k, v, causal=True, bias=bias, segment_ids=segment_ids,
            alibi_slopes=slopes,
        )
    else:
        q = constrain(q, ("dp", "fsdp"), "sp", "tp", None)
        k = constrain(k, ("dp", "fsdp"), "sp", "tp", None)
        v = constrain(v, ("dp", "fsdp"), "sp", "tp", None)
        out = attn_op(
            q, k, v, causal=True, bias=bias, segment_ids=segment_ids,
            alibi_slopes=slopes,
        )  # [B,S,H,hd]
    out = out.reshape(B, S, nh * hd)
    out = tp_out_proj(out, p["wo"])  # scatter ring under overlap_comm
    if cfg.use_bias:
        out = out + p["bo"]
    return out


def _act(cfg: TransformerConfig, x: jax.Array) -> jax.Array:
    if cfg.activation == "gelu_new":
        return jax.nn.gelu(x, approximate=True)
    return jax.nn.gelu(x, approximate=False)


def _swiglu(cfg: TransformerConfig, gate: jax.Array, up: jax.Array
            ) -> jax.Array:
    """``silu(gate) * up``, both clamped first where ``cfg.swiglu_limit``."""
    if cfg.swiglu_limit:
        gate = jnp.minimum(gate, cfg.swiglu_limit)
        up = jnp.clip(up, -cfg.swiglu_limit, cfg.swiglu_limit)
    return jax.nn.silu(gate) * up


def _mlp(cfg: TransformerConfig, p: Params, x: jax.Array, rng: Optional[jax.Array],
         train: bool, dense: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Returns (output, aux_loss). Dense MLP or routed MoE expert layer
    (``dense``: a leading dense layer of a routed model)."""
    if cfg.is_moe and not dense:
        from ..moe.sharded_moe import moe_layer

        return moe_layer(cfg, p, x, rng, train)
    from ..parallel.tensor_overlap import tp_in_proj, tp_out_proj

    if cfg.activation == "swiglu":
        # wi and the gate share one decomposed gather ring under overlap
        h, g = tp_in_proj(x, (p["wi"], p["wg"]))
        h = _swiglu(cfg, g, h)
    else:
        (h,) = tp_in_proj(x, (p["wi"],))
        if cfg.use_bias:
            h = h + p["bi"]
        h = _act(cfg, h)
    h = constrain(h, ("dp", "fsdp"), "sp", "tp")
    out = tp_out_proj(h, p["wo"])
    if cfg.use_bias and not cfg.activation == "swiglu":
        out = out + p["bo"]
    return out, jnp.zeros((), jnp.float32)


def _block(cfg: TransformerConfig, layer: Params, x: jax.Array, positions: jax.Array,
           segment_ids: Optional[jax.Array], rng: Optional[jax.Array], train: bool,
           pos_default: bool = True, kind: str = "full", dense: bool = False):
    """One block -> (x, aux loss, routing stats). ``dense``: a leading dense
    layer of a routed model. The stats are those of the sigmoid routers
    (moe/sharded_moe.moe_held_layer), None for any other MLP. With
    ``cfg.parallel_block`` the attention and the MLP read one norm and the
    stream takes both in one sum."""
    from jax.ad_checkpoint import checkpoint_name

    from ..parallel.tensor_overlap import seq_shard_axes

    # under overlap_comm the residual stream stays sequence-sharded over
    # (sp, tp) — the scatter rings produce that layout and the gather
    # rings consume it, so the residual adds (and the norms) cost zero
    # collectives between projections (Megatron-SP boundaries)
    seq_ax = seq_shard_axes(x)
    normed = _norm(cfg, layer["ln1"], x)
    h = _attention(cfg, layer["attn"], normed, positions, segment_ids,
                   pos_default, kind)
    h = checkpoint_name(h, "attn_out")  # selective remat anchor (attn_only)
    if not cfg.parallel_block:
        x = x + h
        x = constrain(x, ("dp", "fsdp"), seq_ax, None)
        normed = _norm(cfg, layer["ln2"], x)
    # (a parallel block: the MLP reads the norm the attention read)
    stats = None
    if cfg.is_moe and not dense and cfg.moe_gate != "softmax":
        from ..moe.sharded_moe import moe_held_layer

        m, stats = moe_held_layer(cfg, layer["mlp"], normed)
        aux = jnp.zeros((), jnp.float32)
    else:
        m, aux = _mlp(cfg, layer["mlp"], normed, rng, train, dense)
    if cfg.moe_shared_width and not dense:
        # the shared expert: a dense MLP every token takes, beside the routed
        m = m + _mlp(cfg, layer["mlp"]["shared"], normed, None, train, True)[0]
    m = checkpoint_name(m, "mlp_out")
    x = x + h + m if cfg.parallel_block else x + m
    x = constrain(x, ("dp", "fsdp"), seq_ax, None)
    return x, aux, stats


def apply_layer_stack(cfg: TransformerConfig, layers: Params, x: jax.Array,
                      positions: jax.Array, segment_ids, rng, train: bool,
                      remat_policy: Optional[str] = None, pld_keep=None,
                      ltd_keep: Optional[int] = None,
                      ltd_layers: Optional[Tuple[int, int]] = None,
                      pos_default: bool = True, dense: bool = False,
                      with_stats: bool = False):
    """Scan the stacked layer params over the sequence of blocks.

    ``dense``: the stack is a routed model's leading dense layers
    (``lead_layers``: a stack of its own before the main one). With
    ``with_stats`` the result gains the routing stats of the sigmoid_groups
    router, one row a layer (None for any other model).

    pld_keep: optional [L] per-layer keep probabilities (progressive layer
    dropping) — a dropped layer passes its input through unchanged.

    ltd_keep/ltd_layers: random-LTD (reference: data_pipeline/data_routing/
    basic_layer.py) — layers in the half-open range ``ltd_layers`` process a
    random ``ltd_keep``-token subset (gather → block → scatter; dropped
    tokens pass through). ``ltd_keep`` is static: the scheduler quantizes it
    so distinct compiled programs stay bounded. The range must be contiguous
    because a scan body needs one token-count shape for every layer it scans
    — the stack is split pre/ltd/post instead."""
    num_layers = jax.tree_util.tree_leaves(layers)[0].shape[0]
    use_pld = pld_keep is not None and train
    if use_pld and rng is None:
        raise ValueError(
            "progressive layer drop needs an rng (with rng=None every layer "
            "would fold the same zero key and the gates would be a fixed "
            "deterministic cut instead of per-layer/per-step sampling)"
        )
    use_ltd = (
        ltd_keep is not None
        and ltd_layers is not None
        and train
        and int(ltd_keep) < x.shape[1]
    )
    if use_ltd and rng is None:
        raise ValueError("random_ltd needs an rng to sample token subsets")

    # One body for every model: it runs a whole period of the layer pattern,
    # the kinds static and the layers of the period unrolled. A one-kind
    # model is the period of one: its stack, keys and keep probabilities are
    # scanned as they are (no reshape, no index), so its program is what it
    # was before patterns existed.
    pattern = cfg.layer_pattern or ("full",)
    period = len(pattern)
    groups = num_layers // period

    def grouped(a):
        return a if period == 1 else a.reshape(groups, period, *a.shape[1:])

    def member(a, j):
        return a if period == 1 else a[j]

    def body(carry, inp, *, ltd: bool = False):
        x, aux = carry
        stats = []
        for j, kind in enumerate(pattern):
            layer = jax.tree.map(lambda t: member(t, j), inp[0])
            key = member(inp[1], j)
            if ltd:
                from ..data_pipeline.random_ltd import (
                    gather_tokens,
                    sample_token_subset,
                    scatter_tokens,
                )

                B, S = x.shape[:2]
                idx = sample_token_subset(
                    jax.random.fold_in(key, 11), B, S, int(ltd_keep)
                )
                x_kept = gather_tokens(x, idx)
                pos_kept = jnp.take_along_axis(positions, idx, axis=1)
                seg_kept = (
                    jnp.take_along_axis(segment_ids, idx, axis=1)
                    if segment_ids is not None
                    else None
                )
                # gathered positions are no longer sequence indices:
                # pos_default False routes ALiBi through the exact
                # positions-derived bias
                out_kept, a, st = _block(
                    cfg, layer, x_kept, pos_kept, seg_kept, key, train,
                    pos_default=False, kind=kind, dense=dense,
                )
                out = scatter_tokens(x, out_kept, idx)
            else:
                out, a, st = _block(cfg, layer, x, positions, segment_ids,
                                    key, train, pos_default=pos_default,
                                    kind=kind, dense=dense)
            stats.append(st)
            if use_pld:
                keep = jax.random.bernoulli(
                    jax.random.fold_in(key, 7), member(inp[2], j))
                out = jnp.where(keep, out, x)
                a = jnp.where(keep, a, 0.0)
            x, aux = out, aux + a
        # the routing stats leave the scan as its ys: [trips, period, ...]
        return (x, aux), None if stats[0] is None else jax.tree.map(
            lambda *t: jnp.stack(t), *stats)

    import functools

    full_body = functools.partial(body, ltd=False)
    ltd_body = functools.partial(body, ltd=True)
    if remat_policy and remat_policy != "none":
        from ..runtime.activation_checkpointing import policy_by_name

        pol = policy_by_name(remat_policy)
        full_body = jax.checkpoint(full_body, policy=pol, prevent_cse=False)
        ltd_body = jax.checkpoint(ltd_body, policy=pol, prevent_cse=False)

    keys = (
        jax.random.split(rng, num_layers)
        if rng is not None
        else jnp.zeros((num_layers, 2), jnp.uint32)
    )
    xs_all = (jax.tree.map(grouped, layers), grouped(keys)) + (
        (grouped(pld_keep),) if use_pld else ())

    def seg_xs(lo, hi):  # layers lo..hi, whole periods
        if lo % period or hi % period:
            raise ValueError(
                f"layers {lo}..{hi} cut a period of the layer pattern "
                f"{pattern}: a scan runs whole periods"
            )
        return jax.tree.map(lambda a: a[lo // period:hi // period], xs_all)

    # ZeRO-3 one-layer-ahead parameter prefetch (runtime/zero/prefetch.py):
    # with the scope active, the scan carries a rotating gathered-params
    # slot so layer i+1's all-gather issues under layer i's math instead
    # of stalling every layer on its own fetch
    from ..runtime.zero.prefetch import current_prefetch

    z3_puts = current_prefetch()
    if z3_puts is not None and period > 1:
        raise NotImplementedError(
            "ZeRO-3 layer prefetch gathers one layer's slice a tick; a model "
            f"whose layers follow the pattern {pattern} scans periods"
        )

    def seg_scan(bodyfn, carry, lo, hi):
        xs = seg_xs(lo, hi)
        if z3_puts is not None:
            from ..runtime.zero.prefetch import scan_layers

            return scan_layers(bodyfn, carry, xs[0], xs[1:], z3_puts)
        return lax.scan(bodyfn, carry, xs)

    # NOTE: unrolling this scan (lax.scan(..., unroll=2)) was measured
    # 15% SLOWER on-chip at the record config (32,020 vs 37,682 tok/s) —
    # the duplicated remat/checkpoint bodies cost more than the saved
    # per-layer slice plumbing (the 16.9% DUS share in
    # docs/xprof_r5_winner.md is grad STACKING, not loop overhead).
    carry = (x, jnp.zeros((), jnp.float32))
    if use_ltd:
        lo, hi = int(ltd_layers[0]), int(ltd_layers[1])
        if not (0 <= lo < hi <= num_layers):
            raise ValueError(
                f"random_ltd layer range {ltd_layers} outside [0, {num_layers})"
            )
        parts = []
        if lo > 0:
            carry, st = seg_scan(full_body, carry, 0, lo)
            parts.append(st)
        carry, st = seg_scan(ltd_body, carry, lo, hi)
        parts.append(st)
        if hi < num_layers:
            carry, st = seg_scan(full_body, carry, hi, num_layers)
            parts.append(st)
        stats = None if parts[0] is None else jax.tree.map(
            lambda *a: jnp.concatenate(a), *parts)
    else:
        carry, stats = seg_scan(full_body, carry, 0, num_layers)
    x, aux = carry
    if not with_stats:
        return x, aux
    if stats is not None:  # one row a layer
        stats = jax.tree.map(lambda a: a.reshape(-1, *a.shape[2:]), stats)
    return x, aux, stats


# -----------------------------------------------------------------------------
# forward / loss
# -----------------------------------------------------------------------------
def embed_tokens(cfg: TransformerConfig, params: Params, input_ids: jax.Array,
                 positions: jax.Array, dtype) -> jax.Array:
    """Token (+pos) embedding; works for [B,S] and [M,mb,S] id shapes."""
    cast = lambda t: jax.tree.map(
        lambda a: a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating) else a, t
    )
    x = cast(params["embed"]["tok"])[input_ids]
    if cfg.pos_embedding == "learned":
        x = x + cast(params["embed"]["pos"])[positions]
    if cfg.embed_norm:
        x = _norm(cfg, cast(params["embed_norm"]), x)
    lead = (None,) * (input_ids.ndim - 2)
    # match the block boundary layout (seq over (sp, tp) under
    # overlap_comm) so the layer-scan carry is sharding-closed — a
    # mismatch would re-shard the residual stream every scanned layer
    # (shardlint R2 flags exactly that)
    from ..parallel.tensor_overlap import seq_shard_axes

    return constrain(x, *lead, ("dp", "fsdp"), seq_shard_axes(x), None)


def lm_head_weight(cfg: TransformerConfig, params: Params) -> jax.Array:
    """[d, V] output-projection weight (tied or standalone) — the single
    source of the head-layout convention for both the dense-logits and
    fused-CE loss paths."""
    return params["embed"]["tok"].T if cfg.tie_embeddings else params["lm_head"]


def lm_head_logits(cfg: TransformerConfig, params: Params, y: jax.Array) -> jax.Array:
    """Final projection → fp32 logits [..., S, V] (vocab tp-sharded).

    Operands stay in the compute dtype (bf16 → full MXU rate) with fp32
    accumulation; an fp32×fp32 matmul here would run ~8x slower on TPU."""
    head = lm_head_weight(cfg, params)
    logits = jnp.einsum(
        "...sd,dv->...sv", y, head.astype(y.dtype),
        preferred_element_type=jnp.float32,
    )
    lead = (None,) * (y.ndim - 3)
    return constrain(logits, *lead, ("dp", "fsdp"), "sp", "tp")


def masked_ce(logits: jax.Array, labels: jax.Array, num_mb_dims: int = 0):
    """(ce, total_valid_tokens); labels < 0 ignored (HF -100 style).

    num_mb_dims > 0: the first ``num_mb_dims`` dims index microbatches; each
    microbatch is normalized by its own token count and the results averaged
    — matching the engine's per-microbatch accumulation semantics."""
    mask = (labels >= 0).astype(jnp.float32)
    safe = jnp.maximum(labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    if num_mb_dims:
        red = tuple(range(num_mb_dims, labels.ndim))
        per_mb = nll.sum(red) / jnp.maximum(mask.sum(red), 1.0)
        return jnp.mean(per_mb), jnp.maximum(mask.sum(), 1.0)
    denom = jnp.maximum(mask.sum(), 1.0)
    return nll.sum() / denom, denom


def _refuse_uncached(cfg: TransformerConfig) -> None:
    """What only the paged serving step computes (models/decoding.py) is
    refused by the uncached forward, which training and ``forward`` use."""
    from ..config import DeepSpeedConfigError

    if cfg.index_topk:
        raise DeepSpeedConfigError(
            "the uncached forward (training, evaluation, forward) does not "
            "compute the indexer's selection (index_topk), over a latent "
            "cache or inside paged K and V: it is made from cached index "
            "keys alone; serve this configuration through init_serving with "
            "serving.paged")
    if cfg.is_moe and cfg.moe_gate == "softmax" and cfg.moe_dropless:
        raise DeepSpeedConfigError(
            "the uncached forward (training, evaluation, forward) routes a "
            "softmax gate under a capacity (moe/sharded_moe.moe_layer): a "
            "softmax router that drops nothing (moe_capacity_factor 0), "
            "whole or one member's share (moe_routed_experts), is computed "
            "by the serving layer alone, and the exchange between members "
            "by neither; serve this configuration through init_serving")
    if cfg.mixer_types:
        raise DeepSpeedConfigError(
            "the uncached forward (training, evaluation, forward) runs no "
            "mixer of mixer_types: a state layer's recurrence (lightning, "
            "kda, gdn, retention) and a conv layer's carried rows live in a "
            "slot's leaves, a sparse layer's block "
            "selection is made from cached compressed keys and a latent "
            "layer attends the latent pool, all in the paged arena alone; "
            "serve this "
            "configuration through init_serving with serving.paged")


def routing_stats_summary(stats) -> Dict[str, jax.Array]:
    """The step's model metrics from the sigmoid_groups router's stats
    (``counts`` [layers, routed experts], ``held`` [layers, experts held]):
    the rows routed to held experts a layer, and the fullest held expert
    over their mean."""
    held = stats["held"]
    return {
        "moe_rows_held": jnp.mean(jnp.sum(held, axis=-1)),
        "moe_rows_max_over_mean": jnp.mean(
            jnp.max(held, axis=-1) / jnp.maximum(jnp.mean(held, axis=-1),
                                                 1e-9)),
    }


def _hidden(cfg: TransformerConfig, params: Params, input_ids: jax.Array, *,
            dtype, train: bool, rng, positions, segment_ids, remat_policy,
            pld_keep, ltd_keep, ltd_layers):
    """Embedding and both stacks -> (hidden before the final norm [B,S,d],
    aux loss, routing stats one row a routed layer or None, positions)."""
    B, S = input_ids.shape
    _refuse_uncached(cfg)
    pos_default = positions is None
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    from ..ops.quantizer import cast_floating

    x = embed_tokens(cfg, params, input_ids, positions, dtype)
    if cfg.lead_dense_layers:
        lead_rng = None if rng is None else jax.random.fold_in(rng, 1)
        x, _ = apply_layer_stack(
            cfg, cast_floating(params["lead_layers"], dtype), x, positions,
            segment_ids, lead_rng, train, remat_policy,
            pos_default=pos_default, dense=True)
    x, aux, stats = apply_layer_stack(
        cfg, cast_floating(params["layers"], dtype), x, positions,
        segment_ids, rng, train, remat_policy, pld_keep, ltd_keep,
        ltd_layers, pos_default, with_stats=True,
    )
    return x, aux, stats, positions


def apply(cfg: TransformerConfig, params: Params, input_ids: jax.Array, *,
          dtype=jnp.bfloat16, train: bool = False, rng: Optional[jax.Array] = None,
          positions: Optional[jax.Array] = None, segment_ids=None,
          remat_policy: Optional[str] = None, pld_keep=None,
          ltd_keep: Optional[int] = None,
          ltd_layers: Optional[Tuple[int, int]] = None,
          return_hidden: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Forward pass → (logits fp32 [B,S,V], moe_aux_loss); with
    ``return_hidden`` the final normed hidden [B,S,d] instead of logits
    (the fused-CE path projects chunk-wise itself). The next-token logits
    do not depend on an MTP module, which only ``loss_fn`` runs."""
    from ..ops.quantizer import cast_floating

    x, aux, _, _ = _hidden(
        cfg, params, input_ids, dtype=dtype, train=train, rng=rng,
        positions=positions, segment_ids=segment_ids,
        remat_policy=remat_policy, pld_keep=pld_keep, ltd_keep=ltd_keep,
        ltd_layers=ltd_layers)
    x = _norm(cfg, cast_floating(params["final_norm"], dtype), x)
    if return_hidden:
        return x, aux
    return lm_head_logits(cfg, params, x), aux


def mtp_labels(labels: jax.Array) -> jax.Array:
    """The MTP module's targets: position ``i`` of next-token labels holds
    t[i+1], the module predicts t[i+2], and a position whose own next token
    is ignored is ignored."""
    nxt = jnp.concatenate(
        [labels[:, 1:], jnp.full_like(labels[:, :1], -1)], axis=1)
    return jnp.where(labels >= 0, nxt, -1)


def _mtp_hidden(cfg: TransformerConfig, params: Params, h: jax.Array,
                labels: jax.Array, positions: jax.Array, segment_ids, *,
                dtype, train: bool, rng, remat_policy):
    """The MTP module over the main stack's output ``h`` [B,S,d] (before
    the final norm) -> (its own normed hidden [B,S,d], routing stats).
    ``labels`` hold t[i+1] at position i (ignored ones embed token 0; their
    targets are ignored too)."""
    from ..ops.quantizer import cast_floating

    m = cast_floating(params["mtp"], dtype)
    emb = params["embed"]["tok"].astype(dtype)[jnp.maximum(labels, 0)]
    x = jnp.concatenate(
        [_norm(cfg, m["enorm"], emb), _norm(cfg, m["hnorm"], h)], axis=-1)
    x = x @ m["eh_proj"]
    mtp_rng = None if rng is None else jax.random.fold_in(rng, 2)
    x, _, stats = apply_layer_stack(
        cfg, m["layers"], x, positions, segment_ids, mtp_rng, train,
        remat_policy, with_stats=True)
    return _norm(cfg, m["final_norm"], x), stats


def loss_fn(cfg: TransformerConfig, params: Params, batch: Dict[str, jax.Array], *,
            dtype=jnp.bfloat16, train: bool = True, rng=None,
            remat_policy: Optional[str] = None, pld_keep=None,
            ltd_keep: Optional[int] = None,
            ltd_layers: Optional[Tuple[int, int]] = None) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token cross-entropy (fp32), labels < 0 are ignored (HF -100
    style); with an MTP module plus ``mtp_loss_weight`` times its own."""
    from ..ops.cross_entropy import (
        chunked_masked_ce,
        fused_ce_applicable,
        fused_ce_config,
    )
    from ..ops.quantizer import cast_floating
    from .sharding import current_topology

    fused_on, ce_chunk = fused_ce_config()
    fused = fused_on and fused_ce_applicable(cfg.vocab_size, ce_chunk,
                                             current_topology())

    def ce_of(x, labels):
        if fused:
            # memory path: final hidden → chunked CE, [B,S,V] never
            # materializes
            return chunked_masked_ce(x, lm_head_weight(cfg, params), labels,
                                     ce_chunk)
        return masked_ce(lm_head_logits(cfg, params, x), labels)

    h, aux, stats, positions = _hidden(
        cfg, params, batch["input_ids"], dtype=dtype, train=train, rng=rng,
        positions=batch.get("positions"),
        segment_ids=batch.get("segment_ids"), remat_policy=remat_policy,
        pld_keep=pld_keep, ltd_keep=ltd_keep, ltd_layers=ltd_layers)
    ce, denom = ce_of(
        _norm(cfg, cast_floating(params["final_norm"], dtype), h),
        batch["labels"])
    total = ce + cfg.moe_aux_loss_coef * aux if cfg.is_moe else ce
    metrics = {"lm_loss": ce, "moe_aux_loss": aux, "tokens": denom}
    if cfg.mtp_layers:
        with jax.named_scope("mtp"):
            xm, mtp_stats = _mtp_hidden(
                cfg, params, h, batch["labels"], positions,
                batch.get("segment_ids"), dtype=dtype, train=train, rng=rng,
                remat_policy=remat_policy)
            mtp_ce, _ = ce_of(xm, mtp_labels(batch["labels"]))
        total = total + cfg.mtp_loss_weight * mtp_ce
        metrics["mtp_loss"] = mtp_ce
        if stats is not None:  # the module's router: the rows after the main
            stats = jax.tree.map(lambda a, b: jnp.concatenate([a, b]),
                                 stats, mtp_stats)
    if stats is not None:
        metrics.update(routing_stats_summary(stats),
                       moe_counts=stats["counts"])
    return total, metrics


def make_lm_batch(input_ids: jax.Array, pad_id: int = -1) -> Dict[str, jax.Array]:
    """Shift inputs into (input_ids, labels) next-token form."""
    labels = jnp.concatenate(
        [input_ids[:, 1:], jnp.full((input_ids.shape[0], 1), pad_id, input_ids.dtype)], axis=1
    )
    return {"input_ids": input_ids, "labels": labels}


# -----------------------------------------------------------------------------
# partition specs (Megatron TP + ZeRO param axes; see runtime/zero/partition.py
# for how dp/fsdp axes are added per stage)
# -----------------------------------------------------------------------------
def tp_partition_specs(cfg: TransformerConfig, tp_divides_kv: bool = True) -> Params:
    """Tensor-parallel PartitionSpec tree matching init()'s param pytree.

    Column-parallel: qkv + mlp-in shard output dim over tp.
    Row-parallel: attn-out + mlp-out shard input dim over tp.
    Embeddings/lm_head shard vocab over tp (loss is vocab-parallel).
    """
    if cfg.is_latent or cfg.mixer_types or cfg.index_topk:
        # one latent a token serves every head, a block selection is a kv
        # group's, an indexer's a query's (every head's) and a state a
        # slot's: nothing splits by head, and every leaf is whole on every
        # device
        shapes = jax.eval_shape(partial(init, cfg), jax.random.PRNGKey(0))
        return jax.tree.map(lambda a: P(*([None] * a.ndim)), shapes)
    kv_tp = "tp" if tp_divides_kv else None
    ln = {"scale": P(None, None)}
    if cfg.ln_bias:
        ln["bias"] = P(None, None)
    norms = {name: ln for name in ("ln1", "ln2")[:cfg.norms_per_layer]}
    attn = {
        "wq": P(None, None, "tp"),
        "wk": P(None, None, kv_tp),
        "wv": P(None, None, kv_tp),
        "wo": P(None, "tp", None),
    }
    if cfg.use_bias:
        attn.update({"bq": P(None, "tp"), "bk": P(None, kv_tp),
                     "bv": P(None, kv_tp), "bo": P(None, None)})
    if cfg.qk_norm:
        attn["q_norm"] = {"scale": P(None, None)}
        attn["k_norm"] = {"scale": P(None, None)}

    def dense_mlp():
        mlp = {"wi": P(None, None, "tp"), "wo": P(None, "tp", None)}
        if cfg.activation == "swiglu":
            mlp["wg"] = P(None, None, "tp")
        if cfg.use_bias:
            mlp["bi"] = P(None, "tp")
            mlp["bo"] = P(None, None)
        return mlp

    if cfg.is_moe:
        mlp = {
            "router": P(None, None, None),
            "wi": P(None, "ep", None, "tp"),
            "wo": P(None, "ep", "tp", None),
        }
        if cfg.activation == "swiglu":
            mlp["wg"] = P(None, "ep", None, "tp")
        if cfg.moe_use_residual:
            mlp["res_wi"] = P(None, None, "tp")
            mlp["res_wo"] = P(None, "tp", None)
            if cfg.activation == "swiglu":
                mlp["res_wg"] = P(None, None, "tp")
            mlp["coef"] = P(None, None, None)
        if cfg.moe_gate == "sigmoid_groups":
            mlp["sel_bias"] = P(None, None)
        if cfg.moe_shared_width:
            mlp["shared"] = dense_mlp()
    else:
        mlp = dense_mlp()
    specs: Params = {
        "embed": {"tok": P("tp", None)},
        "final_norm": dict(scale=P(None), **({"bias": P(None)} if cfg.ln_bias else {})),
        "layers": {**norms, "attn": attn, "mlp": mlp},
    }
    if cfg.pos_embedding == "learned":
        specs["embed"]["pos"] = P(None, None)
    if cfg.embed_norm:
        specs["embed_norm"] = specs["final_norm"]
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(None, "tp")
    if cfg.lead_dense_layers:
        specs["lead_layers"] = dict(specs["layers"], mlp=dense_mlp())
    if cfg.mtp_layers:
        specs["mtp"] = {
            "enorm": specs["final_norm"], "hnorm": specs["final_norm"],
            "eh_proj": P(None, None), "layers": specs["layers"],
            "final_norm": specs["final_norm"],
        }
    return specs


class TransformerModel:
    """Bundles (config, init, apply, loss, specs) — the engine's model protocol."""

    def __init__(self, cfg: TransformerConfig):
        self.config = cfg

    def init(self, rng, dtype=jnp.float32):
        return init(self.config, rng, dtype)

    def apply(self, params, input_ids, **kw):
        return apply(self.config, params, input_ids, **kw)

    def loss(self, params, batch, **kw):
        return loss_fn(self.config, params, batch, **kw)

    def partition_specs(self, topology=None) -> Params:
        tp = topology.tp_size if topology is not None else 1
        kv_ok = tp <= 1 or (self.config.kv_heads % tp == 0)
        return tp_partition_specs(self.config, tp_divides_kv=kv_ok)

    def num_params(self) -> int:
        return self.config.num_params()

    def buffer_mask(self, params):
        """True at the leaves of ``params`` that are state and no parameter
        (the engine keeps its optimizer off them and calls
        :meth:`update_buffers` after each step): the selection bias of every
        sigmoid_groups router. None if the model has none."""
        cfg = self.config
        if not (cfg.is_moe and cfg.moe_gate == "sigmoid_groups"):
            return None
        return jax.tree_util.tree_map_with_path(
            lambda path, _: getattr(path[-1], "key", None) == "sel_bias",
            params)

    def update_buffers(self, params, metrics):
        """``params`` with every selection bias moved by the ``noaux_tc``
        rule: ``b_e += u * sign(mean count - count_e)`` over a layer's
        router outputs, from the step's own ``metrics["moe_counts"]`` (one
        row a routed layer: the main stack's, then the MTP module's)."""
        cfg = self.config
        counts = metrics.get("moe_counts")
        if counts is None:  # a step that reports the loss alone
            return params
        step = cfg.moe_bias_update_rate * jnp.sign(
            jnp.mean(counts, axis=-1, keepdims=True) - counts)

        def moved(stack, rows):
            mlp = dict(stack["mlp"])
            mlp["sel_bias"] = mlp["sel_bias"] + rows.astype(
                mlp["sel_bias"].dtype)
            return dict(stack, mlp=mlp)

        L = cfg.num_layers
        out = dict(params, layers=moved(params["layers"], step[:L]))
        if cfg.mtp_layers:
            out["mtp"] = dict(params["mtp"], layers=moved(
                params["mtp"]["layers"], step[L:]))
        return out
