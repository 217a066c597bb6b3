"""Flagship decoder-only transformer (Llama-family architecture) in flax linen.

This is the model the framework's benchmarks and multi-chip dry-runs drive
(BASELINE.md targets: Llama-2-7B FSDP on a pod; GPT-2-XL ZeRO-3).  Architecture:
pre-norm RMSNorm, rotary position embeddings, grouped-query attention, SwiGLU MLP —
the standard Llama-2/3 recipe, written TPU-first:

  - static shapes everywhere; layers optionally rolled into ``nn.scan``
    (compile-time win, and the substrate for pipeline parallelism);
  - optional ``jax.checkpoint`` per layer (remat ≡ activation checkpointing,
    the reference's ``FSDP_ACTIVATION_CHECKPOINTING``);
  - attention via ``ops.attention`` (XLA fused / pallas flash / ring);
  - tensor/sequence-parallel sharding is applied *outside* the model by
    path-based rules (``parallel/tensor_parallel.py``) — the module itself is
    placement-agnostic, per the design stance of SURVEY §7.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax import struct

from ..ops.attention import dot_product_attention
from ..ops.view_attention import (
    view_decode_applies,
    view_decode_attention,
    view_flash_applies,
    view_flash_attention,
)


def _constrain_sequence_parallel(x):
    """Shard activations [B, S, H] over the sp axis (batch stays on the data
    axes) so the ring path's shard_map sees already-sequence-sharded inputs —
    without this, GSPMD may keep activations replicated and gather at the
    shard_map boundary every layer."""
    from ..state import PartialState, is_initialized

    if not is_initialized():
        return x
    mesh = PartialState().mesh
    from ..parallel.mesh import present_data_axes, sp_shardable

    if not sp_shardable(mesh, x.shape[0], x.shape[1]):
        return x
    from jax.sharding import NamedSharding, PartitionSpec

    data = present_data_axes(mesh)
    spec = PartitionSpec(data if data else None, "sp", None)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


_REMAT_POLICIES = {
    "full": None,  # save nothing / recompute all
    "nothing_saveable": "nothing_saveable",
    "dots_saveable": "dots_saveable",
    "dots_with_no_batch_dims_saveable": "dots_with_no_batch_dims_saveable",
    # Save the per-layer projection outputs (q/k/v/o/gate/down, tagged
    # "proj_out" below) and recompute only the attention block and the
    # up_proj matmul in the backward.  This is the policy "dots_saveable"
    # *should* be on a transformer whose attention materializes [S, S] scores
    # (the XLA path): dots_saveable would save the S^2 logits — ~1 GB/layer
    # at seq 2048 — while full remat recomputes every matmul.  up_proj is
    # tagged "proj_wide" and excluded: its save is inter-sized (the largest,
    # tied with gate) while costing the same recompute FLOPs per byte as any
    # other matmul, and dropping exactly one wide save is what lets the
    # policy fit next to a full fp32 adam state on 16 GB chips.
    "proj_saveable": "proj_saveable",
}


def _remat_policy(cfg):
    """Resolve ``TransformerConfig.remat_policy`` to a jax checkpoint policy."""
    name = _REMAT_POLICIES[cfg.remat_policy]
    if name is None:
        return None
    if name == "proj_saveable":
        return jax.checkpoint_policies.save_only_these_names("proj_out")
    return getattr(jax.checkpoint_policies, name)


def _tag_proj(x, name: str = "proj_out"):
    """Mark a projection output saveable under remat_policy="proj_saveable"
    (identity otherwise).  ``name="proj_wide"`` marks it recompute-instead
    (see _REMAT_POLICIES)."""
    from jax.ad_checkpoint import checkpoint_name

    return checkpoint_name(x, name)


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """YaRN rope scaling (Peng et al. 2023) as DeepSeek's ``rope_scaling`` block
    gives it: frequencies above ``beta_fast`` turns over the original context
    stay, those below ``beta_slow`` are divided by ``factor``, a linear ramp
    between; ``mscale_all_dim`` enters the softmax scale squared."""

    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


def _yarn_m(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_frequencies(dim: int, theta: float, yarn: Optional[YarnScaling] = None) -> jax.Array:
    """``dim / 2`` rotary frequencies ``theta^(-2j/dim)``, YaRN-blended where
    ``yarn`` is given: the low and high correction dimensions floored and
    ceiled, a linear ramp between them, ``inv / factor`` past it."""
    j = jnp.arange(0, dim, 2, dtype=jnp.float32)
    inv = 1.0 / (theta ** (j / dim))
    if yarn is None:
        return inv

    def corr(beta):
        return dim * math.log(yarn.original_max_position / (2 * math.pi * beta)) / (2 * math.log(theta))

    low = max(math.floor(corr(yarn.beta_fast)), 0)
    high = min(math.ceil(corr(yarn.beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / ((high - low) or 0.001), 0.0, 1.0)
    return (inv / yarn.factor) * ramp + inv * (1.0 - ramp)


def rope_amplitude(yarn: Optional[YarnScaling]) -> float:
    """What YaRN multiplies cos and sin by: ``m(factor, mscale) / m(factor,
    mscale_all_dim)`` (``0.1 ln factor + 1`` at the defaults); 1 without it."""
    if yarn is None:
        return 1.0
    return _yarn_m(yarn.factor, yarn.mscale) / _yarn_m(yarn.factor, yarn.mscale_all_dim)


@dataclasses.dataclass(frozen=True)
class RopeSpec:
    """The rotary encoding of one kind of layer: its ``theta`` and, where given,
    its YaRN scaling (a :class:`YarnScaling` or its dict)."""

    theta: float
    yarn: Optional[YarnScaling] = None

    def __post_init__(self):
        if isinstance(self.yarn, dict):
            object.__setattr__(self, "yarn", YarnScaling(**self.yarn))


@dataclasses.dataclass(frozen=True)
class LatentAttentionSpec:
    """Multi-head latent attention (DeepSeek-V2): queries through a low-rank
    ``q_rank`` bottleneck, keys and values decompressed from one shared latent
    ``c_kv`` of ``kv_rank`` plus one rope key of ``rope_dim`` a token — which
    is all the cache holds (``models/latent_attention.py``)."""

    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    yarn: Optional[YarnScaling] = None

    def __post_init__(self):
        if isinstance(self.yarn, dict):
            object.__setattr__(self, "yarn", YarnScaling(**self.yarn))


@dataclasses.dataclass(frozen=True)
class ExpertSpec:
    """Dropless routed experts with a shared expert (``parallel/moe.py``
    :class:`~accelerate_tpu.parallel.moe.RoutedExperts`).  The router scores
    all ``num_routed`` experts and picks ``top_k`` among them (from the best
    ``topk_group`` of ``n_group`` groups); this device holds experts ``held =
    [lo, hi)`` and computes their part of the result.  Gates are ``scaling *
    score``, renormalised over the chosen instead where ``norm_topk``.  The
    first ``dense_layers`` layers keep a dense MLP of ``dense_width``
    (``intermediate_size`` when None).

    ``score_func`` is what the router's logits pass through before the choice
    (``"softmax"`` over all experts, or ``"sigmoid"`` of each).  With
    ``select_bias`` the layer holds a leaf ``expert_bias [num_routed]`` that is
    added to the scores for the CHOICE only (the correction that balances the
    load without an auxiliary loss); the gates are the chosen experts' scores
    without it.  ``scale_normed``: the renormalised gates of ``norm_topk`` are
    multiplied by ``scaling`` as well."""

    num_routed: int
    top_k: int
    width: int
    held: Optional[Tuple[int, int]] = None
    n_group: int = 1
    topk_group: int = 1
    scaling: float = 1.0
    norm_topk: bool = False
    shared_width: int = 0
    dense_layers: int = 0
    dense_width: Optional[int] = None
    score_func: str = "softmax"
    select_bias: bool = False
    scale_normed: bool = False

    def __post_init__(self):
        if self.score_func not in ("softmax", "sigmoid"):
            raise ValueError(f"Unknown score_func {self.score_func!r}; choose 'softmax' or 'sigmoid'")
        if self.select_bias and self.n_group > 1:
            raise ValueError("select_bias chooses among all experts: n_group must be 1")
        held = (0, self.num_routed) if self.held is None else tuple(int(e) for e in self.held)
        object.__setattr__(self, "held", held)
        if not 0 <= held[0] < held[1] <= self.num_routed:
            raise ValueError(f"held experts {held} must lie within [0, {self.num_routed})")
        if self.num_routed % self.n_group or not 1 <= self.topk_group <= self.n_group:
            raise ValueError(
                f"n_group {self.n_group} must divide num_routed {self.num_routed} "
                f"and topk_group {self.topk_group} lie within it"
            )
        if self.top_k > self.topk_group * (self.num_routed // self.n_group):
            raise ValueError(f"top_k {self.top_k} exceeds the experts of {self.topk_group} groups")

    @property
    def num_held(self) -> int:
        return self.held[1] - self.held[0]


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    head_dim: Optional[int] = None
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    # Architecture family switches (models/hf_compat.py maps real HF
    # checkpoints onto these): the Llama recipe is the default; GPT-2 is
    # norm_type="layernorm" + use_bias=True + positional="learned" +
    # mlp_variant="gelu" + tie_word_embeddings=True.
    norm_type: str = "rmsnorm"         # "rmsnorm" | "layernorm" (centered, with bias)
    # MPT's no_bias LayerNorms: centered statistics but no bias parameter
    norm_bias: bool = True
    use_bias: bool = False             # biases on attention/MLP projections
    # "alibi" (BLOOM/MPT): no positional params at all — per-head linear
    # distance penalties added to the attention logits
    positional: str = "rope"           # "rope" | "learned" (wpe-style table) | "alibi"
    # "gelu" is the tanh approximation (GPT-2 gelu_new); "gelu_exact" the erf
    # form (GPT-NeoX); "relu" the OPT family; "geglu" the gated variant with
    # a tanh-gelu gate (Gemma) — same three-matrix layout as swiglu
    mlp_variant: str = "swiglu"        # "swiglu" | "gelu" | "gelu_exact" | "relu" | "geglu"
    # Learned-position table offset: OPT reserves the first 2 rows (padding
    # convention), so position i reads row i+2 and the table has
    # max_seq_len + pos_offset rows.
    pos_offset: int = 0
    # Parallel-residual block (GPT-J / GPT-NeoX): x + attn(norm(x)) +
    # mlp(norm'(x)) computed from the SAME input instead of sequentially.
    # shared_norm=True (GPT-J) reuses one norm for both branches.
    parallel_residual: bool = False
    shared_norm: bool = False
    # Partial rotary: rope applied to the first rope_dim dims of each head
    # (GPT-J rotary_dim, NeoX rotary_pct), the rest pass through.  None =
    # full head_dim.  rope_interleaved selects GPT-J's rotate-every-two
    # pairing over the default rotate-half convention.
    rope_dim: Optional[int] = None
    rope_interleaved: bool = False
    # Per-site bias overrides (GPT-J: biasless attention but biased MLP);
    # None falls back to use_bias.  lm_head_bias covers GPT-J's biased head.
    attn_bias: Optional[bool] = None
    mlp_bias: Optional[bool] = None
    lm_head_bias: bool = False
    # Qwen2-family: bias on q/k/v only (o_proj and MLP stay biasless).
    # None falls back to attn_bias / use_bias.
    qkv_bias: Optional[bool] = None
    # Mistral-family sliding-window attention: each token sees the previous
    # ``sliding_window`` positions (self included).  None = full causal.
    sliding_window: Optional[int] = None
    # Gemma-family switches: RMSNorm computes (1 + scale) with zeros-init
    # scale, and embeddings are multiplied by sqrt(hidden_size).
    norm_unit_offset: bool = False
    embed_scale: bool = False
    # BLOOM: a LayerNorm directly after the token embedding
    # (word_embeddings_layernorm)
    embed_norm: bool = False
    dtype: Any = jnp.bfloat16          # activation/compute dtype
    param_dtype: Any = jnp.float32
    remat: bool = False                # jax.checkpoint each layer
    # checkpoint policy for per-layer remat: "full" recomputes everything;
    # "dots_saveable" keeps matmul outputs (≈25% less backward recompute for
    # ~1 extra activation set per layer — the usual MFU/memory middle ground)
    remat_policy: str = "full"
    scan_layers: bool = False          # roll layers into lax.scan
    attention_impl: str = "xla"        # "xla" | "blocked" | "pallas" | "ring" (sp sequence parallel)
    ring_attention_layout: str = "contiguous"  # "contiguous" | "zigzag" (balanced causal ring)
    dropout_rate: float = 0.0
    # fp8 matmuls (TransformerEngine analog, ops/fp8.py): projection/MLP dots
    # quantize operands to e4m3 fwd / e5m2 bwd with just-in-time scaling.
    # Set via Accelerator(mixed_precision="fp8") + prepare(model), or directly.
    use_fp8: bool = False
    fp8_margin: int = 0
    fp8_format: str = "HYBRID"         # "HYBRID" (e4m3 fwd / e5m2 bwd) | "E4M3"
    # Weight-only int8/int4 inference (bnb analog, ops/quantization.py):
    # projection/MLP kernels become qweight+scales params dequantized in-kernel.
    # Convert trained weights with quantize_model_params, or pass
    # quantization=... to load_checkpoint_and_dispatch.
    quantization: Optional[int] = None  # None | 8 | 4
    quantization_block_size: int = 64
    # Mixture-of-Experts (num_experts == 0 -> dense MLP).  Reference MoE surface
    # is DeepSpeed passthrough only (utils/dataclasses.py:792-798); here experts
    # are a first-class stacked axis sharded over the ``ep`` mesh axis.
    num_experts: int = 0
    num_experts_per_tok: int = 2
    expert_capacity_factor: float = 2.0
    router_aux_loss_coef: float = 0.01
    # Architectures beyond the Llama/GPT-2 block, as nested specs (a dict from
    # a config file is accepted): latent attention replaces Attention and the
    # K/V cache rows; experts replaces the MLP of every layer past its
    # dense_layers.  Both None: the parameter tree is what it always was.
    latent_attention: Optional[LatentAttentionSpec] = None
    experts: Optional[ExpertSpec] = None
    # Power retention (models/retention.py, a RetentionSpec or its dict)
    # replaces Attention in every layer, and the cache of rows a token by a
    # recurrent state of fixed size a lane (StateCache).
    retention: Optional[Any] = None
    # Qwen3-family head norm: an RMSNorm over each head's width on q and on k
    # (one learned scale of head_dim each, shared by the heads), before rope.
    # Off: no such parameters, the tree is what it was.
    qk_norm: bool = False
    # Two kinds of attention layer in one stack: one entry a layer, "window"
    # (sees the last ``sliding_window`` positions) or "full" (every position).
    # ``sliding_window`` then applies to the "window" layers only, and the
    # serving pool keeps a ring of pages for them and whole tables for the
    # "full" ones (serving/paging.py MixedKVPool).  ``rope_full_layers=False``
    # leaves the "full" layers without any positional encoding; ``full_rope``
    # (a RopeSpec or its dict) gives them a rope of their own (theta, YaRN)
    # while the "window" layers keep plain rope at ``rope_theta``.  None:
    # every layer is what ``sliding_window`` and ``positional`` say.
    layer_types: Optional[Tuple[str, ...]] = None
    rope_full_layers: bool = True
    full_rope: Optional[RopeSpec] = None
    # A sigmoid gate on the attention output, one value a query head and
    # channel, projected from the layer's normed input (``attn/gate_proj``).
    attention_gate: bool = False
    # A norm on each branch's OUTPUT as well as its input: ``x + norm(attn(
    # norm(x)))``, then the same round the MLP (``attn_out_norm``,
    # ``mlp_out_norm``).
    sandwich_norm: bool = False
    # Attention program for PagedKVCache forwards (the serving engine's
    # in-model paged windows): "xla" is the live-masked-gather reference —
    # bitwise identical to the contiguous slab; "pallas" the in-place paged
    # decode kernel; "flash_prefill" the chunk-wide flash prefill kernel
    # (both in ops/paged_attention.py — the choice is static config because
    # a verify window and a short prefill chunk are indistinguishable by
    # runtime shape).  None adds parameters, so one set of params serves
    # Transformers differing only in these fields.
    paged_kernel: str = "xla"

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def cache_row_shapes(self) -> Tuple[Tuple[int, int], Tuple[int, int]]:
        """``((heads, width), (heads, width))`` of the two arrays a token and
        layer leave in the cache (``KVCache.k`` / ``.v``, the page pool's
        ``pages_k`` / ``pages_v``): keys and values of every kv head, or, under
        latent attention, the one latent ``c_kv`` and the one rope key."""
        la = self.latent_attention
        if la is not None:
            return (1, la.kv_rank), (1, la.rope_dim)
        return (self.num_kv_heads, self.resolved_head_dim), (self.num_kv_heads, self.resolved_head_dim)

    def layer_kind(self, layer: int) -> Optional[str]:
        """``"window"`` / ``"full"`` for a stack of two kinds (``layer_types``),
        None where every layer is one kind."""
        return None if self.layer_types is None else self.layer_types[layer]

    def resolved_expert_capacity(self, n_tokens: int) -> int:
        """Per-expert token buffer: factor * even-split share, rounded up to a
        multiple of 8 (TPU sublane tiling; keeps the dispatch einsum MXU-friendly)."""
        even = n_tokens * self.num_experts_per_tok / max(self.num_experts, 1)
        cap = int(-(-self.expert_capacity_factor * even // 1))
        return max(8, -(-cap // 8) * 8)

    def __post_init__(self):
        if isinstance(self.latent_attention, dict):
            object.__setattr__(self, "latent_attention", LatentAttentionSpec(**self.latent_attention))
        if isinstance(self.experts, dict):
            object.__setattr__(self, "experts", ExpertSpec(**self.experts))
        if isinstance(self.full_rope, dict):
            object.__setattr__(self, "full_rope", RopeSpec(**self.full_rope))
        if self.full_rope is not None and (self.layer_types is None or not self.rope_full_layers):
            raise ValueError(
                "full_rope is the rope of the 'full' layers of a stack of two kinds: it "
                "needs layer_types, and rope_full_layers=False (no positions on them) "
                "contradicts it"
            )
        if self.retention is not None:
            from .retention import RetentionSpec

            if isinstance(self.retention, dict):
                object.__setattr__(self, "retention", RetentionSpec(**self.retention))
            excluded = {
                "sliding_window": self.sliding_window is not None,
                "latent_attention": self.latent_attention is not None,
                "quantization": self.quantization is not None,
                "use_fp8": self.use_fp8,
                "paged_kernel": self.paged_kernel != "xla",
                "attention_impl": self.attention_impl != "xla",
                "positional": self.positional != "rope",
                "scan_layers": self.scan_layers,
            }
            for name, used in excluded.items():
                if used:
                    raise ValueError(
                        f"retention excludes {name}: a retention layer is a full-causal "
                        f"rope layer in the model's dtype with a state of its own, and "
                        f"{name} must keep its default"
                    )
            if self.num_heads % self.num_kv_heads or self.retention.gate_heads not in (
                    None, self.num_kv_heads):
                raise ValueError(
                    "retention keeps one state and one gate a key/value head: num_kv_heads "
                    f"{self.num_kv_heads} must divide num_heads {self.num_heads} and equal "
                    f"gate_heads {self.retention.gate_heads}"
                )
        if self.layer_types is not None:
            object.__setattr__(self, "layer_types", tuple(self.layer_types))
            kinds = set(self.layer_types)
            if len(self.layer_types) != self.num_layers or not kinds <= {"window", "full"}:
                raise ValueError(
                    f"layer_types needs one of 'window' / 'full' for each of the "
                    f"{self.num_layers} layers, got {self.layer_types}"
                )
            if "window" in kinds and self.sliding_window is None:
                raise ValueError("layer_types has 'window' layers: set sliding_window")
            excluded = {
                "latent_attention": self.latent_attention is not None,
                "retention": self.retention is not None,
                "scan_layers": self.scan_layers,
                "positional": self.positional != "rope",
                "paged_kernel": self.paged_kernel != "xla",
            }
            for name, used in excluded.items():
                if used:
                    raise ValueError(
                        f"layer_types excludes {name}: layers of two kinds are unrolled "
                        f"rope (or position-free) per-head attention on the 'xla' paged "
                        f"path, and {name} must keep its default"
                    )
        if self.sandwich_norm and self.parallel_residual:
            raise ValueError("sandwich_norm norms each branch of a sequential block: "
                             "parallel_residual must stay off")
        if self.latent_attention is not None and (
            self.positional != "rope" or self.sliding_window is not None
            or self.quantization is not None or self.use_fp8 or self.paged_kernel != "xla"
        ):
            raise ValueError(
                "latent_attention is a full-causal rope attention in the model's "
                "dtype: positional, sliding_window, quantization, use_fp8 and "
                "paged_kernel must keep their defaults"
            )
        if self.experts is not None and (self.num_experts > 0 or self.quantization is not None
                                         or self.use_fp8):
            raise ValueError(
                "experts (the dropless layer) excludes num_experts (the capacity "
                "dispatch), quantization and use_fp8"
            )
        if self.experts is not None and self.experts.dense_layers and self.scan_layers:
            raise ValueError("scan_layers needs one block repeated; experts.dense_layers mixes two")
        if self.remat_policy not in _REMAT_POLICIES:
            raise ValueError(
                f"Unknown remat_policy {self.remat_policy!r}; "
                f"choose from {sorted(_REMAT_POLICIES)}"
            )
        if self.ring_attention_layout not in ("contiguous", "zigzag"):
            raise ValueError(
                f"Unknown ring_attention_layout {self.ring_attention_layout!r}; "
                "choose 'contiguous' or 'zigzag'"
            )
        if self.norm_type not in ("rmsnorm", "layernorm"):
            raise ValueError(
                f"Unknown norm_type {self.norm_type!r}; choose 'rmsnorm' or 'layernorm'"
            )
        if self.positional not in ("rope", "learned", "alibi"):
            raise ValueError(
                f"Unknown positional {self.positional!r}; choose 'rope', "
                "'learned' or 'alibi'"
            )
        if self.mlp_variant not in ("swiglu", "gelu", "gelu_exact", "relu", "geglu"):
            raise ValueError(
                f"Unknown mlp_variant {self.mlp_variant!r}; choose 'swiglu', "
                "'gelu', 'gelu_exact', 'relu' or 'geglu'"
            )
        if self.sliding_window is not None and self.sliding_window <= 0:
            raise ValueError(f"sliding_window must be positive, got {self.sliding_window}")
        if self.paged_kernel not in ("xla", "pallas", "flash_prefill"):
            raise ValueError(
                f"Unknown paged_kernel {self.paged_kernel!r}; choose 'xla', "
                "'pallas' or 'flash_prefill'"
            )
        if self.paged_kernel != "xla" and (
            self.sliding_window is not None or self.positional == "alibi"
        ):
            raise ValueError(
                f"paged_kernel={self.paged_kernel!r} supports full-causal "
                "rope/learned models; sliding_window and alibi need the "
                "'xla' reference path"
            )

    @classmethod
    def llama2_7b(cls, **kw):
        return cls(**{**dict(vocab_size=32000, hidden_size=4096, intermediate_size=11008,
                             num_layers=32, num_heads=32, num_kv_heads=32), **kw})

    @classmethod
    def gpt2_xl_equiv(cls, **kw):
        """GPT-2-XL-sized decoder (1.5B) for the ZeRO-3 parity target."""
        return cls(**{**dict(vocab_size=50257, hidden_size=1600, intermediate_size=6400,
                             num_layers=48, num_heads=25, num_kv_heads=25,
                             max_seq_len=1024), **kw})

    @classmethod
    def gpt2(cls, **kw):
        """Real GPT-2 architecture (124M): layernorm+bias, learned positions,
        gelu MLP, tied embeddings — the checkpoint-interop target
        (models/hf_compat.py builds larger family members from config.json)."""
        return cls(**{**dict(
            vocab_size=50257, hidden_size=768, intermediate_size=3072,
            num_layers=12, num_heads=12, num_kv_heads=12, max_seq_len=1024,
            norm_type="layernorm", use_bias=True, positional="learned",
            mlp_variant="gelu", tie_word_embeddings=True,
        ), **kw})

    @classmethod
    def tiny(cls, **kw):
        """Test-sized config (unit tests, dry-runs)."""
        return cls(**{**dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                             num_layers=2, num_heads=4, num_kv_heads=2,
                             max_seq_len=128), **kw})

    @classmethod
    def tiny_moe(cls, **kw):
        """Test-sized MoE variant (ep-sharding tests, dry-runs)."""
        return cls(**{**dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                             num_layers=2, num_heads=4, num_kv_heads=2,
                             max_seq_len=128, num_experts=4, num_experts_per_tok=2), **kw})


class KVCache(struct.PyTreeNode):
    """Static-shape KV cache for autoregressive decode.

    The reference's published benchmark is token generation
    (``/root/reference/benchmarks/big_model_inference.py:108-139``); its cache
    lives inside transformers' dynamic python objects.  TPU-first the cache is
    one pytree of fixed-shape arrays, so ONE decode executable serves every
    token.  The attention kind that owns the cache decides its layout, from
    the configuration (``cache_row_shapes``, ``latent_attention``):

    * per-head rows (:class:`Attention`): ``[num_layers, batch, kv_heads *
      head_dim, max_len]`` — a position's keys (values) of every head flat in
      one dimension, positions minor.  The TPU tiles an array's two minor
      dimensions to (sublanes, 128 lanes) whatever order the program writes
      them in; with ``(kv_heads, head_dim)`` minor, GPT-2-XL's 25 heads pad to
      32 and its 64 values to 128, so a ``[.., max_len, 25, 64]`` cache
      occupies, and every decode step reads, 2.56 x its bytes.  ``kv_heads *
      head_dim`` by ``max_len`` pads no more than the last tile, and it is the
      serving page pool's own order on the chip (``[.., Hkv, Dh, page]``,
      ``page`` minor), so a gather of pages into this cache moves whole tiles.
      Attention reads it as ``[B, Hkv, Dh, M]`` (:func:`cached_attention`): a
      reshape that splits a dimension on a tile boundary.
    * latent rows (:mod:`~accelerate_tpu.models.latent_attention`):
      ``[num_layers, batch, max_len, 1, width]`` — one latent (rope key) of
      512 (64) values a position, position-major: 512 lanes tile exactly.

    The unrolled forward threads the STACKED arrays through the layers: layer
    ``i`` writes only its new positions at ``[i, lane, .., index : index + S]``
    (:func:`_write_columns`; latent rows: :func:`_write_rows`) and attends over
    the static slice ``k[i]``.  No layer's slab is sliced out, copied or
    stacked back, which is what lets XLA keep the write in place — in a
    donated cache and in a ``lax.scan`` carry alike (a slice -> update ->
    ``jnp.stack`` round trip compiles to several copies of the whole cache per
    forward; ``tests/test_tpu_compile.py`` holds the compiled decode window to
    that).  ``scan_layers=True`` instead lets ``nn.scan`` slice and restack
    per-layer slabs itself.

    ``index`` is either a scalar (the whole batch decodes in lockstep — the
    ``generate`` path) or a per-lane ``[B]`` vector (each lane sits at its own
    position — the continuous-batching slot pool of
    :mod:`accelerate_tpu.serving`, where a "lane" is a request slot).  Writes
    and attention masking follow whichever form is present.
    """

    k: jax.Array            # [L, B, n_kv_heads * head_dim, max_len]
    v: jax.Array            # [L, B, n_kv_heads * head_dim, max_len]
    index: jax.Array        # int32 next write position: scalar, or [B] per lane
    # Under latent attention ``k`` holds the latent ``c_kv`` rows ``[L, B,
    # max_len, 1, kv_rank]`` and ``v`` the shared rope key ``[.., 1,
    # rope_dim]``: the rank of the arrays says which kind built them.

    @classmethod
    def create(cls, config: "TransformerConfig", batch_size: int, max_len: Optional[int] = None,
               dtype: Any = None, per_lane_index: bool = False) -> "KVCache":
        max_len = max_len if max_len is not None else config.max_seq_len
        lead = (config.num_layers, batch_size)
        if config.latent_attention is None:
            shapes = [lead + (heads * width, max_len) for heads, width in config.cache_row_shapes]
        else:
            shapes = [lead + (max_len,) + row for row in config.cache_row_shapes]
        dtype = dtype if dtype is not None else config.dtype
        return cls(
            k=jnp.zeros(shapes[0], dtype),
            v=jnp.zeros(shapes[1], dtype),
            index=jnp.zeros((batch_size,) if per_lane_index else (), jnp.int32),
        )

    @property
    def max_len(self) -> int:
        return self.k.shape[3] if self.k.ndim == 4 else self.k.shape[2]


class PagedKVCache(struct.PyTreeNode):
    """Paged KV cache: the serving page pool threaded *through* the model.

    Where :class:`KVCache` owns a contiguous per-lane slab, this carries the
    shared refcounted page pool (``[L, num_pages, Hkv, page, D]``) plus each
    lane's block table — attention reads pages in place
    (:mod:`accelerate_tpu.ops.paged_attention`), selected by
    ``TransformerConfig.paged_kernel``.  Like :class:`KVCache` the stacked
    pool goes through the unrolled layers whole: layer ``i`` inserts its new
    rows at ``[i, page, :, offset]`` in place and reads ``pages_k[i]``.
    Scales are ALWAYS present (ones for direct-store dtypes) so the pytree
    structure — and with it the compiled window signature — does not fork on
    the KV dtype; quantized-ness is the static page dtype.  ``active`` gates
    writes: frozen lanes' scatters are rerouted to the null page exactly like
    the gather windows in :mod:`accelerate_tpu.serving.pool`.  ``quant_err``
    accumulates the max abs KV round-trip error of values written this
    forward (0 when native) — the engine surfaces it as
    ``serve/kv_quant_error``.
    """

    pages_k: jax.Array      # [L, num_pages, n_kv_heads, page, head_dim]
    pages_v: jax.Array
    k_scales: jax.Array     # [L, num_pages, n_kv_heads] f32 dequant scales
    v_scales: jax.Array
    tables: jax.Array       # [N, pages_per_lane] int32 block tables
    index: jax.Array        # [N] int32 next write position per lane
    active: jax.Array       # [N] bool write gate (frozen lanes -> null page)
    quant_err: jax.Array    # f32 scalar, running max round-trip error

    @property
    def max_len(self) -> int:
        return self.tables.shape[1] * self.pages_k.shape[3]


class MixedKVCache(struct.PyTreeNode):
    """The cache of a stack of two kinds of layer (``layer_types``) as the
    serving pool gathers it: each kind's layers stacked in arrays of their own,
    because the two keep different numbers of positions.

    * ``k`` / ``v`` ``[n_full, B, Hkv * Dh, max_len]``: the "full" layers, a
      :class:`KVCache`'s layout, position ``p`` in column ``p``.
    * ``k_ring`` / ``v_ring`` ``[n_window, B, Hkv * Dh, W]``: the "window"
      layers, a ring: position ``p`` lives in column ``p % W``.  ``W`` is the
      pool's ring of pages (window + the largest prefill chunk, rounded up to
      pages, + one page), so a column is overwritten only by a position at
      least ``W`` later, which no query that still sees the old one can have
      written (:func:`cached_attention`, ``ring=True``).

    Each of the four may instead be a tuple of one array a layer, ``[B, Hkv *
    Dh, M]`` each (the decode window's views where the page copy kernel builds
    them): a layer then writes and reads its own array whole, where a layer of
    a stacked array is a static slice that the compiler copies out of the
    carried array at every step.

    ``index`` is a :class:`KVCache`'s.  ``page`` (static) is the granule of a
    chunk's write: a chunk starts on a page boundary and is whole pages long,
    so it lands in the ring page by page and a page never straddles the ring's
    end.  ``generate`` does not use this cache: its contiguous
    :class:`KVCache` keeps ``max_len`` columns for every layer and masks the
    window layers by the band."""

    k: jax.Array
    v: jax.Array
    k_ring: jax.Array
    v_ring: jax.Array
    index: jax.Array
    page: int = struct.field(pytree_node=False, default=1)

    @property
    def max_len(self) -> int:
        return (self.k[0] if isinstance(self.k, tuple) else self.k).shape[-1]


def create_cache(config: "TransformerConfig", batch_size: int, max_len: Optional[int] = None,
                 **kw):
    """The cache ``generate`` threads for ``config``, by the kind its attention
    keeps: rows a token up to ``max_len`` (:class:`KVCache`), or a retention
    model's state, which has no ``max_len``
    (:class:`~accelerate_tpu.models.retention.StateCache`)."""
    if config.retention is not None:
        from .retention import StateCache

        return StateCache.create(config, batch_size, max_len, **kw)
    return KVCache.create(config, batch_size, max_len, **kw)


def cached_attention(q, k, v, q_positions, window=None, alibi=False,
                     tree_mask=None, ring=False):
    """Attention of ``q`` [B,S,Hq,D] against a full cache ``k``/``v``
    [B,Hkv*D,M]: one layer of a per-head :class:`KVCache`, positions minor.

    Key slot ``j`` is visible to query ``i`` iff ``j <= q_positions[i]`` —
    since the cache is written contiguously from 0, this is simultaneously the
    causal mask and the valid-entry mask (unwritten slots have ``j`` beyond
    every query position).  ``window`` adds the sliding-window band (Mistral):
    ``j > q_positions[i] - window``.  Runs as a masked einsum with an fp32
    softmax over all ``M`` columns: right for verify windows, whose queries
    are a few rows.  For a prefill chunk it is three passes over
    ``[B,Hkv,rep,S,M]`` float32 scores through HBM, most of them over columns
    that hold nothing yet: the one full-attention layer's ``[8,6,512,32768]``
    took 1.24 s of a 12 s slice of the long-document cell (ledger, PR 37;
    ``fusion.*_f32_8_6_512_``), 6 ms of a 23 ms chunk whose live keys needed
    0.4.  So a chunk's worth of bfloat16 rows against a wide view of 128-wide
    heads on a TPU goes to the Pallas flash kernel
    (:func:`~accelerate_tpu.ops.view_attention.view_flash_attention`: only the
    key blocks that can hold a visible key, scores in fast memory), chosen by
    what this call can see (:func:`~accelerate_tpu.ops.view_attention
    .view_flash_applies`) and by nothing else; ``tree_mask``, ``alibi``, float32,
    64-wide heads, narrow views, short queries and every other platform keep the
    einsum below as it was.  A decode step (``S == 1``) over such a view is
    bound by the view's bytes, and the einsum reads every column of every
    lane twice (Trinity's full layer: 5.7 ms of a 24.9 ms window on one TPU
    v5e); it goes to the decode kernel
    (:func:`~accelerate_tpu.ops.view_attention.view_decode_attention`: only
    each lane's live key blocks), by the same kind of rule
    (:func:`~accelerate_tpu.ops.view_attention.view_decode_applies`).  GQA
    groups fold into the query tensor (``[B,S,Hkv,rep,D]``) so the cache is
    contracted UNexpanded — a ``jnp.repeat`` of K/V would multiply the
    per-token HBM reads by the query/kv head ratio on the decode hot path.

    ``tree_mask`` switches the causal row mask to *token-tree* visibility for
    speculative tree verification: an ``[S, S]`` ancestor-or-self boolean
    (compile-time constant, ``tree_mask[i, j]`` = query node ``i`` may see
    tree node ``j``).  The ``S`` tree nodes occupy consecutive cache slots
    starting at each lane's pre-call frontier ``q_positions[:, 0]`` (node 0
    is the lane's pending token, so its depth — and position offset — is 0);
    node ``i`` then sees all committed history ``j < frontier`` plus exactly
    its own root-to-self chain inside the tree span.  Mutually exclusive with
    ``window``/``alibi`` (the engine only builds tree windows for full-causal
    rope/learned models).

    ``ring`` (with ``window``): the cache is a ring of ``M`` columns, position
    ``p`` in column ``p % M`` (:class:`MixedKVCache`).  Column ``j`` then holds
    the newest position congruent to it that has been written, ``hi - ((hi -
    j) mod M)`` with ``hi`` the lane's last query position (the call's writes
    precede its reads), and the band mask is taken over those positions;
    columns not yet written come out negative and are masked.
    """
    if tree_mask is None and not alibi:
        if view_flash_applies(q, k):
            return view_flash_attention(q, k, v, q_positions, window=window, ring=ring)
        if view_decode_applies(q, k):
            return view_decode_attention(q, k, v, q_positions, window=window, ring=ring)
    b, s, n_q, d = q.shape
    m = k.shape[2]
    n_kv = k.shape[1] // d
    rep = n_q // n_kv
    qg = q.reshape(b, s, n_kv, rep, d)
    k = k.reshape(b, n_kv, d, m)
    v = v.reshape(b, n_kv, d, m)
    scale = d ** -0.5
    logits = jnp.einsum("bqhrd,bhdk->bhrqk", qg, k).astype(jnp.float32) * scale
    j = jnp.arange(m)
    if tree_mask is not None:
        if window is not None or alibi:
            raise ValueError(
                "tree_mask needs a full-causal model: sliding_window and "
                "alibi are not supported under tree verification"
            )
        tm = jnp.asarray(tree_mask, bool)               # [S, S] constant
        base = q_positions[:, 0]                        # [B] lane frontier
        rel = j[None, :] - base[:, None]                # [B, M] slot -> node id
        within = (rel >= 0) & (rel < s)
        anc = tm[:, jnp.clip(rel, 0, s - 1)]            # [S, B, M]
        allowed = (j[None, None, :] < base[:, None, None]) | (
            within[:, None, :] & jnp.transpose(anc, (1, 0, 2))
        )                                               # [B, S, M]
        mask = allowed[:, None, None, :, :]             # [B,1,1,S,M]
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        out = jnp.einsum("bhrqk,bhdk->bqhrd", probs, v)
        return out.reshape(b, s, n_q, d)
    if alibi:
        rel = (j[None, None, None, None, :]
               - q_positions[:, None, None, :, None]).astype(jnp.float32)
        slopes = alibi_slopes(n_q).reshape(n_kv, rep)
        logits = logits + slopes[None, :, :, None, None] * rel
    if ring:
        hi = q_positions[:, -1:]                                     # [B, 1]
        held = (hi - jnp.mod(hi - j[None, :], m))[:, None, None, None, :]
        at = q_positions[:, None, None, :, None]
        mask = (held <= at) & (held > at - window) & (held >= 0)     # [B,1,1,S,M]
    else:
        mask = j[None, None, None, None, :] <= q_positions[:, None, None, :, None]  # [B,1,1,S,M]
        if window is not None:
            mask = mask & (
                j[None, None, None, None, :] > q_positions[:, None, None, :, None] - window
            )
    logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhrqk,bhdk->bqhrd", probs, v)
    return out.reshape(b, s, n_q, d)


def alibi_slopes(n_heads: int) -> jax.Array:
    """Per-head alibi slopes — the Press et al. geometric sequence with the
    HF non-power-of-2 correction (``build_alibi_tensor``): the closest power
    of 2 gets the standard sequence, extra heads interleave from the
    double-resolution sequence."""
    import math

    closest = 2 ** math.floor(math.log2(n_heads))
    base = 2.0 ** (-(2.0 ** -(math.log2(closest) - 3)))
    powers = [base ** (i + 1) for i in range(closest)]
    if closest != n_heads:
        extra_base = 2.0 ** (-(2.0 ** -(math.log2(2 * closest) - 3)))
        powers += [extra_base ** (1 + 2 * i) for i in range(n_heads - closest)]
    return jnp.asarray(powers, jnp.float32)


def _alibi_bias(n_heads: int, k_len: int) -> jax.Array:
    """[1, H, 1, K] additive bias ``slope_h * j`` (key position), broadcast
    over queries.  Softmax-equivalent to the relative ``slope_h * (j - i)``
    form (per-query-row shifts cancel) at 1/Q the memory — the bias constant
    would otherwise rival the weights on big-model prefill."""
    j = jnp.arange(k_len, dtype=jnp.float32)
    return (alibi_slopes(n_heads)[:, None, None] * j[None, None, :])[None]


def _cos_sin(positions: jax.Array, d: int, theta: float, yarn=None):
    """``cos, sin [B, S, 1, D/2]`` of the rotary angles at ``positions [B,
    S]``; under YaRN its frequencies, both times its amplitude."""
    freqs = rope_frequencies(d, theta, yarn)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, D/2]
    # plain rope traces the operations it always did, in their order: the
    # programs of every configuration without YaRN lower to the same text
    amp = (lambda a: a) if yarn is None else (lambda a: a * rope_amplitude(yarn))
    return amp(jnp.cos(angles))[:, :, None, :], amp(jnp.sin(angles))[:, :, None, :]


def _rope(x: jax.Array, positions: jax.Array, theta: float, yarn=None) -> jax.Array:
    """Rotary embedding over the last dim of [B, S, H, D] — rotate-half
    convention (Llama/NeoX)."""
    cos, sin = _cos_sin(positions, x.shape[-1], theta, yarn)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _rope_interleaved(x: jax.Array, positions: jax.Array, theta: float, yarn=None) -> jax.Array:
    """GPT-J's rotate-every-two pairing: dims (0,1), (2,3), ... form the
    rotation pairs (vs rotate-half's (i, i+D/2))."""
    cos, sin = _cos_sin(positions, x.shape[-1], theta, yarn)
    xf = x.astype(jnp.float32)
    x_even = xf[..., 0::2]
    x_odd = xf[..., 1::2]
    out_even = x_even * cos - x_odd * sin
    out_odd = x_odd * cos + x_even * sin
    # re-interleave: [e0, o0, e1, o1, ...]
    out = jnp.stack([out_even, out_odd], axis=-1).reshape(xf.shape)
    return out.astype(x.dtype)


def _apply_rope(x: jax.Array, positions: jax.Array, cfg: "TransformerConfig",
                rope: Optional[RopeSpec] = None) -> jax.Array:
    """Config-selected rope: full or partial (first ``rope_dim`` dims),
    rotate-half or interleaved; at ``cfg.rope_theta``, or at the theta and
    YaRN of ``rope`` (a kind of layer's own: ``cfg.full_rope``)."""
    fn = _rope_interleaved if cfg.rope_interleaved else _rope
    theta, yarn = (cfg.rope_theta, None) if rope is None else (rope.theta, rope.yarn)
    rd = cfg.rope_dim
    if rd is None or rd >= x.shape[-1]:
        return fn(x, positions, theta, yarn)
    rotated = fn(x[..., :rd], positions, theta, yarn)
    return jnp.concatenate([rotated, x[..., rd:]], axis=-1)


def scale_embed(cfg: "TransformerConfig", x: jax.Array) -> jax.Array:
    """Gemma-family sqrt(hidden) embedding scale (identity unless
    ``cfg.embed_scale``) — single source for the monolithic forward, the
    streaming embed stage, and both pipeline embed sites."""
    if getattr(cfg, "embed_scale", False):
        return x * jnp.asarray(cfg.hidden_size ** 0.5, x.dtype)
    return x


class RMSNorm(nn.Module):
    eps: float = 1e-5
    param_dtype: Any = jnp.float32
    # Gemma convention: the stored parameter is an offset from 1 (zeros-init),
    # output = normed * (1 + scale) — matches HF's GemmaRMSNorm weights as-is.
    unit_offset: bool = False

    @nn.compact
    def __call__(self, x):
        init = nn.initializers.zeros if self.unit_offset else nn.initializers.ones
        scale = self.param("scale", init, (x.shape[-1],), self.param_dtype)
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
        normed = x.astype(jnp.float32) * jax.lax.rsqrt(var + self.eps)
        if self.unit_offset:
            scale = 1.0 + scale
        return (normed * scale).astype(x.dtype)


class LayerNorm(nn.Module):
    """Centered layernorm (GPT-2 family): fp32 statistics regardless of
    activation dtype, matching torch ``nn.LayerNorm`` numerics.
    ``use_bias=False`` is MPT's no_bias variant (centered, scale-only)."""

    eps: float = 1e-5
    param_dtype: Any = jnp.float32
    use_bias: bool = True

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), self.param_dtype)
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
        normed = (xf - mean) * jax.lax.rsqrt(var + self.eps) * scale
        if self.use_bias:
            normed = normed + self.param(
                "bias", nn.initializers.zeros, (x.shape[-1],), self.param_dtype
            )
        return normed.astype(x.dtype)


def make_norm(cfg: "TransformerConfig", name: Optional[str] = None):
    """The config-selected norm module — single source for DecoderLayer, the
    final norm, big_modeling's streaming head stage, and the pipeline head
    (``name=None`` for root-level ``.apply``, where flax forbids names)."""
    if cfg.norm_type == "layernorm":
        return LayerNorm(cfg.rms_norm_eps, cfg.param_dtype, cfg.norm_bias, name=name)
    return RMSNorm(cfg.rms_norm_eps, cfg.param_dtype, cfg.norm_unit_offset, name=name)


def _write_rows(buf, new, index, layer=None):
    """Write ``new [B, S, H, D]`` into a position-major KV buffer (the latent
    rows of :mod:`~accelerate_tpu.models.latent_attention`) at rows ``index ..
    index + S - 1`` of every lane, in place.

    ``buf`` is one layer's slab ``[B, M, H, D]`` (``layer=None``) or the
    stacked cache ``[L, B, M, H, D]`` addressed at the static ``layer``.
    Scalar ``index`` (generate, a prefill chunk) is one
    ``dynamic_update_slice``; a per-lane ``index [B]`` (the decode windows of
    the serving pool) is one scatter of the ``B * S`` new
    rows.  The start is clamped so the span fits, as ``dynamic_update_slice``
    clamps it: every write the engine admits is in range already (its
    admission check), and a frozen lane's stale index can only land on that
    lane's own dead rows — so the scatter may be told its indices are in
    bounds and unique."""
    new = new.astype(buf.dtype)
    lead = () if layer is None else (layer,)
    if jnp.ndim(index) == 0:
        return jax.lax.dynamic_update_slice(
            buf, new[(None,) * len(lead)], (*lead, 0, index, 0, 0)
        )
    b, s = new.shape[:2]
    start = jnp.clip(index, 0, buf.shape[-3] - s)
    rows = start[:, None] + jnp.arange(s)[None, :]                  # [B, S]
    return buf.at[(*lead, jnp.arange(b)[:, None], rows)].set(
        new, unique_indices=True, mode="promise_in_bounds"
    )


def _write_columns(buf, new, index, layer=None):
    """Write ``new [B, S, H, D]`` into a per-head KV buffer (positions minor:
    :class:`KVCache`) as columns ``index .. index + S - 1`` of every lane, in
    place.

    ``buf`` is one layer's slab ``[B, H*D, M]`` (``layer=None``: the callers
    that hold per-layer arrays, ``ScanBody`` and ``big_modeling``'s streamed
    decode) or the stacked cache ``[L, B, H*D, M]`` addressed at the static
    ``layer``.  Scalar ``index`` (generate, a prefill chunk) is one
    ``dynamic_update_slice`` of ``[B, H*D, S]``; a per-lane ``index [B]``
    (decode, verify and tree windows of the serving pool) is one
    ``dynamic_update_slice`` of ``[H*D, S]`` a lane, each at its lane's own
    index.  Not a scatter: for a scatter of columns the TPU compiler carries
    the buffer position-major and passes the gathered view into that layout
    first; a ``dynamic_update_slice`` leaves the layout to the buffer.  The
    start is clamped so the span fits (``dynamic_update_slice`` does):
    every write the engine admits is in range already (its admission check),
    and a frozen lane's stale index can only land on that lane's own dead
    columns."""
    b, s = new.shape[:2]
    lead = () if layer is None else (layer,)
    cols = new.astype(buf.dtype).reshape(b, s, -1).swapaxes(1, 2)   # [B, H*D, S]
    cols = cols[(None,) * len(lead)]
    if jnp.ndim(index) == 0:
        return jax.lax.dynamic_update_slice(buf, cols, (*lead, 0, 0, index))
    for lane in range(b):
        buf = jax.lax.dynamic_update_slice(
            buf, cols[..., lane:lane + 1, :, :], (*lead, lane, 0, index[lane])
        )
    return buf


def _write_ring(buf, new, index, layer, page: int):
    """:func:`_write_columns` into a ring ``[L, B, H*D, W]``: position ``p``
    goes to column ``p % W``.  A decode step's one column a lane cannot
    straddle the ring's end; a chunk (scalar ``index``, a page boundary, whole
    pages long, ``W`` whole pages too) is written page by page."""
    width, s = buf.shape[-1], new.shape[1]
    if s == 1:
        return _write_columns(buf, new, index % width, layer)
    if jnp.ndim(index) or s % page or width % page:
        raise ValueError(
            f"a ring of {width} columns takes one column a lane or a chunk of whole "
            f"pages of {page} at a scalar index, got {s} rows at index of rank {jnp.ndim(index)}"
        )
    for i in range(s // page):
        column = ((index // page + i) % (width // page)) * page
        buf = _write_columns(buf, new[:, i * page:(i + 1) * page], column, layer)
    return buf


def _write_kind(buf, new, index, at: int, page: Optional[int]):
    """``new`` written into layer ``at`` of one kind's arrays of a
    :class:`MixedKVCache`: the stacked array ``[L, B, H*D, M]``, or the tuple
    of one array a layer, where only that layer's array is replaced.  ``page``:
    a ring's (:func:`_write_ring`), ``None`` for a full layer's columns."""
    write = (lambda b, layer: _write_columns(b, new, index, layer)) if page is None else (
        lambda b, layer: _write_ring(b, new, index, layer, page))
    if isinstance(buf, tuple):
        return buf[:at] + (write(buf[at], None),) + buf[at + 1:]
    return write(buf, at)


class Attention(nn.Module):
    config: TransformerConfig
    # "window" / "full" in a stack of two kinds (``config.layer_types``; set
    # by :class:`DecoderLayer`), None where every layer is one kind
    kind: Optional[str] = None

    @nn.compact
    def __call__(self, x, positions, segment_ids=None, cache=None,
                 tree_mask=None, layer=None):
        """With a ``cache`` the new k/v are written at its ``index`` (post-rope,
        so cached keys never need re-rotation) and attention runs over the
        cache.  The cache's type says who holds the arrays:

        * a :class:`KVCache` / :class:`PagedKVCache` — the STACKED arrays of
          every layer, ``layer`` this layer's static index.  The new rows go
          into ``[layer, ...]`` in place and attention reads the static slice
          ``[layer]``; returns ``(out, cache)`` with the written arrays (and a
          paged cache's ``quant_err``) replaced, ``index`` as it was — the
          unrolled :class:`Transformer` loop advances it once.
        * a tuple — this layer's own arrays, for the callers that hold them
          per layer: ``(k_cache [B,Hkv*D,M], v_cache, index)`` returns
          ``(out, (k_cache, v_cache))``; the paged ``(pages_k, pages_v,
          k_scales, v_scales, tables, index, active)`` returns ``(out,
          (pages_k, pages_v, k_scales, v_scales, quant_err))``.

        Both forms share everything but the address of the write.
        ``tree_mask`` (an ``[S, S]`` ancestor-or-self numpy constant, ``S ==
        x.shape[1]``) switches the cache-read mask to token-tree visibility
        for speculative tree verification — cache required."""
        cfg = self.config
        hd = cfg.resolved_head_dim
        dense = functools_partial_dense(cfg, use_bias=cfg.attn_bias)
        # Qwen2: q/k/v biased, o_proj not — qkv_bias overrides for the three
        # input projections only
        dense_qkv = dense if cfg.qkv_bias is None else functools_partial_dense(
            cfg, use_bias=cfg.qkv_bias
        )
        q = _tag_proj(dense_qkv("q_proj", cfg.num_heads * hd)(x))
        k = _tag_proj(dense_qkv("k_proj", cfg.num_kv_heads * hd)(x))
        v = _tag_proj(dense_qkv("v_proj", cfg.num_kv_heads * hd)(x))
        b, s = x.shape[:2]
        q = q.reshape(b, s, cfg.num_heads, hd)
        k = k.reshape(b, s, cfg.num_kv_heads, hd)
        v = v.reshape(b, s, cfg.num_kv_heads, hd)
        if cfg.qk_norm:
            q = RMSNorm(cfg.rms_norm_eps, cfg.param_dtype, name="q_norm")(q)
            k = RMSNorm(cfg.rms_norm_eps, cfg.param_dtype, name="k_norm")(k)
        # the device scope of a layer of two kinds (``attn/window``, ``attn/full``)
        kind_scope = lambda: (jax.named_scope(f"attn/{self.kind}") if self.kind
                              else contextlib.nullcontext())

        # a "full" layer of two kinds sees every position, and carries no
        # positional encoding where the configuration says so, or a rope of
        # its own (``full_rope``)
        window = None if self.kind == "full" else cfg.sliding_window
        if cfg.positional == "rope" and (self.kind != "full" or cfg.rope_full_layers):
            rope = cfg.full_rope if self.kind == "full" else None
            with kind_scope():
                q = _apply_rope(q, positions, cfg, rope)
                k = _apply_rope(k, positions, cfg, rope)

        def project_out(out):
            """``out [B, S, Hq, D]`` through the output gate, where there is
            one, and ``o_proj``."""
            out = out.reshape(b, s, cfg.num_heads * hd)
            if cfg.attention_gate:
                with jax.named_scope("attn/gate"):
                    gate = dense("gate_proj", cfg.num_heads * hd)(x)
                    out = out * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(out.dtype)
            return dense("o_proj", cfg.hidden_size)(out)

        if isinstance(cache, MixedKVCache):
            # this kind's arrays, the layer's place among its kind
            at_kind = cfg.layer_types[:layer].count(self.kind)
            ring = self.kind == "window"
            names = ("k_ring", "v_ring") if ring else ("k", "v")
            with kind_scope():
                k_all, v_all = (_write_kind(getattr(cache, name), new, cache.index, at_kind,
                                            cache.page if ring else None)
                                for name, new in zip(names, (k, v)))
                cache = cache.replace(**dict(zip(names, (k_all, v_all))))
                out = cached_attention(q, k_all[at_kind], v_all[at_kind], positions,
                                       window=window, ring=ring)
            return project_out(out), cache
        # the stacked cache of every layer, addressed at ``layer``; or this
        # layer's own arrays in a tuple, addressed whole
        stacked = isinstance(cache, (KVCache, PagedKVCache))
        if stacked:
            at = lambda a: a[layer]      # static slice of the leading axis
            if isinstance(cache, PagedKVCache):
                cache_arrays = (cache.pages_k, cache.pages_v, cache.k_scales,
                                cache.v_scales, cache.tables, cache.index,
                                cache.active)
            else:
                cache_arrays = (cache.k, cache.v, cache.index)
        else:
            at, layer, cache_arrays = (lambda a: a), None, cache
        if cache is not None and len(cache_arrays) == 7:
            # paged cache: scatter the new KV through the block tables, then
            # attend over pages in place.  ``index`` doubles as each lane's
            # pre-write length (= first new position).
            pages_k, pages_v, k_scales, v_scales, tables, index, active = cache_arrays
            from ..ops.paged_attention import (
                kv_qmax,
                paged_attention,
                paged_attention_reference,
                paged_flash_prefill,
                paged_insert,
                paged_quantized_insert,
            )

            if kv_qmax(pages_k.dtype) is not None:
                pages_k, k_scales, err_k = paged_quantized_insert(
                    pages_k, k_scales, k, tables, index, active, layer=layer
                )
                pages_v, v_scales, err_v = paged_quantized_insert(
                    pages_v, v_scales, v, tables, index, active, layer=layer
                )
                err = jnp.maximum(err_k, err_v)
                sk, sv = at(k_scales), at(v_scales)
            else:
                pages_k = paged_insert(pages_k, k, tables, index, active, layer=layer)
                pages_v = paged_insert(pages_v, v, tables, index, active, layer=layer)
                err = jnp.float32(0.0)
                sk = sv = None
            if cfg.paged_kernel == "pallas":
                out = paged_attention(
                    q, at(pages_k), at(pages_v), tables, index,
                    k_scales=sk, v_scales=sv, tree_mask=tree_mask,
                )
            elif cfg.paged_kernel == "flash_prefill":
                if tree_mask is not None:
                    raise ValueError(
                        "tree verification is a decode-side program; "
                        "paged_kernel='flash_prefill' cannot carry a tree_mask"
                    )
                out = paged_flash_prefill(
                    q, at(pages_k), at(pages_v), tables, index,
                    k_scales=sk, v_scales=sv,
                )
            else:
                out = paged_attention_reference(
                    q, at(pages_k), at(pages_v), tables, index,
                    k_scales=sk, v_scales=sv, window=window,
                    alibi=cfg.positional == "alibi", tree_mask=tree_mask,
                )
            out = project_out(out)
            if stacked:
                return out, cache.replace(
                    pages_k=pages_k, pages_v=pages_v,
                    k_scales=k_scales, v_scales=v_scales,
                    quant_err=jnp.maximum(cache.quant_err, err),
                )
            return out, (pages_k, pages_v, k_scales, v_scales, err)
        if cache is not None:
            k_cache, v_cache, index = cache_arrays
            k_cache = _write_columns(k_cache, k, index, layer)
            v_cache = _write_columns(v_cache, v, index, layer)
            with kind_scope():
                out = cached_attention(q, at(k_cache), at(v_cache), positions,
                                       window=window,
                                       alibi=cfg.positional == "alibi",
                                       tree_mask=tree_mask)
            out = project_out(out)
            if stacked:
                return out, cache.replace(k=k_cache, v=v_cache)
            return out, (k_cache, v_cache)
        if tree_mask is not None:
            raise ValueError("tree_mask requires a KV cache (verify window)")
        bias = None
        if cfg.positional == "alibi":
            bias = _alibi_bias(cfg.num_heads, s)
        with kind_scope():
            out = dot_product_attention(
                q, k, v, causal=True, implementation=cfg.attention_impl,
                segment_ids=segment_ids, ring_layout=cfg.ring_attention_layout,
                window=window, bias=bias,
            )
        return _tag_proj(project_out(out))


def functools_partial_dense(cfg: TransformerConfig, use_bias: Optional[bool] = None):
    use_bias = cfg.use_bias if use_bias is None else use_bias
    if cfg.quantization is not None:
        if cfg.use_fp8:
            raise ValueError(
                "quantization and use_fp8 are mutually exclusive: int8/int4 weights "
                "already dequantize straight into the matmul. Drop mixed_precision='fp8' "
                "for quantized-inference models."
            )
        from ..ops.quantization import QuantizedDense

        def make_q(name: str, features: int):
            return QuantizedDense(
                features,
                bits=cfg.quantization,
                block_size=cfg.quantization_block_size,
                dtype=cfg.dtype,
                use_bias=use_bias,
                name=name,
            )

        return make_q

    extra = {}
    if cfg.use_fp8:
        from ..ops.fp8 import make_fp8_dot_general
        from ..utils.dataclasses import FP8RecipeKwargs

        extra["dot_general"] = make_fp8_dot_general(
            FP8RecipeKwargs(margin=cfg.fp8_margin, fp8_format=cfg.fp8_format)
        )

    def make(name: str, features: int):
        return nn.Dense(
            features,
            use_bias=use_bias,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            kernel_init=nn.initializers.normal(0.02),
            name=name,
            **extra,
        )

    return make


class MLP(nn.Module):
    config: TransformerConfig
    width: Optional[int] = None        # None: ``config.intermediate_size``

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        width = self.width or cfg.intermediate_size
        dense = functools_partial_dense(cfg, use_bias=cfg.mlp_bias)
        if cfg.mlp_variant in ("gelu", "gelu_exact", "relu"):
            # GPT-2/GPT-J: gelu_new (tanh approximation, = flax approximate
            # gelu); NeoX: exact erf gelu; OPT: relu
            act = {
                "relu": nn.relu,
                "gelu": lambda z: nn.gelu(z, approximate=True),
                "gelu_exact": lambda z: nn.gelu(z, approximate=False),
            }[cfg.mlp_variant]
            up = _tag_proj(dense("up_proj", width)(x), "proj_wide")
            return _tag_proj(dense("down_proj", cfg.hidden_size)(act(up)))
        gate = _tag_proj(dense("gate_proj", width)(x))
        up = _tag_proj(dense("up_proj", width)(x), "proj_wide")
        # swiglu: silu gate (Llama); geglu: tanh-gelu gate (Gemma)
        gated = nn.gelu(gate, approximate=True) if cfg.mlp_variant == "geglu" else nn.silu(gate)
        return _tag_proj(dense("down_proj", cfg.hidden_size)(gated * up))


class DecoderLayer(nn.Module):
    config: TransformerConfig
    # one of ``config.experts.dense_layers`` leading layers: a dense MLP where
    # the layers after it route (set by :class:`Transformer`'s loop)
    leading_dense: bool = False
    # this layer's entry of ``config.layer_types`` (set by the same loop)
    kind: Optional[str] = None

    @nn.compact
    def __call__(self, x, positions, cache=None, tree_mask=None, layer=None):
        cfg = self.config
        normed = make_norm(cfg, "input_norm")(x)
        if cfg.latent_attention is not None:
            from .latent_attention import LatentAttention

            attn = LatentAttention(cfg, name="attn")
        elif cfg.retention is not None:
            from .retention import PowerRetention

            attn = PowerRetention(cfg, name="attn")
        else:
            attn = Attention(cfg, self.kind, name="attn")
        attn_out = attn(normed, positions, cache=cache, tree_mask=tree_mask, layer=layer)
        new_kv = None
        if cache is not None:
            attn_out, new_kv = attn_out
        spec = cfg.experts
        if spec is not None and self.leading_dense:
            mlp = MLP(cfg, spec.dense_width, name="mlp")
        elif spec is not None:
            from ..parallel.moe import RoutedExperts

            mlp = RoutedExperts(cfg, name="moe_mlp")
        elif cfg.num_experts > 0:
            from ..parallel.moe import MoEMLP

            mlp = MoEMLP(cfg, name="moe_mlp")
        else:
            mlp = MLP(cfg, name="mlp")
        if cfg.parallel_residual:
            # GPT-J / GPT-NeoX block: both branches read the SAME input;
            # GPT-J (shared_norm) reuses the attention branch's norm
            mlp_in = normed if cfg.shared_norm else make_norm(cfg, "post_attn_norm")(x)
            x = x + attn_out + mlp(mlp_in)
        elif cfg.sandwich_norm:
            x = x + make_norm(cfg, "attn_out_norm")(attn_out)
            x = x + make_norm(cfg, "mlp_out_norm")(mlp(make_norm(cfg, "post_attn_norm")(x)))
        else:
            x = x + attn_out
            x = x + mlp(make_norm(cfg, "post_attn_norm")(x))
        return x if cache is None else (x, new_kv)


class Transformer(nn.Module):
    """Decoder-only LM.  ``__call__(input_ids [B,S]) -> logits [B,S,V]``.

    With ``cache=``\\ :class:`KVCache` the call is an incremental forward:
    positions default to ``cache.index + arange(S)``, each layer writes its
    new rows into the stacked cache in place and reads its slice of it, and
    the result is ``(logits, new_cache)`` — the substrate for
    :mod:`accelerate_tpu.models.generation`.
    """

    config: TransformerConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, cache: Optional[KVCache] = None,
                 tree_mask=None):
        cfg = self.config
        # Token-tree verification (serving/spec_exec.py): ``tree_mask`` is the
        # [S, S] ancestor-or-self constant; each layer's attention swaps the
        # causal row mask for tree visibility over the S-node span written at
        # the lane frontier.  Positions must then be passed explicitly
        # (frontier + node depth) — the arange default below would assign
        # sibling branches consecutive positions.
        if tree_mask is not None and positions is None:
            raise ValueError("tree_mask requires explicit positions "
                             "(lane frontier + per-node tree depth)")
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(input_ids.shape[1])[None, :], input_ids.shape
            )
            if cache is not None:
                idx = cache.index
                # scalar index: whole batch at one position; [B] per-lane index
                # (serving slot pool): each lane offset by its own length
                positions = positions + (idx[:, None] if jnp.ndim(idx) else idx)
        embed = nn.Embed(
            cfg.vocab_size,
            cfg.hidden_size,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            embedding_init=nn.initializers.normal(0.02),
            name="embed_tokens",
        )
        x = scale_embed(cfg, embed(input_ids))
        if cfg.embed_norm:
            x = make_norm(cfg, "embed_norm")(x)
        if cfg.positional == "learned":
            pos_embed = nn.Embed(
                cfg.max_seq_len + cfg.pos_offset,
                cfg.hidden_size,
                dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                embedding_init=nn.initializers.normal(0.02),
                name="pos_embed",
            )
            x = x + pos_embed(positions + cfg.pos_offset)
        if cfg.attention_impl == "ring":
            x = _constrain_sequence_parallel(x)

        new_cache = None
        if cfg.scan_layers:
            # Roll layers into one scanned module: params stack on axis 0,
            # compile time is O(1) in depth, and stages slice cleanly for PP.
            # The KV cache scans right along (in/out axis 0 = depth).
            body = ScanBody
            if cfg.remat and cache is None:
                body = nn.remat(ScanBody, prevent_cse=False, policy=_remat_policy(cfg))
            ScanLayers = nn.scan(
                body,
                # intermediates must be scanned too, or sown values (MoE router
                # aux loss) are silently dropped inside the scan body
                variable_axes={"params": 0, "intermediates": 0},
                split_rngs={"params": True},
                length=cfg.num_layers,
                in_axes=(nn.broadcast, nn.broadcast, 0, nn.broadcast),
            )
            if cache is None:
                kv_in, bcast = (None, None), None
            elif isinstance(cache, PagedKVCache):
                # pool/scale arrays scan over depth; tables/index/active (and
                # the lane write gate) broadcast to every layer
                kv_in = (cache.pages_k, cache.pages_v, cache.k_scales, cache.v_scales)
                bcast = (cache.tables, cache.index, cache.active)
            else:
                kv_in, bcast = (cache.k, cache.v), cache.index
            x, kv_out = ScanLayers(cfg, name="layers")(
                x, positions, bcast, kv_in, tree_mask
            )
            if isinstance(cache, PagedKVCache):
                new_cache = cache.replace(
                    pages_k=kv_out[0], pages_v=kv_out[1],
                    k_scales=kv_out[2], v_scales=kv_out[3],
                    index=cache.index + input_ids.shape[1],
                    quant_err=jnp.maximum(cache.quant_err, jnp.max(kv_out[4])),
                )
            elif cache is not None:
                new_cache = cache.replace(
                    k=kv_out[0], v=kv_out[1], index=cache.index + input_ids.shape[1]
                )
        else:
            layer_cls = DecoderLayer
            if cfg.remat and cache is None:
                layer_cls = nn.remat(DecoderLayer, prevent_cse=False, policy=_remat_policy(cfg))
            # the cache goes through the layers whole: layer i writes its new
            # rows into the stacked arrays at [i] and reads the slice [i]
            new_cache = cache
            for i in range(cfg.num_layers):
                block = layer_cls(
                    cfg, cfg.experts is not None and i < cfg.experts.dense_layers,
                    cfg.layer_kind(i), name=f"layers_{i}",
                )
                if cache is None:
                    x = block(x, positions)
                else:
                    x, new_cache = block(
                        x, positions, cache=new_cache, tree_mask=tree_mask,
                        layer=i,
                    )
            if cache is not None:
                new_cache = new_cache.replace(
                    index=cache.index + input_ids.shape[1]
                )

        x = make_norm(cfg, "final_norm")(x)
        if cfg.tie_word_embeddings:
            logits = embed.attend(x.astype(cfg.param_dtype))
        else:
            logits = nn.Dense(
                cfg.vocab_size,
                use_bias=cfg.lm_head_bias,
                dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                kernel_init=nn.initializers.normal(0.02),
                name="lm_head",
            )(x)
        logits = logits.astype(jnp.float32)
        return logits if cache is None else (logits, new_cache)


class ScanBody(nn.Module):
    """Scan-compatible layer body: carry = hidden states; positions/cache index
    broadcast; per-layer KV cache slices scanned on axis 0 (depth)."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, x, positions, cache_index=None, kv=(None, None),
                 tree_mask=None):
        layer = DecoderLayer(self.config, name="layer")
        if kv[0] is None:
            return layer(x, positions), None
        if len(kv) == 4:
            # paged: kv = per-layer (pages_k, pages_v, k_scales, v_scales),
            # cache_index = broadcast (tables, index, active)
            x, new_kv = layer(x, positions, cache=tuple(kv) + tuple(cache_index),
                              tree_mask=tree_mask)
            return x, new_kv
        x, new_kv = layer(x, positions, cache=(kv[0], kv[1], cache_index),
                          tree_mask=tree_mask)
        return x, new_kv


def cross_entropy_loss(logits, labels, ignore_index: int = -100, z_loss: float = 0.0):
    """Token-level CE with optional z-loss (stabilizes large-vocab training)."""
    mask = labels != ignore_index
    safe_labels = jnp.where(mask, labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    label_logits = jnp.take_along_axis(logits, safe_labels[..., None], axis=-1)[..., 0]
    nll = logz - label_logits
    if z_loss > 0.0:
        nll = nll + z_loss * jnp.square(logz)
    nll = jnp.where(mask, nll, 0.0)
    return nll.sum() / jnp.maximum(mask.sum(), 1)


def shift_labels(batch) -> jax.Array:
    """Next-token labels for a causal LM batch: ``batch["labels"]`` if given,
    else ``input_ids`` shifted left with ``-100`` (ignore) at the final
    position.  Single source of the shift/ignore convention for both the
    monolithic (``lm_loss_fn``) and pipeline (``pipeline_lm_loss_fn``) paths —
    their parity checks rely on it being identical."""
    labels = batch.get("labels")
    if labels is None:
        labels = jnp.pad(batch["input_ids"][:, 1:], ((0, 0), (0, 1)), constant_values=-100)
    return labels


def lm_loss_fn(model: Transformer):
    """Standard next-token loss for ``Accelerator.compile_train_step``.

    For MoE configs the Switch router aux loss (sown as an intermediate) is
    added with ``router_aux_loss_coef`` — the load-balancing term the reference
    leaves to DeepSpeed's engine.
    """
    cfg = model.config
    is_moe = cfg.num_experts > 0 and cfg.router_aux_loss_coef > 0.0

    def loss_fn(params, batch, rng=None):
        if is_moe:
            logits, mutables = model.apply(
                {"params": params}, batch["input_ids"], mutable=["intermediates"]
            )
        else:
            logits = model.apply({"params": params}, batch["input_ids"])
        labels = shift_labels(batch)
        loss = cross_entropy_loss(logits, labels)
        if is_moe:
            from ..parallel.moe import router_aux_loss

            loss = loss + router_aux_loss(
                mutables["intermediates"], cfg.router_aux_loss_coef
            )
        return loss

    # ring attention shards the sequence over sp inside the forward; the
    # trainer's sp>1 guard (compile_train_step) accepts sp-aware losses only
    loss_fn._sp_aware = cfg.attention_impl == "ring"
    return loss_fn
