"""Model configurations and named presets."""

from __future__ import annotations

import dataclasses
from typing import ClassVar, Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    top_k: int = 2
    expert_mlp_dim: int = 2048
    # "dense": evaluate every expert on every token (exact, full FLOPs —
    #   fine for few experts / small models).
    # "capacity": GShard-style fixed-capacity dispatch — each expert
    #   processes at most ceil(tokens * top_k / num_experts) *
    #   capacity_factor tokens, cutting expert FLOPs by num_experts/top_k
    #   at static shapes XLA can tile. It DROPS the overflow: a token past
    #   an expert's capacity loses that expert's share of its output, so
    #   this dispatch cannot match a reference's logits. The dropless
    #   many-expert layer is ``models/latent_moe.py``'s.
    dispatch: str = "dense"
    capacity_factor: float = 1.25


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    """Llama-3-style decoder-only transformer (GQA + RoPE + SwiGLU)."""

    vocab_size: int = 128256
    embed_dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 128
    mlp_dim: int = 14336
    rope_theta: float = 500000.0
    rms_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: str = "bfloat16"        # compute dtype
    param_dtype: str = "bfloat16"  # storage dtype
    remat: bool = True             # rematerialize each block under scan
    # Which intermediates survive remat: "nothing" recomputes the whole block
    # in backward (min memory); "dots" saves matmul outputs (no-batch-dim
    # contractions), skipping the recompute FLOPs at ~2x activation memory.
    remat_policy: str = "nothing"  # nothing | dots | dots_and_attn | dots_no_mlp
    moe: Optional[MoEConfig] = None
    max_seq_len: int = 8192
    # "auto" → pallas flash for long tileable sequences, XLA otherwise;
    # "ring" is engaged by passing a mesh with sp>1 to forward().
    attn_impl: str = "auto"        # auto | xla | flash
    # fused-xent token chunk (ops/xent.py): live logits are [chunk, V] f32.
    # 512 is optimal at 32k vocab; 128k-vocab configs measure faster at
    # 2048-4096 (fewer scan steps, fatter unembed matmul).
    xent_chunk: int = 512

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def storage_dtype(self):
        return jnp.dtype(self.param_dtype)

    # ---- presets -------------------------------------------------------
    @classmethod
    def llama3_8b(cls, **kw) -> "LlamaConfig":
        return cls(**kw)

    @classmethod
    def llama3_1b(cls, **kw) -> "LlamaConfig":
        """~1.2B params: fits a single v5e chip in bf16 with Adam for bench."""
        base = dict(vocab_size=128256, embed_dim=2048, n_layers=16, n_heads=16,
                    n_kv_heads=8, head_dim=128, mlp_dim=8192)
        base.update(kw)
        return cls(**base)

    @classmethod
    def tiny(cls, **kw) -> "LlamaConfig":
        """CI config: runs on the 8-device virtual CPU mesh in seconds."""
        base = dict(vocab_size=512, embed_dim=64, n_layers=2, n_heads=4,
                    n_kv_heads=2, head_dim=16, mlp_dim=128, remat=False,
                    dtype="float32", param_dtype="float32", max_seq_len=128)
        base.update(kw)
        return cls(**base)

    @classmethod
    def tiny_moe(cls, **kw) -> "LlamaConfig":
        base = dict(moe=MoEConfig(num_experts=4, top_k=2, expert_mlp_dim=128))
        base.update(kw)
        return cls.tiny(**base)


@dataclasses.dataclass(frozen=True)
class LatentMoEConfig:
    """Decoder with latent (compressed) attention and sigmoid-routed
    experts beside shared ones (``models/latent_moe.py``; the layer as the
    DeepSeek-V3 family of public configs writes it).

    Attention, every layer: queries of ``n_heads x (qk_nope_dim +
    qk_rope_dim)`` straight from the hidden state (no query compression);
    keys and values through one ``kv_latent_dim`` vector a position plus one
    ``qk_rope_dim`` rope key shared by all heads: the cache holds those
    two, nothing a head. The first ``n_dense_layers`` layers have a SwiGLU of
    ``dense_mlp_dim``; the rest route each token to ``top_k`` of
    ``n_experts`` SwiGLUs of ``expert_mlp_dim`` (sigmoid scores, a bias that
    only selects, weights renormalised over the chosen and scaled by
    ``routed_scale``) and add ``n_shared_experts`` shared ones computed as
    one SwiGLU of ``n_shared_experts * expert_mlp_dim``."""

    vocab_size: int = 128256
    embed_dim: int = 2048
    n_layers: int = 8
    n_heads: int = 32
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    kv_latent_dim: int = 512
    dense_mlp_dim: int = 6144
    n_dense_layers: int = 1
    n_experts: int = 128
    top_k: int = 6
    expert_mlp_dim: int = 768
    n_shared_experts: int = 2
    routed_scale: float = 2.448
    norm_topk: bool = True
    rope_theta: float = 1e6
    rms_eps: float = 1e-6
    max_seq_len: int = 8192
    dtype: str = "bfloat16"        # compute dtype
    param_dtype: str = "bfloat16"  # storage dtype

    def __post_init__(self):
        if not 0 <= self.n_dense_layers <= self.n_layers:
            raise ValueError(
                f"n_dense_layers {self.n_dense_layers} outside "
                f"0..n_layers {self.n_layers}")
        if self.top_k > self.n_experts:
            raise ValueError(f"top_k {self.top_k} > n_experts "
                             f"{self.n_experts}")
        if self.qk_rope_dim % 2:
            raise ValueError("qk_rope_dim must be even (rope pairs)")

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def storage_dtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    @classmethod
    def tiny(cls, **kw) -> "LatentMoEConfig":
        """CI config: 1 dense + 2 expert layers, float32."""
        base = dict(vocab_size=512, embed_dim=64, n_layers=3, n_heads=4,
                    qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
                    kv_latent_dim=32, dense_mlp_dim=128, n_dense_layers=1,
                    n_experts=8, top_k=2, expert_mlp_dim=32,
                    n_shared_experts=1, max_seq_len=128,
                    dtype="float32", param_dtype="float32")
        base.update(kw)
        return cls(**base)


@dataclasses.dataclass(frozen=True)
class HybridLinearConfig:
    """Decoder whose layers are of two kinds (``models/hybrid_linear.py``;
    the layer as the ``olmo_hybrid`` public config writes it):
    ``linear_attention`` layers keep a recurrent state of ``linear_key_dim x
    linear_value_dim`` a head a ROW (gated delta rule behind a short causal
    convolution) and no position, ``full_attention`` layers keep keys and
    values of ``n_kv_heads x head_dim`` a position (causal softmax, no
    rotary embedding). ``layer_types`` names each layer's kind. Both kinds
    end in a SwiGLU of ``mlp_dim``; every block's output is normed before
    the residual add (``x + norm(f(x))``)."""

    vocab_size: int = 100352
    embed_dim: int = 3840
    layer_types: Tuple[str, ...] = (("linear_attention",) * 3
                                    + ("full_attention",)) * 4
    n_heads: int = 30
    n_kv_heads: int = 30
    head_dim: int = 128
    linear_heads: int = 30          # key heads = value heads
    linear_key_dim: int = 96
    linear_value_dim: int = 192
    conv_width: int = 4
    neg_eigval: bool = True         # beta in (0, 2): eigenvalues in (-1, 1)
    mlp_dim: int = 11008
    rms_eps: float = 1e-6
    max_seq_len: int = 4096
    dtype: str = "bfloat16"        # compute dtype
    param_dtype: str = "bfloat16"  # storage dtype

    KINDS: ClassVar[Tuple[str, ...]] = ("linear_attention",
                                        "full_attention")

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        bad = sorted(set(self.layer_types) - set(self.KINDS))
        if bad or not self.layer_types:
            raise ValueError(f"layer_types must name {self.KINDS}, "
                             f"got {bad or 'no layer'}")
        if self.n_heads != self.n_kv_heads:
            raise ValueError("the full-attention layers are multi-head: "
                             "n_kv_heads must equal n_heads")
        if self.conv_width < 2:
            raise ValueError("conv_width must be >= 2")

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def storage_dtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def n_linear_layers(self) -> int:
        return self.layer_types.count("linear_attention")

    @property
    def n_full_layers(self) -> int:
        return self.layer_types.count("full_attention")

    @property
    def conv_channels(self) -> int:
        """Channels the short convolution runs over: ``[q | k | v]``."""
        return self.linear_heads * (2 * self.linear_key_dim
                                    + self.linear_value_dim)

    @classmethod
    def tiny(cls, **kw) -> "HybridLinearConfig":
        """CI config: one period and a half (L L L F L L), float32."""
        base = dict(vocab_size=512, embed_dim=64,
                    layer_types=("linear_attention",) * 3
                    + ("full_attention",) + ("linear_attention",) * 2,
                    n_heads=4, n_kv_heads=4, head_dim=16, linear_heads=4,
                    linear_key_dim=8, linear_value_dim=16, mlp_dim=128,
                    max_seq_len=128, dtype="float32", param_dtype="float32")
        base.update(kw)
        return cls(**base)


@dataclasses.dataclass(frozen=True)
class WindowMoEConfig:
    """Decoder whose attention layers are of two kinds
    (``models/window_moe.py``; the layer as the ``smallthinker`` public
    config writes it): ``full_attention`` layers see every earlier position
    and carry NO position encoding, ``window_attention`` layers rotate
    queries and keys (``rope_theta``, over the whole head, halves layout)
    and see the last ``window`` positions only (key ``j`` by query ``i`` iff
    ``i - window < j <= i``), so what a row keeps of such a layer is a ring
    of ``window`` positions. Both are grouped-query attention (``n_heads``
    over ``n_kv_heads`` of ``head_dim``). Every layer routes each token to
    ``top_k`` of ``n_experts`` ReGLU experts of ``expert_mlp_dim`` (``down
    (relu(gate x) * up x)``) by a float32 router that reads the layer's
    INPUT, ahead of the input norm and of attention; the weights are the
    softmax over the chosen scores. No shared expert, no bias."""

    vocab_size: int = 151936
    embed_dim: int = 2560
    layer_types: Tuple[str, ...] = (("full_attention",)
                                    + ("window_attention",) * 3) * 2
    n_heads: int = 28
    n_kv_heads: int = 4
    head_dim: int = 128
    window: int = 4096
    rope_theta: float = 1.5e6
    n_experts: int = 64
    top_k: int = 6
    expert_mlp_dim: int = 768
    rms_eps: float = 1e-6
    max_seq_len: int = 16384
    dtype: str = "bfloat16"        # compute dtype
    param_dtype: str = "bfloat16"  # storage dtype

    KINDS: ClassVar[Tuple[str, ...]] = ("full_attention",
                                        "window_attention")

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        bad = sorted(set(self.layer_types) - set(self.KINDS))
        if bad or not self.layer_types:
            raise ValueError(f"layer_types must name {self.KINDS}, "
                             f"got {bad or 'no layer'}")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads must be a multiple of n_kv_heads")
        if self.top_k > self.n_experts:
            raise ValueError(f"top_k {self.top_k} > n_experts "
                             f"{self.n_experts}")
        if self.window < 1 or self.head_dim % 2:
            raise ValueError("window must be >= 1 and head_dim even")

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def storage_dtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def n_full_layers(self) -> int:
        return self.layer_types.count("full_attention")

    @property
    def n_window_layers(self) -> int:
        return self.layer_types.count("window_attention")

    @classmethod
    def tiny(cls, **kw) -> "WindowMoEConfig":
        """CI config: one period and one layer (F W W W F), float32."""
        base = dict(vocab_size=512, embed_dim=64,
                    layer_types=("full_attention",)
                    + ("window_attention",) * 3 + ("full_attention",),
                    n_heads=8, n_kv_heads=4, head_dim=16, window=16,
                    rope_theta=10000.0, n_experts=8, top_k=2,
                    expert_mlp_dim=32, max_seq_len=64,
                    dtype="float32", param_dtype="float32")
        base.update(kw)
        return cls(**base)


@dataclasses.dataclass(frozen=True)
class IndexedMoEConfig:
    """Decoder whose attention CHOOSES its keys (``models/indexed_moe.py``;
    the layer as the ``KeyeVL2`` public config's language model writes it,
    ``sa_config``): grouped-query attention (``n_heads`` over ``n_kv_heads``
    of ``head_dim``, an RMSNorm with a learned weight over each query and
    key head, rope over the whole head, halves layout) in front of which a
    learned INDEX scores every earlier position: ``index_heads`` queries of
    ``index_dim`` against ONE key of ``index_dim`` a position (a LayerNorm
    with weight and bias over it, both rotated), ``I[t, s] = sum_j w[t, j]
    relu(qI[t, j] . kI[s])`` in float32 with ``w`` a float32 projection of
    the token; query ``t`` attends exactly the ``index_topk`` positions ``s
    <= t`` with the largest ``I[t, s]`` (all of them while ``t + 1 <=
    index_topk``; equal scores go to the lower position). What a row keeps
    of a layer is therefore three leaves a position: ``k``, ``v`` and the
    index key ``ik``. Every layer ends in ``top_k`` of ``n_experts`` SwiGLU
    experts of ``expert_mlp_dim`` chosen by a float32 softmax router over
    all experts, the chosen weights renormalised to sum 1. No shared
    expert, no dense layer, no bias but the index key's norm."""

    vocab_size: int = 151936
    embed_dim: int = 2048
    n_layers: int = 4
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    index_heads: int = 16
    index_dim: int = 64
    index_topk: int = 2048
    rope_theta: float = 1e7
    n_experts: int = 128
    top_k: int = 8
    expert_mlp_dim: int = 768
    rms_eps: float = 1e-6
    max_seq_len: int = 32768
    dtype: str = "bfloat16"        # compute dtype
    param_dtype: str = "bfloat16"  # storage dtype

    def __post_init__(self):
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads must be a multiple of n_kv_heads")
        if self.top_k > self.n_experts:
            raise ValueError(f"top_k {self.top_k} > n_experts "
                             f"{self.n_experts}")
        if self.index_topk < 1 or self.head_dim % 2 or self.index_dim % 2:
            raise ValueError("index_topk must be >= 1, head_dim and "
                             "index_dim even")

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def storage_dtype(self):
        return jnp.dtype(self.param_dtype)

    @classmethod
    def tiny(cls, **kw) -> "IndexedMoEConfig":
        """CI config: two layers, an index that keeps 16 positions."""
        base = dict(vocab_size=512, embed_dim=64, n_layers=2, n_heads=8,
                    n_kv_heads=4, head_dim=16, index_heads=4, index_dim=8,
                    index_topk=16, rope_theta=10000.0, n_experts=8, top_k=2,
                    expert_mlp_dim=32, max_seq_len=64,
                    dtype="float32", param_dtype="float32")
        base.update(kw)
        return cls(**base)


@dataclasses.dataclass(frozen=True)
class HybridLatentMoEConfig:
    """Decoder whose mixers are of two kinds, five to one
    (``models/hybrid_latent_moe.py``; the layer as the ``bailing_hybrid``
    public config writes it): KDA layers keep a recurrent state of
    ``kda_key_dim x kda_value_dim`` a head a ROW (the delta rule with a
    decay a key CHANNEL, bounded below by ``decay_lower_bound`` a token,
    behind a short causal convolution) and no position; MLA layers keep one
    latent of ``kv_latent_dim`` and one rope key of ``qk_rope_dim`` a
    position, nothing a head, and gate each head's output by one number.
    ``layer_types`` names each layer by mixer and feed-forward: ``kda_dense``
    | ``kda_moe`` | ``mla_moe`` (pre-norm residual blocks; the dense layers
    lead). An expert layer's router scores all ``n_experts_routed`` experts
    (sigmoid, a bias that only selects) in ``n_group`` groups of consecutive
    experts, keeps the ``topk_group`` groups whose two best ``score + bias``
    sum highest and chooses the ``top_k`` best of their experts, weights
    renormalised over the chosen and scaled by ``routed_scale``; beside them
    one shared SwiGLU of ``n_shared_experts * expert_mlp_dim``.
    ``experts_held`` ``(first, count)`` is the LOCAL part of expert
    parallelism: this program holds those consecutive experts' weights and
    adds their terms of the sum alone; what an absent expert would add is
    another holder's to compute. ``swiglu_limits`` (a layer each; the
    published per-layer clamp) must be zero: its formula is not in the
    config, so a non-zero one is refused, not guessed."""

    vocab_size: int = 39296
    embed_dim: int = 2560
    layer_types: Tuple[str, ...] = (("kda_dense",) + ("kda_moe",) * 5
                                    + ("mla_moe",))
    kda_heads: int = 32
    kda_key_dim: int = 128
    kda_value_dim: int = 128
    conv_width: int = 4
    decay_lower_bound: float = -5.0
    n_heads: int = 32
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    kv_latent_dim: int = 512
    rope_theta: float = 6e6
    dense_mlp_dim: int = 6144
    n_experts_routed: int = 512
    experts_held: Tuple[int, int] = (0, 128)
    n_group: int = 8
    topk_group: int = 4
    top_k: int = 8
    expert_mlp_dim: int = 768
    n_shared_experts: int = 1
    routed_scale: float = 2.5
    norm_topk: bool = True
    swiglu_limits: Tuple[float, ...] = ()
    rms_eps: float = 1e-6
    max_seq_len: int = 32768
    dtype: str = "bfloat16"        # compute dtype
    param_dtype: str = "bfloat16"  # storage dtype

    KINDS: ClassVar[Tuple[str, ...]] = ("kda_dense", "kda_moe", "mla_moe")

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        object.__setattr__(self, "experts_held", tuple(self.experts_held))
        object.__setattr__(self, "swiglu_limits", tuple(self.swiglu_limits))
        kinds = self.layer_types
        bad = sorted(set(kinds) - set(self.KINDS))
        if bad or not kinds:
            raise ValueError(f"layer_types must name {self.KINDS}, "
                             f"got {bad or 'no layer'}")
        dense = kinds.count("kda_dense")
        if any(k != "kda_dense" for k in kinds[:dense]):
            raise ValueError("the dense layers must lead the stack")
        if any(self.swiglu_limits):
            raise ValueError(
                "swiglu_limits: a non-zero expert_swiglu_limit / "
                "share_expert_swiglu_limit is not carried (the clamp's "
                "formula is not in the public config)")
        first, count = self.experts_held
        size = self.n_experts_routed // max(1, self.n_group)
        if (self.n_experts_routed % self.n_group
                or not 1 <= self.topk_group <= self.n_group):
            raise ValueError("n_experts_routed must split into n_group "
                             "groups, of which topk_group are kept")
        if (first < 0 or count < 1 or first + count > self.n_experts_routed
                or first % size or count % size):
            raise ValueError(
                f"experts_held {self.experts_held} must be whole groups of "
                f"{size} inside 0..{self.n_experts_routed}")
        if self.top_k > self.topk_group * size or size < 2:
            raise ValueError("top_k exceeds the kept groups' experts")
        if self.decay_lower_bound < -5.0 or self.decay_lower_bound >= 0:
            raise ValueError(
                "decay_lower_bound must lie in [-5, 0): the chunked scan's "
                "sub-blocks are safe to -5 a token (ops/kda.py)")
        if self.qk_rope_dim % 2 or self.conv_width < 2:
            raise ValueError("qk_rope_dim must be even, conv_width >= 2")

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def storage_dtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def n_kda_layers(self) -> int:
        return self.n_layers - self.n_mla_layers

    @property
    def n_mla_layers(self) -> int:
        return self.layer_types.count("mla_moe")

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.layer_types.count("kda_dense")

    @property
    def n_experts(self) -> int:
        """Experts whose weights this program holds: what the expert layer
        (``models/experts.py``) and its kernels see."""
        return self.experts_held[1]

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    @property
    def conv_channels(self) -> int:
        """Channels the short convolution runs over: ``[q | k | v]``."""
        return self.kda_heads * (2 * self.kda_key_dim + self.kda_value_dim)

    @classmethod
    def tiny(cls, **kw) -> "HybridLatentMoEConfig":
        """CI config: a dense layer, a period and one more (D K K M K),
        every expert held, float32."""
        base = dict(vocab_size=512, embed_dim=64,
                    layer_types=("kda_dense", "kda_moe", "kda_moe",
                                 "mla_moe", "kda_moe"),
                    kda_heads=4, kda_key_dim=8, kda_value_dim=16,
                    n_heads=4, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
                    kv_latent_dim=32, rope_theta=10000.0, dense_mlp_dim=128,
                    n_experts_routed=16, experts_held=(0, 16), n_group=4,
                    topk_group=2, top_k=2, expert_mlp_dim=32,
                    max_seq_len=128, dtype="float32", param_dtype="float32")
        base.update(kw)
        return cls(**base)


@dataclasses.dataclass(frozen=True)
class ViTConfig:
    """ViT-L/16-style image classifier (BASELINE config #4)."""

    image_size: int = 224
    patch_size: int = 16
    num_classes: int = 1000
    embed_dim: int = 1024
    n_layers: int = 24
    n_heads: int = 16
    mlp_dim: int = 4096
    dropout: float = 0.0
    dtype: str = "bfloat16"
    param_dtype: str = "bfloat16"
    remat: bool = False

    @property
    def head_dim(self) -> int:
        return self.embed_dim // self.n_heads

    @property
    def num_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def storage_dtype(self):
        return jnp.dtype(self.param_dtype)

    @classmethod
    def vit_l16(cls, **kw) -> "ViTConfig":
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw) -> "ViTConfig":
        base = dict(image_size=32, patch_size=8, num_classes=10, embed_dim=64,
                    n_layers=2, n_heads=4, mlp_dim=128,
                    dtype="float32", param_dtype="float32")
        base.update(kw)
        return cls(**base)
