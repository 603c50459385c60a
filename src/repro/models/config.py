"""ModelConfig — one dataclass covering all 10 assigned architecture families."""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    vocab: int
    # attention
    n_heads: int = 0
    n_kv_heads: int = 0
    d_head: int = 0  # 0 -> d_model // n_heads
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    window: Optional[int] = None  # local attention window (hybrid archs)
    # per-layer attention kinds, as HF's ``layer_types``: "sliding_attention"
    # layers attend to the last ``window`` keys, "full_attention" layers to
    # every key; () = every layer alike. The list must repeat a period.
    layer_types: Tuple[str, ...] = ()
    # YaRN RoPE of the full-attention layers (HF ``rope_type: yarn``, its
    # default beta_fast 32, beta_slow 1 and attention factor 0.1 ln(factor)
    # + 1): ``yarn_factor`` 0 = plain RoPE at ``rope_theta`` on every layer
    yarn_factor: float = 0.0
    yarn_original_max: int = 0
    # mlp
    d_ff: int = 0
    mlp_act: str = "swiglu"  # swiglu | gelu
    norm: str = "rmsnorm"  # rmsnorm | layernorm
    # moe
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    # routed experts held here: experts [expert_rank * experts_held,
    # (expert_rank + 1) * experts_held) of the router's n_experts (the chip's
    # share under expert parallelism); 0 = all of them
    experts_held: int = 0
    expert_rank: int = 0
    # ssm (mamba2 SSD)
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_headdim: int = 64
    ssm_ngroups: int = 1
    ssm_chunk: int = 128
    conv_kernel: int = 4
    # hybrid (recurrentgemma): repeating block pattern, e.g. ("rec","rec","attn")
    pattern: Tuple[str, ...] = ()
    lru_width: int = 0
    # encoder-decoder (whisper): n_layers == decoder layers
    n_enc_layers: int = 0
    enc_seq: int = 0  # precomputed frame embeddings fed by the stub frontend
    # vlm (llama-3.2-vision): every `cross_every`-th layer is cross-attention
    cross_every: int = 0
    n_img_tokens: int = 0
    # numerics / embedding
    dtype: str = "bfloat16"
    vocab_pad_to: int = 256
    tie_embeddings: bool = False

    def __post_init__(self):
        # JSON configurations give lists; keep the config hashable
        for f in ("layer_types", "pattern"):
            object.__setattr__(self, f, tuple(getattr(self, f)))

    # ------------------------------------------------------------------
    @property
    def head_dim(self) -> int:
        return self.d_head if self.d_head else self.d_model // max(self.n_heads, 1)

    @property
    def vocab_padded(self) -> int:
        return round_up(self.vocab, self.vocab_pad_to)

    @property
    def param_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def yarn(self) -> Optional[Tuple[float, int]]:
        """(factor, original max positions) of YaRN RoPE, or None for plain
        RoPE."""
        return (self.yarn_factor, self.yarn_original_max) if self.yarn_factor else None

    @property
    def n_held_experts(self) -> int:
        return self.experts_held or self.n_experts

    # ssm derived
    @property
    def ssm_dinner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_nheads(self) -> int:
        return self.ssm_dinner // self.ssm_headdim

    @property
    def ssm_conv_dim(self) -> int:
        return self.ssm_dinner + 2 * self.ssm_ngroups * self.ssm_state

    def is_subquadratic(self) -> bool:
        """Can this arch serve 500k-token contexts (long_500k shape)?"""
        return self.family in ("ssm", "hybrid")

    def has_decode(self) -> bool:
        return True  # no encoder-only archs in the assigned pool

    # approximate parameter counts for MODEL_FLOPS = 6·N·D (see benchmarks/roofline)
    def param_count(self, active_only: bool = False) -> int:
        from . import registry

        return registry.count_params(self, active_only=active_only)
