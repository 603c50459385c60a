"""Shared layers: norms, RoPE, MLP, embeddings, Sharder, (Quant)Linear apply.

Every parameter is declared as a ``core.distributed.TensorSpec`` (the mdspan
descriptor: extents × logical axes × dtype × accessor); apply functions consume
the plain buffer pytrees those specs initialize. Quantized weights arrive as
{"q","scale"} buffer dicts and dispatch through kernels/ops.matmul.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding

from repro.core.accessors import QuantizedAccessor
from repro.core.distributed import ShardingRules, TensorSpec, dequantize_array
from repro.kernels import ops


# ---------------------------------------------------------------------------------
# Sharder: activation sharding constraints from logical axis names
# ---------------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Sharder:
    """Applies with_sharding_constraint from logical names; identity off-mesh.

    The activation-side twin of TensorSpec: the same ShardingRules table that lays
    out parameters lays out activations, so a parallelism change (DP→SP, TP width)
    is one table edit (the paper's layout-swap-without-algorithm-change).
    """

    mesh: Optional[Mesh] = None
    rules: Optional[ShardingRules] = None

    def __call__(self, x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
        if self.mesh is None or self.rules is None:
            return x
        sh = self.rules.sharding(logical_axes, x.shape, self.mesh)
        return jax.lax.with_sharding_constraint(x, sh)


NULL_SHARDER = Sharder()


# ---------------------------------------------------------------------------------
# linear / quantized linear
# ---------------------------------------------------------------------------------
def fit_quant(quant: Optional[QuantizedAccessor], d_in: int) -> Optional[QuantizedAccessor]:
    """Largest block <= quant.block that divides d_in; None when d_in is too
    small/odd to quantize (the spec then falls back to dense storage)."""
    if quant is None:
        return None
    import dataclasses as _dc

    for b in (quant.block, 128, 64, 32):
        if b <= quant.block and d_in % b == 0 and b >= 16:
            return _dc.replace(quant, block=b)
    return None


def linear_spec(
    d_in: int,
    d_out: int,
    axes: Tuple[Optional[str], Optional[str]],
    *,
    dtype=jnp.bfloat16,
    quant: Optional[QuantizedAccessor] = None,
    init: str = "fan_in",
) -> TensorSpec:
    """Weight spec. Dense storage: (d_in, d_out) [K-major]. Quantized storage:
    OUTPUT-major (d_out, d_in) int8/int4+scales (kernel layout, see quant_matmul)."""
    quant = fit_quant(quant, d_in)
    if quant is not None:
        return TensorSpec(
            (d_out, d_in), (axes[1], axes[0]), dtype=dtype, init=init, accessor=quant
        )
    return TensorSpec((d_in, d_out), axes, dtype=dtype, init=init)


def apply_linear(x: jax.Array, w, spec: Optional[TensorSpec] = None) -> jax.Array:
    """x: (..., d_in) @ w. Dispatches on the buffer form (dense vs quantized)."""
    if isinstance(w, dict):  # quantized {"q","scale"}: stored (d_out, d_in)
        acc = spec.accessor if spec is not None else QuantizedAccessor(x.dtype, bits=8)
        return ops.matmul(x, w, acc)
    return jnp.matmul(x, w.astype(x.dtype))


# ---------------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------------
def rmsnorm_spec(d: int, axes=( "embed",)) -> TensorSpec:
    return TensorSpec((d,), axes, dtype=jnp.float32, init="ones")


def apply_rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def layernorm_specs(d: int) -> Dict[str, TensorSpec]:
    return {
        "scale": TensorSpec((d,), ("embed",), dtype=jnp.float32, init="ones"),
        "bias": TensorSpec((d,), ("embed",), dtype=jnp.float32, init="zeros"),
    }


def apply_layernorm(x: jax.Array, p: Dict[str, jax.Array], eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]).astype(x.dtype)


def norm_specs(cfg) -> Any:
    return layernorm_specs(cfg.d_model) if cfg.norm == "layernorm" else rmsnorm_spec(cfg.d_model)


def apply_norm(cfg, x, p):
    return apply_layernorm(x, p) if cfg.norm == "layernorm" else apply_rmsnorm(x, p)


# ---------------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------------
def rope_freqs(d_head: int, theta: float) -> jax.Array:
    half = d_head // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


YARN_BETA_FAST, YARN_BETA_SLOW = 32.0, 1.0  # transformers' defaults


def yarn_attention_factor(factor: float) -> float:
    """YaRN's scale of cos and sin (transformers' default: 0.1 ln s + 1)."""
    return 0.1 * math.log(factor) + 1.0


def yarn_freqs(d_head: int, theta: float, factor: float, original_max: int) -> np.ndarray:
    """YaRN's inverse frequencies (arXiv:2309.00071), as transformers'
    ``_compute_yarn_parameters`` gives them (``truncate`` on): dimensions
    that turn fewer than ``YARN_BETA_SLOW`` times over ``original_max``
    positions are interpolated by ``factor``, those that turn more than
    ``YARN_BETA_FAST`` times are kept, and a linear ramp joins the two."""
    half = d_head // 2
    pos_freqs = theta ** (np.arange(0, d_head, 2, dtype=np.float64) / d_head)

    def corr_dim(rotations):
        return (d_head * math.log(original_max / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr_dim(YARN_BETA_FAST)), 0)
    high = min(math.ceil(corr_dim(YARN_BETA_SLOW)), d_head - 1)
    ramp = np.clip((np.arange(half) - low) / ((high - low) or 0.001), 0, 1)
    keep = 1.0 - ramp
    inv = (1.0 / (factor * pos_freqs)) * (1 - keep) + (1.0 / pos_freqs) * keep
    return inv.astype(np.float32)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               yarn=None) -> jax.Array:
    """x: (B, H, T, D); positions: (T,) or (B, T) absolute positions.
    ``yarn`` (ModelConfig.yarn) switches to YaRN's frequencies, and its
    attention factor scales cos and sin, so q . k grows by its square."""
    b, h, t, d = x.shape
    if yarn is None:
        freqs = rope_freqs(d, theta)  # (d/2,)
    else:
        freqs = jnp.asarray(yarn_freqs(d, theta, *yarn))
    if positions.ndim == 1:
        ang = positions[:, None].astype(jnp.float32) * freqs[None, :]  # (T, d/2)
        ang = ang[None, None]  # (1, 1, T, d/2)
    else:
        ang = positions[:, :, None].astype(jnp.float32) * freqs[None, None, :]
        ang = ang[:, None]  # (B, 1, T, d/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if yarn is not None:
        scale = yarn_attention_factor(yarn[0])
        cos, sin = cos * scale, sin * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------------
# MLP (SwiGLU / GELU)
# ---------------------------------------------------------------------------------
def mlp_specs(cfg, *, d_model=None, d_ff=None, quant=None) -> Dict[str, TensorSpec]:
    d = d_model or cfg.d_model
    f = d_ff or cfg.d_ff
    dt = cfg.param_dtype
    if cfg.mlp_act in ("swiglu", "geglu"):
        return {
            "w_gate": linear_spec(d, f, ("embed", "ffn"), dtype=dt, quant=quant),
            "w_up": linear_spec(d, f, ("embed", "ffn"), dtype=dt, quant=quant),
            "w_down": linear_spec(f, d, ("ffn", "embed"), dtype=dt, quant=quant),
        }
    return {
        "w_up": linear_spec(d, f, ("embed", "ffn"), dtype=dt, quant=quant),
        "b_up": TensorSpec((f,), ("ffn",), dtype=jnp.float32, init="zeros"),
        "w_down": linear_spec(f, d, ("ffn", "embed"), dtype=dt, quant=quant),
        "b_down": TensorSpec((d,), ("embed",), dtype=jnp.float32, init="zeros"),
    }


def apply_mlp(cfg, p, x: jax.Array, shard: Sharder = NULL_SHARDER, specs=None) -> jax.Array:
    sp = specs or {}
    if cfg.mlp_act in ("swiglu", "geglu"):
        act = jax.nn.silu if cfg.mlp_act == "swiglu" else jax.nn.gelu
        g = apply_linear(x, p["w_gate"], sp.get("w_gate"))
        u = apply_linear(x, p["w_up"], sp.get("w_up"))
        h = act(g.astype(jnp.float32)).astype(x.dtype) * u
        h = shard(h, "batch", "seq", "ffn")
        return apply_linear(h, p["w_down"], sp.get("w_down"))
    h = apply_linear(x, p["w_up"], sp.get("w_up")) + p["b_up"].astype(x.dtype)
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    h = shard(h, "batch", "seq", "ffn")
    return apply_linear(h, p["w_down"], sp.get("w_down")) + p["b_down"].astype(x.dtype)


# ---------------------------------------------------------------------------------
# embeddings / lm head
# ---------------------------------------------------------------------------------
def embed_specs(cfg) -> Dict[str, TensorSpec]:
    vp = cfg.vocab_padded
    s = {
        "embedding": TensorSpec(
            (vp, cfg.d_model), ("vocab", "embed"), dtype=cfg.param_dtype, init="embed"
        ),
    }
    if not cfg.tie_embeddings:
        # lm head sharded on VOCAB (logits matmul + sharded softmax)
        s["lm_head"] = TensorSpec(
            (cfg.d_model, vp), ("embed", "vocab"), dtype=cfg.param_dtype, init="fan_in"
        )
    return s


def apply_embed(p, tokens: jax.Array) -> jax.Array:
    return jnp.take(p["embedding"], tokens, axis=0)


def apply_lm_head(cfg, p, x: jax.Array) -> jax.Array:
    w = p["embedding"].T if cfg.tie_embeddings else p["lm_head"]
    logits = jnp.matmul(x, w.astype(x.dtype))
    vp = logits.shape[-1]
    if vp != cfg.vocab:  # mask padded vocab slots
        mask = (jnp.arange(vp) < cfg.vocab)
        logits = jnp.where(mask, logits, jnp.asarray(-1e9, logits.dtype))
    return logits


def cross_entropy(logits: jax.Array, labels: jax.Array, mask: Optional[jax.Array] = None):
    """Mean CE over valid positions. logits: (..., V); labels int32 (...).

    The label log-prob is extracted with a masked REDUCTION over the vocab axis
    (not take_along_axis): with vocab sharded over "model" this lowers to a local
    reduce + psum instead of an all-gather of the logits — the difference between
    ~0.5 GB and ~17 GB of temp per device on the 4k×256 cells.
    """
    logits = logits.astype(jnp.float32)
    m = jnp.max(logits, axis=-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1)) + m[..., 0]
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    ll = jnp.sum(jnp.where(vocab_iota == labels[..., None], logits, 0.0), axis=-1)
    nll = lse - ll
    if mask is not None:
        denom = jnp.maximum(jnp.sum(mask), 1)
        return jnp.sum(nll * mask) / denom
    return jnp.mean(nll)
