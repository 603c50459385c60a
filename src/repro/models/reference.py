"""Plain float32 reference of a decoder with mixed attention and routed
experts (Mellum2's layer equations), for the tests that hold the serving
engine to it.

Straightforward ``jax.numpy`` over one whole sequence: no kernels, no cache,
no batching, every matrix product at ``Precision.HIGHEST``. It reads the
program's parameter tree (layers stacked under ``blocks[0]``) and the
``ModelConfig`` fields, and shares no code with the layers it checks:

- RMSNorm (eps 1e-6) before attention and before the experts;
- grouped-query attention, query head ``h`` on K/V head ``h // (H / Hkv)``;
  a ``sliding_attention`` layer lets position p see keys p - window + 1 .. p,
  a ``full_attention`` layer every key up to p;
- RoPE on the two halves of each head at ``rope_theta``; on full layers,
  when ``cfg.yarn_factor`` is set, YaRN's frequencies (beta_fast 32,
  beta_slow 1) with cos and sin scaled by 0.1 ln(factor) + 1
  (transformers' ``_compute_yarn_parameters`` and its defaults);
- routed SwiGLU experts: softmax over all ``n_experts`` router logits, top-k,
  renormalised over the k; the output sums gate x expert over the pairs whose
  expert is held (``experts``: the global ids whose weights ``p`` holds);
- an untied output head (or the embedding's transpose when tied).

``windows=False`` and ``yarn=False`` give the negative controls: full
attention in place of the window, and plain RoPE on the full layers.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
EPS = 1e-6


def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HI)


def _rms(x, w):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) * w


def _inv_freq(cfg, full: bool, yarn: bool) -> np.ndarray:
    dh, theta = cfg.head_dim, cfg.rope_theta
    base = theta ** (np.arange(0, dh, 2) / dh)
    if not (full and yarn and cfg.yarn_factor):
        return 1.0 / base
    lo_rot, hi_rot = 32.0, 1.0
    dim_of = lambda rot: (dh * math.log(cfg.yarn_original_max / (rot * 2 * math.pi))
                          / (2 * math.log(theta)))
    low, high = max(math.floor(dim_of(lo_rot)), 0), min(math.ceil(dim_of(hi_rot)), dh - 1)
    ramp = np.clip((np.arange(dh // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (1.0 / (cfg.yarn_factor * base)) * ramp + (1.0 / base) * (1.0 - ramp)


def _rope(x, inv_freq, scale):
    """x (T, H, D): rotate (x[i], x[i + D/2]) by position * inv_freq[i]."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(
        inv_freq, jnp.float32)[None, :]
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention_layer(cfg, p, h, *, full: bool, windows: bool = True,
                    yarn: bool = True):
    """One layer's attention over the whole sequence. h (T, d) -> (T, d)."""
    f32 = lambda a: a.astype(jnp.float32)
    q = _mm("td,dhk->thk", h, f32(p["wq"]))
    k = _mm("td,dhk->thk", h, f32(p["wk"]))
    v = _mm("td,dhk->thk", h, f32(p["wv"]))
    scale = 0.1 * math.log(cfg.yarn_factor) + 1.0 if (full and yarn and cfg.yarn_factor) else 1.0
    inv = _inv_freq(cfg, full, yarn)
    q, k = _rope(q, inv, scale), _rope(k, inv, scale)
    group = cfg.n_heads // cfg.n_kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = _mm("qhd,khd->hqk", q, k) / np.sqrt(cfg.head_dim)
    t = h.shape[0]
    qp, kp = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    live = kp <= qp
    if not full and windows:
        live = live & (kp > qp - cfg.window)
    s = jnp.where(live[None], s, -jnp.inf)
    o = _mm("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    return _mm("thk,hkd->td", o, f32(p["wo"]))


def routed_experts(cfg, p, h, experts: Optional[Sequence[int]] = None):
    """Routed SwiGLU experts. h (T, d) -> (T, d): the router over all
    ``cfg.n_experts``; the sum over the pairs whose expert is in ``experts``
    (default: the ones ``cfg`` holds), ``p``'s expert weights in that order."""
    if experts is None:
        held = cfg.experts_held or cfg.n_experts
        experts = range(cfg.expert_rank * held, (cfg.expert_rank + 1) * held)
    f32 = lambda a: a.astype(jnp.float32)
    probs = jax.nn.softmax(_mm("td,de->te", h, f32(p["router"])), axis=-1)
    gate, idx = jax.lax.top_k(probs, cfg.top_k)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    out = jnp.zeros_like(h)
    for j, e in enumerate(experts):
        weight = jnp.sum(jnp.where(idx == e, gate, 0.0), axis=-1)  # (T,)
        g = _mm("td,df->tf", h, f32(p["w_gate"][j]))
        u = _mm("td,df->tf", h, f32(p["w_up"][j]))
        y = _mm("tf,fd->td", jax.nn.silu(g) * u, f32(p["w_down"][j]))
        out = out + weight[:, None] * y
    return out


def logits(cfg, params, tokens, *, windows: bool = True, yarn: bool = True):
    """(T, vocab) float32 logits of one sequence ``tokens`` (T,)."""
    f32 = lambda a: a.astype(jnp.float32)
    kinds = cfg.layer_types or ("full_attention",) * cfg.n_layers
    x = f32(params["embed"]["embedding"][jnp.asarray(tokens)])
    stack = params["blocks"][0]
    for layer, kind in enumerate(kinds):
        p = jax.tree.map(lambda a: a[layer], stack)
        full = kind == "full_attention"
        x = x + attention_layer(cfg, p["attn"], _rms(x, f32(p["ln_attn"])),
                                full=full, windows=windows, yarn=yarn)
        x = x + routed_experts(cfg, p["moe"], _rms(x, f32(p["ln_moe"])))
    x = _rms(x, f32(params["final_norm"]))
    e = params["embed"]
    w = e["embedding"].T if cfg.tie_embeddings else e["lm_head"]
    return _mm("td,dv->tv", x, f32(w[:, :cfg.vocab]))
