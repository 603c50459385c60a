"""Mellum2 (JetBrains/Mellum2-12B-A2.5B): every fourth layer attends to
every earlier key and the three before it to the last ``window`` keys, and
every layer's MLP is routed experts, of which this chip holds a share.

The weight tree has the layout the serving engine consumes (the 28 layers
stacked under ``blocks[0]``, the held experts stacked inside each). Scales
follow ``dense_gqa``: projections normal with variance 1/fan-in, norm scales
1 + 0.1 N(0, 1), the embedding and the untied head 3/sqrt(d_model), so the
logits have a standard deviation near 3. The router is normal with variance
1/d_model: over a normed hidden state its 64 logits have unit variance, so a
token's top-8 gates spread over a few of its experts, as a trained,
load-balanced router's do, instead of crowning one.

``served_rows`` is the plain float32 reference, written from the published
architecture and importing nothing of the program (the matrix-product,
RMSNorm and fp8 helpers are ``reference.py``'s): RMSNorm; grouped-query
attention with a sliding window of ``window`` keys on ``sliding_attention``
layers; RoPE at ``rope_theta`` on the two halves of each head, and on
``full_attention`` layers YaRN's frequencies with cos and sin scaled by its
attention factor (transformers' ``_compute_yarn_parameters``); a softmax
router over all ``n_experts``, top-``top_k`` renormalised, of whose pairs only
those on the held experts [rank * held, (rank + 1) * held) are summed, each
gate times a SwiGLU expert; an untied output head. It runs one sequence at a
time, the layers in one scan, logits reduced in blocks of rows, like
``reference.py``; ``fp8`` rounds every product's operands to e4m3.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import peaks
from benchmarks.chip.reference import ROW_BLOCK, SEQ_BUCKET, _head, _mm, _rms
from benchmarks.chip.weights import padded_vocab

LOGIT_STD = 3.0
Q_BLOCK = 512  # query rows per attention block
# YaRN's beta_fast and beta_slow as rope_parameters.full_attention gives them
BETA_FAST, BETA_SLOW = 32.0, 1.0


def shapes(m: Dict) -> Dict:
    """Leaf name -> (shape, dtype, init std or 'norm'), nested like the
    program's parameter tree."""
    d, f, L = m["d_model"], m["d_ff"], m["n_layers"]
    h, hkv, dh = m["n_heads"], m["n_kv_heads"], m["d_head"]
    e, held = m["n_experts"], m["experts_held"]
    vp = padded_vocab(m["vocab"])
    wdt = m["dtype"]
    head_std = LOGIT_STD / math.sqrt(d)
    embed = {"embedding": ((vp, d), wdt, head_std)}
    if not m["tie_embeddings"]:
        embed["lm_head"] = ((d, vp), wdt, head_std)
    block = {
        "ln_attn": ((L, d), "float32", "norm"),
        "attn": {
            "wq": ((L, d, h, dh), wdt, 1 / math.sqrt(d)),
            "wk": ((L, d, hkv, dh), wdt, 1 / math.sqrt(d)),
            "wv": ((L, d, hkv, dh), wdt, 1 / math.sqrt(d)),
            "wo": ((L, h, dh, d), wdt, 1 / math.sqrt(h * dh)),
        },
        "ln_moe": ((L, d), "float32", "norm"),
        "moe": {
            "router": ((L, d, e), "float32", 1 / math.sqrt(d)),
            "w_gate": ((L, held, d, f), wdt, 1 / math.sqrt(d)),
            "w_up": ((L, held, d, f), wdt, 1 / math.sqrt(d)),
            "w_down": ((L, held, f, d), wdt, 1 / math.sqrt(f)),
        },
    }
    return {"embed": embed, "blocks": [block],
            "final_norm": ((d,), "float32", "norm")}


def layers(m: Dict) -> List[peaks.Layer]:
    """The published order: ``window`` layers of span ``window``, full
    layers of every key, each with the held share of the routed experts."""
    ffn = peaks.routed_ffn(m["d_model"], m["d_ff"], m["n_experts"],
                           m["experts_held"], m["top_k"])
    return [peaks.Layer(d_model=m["d_model"], n_heads=m["n_heads"],
                        n_kv_heads=m["n_kv_heads"], d_head=m["d_head"],
                        span=m["window"] if kind == "sliding_attention" else None,
                        ffn_params=ffn)
            for kind in m["layer_types"]]


def _inv_freq(m: Dict, yarn: bool) -> np.ndarray:
    """RoPE inverse frequencies; YaRN's on the full layers."""
    dh, theta = m["d_head"], m["rope_theta"]
    base = theta ** (np.arange(0, dh, 2) / dh)
    if not yarn:
        return 1.0 / base

    def dim(rotations):  # the dimension that turns ``rotations`` times
        return (dh * math.log(m["yarn_original_max"] / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim(BETA_FAST)), 0)
    high = min(math.ceil(dim(BETA_SLOW)), dh - 1)
    ramp = np.clip((np.arange(dh // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (1.0 / (m["yarn_factor"] * base)) * ramp + (1.0 / base) * (1.0 - ramp)


def _rope(x, inv_freq, scale):
    """x (T, H, D): rotate the pair (x[i], x[i + D/2]) by position * inv_freq[i]."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = (jnp.cos(ang) * scale)[:, None, :], (jnp.sin(ang) * scale)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v, span, fp8):
    """Causal GQA, position p seeing keys p - span + 1 .. p (``span`` is a
    traced number: the window, or the sequence for a full layer).
    q (T, H, D), k/v (T, Hkv, D) -> (T, H, D)."""
    t, h, d = q.shape
    group = h // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    kpos = jnp.arange(t)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, axis=0)
        s = _mm("qhd,khd->hqk", qb / np.sqrt(d), k, fp8)
        qpos = (i * Q_BLOCK + jnp.arange(Q_BLOCK))[:, None]
        live = (kpos[None, :] <= qpos) & (kpos[None, :] > qpos - span)
        s = jnp.where(live[None], s, -jnp.inf)
        return _mm("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v, fp8)

    return jax.lax.map(block, jnp.arange(t // Q_BLOCK)).reshape(t, h, d)


def _experts(m, p, h, fp8):
    """The held share of the routed SwiGLU experts. h (T, d) -> (T, d)."""
    f32 = lambda a: a.astype(jnp.float32)
    probs = jax.nn.softmax(_mm("td,de->te", h, f32(p["router"]), fp8), axis=-1)
    gate, idx = jax.lax.top_k(probs, m["top_k"])
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    first = m["expert_rank"] * m["experts_held"]

    def one(acc, ew):
        e, wg, wu, wd = ew
        weight = jnp.sum(jnp.where(idx == first + e, gate, 0.0), axis=-1)
        g = _mm("td,df->tf", h, f32(wg), fp8)
        u = _mm("td,df->tf", h, f32(wu), fp8)
        y = _mm("tf,fd->td", jax.nn.silu(g) * u, f32(wd), fp8)
        return acc + weight[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          (jnp.arange(m["experts_held"]), p["w_gate"], p["w_up"],
                           p["w_down"]))
    return out


def _layer(m, eps, fp8, x, p_full):
    p, full = p_full
    f32 = lambda a: a.astype(jnp.float32)
    a = p["attn"]
    h = _rms(x, f32(p["ln_attn"]), eps)
    q = _mm("td,dhk->thk", h, f32(a["wq"]), fp8)
    k = _mm("td,dhk->thk", h, f32(a["wk"]), fp8)
    v = _mm("td,dhk->thk", h, f32(a["wv"]), fp8)
    inv = jnp.where(full, jnp.asarray(_inv_freq(m, bool(m["yarn_factor"])), jnp.float32),
                    jnp.asarray(_inv_freq(m, False), jnp.float32))
    # the published attention factor, 1.2772588722239782 = 0.1 ln 16 + 1
    scale = jnp.where(full, 0.1 * math.log(m["yarn_factor"]) + 1.0 if m["yarn_factor"]
                      else 1.0, 1.0)
    q, k = _rope(q, inv, scale), _rope(k, inv, scale)
    span = jnp.where(full, x.shape[0], m["window"])
    x = x + _mm("thk,hkd->td", _attention(q, k, v, span, fp8), f32(a["wo"]), fp8)
    h = _rms(x, f32(p["ln_moe"]), eps)
    return x + _experts(m, p["moe"], h, fp8), None


@functools.partial(jax.jit, static_argnames=("m_items", "eps", "fp8"))
def _hidden(params, tokens, *, m_items, eps, fp8):
    m = dict(m_items)
    m["layer_types"] = list(m["layer_types"])
    full = jnp.asarray([t == "full_attention" for t in m["layer_types"]])
    x = params["embed"]["embedding"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(functools.partial(_layer, m, eps, fp8), x,
                        (params["blocks"][0], full))
    return _rms(x, params["final_norm"].astype(jnp.float32), eps)


def served_rows(params, m: Dict, eps: float, prompt: Sequence[int],
                served: Sequence[int], *, fp8: bool = False, lookups=()
                ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, List[np.ndarray]]:
    """Teacher-forced over ``prompt + served[:-1]``; for each served token n,
    the row at position len(prompt) - 1 + n gives the best logit, the logit
    of served[n], the arg-max, and the logit of ``lookup[n]`` for each extra
    token list in ``lookups``."""
    ctx = list(prompt) + list(served[:-1])
    n_pad = -(-len(ctx) // SEQ_BUCKET) * SEQ_BUCKET
    tokens = jnp.asarray(ctx + [0] * (n_pad - len(ctx)), jnp.int32)
    items = tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                         for k, v in m.items()))
    hid = _hidden(params, tokens, m_items=items, eps=eps, fp8=fp8)
    first, g = len(prompt) - 1, len(served)
    n_blk = -(-g // ROW_BLOCK) * ROW_BLOCK
    rows = np.minimum(first + np.arange(n_blk), n_pad - 1)
    cols = []
    for toks in [served, *lookups]:
        t = np.zeros(n_blk, np.int32)
        t[:g] = toks
        outs = [_head(params, hid[rows[b:b + ROW_BLOCK]],
                      jnp.asarray(t[b:b + ROW_BLOCK]), vocab=m["vocab"],
                      tied=m["tie_embeddings"], fp8=fp8)
                for b in range(0, n_blk, ROW_BLOCK)]
        cols.append([np.concatenate([np.asarray(o[i]) for o in outs])[:g]
                     for i in range(3)])
    best, got, arg = cols[0]
    return best, got, arg, [c[1] for c in cols[1:]]
