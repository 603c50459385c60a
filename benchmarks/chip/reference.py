"""Plain float32 reference of a dense GQA decoder (Qwen2 / Llama / Granite).

Written from the published architecture, not from the program, and imports
nothing of it: RMSNorm, rotary embedding on the two halves of each head
(``rotate_half``), grouped-query attention in which query head ``h`` reads
key/value head ``h // (n_heads / n_kv_heads)``, a SwiGLU MLP, and an output
head tied to the embedding or not. Every matrix product runs in float32 at
``Precision.HIGHEST``; the weights are the bf16 arrays the benchmark drew
(``weights.py``), read in float32.

``precision="fp8"`` is the control: every matrix product takes its two
operands rounded to float8_e4m3fn with one scale per tensor (largest
magnitude to 448), the step below the configuration's bf16.

The model is run one sequence at a time, layer by layer inside one scan, and
only the final hidden states leave the layer loop; logits are reduced to
three numbers per position (the best logit, the logit of a given token, the
arg-max) in blocks of rows, so no (positions, vocab) array is kept.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
SEQ_BUCKET = 1024  # sequences are right-padded to a multiple of this
Q_BLOCK = 512  # query rows per attention block
ROW_BLOCK = 512  # positions per logits block
F8_MAX = 448.0


def _f8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec, a, b, fp8):
    if fp8:
        a, b = _f8(a), _f8(b)
    return jnp.einsum(spec, a, b, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x (T, H, D): rotate the pair (x[i], x[i + D/2]) by pos * theta^(-2i/D)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v, fp8):
    """Causal GQA. q (T, H, D), k/v (T, Hkv, D) -> (T, H, D)."""
    t, h, d = q.shape
    group = h // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scale = 1.0 / np.sqrt(d)
    kpos = jnp.arange(t)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, axis=0)
        s = _mm("qhd,khd->hqk", qb * scale, k, fp8)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _mm("hqk,khd->qhd", p, v, fp8)

    out = jax.lax.map(block, jnp.arange(t // Q_BLOCK))
    return out.reshape(t, h, d)


def _layer(m, eps, fp8, x, p):
    f32 = lambda a: a.astype(jnp.float32)
    pos = jnp.arange(x.shape[0])
    a = p["attn"]
    h = _rms(x, f32(p["ln_attn"]), eps)
    q = _mm("td,dhk->thk", h, f32(a["wq"]), fp8)
    k = _mm("td,dhk->thk", h, f32(a["wk"]), fp8)
    v = _mm("td,dhk->thk", h, f32(a["wv"]), fp8)
    if "bq" in a:
        q, k, v = q + f32(a["bq"]), k + f32(a["bk"]), v + f32(a["bv"])
    q = _rope(q, pos, m["rope_theta"])
    k = _rope(k, pos, m["rope_theta"])
    o = _attention(q, k, v, fp8)
    x = x + _mm("thk,hkd->td", o, f32(a["wo"]), fp8)
    h = _rms(x, f32(p["ln_mlp"]), eps)
    mp = p["mlp"]
    g = _mm("td,df->tf", h, f32(mp["w_gate"]), fp8)
    u = _mm("td,df->tf", h, f32(mp["w_up"]), fp8)
    x = x + _mm("tf,fd->td", jax.nn.silu(g) * u, f32(mp["w_down"]), fp8)
    return x, None


@functools.partial(jax.jit, static_argnames=("m_items", "eps", "fp8"))
def _hidden(params, tokens, *, m_items, eps, fp8):
    m = dict(m_items)
    x = params["embed"]["embedding"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(functools.partial(_layer, m, eps, fp8), x,
                        params["blocks"][0])
    return _rms(x, params["final_norm"].astype(jnp.float32), eps)


@functools.partial(jax.jit, static_argnames=("vocab", "tied", "fp8"))
def _head(params, hid, tok, *, vocab, tied, fp8):
    """hid (R, d), tok (R,) -> (best logit, logit of tok, arg-max) per row."""
    e = params["embed"]
    w = e["embedding"][:vocab].T if tied else e["lm_head"][:, :vocab]
    logits = _mm("rd,dv->rv", hid, w.astype(jnp.float32), fp8)
    return (jnp.max(logits, axis=-1),
            jnp.take_along_axis(logits, tok[:, None], axis=-1)[:, 0],
            jnp.argmax(logits, axis=-1).astype(jnp.int32))


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
    hid = _hidden(params, tokens, m_items=tuple(sorted(m.items())), eps=eps,
                  fp8=fp8)
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
