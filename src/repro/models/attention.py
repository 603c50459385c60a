"""GQA self-attention + cross-attention blocks (pre-norm), train/prefill/decode.

Caches are (B, Hkv, S, Dh) per layer — the TensorSpec for them carries the
LayoutTiledTPU-friendly (S on sublanes, Dh on lanes) orientation and the sharding
rules bind Hkv → "model" when divisible (else the KV tensors replicate across the
model axis and only the batch axis shards — the Megatron fallback; see
ShardingRules.binding_for).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.distributed import TensorSpec
from repro.kernels import ops

from .layers import (
    NULL_SHARDER,
    Sharder,
    apply_linear,
    apply_norm,
    apply_rope,
    norm_specs,
)


# ---------------------------------------------------------------------------------
# specs
# ---------------------------------------------------------------------------------
def attn_specs(cfg, *, quant=None) -> Dict[str, TensorSpec]:
    d, h, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = cfg.param_dtype
    s = {
        "wq": TensorSpec((d, h, dh), ("embed", "heads", None), dtype=dt,
                         init="fan_in_to_heads"),
        "wk": TensorSpec((d, hkv, dh), ("embed", "kv_heads", None), dtype=dt,
                         init="fan_in_to_heads"),
        "wv": TensorSpec((d, hkv, dh), ("embed", "kv_heads", None), dtype=dt,
                         init="fan_in_to_heads"),
        "wo": TensorSpec((h, dh, d), ("heads", None, "embed"), dtype=dt,
                         init="fan_in_from_heads"),
    }
    if cfg.qkv_bias:
        s["bq"] = TensorSpec((h, dh), ("heads", None), dtype=jnp.float32, init="zeros")
        s["bk"] = TensorSpec((hkv, dh), ("kv_heads", None), dtype=jnp.float32, init="zeros")
        s["bv"] = TensorSpec((hkv, dh), ("kv_heads", None), dtype=jnp.float32, init="zeros")
    return s


def cross_attn_specs(cfg, *, quant=None) -> Dict[str, TensorSpec]:
    # same projection geometry; kv projects the (stubbed) modality context
    return attn_specs(cfg, quant=quant)


def cache_specs(cfg, batch: int, seq: int) -> Dict[str, TensorSpec]:
    hkv, dh = cfg.n_kv_heads, cfg.head_dim
    dt = cfg.param_dtype
    return {
        "k": TensorSpec((batch, hkv, seq, dh), ("batch", "kv_heads", "kv_seq", None), dtype=dt, init="zeros"),
        "v": TensorSpec((batch, hkv, seq, dh), ("batch", "kv_heads", "kv_seq", None), dtype=dt, init="zeros"),
    }


def paged_cache_specs(cfg, num_pages: int, page_size: int, kv_spec=None) -> Dict[str, TensorSpec]:
    """Per-layer paged KV pool — the LayoutPaged codomain (pool_shape()) as a
    TensorSpec. Page-major with (page_size, head_dim) innermost keeps each page a
    LayoutTiledTPU-friendly (sublane, lane) tile.

    ``kv_spec`` (serving.engine.kvquant.PagedQuantSpec) swaps the element
    representation — the accessor axis — without touching the layout: each of
    k/v becomes {"q": intN page bytes, "scale": one f32 per (page, head)}."""
    hkv, dh = cfg.n_kv_heads, cfg.head_dim
    if kv_spec is not None:
        dq = kv_spec.packed_dim(dh)
        quant = {
            "q": TensorSpec((num_pages, hkv, page_size, dq),
                            (None, "kv_heads", None, None), dtype=jnp.int8, init="zeros"),
            "scale": TensorSpec((num_pages, hkv), (None, "kv_heads"),
                                dtype=jnp.float32, init="zeros"),
        }
        return {"k": quant, "v": dict(quant)}
    dt = cfg.param_dtype
    return {
        "k": TensorSpec((num_pages, hkv, page_size, dh), (None, "kv_heads", None, None), dtype=dt, init="zeros"),
        "v": TensorSpec((num_pages, hkv, page_size, dh), (None, "kv_heads", None, None), dtype=dt, init="zeros"),
    }


def pack_kv_pages(pool: Dict[str, jax.Array], k: jax.Array, v: jax.Array,
                  pages: jax.Array) -> Dict[str, jax.Array]:
    """Scatter freshly-prefilled K/V into pool pages (the prefill->paged adapter).

    pool k/v: (L, num_pages, Hkv, ps, Dh); k/v: (L, 1, Hkv, S, Dh) with S a
    multiple of ps (pack_kv_cache pads); pages: (n,) physical ids of the
    sequence's logical pages 0..n-1, n == S // ps.
    """
    l, _, hkv, s, dh = k.shape
    ps = pool["k"].shape[3]
    n = s // ps
    # (L, Hkv, n, ps, Dh) -> (L, n, Hkv, ps, Dh)
    kp = jnp.swapaxes(k[:, 0].reshape(l, hkv, n, ps, dh), 1, 2)
    vp = jnp.swapaxes(v[:, 0].reshape(l, hkv, n, ps, dh), 1, 2)
    return {
        "k": pool["k"].at[:, pages].set(kp.astype(pool["k"].dtype)),
        "v": pool["v"].at[:, pages].set(vp.astype(pool["v"].dtype)),
    }


def pack_kv_pages_quant(pool, k: jax.Array, v: jax.Array, pages: jax.Array, *,
                        spec) -> Dict[str, Dict[str, jax.Array]]:
    """pack_kv_pages for a quantized pool: quantize AT SCATTER TIME with a fresh
    scale per (page, head) (spec.encode_pages), then write {q, scale} together.

    pool k/v: {"q": (L, num_pages, Hkv, ps, Dq) int8, "scale": (L, num_pages,
    Hkv) f32}; k/v and pages as in pack_kv_pages. Page slack (prompt pad)
    participates in the scale like any other slot — prompts are zero-padded
    deterministically, so a page (bytes AND scale) stays a pure function of the
    tokens that hash to it and prefix sharing dedupes quantized pages exactly
    as f32 ones."""
    l, _, hkv, s, dh = k.shape
    ps = pool["k"]["q"].shape[3]
    n = s // ps
    # (L, Hkv, n, ps, Dh) -> (L, n, Hkv, ps, Dh)
    kp = jnp.swapaxes(k[:, 0].reshape(l, hkv, n, ps, dh), 1, 2)
    vp = jnp.swapaxes(v[:, 0].reshape(l, hkv, n, ps, dh), 1, 2)
    kq, vq = spec.encode_pages(kp), spec.encode_pages(vp)
    return {
        "k": {"q": pool["k"]["q"].at[:, pages].set(kq["q"]),
              "scale": pool["k"]["scale"].at[:, pages].set(kq["scale"])},
        "v": {"q": pool["v"]["q"].at[:, pages].set(vq["q"]),
              "scale": pool["v"]["scale"].at[:, pages].set(vq["scale"])},
    }


def pack_kv_cache(cfg, k: jax.Array, v: jax.Array, *, max_len: Optional[int],
                  window: Optional[int]) -> Dict[str, jax.Array]:
    """Lay freshly-prefilled K/V (B, Hkv, S, Dh) into the decode cache layout.

    Non-windowed: pad the seq dim to ``max_len`` capacity (token p at slot p).
    Windowed: a ring of size ``window`` where token p lives at slot p % window —
    the invariant self_attention_decode's ring arithmetic relies on.
    """
    s = k.shape[2]
    dt = cfg.param_dtype

    def pad_to(x, cap):
        if cap > x.shape[2]:
            return jnp.pad(x, ((0, 0), (0, 0), (0, cap - x.shape[2]), (0, 0)))
        return x

    if window is not None:
        w = window
        if s >= w:
            k = jnp.roll(k[:, :, -w:], s % w, axis=2)
            v = jnp.roll(v[:, :, -w:], s % w, axis=2)
        else:
            k, v = pad_to(k, w), pad_to(v, w)
    else:
        cap = max_len if max_len is not None else s
        k, v = pad_to(k, cap), pad_to(v, cap)
    return {"k": k.astype(dt), "v": v.astype(dt)}


# ---------------------------------------------------------------------------------
# projections
# ---------------------------------------------------------------------------------
def _project_qkv(cfg, p, x, ctx=None):
    """q from x; k/v from ctx (cross) or x (self). Returns (B,H,T,Dh)×3."""
    src = x if ctx is None else ctx
    q = jnp.einsum("btd,dhk->bhtk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("btd,dhk->bhtk", src, p["wk"].astype(x.dtype))
    v = jnp.einsum("btd,dhk->bhtk", src, p["wv"].astype(x.dtype))
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)[None, :, None, :]
        k = k + p["bk"].astype(x.dtype)[None, :, None, :]
        v = v + p["bv"].astype(x.dtype)[None, :, None, :]
    return q, k, v


def _out_proj(p, attn_out, x_dtype):
    return jnp.einsum("bhtk,hkd->btd", attn_out, p["wo"].astype(x_dtype))


# ---------------------------------------------------------------------------------
# self-attention paths
# ---------------------------------------------------------------------------------
def self_attention(
    cfg,
    p,
    x: jax.Array,
    *,
    shard: Sharder = NULL_SHARDER,
    causal: bool = True,
    window: Optional[int] = None,
    pos_offset=0,
    return_kv: bool = False,
):
    """Full-sequence self-attention (train / prefill). x: (B, T, D)."""
    b, t, d = x.shape
    q, k, v = _project_qkv(cfg, p, x)
    pos = jnp.arange(t) + pos_offset
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)
    q = shard(q, "batch", "heads", "seq", None)
    k = shard(k, "batch", "kv_heads", "seq", None)
    out = ops.attention(q, k, v, causal=causal, window=window, q_offset=pos_offset, impl="jnp")
    out = shard(out, "batch", "heads", "seq", None)
    y = _out_proj(p, out, x.dtype)
    if return_kv:
        return y, (k, v)
    return y


def _decode_attention_seq_sharded(cfg, q, k_new, v_new, cache, pos, mesh):
    """Distributed flash-decode over a kv_seq-sharded cache (§Perf decode fix).

    GSPMD's lowering of decode attention against a seq-sharded cache ALL-GATHERS
    the cache (~0.5 GB/layer/token on dbrx — measured). This shard_map version
    keeps every rank's KV slice local: each rank updates its slot (if the write
    position falls in its range), computes partial attention over its slice, and
    the ranks merge with a numerically-exact log-sum-exp combine — the collective
    is a (B, H, D)-sized psum (~3 MB) instead of the cache gather.

    q: (B, Hq, 1, D) [replicated over "model" on entry — a ~1 MB gather];
    cache k/v: (B, Hkv, S, D) sharded S→"model"; pos traced scalar.
    """
    from jax.sharding import PartitionSpec as P

    b, hq, _, d = q.shape
    s_total = cache["k"].shape[2]
    ep = mesh.shape["model"]
    s_loc = s_total // ep
    group = hq // cfg.n_kv_heads
    scale = 1.0 / np.sqrt(cfg.head_dim)

    def local_fn(q, k_new, v_new, ck, cv, pos):
        my = jax.lax.axis_index("model")
        slot = pos - my * s_loc
        in_range = (slot >= 0) & (slot < s_loc)
        slot_c = jnp.clip(slot, 0, s_loc - 1)
        ck_upd = jax.lax.dynamic_update_slice(ck, k_new.astype(ck.dtype), (0, 0, slot_c, 0))
        cv_upd = jax.lax.dynamic_update_slice(cv, v_new.astype(cv.dtype), (0, 0, slot_c, 0))
        ck = jnp.where(in_range, ck_upd, ck)
        cv = jnp.where(in_range, cv_upd, cv)

        # GQA via a group dim on q — the cache is NEVER repeated/materialized
        qg = q.reshape(b, cfg.n_kv_heads, group, d).astype(jnp.float32)
        s = jnp.einsum("bhgd,bhkd->bhgk", qg, ck.astype(jnp.float32)) * scale
        k_pos = my * s_loc + jnp.arange(s_loc)
        live = k_pos[None, None, None, :] <= pos
        s = jnp.where(live, s, -1e30)
        m_loc = jnp.max(s, axis=-1, keepdims=True)  # (B,Hkv,G,1)
        p_ = jnp.exp(s - m_loc)
        p_ = jnp.where(live, p_, 0.0)
        l_loc = jnp.sum(p_, axis=-1, keepdims=True)
        acc_loc = jnp.einsum("bhgk,bhkd->bhgd", p_, cv.astype(jnp.float32))
        # exact LSE merge across seq shards
        m_g = jax.lax.pmax(m_loc, "model")
        w = jnp.exp(m_loc - m_g)
        l_g = jax.lax.psum(l_loc * w, "model")
        acc_g = jax.lax.psum(acc_loc * w, "model")
        out = (acc_g / jnp.where(l_g == 0, 1.0, l_g)).reshape(b, hq, 1, d).astype(q.dtype)
        return out, ck, cv

    out, ck, cv = jax.shard_map(
        local_fn,
        mesh=mesh,
        axis_names={"model"},
        in_specs=(P(), P(), P(), P(None, None, "model", None), P(None, None, "model", None), P()),
        out_specs=(P(), P(None, None, "model", None), P(None, None, "model", None)),
        check_vma=False,
    )(q, k_new, v_new, cache["k"], cache["v"], jnp.asarray(pos, jnp.int32))
    return out, {"k": ck, "v": cv}


def self_attention_decode(
    cfg,
    p,
    x: jax.Array,
    cache: Dict[str, jax.Array],
    pos,
    *,
    shard: Sharder = NULL_SHARDER,
    window: Optional[int] = None,
):
    """One-token decode. x: (B, 1, D); cache k/v: (B, Hkv, S, Dh); pos traced.

    For windowed attention the cache is a ring buffer of size >= window: we write
    at pos % S and attend with absolute positions reconstructed from the ring.
    """
    b, _, d = x.shape
    s_len = cache["k"].shape[2]
    q, k, v = _project_qkv(cfg, p, x)
    posv = jnp.reshape(jnp.asarray(pos, jnp.int32), (1,))
    q = apply_rope(q, posv, cfg.rope_theta)
    k = apply_rope(k, posv, cfg.rope_theta)
    # distributed flash-decode when the cache's seq dim is sharded over "model"
    mesh = getattr(shard, "mesh", None)
    if (
        window is None
        and mesh is not None
        and "model" in mesh.shape
        and mesh.shape["model"] > 1
        and shard.rules is not None
        and shard.rules.rules.get("kv_seq") == "model"
        and s_len % mesh.shape["model"] == 0
    ):
        out, cache = _decode_attention_seq_sharded(cfg, q, k, v, cache, pos, mesh)
        return _out_proj(p, out, x.dtype), cache
    slot = jnp.asarray(pos, jnp.int32) % s_len  # ring for windowed; == pos otherwise
    ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, 0, slot, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, 0, slot, 0))
    if window is None:
        out = ops.decode_attention(q, ck, cv, pos, impl="jnp")
    else:
        # ring-buffer decode: positions of slot i is reconstructed; mask outside window
        # absolute position of ring slot i: pos - ((slot - i) mod S)
        idx = jnp.arange(s_len)
        abs_pos = pos - ((slot - idx) % s_len)
        live = (abs_pos >= jnp.maximum(pos - window + 1, 0)) & (abs_pos <= pos)
        qf = q.astype(jnp.float32)
        scale = 1.0 / jnp.sqrt(jnp.asarray(cfg.head_dim, jnp.float32))
        group = cfg.n_heads // cfg.n_kv_heads
        kf = jnp.repeat(ck.astype(jnp.float32), group, axis=1)
        vf = jnp.repeat(cv.astype(jnp.float32), group, axis=1)
        sL = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * scale
        sL = jnp.where(live[None, None, None, :], sL, -1e30)
        pr = jax.nn.softmax(sL, axis=-1)
        out = jnp.einsum("bhqk,bhkd->bhqd", pr, vf).astype(x.dtype)
    y = _out_proj(p, out, x.dtype)
    return y, {"k": ck, "v": cv}


def _append_tokens(pool, tok, page, slot):
    """Scatter one token per batch row into its (page, slot) of a
    (num_pages, Hkv, ps, Dh) pool; tok: (B, Hkv, Dh), page/slot: (B,) int32.

    Each (row, head) writes one (Dh) window. A (Hkv, Dh) window per row would
    make XLA lay the pool out with the head axis inside the slot axis, and the
    paged kernels' layout would then cost a copy of the whole pool."""
    heads = jnp.arange(pool.shape[1])[None, :]
    return pool.at[page[:, None], heads, slot[:, None], :].set(tok.astype(pool.dtype))


def _quant_append(buf, tok, page, slot, spec):
    """Scatter one quantized token per batch row into its (page, slot).

    buf: {"q": (num_pages, Hkv, ps, Dq), "scale": (num_pages, Hkv)};
    tok: (B, Hkv, Dh) f32; page/slot: (B,) int32. Scale policy (kvquant §scale
    lifecycle): slot 0 means the page is brand new (decode just crossed a page
    boundary), so it takes a fresh per-head scale from the token; otherwise the
    token re-quantizes with the page's EXISTING scale, clipped — the
    QuantizedAccessor.store law. Inactive rows target the reserved null page;
    their writes (bytes and scale) land there harmlessly, like the f32 path."""
    fresh = (slot == 0)[:, None]                       # (B, 1)
    scale = jnp.where(fresh, spec.token_scale(tok), buf["scale"][page])  # (B, Hkv)
    qtok = spec.quantize_tokens(tok, scale)            # (B, Hkv, Dq)
    return {
        "q": _append_tokens(buf["q"], qtok, page, slot),
        "scale": buf["scale"].at[page].set(scale),
    }


def self_attention_decode_paged(
    cfg,
    p,
    x: jax.Array,
    cache: Dict[str, jax.Array],
    block_tables: jax.Array,
    context_lens: jax.Array,
    *,
    shard: Sharder = NULL_SHARDER,
    impl: str = "auto",
    kv_spec=None,
    block_pages: int | None = None,
):
    """One-token decode against a paged KV pool (the LayoutPaged cache adapter).

    x: (B, 1, D); cache k/v: (num_pages, Hkv, ps, Dh) — one layer's page pool;
    block_tables: (B, max_pages) int32 (rows shared by all layers);
    context_lens: (B,) int32 tokens already cached per sequence — the new token
    is written at position context_lens[b], i.e. page block_tables[b, len//ps]
    slot len % ps, exactly LayoutPaged's index->offset map. Unlike the dense
    decode path, every batch row has its OWN position (continuous batching).

    ``kv_spec`` (PagedQuantSpec) switches the pool to the quantized element
    representation: cache k/v are then {"q", "scale"} pytrees, the append
    quantizes at scatter time, and attention runs the dequantizing kernel (or
    its jnp twin) — same layout, same block tables, different accessor.

    ``block_pages`` is the autotuned kernel block-shape knob, forwarded
    verbatim to ops.paged_decode_attention{,_quant} (None = unblocked).

    Single-host path: ``shard`` is accepted for API symmetry with
    self_attention_decode but no mesh-aware variant exists yet — on a mesh the
    page pool replicates (multi-host paging is a ROADMAP open item).
    """
    b, _, d = x.shape
    ps = cache["k"]["q"].shape[2] if kv_spec is not None else cache["k"].shape[2]
    q, k, v = _project_qkv(cfg, p, x)
    pos = jnp.asarray(context_lens, jnp.int32)  # (B,)
    q = apply_rope(q, pos[:, None], cfg.rope_theta)
    k = apply_rope(k, pos[:, None], cfg.rope_theta)
    page = block_tables[jnp.arange(b), pos // ps]  # (B,)
    slot = pos % ps
    if kv_spec is not None:
        ck = _quant_append(cache["k"], k[:, :, 0, :], page, slot, kv_spec)
        cv = _quant_append(cache["v"], v[:, :, 0, :], page, slot, kv_spec)
        out = ops.paged_decode_attention_quant(
            q, ck["q"], ck["scale"], cv["q"], cv["scale"], block_tables, pos + 1,
            bits=kv_spec.bits, block_pages=block_pages, impl=impl,
        )
    else:
        ck = _append_tokens(cache["k"], k[:, :, 0, :], page, slot)
        cv = _append_tokens(cache["v"], v[:, :, 0, :], page, slot)
        out = ops.paged_decode_attention(
            q, ck, cv, block_tables, pos + 1, block_pages=block_pages, impl=impl
        )
    y = _out_proj(p, out, x.dtype)
    return y, {"k": ck, "v": cv}


def self_attention_verify_paged(
    cfg,
    p,
    x: jax.Array,
    cache: Dict[str, jax.Array],
    block_tables: jax.Array,
    context_lens: jax.Array,
    *,
    shard: Sharder = NULL_SHARDER,
    impl: str = "auto",
    kv_spec=None,
):
    """Speculative VERIFY: score C = K+1 tokens per row in ONE chunk-style call.

    x: (B, C, D) embeddings of [current token, draft_1..draft_K];
    context_lens: (B,) tokens already resident per row. Token j of the present
    lands at position lens+j through the SAME per-token append law the decode
    path uses — a static sequential loop, because the quantized scale lifecycle
    (_quant_append: fresh scale at slot 0, existing scale otherwise) is
    order-dependent within a page. The present K/V are then gathered BACK from
    the pool (dequantized under ``kv_spec``, pool dtype otherwise) so each
    draft row attends exactly the bytes a sequential one-token decode would
    have read, and a single chunk-attention call with cursors = context_lens
    scores all C rows against past + causal present. Rejected suffixes need no
    undo here: positions ≥ the accepted length are dead under the rolled-back
    ``lens`` and are overwritten by later appends (rollback is lens
    arithmetic, not page surgery).

    Unlike the prefill chunk path, C is NOT page-aligned and the writes are
    per-token scatters, not whole-page encodes — drafts start mid-page.
    Inactive rows (nulled tables/lens) write into the reserved null page.
    """
    b, c, d = x.shape
    ps = cache["k"]["q"].shape[2] if kv_spec is not None else cache["k"].shape[2]
    q, k, v = _project_qkv(cfg, p, x)  # (B, H, C, Dh)
    lens = jnp.asarray(context_lens, jnp.int32)
    pos = lens[:, None] + jnp.arange(c)[None, :]  # (B, C)
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)
    rows = jnp.arange(b)
    ck, cv = cache["k"], cache["v"]
    pages, slots = [], []
    for j in range(c):
        pj = pos[:, j]
        page = block_tables[rows, pj // ps]  # (B,)
        slot = pj % ps
        pages.append(page)
        slots.append(slot)
        if kv_spec is not None:
            ck = _quant_append(ck, k[:, :, j, :], page, slot, kv_spec)
            cv = _quant_append(cv, v[:, :, j, :], page, slot, kv_spec)
        else:
            ck = _append_tokens(ck, k[:, :, j, :], page, slot)
            cv = _append_tokens(cv, v[:, :, j, :], page, slot)
    # gather the present back from the pool: draft rows must attend the bytes
    # a sequential decode would read (pool dtype / page-scale dequant), not
    # the fresh f32 projections — greedy exactness depends on it
    # one (Dh) window per (row, token, head), like the appends
    pg = jnp.stack(pages, axis=1)[..., None]  # (B, C, 1)
    sl = jnp.stack(slots, axis=1)[..., None]
    heads = jnp.arange(k.shape[1])
    if kv_spec is not None:
        ks = ck["scale"][pg, heads]  # (B, C, Hkv)
        vs = cv["scale"][pg, heads]
        k_pres = kv_spec.decode_pages(ck["q"][pg, heads, sl, :][:, :, :, None, :], ks)[..., 0, :]
        v_pres = kv_spec.decode_pages(cv["q"][pg, heads, sl, :][:, :, :, None, :], vs)[..., 0, :]
    else:
        k_pres = ck[pg, heads, sl, :].astype(jnp.float32)  # (B, C, Hkv, Dh)
        v_pres = cv[pg, heads, sl, :].astype(jnp.float32)
    k_pres = jnp.swapaxes(k_pres, 1, 2)  # (B, Hkv, C, Dh)
    v_pres = jnp.swapaxes(v_pres, 1, 2)
    if kv_spec is not None:
        out = ops.paged_prefill_chunk_attention_quant(
            q, k_pres, v_pres, ck["q"], ck["scale"], cv["q"], cv["scale"],
            block_tables, lens, bits=kv_spec.bits, impl=impl,
        )
    else:
        out = ops.paged_prefill_chunk_attention(
            q, k_pres, v_pres, ck, cv, block_tables, lens, impl=impl
        )
    y = _out_proj(p, out, x.dtype)
    return y, {"k": ck, "v": cv}


def _scatter_chunk_pages(cache, kp, vp, dest, kv_spec):
    """Scatter whole chunk pages into the pool. kp/vp: (B, nP, Hkv, ps, Dh) page-
    factored chunk KV; dest: (B, nP) physical destinations (invalid entries
    already routed to the null page). Quantized pools encode one fresh scale
    per (page, head) from the page's own absmax — exactly pack_kv_pages_quant's
    law, so a chunk-written page is bit-compatible with a monolithic-prefill
    one and the prefix index may dedupe across the two regimes.

    Quantized codes are written one (Dq) token row per (page, head, slot):
    int4's packed rows are 64 bytes, and a whole-page window would make XLA
    keep the pool with the slot axis innermost, so the kernels' layout would
    cost a copy of the whole pool in every layer."""
    b, npg = dest.shape
    flat = dest.reshape(-1)
    if kv_spec is not None:
        kq, vq = kv_spec.encode_pages(kp), kv_spec.encode_pages(vp)
        hkv, ps, dq = kq["q"].shape[2:]
        rows = (flat[:, None, None], jnp.arange(hkv)[None, :, None],
                jnp.arange(ps)[None, None, :])
        ck = {
            "q": cache["k"]["q"].at[rows].set(kq["q"].reshape(b * npg, hkv, ps, dq)),
            "scale": cache["k"]["scale"].at[flat].set(kq["scale"].reshape(b * npg, hkv)),
        }
        cv = {
            "q": cache["v"]["q"].at[rows].set(vq["q"].reshape(b * npg, hkv, ps, dq)),
            "scale": cache["v"]["scale"].at[flat].set(vq["scale"].reshape(b * npg, hkv)),
        }
        return ck, cv
    hkv, ps, dh = kp.shape[2:]
    ck = cache["k"].at[flat].set(kp.reshape(b * npg, hkv, ps, dh).astype(cache["k"].dtype))
    cv = cache["v"].at[flat].set(vp.reshape(b * npg, hkv, ps, dh).astype(cache["v"].dtype))
    return ck, cv


def self_attention_prefill_chunk_paged(
    cfg,
    p,
    x: jax.Array,
    cache: Dict[str, jax.Array],
    block_tables: jax.Array,
    write_tables: jax.Array,
    cursors: jax.Array,
    n_new: jax.Array,
    *,
    shard: Sharder = NULL_SHARDER,
    impl: str = "auto",
    kv_spec=None,
    null_page=0,
):
    """One prefill CHUNK against a paged KV pool — the mixed-step prefill half.

    x: (B, C, D) the chunk's token embeddings (C a page multiple, the engine's
    chunk bucket); block_tables: (B, max_pages) the READ view (every resident
    page, shared ones included); write_tables: the WRITE view — same rows with
    non-writable entries (adopted shared-prefix pages, slots past the
    allocation) nulled to page 0, so the scatter of a chunk that overlaps a
    shared prefix lands harmlessly while its reads still see the donor's KV.
    cursors: (B,) int32 page-aligned count of tokens resident before this
    chunk; n_new: (B,) int32 valid new tokens this chunk contributes (a page
    multiple; positions past it are pad whose KV routes to the null page).

    This is the chunk-view path: the unit of work is formally the submdspan
    ``[cursors, cursors + n_new)`` of the sequence's paged cache view
    (core/submdspan.py §chunk views), executed as: scatter the chunk's KV into
    its own pages, then attend Q rows against everything resident with causal
    masking across the chunk boundary. ``kv_spec`` swaps in the quantized
    accessor exactly as in the decode path. ``null_page`` is where the
    chunk-bucket pad pages land: the pool's own null page (``l·P`` when the
    pool is a layer stack's flat page space, Model.decode_step_paged).
    """
    b, c, d = x.shape
    ps = cache["k"]["q"].shape[2] if kv_spec is not None else cache["k"].shape[2]
    npg = c // ps
    max_pages = block_tables.shape[1]
    q, k, v = _project_qkv(cfg, p, x)  # (B, H, C, Dh)
    pos = cursors[:, None] + jnp.arange(c)[None, :]  # (B, C)
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)
    hkv, dh = k.shape[1], k.shape[3]
    # page-factor the chunk KV: (B, Hkv, C, Dh) -> (B, nP, Hkv, ps, Dh)
    kp = jnp.swapaxes(k.reshape(b, hkv, npg, ps, dh), 1, 2)
    vp = jnp.swapaxes(v.reshape(b, hkv, npg, ps, dh), 1, 2)
    # destination pages: the chunk's logical pages through the WRITE table;
    # pages past n_new (chunk-bucket pad) go to the null page
    logical = cursors[:, None] // ps + jnp.arange(npg)[None, :]  # (B, nP)
    gathered = jnp.take_along_axis(
        write_tables, jnp.clip(logical, 0, max_pages - 1), axis=1
    )
    valid = jnp.arange(npg)[None, :] * ps < n_new[:, None]
    dest = jnp.where(valid, gathered, null_page)
    ck, cv = _scatter_chunk_pages(cache, kp, vp, dest, kv_spec)
    # attention: past from the pool (positions < cursor), present from the
    # chunk's own f32 k/v — the scattered pages never feed back into their own
    # chunk's attention, so intra-chunk math matches monolithic prefill even
    # over quantized pools
    if kv_spec is not None:
        out = ops.paged_prefill_chunk_attention_quant(
            q, k, v, ck["q"], ck["scale"], cv["q"], cv["scale"], block_tables,
            cursors, bits=kv_spec.bits, impl=impl,
        )
    else:
        out = ops.paged_prefill_chunk_attention(
            q, k, v, ck, cv, block_tables, cursors, impl=impl
        )
    y = _out_proj(p, out, x.dtype)
    return y, {"k": ck, "v": cv}


# ---------------------------------------------------------------------------------
# cross-attention paths (whisper decoder, vlm image layers)
# ---------------------------------------------------------------------------------
def cross_attention(cfg, p, x: jax.Array, ctx: jax.Array, *, shard=NULL_SHARDER,
                    return_kv: bool = False):
    """x: (B, T, D) queries; ctx: (B, Tc, D) keys/values (no RoPE on cross)."""
    q, k, v = _project_qkv(cfg, p, x, ctx=ctx)
    out = ops.attention(q, k, v, causal=False, impl="jnp")
    y = _out_proj(p, out, x.dtype)
    if return_kv:
        return y, (k, v)
    return y


def cross_attention_decode(cfg, p, x: jax.Array, kv: Tuple[jax.Array, jax.Array]):
    """Decode-time cross-attention against precomputed context KV."""
    q = jnp.einsum("btd,dhk->bhtk", x, p["wq"].astype(x.dtype))
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)[None, :, None, :]
    k, v = kv
    out = ops.attention(q, k.astype(x.dtype), v.astype(x.dtype), causal=False, impl="jnp")
    return _out_proj(p, out, x.dtype)
