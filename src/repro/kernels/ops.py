"""ops — the jit'd public API over the kernel package, with mdspan-driven dispatch.

This is where the paper's customization points become *dispatch*: the layout and
accessor of an MdSpan/TensorSpec select the kernel schedule at trace time.

  matmul(x, w)            w may be dense (jnp.dot) or quantized buffers
                          ({"q","scale"} from quantize_array) → quant_matmul kernel
                          (or its jnp twin off-TPU).
  attention(...)          train: differentiable blocked-jnp twin; serve: Pallas
                          flash kernel on TPU (jnp twin elsewhere so compiled cost
                          analysis reflects the algorithm, DESIGN.md §2).
  sum3d/matvec/...        paper-suite entries dispatching on span.layout.

Every kernel has a jnp twin of IDENTICAL semantics; `impl="pallas"|"jnp"|"auto"`
overrides for tests and benchmarks.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.accessors import QuantizedAccessor
from repro.core.distributed import dequantize_array
from repro.core.layouts import LayoutLeft, LayoutRight

from . import paged_attention as _paged_attention
from . import ref
from .common import use_interpret
from .flash_attention import flash_attention as _flash_fwd
from .flash_attention import flash_decode as _flash_decode
from .paged_attention import paged_decode_attention_jnp as _paged_decode_jnp
from .paged_attention import paged_decode_attention_quant_jnp as _paged_decode_quant_jnp
from .paged_attention import paged_flash_decode as _paged_flash_decode
from .paged_attention import paged_flash_decode_quant as _paged_flash_decode_quant
from .paged_attention import paged_flash_prefill_chunk as _paged_flash_chunk
from .paged_attention import paged_flash_prefill_chunk_quant as _paged_flash_chunk_quant
from .paged_attention import paged_prefill_chunk_jnp as _paged_chunk_jnp
from .paged_attention import paged_prefill_chunk_quant_jnp as _paged_chunk_quant_jnp
from .matvec import matvec_left, matvec_right
from .quant_matmul import quant_matmul as _qmm_pallas
from .ssd_scan import ssd_scan as _ssd_pallas
from .stencil3d import stencil3d_pallas
from .sum3d import sum3d_mdspan
from .tinymatsum import tinymatsum_dynamic, tinymatsum_static


def _want_pallas(impl: str) -> bool:
    if impl == "pallas":
        return True
    if impl == "jnp":
        return False
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------------
# matmul with accessor dispatch
# ---------------------------------------------------------------------------------
def matmul(x: jax.Array, w, accessor: Optional[QuantizedAccessor] = None, *, impl: str = "auto"):
    """x: (..., K); w: dense (K, N) array OR quantized buffers {"q","scale"}.

    Quantized path: scales are per-(K-block, N) as produced by
    ``quantize_array(wT_blocked...)`` — see models/layers.py:QuantLinear.
    """
    if isinstance(w, dict):  # quantized buffers
        assert accessor is not None
        lead = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1])
        if _want_pallas(impl):
            y = _qmm_pallas(x2, w["q"], w["scale"], bits=accessor.bits)
        else:
            y = ref.quant_matmul(x2, w["q"], w["scale"], bits=accessor.bits)
        return y.reshape(*lead, y.shape[-1])
    return jnp.matmul(x, w)


# ---------------------------------------------------------------------------------
# attention — blocked-jnp twin (differentiable, remat-friendly) + pallas fast path
# ---------------------------------------------------------------------------------
def attention_jnp(
    q, k, v, *, causal=True, window=None, q_offset=0, scale=None, block_k: int = 512
):
    """Blocked online-softmax attention in pure jnp — semantics == ref.attention,
    memory O(Tq·Tk_block). Differentiable; used for train_step and for dry-runs."""
    b, hq, tq, d = q.shape
    _, hkv, tk, _ = k.shape
    group = hq // hkv
    import numpy as np

    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    nblk = -(-tk // block_k)
    pad = nblk * block_k - tk
    if pad:
        kf = jnp.pad(kf, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kf = kf.reshape(b, hkv, nblk, block_k, d)
    vf = vf.reshape(b, hkv, nblk, block_k, d)
    q_pos = jnp.arange(tq)[:, None] + q_offset

    def body(carry, blk):
        m, l, acc = carry
        kb, vb, ki = blk
        kb = jnp.repeat(kb, group, axis=1)  # (b, hq, bk, d)
        vb = jnp.repeat(vb, group, axis=1)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kb)
        k_pos = ki * block_k + jnp.arange(block_k)[None, :]
        live = k_pos < tk
        if causal:
            live = live & (k_pos <= q_pos)
        if window is not None:
            live = live & (k_pos > q_pos - window)
        s = jnp.where(live[None, None], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("bhqk,bhkd->bhqd", p, vb)
        return (m_new, l, acc), None

    m0 = jnp.full((b, hq, tq, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((b, hq, tq, 1), jnp.float32)
    a0 = jnp.zeros((b, hq, tq, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body,
        (m0, l0, a0),
        (jnp.moveaxis(kf, 2, 0), jnp.moveaxis(vf, 2, 0), jnp.arange(nblk)),
    )
    return (acc / jnp.where(l == 0, 1.0, l)).astype(q.dtype)


def attention(
    q, k, v, *, causal=True, window=None, q_offset=0, scale=None, impl: str = "auto"
):
    if _want_pallas(impl):
        return _flash_fwd(
            q, k, v, causal=causal, window=window, q_offset=q_offset, scale=scale
        )
    # differentiable flash twin with the hand-written O(T·D)-residual VJP
    from .flash_vjp import flash_attention_jnp

    return flash_attention_jnp(
        q, k, v, jnp.asarray(q_offset, jnp.int32), causal, window, scale
    )


def decode_attention(q, k_cache, v_cache, pos, *, window=None, scale=None, impl: str = "auto"):
    """One-token GQA decode against a (B, Hkv, S, D) cache; ``pos`` traced."""
    if _want_pallas(impl):
        return _flash_decode(q, k_cache, v_cache, pos, window=window, scale=scale)
    # jnp twin: mask by absolute position (identical semantics to the kernel)
    return attention_jnp(
        q, k_cache, v_cache, causal=True, window=window, q_offset=pos, scale=scale
    )


def effective_block_pages(block_pages, max_pages: int) -> int:
    """Sanitize the decode block-shape knob against a table width.

    Returns the largest divisor of ``max_pages`` that is <= ``block_pages``
    (the Pallas grid needs an exact factorization of the page axis), or 1 when
    the knob is unset (None/0) — 1 reproduces the pre-knob schedule exactly.
    Tuned values therefore degrade gracefully when an engine is sized with a
    different max_pages than the sweep used.
    """
    if not block_pages or max_pages <= 0:
        return 1
    bp = min(int(block_pages), max_pages)
    while max_pages % bp:
        bp -= 1
    return bp


def paged_decode_attention(
    q, k_pool, v_pool, block_tables, context_lens, *, scale=None,
    block_pages=None, window=None, impl: str = "auto",
):
    """One-token GQA decode against a LayoutPaged pool (num_pages, Hkv, ps, D);
    block_tables (B, max_pages) int32; context_lens (B,) int32 per-sequence.
    ``block_pages`` (pages per compute block; autotuned) is sanitized here via
    effective_block_pages, so callers pass the tuned value verbatim.
    ``window``: a sliding-window layer reads only its row's window pages."""
    bp = effective_block_pages(block_pages, block_tables.shape[1])
    if _want_pallas(impl):
        return _paged_flash_decode(
            q, k_pool, v_pool, block_tables, context_lens, scale=scale,
            block_pages=bp, window=window,
        )
    return _paged_decode_jnp(
        q, k_pool, v_pool, block_tables, context_lens, scale=scale,
        block_pages=bp if bp > 1 else None, window=window,
    )


def _tile(n: int, cap: int) -> int:
    """The largest multiple of 128 that divides ``n`` and is at most ``cap``
    (``n`` itself when none does)."""
    for t in range(cap - cap % 128, 0, -128):
        if n % t == 0:
            return t
    return n


def grouped_matmul(rows, w, group_sizes, *, layer=None, impl: str = "auto"):
    """(M, K) rows sorted by group, times each group's (K, N) matrix ->
    (M, N) float32; rows past the groups' sum are left undefined. ``w`` is
    (G, K, N), or a stack (L, G, K, N) with ``layer`` (traced) picking one.

    On the TPU: megablox's grouped matmul (Pallas), whose grid visits only
    the row tiles of non-empty groups and whose index map takes each group's
    weight tiles from ``w``; a stack is handed over whole, as L·G groups of
    which only ``layer``'s are non-empty, so no layer's weights are copied
    out of it. Elsewhere: ``lax.ragged_dot``."""
    if not _want_pallas(impl):
        if layer is not None:
            w = jax.lax.dynamic_index_in_dim(w, layer, keepdims=False)
        return jax.lax.ragged_dot(rows, w, group_sizes,
                                  preferred_element_type=jnp.float32)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    g = w.shape[-3]
    if layer is not None:
        group_sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((w.shape[0] * g,), jnp.int32), group_sizes, (layer * g,))
        w = w.reshape((-1,) + w.shape[2:])
    m, k, n = rows.shape[0], w.shape[1], w.shape[2]
    tm = 16 if m <= 512 else 128  # decode: a few rows a group; chunks: ~64
    pad = -m % tm
    lhs = jnp.pad(rows, ((0, pad), (0, 0))) if pad else rows
    # the paged kernels' interpreter switch (one switch for the serving step)
    out = gmm(lhs, w, group_sizes, preferred_element_type=jnp.float32,
              tiling=(tm, _tile(k, 1152), _tile(n, 1152)),
              interpret=_paged_attention.use_interpret())
    return out[:m]


def paged_decode_attention_quant(
    q, k_q, k_scale, v_q, v_scale, block_tables, context_lens, *,
    bits: int = 8, scale=None, block_pages=None, impl: str = "auto",
):
    """One-token GQA decode against a QUANTIZED LayoutPaged pool: intN page
    bytes (num_pages, Hkv, ps, Dq) + per-(page, head) f32 scales (num_pages,
    Hkv) — the accessor customization point (PagedQuantSpec) composed with the
    layout one. Same block-table/length/block_pages contract as
    paged_decode_attention."""
    bp = effective_block_pages(block_pages, block_tables.shape[1])
    if _want_pallas(impl):
        return _paged_flash_decode_quant(
            q, k_q, k_scale, v_q, v_scale, block_tables, context_lens,
            bits=bits, scale=scale, block_pages=bp,
        )
    return _paged_decode_quant_jnp(
        q, k_q, k_scale, v_q, v_scale, block_tables, context_lens,
        bits=bits, scale=scale, block_pages=bp if bp > 1 else None,
    )


def paged_prefill_chunk_attention(
    q, chunk_k, chunk_v, k_pool, v_pool, block_tables, cursors, *,
    scale=None, window=None, impl: str = "auto",
):
    """Chunked-prefill GQA attention: a Q-chunk (B, Hq, C, D) against the
    resident PAST (pool positions < cursors[b], read through the block table)
    plus its own PRESENT (chunk_k/chunk_v, (B, Hkv, C, D) f32, intra-chunk
    causal) — one online softmax across both. The C == 1 case is
    paged_decode_attention; this is the mixed-step prefill half. ``window``
    limits each query to the keys within it of its position."""
    if _want_pallas(impl):
        return _paged_flash_chunk(
            q, chunk_k, chunk_v, k_pool, v_pool, block_tables, cursors,
            scale=scale, window=window,
        )
    return _paged_chunk_jnp(
        q, chunk_k, chunk_v, k_pool, v_pool, block_tables, cursors, scale=scale,
        window=window,
    )


def paged_prefill_chunk_attention_quant(
    q, chunk_k, chunk_v, k_q, k_scale, v_q, v_scale, block_tables, cursors, *,
    bits: int = 8, scale=None, impl: str = "auto",
):
    """paged_prefill_chunk_attention over an intN paged pool (PagedQuantSpec):
    the past dequantizes in-kernel; the present (the chunk's own K/V) stays
    f32, so only CROSS-chunk attention pays the representation."""
    if _want_pallas(impl):
        return _paged_flash_chunk_quant(
            q, chunk_k, chunk_v, k_q, k_scale, v_q, v_scale, block_tables,
            cursors, bits=bits, scale=scale,
        )
    return _paged_chunk_quant_jnp(
        q, chunk_k, chunk_v, k_q, k_scale, v_q, v_scale, block_tables, cursors,
        bits=bits, scale=scale,
    )


# ---------------------------------------------------------------------------------
# on-device token sampling (the serving hot path's logits consumer)
# ---------------------------------------------------------------------------------

# fold_in domain tags for the speculative verify op: each (slot, position) base
# key fans out into an acceptance-uniform stream and a resample-Gumbel stream.
# Disjoint from sample_tokens' key derivation (which never folds a tag), so a
# speculative engine and a non-speculative one never reuse randomness across
# semantically different draws. serving/sampling.py documents the contract.
SPEC_ACCEPT_FOLD = 0x5ACC
SPEC_RESAMPLE_FOLD = 0x5E5A


def _filter_topk_topp(x, temperature, top_k, top_p, *, vocab: int):
    """Temperature-scale + top-k/top-p filter a batch of masked logit rows.

    x: (N, Vp) f32 with pad columns already -inf; temperature/top_k/top_p: (N,).
    Returns z (N, Vp): x / max(temperature, eps) with filtered-out entries at
    -inf — the categorical distribution Gumbel-max sampling draws from. Shared
    by sample_tokens and verify_draft_tokens so the speculative accept test and
    ordinary sampling see the SAME filtered distribution (the correctness
    precondition for unbiased rejection sampling)."""
    k_eff = jnp.clip(jnp.where(top_k > 0, top_k, vocab), 1, vocab)
    x_desc = jnp.sort(x, axis=-1)[:, ::-1]
    kth = jnp.take_along_axis(x_desc, k_eff[:, None] - 1, axis=1)
    xf = jnp.where(x >= kth, x, -jnp.inf)
    # top-p over the temperature-scaled distribution of the survivors
    t = jnp.maximum(temperature, 1e-6)[:, None]
    z = xf / t
    p_eff = jnp.where(top_p > 0, top_p, 1.0)[:, None]
    z_desc = jnp.sort(z, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(z_desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) < p_eff  # mass BEFORE the token; top-1 always kept
    cutoff = jnp.min(jnp.where(keep, z_desc, jnp.inf), axis=-1, keepdims=True)
    return jnp.where(z >= cutoff, z, -jnp.inf)


def sample_tokens(logits, temperature, top_k, top_p, seed, pos, *, vocab: int,
                  mask=None):
    """Batched token selection on device: greedy / temperature / top-k / top-p.

    logits: (B, Vp) with Vp >= vocab (pad columns masked off); temperature (B,)
    f32 — 0 selects greedy argmax, EXACTLY matching host ``np.argmax`` over
    ``logits[:, :vocab]``; top_k (B,) int32 (0 = off); top_p (B,) f32 in (0, 1]
    (1 = off; non-positive values are treated as off); seed (B,) uint32 per-slot
    stream ids; pos (B,) int32 the absolute sequence index of the token being
    sampled. Returns (B,) int32 token ids.

    ``mask`` (optional, (B, vocab) f32) is an ADDITIVE logit mask applied before
    every filter and both selection paths — the constrained-decoding stage:
    grammar-disallowed tokens carry a large negative value (serving/grammar.py
    precomputes one row per grammar state on the host; the engine gathers the
    per-slot rows on device), allowed tokens carry 0, and an all-zero row is an
    exact no-op, so unconstrained slots in the same batch are unaffected. The
    mask composes BEFORE top-k/top-p: the filters then act on the constrained
    distribution, and greedy picks the best ALLOWED token.

    Determinism: the per-slot key is ``fold_in(PRNGKey(seed[b]), pos[b])`` — a
    pure function of (stream seed, position). A preempted-and-recomputed request
    therefore re-samples the identical token at every position, and two engines
    replaying the same trace agree bit-for-bit (the serving sampling contract;
    serving/sampling.py derives the stream seed).

    Filters compose in the conventional order: top-k keeps the k largest logits
    (ties at the k-th value are all kept), then top-p keeps the smallest prefix
    of the temperature-scaled distribution whose mass reaches top_p (the
    crossing token included, so at least one survives). Sampling itself is the
    Gumbel-max trick — an argmax, so the whole path stays a (B, V) map + two
    sorts with no host round-trip. When NO slot samples (all temperatures 0) a
    ``lax.cond`` skips the sort/softmax machinery at run time and the step pays
    exactly one argmax.
    """
    b, vp = logits.shape
    col = jnp.arange(vp)[None, :]
    x = jnp.where(col < vocab, logits.astype(jnp.float32), -jnp.inf)
    if mask is not None:
        # pad columns are already -inf; the mask only ever biases real tokens
        x = x + jnp.pad(mask.astype(jnp.float32), ((0, 0), (0, vp - vocab)))
    greedy = jnp.argmax(x, axis=-1).astype(jnp.int32)

    def _sampled(_):
        z = _filter_topk_topp(x, temperature, top_k, top_p, vocab=vocab)
        keys = jax.vmap(
            lambda s, p: jax.random.fold_in(jax.random.PRNGKey(s), p)
        )(seed, pos)
        g = jax.vmap(lambda key: jax.random.gumbel(key, (vp,)))(keys)
        tok = jnp.argmax(z + g, axis=-1).astype(jnp.int32)
        return jnp.where(temperature > 0, tok, greedy)

    return jax.lax.cond(
        jnp.any(temperature > 0), _sampled, lambda _: greedy, operand=None
    )


def verify_draft_tokens(logits, draft, temperature, top_k, top_p, seed, pos0,
                        active, *, vocab: int):
    """Speculative accept/resample over one verify window's logits.

    logits: (B, C, Vp) the target model's rows for present positions
    lens..lens+K (C = K+1; row j predicts the token at absolute position
    pos0[b]+j where pos0 = lens+1); draft: (B, K) proposed tokens (clipped to
    the vocab here — a garbage proposal can only be rejected, never crash);
    temperature/top_k/top_p/seed: (B,) per-slot sampling state (the same packed
    rows sample_tokens consumes); active: (B,) phase bitmap.

    Returns (tokens_out (B, C) int32, committed (B,) int32, chosen_lp (B, C)
    f32): committed[b] = n_acc+1 tokens of tokens_out[b] are final — n_acc
    accepted draft tokens followed by one correction (first rejection) or
    bonus (all accepted) token. chosen_lp is the UNMASKED model log-prob of
    every tokens_out entry (rows past committed are dead — the caller's lens
    arithmetic never exposes them). Inactive rows commit 0.

    Greedy rows (temperature == 0): tokens_out = argmax per row and
    accept_j ⇔ argmax_j == draft_j, which makes the committed stream
    token-IDENTICAL to a one-token-at-a-time greedy decode — the correctness
    law CI pins. Sampled rows run textbook rejection sampling against the
    deterministic draft: accept d_j with prob p_j(d_j) under the SAME
    filtered/scaled distribution sample_tokens uses (_filter_topk_topp); on
    the first rejection resample from that distribution with the rejected
    token masked out (the residual max(0, p - q) for a one-point q), and when
    every draft survives the bonus row draws unconditionally. Keys derive from
    fold_in(PRNGKey(seed), pos0+j) + a domain tag (SPEC_ACCEPT_FOLD /
    SPEC_RESAMPLE_FOLD), so a given (stream, position) always consumes the
    same randomness — preemption-recompute reproducibility, same law as
    sample_tokens (though the speculative sampled stream intentionally differs
    from the non-speculative one: only GREEDY promises cross-path exactness).
    """
    b, c, vp = logits.shape
    k = c - 1
    col = jnp.arange(vp)[None, None, :]
    x = jnp.where(col < vocab, logits.astype(jnp.float32), -jnp.inf)
    greedy = jnp.argmax(x, axis=-1).astype(jnp.int32)  # (B, C)
    draft = jnp.clip(draft.astype(jnp.int32), 0, vocab - 1)
    acc_greedy = greedy[:, :k] == draft  # (B, K)

    def _sampled(_):
        z = _filter_topk_topp(
            x.reshape(b * c, vp), jnp.repeat(temperature, c),
            jnp.repeat(top_k, c), jnp.repeat(top_p, c), vocab=vocab,
        ).reshape(b, c, vp)
        pos = pos0[:, None] + jnp.arange(c)[None, :]  # (B, C)
        base = jax.vmap(jax.vmap(
            lambda s, p: jax.random.fold_in(jax.random.PRNGKey(s), p),
            in_axes=(None, 0)), in_axes=(0, 0))(seed, pos)
        u = jax.vmap(jax.vmap(
            lambda kk: jax.random.uniform(jax.random.fold_in(kk, SPEC_ACCEPT_FOLD))
        ))(base)  # (B, C)
        g = jax.vmap(jax.vmap(
            lambda kk: jax.random.gumbel(
                jax.random.fold_in(kk, SPEC_RESAMPLE_FOLD), (vp,))
        ))(base)  # (B, C, Vp)
        probs = jax.nn.softmax(z, axis=-1)
        p_draft = jnp.take_along_axis(probs[:, :k], draft[:, :, None], axis=-1)[..., 0]
        acc = u[:, :k] < p_draft  # (B, K)
        # resample with the rejected draft token excluded; the bonus row
        # (j == K) has no draft and samples from the full distribution
        rb = jnp.arange(b)[:, None]
        rj = jnp.arange(k)[None, :]
        zm = z.at[rb, rj, draft].set(-jnp.inf)
        resamp = jnp.argmax(zm + g, axis=-1).astype(jnp.int32)  # (B, C)
        acc_f = jnp.concatenate([acc, jnp.zeros((b, 1), bool)], axis=1)
        draft_f = jnp.concatenate([draft, jnp.zeros((b, 1), jnp.int32)], axis=1)
        tok = jnp.where(acc_f, draft_f, resamp)
        samp = (temperature > 0)
        return (jnp.where(samp[:, None], tok, greedy),
                jnp.where(samp[:, None], acc, acc_greedy))

    tokens_out, accept = jax.lax.cond(
        jnp.any(temperature > 0), _sampled,
        lambda _: (greedy, acc_greedy), operand=None,
    )
    n_acc = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), axis=1), axis=1)
    committed = jnp.where(active > 0, n_acc + 1, 0).astype(jnp.int32)
    lp = jax.nn.log_softmax(logits[..., :vocab].astype(jnp.float32), axis=-1)
    chosen_lp = jnp.take_along_axis(lp, tokens_out[..., None], axis=-1)[..., 0]
    return tokens_out, committed, chosen_lp


# ---------------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------------
def ssd_jnp(
    x, dt, A, B, C, *, chunk=64, initial_state=None, return_final_state=False
):
    """Chunked SSD in pure jnp (differentiable twin of the Pallas kernel; same
    chunked math, scan over chunks)."""
    b, t, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    rep = h // g
    assert t % chunk == 0
    nc = t // chunk
    xf = x.astype(jnp.float32).reshape(b, nc, chunk, h, p)
    dtf = dt.astype(jnp.float32).reshape(b, nc, chunk, h)
    Bf = jnp.repeat(B.astype(jnp.float32), rep, axis=2).reshape(b, nc, chunk, h, n)
    Cf = jnp.repeat(C.astype(jnp.float32), rep, axis=2).reshape(b, nc, chunk, h, n)
    Af = A.astype(jnp.float32)

    def chunk_step(S, blk):
        xq, dtq, Bq, Cq = blk  # (b, Q, h, p), (b, Q, h), (b, Q, h, n) ×2
        lam = dtq * Af[None, None, :]
        s = jnp.cumsum(lam, axis=1)  # (b, Q, h)
        y_inter = jnp.einsum("bqhn,bhpn->bqhp", Cq, S) * jnp.exp(s)[..., None]
        cb = jnp.einsum("bqhn,buhn->bhqu", Cq, Bq)
        seg = s[:, :, None, :] - s[:, None, :, :]  # (b, t, u, h)
        q_ = xq.shape[1]
        tri = jnp.tril(jnp.ones((q_, q_), jnp.float32))
        m = (
            cb
            * jnp.exp(jnp.minimum(jnp.moveaxis(seg, 3, 1), 0.0))
            * jnp.moveaxis(dtq, 2, 1)[:, :, None, :]
            * tri[None, None]
        )  # (b, h, t, u)
        y_intra = jnp.einsum("bhtu,buhp->bthp", m, xq)
        w = jnp.exp(s[:, -1:, :] - s) * dtq  # (b, Q, h)
        upd = jnp.einsum("bqhp,bqhn->bhpn", xq * w[..., None], Bq)
        S = S * jnp.exp(s[:, -1])[:, :, None, None] + upd
        return S, (y_inter + y_intra).astype(x.dtype)

    S0 = (
        jnp.zeros((b, h, p, n), jnp.float32)
        if initial_state is None
        else initial_state.astype(jnp.float32)
    )
    Sf, ys = jax.lax.scan(
        chunk_step,
        S0,
        (
            jnp.moveaxis(xf, 1, 0),
            jnp.moveaxis(dtf, 1, 0),
            jnp.moveaxis(Bf, 1, 0),
            jnp.moveaxis(Cf, 1, 0),
        ),
    )
    y = jnp.moveaxis(ys, 0, 1).reshape(b, t, h, p)
    if return_final_state:
        return y, Sf
    return y


def ssd(
    x, dt, A, B, C, *, chunk=64, initial_state=None, return_final_state=False,
    impl: str = "auto",
):
    if _want_pallas(impl) and B.shape[2] == 1:
        return _ssd_pallas(
            x, dt, A, B, C, chunk=chunk, initial_state=initial_state,
            return_final_state=return_final_state,
        )
    return ssd_jnp(
        x, dt, A, B, C, chunk=chunk, initial_state=initial_state,
        return_final_state=return_final_state,
    )


def ssd_decode_step(state, xt, dtt, A, Bt, Ct):
    """Single-token SSM state update (decode). state: (b,h,p,n); xt: (b,h,p);
    dtt: (b,h); Bt/Ct: (b,g,n)."""
    b, h, p, n = state.shape
    g = Bt.shape[1]
    rep = h // g
    Bh = jnp.repeat(Bt, rep, axis=1).astype(jnp.float32)
    Ch = jnp.repeat(Ct, rep, axis=1).astype(jnp.float32)
    decay = jnp.exp(dtt.astype(jnp.float32) * A.astype(jnp.float32)[None, :])
    upd = (dtt.astype(jnp.float32)[..., None] * xt.astype(jnp.float32))[..., None] * Bh[:, :, None, :]
    state = state * decay[..., None, None] + upd
    y = jnp.einsum("bhpn,bhn->bhp", state, Ch)
    return state, y.astype(xt.dtype)


# ---------------------------------------------------------------------------------
# paper-suite dispatchers (layout-generic)
# ---------------------------------------------------------------------------------
def sum3d(span, *, impl: str = "auto"):
    from repro.core.mdspan import MdSpan

    if isinstance(span, MdSpan):
        if _want_pallas(impl) or impl == "pallas":
            return sum3d_mdspan(span)
        return ref.sum3d(span.to_dense())
    return ref.sum3d(span)


def matvec(A_span, x, *, impl: str = "auto"):
    """Layout dispatch: LayoutRight → lane-contraction kernel; LayoutLeft →
    sublane-contraction kernel (honest schedules for both, paper Fig. 6)."""
    from repro.core.mdspan import MdSpan

    if not isinstance(A_span, MdSpan):
        return ref.matvec(A_span, x)
    if not _want_pallas(impl):
        return ref.matvec(A_span.to_dense(), x)
    codo = A_span.codomain()
    if isinstance(A_span.layout, LayoutRight):
        return matvec_right(codo.reshape(A_span.shape), x)
    if isinstance(A_span.layout, LayoutLeft):
        return matvec_left(codo.reshape(A_span.shape[::-1]), x)
    return ref.matvec(A_span.to_dense(), x)


def tinymatsum(o, s, *, static_extents: bool = True, impl: str = "auto", **kw):
    if not _want_pallas(impl):
        return ref.tinymatsum(o, s)
    if static_extents:
        return tinymatsum_static(o, s, **kw)
    return tinymatsum_dynamic(o, s, **kw)


def stencil3d(x, *, impl: str = "auto", **kw):
    if not _want_pallas(impl):
        return ref.stencil3d(x)
    return stencil3d_pallas(x, **kw)
