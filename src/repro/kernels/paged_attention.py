"""Paged-attention decode — Pallas TPU kernel over a LayoutPaged KV pool.

The KV cache is a pool of fixed-size pages, (num_pages, Hkv, page_size, D), and
each sequence owns a row of a block table mapping logical page j -> physical
page id. This is core.layouts.LayoutPaged made executable: the kernel's k/v
BlockSpec index maps read the block table through scalar prefetch
(PrefetchScalarGridSpec), so the layout's index->offset indirection runs on the
scalar core while pages DMA into VMEM — no dense (B, Hkv, S, D) cache ever
materializes and pages of different sequences can live anywhere in the pool.

Per-sequence lengths (continuous batching: every row of the batch is at a
different position) ride in through the second prefetch operand and drive both
the online-softmax masking and the page skip predicate.

``paged_decode_attention_jnp`` is the identical-semantics twin (gather pages by
table, mask by length) used off-TPU and as the differentiable/cheap fallback;
both are validated against ref.attention on densified pools in
tests/test_serving_engine.py.

Both decode paths expose one block-shape knob, ``block_pages`` (pages per
compute block), picked per (model, kv_dtype, batch bucket) by
kernels/autotune.py: the Pallas grids factor their page axis into
(compute blocks, pages per block), and the jnp twin switches to a blocked
gather (lax.scan over page blocks with an online-softmax carry) so the knob
bounds its peak gathered working set. Chunked prefill has no separate knob —
its block shape IS the chunk width, already swept by the engine's
chunk-bucket machinery.

Quantized pools (the accessor axis composed with the layout axis): the
``*_quant`` variants consume int8/int4 page pools with one f32 scale per
(physical page, kv head) — serving/engine/kvquant.PagedQuantSpec's encoding.
``paged_flash_decode_quant`` DMAs int8 page tiles and their (page, head) scale
through the SAME block-table index maps as the f32 kernel (the layout is
untouched; only the element representation changed) and dequantizes in VMEM
next to the flash update. int4 pages pack two values per byte SPLIT-HALF along
the feature dim (byte d = feature d in the lo nibble, feature d + D/2 in the
hi), so in-kernel dequant is a lane concat — never an interleave — and a
single token's scatter stays nibble-local to its own (slot, :) row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import use_interpret

NEG_INF = -1e30


def window_pages(window: int, page_size: int) -> int:
    """Pages that a row's last ``window`` keys can overlap: the page axis of
    the windowed decode and chunk grids."""
    return -(-window // page_size) + 1


def _window_first(n, window: int, page_size: int):
    """Logical page of key max(0, n - window): the first page a windowed
    row of ``n`` keys reads (a chunk passes its cursor + 1)."""
    return jnp.maximum(n - window, 0) // page_size


def _window_tag(window):
    """pallas_call keywords that tag a windowed kernel in the program text."""
    return {} if window is None else {"metadata": {"window": str(window)}}


# ---------------------------------------------------------------------------------
# int4 nibble packing (split-half) + page dequantization
# ---------------------------------------------------------------------------------
def pack_int4_splithalf(q: jax.Array) -> jax.Array:
    """Pack signed int4 values (last dim even) two per byte, split-half: byte
    ``d`` holds value ``d`` in the lo nibble and value ``d + D/2`` in the hi
    nibble. Unpacking is then a lane-dim concat (TPU-cheap), and any write that
    covers a full last-dim row (a token's K/V vector) maps to whole bytes."""
    d = q.shape[-1]
    lo = q[..., : d // 2] & 0x0F
    hi = (q[..., d // 2 :] & 0x0F) << 4
    return (lo | hi).astype(jnp.int8)


def unpack_int4_splithalf(b: jax.Array) -> jax.Array:
    """Inverse of pack_int4_splithalf, as int32 values in [-8, 7]. Sign-extends
    via arithmetic shifts in int32: the TPU compiler refuses int8 shifts."""
    x = b.astype(jnp.int32)
    lo = (x << 28) >> 28
    hi = x >> 4
    return jnp.concatenate([lo, hi], axis=-1)


def dequantize_pages(q: jax.Array, scale: jax.Array, *, bits: int) -> jax.Array:
    """q: (..., page_size, Dq) intN bytes; scale: (...) f32 per (page, head).
    Returns f32 (..., page_size, D) — the decode half of PagedQuantSpec."""
    if bits == 4:
        q = unpack_int4_splithalf(q)
    return q.astype(jnp.float32) * scale[..., None, None]


def _head_scale(s_ref, h):
    """(1, 1) scale of kv head ``h`` from a page's (1, 1, Hkv) scale row. The
    row is DMA'd whole because the TPU tiling rule refuses a (1, 1) block of
    the (num_pages, Hkv) array; a lane mask selects the head."""
    row = s_ref[0]  # (1, Hkv)
    lane = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    return jnp.sum(jnp.where(lane == h, row, 0.0), axis=1, keepdims=True)


def _flash_update(q, k, v, live, acc_ref, m_ref, l_ref, *, scale):
    """One online-softmax accumulation step over a (page_size, D) K/V tile —
    shared by the f32 and the dequantizing kernels (identical math)."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (G, page_size)
    s = jnp.where(live, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new


def _paged_decode_kernel(
    bt_ref,    # scalar prefetch: (B, max_pages) int32 block table
    len_ref,   # scalar prefetch: (B,) int32 live token counts
    q_ref,     # (1, 1, G, D)
    k_ref,     # (1, page_size, D) — physical page picked by the index map
    v_ref,     # (1, page_size, D)
    o_ref,     # (1, 1, G, D)
    acc_ref,   # (G, D) f32
    m_ref,     # (G, 1) f32
    l_ref,     # (G, 1) f32
    *,
    scale: float,
    page_size: int,
    block_pages: int,
    window: int | None = None,
):
    b = pl.program_id(0)
    # the page loop is structured as (compute block jb) x (page-in-block ji):
    # the pages_per_compute_block schedule knob of production paged kernels,
    # picked per (model, kv_dtype, batch bucket) by kernels/autotune.py
    jb, ji = pl.program_id(2), pl.program_id(3)
    j = jb * block_pages + ji
    last = (jb == pl.num_programs(2) - 1) & (ji == pl.num_programs(3) - 1)
    g_sz = q_ref.shape[2]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    seq_len = len_ref[b]
    if window is not None:
        # grid step j visits the row's j-th window page (_window_first)
        j = _window_first(seq_len, window, page_size) + j
    # absolute position of slot i in logical page j is j*page_size + i
    k_pos = j * page_size + jax.lax.broadcasted_iota(jnp.int32, (g_sz, page_size), 1)
    live = k_pos < seq_len
    if window is not None:
        live = live & (k_pos >= seq_len - window)

    @pl.when(j * page_size < seq_len)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)  # (G, D)
        k = k_ref[0].astype(jnp.float32)     # (page_size, D)
        v = v_ref[0].astype(jnp.float32)
        _flash_update(q, k, v, live, acc_ref, m_ref, l_ref, scale=scale)

    @pl.when(last)
    def _finalize():
        l = l_ref[...]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)


def paged_flash_decode(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    context_lens: jax.Array,
    *,
    scale: float | None = None,
    block_pages: int = 1,
    window: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """One-token GQA decode against a paged KV pool.

    q: (B, Hq, 1, D); k_pool/v_pool: (num_pages, Hkv, page_size, D) — the
    LayoutPaged codomain factored as an ndarray (layout.pool_shape());
    block_tables: (B, max_pages) int32, row b = physical page of logical page j
    (entries past the sequence's allocation must still be valid pool indices —
    point them at a reserved null page); context_lens: (B,) int32, positions
    < context_lens[b] attend (the current token's K/V must already be written).

    ``block_pages`` (must divide max_pages; ops.effective_block_pages
    sanitizes) is the kernel's block-shape knob: the page axis of the grid is
    factored into (compute blocks, pages per block), the schedule structure
    production paged kernels use to batch page DMAs per compute block. DMA
    granularity here stays one page per grid step (scattered physical pages
    cannot share one BlockSpec window); the knob exists so configurations
    tuned on the jnp twin — where it sets the real gather granularity — carry
    through this kernel's grid unchanged.

    ``window`` (sliding-window layers): a row of length n attends to the keys
    from max(0, n - window) to its own token, and the page axis of the grid
    is ``window_pages(window, page_size)`` steps whatever ``max_pages`` is,
    the index map starting at the row's first live page; ``block_pages`` is
    then 1. The kernel is tagged ``{"window": ...}`` in the program's
    ``kernel_metadata``, so a device trace tells it from the full one.
    """
    interpret = use_interpret() if interpret is None else interpret
    b, hq, tq, d = q.shape
    num_pages, hkv, page_size, _ = k_pool.shape
    assert tq == 1 and hq % hkv == 0
    group = hq // hkv
    max_pages = block_tables.shape[1]
    bp = max(1, int(block_pages)) if window is None else 1
    if max_pages % bp:
        raise ValueError(
            f"block_pages {bp} must divide max_pages {max_pages} "
            "(ops.effective_block_pages picks a valid divisor)"
        )
    scale = float(scale) if scale is not None else 1.0 / np.sqrt(d)
    qg = q.reshape(b, hkv, group, d)

    kern = functools.partial(
        _paged_decode_kernel, scale=scale, page_size=page_size, block_pages=bp,
        window=window,
    )
    if window is None:
        n_steps = max_pages // bp
        # the LayoutPaged indirection: logical page jb*bp + ji of sequence
        # bb DMAs physical page block_tables[bb, jb*bp + ji]
        page_map = lambda bb, h, jb, ji, bt, ln: (bt[bb, jb * bp + ji], h, 0, 0)
    else:
        n_steps = window_pages(window, page_size)
        page_map = lambda bb, h, jb, ji, bt, ln: (bt[bb, jnp.minimum(
            _window_first(ln[bb], window, page_size) + jb, max_pages - 1)], h, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, n_steps, bp),
        in_specs=[
            pl.BlockSpec((1, 1, group, d), lambda bb, h, jb, ji, bt, ln: (bb, h, 0, 0)),
            pl.BlockSpec((1, None, page_size, d), page_map),
            pl.BlockSpec((1, None, page_size, d), page_map),
        ],
        out_specs=pl.BlockSpec((1, 1, group, d), lambda bb, h, jb, ji, bt, ln: (bb, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group, d), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, d), q.dtype),
        interpret=interpret,
        name="paged_decode",
        **_window_tag(window),
    )(block_tables.astype(jnp.int32), context_lens.astype(jnp.int32), qg, k_pool, v_pool)
    return out.reshape(b, hq, 1, d)


def paged_decode_attention_jnp(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    context_lens: jax.Array,
    *,
    scale: float | None = None,
    block_pages: int | None = None,
    window: int | None = None,
) -> jax.Array:
    """jnp twin: gather each sequence's pages by table, mask by length.

    Identical semantics to paged_flash_decode. With ``block_pages`` unset the
    whole table is gathered at once — O(B·max_pages·page_size) peak memory.
    With ``block_pages`` set, the gather is blocked: a lax.scan over page
    blocks of that width with an online-softmax carry, so peak gathered K/V is
    O(B·block_pages·page_size) — here the knob really is the working-set
    granularity, which is what kernels/autotune.py times. With ``window``
    only the row's ``window_pages`` window pages are gathered.
    """
    b, hq, tq, d = q.shape
    _, hkv, page_size, _ = k_pool.shape
    assert tq == 1 and hq % hkv == 0
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    max_pages = block_tables.shape[1]
    if window is not None:
        return _paged_decode_jnp_window(q, k_pool, v_pool, block_tables,
                                        context_lens, scale=scale, window=window)
    if block_pages and block_pages < max_pages:
        return _paged_decode_jnp_blocked(
            q, k_pool, v_pool, block_tables, context_lens,
            scale=scale, block_pages=int(block_pages),
        )
    # (B, max_pages, Hkv, ps, D) -> (B, Hkv, max_pages*ps, D)
    k = jnp.moveaxis(k_pool[block_tables], 2, 1)
    v = jnp.moveaxis(v_pool[block_tables], 2, 1)
    s_len = k.shape[2] * page_size
    k = k.reshape(b, hkv, s_len, d).astype(jnp.float32)
    v = v.reshape(b, hkv, s_len, d).astype(jnp.float32)
    qg = q.reshape(b, hkv, group, d).astype(jnp.float32)
    s = jnp.einsum("bhgd,bhkd->bhgk", qg, k) * scale
    live = jnp.arange(s_len)[None, :] < context_lens[:, None]  # (B, S)
    s = jnp.where(live[:, None, None, :], s, NEG_INF)
    # kernel-parity normalization: fully-masked rows (context_lens == 0) output
    # exact zeros, matching the Pallas safe_l path — not a softmax mean of garbage
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m) * live[:, None, None, :]
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhgk,bhkd->bhgd", p, v) / jnp.where(l == 0.0, 1.0, l)
    return out.reshape(b, hq, 1, d).astype(q.dtype)


def _window_table(block_tables, first, n_pages: int):
    """(B, n_pages) logical pages first..first + n_pages - 1 of each row, and
    their physical pages (past the table: its last entry, never live)."""
    logical = first[:, None] + jnp.arange(n_pages)[None, :]
    last = block_tables.shape[1] - 1
    return logical, jnp.take_along_axis(block_tables, jnp.minimum(logical, last), axis=1)


def _paged_decode_jnp_window(q, k_pool, v_pool, block_tables, context_lens, *,
                             scale: float, window: int) -> jax.Array:
    """Windowed decode twin: gather the row's window pages only, and mask
    keys outside [n - window, n)."""
    b, hq, _, d = q.shape
    _, hkv, page_size, _ = k_pool.shape
    group = hq // hkv
    n_win = window_pages(window, page_size)
    lens = context_lens.astype(jnp.int32)
    logical, table = _window_table(
        block_tables, _window_first(lens, window, page_size), n_win)
    s_len = n_win * page_size
    k = jnp.moveaxis(k_pool[table], 2, 1).reshape(b, hkv, s_len, d)
    v = jnp.moveaxis(v_pool[table], 2, 1).reshape(b, hkv, s_len, d)
    k_pos = (logical[:, :, None] * page_size
             + jnp.arange(page_size)[None, None, :]).reshape(b, s_len)
    live = (k_pos < lens[:, None]) & (k_pos >= lens[:, None] - window)
    qg = q.reshape(b, hkv, group, d).astype(jnp.float32)
    s = jnp.einsum("bhgd,bhkd->bhgk", qg, k.astype(jnp.float32)) * scale
    s = jnp.where(live[:, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m) * live[:, None, None, :]
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhgk,bhkd->bhgd", p, v.astype(jnp.float32))
    out = out / jnp.where(l == 0.0, 1.0, l)
    return out.reshape(b, hq, 1, d).astype(q.dtype)


def _paged_decode_jnp_blocked(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    context_lens: jax.Array,
    *,
    scale: float,
    block_pages: int,
) -> jax.Array:
    """Blocked twin: scan page blocks with an online-softmax (m, l, acc) carry.

    The table is padded to a whole number of blocks with page 0 (the engine's
    reserved null page — always a valid pool index); padded positions are
    masked dead, and dead scores are zeroed through the ``* live`` term rather
    than through exp() (exp(NEG_INF - NEG_INF) == 1 on an all-dead block, so
    masking must not rely on the exponent alone).
    """
    b, hq, tq, d = q.shape
    _, hkv, page_size, _ = k_pool.shape
    group = hq // hkv
    max_pages = block_tables.shape[1]
    nb = -(-max_pages // block_pages)
    pad = nb * block_pages - max_pages
    bt = jnp.pad(block_tables, ((0, 0), (0, pad)))  # null page 0 in the tail
    bt = bt.reshape(b, nb, block_pages)
    qg = q.reshape(b, hkv, group, d).astype(jnp.float32)
    s_blk = block_pages * page_size

    def step(carry, jb):
        m, l, acc = carry
        # (B, bp, Hkv, ps, D) -> (B, Hkv, bp*ps, D): one block's working set
        k = jnp.moveaxis(k_pool[bt[:, jb]], 2, 1).reshape(b, hkv, s_blk, d)
        v = jnp.moveaxis(v_pool[bt[:, jb]], 2, 1).reshape(b, hkv, s_blk, d)
        s = jnp.einsum("bhgd,bhkd->bhgk", qg, k.astype(jnp.float32)) * scale
        pos = jb * s_blk + jnp.arange(s_blk)
        in_table = pos < max_pages * page_size  # padded tail pages are dead
        live = (pos[None, :] < context_lens[:, None]) & in_table[None, :]
        s = jnp.where(live[:, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new) * live[:, None, None, :]
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "bhgk,bhkd->bhgd", p, v.astype(jnp.float32)
        )
        return (m_new, l, acc), None

    m0 = jnp.full((b, hkv, group, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, group, 1), jnp.float32)
    a0 = jnp.zeros((b, hkv, group, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), jnp.arange(nb))
    out = acc / jnp.where(l == 0.0, 1.0, l)
    return out.reshape(b, hq, 1, d).astype(q.dtype)


# ---------------------------------------------------------------------------------
# quantized-pool decode: the accessor customization point inside the kernel
# ---------------------------------------------------------------------------------
def _paged_quant_decode_kernel(
    bt_ref,    # scalar prefetch: (B, max_pages) int32 block table
    len_ref,   # scalar prefetch: (B,) int32 live token counts
    q_ref,     # (1, 1, G, D)
    kq_ref,    # (1, page_size, Dq) int8 — physical page picked by the index map
    ks_ref,    # (1, 1, Hkv) f32 — that page's K scales, one per kv head
    vq_ref,    # (1, page_size, Dq) int8
    vs_ref,    # (1, 1, Hkv) f32
    o_ref,     # (1, 1, G, D)
    acc_ref,   # (G, D) f32
    m_ref,     # (G, 1) f32
    l_ref,     # (G, 1) f32
    *,
    scale: float,
    page_size: int,
    bits: int,
    block_pages: int,
):
    b, h = pl.program_id(0), pl.program_id(1)
    jb, ji = pl.program_id(2), pl.program_id(3)
    j = jb * block_pages + ji
    last = (jb == pl.num_programs(2) - 1) & (ji == pl.num_programs(3) - 1)
    g_sz = q_ref.shape[2]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    seq_len = len_ref[b]
    k_pos = j * page_size + jax.lax.broadcasted_iota(jnp.int32, (g_sz, page_size), 1)
    live = k_pos < seq_len

    @pl.when(j * page_size < seq_len)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)  # (G, D)
        kq = kq_ref[0]                       # (page_size, Dq) int8
        vq = vq_ref[0]
        if bits == 4:
            kq = unpack_int4_splithalf(kq)   # lane concat: (page_size, D)
            vq = unpack_int4_splithalf(vq)
        k = kq.astype(jnp.float32) * _head_scale(ks_ref, h)
        v = vq.astype(jnp.float32) * _head_scale(vs_ref, h)
        _flash_update(q, k, v, live, acc_ref, m_ref, l_ref, scale=scale)

    @pl.when(last)
    def _finalize():
        l = l_ref[...]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)


def paged_flash_decode_quant(
    q: jax.Array,
    k_q: jax.Array,
    k_scale: jax.Array,
    v_q: jax.Array,
    v_scale: jax.Array,
    block_tables: jax.Array,
    context_lens: jax.Array,
    *,
    bits: int = 8,
    scale: float | None = None,
    block_pages: int = 1,
    interpret: bool | None = None,
) -> jax.Array:
    """One-token GQA decode against an intN paged KV pool.

    q: (B, Hq, 1, D); k_q/v_q: (num_pages, Hkv, page_size, Dq) int8 with
    Dq = D (int8) or D // 2 (int4, split-half nibbles); k_scale/v_scale:
    (num_pages, Hkv) f32, one scale per (physical page, kv head) — the
    PagedQuantSpec encoding. Block table / length / ``block_pages`` semantics
    are identical to ``paged_flash_decode``: the layout indirection is
    untouched, the scales ride the same ``bt[bb, j]`` index map as the page
    tiles, and the page grid axis is factored (compute blocks, pages/block).
    """
    interpret = use_interpret() if interpret is None else interpret
    b, hq, tq, d = q.shape
    num_pages, hkv, page_size, dq = k_q.shape
    assert tq == 1 and hq % hkv == 0
    assert dq == (d if bits == 8 else d // 2)
    group = hq // hkv
    max_pages = block_tables.shape[1]
    bp = max(1, int(block_pages))
    if max_pages % bp:
        raise ValueError(
            f"block_pages {bp} must divide max_pages {max_pages} "
            "(ops.effective_block_pages picks a valid divisor)"
        )
    scale = float(scale) if scale is not None else 1.0 / np.sqrt(d)
    qg = q.reshape(b, hkv, group, d)

    kern = functools.partial(
        _paged_quant_decode_kernel, scale=scale, page_size=page_size, bits=bits,
        block_pages=bp,
    )
    page_spec = pl.BlockSpec(
        (1, None, page_size, dq),
        lambda bb, h, jb, ji, bt, ln: (bt[bb, jb * bp + ji], h, 0, 0),
    )
    # scales viewed (num_pages, 1, Hkv): the block is a page's whole row
    scale_spec = pl.BlockSpec(
        (1, 1, hkv), lambda bb, h, jb, ji, bt, ln: (bt[bb, jb * bp + ji], 0, 0)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, max_pages // bp, bp),
        in_specs=[
            pl.BlockSpec((1, 1, group, d), lambda bb, h, jb, ji, bt, ln: (bb, h, 0, 0)),
            page_spec,
            scale_spec,
            page_spec,
            scale_spec,
        ],
        out_specs=pl.BlockSpec(
            (1, 1, group, d), lambda bb, h, jb, ji, bt, ln: (bb, h, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((group, d), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, d), q.dtype),
        interpret=interpret,
        name="paged_decode_quant",
    )(
        block_tables.astype(jnp.int32), context_lens.astype(jnp.int32),
        qg, k_q, k_scale.reshape(num_pages, 1, hkv),
        v_q, v_scale.reshape(num_pages, 1, hkv),
    )
    return out.reshape(b, hq, 1, d)


def paged_decode_attention_quant_jnp(
    q: jax.Array,
    k_q: jax.Array,
    k_scale: jax.Array,
    v_q: jax.Array,
    v_scale: jax.Array,
    block_tables: jax.Array,
    context_lens: jax.Array,
    *,
    bits: int = 8,
    scale: float | None = None,
    block_pages: int | None = None,
) -> jax.Array:
    """jnp twin of paged_flash_decode_quant: dequantize the whole pool, then the
    f32 gather path — manifestly the same semantics, O(pool) extra memory."""
    k_pool = dequantize_pages(k_q, k_scale, bits=bits)
    v_pool = dequantize_pages(v_q, v_scale, bits=bits)
    return paged_decode_attention_jnp(
        q, k_pool, v_pool, block_tables, context_lens, scale=scale,
        block_pages=block_pages,
    )


# ---------------------------------------------------------------------------------
# chunked prefill: a Q-chunk against all previously resident paged KV
# ---------------------------------------------------------------------------------
# Two-part attention per chunk: (1) the PAST — pool positions < cursor, read
# through the block table exactly as decode does (dequantized in-kernel for
# intN pages); (2) the PRESENT — the chunk's own K/V, handed in as fresh f32
# tensors with intra-chunk causal masking, NEVER read back through the pool.
# Part 2 is what keeps a single-chunk prefill bit-equivalent to a monolithic
# one even over quantized pools: the chunk's own tokens attend each other at
# full precision (as monolithic prefill does), and only CROSS-chunk attention
# pays the representation — the same boundary monolithic decode pays at its
# first step. Both parts fold into one online softmax (_flash_update), with
# the chunk tile applied as the last accumulation step.


def _past_live(cursor, c: int, group: int, page_size: int, j, window=None):
    """(C*G, page_size) liveness of logical page j for the past part: every
    slot before the chunk start (causality across the boundary is automatic —
    all past positions precede every chunk row), and under a ``window`` only
    the slots within it of row t's position cursor + t. Rows are t-major
    blocks of size G (see the reshape in the callers)."""
    rows = c * group
    k_pos = j * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (rows, page_size), 1
    )
    live = k_pos < cursor
    if window is not None:
        t = jax.lax.broadcasted_iota(jnp.int32, (rows, page_size), 0) // group
        live = live & (k_pos > cursor + t - window)
    return live


def _chunk_self_live(c: int, group: int, window=None):
    """(C*G, C) intra-chunk causal mask: row t attends chunk column tk <= t
    (and tk > t - window under a window)."""
    rows = c * group
    t = jax.lax.broadcasted_iota(jnp.int32, (rows, c), 0) // group
    tk = jax.lax.broadcasted_iota(jnp.int32, (rows, c), 1)
    if window is not None:
        return (tk <= t) & (tk > t - window)
    return tk <= t


def _paged_chunk_kernel(
    bt_ref,    # scalar prefetch: (B, max_pages) int32 block table
    cur_ref,   # scalar prefetch: (B,) int32 chunk start positions (resident KV)
    q_ref,     # (1, 1, C*G, D) — chunk queries, t-major rows
    ck_ref,    # (1, 1, C, D) — the chunk's own f32 K (never from the pool)
    cv_ref,    # (1, 1, C, D)
    k_ref,     # (1, page_size, D) — physical page picked by the index map
    v_ref,     # (1, page_size, D)
    o_ref,     # (1, 1, C*G, D)
    acc_ref,   # (C*G, D) f32
    m_ref,     # (C*G, 1) f32
    l_ref,     # (C*G, 1) f32
    *,
    scale: float,
    page_size: int,
    chunk: int,
    group: int,
    window: int | None = None,
):
    b = pl.program_id(0)
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    pg = j  # the logical page grid step j reads
    if window is not None:
        pg = _window_first(cur_ref[b] + 1, window, page_size) + j

    @pl.when(pg * page_size < cur_ref[b])
    def _past():
        q = q_ref[0, 0].astype(jnp.float32)  # (C*G, D)
        k = k_ref[0].astype(jnp.float32)     # (page_size, D)
        v = v_ref[0].astype(jnp.float32)
        live = _past_live(cur_ref[b], chunk, group, page_size, pg, window)
        _flash_update(q, k, v, live, acc_ref, m_ref, l_ref, scale=scale)

    @pl.when(j == nj - 1)
    def _present_and_finalize():
        q = q_ref[0, 0].astype(jnp.float32)
        ck = ck_ref[0, 0].astype(jnp.float32)  # (C, D)
        cv = cv_ref[0, 0].astype(jnp.float32)
        live = _chunk_self_live(chunk, group, window)
        _flash_update(q, ck, cv, live, acc_ref, m_ref, l_ref, scale=scale)
        l = l_ref[...]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)


def paged_flash_prefill_chunk(
    q: jax.Array,
    chunk_k: jax.Array,
    chunk_v: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    cursors: jax.Array,
    *,
    scale: float | None = None,
    window: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """GQA chunked-prefill attention: past from the pool, present from f32.

    q: (B, Hq, C, D) — the chunk's queries, absolute positions
    cursors[b]..cursors[b]+C-1; chunk_k/chunk_v: (B, Hkv, C, D) the chunk's own
    freshly-projected K/V (attended intra-chunk causally at full precision);
    k_pool/v_pool: (num_pages, Hkv, page_size, D); block_tables: (B, max_pages)
    int32; cursors: (B,) int32 tokens resident BEFORE this chunk — the pool is
    read only below that bound, so the chunk's scattered pages (and anything
    past them) never feed back into its own attention. Rows past the chunk's
    valid length produce garbage the caller discards (their KV went to the
    null page, so nothing real ever attends them).

    ``window``: row t of the chunk attends to the keys after
    cursor + t - window, so the pool is read from the page of
    max(0, cursor - window + 1), ``window_pages`` grid steps.
    """
    interpret = use_interpret() if interpret is None else interpret
    b, hq, c, d = q.shape
    num_pages, hkv, page_size, _ = k_pool.shape
    assert hq % hkv == 0
    group = hq // hkv
    max_pages = block_tables.shape[1]
    scale = float(scale) if scale is not None else 1.0 / np.sqrt(d)
    # t-major rows: (B, Hkv, C*G, D) with row t*G + g — _past_live's layout
    qg = jnp.swapaxes(q.reshape(b, hkv, group, c, d), 2, 3).reshape(
        b, hkv, c * group, d
    )

    kern = functools.partial(
        _paged_chunk_kernel, scale=scale, page_size=page_size, chunk=c, group=group,
        window=window,
    )
    rows = c * group
    chunk_spec = pl.BlockSpec(
        (1, 1, c, d), lambda bb, h, j, bt, cur: (bb, h, 0, 0)
    )
    if window is None:
        n_steps = max_pages
        page_map = lambda bb, h, j, bt, cur: (bt[bb, j], h, 0, 0)
    else:
        n_steps = window_pages(window, page_size)
        page_map = lambda bb, h, j, bt, cur: (bt[bb, jnp.minimum(
            _window_first(cur[bb] + 1, window, page_size) + j, max_pages - 1)], h, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, n_steps),
        in_specs=[
            pl.BlockSpec((1, 1, rows, d), lambda bb, h, j, bt, cur: (bb, h, 0, 0)),
            chunk_spec,
            chunk_spec,
            pl.BlockSpec((1, None, page_size, d), page_map),
            pl.BlockSpec((1, None, page_size, d), page_map),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, rows, d), lambda bb, h, j, bt, cur: (bb, h, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((rows, d), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rows, d), q.dtype),
        interpret=interpret,
        name="paged_prefill_chunk",
        **_window_tag(window),
    )(
        block_tables.astype(jnp.int32), cursors.astype(jnp.int32),
        qg, chunk_k, chunk_v, k_pool, v_pool,
    )
    # rows back to (B, Hkv, C, G, D) -> (B, Hq, C, D)
    return jnp.swapaxes(out.reshape(b, hkv, c, group, d), 2, 3).reshape(b, hq, c, d)


def paged_prefill_chunk_jnp(
    q: jax.Array,
    chunk_k: jax.Array,
    chunk_v: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    cursors: jax.Array,
    *,
    scale: float | None = None,
    window: int | None = None,
) -> jax.Array:
    """jnp twin: concatenate [gathered past pages | the chunk's own f32 K/V]
    along the key axis, mask (past below cursor, present causally), one
    softmax — identical semantics to the kernel's two-part online update.
    With ``window`` only the window pages are gathered."""
    b, hq, c, d = q.shape
    _, hkv, page_size, _ = k_pool.shape
    assert hq % hkv == 0
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    if window is not None:
        return _paged_chunk_jnp_window(q, chunk_k, chunk_v, k_pool, v_pool,
                                       block_tables, cursors, scale=scale,
                                       window=window)
    k = jnp.moveaxis(k_pool[block_tables], 2, 1)
    v = jnp.moveaxis(v_pool[block_tables], 2, 1)
    s_len = k.shape[2] * page_size
    k = jnp.concatenate(
        [k.reshape(b, hkv, s_len, d), chunk_k.astype(k.dtype)], axis=2
    ).astype(jnp.float32)
    v = jnp.concatenate(
        [v.reshape(b, hkv, s_len, d), chunk_v.astype(v.dtype)], axis=2
    ).astype(jnp.float32)
    qg = q.reshape(b, hkv, group, c, d).astype(jnp.float32)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k) * scale
    t_q = jnp.arange(c)
    past = jnp.arange(s_len)[None, None, :] < cursors[:, None, None]  # (B, 1, S)
    past = jnp.broadcast_to(past, (b, c, s_len))
    present = (t_q[None, :] <= t_q[:, None])[None]  # (1, C, C) causal
    present = jnp.broadcast_to(present, (b, c, c))
    live = jnp.concatenate([past, present], axis=-1)[:, None, None]  # (B,1,1,C,S+C)
    s = jnp.where(live, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m) * live
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", p, v) / jnp.where(l == 0.0, 1.0, l)
    return out.reshape(b, hq, c, d).astype(q.dtype)


def _paged_chunk_jnp_window(q, chunk_k, chunk_v, k_pool, v_pool, block_tables,
                            cursors, *, scale: float, window: int) -> jax.Array:
    """Windowed chunk twin: the window pages of the pool, then the chunk;
    query t attends to keys after cursor + t - window."""
    b, hq, c, d = q.shape
    _, hkv, page_size, _ = k_pool.shape
    group = hq // hkv
    n_win = window_pages(window, page_size)
    cur = cursors.astype(jnp.int32)
    logical, table = _window_table(
        block_tables, _window_first(cur + 1, window, page_size), n_win)
    s_len = n_win * page_size
    k = jnp.moveaxis(k_pool[table], 2, 1).reshape(b, hkv, s_len, d)
    v = jnp.moveaxis(v_pool[table], 2, 1).reshape(b, hkv, s_len, d)
    k = jnp.concatenate([k, chunk_k.astype(k.dtype)], axis=2).astype(jnp.float32)
    v = jnp.concatenate([v, chunk_v.astype(v.dtype)], axis=2).astype(jnp.float32)
    k_pos = (logical[:, :, None] * page_size
             + jnp.arange(page_size)[None, None, :]).reshape(b, 1, s_len)
    q_pos = cur[:, None, None] + jnp.arange(c)[None, :, None]  # (B, C, 1)
    past = (k_pos < cur[:, None, None]) & (k_pos > q_pos - window)
    t_q = jnp.arange(c)
    present = (t_q[None, :] <= t_q[:, None]) & (t_q[None, :] > t_q[:, None] - window)
    present = jnp.broadcast_to(present[None], (b, c, c))
    live = jnp.concatenate([past, present], axis=-1)[:, None, None]
    qg = q.reshape(b, hkv, group, c, d).astype(jnp.float32)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k) * scale
    s = jnp.where(live, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m) * live
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", p, v) / jnp.where(l == 0.0, 1.0, l)
    return out.reshape(b, hq, c, d).astype(q.dtype)


def _paged_chunk_quant_kernel(
    bt_ref,    # scalar prefetch: (B, max_pages) int32 block table
    cur_ref,   # scalar prefetch: (B,) int32 chunk start positions
    q_ref,     # (1, 1, C*G, D)
    ck_ref,    # (1, 1, C, D) f32 — the chunk's own K, never from the pool
    cv_ref,    # (1, 1, C, D) f32
    kq_ref,    # (1, page_size, Dq) int8 — physical page picked by the index map
    ks_ref,    # (1, 1, Hkv) f32 — that page's K scales, one per kv head
    vq_ref,    # (1, page_size, Dq) int8
    vs_ref,    # (1, 1, Hkv) f32
    o_ref,     # (1, 1, C*G, D)
    acc_ref,
    m_ref,
    l_ref,
    *,
    scale: float,
    page_size: int,
    chunk: int,
    group: int,
    bits: int,
):
    b, h = pl.program_id(0), pl.program_id(1)
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j * page_size < cur_ref[b])
    def _past():
        q = q_ref[0, 0].astype(jnp.float32)
        kq = kq_ref[0]
        vq = vq_ref[0]
        if bits == 4:
            kq = unpack_int4_splithalf(kq)
            vq = unpack_int4_splithalf(vq)
        k = kq.astype(jnp.float32) * _head_scale(ks_ref, h)
        v = vq.astype(jnp.float32) * _head_scale(vs_ref, h)
        live = _past_live(cur_ref[b], chunk, group, page_size, j)
        _flash_update(q, k, v, live, acc_ref, m_ref, l_ref, scale=scale)

    @pl.when(j == nj - 1)
    def _present_and_finalize():
        q = q_ref[0, 0].astype(jnp.float32)
        ck = ck_ref[0, 0].astype(jnp.float32)
        cv = cv_ref[0, 0].astype(jnp.float32)
        live = _chunk_self_live(chunk, group)
        _flash_update(q, ck, cv, live, acc_ref, m_ref, l_ref, scale=scale)
        l = l_ref[...]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)


def paged_flash_prefill_chunk_quant(
    q: jax.Array,
    chunk_k: jax.Array,
    chunk_v: jax.Array,
    k_q: jax.Array,
    k_scale: jax.Array,
    v_q: jax.Array,
    v_scale: jax.Array,
    block_tables: jax.Array,
    cursors: jax.Array,
    *,
    bits: int = 8,
    scale: float | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Chunked-prefill attention over an intN paged pool: the past part
    dequantizes page tiles through the same (page, head) scale index maps as
    paged_flash_decode_quant; the present part attends the chunk's own f32
    K/V, so intra-chunk attention never pays the representation."""
    interpret = use_interpret() if interpret is None else interpret
    b, hq, c, d = q.shape
    num_pages, hkv, page_size, dq = k_q.shape
    assert hq % hkv == 0
    assert dq == (d if bits == 8 else d // 2)
    group = hq // hkv
    max_pages = block_tables.shape[1]
    scale = float(scale) if scale is not None else 1.0 / np.sqrt(d)
    qg = jnp.swapaxes(q.reshape(b, hkv, group, c, d), 2, 3).reshape(
        b, hkv, c * group, d
    )

    kern = functools.partial(
        _paged_chunk_quant_kernel, scale=scale, page_size=page_size, chunk=c,
        group=group, bits=bits,
    )
    rows = c * group
    chunk_spec = pl.BlockSpec((1, 1, c, d), lambda bb, h, j, bt, cur: (bb, h, 0, 0))
    page_spec = pl.BlockSpec(
        (1, None, page_size, dq), lambda bb, h, j, bt, cur: (bt[bb, j], h, 0, 0)
    )
    # scales viewed (num_pages, 1, Hkv): the block is a page's whole row
    scale_spec = pl.BlockSpec(
        (1, 1, hkv), lambda bb, h, j, bt, cur: (bt[bb, j], 0, 0)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, max_pages),
        in_specs=[
            pl.BlockSpec((1, 1, rows, d), lambda bb, h, j, bt, cur: (bb, h, 0, 0)),
            chunk_spec,
            chunk_spec,
            page_spec,
            scale_spec,
            page_spec,
            scale_spec,
        ],
        out_specs=pl.BlockSpec(
            (1, 1, rows, d), lambda bb, h, j, bt, cur: (bb, h, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((rows, d), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rows, d), q.dtype),
        interpret=interpret,
        name="paged_prefill_chunk_quant",
    )(
        block_tables.astype(jnp.int32), cursors.astype(jnp.int32),
        qg, chunk_k, chunk_v, k_q, k_scale.reshape(num_pages, 1, hkv),
        v_q, v_scale.reshape(num_pages, 1, hkv),
    )
    return jnp.swapaxes(out.reshape(b, hkv, c, group, d), 2, 3).reshape(b, hq, c, d)


def paged_prefill_chunk_quant_jnp(
    q: jax.Array,
    chunk_k: jax.Array,
    chunk_v: jax.Array,
    k_q: jax.Array,
    k_scale: jax.Array,
    v_q: jax.Array,
    v_scale: jax.Array,
    block_tables: jax.Array,
    cursors: jax.Array,
    *,
    bits: int = 8,
    scale: float | None = None,
) -> jax.Array:
    """jnp twin of paged_flash_prefill_chunk_quant: dequantize the whole pool,
    then the f32 chunk gather path (the chunk's own K/V stay f32 throughout)."""
    k_pool = dequantize_pages(k_q, k_scale, bits=bits)
    v_pool = dequantize_pages(v_q, v_scale, bits=bits)
    return paged_prefill_chunk_jnp(
        q, chunk_k, chunk_v, k_pool, v_pool, block_tables, cursors, scale=scale
    )
