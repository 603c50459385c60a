"""Windowed and full attention in one paged model, with routed experts held
as one chip's share (Mellum2's layer equations), at tiny widths on the CPU.

The serving engine is held to the plain float32 reference
(``repro.models.reference``) through its normal paged path, chunked and
monolithic prefill, then decode through the cache. The model computes in
float32, so engine and reference differ only in the order of their sums:
every logit agrees within ``TOL``. The two negative controls (full attention
in place of the window, plain RoPE in place of YaRN on the full layers) miss
the engine by more than ``CONTROL_MIN``, far outside it.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels import paged_attention as pa
from repro.models import moe as moe_mod
from repro.models import reference
from repro.models.config import ModelConfig
from repro.models.layers import yarn_attention_factor, yarn_freqs
from repro.models.transformer import Model, block_program
from repro.serving import GenerationParams
from repro.serving.engine import EngineConfig, Request, ServeEngine

# engine vs reference, both float32: the sums' order moves a logit of
# magnitude ~4 by ~3e-6 (measured); TOL leaves 30x room and stays 2000x
# below the smallest control gap
TOL = 1e-4
CONTROL_MIN = 0.1

PERIOD = ("sliding_attention",) * 3 + ("full_attention",)
# window 20 on 8-token pages: the window's edge falls inside a page; 16-token
# chunks put chunk edges on page edges and across the window's
CFG = ModelConfig(
    name="tiny-window-moe", family="moe", n_layers=8, d_model=64, vocab=512,
    n_heads=4, n_kv_heads=2, d_head=16, d_ff=32, n_experts=8, top_k=2,
    experts_held=4, rope_theta=5e5, window=20, layer_types=PERIOD * 2,
    yarn_factor=4.0, yarn_original_max=16,
    dtype="float32")
PAGE, CHUNK = 8, 16
PROMPTS = (45, 21, 20, 19)  # past the window, one past, at, one below
NEW = 6


@pytest.fixture(scope="module")
def params():
    return Model(CFG).init_params(jax.random.key(1))


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, CFG.vocab, n).tolist() for n in lengths]


def _serve(params, prompts, *, chunked, impl, cfg=CFG, record_logits=True, **kw):
    eng = ServeEngine(Model(cfg), params, EngineConfig(
        num_pages=64, page_size=PAGE, max_batch=4, max_pages_per_seq=10,
        record_logits=record_logits, attn_impl=impl, chunked_prefill=chunked,
        chunk_tokens=CHUNK if chunked else 0, **kw))
    res = eng.run([Request(i, p, GenerationParams(max_new_tokens=NEW))
                   for i, p in enumerate(prompts)])
    return eng, res


def _gap(eng, res, prompts, params, **control):
    """Widest |engine logit - reference logit| over every served token."""
    widest = 0.0
    for i, p in enumerate(prompts):
        gen = res[i].generated
        ref = np.asarray(reference.logits(CFG, params, p + gen[:-1], **control))
        rows = eng.logits_of[i]
        assert sorted(rows) == list(range(NEW))
        widest = max(widest, max(float(np.abs(rows[n] - ref[len(p) - 1 + n]).max())
                                 for n in rows))
    return widest


def test_the_model_is_one_scan_over_periods_of_stacked_layers(params):
    assert block_program(CFG) == [("period", 2)]
    assert params["blocks"][0]["attn"]["wq"].shape == (8, 64, 4, 16)
    # the router scores all 8 experts; 4 of them are held here
    assert params["blocks"][0]["moe"]["router"].shape == (8, 64, 8)
    assert params["blocks"][0]["moe"]["w_gate"].shape == (8, 4, 64, 32)
    pools = Model(CFG).init_paged_cache(16, PAGE)
    assert [a.shape for a in jax.tree.leaves(pools)] == [(8, 16, 2, PAGE, 16)] * 2


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
@pytest.mark.parametrize("chunked", [True, False], ids=["chunked", "monolithic"])
def test_engine_logits_match_the_reference(params, chunked, impl):
    prompts = _prompts(PROMPTS)
    eng, res = _serve(params, prompts, chunked=chunked, impl=impl)
    assert _gap(eng, res, prompts, params) < TOL
    m = eng.metrics()
    # decode rows (and chunks) count their pairs; 2 of 8 experts routed per
    # token per layer, about half of them held here
    assert m["moe_pairs_routed"] % (CFG.top_k * CFG.n_layers) == 0
    assert 0 < m["moe_pairs_held"] < m["moe_pairs_routed"]
    # held experts read: counted over decode steps, at most all held ones
    assert 0 < m["moe_decode_experts_read"] <= (
        m["decode_steps"] * CFG.n_layers * CFG.n_held_experts)


@pytest.mark.parametrize("control", [{"windows": False}, {"yarn": False}],
                         ids=["full-attention-for-window", "yarn-off"])
def test_each_negative_control_misses_the_engine(params, control):
    prompts = _prompts(PROMPTS)
    eng, res = _serve(params, prompts, chunked=True, impl="jnp")
    assert _gap(eng, res, prompts, params) < TOL
    assert _gap(eng, res, prompts, params, **control) > CONTROL_MIN


def test_a_requests_logits_do_not_depend_on_its_batch(params):
    """Dropless routing: served alone or beside three other requests, a
    request reads the same logits (to float32 rounding of the sums)."""
    prompts = _prompts((45, 33, 27, 38), seed=3)
    eng_1, res_1 = _serve(params, prompts[:1], chunked=True, impl="jnp")
    eng_4, res_4 = _serve(params, prompts, chunked=True, impl="jnp")
    assert res_1[0].generated == res_4[0].generated
    for n in range(NEW):
        np.testing.assert_allclose(eng_1.logits_of[0][n], eng_4.logits_of[0][n],
                                   rtol=0, atol=1e-5)


def test_dropless_layer_ignores_the_batch_where_capacity_routing_drops():
    cfg = dataclasses.replace(CFG, experts_held=0)  # all 8 experts, as training
    p = jax.tree.map(lambda a: a[0], Model(cfg).init_params(jax.random.key(2))
                     ["blocks"][0]["moe"])
    # seven rows of one repeated token overload its two experts
    x = jax.random.normal(jax.random.key(3), (2, 16, 64), jnp.float32)
    x = jnp.concatenate([jnp.broadcast_to(x[0, 0], (7, 16, 64)), x[1:]])
    alone = moe_mod.apply_moe_dropless(cfg, p, x[-1:])[0]
    batch = moe_mod.apply_moe_dropless(cfg, p, x)[0]
    np.testing.assert_allclose(alone, batch[-1:], rtol=0, atol=1e-5)
    # the capacity router (1.25x the mean load) drops the last row's pairs
    # from the experts the batch overloads
    cap_alone = moe_mod.apply_moe(cfg, p, x[-1:])[0]
    cap_batch = moe_mod.apply_moe(cfg, p, x)[0]
    assert float(jnp.abs(cap_alone - cap_batch[-1:]).max()) > 1e-3


def test_the_four_expert_shares_sum_to_the_uncut_layer():
    """Ranks 0-3 of 4 chips, 16 of 64 experts each: the program's share on
    each rank equals the reference's, and the four shares add up to the
    reference layer over all 64 experts."""
    cfg = dataclasses.replace(CFG, n_experts=64, top_k=8, experts_held=0)
    whole = jax.tree.map(lambda a: a[0], Model(cfg).init_params(jax.random.key(4))
                         ["blocks"][0]["moe"])
    h = jax.random.normal(jax.random.key(5), (24, 64), jnp.float32)
    uncut = reference.routed_experts(cfg, whole, h)
    total = jnp.zeros_like(uncut)
    for rank in range(4):
        c = dataclasses.replace(cfg, experts_held=16, expert_rank=rank)
        share = dict(whole, **{n: whole[n][16 * rank:16 * (rank + 1)]
                               for n in ("w_gate", "w_up", "w_down")})
        ref = reference.routed_experts(c, share, h)
        got, counts = moe_mod.apply_moe_dropless(c, share, h[None])
        np.testing.assert_allclose(got[0], ref, rtol=0, atol=1e-5)
        assert int(counts[0]) == 24 * 8
        total = total + ref
    np.testing.assert_allclose(total, uncut, rtol=0, atol=1e-5)


def test_yarn_frequencies_are_the_published_equations():
    """Mellum2's full layers (theta 5e5, factor 16, 8192 original positions,
    beta 32 / 1 over 128-wide heads): dimensions below 18 keep their
    frequency, those above 35 are divided by 16, a linear ramp between; cos
    and sin scale by the published 1.2772588722239782."""
    inv = yarn_freqs(128, 5e5, 16.0, 8192)
    base = 1.0 / 5e5 ** (np.arange(0, 128, 2) / 128)
    np.testing.assert_allclose(inv[:19], base[:19], rtol=1e-6)
    np.testing.assert_allclose(inv[35:], base[35:] / 16, rtol=1e-6)
    ramp = (np.arange(64) - 18) / (35 - 18)
    np.testing.assert_allclose(inv[26], base[26] * (1 - ramp[26] + ramp[26] / 16),
                               rtol=1e-6)
    assert yarn_attention_factor(16.0) == pytest.approx(1.2772588722239782, rel=1e-15)
    c = dataclasses.replace(CFG, d_head=128, yarn_factor=16.0, yarn_original_max=8192)
    np.testing.assert_allclose(inv, reference._inv_freq(c, True, True), rtol=1e-6)


def test_paths_without_windows_refuse_a_windowed_model(params):
    with pytest.raises(NotImplementedError, match="quantized KV pools have no sliding"):
        ServeEngine(Model(CFG), params, EngineConfig(
            num_pages=64, page_size=PAGE, max_batch=4, max_pages_per_seq=10,
            kv_dtype="int8"))
    with pytest.raises(NotImplementedError, match="speculative verify has no sliding"):
        _serve(params, _prompts((12,)), chunked=True, impl="jnp", spec_tokens=2,
               record_logits=False)


# ---- the windowed kernels against contiguous windowed attention ---------------
W, HQ, HKV, D, B = 20, 4, 2, 16, 3


def _paged(k, v, max_pages, seed):
    """Scatter dense (B, Hkv, S, D) K/V into a shuffled pool through a table."""
    b, _, s, _ = k.shape
    n = -(-s // PAGE)
    perm = np.random.default_rng(seed).permutation(b * max_pages) + 1
    table = perm.reshape(b, max_pages)
    pad = n * PAGE - s
    kp = np.pad(np.asarray(k), ((0, 0), (0, 0), (0, pad), (0, 0)))
    vp = np.pad(np.asarray(v), ((0, 0), (0, 0), (0, pad), (0, 0)))
    pool_k = np.zeros((b * max_pages + 1, HKV, PAGE, D), np.float32)
    pool_v = np.zeros_like(pool_k)
    for r in range(b):
        for j in range(n):
            pool_k[table[r, j]] = kp[r, :, j * PAGE:(j + 1) * PAGE]
            pool_v[table[r, j]] = vp[r, :, j * PAGE:(j + 1) * PAGE]
    return jnp.asarray(pool_k), jnp.asarray(pool_v), jnp.asarray(table, jnp.int32)


# lengths one below, at and one past the window, a page edge past it, and
# far past it with the window's first key mid-page
@pytest.mark.parametrize("n", [W - 1, W, W + 1, 3 * PAGE, 57])
@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_windowed_decode_kernel_matches_windowed_attention(n, impl):
    ks = jax.random.split(jax.random.key(n), 3)
    k = jax.random.normal(ks[0], (B, HKV, n, D), jnp.float32)
    v = jax.random.normal(ks[1], (B, HKV, n, D), jnp.float32)
    q = jax.random.normal(ks[2], (B, HQ, 1, D), jnp.float32)
    pk, pv, table = _paged(k, v, 9, seed=n)
    got = ops.paged_decode_attention(q, pk, pv, table, jnp.full((B,), n, jnp.int32),
                                     window=W, impl=impl)
    want = ops.attention_jnp(q, k, v, causal=True, window=W, q_offset=n - 1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    full = ops.paged_decode_attention(q, pk, pv, table, jnp.full((B,), n, jnp.int32),
                                      impl=impl)
    assert (n <= W) == bool(np.allclose(full, want, rtol=1e-5, atol=1e-5))


# chunks whose cursor sits below, at and past the window, on page edges
@pytest.mark.parametrize("cursor", [0, 2 * PAGE, 3 * PAGE, 6 * PAGE])
@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_windowed_chunk_kernel_matches_windowed_attention(cursor, impl):
    c = CHUNK
    ks = jax.random.split(jax.random.key(cursor), 3)
    k = jax.random.normal(ks[0], (B, HKV, cursor + c, D), jnp.float32)
    v = jax.random.normal(ks[1], (B, HKV, cursor + c, D), jnp.float32)
    q = jax.random.normal(ks[2], (B, HQ, c, D), jnp.float32)
    pk, pv, table = _paged(k[:, :, :cursor], v[:, :, :cursor], 9, seed=cursor)
    got = ops.paged_prefill_chunk_attention(
        q, k[:, :, cursor:], v[:, :, cursor:], pk, pv, table,
        jnp.full((B,), cursor, jnp.int32), window=W, impl=impl)
    want = ops.attention_jnp(q, k, v, causal=True, window=W, q_offset=cursor)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("max_pages", [9, 69])
def test_windowed_grids_visit_window_pages_whatever_the_table(max_pages):
    """The page axis of both windowed grids is window_pages(W, page), not the
    table's width."""
    q = jax.ShapeDtypeStruct((B, HQ, 1, D), jnp.float32)
    pool = jax.ShapeDtypeStruct((B * max_pages + 1, HKV, PAGE, D), jnp.float32)
    table = jax.ShapeDtypeStruct((B, max_pages), jnp.int32)
    lens = jax.ShapeDtypeStruct((B,), jnp.int32)
    chunk = jax.ShapeDtypeStruct((B, HKV, CHUNK, D), jnp.float32)
    qc = jax.ShapeDtypeStruct((B, HQ, CHUNK, D), jnp.float32)
    n_win = pa.window_pages(W, PAGE)
    assert n_win == 4
    grids = []
    for fn, args in (
        (lambda *a: pa.paged_flash_decode(*a, window=W, interpret=True),
         (q, pool, pool, table, lens)),
        (lambda *a: pa.paged_flash_prefill_chunk(*a, window=W, interpret=True),
         (qc, chunk, chunk, pool, pool, table, lens)),
    ):
        eqns = jax.make_jaxpr(fn)(*args).eqns
        grids += [e.params["grid_mapping"].grid for e in eqns
                  if e.primitive.name == "pallas_call"]
    assert grids == [(B, HKV, n_win, 1), (B, HKV, n_win)]
