"""Paged serving subsystem: LayoutPaged laws, paged-attention kernel vs the dense
reference, and the continuous-batching engine vs the unbatched decode path."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Extents, LayoutError, LayoutPaged, LayoutRight
from repro.kernels import ref
from repro.kernels.paged_attention import (
    paged_decode_attention_jnp,
    paged_flash_decode,
    paged_flash_prefill_chunk,
    paged_prefill_chunk_jnp,
)
from repro.models import build_model, get_config
from repro.serving import GenerationParams
from repro.serving.engine import (
    PREFILLING, EngineConfig, Request, ServeEngine, aligned_max_logit_err,
)


# =====================================================================================
# LayoutPaged — Table I observer protocol
# =====================================================================================
def test_layout_paged_dense_table_matches_layout_right():
    """Identity block table == LayoutRight over the page-factored domain."""
    S, H, MP, D, ps = 2, 3, 8, 4, 4
    lp = LayoutPaged.dense(S, H, MP, D, ps)
    lr = LayoutRight(Extents.fully_dynamic(S, MP // ps, H, ps, D))
    for s in range(S):
        for h in range(H):
            for p in range(MP):
                for d in range(D):
                    assert lp(s, h, p, d) == lr(s, p // ps, h, p % ps, d)
    assert lp.is_unique()
    assert lp.is_contiguous()  # table is a bijection onto the pool
    assert not lp.is_strided()


def test_layout_paged_observers_on_scattered_table():
    H, D, ps = 2, 4, 4
    lp = LayoutPaged(Extents.fully_dynamic(2, H, 8, D), ((5, 2), (7, 0)), ps, 9)
    assert lp.is_unique()
    assert not lp.is_contiguous()  # pool over-provisioned: 4 of 9 pages used
    assert not lp.is_strided()
    assert lp.required_span_size() == 9 * H * ps * D
    assert lp.pool_shape() == (9, H, ps, D)
    with pytest.raises(LayoutError):
        lp.stride(0)
    # full-domain image: injective, inside the codomain
    offs = np.array(lp.offsets_dense()).reshape(-1)
    assert len(set(offs.tolist())) == offs.size
    assert 0 <= offs.min() and offs.max() < lp.required_span_size()


def test_layout_paged_aliasing_table_not_unique():
    lp = LayoutPaged(Extents.fully_dynamic(2, 2, 8, 4), ((1, 2), (2, 3)), 4, 5)
    assert not lp.is_unique()


def test_layout_paged_traced_indices_match_python_ints():
    lp = LayoutPaged(Extents.fully_dynamic(2, 2, 8, 4), ((5, 2), (7, 0)), 4, 9)
    for idx in [(0, 1, 3, 2), (1, 0, 5, 3), (1, 1, 7, 0)]:
        traced = lp(*(jnp.int32(i) for i in idx))
        assert int(traced) == lp(*idx)


def test_layout_paged_validation():
    with pytest.raises(TypeError):
        LayoutPaged(Extents.fully_dynamic(2, 2, 7, 4), ((0,), (1,)), 4, 2)  # 7 % 4
    with pytest.raises(TypeError):
        LayoutPaged(Extents.fully_dynamic(2, 2, 8, 4), ((0, 1),), 4, 2)  # 1 row for 2 seqs
    with pytest.raises(ValueError):
        LayoutPaged(Extents.fully_dynamic(1, 2, 8, 4), ((0, 9),), 4, 2)  # page id oob


# =====================================================================================
# paged-attention kernel vs dense reference
# =====================================================================================
@pytest.mark.parametrize(
    "batch,page_size,lens",
    [
        (2, 8, (5, 20)),      # mixed lengths, partial last pages
        (3, 16, (1, 16, 31)), # page-exact and one-token edge cases
        (1, 4, (13,)),        # many small pages
    ],
)
@pytest.mark.parametrize("impl", ["pallas", "jnp"])
def test_paged_decode_matches_dense_reference(batch, page_size, lens, impl):
    hq, hkv, d = 4, 2, 16
    max_pages = -(-max(lens) // page_size)
    num_pages = batch * max_pages + 1  # + null page 0
    rng = np.random.default_rng(batch * 100 + page_size)
    q = jnp.asarray(rng.standard_normal((batch, hq, 1, d)), jnp.float32)
    k_pool = jnp.asarray(rng.standard_normal((num_pages, hkv, page_size, d)), jnp.float32)
    v_pool = jnp.asarray(rng.standard_normal((num_pages, hkv, page_size, d)), jnp.float32)
    perm = rng.permutation(np.arange(1, num_pages)).reshape(batch, max_pages)
    bt = jnp.asarray(perm, jnp.int32)
    cl = jnp.asarray(lens, jnp.int32)
    if impl == "pallas":
        out = paged_flash_decode(q, k_pool, v_pool, bt, cl, interpret=True)
    else:
        out = paged_decode_attention_jnp(q, k_pool, v_pool, bt, cl)
    # densify through the block table, then the plain attention oracle
    k_dense = jnp.moveaxis(k_pool[bt], 2, 1).reshape(batch, hkv, max_pages * page_size, d)
    v_dense = jnp.moveaxis(v_pool[bt], 2, 1).reshape(batch, hkv, max_pages * page_size, d)
    for b, L in enumerate(lens):
        want = ref.attention(
            q[b : b + 1], k_dense[b : b + 1, :, :L], v_dense[b : b + 1, :, :L],
            causal=True, q_offset=L - 1,
        )
        np.testing.assert_allclose(
            np.array(out[b], np.float32), np.array(want[0], np.float32),
            rtol=2e-5, atol=2e-5,
        )


# =====================================================================================
# chunked-prefill attention kernel vs dense reference
# =====================================================================================
@pytest.mark.parametrize("impl", ["pallas", "jnp"])
def test_chunk_prefill_attention_matches_dense_reference(impl):
    """Two-part chunk attention (past from the pool, present from f32) equals
    full causal attention over [past | chunk] densified through the table."""
    hq, hkv, d, ps, C, max_pages = 4, 2, 16, 4, 8, 6
    num_pages = 2 * max_pages + 1
    rng = np.random.default_rng(0)
    cursors = np.array([4, 8], np.int32)  # page-aligned resident counts
    valid = (8, 5)                        # row 1: a partial final chunk
    q = jnp.asarray(rng.standard_normal((2, hq, C, d)), jnp.float32)
    ck = jnp.asarray(rng.standard_normal((2, hkv, C, d)), jnp.float32)
    cv = jnp.asarray(rng.standard_normal((2, hkv, C, d)), jnp.float32)
    k_pool = jnp.asarray(rng.standard_normal((num_pages, hkv, ps, d)), jnp.float32)
    v_pool = jnp.asarray(rng.standard_normal((num_pages, hkv, ps, d)), jnp.float32)
    bt = jnp.asarray(
        rng.permutation(np.arange(1, num_pages)).reshape(2, max_pages), jnp.int32
    )
    cur = jnp.asarray(cursors)
    if impl == "pallas":
        out = paged_flash_prefill_chunk(
            q, ck, cv, k_pool, v_pool, bt, cur, interpret=True
        )
    else:
        out = paged_prefill_chunk_jnp(q, ck, cv, k_pool, v_pool, bt, cur)
    k_dense = jnp.moveaxis(k_pool[bt], 2, 1).reshape(2, hkv, max_pages * ps, d)
    v_dense = jnp.moveaxis(v_pool[bt], 2, 1).reshape(2, hkv, max_pages * ps, d)
    for b in range(2):
        kk = jnp.concatenate([k_dense[b : b + 1, :, : int(cursors[b])], ck[b : b + 1]], axis=2)
        vv = jnp.concatenate([v_dense[b : b + 1, :, : int(cursors[b])], cv[b : b + 1]], axis=2)
        for t in range(valid[b]):
            L = int(cursors[b]) + t + 1
            want = ref.attention(
                q[b : b + 1, :, t : t + 1], kk[:, :, :L], vv[:, :, :L],
                causal=True, q_offset=L - 1,
            )
            np.testing.assert_allclose(
                np.array(out[b, :, t], np.float32), np.array(want[0, :, 0], np.float32),
                rtol=2e-5, atol=2e-5,
            )


# =====================================================================================
# engine — continuous batching vs the unbatched path
# =====================================================================================
@pytest.fixture(scope="module")
def small_model():
    cfg = dataclasses.replace(get_config("qwen2-0.5b", smoke=True), dtype="float32")
    model = build_model(cfg)
    params = model.init_params(jax.random.key(0))
    return cfg, model, params


def unbatched_greedy(cfg, model, params, prompt, n):
    toks = jnp.asarray([prompt], jnp.int32)
    logits, caches = model.prefill(params, toks, max_len=len(prompt) + n + 1)
    out = [int(jnp.argmax(logits[0, 0, : cfg.vocab]))]
    for g in range(n - 1):
        l, caches = model.decode_step(
            params, caches, jnp.asarray([out[-1]], jnp.int32), len(prompt) + g
        )
        out.append(int(jnp.argmax(l[0, : cfg.vocab])))
    return out


def test_engine_streams_mixed_lengths_matches_unbatched(small_model):
    cfg, model, params = small_model
    rng = np.random.default_rng(0)
    lengths = (5, 9, 16, 3, 12)
    prompts = [rng.integers(0, cfg.vocab, size=L).tolist() for L in lengths]
    n_gen = 6
    reqs = [Request(
            rid=i,
            prompt=p,
            params=GenerationParams(max_new_tokens=n_gen),
        ) for i, p in enumerate(prompts)]
    eng = ServeEngine(
        model, params,
        EngineConfig(num_pages=32, page_size=4, max_batch=4, max_pages_per_seq=8),
    )
    results = eng.run(reqs)
    assert set(results) == set(range(len(prompts)))
    for i, p in enumerate(prompts):
        assert results[i].generated == unbatched_greedy(cfg, model, params, p, n_gen)
    m = eng.metrics()
    assert m["requests"] == len(prompts)
    assert m["generated_tokens"] == len(prompts) * n_gen


def test_engine_preempts_under_page_pressure_and_stays_exact(small_model):
    cfg, model, params = small_model
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab, size=8).tolist() for _ in range(3)]
    n_gen = 10
    reqs = [Request(
            rid=i,
            prompt=p,
            params=GenerationParams(max_new_tokens=n_gen),
        ) for i, p in enumerate(prompts)]
    # 9 usable pages; each sequence grows to ceil(18/4) = 5 pages -> contention
    eng = ServeEngine(
        model, params,
        EngineConfig(num_pages=10, page_size=4, max_batch=3, max_pages_per_seq=6),
    )
    results = eng.run(reqs)
    assert eng.metrics()["preemptions"] >= 1
    for i, p in enumerate(prompts):
        assert results[i].generated == unbatched_greedy(cfg, model, params, p, n_gen)


def test_engine_prefix_sharing_exact_and_saves_pages(small_model):
    """Shared-prefix burst: outputs are token-exact vs. sharing disabled, and
    the shared pool peaks far lower (capacity O(unique tokens))."""
    cfg, model, params = small_model
    rng = np.random.default_rng(3)
    prefix = rng.integers(0, cfg.vocab, size=16).tolist()
    prompts = [prefix + rng.integers(0, cfg.vocab, size=4).tolist() for _ in range(4)]
    n_gen = 5
    make_reqs = lambda: [
        Request(
                rid=i,
                prompt=p,
                params=GenerationParams(max_new_tokens=n_gen),
            ) for i, p in enumerate(prompts)
    ]
    econf = EngineConfig(num_pages=48, page_size=4, max_batch=4, max_pages_per_seq=8)
    eng_on = ServeEngine(model, params, econf)
    eng_off = ServeEngine(model, params, dataclasses.replace(econf, prefix_sharing=False))
    res_on = eng_on.run(make_reqs())
    res_off = eng_off.run(make_reqs())
    for i in range(len(prompts)):
        assert res_on[i].generated == res_off[i].generated
        assert res_on[i].generated == unbatched_greedy(cfg, model, params, prompts[i], n_gen)
    m_on, m_off = eng_on.metrics(), eng_off.metrics()
    assert m_on["pages_shared"] > 0 and m_off["pages_shared"] == 0
    # 4 sequences share 4 prefix pages: 12 of the pool's pages never needed
    assert m_on["peak_pages_in_use"] <= m_off["peak_pages_in_use"] - 12


def test_engine_forced_cow_identical_prompts_exact(small_model):
    """Identical prompts whose length is NOT page-aligned share even the partial
    last page; the first decode append of each sequence scatters into it, so
    copy-on-write MUST fire — and outputs still match the unbatched oracle."""
    cfg, model, params = small_model
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, cfg.vocab, size=10).tolist()  # 10 % 4 != 0
    n_gen = 6
    reqs = [Request(
            rid=i,
            prompt=list(prompt),
            params=GenerationParams(max_new_tokens=n_gen),
        ) for i in range(3)]
    eng = ServeEngine(
        model, params,
        EngineConfig(num_pages=32, page_size=4, max_batch=3, max_pages_per_seq=8),
    )
    results = eng.run(reqs)
    m = eng.metrics()
    assert m["cow_copies"] >= 2  # every co-tenant of the partial page but one
    assert m["pages_shared"] >= 6  # 3 pages adopted by each of requests 1, 2
    want = unbatched_greedy(cfg, model, params, prompt, n_gen)
    for i in range(3):
        assert results[i].generated == want


def test_engine_sharing_stays_exact_under_preemption(small_model):
    """Tiny pool + shared prefixes: preemption frees only refcount-zero pages
    and re-admission re-shares what survived; greedy outputs stay exact."""
    cfg, model, params = small_model
    rng = np.random.default_rng(5)
    prefix = rng.integers(0, cfg.vocab, size=8).tolist()
    prompts = [prefix + rng.integers(0, cfg.vocab, size=2).tolist() for _ in range(3)]
    n_gen = 10
    reqs = [Request(
            rid=i,
            prompt=p,
            params=GenerationParams(max_new_tokens=n_gen),
        ) for i, p in enumerate(prompts)]
    # 10 usable pages; the full batch peaks at 2 shared + 3x3 own = 11 -> contention
    eng = ServeEngine(
        model, params,
        EngineConfig(num_pages=11, page_size=4, max_batch=3, max_pages_per_seq=6),
    )
    results = eng.run(reqs)
    m = eng.metrics()
    assert m["preemptions"] >= 1
    assert m["pages_shared"] > 0
    for i, p in enumerate(prompts):
        assert results[i].generated == unbatched_greedy(cfg, model, params, p, n_gen)


@pytest.mark.parametrize("kv_dtype,bound", [("int8", 0.75), ("int4", 2.0)])
def test_engine_quantized_kv_bounded_error_and_smaller_pool(small_model, kv_dtype, bound):
    """The whole serving stack over intN pages: same shared-prefix burst
    (adoption + forced CoW on the partial last page) through an f32 and a
    quantized engine. All requests complete, prefix sharing and CoW fire
    identically (allocator is representation-blind), the pool holds the same
    tokens in far fewer bytes, and logits on identical contexts stay within a
    calibrated bound of f32."""
    cfg, model, params = small_model
    rng = np.random.default_rng(6)
    prefix = rng.integers(0, cfg.vocab, size=10).tolist()  # 10 % 4 != 0 -> CoW
    prompts = [list(prefix) for _ in range(2)]
    prompts += [prefix + rng.integers(0, cfg.vocab, size=3).tolist()]
    n_gen = 5
    make_reqs = lambda: [
        Request(rid=i, prompt=list(p), params=GenerationParams(max_new_tokens=n_gen))
        for i, p in enumerate(prompts)
    ]
    econf = EngineConfig(num_pages=32, page_size=4, max_batch=3, max_pages_per_seq=8,
                         record_logits=True)
    eng_f32 = ServeEngine(model, params, econf)
    eng_q = ServeEngine(model, params, dataclasses.replace(econf, kv_dtype=kv_dtype))
    res_f32 = eng_f32.run(make_reqs())
    res_q = eng_q.run(make_reqs())
    assert set(res_q) == set(range(len(prompts)))
    assert all(len(res_q[r].generated) == n_gen for r in res_q)
    m_f32, m_q = eng_f32.metrics(), eng_q.metrics()
    # allocator behavior identical across representations
    assert m_q["pages_shared"] == m_f32["pages_shared"] > 0
    assert m_q["cow_copies"] == m_f32["cow_copies"] >= 1
    assert m_q["peak_pages_in_use"] == m_f32["peak_pages_in_use"]
    # capacity: same pages, a fraction of the bytes
    assert m_f32["kv_pool_bytes"] / m_q["kv_pool_bytes"] >= 1.9
    err = aligned_max_logit_err(eng_f32, eng_q, res_f32, res_q)
    assert 0 < err < bound, f"{kv_dtype} max logit err {err} outside (0, {bound})"


def test_engine_quant_dense_view_matches_prefill_within_scale_bound(small_model):
    """The quantized scatter path implements the layout map: reading the int8
    pool back through LayoutPaged offsets reproduces the dense prefill cache
    elementwise within half a quantization step of each (page, head) scale."""
    cfg, model, params = small_model
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, cfg.vocab, size=10).tolist()
    eng = ServeEngine(
        model, params,
        EngineConfig(num_pages=16, page_size=4, max_batch=2, max_pages_per_seq=8,
                     kv_dtype="int8"),
    )
    eng.submit(Request(rid=0, prompt=prompt, params=GenerationParams(max_new_tokens=1)))
    eng._t0 = 0.0
    eng.queue.push(eng._pending.pop())
    eng._prefill_monolithic(eng._admit_monolithic(0.0))
    layout = eng.cache.layout_for(0)
    assert layout.is_unique() and not layout.is_strided()
    k_paged, _ = eng.cache.dense_view(0)  # decoded through the accessor
    _, caches = model.prefill(params, jnp.asarray([prompt], jnp.int32), max_len=12)
    k_dense = np.array(caches[0]["k"][0, 0, :, : len(prompt)], np.float32)
    # per-(page, head) half-step bound, gathered to each token's page
    scales = np.array(eng.cache.pools[0]["k"]["scale"][0])  # (num_pages, Hkv)
    pages = np.array(eng.cache.pages_of[0])[
        np.arange(len(prompt)) // eng.cache.page_size
    ]
    bound = 0.5 * scales[pages].T[:, :, None] + 1e-6  # (Hkv, len, 1)
    assert np.all(np.abs(np.array(k_paged, np.float32) - k_dense) <= bound)


def test_engine_cache_dense_view_matches_layout(small_model):
    """The pool contents read back through LayoutPaged offsets equal the dense
    prefill cache — the scatter writes implement exactly the layout's map."""
    cfg, model, params = small_model
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, cfg.vocab, size=10).tolist()
    eng = ServeEngine(
        model, params,
        EngineConfig(num_pages=16, page_size=4, max_batch=2, max_pages_per_seq=8),
    )
    eng.submit(Request(rid=0, prompt=prompt, params=GenerationParams(max_new_tokens=1)))
    eng._t0 = 0.0
    eng.queue.push(eng._pending.pop())
    eng._prefill_monolithic(eng._admit_monolithic(0.0))
    layout = eng.cache.layout_for(0)
    assert layout.is_unique() and not layout.is_contiguous() and not layout.is_strided()
    k_paged, _ = eng.cache.dense_view(0)
    _, caches = model.prefill(params, jnp.asarray([prompt], jnp.int32), max_len=12)
    k_dense = caches[0]["k"][0, 0, :, : len(prompt)]  # layer 0: (Hkv, len, Dh)
    np.testing.assert_allclose(
        np.array(k_paged, np.float32), np.array(k_dense, np.float32), rtol=1e-6, atol=1e-6
    )


# =====================================================================================
# chunked prefill (mixed steps) — token-exact vs the monolithic engine
# =====================================================================================
def _staggered_shared_requests(cfg, rng):
    """Donor (long decode keeps it resident) + filler (frees its slot) +
    followers (admitted MID-donor, adopt its published prefix pages and skip
    their compute) — deterministic, no wall-clock staging."""
    prefix = rng.integers(0, cfg.vocab, size=16).tolist()
    return [
        (prefix + rng.integers(0, cfg.vocab, size=4).tolist(), 11),
        (rng.integers(0, cfg.vocab, size=5).tolist(), 2),
        (prefix + rng.integers(0, cfg.vocab, size=3).tolist(), 5),
        (list(prefix), 5),  # whole-prompt adoption incl. the partial page
    ]


def _run_pair(model, params, econf, reqs_spec):
    mk = lambda: [
        Request(rid=i, prompt=list(p), params=GenerationParams(max_new_tokens=n))
        for i, (p, n) in enumerate(reqs_spec)
    ]
    eng_m = ServeEngine(model, params, econf)
    eng_c = ServeEngine(
        model, params,
        dataclasses.replace(econf, chunked_prefill=True, chunk_tokens=8),
    )
    return eng_m.run(mk()), eng_c.run(mk()), eng_m, eng_c


@pytest.mark.parametrize("kv_dtype", ["f32", "int8"])
def test_engine_chunked_exact_with_compute_skip(small_model, kv_dtype):
    """Chunked-on vs chunked-off token-exact on a shared-prefix workload where
    the followers' first chunk starts PAST the adopted pages (compute skip),
    across multi-chunk prompts. int4 is exercised separately: its cross-chunk
    reads go through 4-bit pages where monolithic prefill attends f32, so
    multi-chunk exactness is not a structural guarantee at that width."""
    cfg, model, params = small_model
    reqs_spec = _staggered_shared_requests(cfg, np.random.default_rng(3))
    econf = EngineConfig(num_pages=48, page_size=4, max_batch=2,
                         max_pages_per_seq=9, kv_dtype=kv_dtype)
    res_m, res_c, eng_m, eng_c = _run_pair(model, params, econf, reqs_spec)
    for i in range(len(reqs_spec)):
        assert res_m[i].generated == res_c[i].generated, i
    m = eng_c.metrics()
    assert m["prefill_tokens_skipped"] > 0  # followers skipped the prefix
    assert m["pages_shared"] > 0
    assert eng_m.metrics()["prefill_tokens_skipped"] == 0  # monolithic never skips


def test_engine_chunked_skip_matches_cold_request(small_model):
    """A skipped-prefix request produces the same tokens as a cold request of
    the same prompt (sharing off): the adopted pages hold exactly what its own
    prefill would have computed."""
    cfg, model, params = small_model
    reqs_spec = _staggered_shared_requests(cfg, np.random.default_rng(3))
    econf = EngineConfig(num_pages=48, page_size=4, max_batch=2, max_pages_per_seq=9)
    mk = lambda: [
        Request(rid=i, prompt=list(p), params=GenerationParams(max_new_tokens=n))
        for i, (p, n) in enumerate(reqs_spec)
    ]
    warm = ServeEngine(
        model, params, dataclasses.replace(econf, chunked_prefill=True, chunk_tokens=8)
    )
    cold = ServeEngine(
        model, params,
        dataclasses.replace(econf, chunked_prefill=True, chunk_tokens=8,
                            prefix_sharing=False),
    )
    res_w, res_c = warm.run(mk()), cold.run(mk())
    assert warm.metrics()["prefill_tokens_skipped"] > 0
    assert cold.metrics()["prefill_tokens_skipped"] == 0
    for i in range(len(reqs_spec)):
        assert res_w[i].generated == res_c[i].generated, i


def test_engine_chunked_int4_exact_single_chunk_sharing_and_cow(small_model):
    """int4 pages stay token-exact wherever attention never crosses a chunk
    boundary: single-page prompts with partial-page adoption + forced CoW —
    the whole sharing machinery over 4-bit pages, chunked vs monolithic."""
    cfg, model, params = small_model
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cfg.vocab, size=6).tolist()
    filler = rng.integers(0, cfg.vocab, size=5).tolist()
    reqs_spec = [(prompt, 12), (filler, 2), (prompt, 5), (prompt, 5)]
    econf = EngineConfig(num_pages=24, page_size=8, max_batch=2,
                         max_pages_per_seq=4, kv_dtype="int4")
    res_m, res_c, eng_m, eng_c = _run_pair(model, params, econf, reqs_spec)
    for i in range(len(reqs_spec)):
        assert res_m[i].generated == res_c[i].generated, i
    m = eng_c.metrics()
    assert m["pages_shared"] >= 1 and m["cow_copies"] >= 1


def test_engine_chunked_preemption_mid_prefill_stays_exact(small_model):
    """A decoding sequence's page append exhausts the pool while a long prompt
    is mid-prefill: the PREFILLING slot is preempted (cursor reset, deferred
    index entries discarded), re-admitted, and the final tokens still match the
    monolithic engine."""
    cfg, model, params = small_model
    rng = np.random.default_rng(7)
    long_p = rng.integers(0, cfg.vocab, size=44).tolist()
    short_p = rng.integers(0, cfg.vocab, size=4).tolist()
    reqs_spec = [(long_p, 4), (short_p, 10)]
    econf = EngineConfig(num_pages=16, page_size=4, max_batch=2, max_pages_per_seq=12)
    mk = lambda: [
        Request(rid=i, prompt=list(p), params=GenerationParams(max_new_tokens=n))
        for i, (p, n) in enumerate(reqs_spec)
    ]
    eng_m = ServeEngine(model, params, econf)
    eng_c = ServeEngine(
        model, params,
        dataclasses.replace(econf, chunked_prefill=True, chunk_tokens=4),
    )
    victim_phases = []
    orig = eng_c.scheduler._preempt_one

    def spying_preempt(queue, keep_slot):
        victims = [s for s in eng_c.scheduler.running if s != keep_slot]
        if victims:
            victim_phases.append(eng_c.scheduler.running[victims[-1]].phase)
        return orig(queue, keep_slot)

    eng_c.scheduler._preempt_one = spying_preempt
    res_m, res_c = eng_m.run(mk()), eng_c.run(mk())
    assert PREFILLING in victim_phases  # the long prompt was evicted mid-prefill
    assert eng_c.metrics()["preemptions"] >= 1
    for i in range(len(reqs_spec)):
        assert res_m[i].generated == res_c[i].generated, i


def test_engine_chunked_mixed_lengths_exact_and_single_compile_family(small_model):
    """Mixed prompt lengths through the chunked engine match the unbatched
    oracle, and the engine compiles NO per-prompt-length prefill functions —
    the traced-cursor chunk step is the only prefill compile family."""
    cfg, model, params = small_model
    rng = np.random.default_rng(0)
    lengths = (5, 9, 16, 3, 12)
    prompts = [rng.integers(0, cfg.vocab, size=L).tolist() for L in lengths]
    n_gen = 6
    reqs = [Request(
            rid=i,
            prompt=p,
            params=GenerationParams(max_new_tokens=n_gen),
        ) for i, p in enumerate(prompts)]
    eng = ServeEngine(
        model, params,
        EngineConfig(num_pages=32, page_size=4, max_batch=4, max_pages_per_seq=8,
                     chunked_prefill=True, chunk_tokens=8),
    )
    results = eng.run(reqs)
    for i, p in enumerate(prompts):
        assert results[i].generated == unbatched_greedy(cfg, model, params, p, n_gen)
    assert not eng._prefill_fns  # monolithic path never compiled


# =====================================================================================
# impossible requests fail loudly instead of wedging the queue
# =====================================================================================
def test_submit_rejects_prompt_larger_than_pool(small_model):
    cfg, model, params = small_model
    eng = ServeEngine(
        model, params,
        EngineConfig(num_pages=4, page_size=4, max_batch=2, max_pages_per_seq=16),
    )
    with pytest.raises(ValueError, match="usable pages"):
        eng.submit(Request(
                rid=0,
                prompt=list(range(1, 40)),
                params=GenerationParams(max_new_tokens=2),
            ))


def test_grown_context_fails_request_and_serves_the_rest(small_model):
    """A request whose context GROWS past the whole pool (legal at submit
    time) is failed with .error set — the engine keeps serving everything
    else instead of spinning on an unadmittable queue head."""
    cfg, model, params = small_model
    eng = ServeEngine(
        model, params,
        EngineConfig(num_pages=6, page_size=4, max_batch=2, max_pages_per_seq=8),
    )
    ok = Request(rid=0, prompt=[5, 6, 7], params=GenerationParams(max_new_tokens=3))
    # 18-token prompt fits 5 of 5 usable pages at submit; +8 new tokens can
    # never fit — the scheduler must fail it at (re-)admission, not spin
    doomed = Request(
            rid=1,
            prompt=list(range(1, 19)),
            params=GenerationParams(max_new_tokens=8),
        )
    eng.submit_all([ok, doomed])
    # simulate the grown-context state preemption would produce
    eng._pending[1].generated.extend([9, 9, 9])
    results = eng.run()
    assert results[0].error is None and len(results[0].generated) == 3
    assert results[1].error is not None and "pool" in results[1].error
    assert eng.metrics()["failed"] == 1


# =====================================================================================
# the layer scan writes the pool in place: exact against per-layer slicing
# =====================================================================================
def _per_layer_step(model, params, caches, tokens, block_tables, context_lens, *,
                    kv_spec=None, write_tables=None, n_new=None, last_index=None,
                    active=None, spec_verify=False):
    """decode_step_paged as it was before the flat pool: the stacked pool is a
    scanned input and the new pool a scanned output, so each layer's pool is
    sliced out, updated and restacked, and every layer addresses its own
    pages 0..P-1 through the unshifted tables."""
    from repro.models.layers import NULL_SHARDER, apply_embed, apply_lm_head, apply_norm
    from repro.models.transformer import KINDS, block_program

    cfg = model.cfg
    chunk = tokens.ndim == 2 and not spec_verify
    if active is not None and not chunk:
        block_tables = jnp.where(active[:, None] > 0, block_tables, 0)
        context_lens = jnp.where(active > 0, context_lens, 0)
    x = apply_embed(params["embed"], tokens if tokens.ndim == 2 else tokens[:, None])
    new_caches = []
    for (kind, _), p, cache in zip(block_program(cfg), params["blocks"], caches):
        blk = KINDS[kind]

        def body(xc, pc, _blk=blk):
            pl, cl = pc
            if chunk:
                return _blk.prefill_chunk_paged(
                    cfg, pl, xc, cl, block_tables, write_tables, context_lens,
                    n_new, NULL_SHARDER, kv_spec=kv_spec)
            if spec_verify:
                return _blk.verify_paged(cfg, pl, xc, cl, block_tables, context_lens,
                                         NULL_SHARDER, kv_spec=kv_spec)
            return _blk.decode_paged(cfg, pl, xc, cl, block_tables, context_lens,
                                     NULL_SHARDER, kv_spec=kv_spec)

        x, cache = jax.lax.scan(body, x, (p, cache))
        new_caches.append(cache)
    x = apply_norm(cfg, x, params["final_norm"])
    if spec_verify:
        return apply_lm_head(cfg, params["embed"], x), new_caches
    if chunk:
        x = jnp.take_along_axis(x, last_index[:, None, None], axis=1)
    return apply_lm_head(cfg, params["embed"], x)[:, 0], new_caches


def _random_pool(model, num_pages, page_size, kv_spec, seed):
    """A pool of random K/V in every page, the null pages included, so that a
    read or a write through a wrong page shows."""
    pools = model.init_paged_cache(num_pages, page_size, kv_spec=kv_spec)
    leaves, tree = jax.tree.flatten(pools)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    filled = []
    for key, leaf in zip(keys, leaves):
        if leaf.dtype == jnp.int8:
            filled.append(jax.random.randint(key, leaf.shape, -100, 100, jnp.int8))
        elif kv_spec is not None:  # per-(page, head) scales
            filled.append(jax.random.uniform(key, leaf.shape, leaf.dtype, 0.01, 0.05))
        else:
            filled.append(jax.random.normal(key, leaf.shape, leaf.dtype))
    return jax.tree.unflatten(tree, filled)


@pytest.mark.parametrize("mode", ["decode", "chunk", "verify"])
@pytest.mark.parametrize("kv_dtype", ["f32", "int8", "int4"])
def test_flat_pool_layer_scan_matches_per_layer_slicing(small_model, kv_dtype, mode):
    """decode_step_paged, whose layer scan carries the pool as one flat page
    space (layer l's page j is flat page l·P + j), gives bit-equal logits and
    bit-equal pools to the per-layer slice / update / restack scan: decode
    with an inactive row routed to the null page, a chunk whose write table
    nulls an adopted shared-prefix page and whose bucket has a pad page, and
    a speculative verify window that starts mid-page."""
    from repro.serving.engine.kvquant import KV_DTYPES

    cfg, model, params = small_model
    kv_spec = KV_DTYPES[kv_dtype]
    ps, num_pages = 8, 13
    pools = _random_pool(model, num_pages, ps, kv_spec, seed=len(mode))
    tables = jnp.asarray([[3, 7, 1, 9], [2, 4, 0, 0], [5, 11, 6, 12]], jnp.int32)
    kw = dict(kv_spec=kv_spec)
    if mode == "decode":
        tokens = jnp.asarray([17, 230, 401], jnp.int32)
        lens = jnp.asarray([20, 9, 8], jnp.int32)  # row 2 starts a fresh page
        kw.update(active=jnp.asarray([1, 0, 1], jnp.int32))
    elif mode == "chunk":
        tokens = jax.random.randint(jax.random.key(3), (3, 2 * ps), 0, cfg.vocab)
        lens = jnp.asarray([0, 8, 16], jnp.int32)  # page-aligned cursors
        # row 0 adopted its first page from a donor: read, never written
        write = tables.at[0, 0].set(0)
        kw.update(write_tables=write, n_new=jnp.asarray([16, 8, 16], jnp.int32),
                  last_index=jnp.asarray([15, 7, 15], jnp.int32))
    else:
        tokens = jax.random.randint(jax.random.key(4), (3, 4), 0, cfg.vocab)
        lens = jnp.asarray([5, 13, 19], jnp.int32)  # drafts cross page ends
        kw.update(active=jnp.asarray([1, 1, 0], jnp.int32), spec_verify=True)
    want_logits, want_pools = jax.jit(
        lambda c: _per_layer_step(model, params, c, tokens, tables, lens, **kw)
    )(pools)
    got_logits, got_pools = jax.jit(
        lambda c: model.decode_step_paged(params, c, tokens, tables, lens, **kw)
    )(pools)
    np.testing.assert_array_equal(np.asarray(got_logits), np.asarray(want_logits))
    assert jax.tree.structure(got_pools) == jax.tree.structure(want_pools)
    for got, want, before in zip(jax.tree.leaves(got_pools), jax.tree.leaves(want_pools),
                                 jax.tree.leaves(pools)):
        assert got.shape == want.shape == before.shape
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # the step wrote something in every layer: the comparison is not vacuous
    for got, before in zip(jax.tree.leaves(got_pools), jax.tree.leaves(pools)):
        for layer in range(cfg.n_layers):
            assert not np.array_equal(np.asarray(got[layer]), np.asarray(before[layer]))
