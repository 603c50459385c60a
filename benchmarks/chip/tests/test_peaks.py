import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import peaks
from benchmarks.chip.arch import dense_gqa
from repro.core.accessors import BasicAccessor
from repro.core.instrument import CountingAccessor, TrafficTally, counted_paged_decode

M = dict(n_layers=2, d_model=64, d_ff=128, n_heads=4, n_kv_heads=2, d_head=16,
         vocab=500)
LAYERS = dense_gqa.layers(M)


def one_head(span):
    """A layer of one query and one KV head of size 1 and no weights, so its
    attention FLOPs are 4 per key and its K/V bytes 2 per position and byte."""
    return peaks.Layer(d_model=1, n_heads=1, n_kv_heads=1, d_head=1, span=span,
                       ffn_params=0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
    assert peaks.peaks("TPU v5 lite")["flops_bf16"] == 197e12


def test_decode_bytes_match_counting_accessor_on_a_bf16_pool():
    rng = np.random.default_rng(0)
    b, hkv, d, ps, num_pages, max_pages = 4, 2, 16, 8, 17, 4
    lens = [29, 0, 9, 17]
    pool_k = rng.standard_normal((num_pages, hkv, ps, d)).astype(np.float32)
    pool_v = rng.standard_normal((num_pages, hkv, ps, d)).astype(np.float32)
    tables = rng.permutation(np.arange(1, num_pages))[: b * max_pages].reshape(b, max_pages)
    q = rng.standard_normal((b, 4, 1, d)).astype(np.float32)
    acc = CountingAccessor(BasicAccessor(element_type=jnp.bfloat16), TrafficTally())
    kb = acc.from_codomain(jnp.asarray(pool_k.reshape(-1), jnp.bfloat16))
    vb = acc.from_codomain(jnp.asarray(pool_v.reshape(-1), jnp.bfloat16))
    _, tally = counted_paged_decode(q, kb, vb, acc, tables, np.array(lens),
                                    pool_shape=(num_pages, hkv, ps, d))
    # one kernel call is one layer
    _, byt = peaks.decode_attn(LAYERS[:1], lens, ps, kv_bytes=2, act_bytes=0)
    assert tally.bytes_loaded == byt
    # the bf16 pool is half the bytes of an f32 one: the dtype is priced
    _, byt32 = peaks.decode_attn(LAYERS[:1], lens, ps, kv_bytes=4, act_bytes=0)
    assert byt32 == 2 * byt
    assert peaks.decode_attn(LAYERS, lens, ps, kv_bytes=2, act_bytes=0)[1] == 2 * byt


def test_model_flops_counts_attention_at_each_position():
    # per layer: q and o 2 * 64 * 4 * 16, k and v 2 * 64 * 2 * 16, the MLP
    # 3 * 64 * 128; then the head 64 * 500
    assert peaks.matmul_params(M, LAYERS) == 2 * (8192 + 4096 + 24576) + 32000
    mm = 2 * peaks.matmul_params(M, LAYERS)
    per_key = 4.0 * 2 * 4 * 16  # 2 layers of 4 heads of 16
    assert peaks.model_flops(M, LAYERS, [(0, 1)]) == mm + per_key
    # a 3-token prefill = positions 0, 1, 2 attending to 1, 2, 3 keys
    assert peaks.model_flops(M, LAYERS, [(0, 3)]) == 3 * mm + 6 * per_key
    assert peaks.model_flops(M, LAYERS, [(5, 5)]) == 0.0


def test_chunk_ops_are_causal_and_roofline_names_its_bound():
    ops, byt = peaks.chunk_attn(LAYERS[:1], cursor=32, n_new=16, kv_bytes=2)
    assert ops == 4.0 * 4 * 16 * (32 * 16 + 16 * 17 / 2)
    share, bound = peaks.roofline_share(ops, byt, 1e-3, "TPU v5 lite")
    assert 0 < share < 100 and bound == "memory"


def test_a_window_layer_attends_to_its_span_and_reads_only_its_keys():
    full, window = one_head(None), one_head(4)
    # positions 0..9: 1 + 2 + ... + 10 keys, and 1 + 2 + 3 + 4 + 6 * 4
    assert peaks.attn_flops([full], 0, 10) == 4.0 * 55
    assert peaks.attn_flops([window], 0, 10) == 4.0 * 34
    assert peaks.attn_flops([full, window], 0, 10) == 4.0 * (55 + 34)
    assert peaks.attn_flops([window], 2, 3) == 4.0 * 3  # inside the first window
    # chunks of a prompt add up to the prompt, and each reads K and V from
    # the window's first key to the chunk's end (2 tensors of 1 head of 1)
    for ly, first in ((full, 0), (window, 5)):
        ops = sum(peaks.chunk_attn([ly], c, n, kv_bytes=1, act_bytes=0)[0]
                  for c, n in ((0, 3), (3, 5), (8, 2)))
        assert ops == peaks.attn_flops([ly], 0, 10)
        _, byt = peaks.chunk_attn([ly], 8, 2, kv_bytes=1, act_bytes=0)
        assert byt == 2.0 * (10 - first)
    # a decode row of 10 keys on 4-key pages: every page under full
    # attention; under a window of 4 (keys 6..9) the pages 1 and 2
    ops, byt = peaks.decode_attn([full, window], [10], 4, kv_bytes=1, act_bytes=0)
    assert ops == 4.0 * (10 + 4)
    assert byt == 2.0 * 4 * (3 + 2)


def test_a_routed_layer_counts_its_router_and_this_chips_share_of_experts():
    d, f_e, experts, held, top_k = 8, 4, 64, 16, 8
    routed = peaks.routed_ffn(d, f_e, experts, held, top_k)
    # the router d * 64, and 8 of 64 experts of 3 * d * f_e on average, of
    # which a quarter are held here
    assert routed == d * experts + top_k * held / experts * 3 * d * f_e == 704.0
    assert peaks.routed_ffn(d, f_e, experts, held, top_k, shared_f=2) == 704.0 + 3 * d * 2
    bare = dict(d_model=d, n_heads=1, n_kv_heads=1, d_head=2, span=None)
    m = dict(d_model=d, vocab=10)
    with_experts = peaks.matmul_params(m, [peaks.Layer(**bare, ffn_params=routed)])
    without = peaks.matmul_params(m, [peaks.Layer(**bare, ffn_params=0)])
    assert with_experts - without == routed
