import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import peaks
from repro.core.accessors import BasicAccessor
from repro.core.instrument import CountingAccessor, TrafficTally, counted_paged_decode

M = dict(n_layers=2, d_model=64, d_ff=128, n_heads=4, n_kv_heads=2, d_head=16,
         vocab=500)


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
    _, byt = peaks.decode_attn(M, lens, ps, kv_bytes=2, act_bytes=0)
    assert tally.bytes_loaded == byt
    # the bf16 pool is half the bytes of an f32 one: the dtype is priced
    _, byt32 = peaks.decode_attn(M, lens, ps, kv_bytes=4, act_bytes=0)
    assert byt32 == 2 * byt


def test_model_flops_counts_attention_at_each_position():
    mm = 2 * peaks.matmul_params(M)
    one = peaks.model_flops(M, [(0, 1)])
    assert one == mm + peaks.attn_flops(M, 1)
    # a 3-token prefill = positions 0, 1, 2 attending to 1, 2, 3 keys
    assert peaks.model_flops(M, [(0, 3)]) == 3 * mm + peaks.attn_flops(M, 6)
    assert peaks.model_flops(M, [(5, 5)]) == 0.0


def test_chunk_ops_are_causal_and_roofline_names_its_bound():
    ops, byt = peaks.chunk_attn(M, cursor=32, n_new=16, kv_bytes=2)
    assert ops == 4.0 * 4 * 16 * (32 * 16 + 16 * 17 / 2)
    share, bound = peaks.roofline_share(ops, byt, 1e-3, "TPU v5 lite")
    assert 0 < share < 100 and bound == "memory"
