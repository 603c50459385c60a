"""Accessor laws — the paper's Table II, functionally restated (DESIGN.md §8).

  ROUND-TRIP   decay(from_codomain(x)) ≈ x  (within quantization error bound)
  ACCESS       access(p, i) == decay(p)[i]
  STORE        access(store(p, i, v), i) ≈ v ; other offsets untouched
  OFFSET       A::offset_policy(a).access(offset(p, i), 0) == access(p, i)
  ACCUMULATE   store-twice linearity (the TPU atomic analogue)
"""
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import (
    AccumulateAccessor,
    BasicAccessor,
    BitPackedAccessor,
    MemorySpace,
    MemorySpaceAccessor,
    QuantizedAccessor,
    RestrictAccessor,
    require_same_space,
)

floats = st.lists(
    st.floats(-100, 100, allow_nan=False, width=32), min_size=8, max_size=64
)


@settings(max_examples=30, deadline=None)
@given(floats)
def test_basic_roundtrip_access_store(vals):
    acc = BasicAccessor(jnp.float32)
    buf = acc.from_codomain(jnp.array(vals, jnp.float32))
    np.testing.assert_array_equal(np.array(acc.decay(buf)), np.float32(vals))
    i = len(vals) // 2
    assert float(acc.access(buf, i)) == np.float32(vals[i])
    buf2 = acc.store(buf, i, 7.5)
    assert float(acc.access(buf2, i)) == 7.5
    assert float(acc.access(buf2, 0)) == np.float32(vals[0])  # untouched


@settings(max_examples=30, deadline=None)
@given(floats, st.sampled_from([4, 8]))
def test_quantized_roundtrip_error_bound(vals, bits):
    acc = QuantizedAccessor(jnp.float32, bits=bits, block=8)
    x = jnp.array(vals, jnp.float32)
    bufs = acc.from_codomain(x)
    rec = acc.decay(bufs, span=len(vals))
    # error bound: half a quantization step per block
    xs = np.array(x).reshape(-1)
    nb = -(-len(xs) // 8)
    pad = np.pad(xs, (0, nb * 8 - len(xs))).reshape(nb, 8)
    step = np.abs(pad).max(axis=1) / acc.qmax
    bound = np.repeat(np.maximum(step, 1e-7), 8)[: len(xs)] * 0.5 + 1e-6
    assert np.all(np.abs(np.array(rec) - xs) <= bound + 1e-5)


@settings(max_examples=50, deadline=None)
@given(
    st.integers(1, 3),            # whole blocks
    st.integers(-1, 1),           # span offset: straddle / hit / overhang a boundary
    st.sampled_from([4, 8]),      # bits — int4 exercises nibble packing at tails
    st.integers(1, 9),            # block size, odd blocks make bytes straddle blocks
    st.data(),
)
def test_quantized_roundtrip_at_block_boundaries(nblocks, delta, bits, block, data):
    """Round-trip at spans exactly on, one under, and one over block boundaries
    — odd spans leave a pad nibble in the int4 byte stream, and per-offset
    access must agree with the bulk decay at both tails (nibble parity)."""
    span = max(1, nblocks * block + delta)
    vals = data.draw(
        st.lists(st.floats(-100, 100, allow_nan=False, width=32),
                 min_size=span, max_size=span)
    )
    acc = QuantizedAccessor(jnp.float32, bits=bits, block=block)
    bufs = acc.from_codomain(jnp.array(vals, jnp.float32))
    rec = np.array(acc.decay(bufs, span=span))
    xs = np.array(vals, np.float32)
    nb = -(-span // block)
    pad = np.pad(xs, (0, nb * block - span)).reshape(nb, block)
    step = np.abs(pad).max(axis=1) / acc.qmax
    bound = np.repeat(np.maximum(step, 1e-7), block)[:span] * 0.5 + 1e-5
    assert np.all(np.abs(rec - xs) <= bound)
    for i in {0, span // 2, span - 1}:  # both tails + a block interior
        assert float(acc.access(bufs, i)) == rec[i]


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 2), st.integers(-1, 1), st.sampled_from([4, 8]), st.data())
def test_quantized_store_roundtrip_at_tail_offsets(nblocks, delta, bits, data):
    """store/access at the first and last offsets around block boundaries:
    the written value reads back within half a step of the block's existing
    scale and every other offset is untouched (catches nibble-parity and
    read-modify-write bugs at odd int4 tails)."""
    block = 8
    span = max(1, nblocks * block + delta)
    vals = data.draw(
        st.lists(st.floats(-50, 50, allow_nan=False, width=32),
                 min_size=span, max_size=span)
    )
    acc = QuantizedAccessor(jnp.float32, bits=bits, block=block)
    bufs = acc.from_codomain(jnp.array(vals, jnp.float32))
    before = np.array(acc.decay(bufs, span=span))
    for i in (0, span - 1):
        scale = float(np.array(bufs["scale"])[i // block])
        # width=32 draws need float32-representable bounds: round the limit
        # to float32, toward zero so every draw stays inside the block's range
        lim = np.float32(abs(scale) * acc.qmax)
        if lim > abs(scale) * acc.qmax:
            lim = np.nextafter(lim, np.float32(0))
        v = data.draw(st.floats(-float(lim), float(lim), allow_nan=False,
                                width=32))
        b2 = acc.store(bufs, i, v)
        got = float(acc.access(b2, i))
        assert abs(got - v) <= max(scale, 1e-7) * 0.5 + 1e-5
        rest = np.array(acc.decay(b2, span=span))
        mask = np.arange(span) != i
        np.testing.assert_array_equal(rest[mask], before[mask])


def test_quantized_store_uses_block_scale():
    acc = QuantizedAccessor(jnp.float32, bits=8, block=4)
    bufs = acc.from_codomain(jnp.array([1.0, 2.0, 3.0, 4.0, 10.0, 20.0, 30.0, 40.0]))
    bufs = acc.store(bufs, 1, 3.5)
    got = float(acc.access(bufs, 1))
    assert abs(got - 3.5) <= 4.0 / 127 + 1e-6
    # storing beyond the block's representable range clips
    bufs = acc.store(bufs, 1, 1000.0)
    assert float(acc.access(bufs, 1)) <= 4.0 + 1e-6


def test_accessor_offset_law():
    for acc in [BasicAccessor(jnp.float32), QuantizedAccessor(jnp.float32, bits=8, block=4)]:
        x = jnp.arange(16, dtype=jnp.float32)
        bufs = acc.from_codomain(x)
        p2 = acc.offset(bufs, 4)
        a2 = acc.offset_policy
        np.testing.assert_allclose(
            float(a2.access(p2, 0)), float(acc.access(bufs, 4)), rtol=1e-6
        )


def test_bitpacked_roundtrip_and_bit_ops():
    acc = BitPackedAccessor()
    bits = jnp.array([True, False, True, True, False, False, True, False, True, True])
    bufs = acc.from_codomain(bits)
    assert bufs.dtype == jnp.uint8 and bufs.shape == (2,)
    np.testing.assert_array_equal(np.array(acc.decay(bufs)[:10]), np.array(bits))
    bufs = acc.store(bufs, 1, True)
    bufs = acc.store(bufs, 0, False)
    assert bool(acc.access(bufs, 1)) and not bool(acc.access(bufs, 0))


def test_accumulate_linearity():
    """The atomic-accessor law, TPU-adapted: order-independent accumulation."""
    acc = AccumulateAccessor(jnp.float32)
    buf = acc.from_codomain(jnp.zeros(4))
    idx = jnp.array([1, 1, 2, 1])
    vals = jnp.array([1.0, 2.0, 5.0, 4.0])
    buf = acc.store(buf, idx, vals)
    np.testing.assert_allclose(np.array(acc.decay(buf)), [0.0, 7.0, 5.0, 0.0])


def test_restrict_is_identity():
    acc = RestrictAccessor(jnp.float32)
    x = jnp.arange(4, dtype=jnp.float32)
    np.testing.assert_array_equal(np.array(acc.decay(acc.from_codomain(x))), np.array(x))


def test_memory_space_strong_typing():
    a = MemorySpaceAccessor(jnp.float32, MemorySpace.VMEM)
    b = MemorySpaceAccessor(jnp.float32, MemorySpace.HBM)
    c = MemorySpaceAccessor(jnp.float32, MemorySpace.ANY)
    with pytest.raises(TypeError):
        require_same_space(a, b)
    require_same_space(a, c)  # ANY unifies
    # offsetting a VMEM (alignment-carrying) accessor decays to ANY (paper's
    # over-aligned pointer example)
    assert a.offset_policy.space == MemorySpace.ANY
    assert b.offset_policy.space == MemorySpace.HBM
