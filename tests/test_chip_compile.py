"""The paged kernels of the serving path compile for a TPU v5e.

Each case lowers one Pallas kernel with ``interpret=False`` at
Llama-3.2-1B's attention widths and compiles it for one chip of a described
``v5e:2x2`` topology. No chip is needed: the TPU compiler refuses here what
the chip would refuse (block shapes off the (8, 128) tiling, ops Mosaic
cannot legalize), which interpret-mode tests cannot show.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file. Keep every such compile in this one file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import paged_attention as pa

# llama3.2-1b attention widths; page_size 16 and the engine's default chunk
# (2 pages) as served; B 8 and a 400-token context as in chip_smoke.py
HQ, HKV, D, PAGE = 32, 8, 64, 16
B, CTX = 8, 400
CHUNK = 2 * PAGE
VERIFY = 4  # speculative verify width K + 1 at K = 3
KV_REPS = ("bf16", "int8", "int4")


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _pool(sharding, rep: str, batch: int, ctx: int):
    """Abstract (pool leaves, block table) for ``batch`` sequences of ``ctx``
    tokens: the K and V leaves in kernel-argument order."""
    pages_per_seq = -(-ctx // PAGE) + 1
    num_pages = batch * pages_per_seq + 1
    table = _shape(sharding, (batch, pages_per_seq), jnp.int32)
    if rep == "bf16":
        page = _shape(sharding, (num_pages, HKV, PAGE, D), jnp.bfloat16)
        return (page, page), table
    dq = D if rep == "int8" else D // 2
    q = _shape(sharding, (num_pages, HKV, PAGE, dq), jnp.int8)
    scale = _shape(sharding, (num_pages, HKV), jnp.float32)
    return (q, scale, q, scale), table


def _compile_decode(sharding, rep: str, batch: int, ctx: int) -> str:
    pool, table = _pool(sharding, rep, batch, ctx)
    if rep == "bf16":
        fn = lambda q, *a: pa.paged_flash_decode(q, *a, interpret=False)
    else:
        fn = lambda q, *a: pa.paged_flash_decode_quant(
            q, *a, bits=int(rep[3:]), interpret=False)
    q = _shape(sharding, (batch, HQ, 1, D), jnp.bfloat16)
    lens = _shape(sharding, (batch,), jnp.int32)
    return jax.jit(fn).lower(q, *pool, table, lens).compile().as_text()


@pytest.mark.parametrize("rep", KV_REPS)
def test_paged_decode_compiles_for_v5e(one_chip, rep):
    assert "tpu_custom_call" in _compile_decode(one_chip, rep, B, CTX)


@pytest.mark.parametrize("width", [VERIFY, CHUNK], ids=["verify", "chunk"])
@pytest.mark.parametrize("rep", KV_REPS)
def test_paged_prefill_chunk_compiles_for_v5e(one_chip, rep, width):
    pool, table = _pool(one_chip, rep, B, CTX)
    if rep == "bf16":
        fn = lambda q, ck, cv, *a: pa.paged_flash_prefill_chunk(
            q, ck, cv, *a, interpret=False)
    else:
        fn = lambda q, ck, cv, *a: pa.paged_flash_prefill_chunk_quant(
            q, ck, cv, *a, bits=int(rep[3:]), interpret=False)
    q = _shape(one_chip, (B, HQ, width, D), jnp.bfloat16)
    chunk_kv = _shape(one_chip, (B, HKV, width, D), jnp.bfloat16)
    cursors = _shape(one_chip, (B,), jnp.int32)
    text = jax.jit(fn).lower(q, chunk_kv, chunk_kv, *pool, table, cursors).compile().as_text()
    assert "tpu_custom_call" in text


def test_deployment_sized_block_table_compiles_for_v5e(one_chip):
    """Block tables ride scalar prefetch into SMEM: a 32-sequence batch at a
    4096-token context (32 x 257 int32 entries) must still fit."""
    assert "tpu_custom_call" in _compile_decode(one_chip, "bf16", 32, 4096)
