"""The paged kernels and step programs of the serving path compile for a
TPU v5e.

Each kernel case lowers one Pallas kernel with ``interpret=False`` at
Llama-3.2-1B's attention widths and compiles it for one chip of a described
``v5e:2x2`` topology. No chip is needed: the TPU compiler refuses here what
the chip would refuse (block shapes off the (8, 128) tiling, ops Mosaic
cannot legalize), which interpret-mode tests cannot show. The step cases
compile whole serving programs and read the compiled HLO: the layer scan
must write the KV pool in place, never copy or slice it.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file. Keep every such compile in this one file.
"""
from __future__ import annotations

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import paged_attention as pa
from repro.models.config import ModelConfig
from repro.models.transformer import Model
from repro.serving.engine.kvquant import KV_DTYPES
from repro.serving.speculative import NGramProposer, make_paged_serve_spec_multistep
from repro.serving.step import make_chunked_prefill_step, make_paged_serve_step

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


# Mellum2's attention: 32 / 4 heads of 128, 256-token pages, a 1,024-token
# window, a 32-row batch and a 69-page table (16,384 + 1,024 tokens)
WIN_HQ, WIN_HKV, WIN_D, WIN_PAGE, WINDOW, WIN_B = 32, 4, 128, 256, 1024, 32


@pytest.mark.parametrize("max_pages", [17, 69])
@pytest.mark.parametrize("kernel", ["decode", "chunk"])
def test_windowed_kernels_compile_for_v5e_on_a_five_page_grid(one_chip, kernel,
                                                              max_pages):
    """The windowed decode and chunk kernels compile for a v5e at Mellum2's
    widths, and the page axis of their grids is ceil(1024 / 256) + 1 = 5
    whatever the table's width."""
    sds = lambda s, dt: _shape(one_chip, s, dt)
    pool = sds((WIN_B * max_pages + 1, WIN_HKV, WIN_PAGE, WIN_D), jnp.bfloat16)
    table = sds((WIN_B, max_pages), jnp.int32)
    lens = sds((WIN_B,), jnp.int32)
    if kernel == "decode":
        fn = lambda *a: pa.paged_flash_decode(*a, window=WINDOW, interpret=False)
        args = (sds((WIN_B, WIN_HQ, 1, WIN_D), jnp.bfloat16), pool, pool, table, lens)
    else:
        fn = lambda *a: pa.paged_flash_prefill_chunk(*a, window=WINDOW, interpret=False)
        chunk = sds((1, WIN_HKV, 2 * WIN_PAGE, WIN_D), jnp.bfloat16)
        args = (sds((1, WIN_HQ, 2 * WIN_PAGE, WIN_D), jnp.bfloat16), chunk, chunk,
                pool, pool, sds((1, max_pages), jnp.int32), sds((1,), jnp.int32))
    grids = [e.params["grid_mapping"].grid for e in jax.make_jaxpr(fn)(*args).eqns
             if e.primitive.name == "pallas_call"]
    assert [g[2] for g in grids] == [5]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text and '"window":"1024"' in text


def test_deployment_sized_block_table_compiles_for_v5e(one_chip):
    """Block tables ride scalar prefetch into SMEM: a 32-sequence batch at a
    4096-token context (32 x 257 int32 entries) must still fit."""
    assert "tpu_custom_call" in _compile_decode(one_chip, "bf16", 32, 4096)


# ---- whole step programs: the KV pool is written in place ---------------------
# a small dense stack at the served pool geometry: 2 KV heads of 128,
# 256-token pages; an odd page count keeps the pool's shapes unlike any other
STEP_CFG = ModelConfig(name="pool-in-place", family="dense", n_layers=4, d_model=256,
                       vocab=512, n_heads=4, n_kv_heads=2, d_head=128, d_ff=512)
STEP_PAGE, STEP_B, STEP_MP, STEP_PAGES = 256, 8, 4, 1021
SPEC_K = 3
_HLO_OP = re.compile(r"^\s*(?:ROOT )?%(\S+) = (\w+)\[([\d,]*)\]\S* ([\w-]+)\(([^)]*)\)")
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) .*\{$")
_HLO_CALL = re.compile(r"(body|condition|calls|to_apply)=%([\w.-]+)")
_HLO_ARRAY = re.compile(r"\w+\[([\d,]*)\]\{([\d,]*)")


def _dims(text: str):
    return tuple(int(d) for d in text.split(",") if d)


def _row_major_params(hlo: str):
    """Shapes of the program's arguments that it receives row-major."""
    entry = hlo[hlo.index("entry_computation_layout"):].split("->", 1)[0]
    return {_dims(d) for d, m2m in _HLO_ARRAY.findall(entry)
            if _dims(m2m) == tuple(reversed(range(len(_dims(d)))))}


# a period of three windowed and one full layer, each with routed experts
# (4 of 8 held), at the same pool geometry: Mellum2's program at tiny widths
PERIOD_CFG = ModelConfig(
    name="period-in-place", family="moe", n_layers=8, d_model=256, vocab=512,
    n_heads=4, n_kv_heads=2, d_head=128, d_ff=256, n_experts=8, top_k=2,
    experts_held=4, window=512,
    layer_types=(("sliding_attention",) * 3 + ("full_attention",)) * 2,
    yarn_factor=16.0, yarn_original_max=8192)


def _step_program(sharding, rep: str, program: str, cfg=STEP_CFG):
    """(jitted step, abstract args, abstract pool leaves) of one serving program
    over a ``rep`` pool of ``cfg``."""
    model = Model(cfg)
    spec = KV_DTYPES[rep] if rep != "bf16" else None
    sds = lambda s, dt: _shape(sharding, s, dt)
    params = jax.tree.map(lambda a: sds(a.shape, a.dtype),
                          jax.eval_shape(model.init_params, jax.random.key(0)))
    pools = jax.tree.map(lambda a: sds(a.shape, a.dtype), jax.eval_shape(
        lambda: model.init_paged_cache(STEP_PAGES, STEP_PAGE, kv_spec=spec)))
    i32, b, mp = jnp.int32, STEP_B, STEP_MP
    slots = (sds((2, b), jnp.float32), sds((3, b), i32))
    vocab = cfg.vocab
    if program == "decode":
        fn = jax.jit(make_paged_serve_step(model, attn_impl="pallas", kv_spec=spec,
                                           vocab=vocab), donate_argnums=(1, 2, 4))
        args = (sds((b,), i32), sds((b, mp), i32), sds((b,), i32)) + slots
    elif program == "chunk":
        fn = jax.jit(make_chunked_prefill_step(model, attn_impl="pallas", kv_spec=spec),
                     donate_argnums=(1,))
        args = (sds((1, 2 * STEP_PAGE), i32), sds((1, mp), i32), sds((1, mp), i32),
                sds((1,), i32), sds((1,), i32), sds((1,), i32))
    else:
        hist_len = mp * STEP_PAGE + SPEC_K + 2
        proposer = NGramProposer(spec_tokens=SPEC_K, table_size=64, vocab=vocab,
                                 hist_len=hist_len)
        fn = jax.jit(make_paged_serve_spec_multistep(
            model, 2, proposer, attn_impl="pallas", kv_spec=spec, vocab=vocab),
            donate_argnums=(1, 2, 4, 7, 8))
        args = ((sds((b,), i32), sds((b, mp), i32), sds((b,), i32)) + slots
                + (sds((b, hist_len), i32), sds((b, 65), i32)))
    return fn, (params, pools) + args, jax.tree.leaves(pools)


def _pool_ops(hlo: str, leaves):
    """The ops of ``hlo`` that slice or copy the KV pool, each as a string.

    Forbidden anywhere: an array of one layer's pool shape (P, ...), which
    only exists if the scan slices a layer out or restacks it, and a slice or
    a layer-sized update of the stacked (L, P, ...) or flat (L·P, ...) pool.
    Forbidden in the layer scan: any copy of a pool leaf. Outside it a leaf
    may be copied only if the program receives it in another layout than
    the kernels' row-major one: the TPU stores an array whose minor
    dimension is narrow (int4's 64 packed bytes, the per-head scales)
    transposed, and that relayout happens once a step (or a window), not
    once a layer."""
    row_major = _row_major_params(hlo)
    whole, layer = {}, {}
    for leaf in leaves:
        n, p, rest = leaf.shape[0], leaf.shape[1], tuple(leaf.shape[2:])
        relayout = leaf.shape not in row_major
        whole[leaf.shape] = whole[(n * p,) + rest] = (p * math.prod(rest), relayout)
        layer[(p,) + rest] = None
    calls, bodies, kernels, current, ops_of = {}, {}, set(), None, {}
    for line in hlo.splitlines():
        head = _HLO_COMPUTATION.match(line)
        if head:
            current = head.group(1)
            calls[current], bodies[current], ops_of[current] = set(), set(), []
            continue
        if current is None:
            continue
        # a while (or a kernel) may return a tuple, which _HLO_OP does not read
        callees = _HLO_CALL.findall(line)
        calls[current].update(callee for _, callee in callees)
        if " while(" in line:
            bodies[current].update(callee for kind, callee in callees if kind == "body")
        if 'custom_call_target="tpu_custom_call"' in line:
            kernels.add(current)
        m = _HLO_OP.match(line)
        if m:
            ops_of[current].append(m.groups())

    def reach(name, seen):
        if name not in seen:
            seen.add(name)
            for callee in calls.get(name, ()):
                reach(callee, seen)
        return seen

    # the layer scan: a loop that runs a kernel, and no inner loop that does
    # (a routed layer's group metadata has small loops of its own)
    runs_kernel = lambda body: bool(reach(body, set()) & kernels)
    in_layer_loop = set()
    for body in set().union(*bodies.values()):
        inside = reach(body, set())
        if inside & kernels and not any(
                runs_kernel(inner) for c in inside for inner in bodies.get(c, ())):
            in_layer_loop |= inside
    assert in_layer_loop, "no layer scan found in the program"
    found, dims = [], {}
    for computation, ops in ops_of.items():
        for name, dtype, shape, opcode, operands in ops:
            shape = _dims(shape)
            dims[name] = shape
            what = f"{opcode} {dtype}{list(shape)}"
            if shape in layer and opcode != "parameter":
                found.append(what)
            if shape not in whole:
                continue
            layer_elems, relayout = whole[shape]
            if opcode in ("slice", "dynamic-slice"):
                found.append(what)
            elif opcode in ("copy", "copy-start") and (
                    computation in in_layer_loop or not relayout):
                found.append(f"{what} in {computation}")
            elif opcode == "dynamic-update-slice":
                update = operands.split(",")[1].strip().lstrip("%")
                if math.prod(dims.get(update, shape)) >= layer_elems:
                    found.append(f"{what} <- {list(dims.get(update, ()))}")
    return found


def _check_in_place(one_chip, rep, program, cfg):
    fn, args, leaves = _step_program(one_chip, rep, program, cfg)
    compiled = fn.lower(*args).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert _pool_ops(hlo, leaves) == []
    nbytes = lambda leaf: math.prod(leaf.shape) * leaf.dtype.itemsize
    row_major = _row_major_params(hlo)
    layer_bytes = sum(nbytes(leaf) for leaf in leaves) // cfg.n_layers
    relayout_bytes = sum(nbytes(leaf) for leaf in leaves if leaf.shape not in row_major)
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes + 2 * relayout_bytes
    return hlo


@pytest.mark.parametrize("program", ["decode", "chunk", "spec_window"])
@pytest.mark.parametrize("rep", KV_REPS)
def test_step_writes_pool_in_place_on_v5e(one_chip, monkeypatch, rep, program):
    """The fused decode step, the chunk step and the speculative window step
    compiled for a v5e write the KV pool in place: no op slices it or copies
    it in the layer scan (``_pool_ops``), and the program's temp memory is
    below one layer's pool, plus two buffers for each leaf the TPU holds in
    another layout than the kernels' (its relayout in and out)."""
    monkeypatch.setattr(pa, "use_interpret", lambda: False)
    _check_in_place(one_chip, rep, program, STEP_CFG)


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_period_moe_step_writes_pool_in_place_on_v5e(one_chip, monkeypatch, program):
    """The same law for a scan over periods of windowed and full layers with
    routed experts: one flat pool of all eight layers, no pool-shaped copy;
    the windowed kernels, the full ones and the grouped expert products
    (megablox ``gmm``) all compile for the v5e."""
    monkeypatch.setattr(pa, "use_interpret", lambda: False)
    hlo = _check_in_place(one_chip, "bf16", program, PERIOD_CFG)
    assert '"window":"512"' in hlo and "%gmm" in hlo
