"""Serve Llama-3.2-1B at its published widths on one TPU chip and check it.

    python chip_smoke.py [--seed N]

The model is ``get_config("llama3.2-1b")`` (16 layers, d_model 2048, 32/8
heads, head_dim 64, d_ff 8192, vocab 128256) in its bf16 dtype, with random
weights from ``--seed``. Eight greedy requests of a few hundred prompt tokens
go through ``ServeEngine`` with chunked prefill, so both the decode kernel and
the chunk kernel run. All phases run in this one process:

  (a) bf16 pages through the Pallas kernels, checked against
      ``Model.forward`` over each request's whole context (no pages, no
      kernels);
  (b) the same requests through the jnp attention path, logits compared
      with (a);
  (c) int8 pages, logits compared with (a);
  (d) each paged kernel, Pallas against its jnp twin, for bf16, int8 and
      int4 pages: decode, a prefill chunk, and the speculative verify width;
  (e) the engine's fused decode step compiled, and checked to hold a Mosaic
      kernel (``tpu_custom_call``).

Every error is printed beside its bound, with the reason for the bound next
to its constant below. The script exits non-zero when any check fails, and
before doing anything else when JAX's first device is not a TPU. On success
the last line of stdout is one JSON object naming the device. It reports no
utilization or rate: it shows that the path runs and is right.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path
from typing import Callable, List

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import monitoring  # noqa: E402

from repro.kernels import ops  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import build_model, get_config  # noqa: E402
from repro.serving import GenerationParams, make_paged_serve_step  # noqa: E402
from repro.serving.engine import (  # noqa: E402
    EngineConfig,
    PagedQuantSpec,
    ServeEngine,
    aligned_max_logit_err,
)

ARCH = "llama3.2-1b"

# bf16 keeps 8 significant bits: one rounding moves a value by at most 2**-8
# of itself. Each layer may flip one such rounding of its attention output
# between two paths, and in the worst case the flips add over the layers, so
# two bf16 engines may differ by n_layers * 2**-8 of the largest logit.
BF16_LOGIT_REL_PER_LAYER = 2.0**-8
# int8 pages round each K/V element to 1/254 of its page's absmax, but the
# scale lifecycle (serving/engine/kvquant.py) fixes a decode page's scale at
# the page's first token and clips later, larger tokens to it, so no rounding
# argument bounds the gap to bf16 pages. The bound is the largest logit of
# (a) itself: int8 serving must stay within the range of the bf16 logits.
# Phase (d) holds the int8 kernel to its jnp twin at KERNEL_REL.
INT8_LOGIT_REL = 1.0
# A kernel and its jnp twin both accumulate in f32 and round their output to
# bf16 once. Run at highest matmul precision (the TPU's default rounds f32
# matmul operands to bf16, and XLA and Mosaic need not round alike), the f32
# results differ only in summation order, so the two bf16 outputs are at most
# one unit in the last place apart: 2**-7 of the value, and so at most 2**-7
# of the largest output.
KERNEL_REL = 2.0**-7

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass(frozen=True)
class Sizes:
    requests: int
    prompt_lens: tuple  # (shortest, longest) prompt
    new_tokens: int
    page_size: int
    max_batch: int
    kernel_ctx: int  # longest context in the kernel checks
    chunk: int  # prefill chunk width in the kernel checks
    verify: int  # speculative verify width K + 1


CHIP = Sizes(requests=8, prompt_lens=(200, 400), new_tokens=32, page_size=16,
             max_batch=8, kernel_ctx=400, chunk=32, verify=4)


@dataclasses.dataclass
class Check:
    name: str
    value: float
    rel: str  # "<=" or ">="
    bound: float

    @property
    def ok(self) -> bool:
        if not np.isfinite(self.value):
            return False
        return self.value <= self.bound if self.rel == "<=" else self.value >= self.bound

    def line(self) -> str:
        return (f"  {'ok  ' if self.ok else 'FAIL'} {self.name}: "
                f"{self.value:.6g} {self.rel} {self.bound:.6g}")


def at_most(name: str, value: float, bound: float) -> Check:
    return Check(name, float(value), "<=", float(bound))


class CompileClock:
    """Seconds XLA spends compiling, summed from JAX's monitoring events
    (tracing and lowering nest, so they are left out)."""

    def __init__(self):
        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event: str, duration: float, **_):
        if event == _BACKEND_COMPILE_EVENT:
            self.seconds += duration


def build(*, smoke: bool = False, seed: int = 0):
    model = build_model(get_config(ARCH, smoke=smoke))
    return model, model.init_params(jax.random.key(seed))


def make_prompts(vocab: int, sizes: Sizes, seed: int) -> List[List[int]]:
    rng = np.random.default_rng(seed)
    lo, hi = sizes.prompt_lens
    return [rng.integers(0, vocab, size=int(rng.integers(lo, hi + 1))).tolist()
            for _ in range(sizes.requests)]


def serve(model, params, prompts, sizes: Sizes, *, kv_dtype: str,
          attn_impl: str):
    """Greedy requests through the public engine API, logits recorded."""
    econf = EngineConfig.sized_for(
        max(len(p) for p in prompts) + sizes.new_tokens + 1,
        page_size=sizes.page_size, max_batch=sizes.max_batch,
        attn_impl=attn_impl, kv_dtype=kv_dtype, chunked_prefill=True,
        record_logits=True,
    )
    engine = ServeEngine(model, params, econf)
    gp = GenerationParams(max_new_tokens=sizes.new_tokens)
    for rid, prompt in enumerate(prompts):
        engine.submit(prompt, gp, rid=rid)
    return engine, engine.run()


def served_checks(tag: str, engine, results, prompts, sizes: Sizes) -> List[Check]:
    """Every request finished with its token budget and finite logits."""
    m = engine.metrics()
    n = len(prompts)
    short = sum(len(results[r].generated) != sizes.new_tokens
                for r in range(n) if r in results)
    rows = [row for per in engine.logits_of.values() for row in per.values()]
    bad_rows = sum(not np.all(np.isfinite(row)) for row in rows)
    pools = sorted({str(x.dtype) for x in jax.tree.leaves(engine.cache.pools)})
    print(f"  {tag}: pool dtypes {pools}; tokens per request "
          f"{[len(results[r].generated) for r in sorted(results)]}")
    return [
        at_most(f"{tag} requests missing or failed",
                n - m.get("requests", 0) + m.get("failed", 0), 0),
        at_most(f"{tag} requests short of {sizes.new_tokens} tokens", short, 0),
        at_most(f"{tag} logits rows recorded short of "
                f"{n * sizes.new_tokens}", n * sizes.new_tokens - len(rows), 0),
        at_most(f"{tag} logits rows not finite", bad_rows, 0),
    ]


def logit_scale(engine) -> float:
    return max(float(np.max(np.abs(row)))
               for per in engine.logits_of.values() for row in per.values())


def forward_err(model, params, engine, results) -> float:
    """Largest |logit| gap between the engine's recorded rows and one batched
    ``Model.forward`` over every request's prompt plus generated tokens,
    right-padded to one length (the model is causal: padding after a
    position never reaches it). Only the rows that predicted a generated
    token leave the device."""
    rids = sorted(results)
    ctxs = [list(results[r].request.prompt) + list(results[r].generated[:-1])
            for r in rids]
    width = max(len(c) for c in ctxs)
    tokens = jnp.asarray([c + [0] * (width - len(c)) for c in ctxs], jnp.int32)
    n_gen = len(results[rids[0]].generated)
    # row len(prompt) - 1 + n predicts generated[n]
    rows = jnp.asarray([[len(results[r].request.prompt) - 1 + n
                         for n in range(n_gen)] for r in rids], jnp.int32)

    @jax.jit
    def predicted(p, t, idx):
        logits = model.forward(p, t, remat=False)[0]
        return jnp.take_along_axis(logits, idx[..., None], axis=1)

    ref = np.asarray(predicted(params, tokens, rows)[..., : model.cfg.vocab],
                     np.float32)
    return max(float(np.max(np.abs(ref[i, n] - row)))
               for i, rid in enumerate(rids)
               for n, row in engine.logits_of[rid].items())


def _kernel_inputs(cfg, sizes: Sizes, seed: int):
    rng = np.random.default_rng(seed)
    b, ps = sizes.max_batch, sizes.page_size
    hq, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    max_pages = -(-sizes.kernel_ctx // ps) + 1
    num_pages = b * max_pages + 1
    # distinct physical pages in a random order: the table indirection is real
    tables = (rng.permutation(num_pages - 1)[: b * max_pages] + 1).reshape(b, max_pages)
    lens = rng.integers(1, sizes.kernel_ctx + 1, size=b)
    edge = (sizes.kernel_ctx, ps, 1)[:b]  # full, one page, one token
    lens[: len(edge)] = edge
    # prefill chunks start on page boundaries, verify windows anywhere
    n_chunk_starts = (sizes.kernel_ctx - sizes.chunk) // ps + 1
    chunk_cur = rng.integers(0, n_chunk_starts, size=b) * ps
    chunk_cur[0] = 0
    verify_cur = rng.integers(0, sizes.kernel_ctx - sizes.verify + 1, size=b)
    normal = lambda *shape: rng.standard_normal(shape, dtype=np.float32)
    return dict(
        tables=jnp.asarray(tables, jnp.int32), lens=jnp.asarray(lens, jnp.int32),
        chunk_cur=jnp.asarray(chunk_cur, jnp.int32),
        verify_cur=jnp.asarray(verify_cur, jnp.int32),
        k=normal(num_pages, hkv, ps, d), v=normal(num_pages, hkv, ps, d),
        q1=normal(b, hq, 1, d), qc=normal(b, hq, sizes.chunk, d),
        ck=normal(b, hkv, sizes.chunk, d), cv=normal(b, hkv, sizes.chunk, d),
        qv=normal(b, hq, sizes.verify, d), vk=normal(b, hkv, sizes.verify, d),
        vv=normal(b, hkv, sizes.verify, d),
    )


def kernel_checks(cfg, sizes: Sizes, seed: int) -> List[Check]:
    """Each paged kernel against its jnp twin on random pools at the model's
    head widths. Activations are in the model's dtype; verify windows carry
    f32 present K/V holding values of the model's dtype, as the engine's
    verify step gathers them back from a bf16 pool."""
    x = _kernel_inputs(cfg, sizes, seed)
    dt = cfg.param_dtype
    act = lambda a: jnp.asarray(a, dt)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    checks = []
    for rep in ("bf16", "int8", "int4"):
        if rep == "bf16":
            pool = (act(x["k"]), act(x["v"]))
            decode = lambda impl, pool, q, t, n: ops.paged_decode_attention(
                q, *pool, t, n, impl=impl)
            chunk = lambda impl, pool, q, ck, cv, t, c: (
                ops.paged_prefill_chunk_attention(q, ck, cv, *pool, t, c, impl=impl))
        else:
            spec = PagedQuantSpec(bits=int(rep[3:]))
            ek, ev = spec.encode_pages(x["k"]), spec.encode_pages(x["v"])
            pool = (ek["q"], ek["scale"], ev["q"], ev["scale"])
            decode = lambda impl, pool, q, t, n, bits=spec.bits: (
                ops.paged_decode_attention_quant(
                    q, *pool, t, n, bits=bits, impl=impl))
            chunk = lambda impl, pool, q, ck, cv, t, c, bits=spec.bits: (
                ops.paged_prefill_chunk_attention_quant(
                    q, ck, cv, *pool, t, c, bits=bits, impl=impl))
        cases = [
            ("decode", decode, (act(x["q1"]), x["tables"], x["lens"])),
            (f"chunk C={sizes.chunk}", chunk,
             (act(x["qc"]), act(x["ck"]), act(x["cv"]), x["tables"], x["chunk_cur"])),
            (f"verify C={sizes.verify}", chunk,
             (act(x["qv"]), f32(act(x["vk"])), f32(act(x["vv"])), x["tables"],
              x["verify_cur"])),
        ]
        for name, fn, args in cases:
            with jax.default_matmul_precision("highest"):
                got, ref = (
                    np.asarray(jax.jit(fn, static_argnums=0)(impl, pool, *args),
                               np.float32)
                    for impl in ("pallas", "jnp")
                )
            checks.append(at_most(
                f"{rep} {name} |pallas - jnp|", np.max(np.abs(got - ref)),
                KERNEL_REL * np.max(np.abs(ref))))
    return checks


def fused_step_kernels(model, engine) -> int:
    """Compile the engine's fused decode step at its shapes and count the
    Mosaic kernel calls in the compiled program."""
    cfg = engine.config
    step = make_paged_serve_step(
        model, attn_impl=cfg.attn_impl, kv_spec=engine.cache.kv_spec,
        vocab=model.cfg.vocab,
    )
    b = cfg.max_batch
    tables, lens = engine.cache.device_state()
    args = (engine.params, engine.cache.pools, jnp.zeros((b,), jnp.int32),
            tables, lens, jnp.zeros((2, b), jnp.float32),
            jnp.zeros((3, b), jnp.int32))
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
    return jax.jit(step).lower(*shapes).compile().as_text().count("tpu_custom_call")


def run_checks(model, params, sizes: Sizes, *, seed: int, on_chip: bool,
               clock: CompileClock = None) -> List[Check]:
    """Phases (a)-(e) in order. Off the chip the kernels run in the Pallas
    interpreter, which lowers to plain XLA, so (e) then expects no Mosaic
    kernel instead of at least one."""
    cfg = model.cfg
    prompts = make_prompts(cfg.vocab, sizes, seed)
    print(f"  {len(prompts)} requests, prompt lengths {[len(p) for p in prompts]}, "
          f"{sizes.new_tokens} new tokens each, page_size {sizes.page_size}")
    bf16_bound = cfg.n_layers * BF16_LOGIT_REL_PER_LAYER
    checks: List[Check] = []

    def phase(name: str, fn: Callable):
        c0, t0 = clock.seconds if clock else 0.0, time.perf_counter()
        out = fn()
        compile_s = (clock.seconds - c0) if clock else float("nan")
        print(f"[{name}] {time.perf_counter() - t0:.1f} s, of which compile "
              f"{compile_s:.1f} s")
        return out

    eng_a, res_a = phase("a: bf16 pages, Pallas", lambda: serve(
        model, params, prompts, sizes, kv_dtype="f32", attn_impl="pallas"))
    scale = logit_scale(eng_a)
    print(f"  largest |logit| in (a): {scale:.6g}")
    checks += served_checks("(a)", eng_a, res_a, prompts, sizes)
    checks.append(at_most("(a) max |logit - Model.forward|",
                          forward_err(model, params, eng_a, res_a),
                          bf16_bound * scale))

    eng_b, res_b = phase("b: bf16 pages, jnp", lambda: serve(
        model, params, prompts, sizes, kv_dtype="f32", attn_impl="jnp"))
    checks += served_checks("(b)", eng_b, res_b, prompts, sizes)
    checks.append(at_most("(b) aligned max |logit jnp - logit Pallas|",
                          aligned_max_logit_err(eng_a, eng_b, res_a, res_b),
                          bf16_bound * scale))

    eng_c, res_c = phase("c: int8 pages, Pallas", lambda: serve(
        model, params, prompts, sizes, kv_dtype="int8", attn_impl="pallas"))
    checks += served_checks("(c)", eng_c, res_c, prompts, sizes)
    checks.append(at_most("(c) aligned max |logit int8 - logit bf16|",
                          aligned_max_logit_err(eng_a, eng_c, res_a, res_c),
                          INT8_LOGIT_REL * scale))
    same = sum(res_a[r].generated == res_c[r].generated for r in res_a)
    print(f"  (c) greedy tokens identical to (a) on {same}/{len(res_a)} requests")

    checks += phase("d: kernels, Pallas vs jnp",
                    lambda: kernel_checks(cfg, sizes, seed))

    n_mosaic = phase("e: fused decode step compiled",
                     lambda: fused_step_kernels(model, eng_a))
    if on_chip:
        checks.append(Check("(e) tpu_custom_call in fused step", n_mosaic,
                            ">=", 1))
    else:
        checks.append(at_most("(e) tpu_custom_call in interpreted step",
                              n_mosaic, 0))
    return checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights, prompts and kernel inputs")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's first device is on platform "
              f"{dev.platform!r}", file=sys.stderr)
        return 1
    print(f"device: {dev.platform} {dev.device_kind}, {len(jax.devices())} "
          f"visible; compile cache {enable_compile_cache()}")
    clock = CompileClock()
    t0 = time.perf_counter()
    model, params = build(seed=args.seed)
    cfg = model.cfg
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"heads {cfg.n_heads}/{cfg.n_kv_heads}, head_dim {cfg.head_dim}, "
          f"d_ff {cfg.d_ff}, vocab {cfg.vocab}; {n_params} params in "
          f"{cfg.dtype}, built in {time.perf_counter() - t0:.1f} s")
    checks = run_checks(model, params, CHIP, seed=args.seed, on_chip=True,
                        clock=clock)
    print("checks:")
    for c in checks:
        print(c.line())
    failed = [c.name for c in checks if not c.ok]
    print(f"{len(checks) - len(failed)}/{len(checks)} checks passed; compile "
          f"{clock.seconds:.1f} s; total {time.perf_counter() - t0:.1f} s")
    if failed:
        print(f"chip_smoke: failed: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
