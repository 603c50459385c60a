"""Quickstart: continuous-batching serving with a paged KV cache.

The engine serves many concurrent generation requests from one fixed-size page
pool. Each sequence's KV cache is a set of fixed-size pages scattered anywhere
in the pool; a per-sequence block table (core.layouts.LayoutPaged — the paper's
layout-mapping customization point on a layout the C++ committee never shipped)
maps logical token positions to (page, slot) storage, and the paged-attention
kernel consumes the table directly. Requests are admitted as pages free up,
batched together mid-flight, and preempted/recomputed under memory pressure —
outputs are bit-identical to running each request alone.

    # serve 6 requests with Poisson arrivals on a small model
    PYTHONPATH=src python examples/serve_engine.py --requests 6 --tokens 8

    # engine in five lines:
    from repro.serving import GenerationParams
    from repro.serving.engine import EngineConfig, ServeEngine
    engine = ServeEngine(model, params, EngineConfig(num_pages=64, page_size=16))
    handle = engine.submit([1, 2, 3], GenerationParams(max_new_tokens=32), rid=0)
    engine.run()                      # handle.sequences -> per-branch Sequence list
    print(engine.metrics())           # tokens/sec, p50/p99 latency, preemptions

Prefix sharing (on by default): requests whose prompts open with the same
token block are mapped onto the SAME physical pages — per-page refcounts plus a
page-granular prompt-hash index give the pool O(unique tokens) capacity, and
copy-on-write privatizes a shared page the first time a sequence appends into
it. ``--shared-prefix N`` demos it: every prompt gets a common N-token system
block and the run reports pages saved vs. sharing disabled.

Quantized KV pages: ``--kv-dtype int8`` (or ``int4``) stores the page pool as
intN bytes with one f32 scale per (page, head) — the mdspan paper's ACCESSOR
customization point composed with the LayoutPaged layout one. Pages, tables,
admission, sharing and CoW behave identically (the allocator never looks at
bytes); the pool just holds ~4x/~8x more KV per byte. The demo runs an f32
engine on the same trace and reports the capacity gain and token agreement
(quantization is lossy: greedy outputs may diverge within a bounded logit
error — the CI bench gates the bound).

Chunked prefill (mixed steps): ``--chunked`` switches the engine from
monolithic prefill (a long prompt stalls every step until its whole prefill
finishes) to page-sized prefill CHUNKS interleaved with decode — each chunk is
formally a submdspan of the sequence's paged cache view, executed by one
compiled chunk step that serves every chunk position and prompt length. The
demo prepends long prompts to the trace, runs a monolithic engine on the same
trace, and reports time-to-first-token p50 for both plus token-exactness; with
prefix sharing, a request whose prompt prefix is already resident skips the
shared pages' prefill COMPUTE (not just their storage) and the demo reports
the skipped tokens.

On-device sampling: ``--temperature/--top-k/--top-p/--seed`` set the
GenerationParams sampling policy on every request — token selection (greedy included)
runs INSIDE the fused serve step, so logits never leave the device and the
decode loop's only per-token transfer is the (B,) chosen ids. Sampling is
seeded per (seed, request id, position): the demo re-runs the sampled trace
through a second engine and asserts the outputs are identical (and the
comparisons below — sharing on/off, chunked vs monolithic — stay exact even
when sampled, because the fold depends on position, never on scheduling).

Multi-step fused decode: ``--multi-step K`` lets the engine run K decode
iterations in ONE on-device loop whenever the scheduler proves the horizon
event-free (no admission, page append, CoW, or finish within K) — append,
attend, sample and feed back without touching the host, amortizing dispatch
over K tokens. Token-exact for any K; the run reports how many steps fused.

Hierarchical KV: ``--host-pool N`` adds a host-RAM page tier of N pages
behind the accessor customization point — the same page pool, one more
memory space. Finished sessions RETIRE their KV pages to the host tier
(content-keyed, retention-windowed) instead of dropping them; a follow-up
request that opens with the same context PREFETCHES those pages back at
admission, so resuming a conversation costs a page copy instead of a prefill
recompute. The demo resumes every session through the tiered engine and
through the identical config with the tier off (one ``dataclasses.replace``
apart) and reports resume TTFT side by side plus token-exactness. Under
memory pressure the same machinery turns preemption into swap-out.

Lifecycle tracing: ``--trace FILE`` records every engine transition (enqueue,
admit, prefill/chunk spans, page appends, CoW, preemption, fused decode
windows, finish) into a bounded in-memory ring and exports it as Chrome
trace-event JSON — open the file in Perfetto (https://ui.perfetto.dev) or
chrome://tracing to see one timeline track per batch slot plus a scheduler
track. Tracing is host-side only: no device work, no extra transfers.

Knobs: ``num_pages`` (pool memory budget), ``page_size`` (tokens per page),
``max_batch`` (decode batch width), ``attn_impl`` ("pallas" routes decode
through the paged flash kernel; "auto" picks by backend), ``kv_dtype``
(f32 | int8 | int4 page representation), ``--chunked`` + ``--chunk-tokens``
(mixed-step prefill), ``--temperature/--top-k/--top-p/--seed`` (on-device
sampling), ``--multi-step`` (fused decode horizon), ``--host-pool`` (host-RAM
page tier for session resume / preemption-as-swap), ``--trace FILE``
(lifecycle trace export).
"""
import argparse
import dataclasses

import jax
import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model, get_config
from repro.serving import GenerationParams
from repro.serving.engine import EngineConfig, Request, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--tokens", type=int, default=8)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--rate", type=float, default=20.0, help="arrivals per second")
    ap.add_argument("--attn-impl", default="auto", choices=["auto", "pallas", "jnp"],
                    help="paged-attention path (pallas = the kernel, interpreted off-TPU)")
    ap.add_argument("--shared-prefix", type=int, default=0, metavar="N",
                    help="prepend a common N-token block to every prompt and "
                         "report pages saved by prefix sharing")
    ap.add_argument("--kv-dtype", default="f32", choices=["f32", "int8", "int4"],
                    help="KV page representation (QuantizedAccessor-style intN "
                         "pages + per-(page, head) scales); non-f32 also runs an "
                         "f32 engine and reports the capacity gain")
    ap.add_argument("--chunked", action="store_true",
                    help="mixed-step engine: page-sized prefill chunks "
                         "interleaved with decode; prepends long prompts to the "
                         "trace and compares TTFT against a monolithic engine")
    ap.add_argument("--chunk-tokens", type=int, default=0,
                    help="max tokens per prefill chunk (page multiple; 0 = auto)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy argmax); selection "
                         "always runs on device inside the fused serve step")
    ap.add_argument("--top-k", type=int, default=0,
                    help="keep only the k largest logits before sampling (0 = off)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling: keep the smallest head of the "
                         "distribution with mass top_p (1.0 = off)")
    ap.add_argument("--seed", type=int, default=0,
                    help="sampling PRNG stream seed (per-request streams fold "
                         "the request id; same seed => same tokens, always)")
    ap.add_argument("--multi-step", type=int, default=1, metavar="K",
                    help="fused decode horizon: run K decode iterations in one "
                         "on-device loop over event-free horizons (1 = off)")
    ap.add_argument("--host-pool", type=int, default=0, metavar="N",
                    help="host-RAM page tier of N pages (try 64): finished "
                         "sessions retire their KV pages host-side and resume "
                         "by prefetching them back; the demo compares resume "
                         "TTFT against the same engine with the tier off")
    ap.add_argument("--trace", default="", metavar="FILE",
                    help="record the request-lifecycle trace and export it to "
                         "FILE as Chrome trace-event JSON (view in Perfetto)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = dataclasses.replace(get_config(args.arch, smoke=True), dtype="float32")
    model = build_model(cfg)
    params = model.init_params(jax.random.key(0))

    rng = np.random.default_rng(0)
    prefix = rng.integers(0, cfg.vocab, size=args.shared_prefix).tolist()
    arrivals = np.cumsum(rng.exponential(1.0 / args.rate, size=args.requests))
    prompts = [
        prefix + rng.integers(0, cfg.vocab, size=int(rng.choice([6, 10, 14]))).tolist()
        for _ in range(args.requests)
    ]
    long_len = 0
    if args.chunked and not args.shared_prefix:
        # two long prompts at the head of the burst: the monolithic comparison
        # engine must prefill each whole before anything behind them moves.
        # Skipped under --shared-prefix: the longs would hold slots while the
        # same-prefix requests run disjointly, so none would overlap and the
        # sharing demo would (correctly) report zero adoptions.
        long_len = 8 * args.page_size
        prompts = [
            rng.integers(0, cfg.vocab, size=long_len).tolist() for _ in range(2)
        ] + prompts
        arrivals = np.concatenate([[0.0, 0.0], arrivals])
    gen_params = GenerationParams(
        max_new_tokens=args.tokens, temperature=args.temperature,
        top_k=args.top_k, top_p=args.top_p, seed=args.seed,
    )
    make_requests = lambda: [
        Request(rid=i, prompt=list(p), params=gen_params,
                arrival_time=float(arrivals[i]))
        for i, p in enumerate(prompts)
    ]
    econf = EngineConfig.sized_for(
        max(long_len, args.shared_prefix + 14) + args.tokens + 1,
        page_size=args.page_size,
        max_batch=args.max_batch,
        attn_impl=args.attn_impl,
        kv_dtype=args.kv_dtype,
        chunked_prefill=args.chunked,
        chunk_tokens=args.chunk_tokens,
        multi_step=args.multi_step,
        trace=bool(args.trace),
    )

    engine = ServeEngine(model, params, econf)
    results = engine.run(make_requests())
    if args.trace:
        engine.trace.export(args.trace)
        n_ev = len(engine.trace.events)
        print(
            f"lifecycle trace: {n_ev} events -> {args.trace} "
            f"(open in https://ui.perfetto.dev or chrome://tracing)"
        )

    for rid in sorted(results):
        s = results[rid]
        print(f"req {rid}: prompt[{len(s.request.prompt)}] -> {s.generated}")
    m = engine.metrics()
    print(
        f"\n{m['requests']} requests, {m['generated_tokens']} tokens in {m['wall_s']:.2f}s "
        f"({m['tokens_per_s']:.1f} tok/s, CPU demo incl. compiles) | "
        f"latency p50 {m['latency_s_p50']*1e3:.0f}ms p99 {m['latency_s_p99']*1e3:.0f}ms | "
        f"step p50 {m['step_ms_p50']:.2f}ms (host overhead "
        f"{m['host_overhead_ms_p50']:.2f}ms) | preemptions {m['preemptions']}"
    )
    if args.multi_step > 1:
        print(
            f"multi-step fused decode (K={args.multi_step}): "
            f"{m['fused_steps']}/{m['decode_steps']} decode steps ran inside "
            f"on-device fused windows (event-free horizons only; token-exact vs K=1)"
        )
    if args.temperature > 0:
        # seeded sampling is a pure function of (seed, rid, position): a second
        # engine on the same trace must reproduce every token
        rerun = ServeEngine(model, params, econf).run(make_requests())
        assert all(
            results[r].generated == rerun[r].generated for r in results
        ), "seeded sampling must be reproducible"
        print(
            f"on-device sampling: temperature={args.temperature} "
            f"top_k={args.top_k} top_p={args.top_p} seed={args.seed} | "
            f"re-run reproduces all {len(results)} outputs exactly "
            f"(logits never left the device)"
        )

    if args.chunked:
        # same trace through a monolithic-prefill engine: the TTFT cost of
        # stalling every step behind whole-prompt prefills
        mono = ServeEngine(
            model, params, dataclasses.replace(econf, chunked_prefill=False)
        )
        mono_results = mono.run(make_requests())
        mm = mono.metrics()
        agree = sum(
            results[r].generated == mono_results[r].generated for r in results
        )
        if args.kv_dtype == "f32":
            # exactness holds only at full precision: quantized pools pay the
            # intN representation on cross-chunk attention reads where the
            # monolithic engine attends f32 (see ROADMAP — int4 especially)
            assert agree == len(results), "chunked prefill must not change tokens"
            match_note = "outputs identical"
        else:
            match_note = (
                f"outputs match monolithic on {agree}/{len(results)} requests "
                f"(cross-chunk reads pay the {args.kv_dtype} representation)"
            )
        trace = (
            f"a {long_len}-token long-prompt burst" if long_len
            else "the shared-prefix trace"
        )
        print(
            f"chunked prefill: ttft p50 {m['ttft_s_p50']*1e3:.0f}ms vs "
            f"{mm['ttft_s_p50']*1e3:.0f}ms monolithic "
            f"({mm['ttft_s_p50']/max(m['ttft_s_p50'], 1e-9):.1f}x) on {trace} | "
            f"prefill compute: {m['prefill_tokens_computed']} tokens computed, "
            f"{m['prefill_tokens_skipped']} skipped via shared prefixes | "
            f"{match_note}"
        )

    if args.kv_dtype != "f32":
        # same trace at f32: the byte cost of NOT quantizing the page pool
        ref = ServeEngine(model, params, dataclasses.replace(econf, kv_dtype="f32"))
        ref_results = ref.run(make_requests())
        rm = ref.metrics()
        agree = sum(
            results[r].generated == ref_results[r].generated for r in results
        )
        print(
            f"quantized KV ({args.kv_dtype}): pool {m['kv_pool_bytes']} bytes vs "
            f"{rm['kv_pool_bytes']} at f32 -> {rm['kv_pool_bytes']/m['kv_pool_bytes']:.1f}x "
            f"more KV capacity per byte (same {m['peak_pages_in_use']} peak pages) | "
            f"greedy outputs match f32 on {agree}/{len(results)} requests "
            f"(quantization is lossy; the CI bench bounds the logit error)"
        )

    if args.host_pool:
        # hierarchical KV: every finished session is resumed — its full
        # context plus a fresh user tail — through a tiered engine (pages
        # prefetched back from host RAM) and through the identical config one
        # dataclasses.replace away (tier off: full prefill recompute). Each
        # engine rehearses the resume TWICE so the comparison times compiled
        # code — after the first rehearsal the tiered engine retains the
        # resume context itself, so only the second rehearsal runs the exact
        # (smaller) chunk shapes the measured resume will — then measures a
        # final resume of the same contexts.
        resume_tail = rng.integers(0, cfg.vocab, size=8).tolist()
        max_resume = (
            max(len(p) for p in prompts) + 2 * args.tokens
            + len(resume_tail) + 1
        )
        hconf = EngineConfig.sized_for(
            max_resume, page_size=args.page_size, max_batch=args.max_batch,
            attn_impl=args.attn_impl, chunked_prefill=True,
            chunk_tokens=args.chunk_tokens,
            host_pool_pages=args.host_pool, retain_finished_s=600.0,
        )
        tiered = ServeEngine(model, params, hconf)
        untiered = ServeEngine(
            model, params,
            dataclasses.replace(hconf, host_pool_pages=0,
                                retain_finished_s=0.0),
        )
        resumed, rstats = {}, {}
        for name, eng in (("prefetch", tiered), ("recompute", untiered)):
            sessions = eng.run(make_requests())
            resume = lambda base: [
                Request(
                    rid=base + rid,
                    prompt=list(s.request.prompt) + list(s.generated)
                    + resume_tail,
                    params=gen_params,
                )
                for rid, s in sorted(sessions.items())
                if rid < 100
            ]
            eng.run(resume(200))  # rehearsal 1: warms the tier
            eng.run(resume(300))  # rehearsal 2: compiles warm-tier shapes
            eng.reset_metrics()
            out = eng.run(resume(100))
            resumed[name] = {
                r - 100: out[r].generated for r in out if 100 <= r < 200
            }
            rstats[name] = eng.metrics()
        assert resumed["prefetch"] == resumed["recompute"], (
            "host-tier resume must not change tokens"
        )
        wm, cm = rstats["prefetch"], rstats["recompute"]
        print(
            f"hierarchical KV (host pool {args.host_pool} pages): resume "
            f"ttft p50 {wm['ttft_s_p50']*1e3:.1f}ms prefetching vs "
            f"{cm['ttft_s_p50']*1e3:.1f}ms recomputing "
            f"({cm['ttft_s_p50']/max(wm['ttft_s_p50'], 1e-9):.1f}x) | "
            f"{wm['prefetch_hits']} pages prefetched, prefill tokens "
            f"computed {wm['prefill_tokens_computed']} vs "
            f"{cm['prefill_tokens_computed']} | outputs identical"
        )

    if args.shared_prefix:
        # same trace, sharing disabled: the page-pool cost of NOT deduping
        baseline = ServeEngine(
            model, params, dataclasses.replace(econf, prefix_sharing=False)
        )
        base_results = baseline.run(make_requests())
        bm = baseline.metrics()
        assert all(
            results[r].generated == base_results[r].generated for r in results
        ), "prefix sharing must not change tokens"
        saved = bm["peak_pages_in_use"] - m["peak_pages_in_use"]
        print(
            f"prefix sharing: peak pages {m['peak_pages_in_use']} vs "
            f"{bm['peak_pages_in_use']} without -> {saved} pages saved "
            f"({100.0 * saved / max(bm['peak_pages_in_use'], 1):.0f}%) | "
            f"{m['pages_shared']} page adoptions, {m['cow_copies']} CoW copies, "
            f"outputs identical"
        )


if __name__ == "__main__":
    main()
