"""ServeEngine: continuous-batching generation over a paged KV cache.

One engine step is a MIXED step: (admit newcomers) then (one prefill chunk for
each PREFILLING sequence, token-budgeted) then (one batched decode step for
every DECODING sequence). Sequences enter and leave the batch at arbitrary
steps (continuous batching): a fixed-size slot vector keeps the decode
computation at one compiled shape, and per-slot positions (context_lens) +
block-table rows carry each sequence's own state into decode_step_paged — the
LayoutPaged path.

Invariants the step loop maintains per running slot:
  - DECODING: cache.lens[slot] == len(state.context) - 1 — every context token
    EXCEPT the newest generated one has its KV in the pool; the decode input is
    state.generated[-1]; its KV is written at position lens[slot] during the
    step (LayoutPaged: page table[lens//ps], slot lens%ps); and the slot owns a
    WRITABLE page covering position lens[slot]: the scheduler appends a page at
    page boundaries and copy-on-write-privatizes it when prefix sharing left it
    refcount>1 (preempting later arrivals when the pool runs dry), so the
    decode scatter never lands in a page another sequence still reads.
  - PREFILLING (chunked mode): cache.lens[slot] == state.chunk_cursor — the
    page-aligned count of context tokens whose KV is computed and resident.
    Each mixed step advances the cursor by one chunk (formally: the engine
    executes the submdspan [cursor, cursor + chunk) of the sequence's paged
    view — cache.chunk_view); the slot is masked out of the batched decode
    (null table row, length 0, so its lockstep "write" lands in the null page).

Prefill comes in two regimes:
  - monolithic (chunked_prefill=False, the pre-mixed-step behavior): a newly
    admitted request prefills at batch 1 on its full padded length, one compile
    per page bucket, stalling the step for the whole prompt;
  - chunked (chunked_prefill=True): the prompt advances chunk_tokens per step
    through ONE compiled chunk step (cursor traced — every chunk position and
    every prompt length share the compile), interleaved with decode so
    long prompts stop freezing the batch. A per-step token quota splits the
    step between decode appends and chunks; chunk boundaries are page-aligned
    so a chunk-written page is bit-compatible with a monolithic one (the last
    chunk computes the same zero-pad tail a monolithic prefill would).
    When prefix sharing finds the prompt's leading pages resident, the first
    chunk starts at the last whole page boundary before the first non-shared
    token: the shared pages' COMPUTE is skipped, not just their storage
    (metrics: prefill_tokens_skipped). KV is a pure per-token function of
    token ids and absolute position, so the adopted pages already hold
    exactly what this prompt's prefill would write.

The decode hot path is DEVICE-RESIDENT: block tables and lengths live in
persistent device mirrors beside the page pools (PagedKVCache.device_state —
allocator events patch single rows, routine appends advance lengths on device),
token selection (serving/sampling.py: greedy/temperature/top-k/top-p) is fused
into the serve step so logits never cross to the host, and the host loop splits
into an event-driven scheduler tick (admission, page appends, CoW, sweeping)
and a device-loop driver (_decode_once) whose only per-token D2H traffic is the
(B,) sampled ids. Over a scheduler-proven event-free horizon the driver runs
``multi_step`` iterations in ONE on-device lax.scan (append -> attend ->
sample -> feed back), amortizing dispatch over K tokens — token-exact vs K=1
because sampling folds absolute positions, never steps or slots.

Quantization (``kv_dtype`` int8/int4, kvquant.PagedQuantSpec) composes with
both regimes: prefill chunks quantize at scatter time page-by-page with the
same whole-page scale law as monolithic prefill. Preemption is recompute-style
in both regimes: pages are dropped (mid-prefill chunks included), and the full
context (prompt + generated so far) is re-prefilled on re-admission, which
under greedy decoding reproduces the identical continuation.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.runtime.health import StragglerPolicy
from repro.serving.params import (
    FINISH_EOS,
    FINISH_ERROR,
    FINISH_LENGTH,
    GenerationParams,
    RequestHandle,
    Sequence as SequenceResult,
)
from repro.serving.sampling import pack_slot_params, stream_seed
from repro.serving.speculative import (
    NGramProposer,
    make_paged_serve_spec_multistep,
)
from repro.serving.step import (
    make_chunked_prefill_step,
    make_paged_serve_multistep,
    make_paged_serve_step,
    make_prefill,
    top_logprobs,
)
from repro.serving.telemetry import EngineTrace, MetricsRegistry, gc_spans, span

from .cache import PagedKVCache
from .request import (
    DECODING,
    PREFILLING,
    BranchGroup,
    Request,
    RequestQueue,
    RequestState,
)
from .scheduler import Scheduler, SchedulerConfig


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    num_pages: int = 64
    page_size: int = 16
    max_batch: int = 8
    max_pages_per_seq: int = 16
    watermark_pages: int = 1
    attn_impl: str = "auto"  # "pallas" | "jnp" | "auto" — ops.paged_decode_attention
    prefix_sharing: bool = True  # dedupe common prompt prefixes onto shared pages
    kv_dtype: str = "f32"  # "f32" | "int8" | "int4" — KV page representation
    # (kvquant.PagedQuantSpec): same pages/tables/admission, ~4x/~8x fewer bytes
    record_logits: bool = False  # keep per-step logits rows (ServeEngine.logits_of)
    # for cross-engine accuracy audits (e.g. int8 vs f32 max-logit-error).
    # OPT-IN SLOW PATH: the fused step normally samples on device and logits
    # never cross to the host; recording fetches the full (B, vocab) rows each
    # step and disables the multi-step fused loop
    multi_step: int = 1  # fused decode horizon K: when the scheduler proves the
    # next K steps event-free (no admission/page-append/CoW/max-token finish —
    # Scheduler.event_free_horizon), run them as ONE on-device lax.scan loop:
    # append -> attend -> sample -> feed back, amortizing a dispatch and a
    # (K, B) ids fetch over K tokens. 1 = off; token-exact for any K
    spec_tokens: int = 0  # speculative decoding draft length K (0 = off):
    # each decode step becomes a WINDOW — an n-gram table over the request's
    # own context proposes K tokens, ONE chunk-style verify pass scores all of
    # them against the paged cache, and the longest agreeing prefix (+1
    # correction/bonus token) commits. Rejection is pure lens arithmetic —
    # no page frees, no host work (serving/speculative.py). GREEDY requests
    # are token-exact vs spec_tokens=0 (CI pins it); per-request opt-out via
    # GenerationParams.speculative=False. Windows fuse multi_step-at-a-time
    # under the same event-free-horizon contract as plain fused decode,
    # with tokens_per_step = K+1
    spec_ngram: int = 2  # n-gram order of the draft lookup key
    spec_table_size: int = 512  # n-gram hash buckets per slot (power of two)
    spec_accept_floor: float = 2.0  # adaptive backoff: a verify window costs
    # ~2x a plain decode step (C=K+1 positions through the chunk kernel plus
    # window host accounting), so speculation only pays while the mean
    # accepted-tokens-per-window clears this floor. The engine keeps an EMA of
    # per-dispatch acceptance; when it dips under the floor the planner runs
    # plain decode for spec_backoff dispatches, then re-probes — repetitive
    # streams keep full-window speed, incompressible streams pay only the
    # occasional probe instead of a per-step verify tax. Consecutive
    # under-floor probes DOUBLE the wait (capped at 32x spec_backoff; an
    # above-floor probe resets it), so a stream that stays incompressible
    # converges to ~zero verify overhead. 0 disables backoff
    spec_backoff: int = 32  # base plain-dispatch count between re-probes
    chunked_prefill: bool = False  # mixed steps: page-sized prefill chunks
    # interleaved with decode instead of monolithic batch-1 prefills
    chunk_tokens: int = 0  # max tokens per prefill chunk (page multiple; 0 =
    # auto: 2 pages). Chunks dispatch at the smallest power-of-two-of-page-size
    # bucket >= their real length, so a short prompt never pays a full-width
    # chunk step — one compile per bucket, O(log(chunk_tokens/page_size)) total
    step_token_quota: int = 0  # per-step token budget split across decode
    # appends + prefill chunks (0 = auto: max_batch + chunk_tokens)
    prefill_compute_skip: bool = True  # start a shared-prefix request's first
    # chunk past the adopted pages (skip their COMPUTE, not just their storage);
    # effective only with chunked_prefill + prefix_sharing
    trace: bool = False  # record lifecycle events (serving/telemetry.EngineTrace):
    # enqueue/admit/chunk/CoW/preempt/fused-window/finish, exportable as Chrome
    # trace JSON (ServeEngine.trace.export -> Perfetto). Off: every emission
    # site is one `is None` check. On: host appends at engine EVENTS only —
    # the per-token D2H budget of the fused step is untouched
    trace_capacity: int = 65536  # trace ring-buffer events before wrap
    logprobs_k: int = 0  # compile-time top-k logprob width of the fused step.
    # 0 compiles the identical step as before the feature; > 0 lets requests
    # opt in (Request.logprobs <= this) to per-token top-k logprobs that ride
    # the existing ids fetch
    max_beam_width: int = 0  # widest beam_width a request may ask for. Beam
    # candidates come from the fused step's top-k logprob pair, so this widens
    # the compile-time logprob width to max_beam_width + 1 (the +1 guarantees
    # enough non-eos continuations even when every branch's top candidate is
    # eos — eos is ONE token id, so at most one of any row's top entries is it)
    grammar_states: int = 0  # grammar-table rows reserved for constrained
    # decoding (sum of TokenDFA.n_states over every grammar registered with
    # this engine). The mask/transition tables compile at the FIXED shape
    # (1 + grammar_states, vocab) — row 0 is the reserved unconstrained state —
    # so registering a grammar never recompiles the fused step; 0 compiles the
    # identical step as before the feature
    slow_step_threshold: float = 2.0  # decode steps slower than this multiple
    # of the per-token EMA (runtime/health.StragglerPolicy) count as slow:
    # trace event + `slow_steps` counter
    autotune: bool = False  # consult kernels/autotune.py at engine init: fill
    # any block-shape field left at its auto sentinel (page_size=0 via
    # sized_for, decode_block_pages=0, chunk_tokens=0) from the disk-cached
    # tuning table for (model, kv_dtype, batch bucket), sweeping once on a
    # cache miss. Explicitly-set fields are never overridden; the decision is
    # surfaced in metrics() and as a `tuning_selected` trace instant
    decode_block_pages: int = 0  # pages per decode-kernel compute block
    # (paged_attention block_pages). 0 = auto: tuned when autotune is on,
    # unblocked (the pre-knob schedule) otherwise; > 0 pins the value
    sized_max_len: int = 0  # the max_len sized_for() was called with (0 when
    # the pool was sized by hand); lets autotune re-derive the pool extents
    # when page_size itself is deferred to the tuner
    host_pool_pages: int = 0  # host-RAM page tier capacity (README
    # "Hierarchical KV"). 0 = no tier (identical engine to before the
    # feature); > 0 turns preemption into swap-out and re-admission into
    # prefetch: demoted pages live host-side under their prefix-chain keys,
    # so resumable-session capacity scales with host RAM, not HBM. Requires
    # prefix_sharing (the tier is a content-keyed index)
    swap_budget_pages_per_step: int = 0  # per-step HBM<->host migration
    # allowance, shared by demotions and promotions (0 = unlimited). Keeps
    # swap traffic from starving a step; overflow truncates a run's TAIL, and
    # a shorter warm prefix is still a valid prefix
    retain_finished_s: float = 0.0  # on finish, demote a request's pages to
    # the host tier and retain them for this many seconds (session resume: a
    # follow-up sharing the context prefetches instead of re-prefilling).
    # Retained pages are evicted deadline-first, then LRU; 0 = don't retain

    @classmethod
    def sized_for(cls, max_len: int, *, page_size: int, max_batch: int,
                  **kw) -> "EngineConfig":
        """Pool sized so max_batch sequences of ``max_len`` tokens (prompt + new)
        can run with no contention: per-seq pages cover max_len plus the one-page
        decode headroom, and the pool adds the reserved null page 0.

        ``page_size=0`` defers the page size to the autotuner (requires
        autotune=True): pool sizing then happens at engine init, after the
        tuning table has been consulted, from the stored ``sized_max_len``."""
        if page_size == 0:
            if not kw.get("autotune"):
                raise ValueError("page_size=0 requires autotune=True")
            return cls(
                num_pages=0, page_size=0, max_batch=max_batch,
                max_pages_per_seq=0, sized_max_len=max_len, **kw,
            )
        pages_per_seq = -(-max_len // page_size) + 1
        return cls(
            num_pages=max_batch * pages_per_seq + 1,
            page_size=page_size,
            max_batch=max_batch,
            max_pages_per_seq=pages_per_seq,
            sized_max_len=max_len,
            **kw,
        )


def aligned_max_logit_err(eng_ref, eng, results_ref, results) -> float:
    """Max |logit difference| between two record_logits engines over steps
    where both saw the SAME context: per request, every step up to and
    including the first divergent generated token (those logits were computed
    on identical prefixes, so the comparison stays meaningful after greedy
    trajectories split). The accuracy metric the quantized-KV CI gate bounds."""
    errs = [0.0]
    for rid, s_ref in results_ref.items():
        a, b = s_ref.generated, results[rid].generated
        n_cmp = min(len(a), len(b))
        div = next((i for i in range(n_cmp) if a[i] != b[i]), n_cmp - 1)
        for n in range(div + 1):
            errs.append(float(np.max(np.abs(
                eng_ref.logits_of[rid][n] - eng.logits_of[rid][n]
            ))))
    return max(errs)


def _apply_tuning(config: EngineConfig, tuned) -> EngineConfig:
    """Fill every auto-sentinel block-shape field of ``config`` from a
    TunedPoint; explicitly-set fields win. page_size=0 (sized_for deferral)
    re-derives the pool extents from sized_max_len at the tuned page size."""
    kw = {}
    if config.page_size == 0:
        if not config.sized_max_len:
            raise ValueError(
                "page_size=0 needs EngineConfig.sized_for (sized_max_len unset)"
            )
        ps = tuned.page_size
        pps = -(-config.sized_max_len // ps) + 1
        kw.update(
            page_size=ps,
            max_pages_per_seq=pps,
            num_pages=config.max_batch * pps + 1,
        )
    if config.decode_block_pages == 0:
        kw["decode_block_pages"] = tuned.block_pages
    if config.chunked_prefill and config.chunk_tokens == 0:
        kw["chunk_tokens"] = tuned.chunk_tokens
    return dataclasses.replace(config, **kw) if kw else config


def _named_jit(name: str, fn, **kw):
    """``jax.jit`` under a stable program name: a profile's XLA Modules line
    shows ``jit_<name>``, whatever the factory called its function."""
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn, **kw)


class ServeEngine:
    def __init__(self, model, params, config: EngineConfig = EngineConfig(),
                 mesh=None, rules=None):
        self.model = model
        self.params = params
        # autotune: resolve block shapes BEFORE the pool is sized — a deferred
        # page_size (sized_for(..., page_size=0)) materializes here. Warm path
        # (tuning table hit) is a pure file read; the sweep runs once per
        # (model, kv_dtype, batch bucket) per cache file.
        self.tuned = None
        if config.autotune:
            from repro.kernels import autotune as _autotune

            self.tuned = _autotune.resolve(
                model.cfg, kv_dtype=config.kv_dtype, batch=config.max_batch,
                seq_len=config.sized_max_len,
                page_size=config.page_size or None,
            )
            config = _apply_tuning(config, self.tuned)
        self.config = config
        if config.host_pool_pages and not config.prefix_sharing:
            raise ValueError(
                "host_pool_pages requires prefix_sharing: the host tier is a "
                "content-keyed index over the same page-hash chains"
            )
        self.cache = PagedKVCache(
            model,
            num_pages=config.num_pages,
            page_size=config.page_size,
            max_batch=config.max_batch,
            max_pages_per_seq=config.max_pages_per_seq,
            prefix_sharing=config.prefix_sharing,
            kv_dtype=config.kv_dtype,
            host_pool_pages=config.host_pool_pages,
            swap_budget_pages_per_step=config.swap_budget_pages_per_step,
        )
        self.scheduler = Scheduler(
            self.cache, SchedulerConfig(config.max_batch, config.watermark_pages)
        )
        self.queue = RequestQueue()
        self._pending: List[RequestState] = []  # submitted, not yet arrived
        self._mesh, self._rules = mesh, rules
        # telemetry: one trace shared by engine/scheduler/allocator (None =
        # off, every emission site a single check), one metrics registry
        # backing metrics() with O(1)-memory sketches
        self.trace = EngineTrace(config.trace_capacity) if config.trace else None
        self.cache.trace = self.trace
        self.scheduler.trace = self.trace
        if self.trace is not None and self.tuned is not None:
            # the tuning decision is an engine event like any other: observable
            # in the exported trace, not a silent constant baked into the jit
            self.trace.instant(
                "tuning_selected",
                page_size=config.page_size,
                block_pages=config.decode_block_pages,
                chunk_tokens=config.chunk_tokens,
                source=self.tuned.source,
            )
        self.registry = MetricsRegistry()
        self._h_step = self.registry.histogram("step_time_s")
        self._h_host = self.registry.histogram("host_overhead_s")
        # wall between two ids fetches of consecutive decoding ticks, per
        # token: the gap every decoding request sees between its tokens
        self._h_gap = self.registry.histogram("decode_gap_s")
        self._last_fetch_end: Optional[float] = None
        self._c_decode = self.registry.counter("decode_steps")
        self._c_fused = self.registry.counter("fused_steps")
        self._c_pf_computed = self.registry.counter("prefill_tokens_computed")
        self._c_pf_skipped = self.registry.counter("prefill_tokens_skipped")
        self._c_slow = self.registry.counter("slow_steps")
        self._last_step_time: Optional[float] = None  # fused-horizon estimate
        self._straggler = StragglerPolicy(threshold=config.slow_step_threshold)
        # beam search selects from the fused step's top-k logprob pair, so the
        # compiled width covers max_beam_width + 1 (+1: eos is one token id, so
        # at most one top entry per row is eos and W non-eos continuations
        # always exist)
        self._lp_k = max(
            0, int(config.logprobs_k),
            (config.max_beam_width + 1) if config.max_beam_width else 0,
        )
        vocab = model.cfg.vocab
        # constrained decoding: one stacked mask row + transition row per
        # GLOBAL grammar state, row 0 the reserved unconstrained state (zero
        # mask, self-loop). FIXED shape (1 + grammar_states, vocab): grammar
        # registration rewrites table CONTENT (one upload), never the compiled
        # step. Per-slot states live in a device vector the fused step advances
        # itself (donated, like the lens mirror); the host replays the same
        # transitions on its own copy of the tables.
        self._grammar_on = config.grammar_states > 0
        if self._grammar_on:
            n_rows = 1 + config.grammar_states
            self._gmask_host = np.zeros((n_rows, vocab), np.float32)
            self._gtrans_host = np.zeros((n_rows, vocab), np.int32)
            self._gmask_dev = jnp.asarray(self._gmask_host)
            self._gtrans_dev = jnp.asarray(self._gtrans_host)
            self._gstate_dev = jnp.zeros((config.max_batch,), jnp.int32)
            self._grammars: Dict[int, int] = {}  # id(dfa) -> global row offset
            self._grammar_refs: List[object] = []  # keep registrants alive
            self._grammar_used = 0
        # fused step: sample on device, advance lens on device; donate the page
        # pools, the fed-back token vector, the lens mirror — and the grammar
        # state vector when constrained decoding is compiled in — so the step
        # mutates them in place. Tables are NOT donated — the device mirror is
        # persistent and only patched by allocator events (cache.device_state).
        step_donate = (1, 2, 4) + ((7,) if self._grammar_on else ())
        self._block_pages = config.decode_block_pages or None
        self._step = _named_jit(
            "serve_decode_step",
            make_paged_serve_step(
                model, mesh, rules, attn_impl=config.attn_impl,
                kv_spec=self.cache.kv_spec, vocab=vocab,
                logprobs_k=self._lp_k, grammar=self._grammar_on,
                block_pages=self._block_pages,
            ),
            donate_argnums=step_donate,
        )
        # multi-step fused loop (one compile: only exactly-K windows fuse).
        # record_logits needs per-step rows on the host, so it forces K = 1.
        self._k = 1 if config.record_logits else max(1, int(config.multi_step))
        if self._k > 1:
            self._multistep = _named_jit(
                "serve_decode_multistep",
                make_paged_serve_multistep(
                    model, self._k, mesh, rules, attn_impl=config.attn_impl,
                    kv_spec=self.cache.kv_spec, vocab=vocab,
                    logprobs_k=self._lp_k, grammar=self._grammar_on,
                    block_pages=self._block_pages,
                ),
                donate_argnums=step_donate,
            )
        # speculative decoding (serving/speculative.py): the window step is a
        # SIBLING of the fused multistep — same donation discipline (pools,
        # fed-back tokens, lens mirror; tables NOT donated), plus the
        # proposer's two persistent per-slot device arrays (hist, table)
        # donated and flowed back exactly like the lens mirror. Host rebuilds
        # of individual rows happen only on slot-composition events
        # (_spec_stale), mirroring _sync_slot_state.
        self._spec_k = int(config.spec_tokens)
        if self._spec_k:
            if config.record_logits:
                raise ValueError(
                    "spec_tokens does not compose with record_logits: "
                    "recording needs per-step host logits rows, but the "
                    "speculative window never materializes them off device"
                )
            self._spec_windows = max(1, int(config.multi_step))
            # hist must cover every legal position plus one full window past
            # it, so the in-scan history write never clamps for active rows
            hist_len = (
                config.max_pages_per_seq * config.page_size
                + self._spec_k + 2
            )
            self._proposer = NGramProposer(
                spec_tokens=self._spec_k, ngram=config.spec_ngram,
                table_size=config.spec_table_size, vocab=vocab,
                hist_len=hist_len,
            )
            self._spec_step = _named_jit(
                "serve_spec_multistep",
                make_paged_serve_spec_multistep(
                    model, self._spec_windows, self._proposer, mesh, rules,
                    attn_impl=config.attn_impl, kv_spec=self.cache.kv_spec,
                    vocab=vocab, logprobs_k=self._lp_k,
                ),
                donate_argnums=(1, 2, 4, 7, 8),
            )
            self._hist_dev = jnp.zeros((config.max_batch, hist_len), jnp.int32)
            self._table_dev = jnp.zeros(
                (config.max_batch, config.spec_table_size + 1), jnp.int32
            )
            self._spec_stale: set = set()
            # adaptive backoff state (spec_accept_floor / spec_backoff):
            # EMA of per-dispatch mean accepted-tokens-per-window, the plain
            # dispatches left before the next speculative re-probe, and the
            # current (exponentially grown) backoff length
            self._spec_accept_ema: float = None
            self._spec_backoff_left = 0
            self._spec_backoff_len = int(config.spec_backoff)
            self._c_spec_windows = self.registry.counter("spec_windows")
            self._c_spec_backoffs = self.registry.counter("spec_backoffs")
            self._c_spec_accepted = self.registry.counter(
                "spec_accepted_tokens"
            )
            self._c_spec_hits = self.registry.counter("spec_draft_hits")
            self._c_spec_rollback = self.registry.counter(
                "spec_rollback_tokens"
            )
        if self._lp_k:
            # prefill first tokens sample from a single (Vp,) logits row; the
            # same row yields its top-k logprobs on device, fetched with the
            # chosen id (no extra sync — the id fetch already blocks)
            self._row_logprobs = jax.jit(
                lambda row: top_logprobs(row[None], vocab, self._lp_k)
            )

        # single-row sampler for prefill first tokens: the (vocab,) logits row
        # stays on device; only the chosen id (+ its unmasked logprob, the
        # cumulative-score increment) crosses to the host. Policy rides in two
        # packed vectors (f32 [temp, top_p], i32 [top_k, seed-bits, pos]) —
        # two device_puts per prefill token, not five scalar ones. The masked
        # variant adds the slot's grammar mask row (constrained first tokens).
        def _row_sample(row, f, i, mask=None):
            tok = ops.sample_tokens(
                row[None], f[0:1], i[0:1], f[1:2],
                i[1:2].astype(jnp.uint32), i[2:3], vocab=vocab, mask=mask,
            )[0]
            lp = jax.nn.log_softmax(row[:vocab].astype(jnp.float32))
            return tok, lp[tok]

        self._sample_row = jax.jit(_row_sample)
        self._sample_row_masked = jax.jit(
            lambda row, f, i, m: _row_sample(row, f, i, m[None])
        )
        # per-slot device vectors for the fused step: fed-back tokens + the
        # packed policy/phase arrays (slot_f32 (2, B): temperature, top_p;
        # slot_i32 (3, B): active bitmap, top_k, seed-bits). Rebuilt — three
        # small uploads — only when slot composition changes; in steady state
        # the previous step's device outputs flow straight back in.
        self._tokens_dev = jnp.zeros((config.max_batch,), jnp.int32)
        f32p, i32p = pack_slot_params({}, config.max_batch)
        self._slot_f32 = jnp.asarray(f32p)
        self._slot_i32 = jnp.asarray(
            np.vstack([np.zeros((1, config.max_batch), np.int32), i32p])
        )
        self._slots_stale = True
        self._slot_sig: object = None
        self._prefill_fns: Dict[int, object] = {}  # padded_len -> jitted prefill
        self._chunk_tokens = 0
        if config.chunked_prefill:
            self._chunk_tokens = config.chunk_tokens or 2 * config.page_size
            if self._chunk_tokens % config.page_size:
                raise ValueError(
                    f"chunk_tokens {self._chunk_tokens} must be a multiple of "
                    f"page_size {config.page_size} (chunk boundaries are "
                    f"page-aligned so chunk-written pages match monolithic ones)"
                )
            # ONE compile serves every chunk of every prompt: cursor, valid
            # length and logits index are all traced
            self._chunk_step = _named_jit(
                "serve_prefill_chunk",
                make_chunked_prefill_step(
                    model, mesh, rules, attn_impl=config.attn_impl,
                    kv_spec=self.cache.kv_spec,
                ),
                donate_argnums=(1,),
            )
        self.results: Dict[int, RequestState] = {}
        self._next_rid = 0  # auto-assigned rids for prompt-form submit()
        # rid -> {n: logits row that produced generated[n]} (config.record_logits).
        # Keyed by generated-token index, not step, so preemption/recompute
        # overwrites deterministically and traces align across engines.
        self.logits_of: Dict[int, Dict[int, np.ndarray]] = {}
        # per-token timing lives in the registry histograms (step_time_s:
        # device dispatch + execute + ids D2H, fused windows contributing
        # time / K per token; host_overhead_s: the wall the host loop adds
        # around it; decode_gap_s: see _tokens_landed) — O(1) memory however
        # long the run, metrics() snapshots their sketches

    # -- submission -------------------------------------------------------------
    def _register_grammar(self, dfa) -> int:
        """Install a TokenDFA's mask/transition rows into the engine's stacked
        grammar tables; returns the grammar's GLOBAL row offset (its state 0).
        Idempotent per automaton instance. The tables keep their compiled shape
        — registration is one content upload, never a recompile."""
        off = self._grammars.get(id(dfa))
        if off is not None:
            return off
        if dfa.vocab != self.model.cfg.vocab:
            raise ValueError(
                f"grammar compiled for vocab {dfa.vocab} but the model's is "
                f"{self.model.cfg.vocab}"
            )
        if self._grammar_used + dfa.n_states > self.config.grammar_states:
            raise ValueError(
                f"grammar needs {dfa.n_states} states but only "
                f"{self.config.grammar_states - self._grammar_used} of "
                f"EngineConfig.grammar_states={self.config.grammar_states} "
                f"remain — raise grammar_states"
            )
        off = 1 + self._grammar_used
        self._grammar_used += dfa.n_states
        self._grammars[id(dfa)] = off
        self._grammar_refs.append(dfa)  # id() stays unique while referenced
        self._gmask_host[off : off + dfa.n_states] = dfa.mask
        self._gtrans_host[off : off + dfa.n_states] = dfa.next_state + off
        self._gmask_dev = jnp.asarray(self._gmask_host)
        self._gtrans_dev = jnp.asarray(self._gtrans_host)
        return off

    def submit(self, request=None, params: Optional[GenerationParams] = None, *,
               rid: Optional[int] = None, arrival_time: float = 0.0,
               **legacy) -> RequestHandle:
        """Enqueue one request; returns its RequestHandle. Two call forms:

          submit(Request(rid, prompt, params))          # explicit identity
          submit(prompt_tokens, GenerationParams(...))  # rid auto-assigned

        (plus the deprecated legacy kwargs, which Request shims onto
        GenerationParams). EVERY impossible-combination check lives here or in
        GenerationParams.__post_init__ — at enqueue — so the mid-step
        scheduler never meets a request it cannot serve."""
        if not isinstance(request, Request):
            if request is None:
                raise ValueError("submit() needs a Request or a prompt")
            if rid is None:
                rid = self._next_rid
            request = Request(
                rid, request, params, arrival_time=arrival_time, **legacy
            )
        elif params is not None or rid is not None or legacy:
            raise ValueError(
                "submit(Request(...)) takes no extra params/rid/legacy kwargs "
                "— they belong on the Request"
            )
        self._next_rid = max(self._next_rid, request.rid + 1)
        p = request.params
        if p.logprobs > self._lp_k:
            raise ValueError(
                f"request {request.rid} asks for {p.logprobs} logprobs "
                f"but the engine compiled logprobs_k={self._lp_k} — raise "
                f"EngineConfig.logprobs_k"
            )
        if p.beam_width > self.config.max_beam_width:
            raise ValueError(
                f"request {request.rid} asks for beam_width={p.beam_width} but "
                f"the engine compiled max_beam_width="
                f"{self.config.max_beam_width} — raise "
                f"EngineConfig.max_beam_width"
            )
        if p.n_branches > self.config.max_batch:
            raise ValueError(
                f"request {request.rid} needs {p.n_branches} batch slots "
                f"(admitted as a unit) > max_batch {self.config.max_batch}"
            )
        if p.record_logits and not self.config.record_logits:
            raise ValueError(
                f"request {request.rid} asks for record_logits but the engine "
                f"was built with record_logits=False"
            )
        if p.speculative and not self.config.spec_tokens:
            raise ValueError(
                f"request {request.rid} asks for speculative decoding but the "
                f"engine was built with spec_tokens=0 — set "
                f"EngineConfig.spec_tokens"
            )
        if p.n_branches > 1 and self.config.record_logits:
            raise ValueError(
                "record_logits keys rows by rid — unsupported for parallel "
                "generation (n > 1 / beam_width > 0)"
            )
        grammar_off = None
        if p.grammar is not None:
            if not self._grammar_on:
                raise ValueError(
                    f"request {request.rid} carries a grammar but the engine "
                    f"was built with grammar_states=0 — set "
                    f"EngineConfig.grammar_states"
                )
            grammar_off = self._register_grammar(p.grammar)
        need = self.cache.pages_for(len(request.prompt) + p.max_new_tokens)
        if need > self.config.max_pages_per_seq:
            raise ValueError(
                f"request {request.rid} will need {need} pages "
                f"(prompt {len(request.prompt)} + up to {p.max_new_tokens} new) "
                f"> max_pages_per_seq {self.config.max_pages_per_seq}"
            )
        # a prompt whose admission floor exceeds the whole pool can never run,
        # even against an empty cache — fail loudly at enqueue instead of
        # letting it wedge the queue head forever (Scheduler.impossible covers
        # the runtime variant: a preempted request whose context GREW past the
        # pool). A branch group's floor adds one fork-headroom page per sibling.
        floor = self.cache.pages_for(len(request.prompt) + 1) + (p.n_branches - 1)
        if floor > self.config.num_pages - 1:
            raise ValueError(
                f"request {request.rid} needs {floor} pages just to admit its "
                f"{len(request.prompt)}-token prompt"
                + (f" across {p.n_branches} branches" if p.n_branches > 1 else "")
                + f", but the pool only has {self.config.num_pages - 1} usable "
                f"pages — raise num_pages"
            )
        if p.n_branches > 1:
            group = BranchGroup(request)
            for st in group.branches:
                st.grammar_state = grammar_off
            self._pending.append(group.primary)  # siblings ride the primary
        else:
            state = RequestState(request)
            state.grammar_state = grammar_off
            self._pending.append(state)
        return RequestHandle(self, request.rid)

    def submit_all(self, requests: Sequence[Request]) -> List[RequestHandle]:
        return [self.submit(r) for r in requests]

    # -- prefill path -----------------------------------------------------------
    def _prefill_fn(self, padded_len: int):
        fn = self._prefill_fns.get(padded_len)
        if fn is None:
            fn = jax.jit(
                make_prefill(self.model, self._mesh, self._rules, max_len=padded_len)
            )
            self._prefill_fns[padded_len] = fn
        return fn

    def _admit_monolithic(self, now: float) -> list:
        """Admit what fits; returns the (slot, state) pairs to prefill.
        Fresh branch-group siblings FORK the primary's pages once ITS
        prefill completes (_first_token), which also CLEARS their await_fork
        flag — snapshot the flag at admission so a sibling admitted alongside
        its primary isn't prefilled a second time in this same pass (that
        ghost prefill writes no KV — every page is shared — but would sample
        a duplicate first token)."""
        return [
            (slot, state)
            for slot, state in self.scheduler.admit(self.queue, now)
            if not state.await_fork
        ]

    def _prefill_monolithic(self, to_prefill) -> None:
        tr = self.trace
        for slot, state in to_prefill:
            ctx = state.context
            padded = self.cache.pages_for(len(ctx)) * self.cache.page_size
            if tr is not None:
                tr.instant("admit", slot, rid=state.request.rid, context=len(ctx))
            with span("serve.prefill", tr, slot, rid=state.request.rid,
                      tokens=padded):
                # right-pad to the page bucket so ONE compile serves every
                # context length that rounds to it (preempted re-admissions
                # arrive with arbitrary lengths); logits read at the true last
                # position, the pad tail's KV lands in page slack that is
                # masked or overwritten
                tokens = jnp.asarray(
                    [list(ctx) + [0] * (padded - len(ctx))], jnp.int32
                )
                logits, caches = self._prefill_fn(padded)(
                    self.params, tokens, last_index=jnp.int32(len(ctx) - 1)
                )
                self.cache.write_prefill(slot, caches)
                self.cache.set_len(slot, len(ctx))
                self._c_pf_computed.inc(padded)
            self._first_token(state, logits[0, 0])

    def _first_token(self, state: RequestState, logits_row) -> None:
        """Sample the token a completed prefill produced (either regime), ON
        DEVICE: ``logits_row`` is the (Vp,) device array; only the chosen id
        (and its logprob — the cumulative-score increment) crosses to the host
        (the full row only under record_logits). The PRNG fold position is
        len(context) — the length of the context the token extends — identical
        to what the decode path would fold for the same token, so
        preemption-recompute re-samples it bit-for-bit.

        This is also the parallel-generation FORK HOOK, shared by both prefill
        regimes: when a sample-mode group's primary takes its first token, each
        awaiting sibling's block-table row forks the primary's pages
        (cache.fork_slot) and samples its own first token from the SAME logits
        row under its branch seed; a beam-mode branch instead stashes its row's
        top candidates and the joint selection runs once every live branch has
        reported (_beam_advance)."""
        grp = state.group
        if grp is not None and grp.mode == "beam":
            vals, ids = self._row_logprobs(logits_row)
            with span("serve.fetch", what="first_token", rid=state.request.rid):
                grp.pending_rows[state.branch] = (
                    np.asarray(vals[0]), np.asarray(ids[0])
                )
            state.hold = True  # masked from decode until the joint selection
            if state.first_token_time is None:
                state.first_token_time = time.perf_counter() - self._t0
            started = [
                st for st in grp.branches if not st.await_fork and not st.done
            ]
            if all(st.branch in grp.pending_rows for st in started):
                self._beam_advance(grp)
            return
        sp = state.sampling  # branch-aware: branch b draws from seed + b
        seed_bits = np.uint32(
            stream_seed(sp.seed, state.request.rid)
        ).astype(np.int32)
        f = jnp.asarray(np.array([sp.temperature, sp.top_p], np.float32))
        i = jnp.asarray(np.array(
            [sp.top_k, seed_bits, len(state.context)], np.int32
        ))
        if state.grammar_state is not None:
            tok_dev, lp_dev = self._sample_row_masked(
                logits_row, f, i,
                jnp.asarray(self._gmask_host[state.grammar_state]),
            )
        else:
            tok_dev, lp_dev = self._sample_row(logits_row, f, i)
        with span("serve.fetch", what="first_token", rid=state.request.rid):
            tok, lp = int(tok_dev), float(lp_dev)
        state.generated.append(tok)
        state.cum_logprob += lp
        if state.grammar_state is not None:
            state.grammar_state = int(self._gtrans_host[state.grammar_state, tok])
        self._slots_stale = True  # the slot's next decode input is host-known
        if self._spec_k:
            # the proposer's hist/table rows for this slot must be rebuilt
            # from the (new) context before the next speculative window
            self._spec_stale.add(state.slot)
        if state.request.logprobs:
            vals, ids = self._row_logprobs(logits_row)
            with span("serve.fetch", what="first_token", rid=state.request.rid):
                vals, ids = np.asarray(vals[0]), np.asarray(ids[0])
            state.logprobs[len(state.generated) - 1] = [
                (int(i_), float(v))
                for i_, v in zip(ids[: state.request.logprobs],
                                 vals[: state.request.logprobs])
            ]
        if self._records(state):
            with span("serve.fetch", what="first_token", rid=state.request.rid):
                row = np.asarray(logits_row[: self.model.cfg.vocab], np.float32)
            self.logits_of.setdefault(state.request.rid, {})[
                len(state.generated) - 1
            ] = row
        if state.first_token_time is None:
            state.first_token_time = time.perf_counter() - self._t0
        if grp is not None and state.branch == 0:
            # fork the awaiting siblings onto the primary's prompt pages: each
            # aliases the resident KV (incref, zero copies — CoW privatizes on
            # first divergent write) and samples its own first token from the
            # same row under its branch seed
            n_resident = int(self.cache.lens[state.slot])
            for sib in grp.branches[1:]:
                if sib.await_fork and not sib.done:
                    self.cache.fork_slot(state.slot, sib.slot, n_resident)
                    sib.await_fork = False
                    self._first_token(sib, logits_row)

    def _records(self, state: RequestState) -> bool:
        rl = state.request.params.record_logits
        return self.config.record_logits and rl is not False

    # -- beam search (host-side selection, device-layout reorder) -----------------
    def _beam_advance(self, group: BranchGroup) -> None:
        """One joint beam step over a group's stashed candidate rows.

        Pure HOST-side selection — the candidates already rode the step's
        existing top-k logprob fetch — followed by block-table surgery only:
        every surviving hypothesis is (parent branch, token); a branch that
        keeps continuing itself keeps its slot untouched (the common,
        non-diverging case — NO allocator event at all), a hypothesis hopping
        parents rebinds its slot's row to a snapshot of the parent's
        (cache.reorder_rows: incref'd aliasing, zero page copies — the next
        divergent write CoWs), and a first-step sibling forks the primary
        (cache.fork_slot). Candidates ending in eos move to the finished pool;
        the group completes at >= beam_width finished hypotheses or the length
        cap, returning the best n by cumulative logprob."""
        params = group.request.params
        w = params.beam_width
        eos = group.request.eos_id
        live = [st for st in group.branches if not st.done]
        started = [st for st in live if not st.await_fork]
        by_branch = {st.branch: st for st in started}
        cands = []
        for st in started:
            vals, ids = group.pending_rows[st.branch]
            for v, t in zip(vals[: w + 1], ids[: w + 1]):
                cands.append((st.cum_logprob + float(v), st.branch, int(t)))
        group.pending_rows.clear()
        # deterministic total order: score desc, then branch, then token —
        # replays identically across engines/preemptions
        cands.sort(key=lambda c: (-c[0], c[1], c[2]))
        cont = []
        for score, b, t in cands:
            if eos is not None and t == eos:
                group.finished.append(SequenceResult(
                    tokens=list(by_branch[b].generated) + [t], logprobs={},
                    cumulative_logprob=score, finish_reason=FINISH_EOS,
                ))
                continue
            if len(cont) < w:
                cont.append((score, b, t))
        if len(group.finished) >= w or not cont:
            self._finish_beam(group, live, survivors=False)
            return
        # slot assignment, identity-greedy: each parent's best continuation
        # keeps the parent's own slot, so a step where every branch follows
        # itself is a pure host append — no reorder, no allocator event
        base = {st.branch: list(st.generated) for st in started}
        carriers = list(live)
        assign, spill = [], []
        for score, b, t in cont:
            st = by_branch[b]
            if st in carriers:
                carriers.remove(st)
                assign.append((st, st, t, score))
            else:
                spill.append((score, b, t))
        for (score, b, t), carrier in zip(spill, carriers):
            assign.append((carrier, by_branch[b], t, score))
        now = time.perf_counter() - self._t0
        forks = [
            (c, p) for c, p, _, _ in assign if c is not p and c.await_fork
        ]
        reorder = {
            c.slot: p.slot for c, p, _, _ in assign
            if c is not p and not c.await_fork
        }
        for carrier, parent in forks:
            self.cache.fork_slot(
                parent.slot, carrier.slot, int(self.cache.lens[parent.slot])
            )
            carrier.await_fork = False
        self.cache.reorder_rows(reorder)
        for carrier, parent, t, score in assign:
            carrier.generated = base[parent.branch] + [t]
            carrier.cum_logprob = score
            carrier.hold = False
            if carrier.first_token_time is None:
                carrier.first_token_time = now
        self._slots_stale = True
        if self.trace is not None:
            self.trace.instant(
                "beam_step", group.primary.slot, rid=group.request.rid,
                moves=len(reorder), forks=len(forks),
                finished=len(group.finished),
            )
        if len(assign[0][0].generated) >= params.max_new_tokens:
            self._finish_beam(group, live, survivors=True)

    def _finish_beam(self, group: BranchGroup, live, *, survivors: bool) -> None:
        """Retire a beam group: at the length cap the live hypotheses join the
        finished pool as FINISH_LENGTH survivors; every live branch gets its
        finish_reason stamped so the group sweeps out as a unit (the branch
        states' own reasons never surface — group.sequences() ranks the
        finished pool)."""
        if survivors:
            for st in live:
                if not st.await_fork and not st.hold:
                    group.finished.append(SequenceResult(
                        tokens=list(st.generated), logprobs={},
                        cumulative_logprob=st.cum_logprob,
                        finish_reason=FINISH_LENGTH,
                    ))
        for st in live:
            if st.finish_reason is None:
                st.finish_reason = FINISH_LENGTH
            st.hold = False

    # -- chunked prefill path ----------------------------------------------------
    def _admit_chunked(self, now: float) -> None:
        """Admit without computing anything: pages bind now (index registration
        deferred to publish_prefix, which releases them chunk by chunk as their
        content lands), and the chunk cursor starts at the shared-prefix
        compute skip — the last whole-page boundary at or before the first
        token the adopted pages don't already cover (always leaving >= 1 token
        to compute: the prompt's last position must produce logits).
        Returns the admitted (slot, state) pairs."""
        ps = self.cache.page_size
        admitted = self.scheduler.admit(self.queue, now, publish=False)
        for slot, state in admitted:
            if state.await_fork:
                continue  # fresh sibling: forks at the primary's first token
            n_ctx = len(state.context)
            skip = 0
            if self.config.prefill_compute_skip and self.cache.prefix_sharing:
                adopted = self.cache.adopted_pages(slot)
                skip = min(adopted * ps, ((n_ctx - 1) // ps) * ps)
            state.chunk_cursor = skip
            self.cache.set_len(slot, skip)
            self._c_pf_skipped.inc(skip)
            if self.trace is not None:
                self.trace.instant(
                    "admit", slot, rid=state.request.rid, context=n_ctx,
                    skip=skip,
                )
        return admitted

    def _prefill_chunks(self, now: float) -> None:
        """Advance PREFILLING slots by at most one chunk each, within the
        step's token quota (decode appends are charged first — decode latency
        is what chunking protects). Chunks run shortest-remaining-first,
        stable on admission order: an interactive prompt's whole prefill costs
        less than one long chunk, so it never queues behind one — this is the
        TTFT bound chunking exists for. The budget's leftover flows to the
        longest prompts in admission order (the same serialization a
        monolithic engine imposes, at chunk granularity instead of
        whole-prompt granularity)."""
        running = self.scheduler.running
        # chunk-cursor holders only: await_fork and beam-hold slots are
        # PREFILLING (masked from decode) but have no chunk to advance
        prefilling = [
            s for s in sorted(running)
            if running[s].chunk_cursor is not None
            and self.cache.frontier_ready(s)  # twin adopters wait on the
            # donor's written frontier — their adopted pages are not real yet
        ]
        if not prefilling:
            return
        ps = self.cache.page_size
        n_decoding = sum(1 for st in running.values() if st.phase == DECODING)
        quota = self.config.step_token_quota or (
            self.config.max_batch + self._chunk_tokens
        )
        budget = max(0, quota - n_decoding)
        if n_decoding == 0:
            # liveness: with nothing decoding, the step makes progress only
            # through chunks — a too-small quota must not stall the engine
            budget = max(budget, ps)
        prefilling.sort(
            key=lambda s: self.cache.pages_for(len(running[s].context)) * ps
            - running[s].chunk_cursor
        )
        for slot in prefilling:
            if budget < ps:
                break
            state = running[slot]
            ctx = state.context
            n_ctx = len(ctx)
            padded = self.cache.pages_for(n_ctx) * ps
            cursor = state.chunk_cursor
            c_real = min(self._chunk_tokens, padded - cursor, (budget // ps) * ps)
            budget -= c_real
            # dispatch at the smallest bucket that holds the chunk: the jit
            # cache traces one compile per bucket width, so an 8-token short
            # prompt costs an 8-wide step, not a chunk_tokens-wide one
            bucket = ps
            while bucket < c_real:
                bucket *= 2
            bucket = min(bucket, self._chunk_tokens)
            with span("serve.chunk", self.trace, slot, rid=state.request.rid,
                      cursor=cursor, tokens=c_real, bucket=bucket):
                # the chunk's tokens, zero-padded through the page bucket
                # exactly as a monolithic prefill pads — the last chunk
                # COMPUTES the pad tail's KV so its final page is
                # bit-compatible with the monolithic page (and with the prefix
                # index's purity law)
                padded_ctx = list(ctx) + [0] * (padded - n_ctx)
                toks = padded_ctx[cursor : cursor + c_real]
                toks += [0] * (bucket - c_real)
                read_row = self.cache.tables[slot : slot + 1]
                write_row = self.cache.write_table_row(slot)[None, :]
                logits, pools = self._chunk_step(
                    self.params,
                    self.cache.pools,
                    jnp.asarray([toks], jnp.int32),
                    jnp.asarray(read_row),
                    jnp.asarray(write_row),
                    jnp.asarray([cursor], jnp.int32),
                    jnp.asarray([c_real], jnp.int32),
                    jnp.asarray([min(n_ctx - 1 - cursor, c_real - 1)], jnp.int32),
                )
                self.cache.pools = pools
            self._c_pf_computed.inc(c_real)
            if cursor + c_real >= n_ctx:  # this chunk covered the last position
                state.chunk_cursor = None
                self.cache.set_len(slot, n_ctx)
                self.cache.publish_prefix(slot)
                self._first_token(state, logits[0])
            else:
                state.chunk_cursor = cursor + c_real
                self.cache.set_len(slot, cursor + c_real)
                # pages behind the new cursor are final: publish them so a
                # same-prefix arrival can adopt (and compute-skip) mid-prefill
                self.cache.publish_prefix(slot, (cursor + c_real) // ps)

    # -- decode path (the device-loop driver) -------------------------------------
    def _sync_slot_state(self) -> None:
        """Re-upload the per-slot device vectors — fed-back tokens + the two
        packed policy/phase arrays — ONLY when slot composition changed
        (admission, finish, preemption, a prefill completing). In steady-state
        decode the previous step's sampled tokens ARE the next inputs and flow
        back as device arrays: the step's only recurring H2D traffic is zero
        and its only D2H traffic is the (B,) sampled ids."""
        running = self.scheduler.running
        sig = tuple(
            (slot, st.request.rid, st.phase) for slot, st in sorted(running.items())
        )
        if not self._slots_stale and sig == self._slot_sig:
            return
        b = self.config.max_batch
        tokens = np.zeros((b,), np.int32)
        active = np.zeros((1, b), np.int32)
        decoding = {}
        for slot, state in running.items():
            if state.phase == DECODING:
                tokens[slot] = state.generated[-1]
                active[0, slot] = 1
                decoding[slot] = state
        f32p, i32p = pack_slot_params(decoding, b)
        self._tokens_dev = jnp.asarray(tokens)
        self._slot_f32 = jnp.asarray(f32p)
        self._slot_i32 = jnp.asarray(np.vstack([active, i32p]))
        if self._grammar_on:
            # per-slot grammar states re-seed from the host mirror on the same
            # trigger as the other vectors; in steady state the step's own
            # (donated) output flows back and the host just replays transitions
            gstate = np.zeros((b,), np.int32)
            for slot, state in decoding.items():
                if state.grammar_state is not None:
                    gstate[slot] = state.grammar_state
            self._gstate_dev = jnp.asarray(gstate)
        self._slots_stale = False
        self._slot_sig = sig

    def _fused_k(self, now: float) -> int:
        """How many decode steps to run in one device dispatch: K when the
        scheduler proves the horizon event-free AND no pending arrival lands
        inside it (estimated from the last measured step), else 1. Page
        capacity is the one horizon limit the host can raise for free, so a
        short horizon first pre-appends decode pages up to the window
        (Scheduler.reserve_decode_tokens) and re-proves."""
        if self._k <= 1:
            return 1
        if self.scheduler.event_free_horizon(self.queue) < self._k:
            if self.queue:
                return 1
            for slot, st in self.scheduler.running.items():
                if st.phase == DECODING:
                    self.scheduler.reserve_decode_tokens(slot, self._k)
            if self.scheduler.event_free_horizon(self.queue) < self._k:
                return 1
        if self._pending:
            est = self._last_step_time if self._last_step_time else 2e-3
            if self._pending[0].request.arrival_time <= now + self._k * est:
                return 1
        return self._k

    # -- speculative path (serving/speculative.py) --------------------------------
    def _spec_plan(self, now: float, decoding) -> int:
        """Windows to run speculatively in THIS dispatch (0 = plain decode).
        Speculation is a batch-wide window: every decoding slot must be
        eligible (no per-request opt-out, no grammar, no branch group), the
        whole window's page budget must pre-reserve
        (Scheduler.reserve_decode_tokens — at most S*(K+1) tokens per slot),
        the horizon must prove S windows event-free at tokens_per_step = K+1,
        and no pending arrival may land inside the window. Any failure
        degrades to the plain path for this dispatch — never an error.

        Adaptive backoff: while the acceptance EMA sits under
        spec_accept_floor (speculation not paying for its ~2x-a-step verify
        cost on this stream), the planner answers 0 for spec_backoff
        dispatches before probing another window — an incompressible stream
        pays an occasional probe, not a per-step verify tax."""
        if not decoding or self.queue:
            return 0
        if self._spec_backoff_left:
            self._spec_backoff_left -= 1
            return 0
        for state in decoding.values():
            p = state.request.params
            if (p.speculative is False or p.grammar is not None
                    or state.group is not None):
                return 0
        c = self._spec_k + 1
        s = self._spec_windows
        for slot in decoding:
            if not self.scheduler.reserve_decode_tokens(slot, s * c):
                return 0
        if self.scheduler.event_free_horizon(
                self.queue, tokens_per_step=c) < s:
            return 0
        if self._pending:
            est = self._last_step_time if self._last_step_time else 2e-3
            if self._pending[0].request.arrival_time <= now + s * est:
                return 0
        return s

    def _sync_spec_state(self, decoding) -> None:
        """Rebuild the proposer's hist/table rows for slots whose context
        changed outside a speculative window (admission, plain-decode steps,
        preemption-recompute) — the spec twin of _sync_slot_state. Rebuilt
        rows are bit-identical to what in-window device updates would have
        produced (NGramProposer's shifted-insertion law; tests pin it), so
        mixing plain and speculative dispatches never drifts the table."""
        stale = sorted(s for s in self._spec_stale if s in decoding)
        if stale:
            hists, tables = [], []
            for slot in stale:
                h, t = self._proposer.rebuild_row(decoding[slot].context)
                hists.append(h)
                tables.append(t)
            idx = jnp.asarray(stale, jnp.int32)
            self._hist_dev = self._hist_dev.at[idx].set(
                jnp.asarray(np.stack(hists))
            )
            self._table_dev = self._table_dev.at[idx].set(
                jnp.asarray(np.stack(tables))
            )
        self._spec_stale.difference_update(stale)

    def _decode_spec_once(self, now: float, decoding, s: int) -> None:
        """One speculative dispatch: S windows of propose -> verify -> accept
        inside one on-device lax.scan. Each window commits 1..K+1 tokens per
        slot; the rejected suffix is never covered by the advanced lens
        (rollback = layout arithmetic — its KV bytes sit in pre-reserved
        owned pages and later appends overwrite them). The only bulk D2H is
        the (S, B, K+1) ids + committed-counts fetch."""
        wall0 = time.perf_counter()
        with span("serve.sync"):
            self._sync_slot_state()
            self._sync_spec_state(decoding)
            tables, lens = self.cache.device_state()
        kd = self._spec_k
        c = kd + 1
        tr = self.trace
        with span("serve.spec_window", tr, windows=s, k=kd, batch=len(decoding)):
            want_lp = self._lp_k and any(
                st.request.logprobs for st in decoding.values()
            )
            t0 = time.perf_counter()
            with span("serve.dispatch", k=s, batch=len(decoding)):
                out = self._spec_step(
                    self.params, self.cache.pools, self._tokens_dev, tables,
                    lens, self._slot_f32, self._slot_i32, self._hist_dev,
                    self._table_dev,
                )
            toks, committed, last, new_lens, pools, lps = out[:6]
            with span("serve.fetch", what="ids"):
                ids = np.asarray(toks)  # (S, B, C)
                acc = np.asarray(committed)  # (S, B) tokens committed per window
                lp_arr = np.asarray(lps)  # (S, B, C)
                lp_vals = lp_ids = None
                if want_lp:
                    lp_vals = np.asarray(out[8][0])  # (S, B, C, k)
                    lp_ids = np.asarray(out[8][1])
            self._tokens_landed(s)
            t_dev = time.perf_counter() - t0
            self.cache.pools = pools
            self.cache.adopt_lens_device(new_lens)
            self._tokens_dev = last
            self._hist_dev, self._table_dev = out[6], out[7]
            per_win = t_dev / s  # one window = one model dispatch, like one step
            self._observe_step(per_win, s)
            self._c_fused.inc(s)
            with span("serve.commit"):
                win_acc, win_n = self._commit_spec(decoding, s, c, ids, acc,
                                                   lp_arr, lp_vals, lp_ids)
            mean = (win_acc / win_n) if win_n else 0.0
            ema = self._spec_accept_ema
            self._spec_accept_ema = mean if ema is None else 0.6 * ema + 0.4 * mean
            if self.config.spec_backoff:
                if self._spec_accept_ema < self.config.spec_accept_floor:
                    self._spec_backoff_left = self._spec_backoff_len
                    self._spec_backoff_len = min(
                        self._spec_backoff_len * 2, 32 * self.config.spec_backoff
                    )
                    self._c_spec_backoffs.inc()
                    if tr is not None:
                        tr.instant(
                            "spec_backoff", -1, ema=self._spec_accept_ema,
                            floor=self.config.spec_accept_floor,
                            dispatches=self._spec_backoff_left,
                        )
                else:
                    # the stream pays again: next backoff starts from the base
                    self._spec_backoff_len = int(self.config.spec_backoff)
            if tr is not None:
                tr.instant(
                    "spec_accept", -1, windows=win_n, accepted=win_acc,
                    mean=mean,
                )
        wall = time.perf_counter() - wall0
        self._h_host.observe((wall - t_dev) / s)

    def _commit_spec(self, decoding, s, c, ids, acc, lp_arr, lp_vals, lp_ids):
        """Commit each slot's accepted tokens of S speculative windows; returns
        (tokens committed, slot-windows)."""
        win_acc = 0
        win_n = 0
        for i in range(s):
            for slot, state in decoding.items():
                if state.done:
                    continue  # finished mid-window: overrun windows discarded
                a = int(acc[i, slot])
                take = 0
                for j in range(a):
                    tok = int(ids[i, slot, j])
                    state.generated.append(tok)
                    state.cum_logprob += float(lp_arr[i, slot, j])
                    take += 1
                    n_lp = state.request.logprobs
                    if n_lp and lp_vals is not None:
                        state.logprobs[len(state.generated) - 1] = [
                            (int(t), float(v))
                            for t, v in zip(lp_ids[i, slot, j, :n_lp],
                                            lp_vals[i, slot, j, :n_lp])
                        ]
                    if state.done:
                        break  # EOS inside the window truncates the commit
                # host mirror follows the HONEST count; an EOS-truncated slot
                # (take < a) is done and sweeps out — free_slot dirty-marks
                # its row, repairing the device lens the window over-advanced
                self.cache.bump_len(slot, take)
                win_n += 1
                win_acc += take
                self._c_spec_windows.inc()
                self._c_spec_accepted.inc(take)
                # draft hits: committed tokens that CAME from the draft (the
                # last committed token is the target's correction/bonus)
                self._c_spec_hits.inc(min(take, max(a - 1, 0)))
                self._c_spec_rollback.inc(c - a)
        return win_acc, win_n

    def _tokens_landed(self, k: int) -> None:
        """Feed ``decode_gap_s`` at the end of an ids fetch: when the tick
        before this one also decoded, the wall since its fetch ended, over the
        ``k`` steps this fetch brings, observed k times (as step_time_s is) —
        the gap between two tokens that every decoding request sees. The
        fetch that starts a decoding stretch observes nothing."""
        end = time.perf_counter()
        if self._last_fetch_end is not None:
            gap = (end - self._last_fetch_end) / k
            for _ in range(k):
                self._h_gap.observe(gap)
        self._last_fetch_end = end

    def _observe_step(self, per_tok: float, k: int) -> None:
        """Step-time histogram, the fused-horizon estimate, the decode counter
        and the straggler verdict for one dispatch of ``k`` steps."""
        for _ in range(k):
            self._h_step.observe(per_tok)
        self._last_step_time = per_tok
        self._c_decode.inc(k)
        verdict = self._straggler.observe(per_tok)
        if verdict != "ok":
            self._c_slow.inc()
            if self.trace is not None:
                self.trace.instant(
                    "slow_step", -1, verdict=verdict,
                    step_ms=per_tok * 1e3,
                    ema_ms=(self._straggler.ema or 0.0) * 1e3,
                )

    def _decode_once(self, now: float) -> None:
        """One device dispatch of the decode hot path: a single fused step, or
        a K-step on-device loop over an event-free horizon. PREFILLING slots
        (mixed steps only) are masked ON DEVICE via the phase bitmap — table
        row and length null-routed inside the step — so the host never copies
        or re-uploads tables to mask them; the compiled shape never changes.
        Tokens are sampled on device; the only per-token D2H traffic is the
        sampled ids ((B,) per step, (K, B) per fused window)."""
        running = self.scheduler.running
        decoding = {s: st for s, st in running.items() if st.phase == DECODING}
        if self._spec_k:
            n_win = self._spec_plan(now, decoding)
            if n_win:
                self._decode_spec_once(now, decoding, n_win)
                return
            # plain decode generates tokens the proposer's device arrays
            # never saw — every decoding row is stale for the next window
            self._spec_stale.update(decoding)
        wall0 = time.perf_counter()
        k = self._fused_k(now)
        with span("serve.sync"):
            self._sync_slot_state()
            tables, lens = self.cache.device_state()
        record = self.config.record_logits
        # requests riding the per-token fetch for logprobs (opt-in per request;
        # with nobody opted in the (B, k) pair is computed but never fetched) —
        # beam groups always ride it: the top-k pair IS their candidate set
        want_lp = self._lp_k and any(
            st.request.logprobs
            or (st.group is not None and st.group.mode == "beam")
            for st in decoding.values()
        )
        lp_vals = lp_ids = logits_rows = None
        g_args = (
            (self._gstate_dev, self._gmask_dev, self._gtrans_dev)
            if self._grammar_on else ()
        )
        lp_i = 6 if self._grammar_on else 5  # top-k pair's output index
        with span("serve.fused_window" if k > 1 else "serve.decode", self.trace,
                  k=k, batch=len(decoding)):
            t0 = time.perf_counter()
            with span("serve.dispatch", k=k, batch=len(decoding)):
                out = (self._multistep if k > 1 else self._step)(
                    self.params, self.cache.pools, self._tokens_dev, tables,
                    lens, self._slot_f32, self._slot_i32, *g_args,
                )
            if k > 1:
                toks, last, new_lens, pools = out[:4]
            else:
                last, logits, new_lens, pools = out[:4]
                toks = last
            # (K, B) ids of a fused window, (B,) of one step: one round each
            with span("serve.fetch", what="ids"):
                ids = np.asarray(toks).reshape(k, -1)
                lps = np.asarray(out[4]).reshape(k, -1)  # chosen logprobs
                if want_lp:  # (K, B, top-k width)
                    lp_vals = np.asarray(out[lp_i][0]).reshape(*ids.shape, -1)
                    lp_ids = np.asarray(out[lp_i][1]).reshape(*ids.shape, -1)
                if record and k == 1:
                    logits_rows = np.asarray(
                        logits[:, : self.model.cfg.vocab], np.float32
                    )
            self._tokens_landed(k)
            if k > 1:
                self._c_fused.inc(k)
            if self._grammar_on:
                self._gstate_dev = out[5]  # donated input's successor
            t_dev = time.perf_counter() - t0
            self.cache.pools = pools
            self.cache.adopt_lens_device(new_lens)
            self._tokens_dev = last
            self._observe_step(t_dev / k, k)
            with span("serve.commit"):
                self._commit_decode(decoding, k, ids, lps, lp_vals, lp_ids,
                                    logits_rows)
        wall = time.perf_counter() - wall0
        self._h_host.observe((wall - t_dev) / k)

    def _commit_decode(self, decoding, k, ids, lps, lp_vals, lp_ids,
                       logits_rows) -> None:
        """Append each decoding slot's sampled tokens of ``k`` steps, advance
        the host length mirror, and run the beam groups' joint selections."""
        beam_groups = []
        for i in range(k):
            for slot, state in decoding.items():
                if state.done:
                    continue  # finished mid-window (EOS): overrun ids discarded
                grp = state.group
                if grp is not None and grp.mode == "beam":
                    # the KV write happened (bump the mirror), but the DEVICE
                    # sample is not the branch's next token — the top-k pair
                    # is this branch's candidate row, selection is joint
                    self.cache.bump_len(slot)
                    grp.pending_rows[state.branch] = (
                        lp_vals[i, slot], lp_ids[i, slot]
                    )
                    if grp not in beam_groups:
                        beam_groups.append(grp)
                    continue
                tok = int(ids[i, slot])
                state.generated.append(tok)
                state.cum_logprob += float(lps[i, slot])
                if state.grammar_state is not None:
                    state.grammar_state = int(
                        self._gtrans_host[state.grammar_state, tok]
                    )
                self.cache.bump_len(slot)
                n_lp = state.request.logprobs
                if n_lp and lp_vals is not None:
                    state.logprobs[len(state.generated) - 1] = [
                        (int(t), float(v))
                        for t, v in zip(lp_ids[i, slot, :n_lp],
                                        lp_vals[i, slot, :n_lp])
                    ]
                if logits_rows is not None and self._records(state):
                    self.logits_of.setdefault(state.request.rid, {})[
                        len(state.generated) - 1
                    ] = logits_rows[slot].copy()
        for grp in beam_groups:
            started = [
                st for st in grp.branches if not st.await_fork and not st.done
            ]
            if all(st.branch in grp.pending_rows for st in started):
                self._beam_advance(grp)

    def _sweep_finished(self) -> None:
        with span("serve.commit"):
            for slot in list(self.scheduler.running):
                state = self.scheduler.running[slot]
                if state.done:
                    self._retire(slot, state)

    def _retire(self, slot: int, state: RequestState) -> None:
        """Stamp a finished request, free its slot and record its result."""
        state.finish_time = time.perf_counter() - self._t0
        reason = state.finished_reason()
        if self.trace is not None:
            self.trace.instant(
                "finish", slot, rid=state.request.rid, reason=reason,
                generated=len(state.generated), branch=state.branch,
            )
        # session retention: demote a cleanly-finished request's pages
        # to the host tier with an eviction deadline, so a follow-up
        # sharing this context prefetches instead of re-prefilling
        if (self.cache.tier is not None
                and self.config.retain_finished_s > 0
                and state.error is None):
            self.cache.demote_slot(
                slot, state.hash_chain(self.cache.page_size),
                retain_s=self.config.retain_finished_s,
            )
        # freeing this branch's pages decrefs — never frees — the
        # pages its still-running siblings alias (cache.free_slot),
        # so one branch's EOS neither stalls nor corrupts the rest
        self.scheduler.finish(slot)
        grp = state.group
        if grp is None:
            self.results[state.request.rid] = state
        elif grp.all_done and state.request.rid not in self.results:
            # the group completes as a UNIT: results carry the primary,
            # whose .sequences ranks/collects every branch
            grp.primary.finish_time = state.finish_time
            self.results[state.request.rid] = grp.primary

    # -- main loop ----------------------------------------------------------------
    def run(self, requests: Optional[Sequence[Request]] = None) -> Dict[int, RequestState]:
        """Serve until every submitted request completes; returns rid -> state.
        A request the pool can never hold (Scheduler.impossible) is FAILED —
        returned with .error set and empty .generated — instead of wedging the
        queue; everything behind it keeps serving. Each iteration is one
        ``serve.tick`` span; waiting for the next arrival lies outside it."""
        if requests is not None:
            self.submit_all(requests)
        self._pending.sort(key=lambda s: s.request.arrival_time)
        self._t0 = time.perf_counter()
        self._last_fetch_end = None
        with gc_spans():
            while self._pending or self.queue or self.scheduler.running:
                with span("serve.tick"):
                    wait = self._tick()
                if wait:
                    time.sleep(wait)
        return self.results

    def _tick(self) -> float:
        """One iteration of the serving loop: arrivals, admission, prefill,
        one decode dispatch, sweeping. Returns the seconds to sleep before the
        next when nothing ran and the next arrival is not yet due, else 0."""
        now = time.perf_counter() - self._t0
        if self.cache.tier is not None:
            self.cache.tier.begin_step()
        # broken twins: a slot whose twin donor died before covering its
        # adopted pages holds garbage — preempt it back to the queue for a
        # clean re-admit (its pages never demote; they were never written)
        for slot in self.cache.take_broken():
            if slot in self.scheduler.running:
                self.scheduler.preempt_slot(slot, self.queue)
        chunked = self.config.chunked_prefill
        with span("serve.admit") as sp:
            self._enqueue_arrivals(now)
            admitted = (self._admit_chunked(now) if chunked
                        else self._admit_monolithic(now))
            sp.set_metadata(admitted=len(admitted))
        if chunked:
            self._prefill_chunks(now)
        else:
            self._prefill_monolithic(admitted)
        self._sweep_finished()  # a request can complete at prefill time
        running = self.scheduler.running
        if any(st.phase == DECODING for st in running.values()):
            for slot in sorted(running):
                if slot in running and running[slot].phase == DECODING:
                    self.scheduler.ensure_decode_page(slot, self.queue)
            self._decode_once(now)
            self._sweep_finished()
            return 0.0
        self._last_fetch_end = None  # the decoding stretch, if any, ended
        if running:
            return 0.0  # only PREFILLING slots: next mixed step continues chunking
        if self._pending and not self.queue:
            return min(max(self._pending[0].request.arrival_time - now, 0.0), 0.01)
        if self.queue:
            # nothing running, nothing arriving, head request not admitted:
            # the whole (free) pool cannot hold its unshared pages — this
            # can never resolve (with nothing running, no donor pages will
            # ever join the prefix index). reject_impossible already failed
            # requests too big for the pool, so this is the safety net for
            # allocator states it cannot see.
            head = self.queue.peek()
            raise RuntimeError(
                f"request {head.request.rid} needs "
                f"{self.cache.new_pages_needed(head.context)} new pages but only "
                f"{self.cache.num_free} exist — raise num_pages"
            )
        return 0.0

    def _enqueue_arrivals(self, now: float) -> None:
        """Queue every pending request due by ``now``, then fail the queue-head
        requests the pool can never hold."""
        while self._pending and self._pending[0].request.arrival_time <= now:
            state = self._pending.pop(0)
            if self.trace is not None:
                self.trace.instant("enqueue", rid=state.request.rid)
            self.queue.push(state)
        for state in self.scheduler.reject_impossible(self.queue):
            state.finish_time = time.perf_counter() - self._t0
            # a rejected request can never resume: drop any host-tier
            # residency its context holds (no orphaned host pages)
            if self.cache.tier is not None:
                self.cache.release_host(
                    state.hash_chain(self.cache.page_size)
                )
            if state.group is not None:
                for st in state.group.branches:
                    if st.finish_reason is None:  # keep earlier finishes
                        st.error = state.error
                        st.finish_reason = FINISH_ERROR
            else:
                state.finish_reason = FINISH_ERROR
            self.results[state.request.rid] = state

    def reset_metrics(self) -> None:
        """Drop finished-request records and timing state (benchmarks rehearse a
        warmup trace on the same engine so jit caches stay hot, then reset):
        zero every registry instrument, clear the trace ring, restart the
        straggler EMA, and reset allocator stats."""
        self.results = {}
        self.logits_of = {}
        self.registry.reset()
        if self.trace is not None:
            self.trace.clear()
        self._last_step_time = None
        self._straggler = StragglerPolicy(
            threshold=self.config.slow_step_threshold
        )
        self.cache.reset_stats()

    # -- metrics ------------------------------------------------------------------
    def metrics(self) -> Dict[str, float]:
        """Flat snapshot over the registry + per-request records + allocator
        stats — same keys the bench suite always consumed, now backed by
        O(1)-memory sketches (histogram percentiles are within one log-bucket
        of exact, ~7.5% relative)."""
        # the autotuner's decision rides every snapshot (empty ones included)
        # so "what config is this engine actually running" is always one
        # metrics() call away; absent entirely when autotune is off, keeping
        # the no-autotune snapshot shape byte-identical to before the feature
        tuning: Dict[str, float] = {}
        if self.tuned is not None:
            tuning = {
                "tuned_page_size": self.config.page_size,
                "tuned_block_pages": self.config.decode_block_pages,
                "tuned_chunk_tokens": self.config.chunk_tokens,
                "tuned_source": self.tuned.source,
            }
        failed = [s for s in self.results.values() if s.error is not None]
        states = [s for s in self.results.values() if s.error is None]
        if not states:
            out = {"failed": len(failed)} if failed else {}
            out.update(tuning)
            return out
        wall = max(s.finish_time for s in states)
        # throughput over the SPAN the engine was actually serving: replayed
        # traces with offset arrivals used to divide by max(finish) alone,
        # under-reporting whenever the first arrival wasn't at epoch 0
        span = wall - min(s.request.arrival_time for s in states)
        e2e = np.array([s.finish_time - s.request.arrival_time for s in states])
        ttft = np.array(
            [s.first_token_time - s.request.arrival_time for s in states]
        )
        # TTFT split at the first admission: time queued, then prefill (the
        # chunks, spread over ticks, and the first token's sampling)
        queue_wait = np.array(
            [s.admit_time - s.request.arrival_time for s in states]
        )
        prefill = np.array([s.first_token_time - s.admit_time for s in states])
        # decode work done: a branch group's primary stands for the whole
        # group in results, so count every branch's tokens, not just its own
        n_tok = sum(
            sum(len(b.generated) for b in s.group.branches)
            if s.group is not None else len(s.generated)
            for s in states
        )
        # speculative acceptance telemetry (absent when spec_tokens=0, so the
        # non-speculative snapshot keeps its exact pre-feature shape):
        # accepted_tokens_per_step is the headline — mean tokens committed per
        # slot-window (>= 1 by construction: the correction token always
        # commits); draft_hit_rate is the fraction of PROPOSED draft tokens
        # that committed; spec_rollback_tokens counts positions whose KV was
        # written then abandoned to the lens rollback
        spec: Dict[str, float] = {}
        if self._spec_k:
            w = self._c_spec_windows.value
            spec = {
                "spec_windows": w,
                "spec_accepted_tokens": self._c_spec_accepted.value,
                "accepted_tokens_per_step": (
                    self._c_spec_accepted.value / w if w else 0.0
                ),
                "draft_hit_rate": (
                    self._c_spec_hits.value / (w * self._spec_k) if w else 0.0
                ),
                "spec_rollback_tokens": self._c_spec_rollback.value,
                "spec_backoffs": self._c_spec_backoffs.value,
            }
        return {
            "requests": len(states),
            "failed": len(failed),
            "generated_tokens": n_tok,
            "wall_s": float(wall),
            "tokens_per_s": float(n_tok / span) if span > 0 else float("inf"),
            "decode_steps": self._c_decode.value,
            "fused_steps": self._c_fused.value,
            # device-path tail + the host-vs-device breakdown: step_ms_* times
            # dispatch + device execute + the (B,)/(K, B) ids fetch per token;
            # host_overhead_ms_p50 is the wall-clock the host loop adds around
            # it (slot sync, scheduler bookkeeping) — what the device-resident
            # refactor squeezed out, and what the bench's breakdown proves
            "step_ms_p50": self._h_step.percentile(50) * 1e3,
            "step_ms_p95": self._h_step.percentile(95) * 1e3,
            # summed device step time (dispatch + execute + ids fetch) over
            # every decode step/window: generated-minus-first tokens divided
            # by this is DECODE throughput, the hot-path quantity the
            # speculative bench gates on without prefill/scheduler noise
            "decode_ms_total": self._h_step.total * 1e3,
            "host_overhead_ms_p50": self._h_host.percentile(50) * 1e3,
            "decode_gap_ms_p50": self._h_gap.percentile(50) * 1e3,
            "decode_gap_ms_p98": self._h_gap.percentile(98) * 1e3,
            "latency_s_p50": float(np.percentile(e2e, 50)),
            "latency_s_p99": float(np.percentile(e2e, 99)),
            "ttft_s_p50": float(np.percentile(ttft, 50)),
            "ttft_s_p95": float(np.percentile(ttft, 95)),
            "ttft_s_p99": float(np.percentile(ttft, 99)),
            "queue_wait_s_p50": float(np.percentile(queue_wait, 50)),
            "queue_wait_s_p85": float(np.percentile(queue_wait, 85)),
            "prefill_s_p50": float(np.percentile(prefill, 50)),
            "prefill_s_p85": float(np.percentile(prefill, 85)),
            "preemptions": sum(s.n_preemptions for s in states),
            "slow_steps": self._c_slow.value,
            "prefill_tokens_computed": self._c_pf_computed.value,
            "prefill_tokens_skipped": self._c_pf_skipped.value,
            **spec,
            **self.cache.stats(),
            **tuning,
        }
