"""Serving telemetry: streaming metrics registry, lifecycle trace, per-request
logprobs, and the straggler hook — the observability layer of the engine.

The registry replaces unbounded timing lists with O(1)-memory sketches, so
the tests pin the sketch's accuracy against exact numpy percentiles; the
trace is the host-side log of every engine transition, so the tests replay
runs that exercise each transition (admission, chunked prefill, preemption,
CoW, fused windows, finish) and cross-check the trace's event counts against
the engine's own metrics counters — two independent observers of the same
execution must agree.
"""
import dataclasses
import gc
import json
from typing import Dict, NamedTuple

import jax
import numpy as np
import pytest

from repro.models import build_model, get_config
from repro.serving import GenerationParams
from repro.serving.engine import (
    EngineConfig, Request, RequestState, ServeEngine, validate_chrome_trace,
)
from repro.serving.telemetry import (
    SCHED_TRACK, Counter, EngineTrace, Gauge, Histogram, MetricsRegistry,
)


# =====================================================================================
# histogram / registry — O(1)-memory sketches
# =====================================================================================
def test_histogram_percentiles_match_numpy():
    """32 log buckets per decade bound relative error at ~7.5% worst-case;
    lognormal timing-like data lands well inside it."""
    rng = np.random.default_rng(0)
    xs = rng.lognormal(mean=-6.0, sigma=0.7, size=20_000)  # ~ms-scale timings
    h = Histogram()
    for x in xs:
        h.observe(float(x))
    for q in (50, 95, 99):
        exact = float(np.percentile(xs, q))
        assert abs(h.percentile(q) - exact) / exact < 0.075, q
    assert abs(h.mean - float(xs.mean())) / float(xs.mean()) < 1e-6
    snap = h.snapshot()
    assert snap["count"] == xs.size
    assert snap["min"] == pytest.approx(float(xs.min()))
    assert snap["max"] == pytest.approx(float(xs.max()))


def test_histogram_empty_single_and_out_of_range():
    h = Histogram()
    assert h.percentile(50) == 0.0
    assert h.snapshot()["count"] == 0
    h.observe(3.5e-3)
    assert h.percentile(50) == pytest.approx(3.5e-3)  # clamp to [min, max]
    assert h.percentile(99) == pytest.approx(3.5e-3)
    # under/overflow land in the edge buckets but percentiles stay clamped to
    # observed extremes — no fabricated values outside the data
    h2 = Histogram(lo=1e-3, hi=1e0)
    h2.observe(1e-6)
    h2.observe(42.0)
    assert h2.percentile(1) == pytest.approx(1e-6)
    assert h2.percentile(99) == pytest.approx(42.0)
    assert h2.snapshot()["count"] == 2


def test_histogram_sub_resolution_samples():
    """Samples below the default lo=1e-7 (sub-100ns 'timings' — clock jitter,
    zero-work steps) land in the underflow bucket but never corrupt the
    sketch: count/mean/min stay exact and percentiles never fabricate a value
    the data doesn't contain."""
    h = Histogram()
    tiny = (0.0, 1e-12, 9.9e-8)
    for v in tiny:
        h.observe(v)
    assert h.counts[0] == len(tiny)  # all three under lo -> underflow bucket
    assert h.percentile(50) == 0.0  # == observed min, not a bucket edge
    assert h.snapshot()["min"] == 0.0
    assert h.snapshot()["mean"] == pytest.approx(sum(tiny) / len(tiny))
    # a normal sample after the underflow run: p99 tops out at the real max
    h.observe(2e-3)
    assert h.percentile(99) == pytest.approx(2e-3)
    assert h.snapshot()["count"] == 4


def test_histogram_single_sample_every_percentile():
    """With one observation every percentile IS that observation — the
    interpolation path must clamp to [min, max] rather than report an edge of
    the covering bucket."""
    h = Histogram()
    h.observe(7.3e-4)
    for q in (0, 1, 50, 95, 99, 100):
        assert h.percentile(q) == pytest.approx(7.3e-4), q


def test_histogram_reset_then_record():
    """reset() must return the histogram to a pristine state: stale min/max
    or counts surviving a reset would poison the first post-reset snapshot —
    exactly the rehearsal -> reset_metrics -> measure idiom the bench suite
    leans on."""
    h = Histogram()
    for v in (1e-9, 5e-3, 2.0, 5e3):  # underflow, two in-range, overflow
        h.observe(v)
    h.reset()
    assert h.count == 0 and h.total == 0.0
    assert h.percentile(50) == 0.0
    assert all(c == 0 for c in h.counts)
    snap = h.snapshot()
    assert snap["min"] == 0.0 and snap["max"] == 0.0  # not inf / stale
    h.observe(4e-2)
    assert h.percentile(50) == pytest.approx(4e-2)
    assert h.snapshot()["count"] == 1
    assert h.min == pytest.approx(4e-2) and h.max == pytest.approx(4e-2)


def test_registry_create_or_get_and_reset():
    reg = MetricsRegistry()
    c = reg.counter("steps")
    assert reg.counter("steps") is c  # create-or-get: one instrument per name
    c.inc()
    c.inc(4)
    g = reg.gauge("depth")
    g.set(7.0)
    h = reg.histogram("lat")
    h.observe(0.25)
    snap = reg.snapshot()
    assert snap["steps"] == 5
    assert snap["depth"] == 7.0
    assert snap["lat"]["count"] == 1
    reg.reset()  # zero values, keep registrations (cached references stay live)
    assert c.value == 0
    assert g.value == 0.0
    assert h.snapshot()["count"] == 0
    assert reg.counter("steps") is c


def test_counter_gauge_direct():
    c = Counter()
    c.inc()
    assert c.value == 1
    g = Gauge()
    g.set(2.5)
    assert g.value == 2.5


# =====================================================================================
# trace ring + Chrome export invariants
# =====================================================================================
def test_trace_chrome_export_and_tracks():
    tr = EngineTrace()
    tr.instant("enqueue", rid=0)
    tr.begin("prefill", 0, rid=0)
    tr.end("prefill", 0)
    tr.begin("decode", SCHED_TRACK, batch=1)
    tr.end("decode", SCHED_TRACK)
    chrome = tr.to_chrome()
    validate_chrome_trace(chrome)
    evs = chrome["traceEvents"]
    # one thread-name metadata record per track, scheduler tid 0, slot s+1
    names = {e["tid"]: e["args"]["name"] for e in evs if e["ph"] == "M"}
    assert "scheduler" in names[0].lower()
    assert {e["tid"] for e in evs if e["ph"] != "M"} == {0, 1}
    ts = [e["ts"] for e in evs if e["ph"] != "M"]
    assert ts == sorted(ts)


def test_trace_ring_wrap_still_validates():
    """Wrapping the ring can orphan B/E pairs at the edges; the export must
    repair them (drop stray Es, close stray Bs) so the file always opens."""
    tr = EngineTrace(capacity=8)
    for i in range(50):
        tr.begin("span", i % 3)
        tr.instant("tick", i % 3, i=i)
        tr.end("span", i % 3)
    assert tr.dropped > 0
    assert len(tr.events) == 8
    validate_chrome_trace(tr.to_chrome())


def test_trace_clear():
    tr = EngineTrace()
    tr.instant("x")
    tr.clear()
    assert len(tr.events) == 0
    assert tr.dropped == 0


def test_validate_chrome_trace_rejects_malformed():
    ok = {"traceEvents": [
        {"ph": "B", "name": "s", "pid": 1, "tid": 0, "ts": 1},
        {"ph": "E", "name": "s", "pid": 1, "tid": 0, "ts": 2},
    ]}
    validate_chrome_trace(ok)
    with pytest.raises(ValueError):
        validate_chrome_trace({})  # no traceEvents
    with pytest.raises(ValueError):  # decreasing ts
        validate_chrome_trace({"traceEvents": [
            {"ph": "i", "name": "a", "pid": 1, "tid": 0, "ts": 5},
            {"ph": "i", "name": "b", "pid": 1, "tid": 0, "ts": 1},
        ]})
    with pytest.raises(ValueError):  # E without B
        validate_chrome_trace({"traceEvents": [
            {"ph": "E", "name": "s", "pid": 1, "tid": 0, "ts": 1},
        ]})
    with pytest.raises(ValueError):  # mismatched span names
        validate_chrome_trace({"traceEvents": [
            {"ph": "B", "name": "a", "pid": 1, "tid": 0, "ts": 1},
            {"ph": "E", "name": "b", "pid": 1, "tid": 0, "ts": 2},
        ]})
    with pytest.raises(ValueError):  # unclosed span
        validate_chrome_trace({"traceEvents": [
            {"ph": "B", "name": "a", "pid": 1, "tid": 0, "ts": 1},
        ]})


# =====================================================================================
# engine integration — the trace and the metrics observe the same run
# =====================================================================================
@pytest.fixture(scope="module")
def small_model():
    cfg = dataclasses.replace(get_config("qwen2-0.5b", smoke=True), dtype="float32")
    model = build_model(cfg)
    params = model.init_params(jax.random.key(0))
    return cfg, model, params


def test_trace_off_by_default(small_model):
    cfg, model, params = small_model
    eng = ServeEngine(
        model, params, EngineConfig(num_pages=16, page_size=4, max_batch=2)
    )
    assert eng.trace is None
    rng = np.random.default_rng(0)
    eng.run([Request(
            rid=0,
            prompt=rng.integers(0, cfg.vocab, size=5).tolist(),
            params=GenerationParams(max_new_tokens=3),
        )])
    m = eng.metrics()
    assert m["requests"] == 1
    assert "slow_steps" in m


def test_preemption_run_trace_is_valid_and_matches_metrics(small_model, tmp_path):
    """Tight pool forces preemption mid-run; the exported trace must be valid
    Chrome JSON and its event counts must agree with the engine's counters —
    the trace IS the host-side allocator/scheduler log, just timestamped."""
    cfg, model, params = small_model
    eng = ServeEngine(model, params, EngineConfig(
        num_pages=10, page_size=4, max_batch=3, max_pages_per_seq=6, trace=True,
    ))
    rng = np.random.default_rng(3)
    reqs = [Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab, size=8).tolist(),
            params=GenerationParams(max_new_tokens=10),
        ) for i in range(3)]
    results = eng.run(reqs)
    m = eng.metrics()
    assert m["preemptions"] >= 1  # the pool is sized to make this certain
    tr = eng.trace
    assert tr.count("enqueue") == len(reqs)
    assert tr.count("finish") == m["requests"]
    assert tr.count("preempt") == m["preemptions"]
    assert tr.count("cow") == m["cow_copies"]
    # every admission allocates exactly once (re-admissions after preemption
    # allocate again — both counts include them)
    assert tr.count("admit") == tr.count("alloc")
    assert tr.count("admit") == len(reqs) + m["preemptions"]
    assert tr.count("prefill", ph="B") == tr.count("prefill", ph="E")
    chrome = tr.to_chrome()
    validate_chrome_trace(chrome)
    path = tmp_path / "trace.json"
    tr.export(path)
    reloaded = json.loads(path.read_text())
    validate_chrome_trace(reloaded)
    # tracks: scheduler (tid 0) plus one per slot that saw events
    tids = {e["tid"] for e in reloaded["traceEvents"] if e["ph"] != "M"}
    assert 0 in tids and len(tids) >= 2
    assert all(results[r].error is None for r in results)


class Span(NamedTuple):
    name: str
    start: int  # ns, the profile's clock
    end: int
    args: Dict[str, object]


def _profiled(tmp_path, fn):
    """Run ``fn`` under the JAX profiler; returns (its result, the ``serve.*``
    spans of the profile's host plane in start order)."""
    from jax.profiler import ProfileData, ProfileOptions

    opts = ProfileOptions()
    opts.python_tracer_level = 0  # the program's spans, not every Python call
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    data = ProfileData.from_file(str(path))
    spans = [
        Span(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, {k: v for k, v in ev.stats})
        for plane in data.planes if plane.name.startswith("/host")
        for line in plane.lines for ev in line.events
        if ev.name.startswith("serve.")
    ]
    return out, sorted(spans, key=lambda sp: sp.start)


def _named(spans, *names):
    return [sp for sp in spans if sp.name in names]


def _inside(inner, outers) -> bool:
    return any(o.start <= inner.start and inner.end <= o.end for o in outers)


SERVE_SPANS = ("serve.tick", "serve.admit", "serve.chunk", "serve.sync",
               "serve.dispatch", "serve.fetch", "serve.commit", "serve.gc")


def test_chunked_run_traces_chunk_spans(small_model, tmp_path):
    cfg, model, params = small_model
    eng = ServeEngine(model, params, EngineConfig(
        num_pages=32, page_size=4, max_batch=2, max_pages_per_seq=16,
        chunked_prefill=True, chunk_tokens=8, trace=True,
    ))
    rng = np.random.default_rng(5)
    _, spans = _profiled(tmp_path, lambda: eng.run([
        Request(
                rid=0,
                prompt=rng.integers(0, cfg.vocab, size=30).tolist(),
                params=GenerationParams(max_new_tokens=4),
            ),
        Request(
                rid=1,
                prompt=rng.integers(0, cfg.vocab, size=6).tolist(),
                params=GenerationParams(max_new_tokens=4),
            ),
    ]))
    tr = eng.trace
    assert tr.count("chunk", ph="B") >= 2  # the 30-token prompt needs several
    assert tr.count("chunk", ph="B") == tr.count("chunk", ph="E")
    validate_chrome_trace(tr.to_chrome())
    # each chunk is one serve.chunk span on the profiler's clock, the ring's
    # B/E pair written by the same call
    chunks = _named(spans, "serve.chunk")
    assert len(chunks) == tr.count("chunk", ph="B")
    assert all(sp.end > sp.start for sp in chunks)
    assert sum(sp.args["tokens"] for sp in chunks) == (
        eng.metrics()["prefill_tokens_computed"])


def test_serving_loop_spans_land_in_the_profilers_host_plane(small_model, tmp_path):
    """A chunked run under the profiler records every span of the serving
    loop, with its args as event stats: fetches and chunks nest inside a
    tick, and with the ring on its B/E counts match the spans'."""
    cfg, model, params = small_model
    eng = ServeEngine(model, params, EngineConfig(
        num_pages=32, page_size=4, max_batch=2, max_pages_per_seq=16,
        chunked_prefill=True, chunk_tokens=8, trace=True,
    ))
    sweep = eng._sweep_finished

    def sweep_and_collect():  # a collector pause inside the serving loop
        gc.collect()
        sweep()

    eng._sweep_finished = sweep_and_collect
    rng = np.random.default_rng(6)
    _, spans = _profiled(tmp_path, lambda: eng.run([Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab, size=n).tolist(),
            params=GenerationParams(max_new_tokens=4),
        ) for i, n in enumerate((30, 6, 9))]))
    assert {sp.name for sp in spans} >= set(SERVE_SPANS)
    ticks = _named(spans, "serve.tick")
    for sp in _named(spans, "serve.fetch", "serve.chunk", "serve.dispatch"):
        assert _inside(sp, ticks), sp
    fetches = _named(spans, "serve.fetch")
    assert {sp.args["what"] for sp in fetches} == {"ids", "first_token"}
    assert all("rid" in sp.args for sp in fetches if sp.args["what"] == "first_token")
    assert all({"rid", "tokens", "bucket"} <= set(sp.args)
               for sp in _named(spans, "serve.chunk"))
    assert sum(sp.args["admitted"] for sp in _named(spans, "serve.admit")) == 3
    assert all("generation" in sp.args for sp in _named(spans, "serve.gc"))
    # the ring's pairs come from the same span calls
    tr = eng.trace
    assert tr.count("chunk", ph="B") == len(_named(spans, "serve.chunk"))
    windows = _named(spans, "serve.decode", "serve.fused_window")
    assert tr.count("decode", ph="B") + tr.count("fused_window", ph="B") == (
        len(windows)) == len(_named(spans, "serve.dispatch"))
    validate_chrome_trace(tr.to_chrome())


def test_queue_wait_plus_prefill_is_ttft_and_first_admission_is_kept(small_model):
    """TTFT splits at the first admission into queue wait and prefill, from
    the stamps alone; a re-admission after preemption keeps that stamp."""
    cfg, model, params = small_model
    eng = ServeEngine(model, params, EngineConfig(
        num_pages=10, page_size=4, max_batch=3, max_pages_per_seq=6,
    ))
    first_admit = {}
    admit = eng.scheduler.admit

    def spy(queue, now, **kw):
        out = admit(queue, now, **kw)
        for _, st in out:
            first_admit.setdefault(st.request.rid, now)
        return out

    eng.scheduler.admit = spy
    rng = np.random.default_rng(3)
    results = eng.run([Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab, size=8).tolist(),
            params=GenerationParams(max_new_tokens=10),
        ) for i in range(3)])
    m = eng.metrics()
    assert m["preemptions"] >= 1  # the pool is sized to make this certain
    states = list(results.values())
    assert any(st.n_preemptions for st in states)
    for st in states:
        assert st.admit_time == first_admit[st.request.rid]
        queue_wait = st.admit_time - st.request.arrival_time
        prefill = st.first_token_time - st.admit_time
        assert queue_wait >= 0 and prefill > 0
        assert queue_wait + prefill == pytest.approx(
            st.first_token_time - st.request.arrival_time, rel=0, abs=1e-12)
    waits = [st.admit_time - st.request.arrival_time for st in states]
    prefills = [st.first_token_time - st.admit_time for st in states]
    for q in (50, 85):
        assert m[f"queue_wait_s_p{q}"] == pytest.approx(np.percentile(waits, q))
        assert m[f"prefill_s_p{q}"] == pytest.approx(np.percentile(prefills, q))


@pytest.mark.parametrize("multi_step", [1, 4])
def test_decode_gap_counts_every_step_but_a_stretchs_first(
        small_model, tmp_path, multi_step):
    """``decode_gap_s`` holds decode_steps observations less the k of each
    window that began a decoding stretch (its tick followed one that did not
    decode): the arrivals are spaced so the engine idles between them."""
    cfg, model, params = small_model
    eng = ServeEngine(model, params, EngineConfig.sized_for(
        8 + 16 + 1, page_size=8, max_batch=2, multi_step=multi_step,
    ))
    rng = np.random.default_rng(8)
    make = lambda: [Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab, size=8).tolist(),
            params=GenerationParams(max_new_tokens=16),
            arrival_time=t,
        ) for i, t in enumerate((0.0, 0.0, 1.0, 2.0))]
    eng.run(make())  # compile outside the spaced run
    eng.reset_metrics()
    _, spans = _profiled(tmp_path, lambda: eng.run(make()))
    ticks = _named(spans, "serve.tick")
    dispatches = _named(spans, "serve.dispatch")
    ks = [[d.args["k"] for d in dispatches if _inside(d, [t])] for t in ticks]
    first_ks = [k[0] for prev, k in zip([[]] + ks, ks) if k and not prev]
    m = eng.metrics()
    assert len(first_ks) >= 3  # one stretch per spaced arrival
    assert sum(sum(k) for k in ks) == m["decode_steps"]
    assert eng.registry.histogram("decode_gap_s").count == (
        m["decode_steps"] - sum(first_ks))
    assert 0 < m["decode_gap_ms_p50"] <= m["decode_gap_ms_p98"]


def test_fused_window_trace_k_sums_to_fused_steps(small_model):
    cfg, model, params = small_model
    eng = ServeEngine(model, params, EngineConfig.sized_for(
        8 + 16 + 1, page_size=8, max_batch=2, multi_step=4, trace=True,
    ))
    rng = np.random.default_rng(7)
    eng.run([Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab, size=8).tolist(),
            params=GenerationParams(max_new_tokens=16),
        ) for i in range(2)])
    m = eng.metrics()
    assert m["fused_steps"] > 0
    k_sum = sum(
        ev.args["k"] for ev in eng.trace.events
        if ev.name == "fused_window" and ev.ph == "B"
    )
    assert k_sum == m["fused_steps"]
    assert m["decode_steps"] >= m["fused_steps"]
    validate_chrome_trace(eng.trace.to_chrome())


def test_metrics_degenerate_paths(small_model):
    cfg, model, params = small_model
    eng = ServeEngine(
        model, params,
        EngineConfig(num_pages=4, page_size=4, max_batch=2, max_pages_per_seq=4),
    )
    assert eng.metrics() == {}  # nothing ran yet
    # a prompt whose floor pages exceed the pool is refused at submit() — the
    # static twin of Scheduler.impossible (which covers preempted requests
    # whose context GREW past the pool at runtime)
    rng = np.random.default_rng(1)
    with pytest.raises(ValueError, match="num_pages"):
        eng.submit(Request(
                rid=0,
                prompt=rng.integers(0, cfg.vocab, size=12).tolist(),
                params=GenerationParams(max_new_tokens=2),
            ))
    # all-failed snapshot: when every recorded request carries .error (the
    # reject_impossible outcome), metrics reports ONLY the failure count —
    # no throughput/latency keys fabricated from an empty sample
    eng.results[0] = RequestState(
        Request(
                rid=0,
                prompt=[1, 2, 3],
                params=GenerationParams(max_new_tokens=2),
            ), error="too big"
    )
    eng.results[1] = RequestState(
        Request(
                rid=1,
                prompt=[4, 5],
                params=GenerationParams(max_new_tokens=2),
            ), error="too big"
    )
    assert eng.metrics() == {"failed": 2}


def test_reset_metrics_zeroes_registry_and_trace(small_model):
    cfg, model, params = small_model
    eng = ServeEngine(model, params, EngineConfig(
        num_pages=16, page_size=4, max_batch=2, trace=True,
    ))
    rng = np.random.default_rng(2)
    make = lambda: [Request(
            rid=0,
            prompt=rng.integers(0, cfg.vocab, size=6).tolist(),
            params=GenerationParams(max_new_tokens=4),
        )]
    eng.run(make())
    assert eng.metrics()["decode_steps"] > 0
    assert len(eng.trace.events) > 0
    eng.reset_metrics()
    assert eng.metrics() == {}
    assert eng.registry.counter("decode_steps").value == 0
    assert eng.registry.histogram("step_time_s").snapshot()["count"] == 0
    assert len(eng.trace.events) == 0
    # the engine keeps serving after a reset, repopulating the same instruments
    eng.run(make())
    assert eng.metrics()["decode_steps"] > 0


def test_tokens_per_s_spans_arrival_to_finish(small_model):
    """Offset arrivals: throughput must divide by (max finish - min arrival),
    not by max finish alone — the old baseline under-reported whenever the
    first arrival wasn't at the run epoch."""
    cfg, model, params = small_model
    eng = ServeEngine(
        model, params, EngineConfig(num_pages=16, page_size=4, max_batch=2)
    )
    rng = np.random.default_rng(4)
    offset = 0.2
    eng.run([Request(
            rid=0,
            prompt=rng.integers(0, cfg.vocab, size=5).tolist(),
            params=GenerationParams(max_new_tokens=4),
            arrival_time=offset,
        )])
    m = eng.metrics()
    span = m["wall_s"] - offset
    assert span > 0
    assert m["tokens_per_s"] == pytest.approx(m["generated_tokens"] / span)
    assert m["tokens_per_s"] > m["generated_tokens"] / m["wall_s"]


# =====================================================================================
# per-request top-k logprobs (ride the existing per-token fetch)
# =====================================================================================
def test_logprobs_greedy_top1_is_generated_token(small_model):
    cfg, model, params = small_model
    eng = ServeEngine(model, params, EngineConfig(
        num_pages=16, page_size=4, max_batch=3, logprobs_k=3,
    ))
    rng = np.random.default_rng(6)
    reqs = [
        Request(
                rid=0,
                prompt=rng.integers(0, cfg.vocab, size=5).tolist(),
                params=GenerationParams(max_new_tokens=5, logprobs=2),
            ),
        Request(
                rid=1,
                prompt=rng.integers(0, cfg.vocab, size=7).tolist(),
                params=GenerationParams(max_new_tokens=5, logprobs=3),
            ),
        Request(
                rid=2,
                prompt=rng.integers(0, cfg.vocab, size=6).tolist(),
                params=GenerationParams(max_new_tokens=5),
            ),  # no opt-in: no logprobs recorded
    ]
    results = eng.run(reqs)
    assert results[2].logprobs == {}
    for rid, want_k in ((0, 2), (1, 3)):
        s = results[rid]
        assert sorted(s.logprobs) == list(range(len(s.generated)))
        for idx, tok in enumerate(s.generated):
            entries = s.logprobs[idx]
            assert len(entries) == want_k
            ids = [t for t, _ in entries]
            vals = [v for _, v in entries]
            # greedy: the sampled token IS the top-1 logprob id
            assert ids[0] == tok
            assert vals == sorted(vals, reverse=True)
            assert all(v <= 0.0 for v in vals)  # log-probabilities


def test_logprobs_wider_than_engine_rejected(small_model):
    cfg, model, params = small_model
    eng = ServeEngine(model, params, EngineConfig(
        num_pages=16, page_size=4, max_batch=2, logprobs_k=3,
    ))
    with pytest.raises(ValueError, match="logprobs"):
        eng.submit(Request(
                rid=0,
                prompt=[1, 2, 3],
                params=GenerationParams(max_new_tokens=2, logprobs=5),
            ))


def test_logprobs_identical_across_fused_horizons(small_model):
    cfg, model, params = small_model
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, cfg.vocab, size=8).tolist() for _ in range(2)]
    make = lambda: [Request(
            rid=i,
            prompt=list(p),
            params=GenerationParams(max_new_tokens=12, logprobs=3),
        )
                    for i, p in enumerate(prompts)]
    conf = EngineConfig.sized_for(8 + 12 + 1, page_size=8, max_batch=2,
                                  logprobs_k=3)
    res = {}
    for k in (1, 4):
        res[k] = ServeEngine(
            model, params, dataclasses.replace(conf, multi_step=k)
        ).run(make())
    for rid in res[1]:
        a, b = res[1][rid], res[4][rid]
        assert a.generated == b.generated
        assert sorted(a.logprobs) == sorted(b.logprobs)
        for idx in a.logprobs:
            assert [t for t, _ in a.logprobs[idx]] == [t for t, _ in b.logprobs[idx]]
            np.testing.assert_allclose(
                [v for _, v in a.logprobs[idx]],
                [v for _, v in b.logprobs[idx]], rtol=1e-4, atol=1e-5,
            )


# =====================================================================================
# straggler hook — slow decode steps are counted and traced
# =====================================================================================
def test_straggler_flags_slow_steps(small_model):
    """threshold < 1 makes every post-seed step 'slower than threshold x EMA',
    so the policy must flag steps, the counter must advance, and each flag
    must land in the trace — without perturbing the run."""
    cfg, model, params = small_model
    eng = ServeEngine(model, params, EngineConfig(
        num_pages=16, page_size=4, max_batch=2, trace=True,
        slow_step_threshold=0.01,
    ))
    rng = np.random.default_rng(9)
    make = lambda: [Request(
            rid=0,
            prompt=rng.integers(0, cfg.vocab, size=6).tolist(),
            params=GenerationParams(max_new_tokens=8),
        )]
    # rehearse first: the compile-laden first dispatch would otherwise seed
    # the EMA ~1000x above steady state and nothing would ever flag.
    # reset_metrics restarts the EMA along with the counters.
    eng.run(make())
    eng.reset_metrics()
    results = eng.run(make())
    m = eng.metrics()
    assert m["slow_steps"] > 0
    assert eng.trace.count("slow_step") == m["slow_steps"]
    assert results[0].error is None
    ev = next(e for e in eng.trace.events if e.name == "slow_step")
    assert ev.args["verdict"] in ("straggle", "rebalance")
    assert ev.args["step_ms"] > 0
