"""Run one benchmark cell on the chip and print one JSON result line.

    python benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: check for the chip, draw weights and traffic from the seed, build
the engine, warm it on a rehearsal trace whose token ids are disjoint from the
window's, then serve the window's requests through ``ServeEngine.run``. After
the window the engine is freed and the plain float32 reference
(``reference.py``) re-reads a sample of the served tokens; ``correct`` holds
when every request was served in full and no sampled token's logit lies more
than the configuration's limit below the reference's best. With ``--trace 1``
a profiler trace of a sub-window gives the device metrics, and the per-layer
metrics are printed instead of the end-to-end ones.

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips than
the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT))

FIRST_ID = 1  # the window's token ids are [1, vocab // 2), the rehearsal's the rest
TRACE_AT, TRACE_FOR = 0.4, 4.0  # traced sub-window: start share, seconds
T_CHIP = T_START  # when JAX had found the chip


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def find_cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json")


def metric_names(bench: dict, cell: str, trace: bool) -> list:
    """The cell's end-to-end metrics, or with ``trace`` its per-layer ones
    (a per-layer metric without ``workloads`` goes wherever its ``moves``
    metric is reported)."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return [(m["name"], m["unit"]) for m in e2e]
    moves = {m["name"] for m in e2e}
    return [(m["name"], m["unit"]) for m in bench["per_layer"]
            if cell in m.get("workloads", [cell] if m["moves"] in moves else [])]


def reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.chip.metrics.{name}", HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    bench = load_benchmark()
    cell = find_cell(bench, a.workload)

    import jax

    devs = jax.devices()
    global T_CHIP
    T_CHIP = time.perf_counter()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"run.py: cell {cell['name']} needs {cell['chips']} TPU chip(s); "
              f"JAX found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 3
    return execute(cell, a.seed, a.seconds, bool(a.trace),
                   metric_names(bench, cell["name"], bool(a.trace)))


def build(cfg, mix, seed):
    """Weights from the seed, the engine, and its warm-up on a rehearsal trace
    of the mix's own kinds of request (token ids disjoint from the window's),
    then ``reset_metrics``. Returns (params, engine)."""
    import jax

    from benchmarks.chip import engine_cell as ec
    from benchmarks.chip import traffic, weights

    m = cfg["model"]
    t0 = time.perf_counter()
    params = weights.make_init(m)(weights.weight_key(seed))
    jax.block_until_ready(params)
    t1 = time.perf_counter()
    engine = ec.build_engine(ec.build_model(cfg), params, ec.engine_config(cfg))
    t2 = time.perf_counter()
    warm = cfg["rehearsal"]
    ps = cfg["engine"]["page_size"]
    ids = (m["vocab"] // 2, m["vocab"])
    # every prefill-chunk bucket: page_size * 2**k up to the engine's default
    # chunk of two pages. Each first alone, as one unshared prompt of that
    # length: among the mix's requests the prefix cache or the step's token
    # budget may cut a chunk to a smaller bucket.
    cuts = [ps * 2**k for k in range(2)]
    for k, it in enumerate(traffic.solo(seed + 1, ids, cuts, warm["new_tokens"])):
        engine.run(ec.make_requests([(it.prompt, it.new_tokens, 0.0)],
                                    rid0=(1 << 30) - 1 - k))
    engine.run(ec.make_requests(
        [(it.prompt, it.new_tokens, 0.0) for it in traffic.rehearsal(
            mix, seed + 1, ids, n=warm["requests"], cuts=cuts,
            new_tokens=warm["new_tokens"])],
        rid0=1 << 30))
    engine.reset_metrics()
    jax.block_until_ready(engine.cache.pools)
    print(f"set-up: JAX and the chip {T_CHIP - T_START:.3f} s, imports "
          f"{t0 - T_CHIP:.3f} s, weights {t1 - t0:.3f} s, "
          f"engine {t2 - t1:.3f} s, rehearsal {time.perf_counter() - t2:.3f} s",
          file=sys.stderr, flush=True)
    return params, engine


def window_items(cfg, mix, seed, seconds):
    from benchmarks.chip import engine_cell as ec
    from benchmarks.chip import traffic

    items = traffic.generate(mix, seed, seconds,
                             (FIRST_ID, cfg["model"]["vocab"] // 2))
    return items, ec.make_requests(
        [(it.prompt, it.new_tokens, it.arrival_s) for it in items])


def records_of(items, requests, results):
    from benchmarks.chip.window import Record

    out = []
    for it, req in zip(items, requests):
        st = results.get(req.rid)
        out.append(Record(
            rid=req.rid, prompt_len=len(it.prompt), new_tokens=it.new_tokens,
            arrival_s=it.arrival_s,
            first_token_s=getattr(st, "first_token_time", None),
            finish_s=getattr(st, "finish_time", None),
            generated=tuple(st.generated) if st is not None else (),
            error="never finished" if st is None else (
                None if st.error is None else str(st.error))))
    return out


def execute(cell, seed, seconds, trace, names, *, cfg=None, mix=None) -> int:
    """Everything after the look for the chip. ``cfg`` and ``mix`` replace the
    cell's configuration and traffic files (the tests run tiny ones on the
    CPU)."""
    import jax

    from benchmarks.chip import check, compile_cache, traffic
    from benchmarks.chip import engine_cell as ec
    from benchmarks.chip import trace as tr
    from benchmarks.chip.window import Run

    compile_cache.enable()
    counter = tr.CompileCounter()
    dev = jax.devices()[0]
    cfg = cfg or ec.load_config(cell["config"])
    mix = mix or traffic.load_mix(cell["traffic"])
    params, engine = build(cfg, mix, seed)
    items, requests = window_items(cfg, mix, seed, seconds)
    setup_s = time.perf_counter() - T_START

    before = counter.snapshot()
    tracer = tr.SubWindow(engine, seconds * TRACE_AT, TRACE_FOR,
                          HERE / "out" / "trace") if trace else None
    if tracer:
        tracer.start()
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        results = engine.run(requests)
    window_wall = time.perf_counter() - t0
    if tracer:
        tracer.join()
        tracer.engine = None
    in_window = counter.since(before)
    print(f"window: {len(requests)} requests in {window_wall:.3f} s; programs "
          f"lowered in the window {in_window['lowered']}, compiled "
          f"{in_window['compiled']}", file=sys.stderr, flush=True)

    records = records_of(items, requests, results)
    print("ttft_s " + " ".join(f"{r.first_token_s - r.arrival_s:.4f}" for r in
                              sorted(records, key=lambda r: r.arrival_s) if r.ok),
          file=sys.stderr)
    run = Run(cell=cell, model=cfg["model"], engine=cfg["engine"],
              device_kind=dev.device_kind, seconds=seconds, setup_s=setup_s,
              records=records, engine_metrics=engine.metrics())
    if tracer:
        run.trace = tr.reduce(tracer.path(), window_s=tracer.window_s)
        run.traced = tracer.positions()
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")

    metrics = {}
    for name, unit in names:
        v = reader(name)(run)
        if v is not None and math.isfinite(v):
            metrics[name] = {"value": float(v), "unit": unit}

    del engine, results
    gc.collect()
    verdict = check.judge(params, cfg, items, records, seed)
    for line in verdict["lines"]:
        print(line, file=sys.stderr)
    out = {
        "correct": verdict["correct"],
        "attempted": len(records),
        "failed": sum(not r.ok for r in records),
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices()), "memory_peak_bytes": peak},
    }
    if tracer:
        out["device"].update(busy_s=run.trace["busy_s"],
                             window_s=run.trace["window_s"])
        out["breakdown"] = {"device_ops": run.trace["top_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    out["checks"] = verdict["checks"]
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
