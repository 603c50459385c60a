"""Find an open-loop cell's knee once: one set-up, then the cell's traffic
offered as a staircase of rising rates in one continuous run of the engine.

    python benchmarks/chip/sweep.py --workload <cell> --rates 1,1.4,1.8 --stage 60

Stage k offers the mix at ``rates[k]`` for ``--stage`` seconds, right after
stage k - 1, so each stage starts from the state the rate below it left (a
full batch, not an empty engine). A side thread samples how many requests
wait for a slot. A rate is sustained when the wait does not grow through its
stage: fewer than one request waits, on average, over its second half. The
knee is the highest rate sustained. One JSON line per stage.
"""
from __future__ import annotations

import argparse
import json
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks.chip import run  # noqa: E402

SAMPLE_S = 0.25
SUSTAINED_WAIT = 1.0  # requests waiting, mean over a stage's second half


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--stage", type=float, default=60)
    ap.add_argument("--seed", type=int, default=2**31 + 99)
    a = ap.parse_args(argv)
    import jax
    import numpy as np

    from benchmarks.chip import compile_cache, traffic
    from benchmarks.chip import engine_cell as ec

    if jax.devices()[0].platform != "tpu":
        print("sweep.py: needs a TPU", file=sys.stderr)
        return 3
    compile_cache.enable()
    cell = run.find_cell(run.load_benchmark(), a.workload)
    cfg = ec.load_config(cell["config"])
    mix = traffic.load_mix(cell["traffic"])
    if mix["loop"] != "open":
        print("sweep.py: an offline mix has no knee", file=sys.stderr)
        return 2
    t0 = time.perf_counter()
    _, engine = run.build(cfg, mix, a.seed)
    print(json.dumps({"setup_s": time.perf_counter() - t0}), flush=True)

    rates = [float(r) for r in a.rates.split(",")]
    items, stage_of = [], []
    for k, rate in enumerate(rates):
        part, _ = run.window_items(cfg, dict(mix, rate_per_s=rate), a.seed + k,
                                   a.stage)
        items += [traffic.Item(it.prompt, it.new_tokens,
                               it.arrival_s + k * a.stage, it.prefix)
                  for it in part]
        stage_of += [k] * len(part)
    requests = ec.make_requests(
        [(it.prompt, it.new_tokens, it.arrival_s) for it in items])

    samples, done = [], threading.Event()

    def sample():
        s0 = time.perf_counter()
        while not done.is_set():
            samples.append((time.perf_counter() - s0, len(engine.queue),
                            len(engine.scheduler.running)))
            time.sleep(SAMPLE_S)

    th = threading.Thread(target=sample, daemon=True)
    th.start()
    results = engine.run(requests)
    done.set()
    th.join()
    recs = run.records_of(items, requests, results)
    s = np.array(samples)
    for k, rate in enumerate(rates):
        mine = [r for r, st in zip(recs, stage_of) if st == k]
        ok = [r for r in mine if r.ok]
        ttft = np.array([r.first_token_s - r.arrival_s for r in ok])
        tpot = [1e3 * (r.finish_s - r.first_token_s) / (len(r.generated) - 1)
                for r in ok if len(r.generated) > 1]
        lo, mid, hi = k * a.stage, (k + 0.5) * a.stage, (k + 1) * a.stage
        first = s[(s[:, 0] >= lo) & (s[:, 0] < mid)]
        second = s[(s[:, 0] >= mid) & (s[:, 0] < hi)]
        half = [r.first_token_s - r.arrival_s for r in ok if r.arrival_s >= mid]
        print(json.dumps({
            "rate": rate, "requests": len(mine), "ok": len(ok),
            "ttft_p50": float(np.median(ttft)),
            "ttft_p85": float(np.percentile(ttft, 85)),
            "ttft_p95": float(np.percentile(ttft, 95)),
            "ttft_p50_second_half": float(np.median(half)) if half else None,
            "tpot_p50": float(np.median(tpot)),
            "tpot_p95": float(np.percentile(tpot, 95)),
            "waiting_first_half": float(first[:, 1].mean()),
            "waiting_second_half": float(second[:, 1].mean()),
            "waiting_at_end": int(second[-1, 1]),
            "sustained": bool(second[:, 1].mean() < SUSTAINED_WAIT),
            "running_mean": float(s[(s[:, 0] >= lo) & (s[:, 0] < hi), 2].mean()),
        }), flush=True)
    print(json.dumps({"last_finish": max(r.finish_s for r in recs if r.ok),
                      "preemptions": engine.metrics().get("preemptions")}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
