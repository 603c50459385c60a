"""Time the engine's decode and chunk steps at a configuration's widths for a
few page sizes, and write a short profiler trace: the look at the chip that
sizes the cells. Not part of a benchmark run.

    python benchmarks/chip/probe.py --config qwen2.5-3b --page-sizes 16,128
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.chip import arch, weights  # noqa: E402
from benchmarks.chip import engine_cell as ec  # noqa: E402
from benchmarks.chip.compile_cache import enable  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="qwen2.5-3b")
    ap.add_argument("--page-sizes", default="16,128")
    ap.add_argument("--prompt", type=int, default=256)
    ap.add_argument("--new", type=int, default=48)
    ap.add_argument("--ctx", type=int, default=5120, help="longest context")
    ap.add_argument("--pool-gb", type=float, default=7.5)
    ap.add_argument("--trace-dir", default="benchmarks/chip/out/probe_trace")
    ap.add_argument("--tiny", action="store_true", help="CPU rehearsal size")
    a = ap.parse_args(argv)
    enable()
    cfg = ec.load_config(a.config)
    if a.tiny:
        cfg["model"].update(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                            d_head=16, d_ff=128, vocab=512)
        cfg["engine"]["max_batch"] = 4
    m = cfg["model"]
    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind}", flush=True)
    t0 = time.perf_counter()
    params = weights.make_init(arch.of(cfg).shapes(m))(weights.weight_key(0))
    jax.block_until_ready(params)
    print(f"weights {time.perf_counter() - t0:.1f} s", flush=True)
    model = ec.build_model(cfg)
    b = cfg["engine"]["max_batch"]
    rng = np.random.default_rng(0)
    out = []
    for ps in [int(x) for x in a.page_sizes.split(",")]:
        kv_page = ec.pool_bytes(model, 1, ps)
        cfg["engine"].update(
            page_size=ps, max_pages_per_seq=-(-a.ctx // ps) + 1,
            num_pages=int(a.pool_gb * 1e9 / kv_page) if not a.tiny else 512)
        t1 = time.perf_counter()
        eng = ec.build_engine(model, params, ec.engine_config(cfg))

        def job(lo):
            items = [(rng.integers(lo, lo + 1000, a.prompt), a.new, 0.0)
                     for _ in range(b)]
            return ec.make_requests(items)

        eng.run(job(1000))
        warm = time.perf_counter() - t1
        eng.reset_metrics()
        t2 = time.perf_counter()
        eng.run(job(3000))
        wall = time.perf_counter() - t2
        mt = eng.metrics()
        row = dict(page_size=ps, max_pages=cfg["engine"]["max_pages_per_seq"],
                   num_pages=cfg["engine"]["num_pages"], warm_s=warm, wall_s=wall,
                   **{k: mt[k] for k in ("step_ms_p50", "step_ms_p95",
                                         "host_overhead_ms_p50", "decode_steps",
                                         "tokens_per_s", "prefill_tokens_computed")})
        if ps == int(a.page_sizes.split(",")[0]):
            eng.reset_metrics()
            jax.profiler.start_trace(a.trace_dir)
            eng.run(ec.make_requests(
                [(rng.integers(5000, 6000, 2 * ps), 3, 0.0) for _ in range(2)]))
            jax.profiler.stop_trace()
        print(json.dumps(row), flush=True)
        out.append(row)
        del eng
    stats = dev.memory_stats() or {}
    print(json.dumps({"peak_bytes": stats.get("peak_bytes_in_use"),
                      "limit": stats.get("bytes_limit")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
