"""Readings that set the limit of ``logit_gap``: the program's on many seeds
and the fp8 control's on the same samples, all in one process on the chip.

    python benchmarks/chip/readings.py --workload <cell> --seeds 11,12,13 \\
        --control 3 --seconds 20

Each seed is a whole run of the cell at its own load (weights, engine,
rehearsal, window) followed by ``check``'s sample; the first ``--control``
seeds also read the control there. One JSON line per seed.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks.chip import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=20)
    a = ap.parse_args(argv)
    import jax
    import numpy as np

    from benchmarks.chip import check, compile_cache, traffic
    from benchmarks.chip import engine_cell as ec

    if jax.devices()[0].platform != "tpu":
        print("readings.py: needs a TPU", file=sys.stderr)
        return 3
    compile_cache.enable()
    cell = run.find_cell(run.load_benchmark(), a.workload)
    cfg = ec.load_config(cell["config"])
    mix = traffic.load_mix(cell["traffic"])
    for k, seed in enumerate(int(s) for s in a.seeds.split(",")):
        t0 = time.perf_counter()
        params, engine = run.build(cfg, mix, seed)
        items, requests = run.window_items(cfg, mix, seed, a.seconds)
        t1 = time.perf_counter()
        results = engine.run(requests)
        t2 = time.perf_counter()
        records = run.records_of(items, requests, results)
        del engine, results
        gc.collect()
        picked = check.sample(records, cfg, seed)
        prog, ctrl = [], []
        for i in picked:
            if k < a.control:
                p, c = check.control_gaps(params, cfg, items[i].prompt,
                                          records[i].generated)
                ctrl.append(c)
            else:
                p = check.gaps(params, cfg, items, records, [i])
            prog.append(p)
        prog = np.concatenate(prog)
        row = {"seed": seed, "unserved": sum(not r.ok for r in records),
               "requests": len(records), "checked_tokens": int(prog.size),
               "program_gap": float(prog.max()),
               "program_gap_p99": float(np.percentile(prog, 99)),
               "build_s": round(t1 - t0, 2), "window_s": round(t2 - t1, 2),
               "reference_s": round(time.perf_counter() - t2, 2)}
        if ctrl:
            ctrl = np.concatenate(ctrl)
            row.update(control_gap=float(ctrl.max()),
                       control_gap_median=float(np.median(ctrl)),
                       control_flips=int((ctrl > 0).sum()))
        print(json.dumps(row), flush=True)
        del params
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
