"""Place a mix's replayed schedule among random orders, on the CPU.

    python benchmarks/chip/schedule.py --traffic chat --seconds 51 --candidates 0 1 2

A queue model of the engine's chunked-prefill loop (one mixed step at a time:
prefill chunks within the step's token quota, shortest remaining first, then
one decode step for every decoding slot) serves each candidate
``schedule_seed`` and ``--orders`` random orders of the same requests, and
prints where each candidate's TTFT p85 lies among the random orders. A
replayed schedule stands for its mix only where it lies near their median.
Step times are fitted to the v5e sweep of the chat cell (``PERF.md``); the
model ranks orders, it does not predict a chip's numbers.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks.chip import traffic  # noqa: E402

DECODE_MS = (95.0, 0.5)  # a decode step: fixed + per decoding slot
CHUNK_MS = (12.0, 0.06)  # a prefill chunk: fixed + per token


def ttft(items, *, max_batch=64, page=256, chunk=512) -> np.ndarray:
    """Seconds from due to first token for each item, under the model."""
    n = len(items)
    plen = [len(it.prompt) for it in items]
    due = [it.arrival_s for it in items]
    first = np.full(n, np.inf)
    t, nxt, queue, run, done = 0.0, 0, [], {}, 0
    while done < n:
        while nxt < n and due[nxt] <= t:
            queue.append(nxt)
            nxt += 1
        while queue and len(run) < max_batch:
            r = queue.pop(0)
            run[r] = [0, 0, -(-plen[r] // page) * page]  # cursor, tokens, padded
        decoding = sum(s[0] is None for s in run.values())
        budget = max_batch + chunk - decoding
        for r in sorted((r for r, s in run.items() if s[0] is not None),
                        key=lambda r: (run[r][2] - run[r][0], r)):
            if budget < page:
                break
            s = run[r]
            c = min(chunk, s[2] - s[0], budget // page * page)
            budget -= c
            t += (CHUNK_MS[0] + CHUNK_MS[1] * c) / 1e3
            s[0] += c
            if s[0] >= plen[r]:
                s[0], s[1], first[r] = None, 1, t
        dec = [r for r, s in run.items() if s[0] is None]
        if dec:
            t += (DECODE_MS[0] + DECODE_MS[1] * len(dec)) / 1e3
        for r in dec:
            run[r][1] += 1
        for r in [r for r in dec if run[r][1] >= items[r].new_tokens]:
            del run[r]
            done += 1
        if not run and not queue and nxt < n:
            t = max(t, due[nxt])
    return first - np.array(due)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--candidates", type=int, nargs="+", required=True)
    ap.add_argument("--orders", type=int, default=200)
    a = ap.parse_args(argv)
    mix = {k: v for k, v in traffic.load_mix(a.traffic).items()
           if k != "schedule_seed"}
    ids = (1, 2)

    def p85(m, seed):
        return float(np.percentile(ttft(traffic.generate(m, seed, a.seconds, ids)), 85))

    rand = np.array([p85(mix, 1 << 20 | k) for k in range(a.orders)])
    print(f"random orders: TTFT p85 median {np.median(rand):.3f} s, "
          f"quartiles {np.percentile(rand, 25):.3f} / {np.percentile(rand, 75):.3f}")
    for c in a.candidates:
        v = p85(dict(mix, schedule_seed=c), 0)
        print(f"schedule_seed {c}: TTFT p85 {v:.3f} s, above "
              f"{100 * np.mean(rand < v):.0f} % of random orders")
    return 0


if __name__ == "__main__":
    sys.exit(main())
