"""The one traffic generator: a mix file of parameters -> requests.

Every seed gets the same work in another order. Lengths are the stratified
quantiles ``F^-1((i + 0.5) / n)`` of the mix's distributions, arrival gaps the
same quantiles of an exponential at the mix's rate (a Poisson process with its
gaps stratified), and shared prefixes are dealt by Zipf weights in fixed
counts; the seed permutes each list and draws the token ids. Runs on different
seeds therefore differ in order and content, never in the amount of work,
which keeps a cell's spread down to what the system does with it.

A mix that names a ``schedule_seed`` replays one schedule: that seed, not the
run's, orders the lengths, the gaps and the prefixes, so every run serves the
same requests at the same times and the run's seed draws only the token ids.
A queue's tail turns on which lengths arrive close together, so where the
order moves a tail by more than the system's own noise, the mix fixes it.

Copied in spirit from the program's ``benchmarks/serving_suite.py``
(``_poisson_arrivals``, lognormal length buckets), kept here so that no change
to the program moves the yardstick.
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist
from typing import Dict, List, Optional, Tuple

import numpy as np

HERE = Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Item:
    prompt: Tuple[int, ...]
    new_tokens: int
    arrival_s: float
    prefix: Optional[int]  # shared-prefix index, None when unshared


def load_mix(name: str) -> Dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: Dict, n: int) -> np.ndarray:
    """The n stratified quantiles of a length distribution, clipped."""
    q = _quantiles(n)
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(u)) for u in q])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        x = spec["min"] + q * (spec["max"] - spec["min"])
    elif spec["dist"] == "fixed":
        x = np.full(n, spec["value"], float)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    lo, hi = spec.get("min", 1), spec.get("max", 1 << 30)
    return np.clip(np.rint(x), lo, hi).astype(int)


def n_requests(mix: Dict, seconds: float) -> int:
    if mix["loop"] == "open":
        return max(1, round(mix["rate_per_s"] * seconds))
    if mix["loop"] == "offline":
        return max(1, math.ceil(mix["requests_per_s"] * seconds))
    raise ValueError(f"unknown loop {mix['loop']!r}")


def arrivals(mix: Dict, n: int, rng: np.random.Generator) -> np.ndarray:
    if mix["loop"] == "offline":
        return np.zeros(n)
    gaps = -np.log1p(-_quantiles(n)) / mix["rate_per_s"]
    t = np.cumsum(rng.permutation(gaps))
    return t - t[0]


def prefix_counts(share: Dict, n: int) -> np.ndarray:
    """How many of n requests take each prefix: Zipf(s) over ``count``
    prefixes, dealt by stratified quantiles of its CDF."""
    w = 1.0 / np.arange(1, share["count"] + 1) ** share["zipf_s"]
    cdf = np.cumsum(w / w.sum())
    idx = np.searchsorted(cdf, _quantiles(n))
    return np.bincount(np.minimum(idx, share["count"] - 1),
                       minlength=share["count"])


def generate(mix: Dict, seed: int, seconds: float, id_range: Tuple[int, int]
             ) -> List[Item]:
    """The requests of one run, in arrival order. Token ids are drawn from
    ``id_range`` (a rehearsal takes a range disjoint from the window's)."""
    rng = np.random.default_rng(seed)
    order = (np.random.default_rng(mix["schedule_seed"])
             if "schedule_seed" in mix else rng)
    n = n_requests(mix, seconds)
    lo, hi = id_range
    prompt_lens = order.permutation(lengths(mix["prompt_len"], n))
    out_lens = order.permutation(lengths(mix["output_len"], n))
    share = mix.get("shared_prefix")
    if share:
        prefixes = [tuple(rng.integers(lo, hi, share["len"]).tolist())
                    for _ in range(share["count"])]
        which = order.permutation(np.repeat(np.arange(share["count"]),
                                            prefix_counts(share, n)))
    t = arrivals(mix, n, order)
    items = []
    for i in range(n):
        body = tuple(rng.integers(lo, hi, int(prompt_lens[i])).tolist())
        pre = int(which[i]) if share else None
        prompt = (prefixes[pre] + body) if share else body
        items.append(Item(prompt, int(out_lens[i]), float(t[i]), pre))
    return items


def rehearsal(mix: Dict, seed: int, id_range: Tuple[int, int], *,
              n: int, cuts: List[int], new_tokens: int) -> List[Item]:
    """A short trace of the mix's own kinds of request, all due at once, to
    warm the programs the window will run: prompts cut to the lengths in
    ``cuts`` in turn (one per prefill-chunk bucket; shared prefixes stay
    shared), ``new_tokens`` each."""
    base = generate(mix, seed, n / _rate(mix), id_range)[:n]
    return [Item(it.prompt[:cuts[i % len(cuts)]], new_tokens, 0.0, it.prefix)
            for i, it in enumerate(base)]


def solo(seed: int, id_range: Tuple[int, int], lens: List[int],
         new_tokens: int) -> List[Item]:
    """One unshared prompt of each length in ``lens``, ids drawn from
    ``id_range``."""
    rng = np.random.default_rng([seed, 1])
    return [Item(tuple(rng.integers(*id_range, n).tolist()), new_tokens, 0.0,
                 None) for n in lens]


def _rate(mix: Dict) -> float:
    return mix["rate_per_s"] if mix["loop"] == "open" else mix["requests_per_s"]
