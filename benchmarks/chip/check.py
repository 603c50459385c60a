"""How ``correct`` is decided.

Two numbers, each beside its limit:

- ``unserved``: window requests that failed, never finished, or came back
  with another number of tokens than asked (greedy, no end token): limit 0.
- ``logit_gap``: over a sample of the served requests, drawn from the seed
  and always holding the one with the most tokens, the widest gap by which a
  served token's logit lies below the best logit of the float32 reference at
  the same position (teacher-forced over the prompt and the served tokens).
  Greedy serving in bf16 picks the reference's best token or one within
  rounding of it; the limit sits between what sound runs read and what the
  fp8 control reads (``PERF.md``, section 2).
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from benchmarks.chip import reference

SAMPLE_SALT = 0x5A3F1E


def sample(records, cfg: Dict, seed: int) -> List[int]:
    """Indices of the served requests to re-read: the one with the most
    tokens, then others in a seeded order until ``served_tokens`` tokens or
    ``max_requests`` requests."""
    c = cfg["check"]
    ok = [i for i, r in enumerate(records) if r.ok]
    if not ok:
        return []
    longest = max(ok, key=lambda i: (len(records[i].generated), -i))
    rng = np.random.default_rng([seed, SAMPLE_SALT])
    rest = [i for i in rng.permutation(ok).tolist() if i != longest]
    picked, n = [longest], len(records[longest].generated)
    for i in rest:
        if n >= c["served_tokens"] or len(picked) >= c["max_requests"]:
            break
        picked.append(i)
        n += len(records[i].generated)
    return picked


def gaps(params, cfg: Dict, items, records, picked) -> np.ndarray:
    """Per sampled served token: the reference's best logit minus its logit
    of the served token."""
    out = []
    for i in picked:
        best, got, _, _ = reference.served_rows(
            params, cfg["model"], cfg["rms_norm_eps"], items[i].prompt,
            records[i].generated)
        out.append(best - got)
    return np.concatenate(out) if out else np.zeros(0)


def control_gaps(params, cfg: Dict, prompt, served):
    """(program gaps, control gaps) at the same positions: the float32
    reference's best logit minus its logit of the served token, and of the
    token the fp8 reference puts first."""
    m, eps = cfg["model"], cfg["rms_norm_eps"]
    _, _, first8, _ = reference.served_rows(params, m, eps, prompt, served,
                                            fp8=True)
    best, got, _, (ctrl,) = reference.served_rows(params, m, eps, prompt,
                                                  served, lookups=[first8])
    return best - got, best - ctrl


def judge(params, cfg: Dict, items, records, seed: int) -> Dict:
    limit = cfg["limits"]["logit_gap"]
    unserved = sum(not r.ok for r in records)
    picked = sample(records, cfg, seed)
    g = gaps(params, cfg, items, records, picked)
    widest = float(g.max()) if g.size else float("inf")
    checks = {
        "unserved": {"value": unserved, "limit": 0},
        "logit_gap": {"value": widest, "limit": limit},
    }
    lines = [
        f"checked {len(picked)} requests, {g.size} served tokens against the "
        f"float32 reference",
        f"unserved {unserved} <= limit 0",
        f"logit_gap {widest:.6g} <= limit {limit}",
    ]
    correct = unserved == 0 and g.size > 0 and widest <= limit
    return {"correct": bool(correct), "checks": checks, "lines": lines}
