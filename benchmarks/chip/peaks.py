"""The chip's peaks, and the operations and bytes each piece of work needs.

Peaks are keyed by ``device_kind`` as JAX reports it; a kind that is not in
the table is an error, never a default. Operations count a multiply-add as
two. Bytes are priced from the KV pool's real dtype (``kv_bytes``: 2 for the
bf16 pages the cells serve), and the decode kernel reads whole live pages, as
its DMAs do.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

PEAKS: Dict[str, Dict] = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
                  "16 GB HBM2 at 819 GB/s per chip",
    },
}


def peaks(device_kind: str) -> Dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)}") from None


def matmul_params(m: Dict) -> int:
    """Weights that multiply each token: the layers' projections and MLP and
    the output head (the embedding lookup is a gather and counts nothing)."""
    d, f, h, hkv, dh = (m["d_model"], m["d_ff"], m["n_heads"],
                        m["n_kv_heads"], m["d_head"])
    per_layer = d * h * dh + 2 * d * hkv * dh + h * dh * d + 3 * d * f
    return m["n_layers"] * per_layer + d * m["vocab"]


def attn_flops(m: Dict, n_keys: int) -> float:
    """Scores and weighted sum of one query over ``n_keys`` keys, all layers."""
    return 4.0 * m["n_layers"] * m["n_heads"] * m["d_head"] * n_keys


def model_flops(m: Dict, positions: Iterable[Tuple[int, int]]) -> float:
    """Model FLOPs of processing the positions [a, b) of each sequence: the
    matrix products of every token, and attention at each token's real
    context (position p attends to p + 1 keys). The output head is counted
    for every token, though the program applies it at one row per prefill
    chunk: this is the model's work, not the program's."""
    mm = 2.0 * matmul_params(m)
    total = 0.0
    for a, b in positions:
        n = b - a
        if n <= 0:
            continue
        keys = (a + 1 + b) * n / 2.0  # sum of p + 1 over p in [a, b)
        total += mm * n + 4.0 * m["n_layers"] * m["n_heads"] * m["d_head"] * keys
    return total


def decode_attn(m: Dict, ctx_lens: Iterable[int], page_size: int,
                kv_bytes: int, act_bytes: int = 2) -> Tuple[float, float]:
    """(ops, bytes) of one paged-decode kernel call (one layer) over rows
    with ``ctx_lens`` keys each: QK and PV over the live keys; whole live
    pages of K and V, plus q and the output."""
    h, hkv, dh = m["n_heads"], m["n_kv_heads"], m["d_head"]
    ops = byt = 0.0
    for n in ctx_lens:
        if n <= 0:
            continue
        pages = -(-n // page_size)
        ops += 4.0 * h * dh * n
        byt += 2.0 * pages * page_size * hkv * dh * kv_bytes + 2.0 * h * dh * act_bytes
    return ops, byt


def chunk_attn(m: Dict, cursor: int, n_new: int, kv_bytes: int,
               act_bytes: int = 2) -> Tuple[float, float]:
    """(ops, bytes) of one prefill-chunk kernel call (one layer): ``n_new``
    causal queries at positions [cursor, cursor + n_new) over the resident
    keys and their own; K and V of cursor + n_new positions, plus q and the
    output."""
    h, hkv, dh = m["n_heads"], m["n_kv_heads"], m["d_head"]
    keys = cursor * n_new + n_new * (n_new + 1) / 2.0
    ops = 4.0 * h * dh * keys
    byt = (2.0 * (cursor + n_new) * hkv * dh * kv_bytes
           + 2.0 * n_new * h * dh * act_bytes)
    return ops, byt


def roofline_share(ops: float, byt: float, seconds: float, kind: str
                   ) -> Tuple[float, str]:
    """(% of the least time the chip needs, the bound that sets it)."""
    p = peaks(kind)
    t_c, t_m = ops / p["flops_bf16"], byt / p["hbm_bytes_per_s"]
    bound = "compute" if t_c >= t_m else "memory"
    return 100.0 * max(t_c, t_m) / seconds, bound
