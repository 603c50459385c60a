"""The chip's peaks, and the operations and bytes each piece of work needs.

Peaks are keyed by ``device_kind`` as JAX reports it; a kind that is not in
the table is an error, never a default. Work is summed over the layer list
that the configuration's architecture module gives (``arch``), so a window
layer or a routed MLP is counted as it is, whatever implements it.
Operations count a multiply-add as two. Bytes are priced from the KV pool's
real dtype (``kv_bytes``: 2 for the bf16 pages the cells serve), and the
decode kernel reads whole live pages, as its DMAs do.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, Optional, Sequence, Tuple

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


@dataclasses.dataclass(frozen=True)
class Layer:
    """The work one token does in one layer, as an architecture module's
    ``layers(m)`` gives it: the attention's heads, the keys it attends to
    (``span``: ``None`` for every earlier key, ``w`` for a window of the last
    ``w`` keys, the token itself among them), and the weights it multiplies
    outside attention (``ffn_params``: ``dense_ffn`` or ``routed_ffn``)."""
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    span: Optional[int]
    ffn_params: float


def dense_ffn(d: int, f: int) -> int:
    """A gated MLP (gate, up and down projections)."""
    return 3 * d * f


def routed_ffn(d: int, f_expert: int, experts: int, held: int, top_k: int,
               shared_f: int = 0) -> float:
    """Routed experts as this chip serves them: the router over all
    ``experts``, and the expected share of a token's ``top_k`` gated expert
    MLPs that fall on the ``held`` experts here, plus a shared expert of
    width ``shared_f`` if any."""
    return (d * experts + top_k * held / experts * dense_ffn(d, f_expert)
            + dense_ffn(d, shared_f))


def _keys(span: Optional[int], a: int, b: int) -> float:
    """Keys attended by the positions [a, b): position p attends to
    min(p + 1, span)."""
    n = b - a
    if n <= 0:
        return 0.0
    if span is None or b <= span:
        return (a + 1 + b) * n / 2.0  # sum of p + 1 over p in [a, b)
    ramp = max(0, span - a)  # positions still inside the first window
    return (a + 1 + a + ramp) * ramp / 2.0 + (n - ramp) * float(span)


def matmul_params(m: Dict, layers: Sequence[Layer]) -> float:
    """Weights that multiply each token: each layer's projections and the
    weights outside attention, and the output head once (the embedding
    lookup is a gather and counts nothing)."""
    total = 0.0
    for ly in layers:
        qo = 2 * ly.d_model * ly.n_heads * ly.d_head
        kv = 2 * ly.d_model * ly.n_kv_heads * ly.d_head
        total += qo + kv + ly.ffn_params
    return total + m["d_model"] * m["vocab"]


def attn_flops(layers: Sequence[Layer], a: int, b: int) -> float:
    """Scores and weighted sums of the positions [a, b) of one sequence, all
    layers."""
    return sum(4.0 * ly.n_heads * ly.d_head * _keys(ly.span, a, b)
               for ly in layers)


def model_flops(m: Dict, layers: Sequence[Layer],
                positions: Iterable[Tuple[int, int]]) -> float:
    """Model FLOPs of processing the positions [a, b) of each sequence: the
    matrix products of every token, and attention at each token's real
    context. The output head is counted for every token, though the program
    applies it at one row per prefill chunk: this is the model's work, not
    the program's."""
    mm = 2.0 * matmul_params(m, layers)
    total = 0.0
    for a, b in positions:
        if b > a:
            total += mm * (b - a) + attn_flops(layers, a, b)
    return total


def decode_attn(layers: Sequence[Layer], ctx_lens: Iterable[int],
                page_size: int, kv_bytes: int, act_bytes: int = 2
                ) -> Tuple[float, float]:
    """(ops, bytes) of the paged-decode kernel calls of ``layers`` over rows
    with ``ctx_lens`` keys each: QK and PV over the keys each layer attends;
    the whole pages of K and V that overlap them, plus q and the output."""
    ops = byt = 0.0
    for n in ctx_lens:
        if n <= 0:
            continue
        for ly in layers:
            k = n if ly.span is None else min(n, ly.span)
            pages = -(-n // page_size) - (n - k) // page_size
            ops += 4.0 * ly.n_heads * ly.d_head * k
            byt += (2.0 * pages * page_size * ly.n_kv_heads * ly.d_head * kv_bytes
                    + 2.0 * ly.n_heads * ly.d_head * act_bytes)
    return ops, byt


def chunk_attn(layers: Sequence[Layer], cursor: int, n_new: int,
               kv_bytes: int, act_bytes: int = 2) -> Tuple[float, float]:
    """(ops, bytes) of the prefill-chunk kernel calls of ``layers``:
    ``n_new`` causal queries at positions [cursor, cursor + n_new) over the
    keys each attends; K and V of the positions from
    max(0, cursor - span + 1) to cursor + n_new, plus q and the output."""
    ops = byt = 0.0
    for ly in layers:
        first = 0 if ly.span is None else max(0, cursor - ly.span + 1)
        ops += 4.0 * ly.n_heads * ly.d_head * _keys(ly.span, cursor, cursor + n_new)
        byt += (2.0 * (cursor + n_new - first) * ly.n_kv_heads * ly.d_head * kv_bytes
                + 2.0 * n_new * ly.n_heads * ly.d_head * act_bytes)
    return ops, byt


def roofline_share(ops: float, byt: float, seconds: float, kind: str
                   ) -> Tuple[float, str]:
    """(% of the least time the chip needs, the bound that sets it)."""
    p = peaks(kind)
    t_c, t_m = ops / p["flops_bf16"], byt / p["hbm_bytes_per_s"]
    bound = "compute" if t_c >= t_m else "memory"
    return 100.0 * max(t_c, t_m) / seconds, bound
