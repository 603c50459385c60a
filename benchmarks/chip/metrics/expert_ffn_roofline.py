"""The routed experts' grouped products in the decode steps of the traced
sub-window, as a share of their roofline, in %: the least time the chip
needs, max(held pairs x 2 * 3 * d * f / peak FLOP/s, bytes of the held
experts' weights read / peak HBM), over the device time of the decode
steps' grouped-matmul calls (``%gmm`` with fewer rows than the smallest
prefill chunk routes: the ``moe.experts`` products of a decode step).

A decode step reads each held expert with a pair once in each layer,
whatever its batch, so the bytes go with the steps: the sub-window's decode
steps are the decode positions of its longest-decoding request (every
decoding row takes one token a step), times the window's mean of held
experts read per decode step and layer (``moe_decode_experts_read`` /
(``decode_steps`` x layers)). The pairs go with the tokens: the
sub-window's decode positions x top_k x layers x the held share
(``moe_pairs_held`` / ``moe_pairs_routed``). Kernels layer."""
import re

from benchmarks.chip import peaks

# megablox's grouped matmul as the program names it, and its rows
KERNEL = re.compile(r"%gmm[\w.]* = f32\[(\d+),")


def decode_seconds(run) -> float:
    """Device seconds of the grouped-matmul calls of decode steps: their
    rows (the step's tokens x top_k, padded) stay below those of the
    smallest prefill chunk, one page of tokens."""
    chunk_rows = run.engine["page_size"] * run.model["top_k"]
    secs = 0.0
    for k, v in run.trace["ops"].items():
        rows = KERNEL.match(k)
        if rows and "tpu_custom_call" in k and int(rows.group(1)) < chunk_rows:
            secs += v
    return secs


def read(run):
    t, m, em = run.trace, run.model, run.engine_metrics
    steps, routed = em.get("decode_steps", 0), em.get("moe_pairs_routed", 0)
    experts = em.get("moe_decode_experts_read", 0)
    if not t or not run.traced or not steps or not routed or not experts:
        return None
    decode = [b - max(a, plen) for plen, a, b in run.traced if b > max(a, plen)]
    secs = decode_seconds(run)
    if secs <= 0 or not decode:
        return None
    layers, expert = m["n_layers"], 3 * m["d_model"] * m["d_ff"]
    held_share = em["moe_pairs_held"] / routed
    ops = 2.0 * expert * sum(decode) * m["top_k"] * layers * held_share
    byt = 2.0 * expert * max(decode) * experts / steps  # bf16 weights
    return peaks.roofline_share(ops, byt, secs, run.device_kind)[0]
