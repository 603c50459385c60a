"""The prefill-chunk kernel's share of its roofline in the traced sub-window,
in %: the least time the chip needs for the chunk attention done there
(``peaks.chunk_attn`` over the layers, per chunk, chunks cut as the engine
cuts them, from each prompt's cursor in steps of ``chunk_tokens``) over the
kernel's device time. Kernels layer."""
from typing import List

from benchmarks.chip import peaks, trace


def kernel_outs(run) -> List[str]:
    """The kernel's outputs in the trace, up to their bucket-dependent axis:
    (1 chunk, Hkv, (Hq / Hkv) x bucket, head_dim) in bf16, once for each
    KV-head count among the layers."""
    return [f"bf16[1,{h}," for h in sorted({ly.n_kv_heads for ly in run.layers})]


def read(run):
    t = run.trace
    secs = sum(trace.kernel_seconds(t, out) for out in kernel_outs(run)) if t else 0.0
    if secs <= 0 or not run.traced:
        return None
    step = 2 * run.engine["page_size"]  # the engine's default chunk
    ops = byt = 0.0
    for plen, a, b in run.traced:
        c = a
        while c < min(b, plen):
            n = min(step, min(b, plen) - c)
            o, y = peaks.chunk_attn(run.layers, c, n, kv_bytes=2)
            ops, byt, c = ops + o, byt + y, c + n
    return peaks.roofline_share(ops, byt, secs, run.device_kind)[0]
