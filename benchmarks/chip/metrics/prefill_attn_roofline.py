"""The prefill-chunk kernel's share of its roofline in the traced sub-window,
in %: the least time the chip needs for the chunk attention done there
(``peaks.chunk_attn`` per chunk and layer, chunks cut as the engine cuts
them, from each prompt's cursor in steps of ``chunk_tokens``) over the
kernel's device time. Kernels layer."""
from benchmarks.chip import peaks, trace


def kernel_out(run) -> str:
    """The kernel's output in the trace, up to its bucket-dependent axis:
    (1 chunk, Hkv, (Hq / Hkv) x bucket, head_dim) in bf16."""
    return f"bf16[1,{run.model['n_kv_heads']},"


def read(run):
    t = run.trace
    secs = trace.kernel_seconds(t, kernel_out(run)) if t else 0.0
    if secs <= 0 or not run.traced:
        return None
    step = 2 * run.engine["page_size"]  # the engine's default chunk
    ops = byt = 0.0
    for plen, a, b in run.traced:
        c = a
        while c < min(b, plen):
            n = min(step, min(b, plen) - c)
            o, y = peaks.chunk_attn(run.model, c, n, kv_bytes=2)
            ops, byt, c = ops + o, byt + y, c + n
    n_layers = run.model["n_layers"]
    return peaks.roofline_share(n_layers * ops, n_layers * byt, secs,
                                run.device_kind)[0]
