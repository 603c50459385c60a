"""The windowed paged-decode kernel's share of its roofline in the traced
sub-window, in %: the least time the chip needs for the decode attention of
the window layers there (``peaks.decode_attn`` over the layers whose
``span`` is set, one row of n + 1 keys for each decode position n the
sub-window processed) over the device time of the kernel calls tagged with a
window. Kernels layer."""
from benchmarks.chip import peaks

TAG = '"window"'  # the windowed kernels' kernel_metadata key


def kernel_seconds(run) -> float:
    """Device seconds of the windowed decode kernel: Mosaic calls that carry
    the window tag, with the decode output (max_batch, Hkv, Hq / Hkv, head)."""
    layers = [ly for ly in run.layers if ly.span is not None]
    outs = {f" = bf16[{run.engine['max_batch']},{ly.n_kv_heads},"
            f"{ly.n_heads // ly.n_kv_heads},{ly.d_head}]" for ly in layers}
    return sum(v for k, v in run.trace["ops"].items()
               if "tpu_custom_call" in k and TAG in k
               and any(o in k.split(" custom-call(")[0] for o in outs))


def read(run):
    windowed = [ly for ly in run.layers if ly.span is not None]
    if not run.trace or not run.traced or not windowed:
        return None
    secs = kernel_seconds(run)
    ctx = [p + 1 for plen, a, b in run.traced for p in range(max(a, plen), b)]
    if secs <= 0 or not ctx:
        return None
    ops, byt = peaks.decode_attn(windowed, ctx, run.engine["page_size"], kv_bytes=2)
    return peaks.roofline_share(ops, byt, secs, run.device_kind)[0]
