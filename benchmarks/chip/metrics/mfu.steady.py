"""Model FLOPs of the positions processed in the traced sub-window (matrix
products of every token, attention at each token's context) over the device's
busy time in it at the chip's bf16 peak, in %. Step-program layer."""
from benchmarks.chip import peaks


def read(run):
    t = run.trace
    if not t or not run.traced or t["busy_s"] <= 0:
        return None
    flops = peaks.model_flops(run.model, [(a, b) for _, a, b in run.traced])
    return 100.0 * flops / (t["busy_s"] * peaks.peaks(run.device_kind)["flops_bf16"])
