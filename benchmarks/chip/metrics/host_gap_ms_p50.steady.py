"""Median host time per decode token that is not the device step: the engine's
``host_overhead_ms_p50`` (scheduler tick, slot sync, bookkeeping) over the
window. Engine / scheduler layer."""


def read(run):
    return run.engine_metrics.get("host_overhead_ms_p50")
