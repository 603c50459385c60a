"""85th percentile over the window's served requests of first token - first
admission: the part of the time to first token spent in prefill, its chunks
spread over the serving loop's ticks, from the engine's stamps (its
``prefill_s_p85``). Step-program layer."""


def read(run):
    return run.engine_metrics.get("prefill_s_p85")
