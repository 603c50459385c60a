"""85th percentile over the window's served requests of first admission -
arrival: the part of the time to first token spent queued for a batch slot
and pages, from the engine's admission stamps (its ``queue_wait_s_p85``).
Engine / scheduler layer."""


def read(run):
    return run.engine_metrics.get("queue_wait_s_p85")
