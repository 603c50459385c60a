"""98th percentile of the gap between two tokens of a decoding request: the
wall between the ids fetches of consecutive decoding ticks, over the steps
the later fetch brings (the engine's ``decode_gap_ms_p98``, from its
``decode_gap_s`` histogram). A chunk dispatched in between lands in the gap.
Engine / scheduler layer."""


def read(run):
    return run.engine_metrics.get("decode_gap_ms_p98")
