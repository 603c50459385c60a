"""Share of the routed (token, expert) pairs whose expert is held on this
chip, over the window's decode and chunk steps: ``moe_pairs_held`` /
``moe_pairs_routed``, in %. Near-uniform routing over 16 of 64 experts
reads about 25 %. Step-program layer."""


def read(run):
    m = run.engine_metrics
    routed = m.get("moe_pairs_routed", 0)
    return 100.0 * m["moe_pairs_held"] / routed if routed else None
