"""Seconds from process start to the window's first request: JAX start-up,
weights, engine and KV pool, warm-up (compiles included)."""


def read(run):
    return run.setup_s
