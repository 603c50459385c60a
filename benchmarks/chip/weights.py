"""Random weights, drawn from the seed on the device.

The tree of leaves comes from the configuration's architecture module
(``arch.of(cfg).shapes(m)``). One jitted program draws every leaf in the
dtype it is served in, so set-up pays one compile (cached across runs) and no
per-leaf dispatch. The tree has the layout the serving engine consumes
(``Model.param_specs`` of the program); ``tests/test_weights.py`` pins that
the shapes agree for every committed configuration. The architecture's plain
reference reads the same arrays.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

VOCAB_PAD = 256  # the program pads the vocabulary rows to this multiple


def padded_vocab(vocab: int) -> int:
    return -(-vocab // VOCAB_PAD) * VOCAB_PAD


def weight_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed (seeds may exceed 32 bits)."""
    return jax.random.key(int(np.random.default_rng(seed).integers(0, 2**31 - 1)))


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)


def _draw(key, leaf):
    shape, dtype, std = leaf
    z = jax.random.normal(key, shape, jnp.float32)
    if std == "norm":
        z = 1.0 + 0.1 * z
    elif std == "bias":
        z = 0.1 * z
    else:
        z = z * std
    return z.astype(dtype)


def make_init(tree: Dict):
    """A jitted ``key -> params`` drawing the leaves of ``tree`` (one
    program)."""
    leaves, treedef = jax.tree.flatten(tree, is_leaf=_is_leaf)

    @jax.jit
    def init(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(
            treedef, [_draw(k, leaf) for k, leaf in zip(keys, leaves)])

    return init


def param_bytes(tree: Dict) -> int:
    leaves = jax.tree.leaves(tree, is_leaf=_is_leaf)
    return sum(math.prod(s) * jnp.dtype(dt).itemsize for s, dt, _ in leaves)


def param_count(m: Dict, tree: Dict, *, padded: bool = False) -> int:
    """Parameters of ``tree``, the weights of model keys ``m``; the
    vocabulary's pad rows only if asked."""
    n = sum(math.prod(s) for s, _, _ in jax.tree.leaves(tree, is_leaf=_is_leaf))
    if not padded:
        pad = padded_vocab(m["vocab"]) - m["vocab"]
        n -= pad * m["d_model"] * (1 if m["tie_embeddings"] else 2)
    return n
