"""Random weights for a dense GQA decoder, drawn from the seed on the device.

One jitted program draws every leaf in the dtype it is served in, so set-up
pays one compile (cached across runs) and no per-leaf dispatch. The pytree has
the layout the serving engine consumes (``Model.param_specs`` of the program:
stacked layers under ``blocks[0]``); ``tests/test_weights.py`` pins that the
shapes agree. The plain reference (``reference.py``) reads the same arrays.

Scales keep a 36-layer random model well-conditioned: projections are normal
with variance 1/fan-in, norm scales 1 + 0.1 N(0, 1), biases 0.1 N(0, 1), and
the embedding and output head 3/sqrt(d_model), so the logits have a standard
deviation near 3, as a trained model's do.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

VOCAB_PAD = 256  # the program pads the vocabulary rows to this multiple
LOGIT_STD = 3.0


def padded_vocab(vocab: int) -> int:
    return -(-vocab // VOCAB_PAD) * VOCAB_PAD


def weight_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed (seeds may exceed 32 bits)."""
    return jax.random.key(int(np.random.default_rng(seed).integers(0, 2**31 - 1)))


def shapes(m: Dict) -> Dict:
    """Leaf name -> (shape, dtype, init std or 'norm'/'bias'), nested like
    the program's parameter tree."""
    d, f, L = m["d_model"], m["d_ff"], m["n_layers"]
    h, hkv, dh = m["n_heads"], m["n_kv_heads"], m["d_head"]
    vp = padded_vocab(m["vocab"])
    wdt = m["dtype"]
    head_std = LOGIT_STD / math.sqrt(d)
    embed = {"embedding": ((vp, d), wdt, head_std)}
    if not m["tie_embeddings"]:
        embed["lm_head"] = ((d, vp), wdt, head_std)
    attn = {
        "wq": ((L, d, h, dh), wdt, 1 / math.sqrt(d)),
        "wk": ((L, d, hkv, dh), wdt, 1 / math.sqrt(d)),
        "wv": ((L, d, hkv, dh), wdt, 1 / math.sqrt(d)),
        "wo": ((L, h, dh, d), wdt, 1 / math.sqrt(h * dh)),
    }
    if m["qkv_bias"]:
        attn.update(bq=((L, h, dh), "float32", "bias"),
                    bk=((L, hkv, dh), "float32", "bias"),
                    bv=((L, hkv, dh), "float32", "bias"))
    block = {
        "ln_attn": ((L, d), "float32", "norm"),
        "attn": attn,
        "ln_mlp": ((L, d), "float32", "norm"),
        "mlp": {
            "w_gate": ((L, d, f), wdt, 1 / math.sqrt(d)),
            "w_up": ((L, d, f), wdt, 1 / math.sqrt(d)),
            "w_down": ((L, f, d), wdt, 1 / math.sqrt(f)),
        },
    }
    return {"embed": embed, "blocks": [block],
            "final_norm": ((d,), "float32", "norm")}


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


def make_init(m: Dict):
    """A jitted ``key -> params`` for model keys ``m`` (one program)."""
    tree = shapes(m)
    leaves, treedef = jax.tree.flatten(tree, is_leaf=_is_leaf)

    @jax.jit
    def init(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(
            treedef, [_draw(k, leaf) for k, leaf in zip(keys, leaves)])

    return init


def param_bytes(m: Dict) -> int:
    leaves = jax.tree.leaves(shapes(m), is_leaf=_is_leaf)
    return sum(math.prod(s) * jnp.dtype(dt).itemsize for s, dt, _ in leaves)


def param_count(m: Dict, *, padded: bool = False) -> int:
    """Parameters of the model; the vocabulary's pad rows only if asked."""
    tree = shapes(m)
    n = sum(math.prod(s) for s, _, _ in jax.tree.leaves(tree, is_leaf=_is_leaf))
    if not padded:
        pad = padded_vocab(m["vocab"]) - m["vocab"]
        n -= pad * m["d_model"] * (1 if m["tie_embeddings"] else 2)
    return n
