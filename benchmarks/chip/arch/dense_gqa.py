"""Dense GQA decoders (Qwen2 / Llama / Granite): every layer attends to every
earlier key and multiplies a gated MLP.

The weight tree has the layout the serving engine consumes (stacked layers
under ``blocks[0]``). Scales keep a 36-layer random model well-conditioned:
projections are normal with variance 1/fan-in, norm scales 1 + 0.1 N(0, 1),
biases 0.1 N(0, 1), and the embedding and output head 3/sqrt(d_model), so the
logits have a standard deviation near 3, as a trained model's do. The
reference is ``reference.py``.
"""
from __future__ import annotations

import math
from typing import Dict, List

from benchmarks.chip import peaks
from benchmarks.chip.reference import served_rows  # noqa: F401
from benchmarks.chip.weights import padded_vocab

LOGIT_STD = 3.0


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


def layers(m: Dict) -> List[peaks.Layer]:
    """``n_layers`` equal layers of full causal attention and a gated MLP."""
    layer = peaks.Layer(d_model=m["d_model"], n_heads=m["n_heads"],
                        n_kv_heads=m["n_kv_heads"], d_head=m["d_head"],
                        span=None, ffn_params=peaks.dense_ffn(m["d_model"], m["d_ff"]))
    return [layer] * m["n_layers"]
