"""Generic transformer stack executor covering all 10 assigned architectures.

An architecture is a PROGRAM: a list of (block-kind, count) entries. Homogeneous
runs of blocks are stacked (leading `count` dim on every param/cache leaf) and
executed with jax.lax.scan (+ optional per-layer remat for training) — keeping
compiled HLO size O(1) in depth, which is what makes the 100-layer dry-runs cheap.

Block kinds:
  dense       self-attn (+optional local window) + MLP          (llama/qwen/granite)
  moe         self-attn + mixture-of-experts FFN                (dbrx, kimi-k2)
  ssm         mamba-2 SSD block (no MLP)                        (mamba2-780m)
  rec         RG-LRU temporal block + MLP                       (recurrentgemma)
  local_attn  windowed self-attn + MLP                          (recurrentgemma)
  rg_group    composite [rec, rec, local_attn]                  (recurrentgemma 1:2)
  enc         non-causal self-attn + MLP (no cache)             (whisper encoder)
  dec         causal self-attn + cross-attn + MLP               (whisper decoder)
  vis_group   composite [4 × dense self] + gated cross-attn     (llama-3.2-vision)

Every kind implements: specs / cache_specs / train / prefill / decode with uniform
signatures so the executor is kind-agnostic. `train` returns (x, aux) where aux is
the MoE load-balance loss (0 elsewhere).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.accessors import QuantizedAccessor
from repro.core.distributed import TensorSpec, tree_initialize

from . import attention as attn
from . import moe as moe_mod
from . import rglru as rg_mod
from . import ssm as ssm_mod
from .layers import (
    NULL_SHARDER,
    Sharder,
    apply_embed,
    apply_lm_head,
    apply_mlp,
    apply_norm,
    cross_entropy,
    embed_specs,
    mlp_specs,
    norm_specs,
)


# Dry-run probes set this to unroll layer scans so XLA cost analysis (which
# counts while-loop bodies ONCE) sees every layer — see launch/dryrun.py.
_SCAN_UNROLL = False


def set_scan_unroll(flag: bool) -> None:
    global _SCAN_UNROLL
    _SCAN_UNROLL = bool(flag)


def stack_scan(body, carry, xs):
    if _SCAN_UNROLL:
        return jax.lax.scan(body, carry, xs, unroll=True)
    return jax.lax.scan(body, carry, xs)


def stack_specs(specs, n: int):
    """Prepend a layer dim (logical axis "layers" → replicated) to every spec."""
    return jax.tree.map(
        lambda s: TensorSpec(
            (n,) + s.shape, ("layers",) + s.logical_axes, dtype=s.dtype,
            init=s.init, accessor=s.accessor,
        ),
        specs,
        is_leaf=lambda x: isinstance(x, TensorSpec),
    )


# =====================================================================================
# block kinds
# =====================================================================================
class DenseBlock:
    def __init__(self, use_window: bool = False, causal: bool = True):
        self.use_window = use_window
        self.causal = causal

    def _window(self, cfg):
        return cfg.window if self.use_window else None

    def specs(self, cfg, quant=None):
        return {
            "ln_attn": norm_specs(cfg),
            "attn": attn.attn_specs(cfg, quant=quant),
            "ln_mlp": norm_specs(cfg),
            "mlp": mlp_specs(cfg, quant=quant),
        }

    def cache_specs(self, cfg, batch: int, seq: int):
        w = self._window(cfg)
        s = min(seq, w) if w is not None else seq
        return attn.cache_specs(cfg, batch, s)

    def train(self, cfg, p, x, shard, ctx=None, pos_offset=0):
        h = apply_norm(cfg, x, p["ln_attn"])
        x = x + attn.self_attention(
            cfg, p["attn"], h, shard=shard, causal=self.causal,
            window=self._window(cfg), pos_offset=pos_offset,
        )
        h = apply_norm(cfg, x, p["ln_mlp"])
        x = x + apply_mlp(cfg, p["mlp"], h, shard)
        return x, jnp.float32(0)

    def prefill(self, cfg, p, x, shard, ctx=None, max_len=None):
        h = apply_norm(cfg, x, p["ln_attn"])
        y, (k, v) = attn.self_attention(
            cfg, p["attn"], h, shard=shard, causal=self.causal,
            window=self._window(cfg), return_kv=True,
        )
        x = x + y
        h = apply_norm(cfg, x, p["ln_mlp"])
        x = x + apply_mlp(cfg, p["mlp"], h, shard)
        cache = attn.pack_kv_cache(cfg, k, v, max_len=max_len, window=self._window(cfg))
        return x, cache

    def decode(self, cfg, p, x, cache, pos, shard, ctx=None):
        h = apply_norm(cfg, x, p["ln_attn"])
        y, cache = attn.self_attention_decode(
            cfg, p["attn"], h, cache, pos, shard=shard, window=self._window(cfg)
        )
        x = x + y
        h = apply_norm(cfg, x, p["ln_mlp"])
        x = x + apply_mlp(cfg, p["mlp"], h, shard)
        return x, cache

    def paged_cache_specs(self, cfg, num_pages: int, page_size: int, kv_spec=None):
        if self._window(cfg) is not None:
            raise NotImplementedError("paged KV caching does not support local windows")
        return attn.paged_cache_specs(cfg, num_pages, page_size, kv_spec=kv_spec)

    def decode_paged(self, cfg, p, x, cache, block_tables, context_lens, shard,
                     impl: str = "auto", kv_spec=None, block_pages=None):
        h = apply_norm(cfg, x, p["ln_attn"])
        y, cache = attn.self_attention_decode_paged(
            cfg, p["attn"], h, cache, block_tables, context_lens, shard=shard,
            impl=impl, kv_spec=kv_spec, block_pages=block_pages,
        )
        x = x + y
        h = apply_norm(cfg, x, p["ln_mlp"])
        x = x + apply_mlp(cfg, p["mlp"], h, shard)
        return x, cache

    def prefill_chunk_paged(self, cfg, p, x, cache, block_tables, write_tables,
                            cursors, n_new, shard, impl: str = "auto", kv_spec=None,
                            null_page=0):
        h = apply_norm(cfg, x, p["ln_attn"])
        y, cache = attn.self_attention_prefill_chunk_paged(
            cfg, p["attn"], h, cache, block_tables, write_tables, cursors, n_new,
            shard=shard, impl=impl, kv_spec=kv_spec, null_page=null_page,
        )
        x = x + y
        h = apply_norm(cfg, x, p["ln_mlp"])
        x = x + apply_mlp(cfg, p["mlp"], h, shard)
        return x, cache

    def verify_paged(self, cfg, p, x, cache, block_tables, context_lens, shard,
                     impl: str = "auto", kv_spec=None):
        h = apply_norm(cfg, x, p["ln_attn"])
        y, cache = attn.self_attention_verify_paged(
            cfg, p["attn"], h, cache, block_tables, context_lens, shard=shard,
            impl=impl, kv_spec=kv_spec,
        )
        x = x + y
        h = apply_norm(cfg, x, p["ln_mlp"])
        x = x + apply_mlp(cfg, p["mlp"], h, shard)
        return x, cache


class MoEBlock(DenseBlock):
    def specs(self, cfg, quant=None):
        return {
            "ln_attn": norm_specs(cfg),
            "attn": attn.attn_specs(cfg, quant=quant),
            "ln_moe": norm_specs(cfg),
            "moe": moe_mod.moe_specs(cfg, quant=quant),
        }

    def train(self, cfg, p, x, shard, ctx=None, pos_offset=0):
        h = apply_norm(cfg, x, p["ln_attn"])
        x = x + attn.self_attention(cfg, p["attn"], h, shard=shard, pos_offset=pos_offset)
        h = apply_norm(cfg, x, p["ln_moe"])
        y, aux = moe_mod.apply_moe_dispatch(cfg, p["moe"], h, shard)
        return x + y, aux

    def prefill(self, cfg, p, x, shard, ctx=None, max_len=None):
        h = apply_norm(cfg, x, p["ln_attn"])
        y, (k, v) = attn.self_attention(cfg, p["attn"], h, shard=shard, return_kv=True)
        x = x + y
        h = apply_norm(cfg, x, p["ln_moe"])
        y, _ = moe_mod.apply_moe_dispatch(cfg, p["moe"], h, shard)
        return x + y, attn.pack_kv_cache(cfg, k, v, max_len=max_len, window=None)

    def decode(self, cfg, p, x, cache, pos, shard, ctx=None):
        h = apply_norm(cfg, x, p["ln_attn"])
        y, cache = attn.self_attention_decode(cfg, p["attn"], h, cache, pos, shard=shard)
        x = x + y
        h = apply_norm(cfg, x, p["ln_moe"])
        y, _ = moe_mod.apply_moe_dispatch(cfg, p["moe"], h, shard)
        return x + y, cache

    def decode_paged(self, cfg, p, x, cache, block_tables, context_lens, shard,
                     impl: str = "auto", kv_spec=None, block_pages=None):
        h = apply_norm(cfg, x, p["ln_attn"])
        y, cache = attn.self_attention_decode_paged(
            cfg, p["attn"], h, cache, block_tables, context_lens, shard=shard,
            impl=impl, kv_spec=kv_spec, block_pages=block_pages,
        )
        x = x + y
        h = apply_norm(cfg, x, p["ln_moe"])
        y, _ = moe_mod.apply_moe_dispatch(cfg, p["moe"], h, shard)
        return x + y, cache

    def prefill_chunk_paged(self, cfg, p, x, cache, block_tables, write_tables,
                            cursors, n_new, shard, impl: str = "auto", kv_spec=None,
                            null_page=0):
        h = apply_norm(cfg, x, p["ln_attn"])
        y, cache = attn.self_attention_prefill_chunk_paged(
            cfg, p["attn"], h, cache, block_tables, write_tables, cursors, n_new,
            shard=shard, impl=impl, kv_spec=kv_spec, null_page=null_page,
        )
        x = x + y
        h = apply_norm(cfg, x, p["ln_moe"])
        y, _ = moe_mod.apply_moe_dispatch(cfg, p["moe"], h, shard)
        return x + y, cache

    def verify_paged(self, cfg, p, x, cache, block_tables, context_lens, shard,
                     impl: str = "auto", kv_spec=None):
        h = apply_norm(cfg, x, p["ln_attn"])
        y, cache = attn.self_attention_verify_paged(
            cfg, p["attn"], h, cache, block_tables, context_lens, shard=shard,
            impl=impl, kv_spec=kv_spec,
        )
        x = x + y
        h = apply_norm(cfg, x, p["ln_moe"])
        y, _ = moe_mod.apply_moe_dispatch(cfg, p["moe"], h, shard)
        return x + y, cache


class SSMBlock:
    def specs(self, cfg, quant=None):
        return {"ln": norm_specs(cfg), "ssm": ssm_mod.ssm_specs(cfg, quant=quant)}

    def cache_specs(self, cfg, batch: int, seq: int):
        return ssm_mod.ssm_cache_specs(cfg, batch)

    def train(self, cfg, p, x, shard, ctx=None, pos_offset=0):
        h = apply_norm(cfg, x, p["ln"])
        return x + ssm_mod.apply_ssm(cfg, p["ssm"], h, shard=shard), jnp.float32(0)

    def prefill(self, cfg, p, x, shard, ctx=None, max_len=None):
        h = apply_norm(cfg, x, p["ln"])
        y, cache = ssm_mod.apply_ssm(cfg, p["ssm"], h, shard=shard, return_state=True)
        return x + y, cache

    def decode(self, cfg, p, x, cache, pos, shard, ctx=None):
        h = apply_norm(cfg, x, p["ln"])
        y, cache = ssm_mod.apply_ssm_decode(cfg, p["ssm"], h, cache, pos, shard=shard)
        return x + y, cache


class RecBlock:
    def specs(self, cfg, quant=None):
        return {
            "ln_rec": norm_specs(cfg),
            "rec": rg_mod.rglru_specs(cfg, quant=quant),
            "ln_mlp": norm_specs(cfg),
            "mlp": mlp_specs(cfg, quant=quant),
        }

    def cache_specs(self, cfg, batch: int, seq: int):
        return rg_mod.rglru_cache_specs(cfg, batch)

    def train(self, cfg, p, x, shard, ctx=None, pos_offset=0):
        h = apply_norm(cfg, x, p["ln_rec"])
        x = x + rg_mod.apply_rglru(cfg, p["rec"], h, shard=shard)
        h = apply_norm(cfg, x, p["ln_mlp"])
        x = x + apply_mlp(cfg, p["mlp"], h, shard)
        return x, jnp.float32(0)

    def prefill(self, cfg, p, x, shard, ctx=None, max_len=None):
        h = apply_norm(cfg, x, p["ln_rec"])
        y, cache = rg_mod.apply_rglru(cfg, p["rec"], h, shard=shard, return_state=True)
        x = x + y
        h = apply_norm(cfg, x, p["ln_mlp"])
        x = x + apply_mlp(cfg, p["mlp"], h, shard)
        return x, cache

    def decode(self, cfg, p, x, cache, pos, shard, ctx=None):
        h = apply_norm(cfg, x, p["ln_rec"])
        y, cache = rg_mod.apply_rglru_decode(cfg, p["rec"], h, cache, pos, shard=shard)
        x = x + y
        h = apply_norm(cfg, x, p["ln_mlp"])
        x = x + apply_mlp(cfg, p["mlp"], h, shard)
        return x, cache


class RGGroup:
    """RecurrentGemma's repeating unit: [rec, rec, local_attn]."""

    def __init__(self):
        self.rec = RecBlock()
        self.attn = DenseBlock(use_window=True)

    def specs(self, cfg, quant=None):
        return {
            "rec0": self.rec.specs(cfg, quant),
            "rec1": self.rec.specs(cfg, quant),
            "attn": self.attn.specs(cfg, quant),
        }

    def cache_specs(self, cfg, batch, seq):
        return {
            "rec0": self.rec.cache_specs(cfg, batch, seq),
            "rec1": self.rec.cache_specs(cfg, batch, seq),
            "attn": self.attn.cache_specs(cfg, batch, seq),
        }

    def train(self, cfg, p, x, shard, ctx=None, pos_offset=0):
        x, _ = self.rec.train(cfg, p["rec0"], x, shard)
        x, _ = self.rec.train(cfg, p["rec1"], x, shard)
        x, _ = self.attn.train(cfg, p["attn"], x, shard, pos_offset=pos_offset)
        return x, jnp.float32(0)

    def prefill(self, cfg, p, x, shard, ctx=None, max_len=None):
        x, c0 = self.rec.prefill(cfg, p["rec0"], x, shard)
        x, c1 = self.rec.prefill(cfg, p["rec1"], x, shard)
        x, ca = self.attn.prefill(cfg, p["attn"], x, shard, max_len=max_len)
        return x, {"rec0": c0, "rec1": c1, "attn": ca}

    def decode(self, cfg, p, x, cache, pos, shard, ctx=None):
        x, c0 = self.rec.decode(cfg, p["rec0"], x, cache["rec0"], pos, shard)
        x, c1 = self.rec.decode(cfg, p["rec1"], x, cache["rec1"], pos, shard)
        x, ca = self.attn.decode(cfg, p["attn"], x, cache["attn"], pos, shard)
        return x, {"rec0": c0, "rec1": c1, "attn": ca}


class DecBlock:
    """Whisper decoder layer: causal self-attn + cross-attn (encoder ctx) + MLP."""

    def specs(self, cfg, quant=None):
        return {
            "ln_self": norm_specs(cfg),
            "self": attn.attn_specs(cfg, quant=quant),
            "ln_cross": norm_specs(cfg),
            "cross": attn.cross_attn_specs(cfg, quant=quant),
            "ln_mlp": norm_specs(cfg),
            "mlp": mlp_specs(cfg, quant=quant),
        }

    def cache_specs(self, cfg, batch: int, seq: int):
        return {
            "self": attn.cache_specs(cfg, batch, seq),
            "cross": attn.cache_specs(cfg, batch, cfg.enc_seq),
        }

    def train(self, cfg, p, x, shard, ctx=None, pos_offset=0):
        h = apply_norm(cfg, x, p["ln_self"])
        x = x + attn.self_attention(cfg, p["self"], h, shard=shard, pos_offset=pos_offset)
        h = apply_norm(cfg, x, p["ln_cross"])
        x = x + attn.cross_attention(cfg, p["cross"], h, ctx, shard=shard)
        h = apply_norm(cfg, x, p["ln_mlp"])
        x = x + apply_mlp(cfg, p["mlp"], h, shard)
        return x, jnp.float32(0)

    def prefill(self, cfg, p, x, shard, ctx=None, max_len=None):
        h = apply_norm(cfg, x, p["ln_self"])
        y, (k, v) = attn.self_attention(cfg, p["self"], h, shard=shard, return_kv=True)
        x = x + y
        h = apply_norm(cfg, x, p["ln_cross"])
        y, (ck, cv) = attn.cross_attention(cfg, p["cross"], h, ctx, shard=shard, return_kv=True)
        x = x + y
        h = apply_norm(cfg, x, p["ln_mlp"])
        x = x + apply_mlp(cfg, p["mlp"], h, shard)
        dt = cfg.param_dtype
        return x, {
            "self": attn.pack_kv_cache(cfg, k, v, max_len=max_len, window=None),
            "cross": {"k": ck.astype(dt), "v": cv.astype(dt)},
        }

    def decode(self, cfg, p, x, cache, pos, shard, ctx=None):
        h = apply_norm(cfg, x, p["ln_self"])
        y, self_cache = attn.self_attention_decode(cfg, p["self"], h, cache["self"], pos, shard=shard)
        x = x + y
        h = apply_norm(cfg, x, p["ln_cross"])
        x = x + attn.cross_attention_decode(
            cfg, p["cross"], h, (cache["cross"]["k"], cache["cross"]["v"])
        )
        h = apply_norm(cfg, x, p["ln_mlp"])
        x = x + apply_mlp(cfg, p["mlp"], h, shard)
        return x, {"self": self_cache, "cross": cache["cross"]}


class VisGroup:
    """llama-3.2-vision unit: 4 dense self-attn layers + 1 gated cross-attn layer."""

    N_SELF = 4

    def __init__(self):
        self.dense = DenseBlock()

    def specs(self, cfg, quant=None):
        return {
            "self": stack_specs(self.dense.specs(cfg, quant), self.N_SELF),
            "ln_cross": norm_specs(cfg),
            "cross": attn.cross_attn_specs(cfg, quant=quant),
            "gate": TensorSpec((), (), dtype=jnp.float32, init="zeros"),
            "ln_mlp": norm_specs(cfg),
            "mlp": mlp_specs(cfg, quant=quant),
        }

    def cache_specs(self, cfg, batch, seq):
        return {
            "self": stack_specs(self.dense.cache_specs(cfg, batch, seq), self.N_SELF),
            "cross": attn.cache_specs(cfg, batch, cfg.n_img_tokens),
        }

    def _cross(self, cfg, p, x, ctx, shard, kv=None):
        h = apply_norm(cfg, x, p["ln_cross"])
        gate = jnp.tanh(p["gate"]).astype(x.dtype)
        if kv is not None:
            y = attn.cross_attention_decode(cfg, p["cross"], h, kv)
            x = x + gate * y
            h = apply_norm(cfg, x, p["ln_mlp"])
            return x + apply_mlp(cfg, p["mlp"], h, shard), None
        y, (ck, cv) = attn.cross_attention(cfg, p["cross"], h, ctx, shard=shard, return_kv=True)
        x = x + gate * y
        h = apply_norm(cfg, x, p["ln_mlp"])
        return x + apply_mlp(cfg, p["mlp"], h, shard), (ck, cv)

    def train(self, cfg, p, x, shard, ctx=None, pos_offset=0):
        def body(xc, pl):
            y, _ = self.dense.train(cfg, pl, xc, shard, pos_offset=pos_offset)
            return y, None

        x, _ = stack_scan(body, x, p["self"])
        x, _ = self._cross(cfg, p, x, ctx, shard)
        return x, jnp.float32(0)

    def prefill(self, cfg, p, x, shard, ctx=None, max_len=None):
        def body(xc, pl):
            return self.dense.prefill(cfg, pl, xc, shard, max_len=max_len)

        x, self_caches = stack_scan(body, x, p["self"])
        x, (ck, cv) = self._cross(cfg, p, x, ctx, shard)
        dt = cfg.param_dtype
        return x, {"self": self_caches, "cross": {"k": ck.astype(dt), "v": cv.astype(dt)}}

    def decode(self, cfg, p, x, cache, pos, shard, ctx=None):
        def body(xc, pc):
            pl, cl = pc
            return self.dense.decode(cfg, pl, xc, cl, pos, shard)

        x, self_caches = stack_scan(body, x, (p["self"], cache["self"]))
        kv = (cache["cross"]["k"], cache["cross"]["v"])
        x, _ = self._cross(cfg, p, x, None, shard, kv=kv)
        return x, {"self": self_caches, "cross": cache["cross"]}


KINDS: Dict[str, Any] = {
    "dense": DenseBlock(),
    "local_attn": DenseBlock(use_window=True),
    "enc": DenseBlock(causal=False),
    "moe": MoEBlock(),
    "ssm": SSMBlock(),
    "rec": RecBlock(),
    "rg_group": RGGroup(),
    "dec": DecBlock(),
    "vis_group": VisGroup(),
}


# =====================================================================================
# model programs
# =====================================================================================
def block_program(cfg) -> List[Tuple[str, int]]:
    if cfg.family in ("dense",):
        return [("dense", cfg.n_layers)]
    if cfg.family == "moe":
        return [("moe", cfg.n_layers)]
    if cfg.family == "ssm":
        return [("ssm", cfg.n_layers)]
    if cfg.family == "hybrid":
        n_groups, rem = divmod(cfg.n_layers, len(cfg.pattern))
        prog: List[Tuple[str, int]] = [("rg_group", n_groups)]
        if rem:
            prog.append(("rec", rem))
        return prog
    if cfg.family == "vlm":
        assert cfg.n_layers % (VisGroup.N_SELF + 1) == 0
        return [("vis_group", cfg.n_layers // (VisGroup.N_SELF + 1))]
    if cfg.family == "encdec":
        return [("dec", cfg.n_layers)]
    raise ValueError(cfg.family)


def _sinusoidal(t: int, d: int) -> jnp.ndarray:
    pos = jnp.arange(t, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, 2 * dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# =====================================================================================
# Model
# =====================================================================================
@dataclasses.dataclass(frozen=True)
class Model:
    cfg: Any
    quant: Optional[QuantizedAccessor] = None  # serving-weight accessor

    # ---- specs -----------------------------------------------------------------
    def param_specs(self):
        cfg = self.cfg
        specs: Dict[str, Any] = {"embed": embed_specs(cfg)}
        specs["blocks"] = [
            stack_specs(KINDS[k].specs(cfg, self.quant), n) for k, n in block_program(cfg)
        ]
        specs["final_norm"] = norm_specs(cfg)
        if cfg.family == "encdec":
            enc_cfg = dataclasses.replace(cfg, mlp_act="gelu")
            specs["encoder"] = {
                "blocks": [stack_specs(KINDS["enc"].specs(enc_cfg, self.quant), cfg.n_enc_layers)],
                "final_norm": norm_specs(cfg),
            }
        return specs

    def cache_specs(self, batch: int, seq: int):
        cfg = self.cfg
        return [
            stack_specs(KINDS[k].cache_specs(cfg, batch, seq), n)
            for k, n in block_program(cfg)
        ]

    def init_params(self, key):
        return tree_initialize(self.param_specs(), key)

    def init_cache(self, batch: int, seq: int):
        return tree_initialize(self.cache_specs(batch, seq), jax.random.key(0))

    # ---- context (stub frontends) ------------------------------------------------
    def encode_ctx(self, params, batch: Dict[str, jax.Array], shard=NULL_SHARDER):
        """Returns the cross-attention context: whisper = encoder(frames stub);
        vlm = the precomputed image embeddings; None otherwise."""
        cfg = self.cfg
        if cfg.family == "encdec":
            frames = batch["frames"]  # (B, enc_seq, D) — precomputed frame embeds
            x = frames + _sinusoidal(frames.shape[1], cfg.d_model).astype(frames.dtype)[None]
            enc_cfg = dataclasses.replace(cfg, mlp_act="gelu")

            def body(xc, pl):
                y, _ = KINDS["enc"].train(enc_cfg, pl, xc, shard)
                return y, None

            x, _ = stack_scan(body, x, params["encoder"]["blocks"][0])
            return apply_norm(cfg, x, params["encoder"]["final_norm"])
        if cfg.family == "vlm":
            return batch["image_embeds"]
        return None

    # ---- full-sequence forward ------------------------------------------------------
    def forward(
        self,
        params,
        tokens: jax.Array,
        *,
        ctx=None,
        shard: Sharder = NULL_SHARDER,
        remat: bool = True,
        remat_policy=None,
    ):
        cfg = self.cfg
        x = apply_embed(params["embed"], tokens)
        if cfg.family == "hybrid":  # gemma convention
            x = x * jnp.asarray(jnp.sqrt(cfg.d_model), x.dtype)
        x = shard(x, "batch", "seq", None)
        aux_total = jnp.float32(0)
        for (kind, n), p in zip(block_program(cfg), params["blocks"]):
            blk = KINDS[kind]

            def body(carry, pl, _blk=blk):
                xc, aux = carry
                y, a = _blk.train(cfg, pl, xc, shard, ctx=ctx)
                return (y, aux + a), None

            if remat:
                body = jax.checkpoint(body, policy=remat_policy)
            (x, aux_total), _ = stack_scan(body, (x, aux_total), p)
        x = apply_norm(cfg, x, params["final_norm"])
        logits = apply_lm_head(cfg, params["embed"], x)
        logits = shard(logits, "batch", "seq", "vocab")
        return logits, aux_total

    def loss_fn(self, params, batch, *, shard=NULL_SHARDER, remat=True, remat_policy=None,
                aux_weight: float = 0.01):
        tokens = batch["tokens"]
        inp, labels = tokens[:, :-1], tokens[:, 1:]
        ctx = self.encode_ctx(params, batch, shard)
        logits, aux = self.forward(
            params, inp, ctx=ctx, shard=shard, remat=remat, remat_policy=remat_policy
        )
        loss = cross_entropy(logits, labels, batch.get("mask"))
        return loss + aux_weight * aux, {"ce": loss, "aux": aux}

    # ---- serving -----------------------------------------------------------------
    def prefill(self, params, tokens: jax.Array, *, ctx=None, batch_inputs=None,
                shard: Sharder = NULL_SHARDER, max_len: Optional[int] = None,
                last_index=None):
        """``last_index`` (traced int32 scalar) reads the logits at that position
        instead of the static last column — the paged engine right-pads prompts
        to whole-page lengths so ONE compile serves every prompt in a page
        bucket, and the pad tail (causal: it attends backward only) never leaks
        into real positions' KV. Leave None for recurrent/hybrid families: their
        caches carry a final state that padding would pollute."""
        cfg = self.cfg
        if ctx is None and batch_inputs is not None:
            ctx = self.encode_ctx(params, batch_inputs, shard)
        x = apply_embed(params["embed"], tokens)
        if cfg.family == "hybrid":
            x = x * jnp.asarray(jnp.sqrt(cfg.d_model), x.dtype)
        x = shard(x, "batch", "seq", None)
        caches = []
        for (kind, n), p in zip(block_program(cfg), params["blocks"]):
            blk = KINDS[kind]

            def body(xc, pl, _blk=blk):
                return _blk.prefill(cfg, pl, xc, shard, ctx=ctx, max_len=max_len)

            x, cache = stack_scan(body, x, p)
            caches.append(cache)
        x = apply_norm(cfg, x, params["final_norm"])
        if last_index is None:
            x_last = x[:, -1:]
        else:
            x_last = jax.lax.dynamic_slice_in_dim(x, last_index, 1, axis=1)
        logits = apply_lm_head(cfg, params["embed"], x_last)
        logits = shard(logits, "batch", "seq", "vocab")
        return logits, caches

    # ---- paged serving (continuous batching) -------------------------------------
    def paged_cache_specs(self, num_pages: int, page_size: int, kv_spec=None):
        cfg = self.cfg
        for kind, _ in block_program(cfg):
            if not hasattr(KINDS[kind], "paged_cache_specs"):
                raise NotImplementedError(
                    f"paged KV caching supports dense-attention blocks; got {kind!r}"
                )
        return [
            stack_specs(KINDS[k].paged_cache_specs(cfg, num_pages, page_size, kv_spec), n)
            for k, n in block_program(cfg)
        ]

    def init_paged_cache(self, num_pages: int, page_size: int, kv_spec=None):
        return tree_initialize(
            self.paged_cache_specs(num_pages, page_size, kv_spec), jax.random.key(0)
        )

    def decode_step_paged(self, params, caches, tokens: jax.Array,
                          block_tables: jax.Array, context_lens: jax.Array, *,
                          shard: Sharder = NULL_SHARDER, attn_impl: str = "auto",
                          kv_spec=None, write_tables=None, n_new=None,
                          last_index=None, active=None, block_pages=None,
                          spec_verify: bool = False):
        """The MIXED serving step: decode rows and prefill chunks are the same
        computation at different widths.

        tokens (B,): classic continuous-batching decode — block_tables
        (B, max_pages) int32, context_lens (B,) int32 per-sequence positions,
        caches per-layer page pools addressed through the shared block table
        (the LayoutPaged serving path). With ``kv_spec`` (PagedQuantSpec) the
        pools are intN {"q", "scale"} pytrees and decode runs the dequantizing
        kernel — same tables, same layout, different accessor.

        tokens (B, C): a prefill CHUNK per row — the chunk-view path
        (core/submdspan.py §chunk views). ``context_lens`` is then the chunk
        cursor (tokens resident before the chunk, page-aligned and TRACED, so
        one compile serves every chunk position of every prompt in the C
        bucket); ``write_tables`` routes the chunk's KV scatter (adopted
        shared-prefix pages nulled — the compute-skip regime reads them but
        never writes); ``n_new`` (B,) is the chunk's valid token count and
        ``last_index`` (B,) picks the logits row (the prompt's true last
        position when the chunk completes a prefill). Decode is the C == 1
        degenerate case; the split exists so decode keeps its one-token
        scatter-append (with the CoW contract) while chunks scatter whole
        pages.

        ``active`` (B,) int32/bool — decode path only — is the phase bitmap:
        rows with active == 0 (PREFILLING or empty slots in a mixed step) have
        their table row and length nulled ON DEVICE, so their lockstep write
        lands in the null page and the host never copies/patches the full
        tables to mask them. The engine's device-resident table/len mirrors
        stay untouched.

        ``spec_verify=True`` with tokens (B, C) is the speculative VERIFY step:
        C = K+1 rows of [current token, draft] appended and scored per block
        via verify_paged, ``context_lens`` the per-row resident length
        (NOT page-aligned), ``active`` honored as in decode, and the lm_head
        applied to ALL C rows — returns logits (B, C, Vp).

        The layer scan writes the pool in place. Each stacked pool leaf
        (L, P, ...) is viewed as ONE flat page space (L·P, ...) — a bitcast —
        that rides the scan carry, and the indexing law is: layer ``l``'s page
        ``j`` is flat page ``l·P + j``. Layer ``l`` therefore addresses the
        flat pool through ``block_tables + l·P`` (and ``write_tables + l·P``),
        so nulled entries land in its own null page ``l·P``; the kernels see
        an ordinary (num_pages, Hkv, ps, Dh) pool. This is the layer extent
        folded into the paged layout's codomain: no layer of the pool is
        sliced out of a scanned input or restacked into a scanned output, so
        the step never copies the pool. The caller sees the (L, P, ...)
        pytree it passed in."""
        cfg = self.cfg
        chunk = tokens.ndim == 2 and not spec_verify
        if active is not None and not chunk:
            block_tables = jnp.where(active[:, None] > 0, block_tables, 0)
            context_lens = jnp.where(active > 0, context_lens, 0)
        x = apply_embed(params["embed"], tokens if tokens.ndim == 2 else tokens[:, None])
        if cfg.family == "hybrid":
            x = x * jnp.asarray(jnp.sqrt(cfg.d_model), x.dtype)
        new_caches = []
        for (kind, n), p, cache in zip(block_program(cfg), params["blocks"], caches):
            blk = KINDS[kind]
            n_pages = jax.tree.leaves(cache)[0].shape[1]
            flat = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), cache)

            def body(carry, pl_layer, _blk=blk, _n_pages=n_pages):
                xc, pool = carry
                pl, layer = pl_layer
                base = layer * _n_pages
                tables = block_tables + base
                if chunk:
                    xc, pool = _blk.prefill_chunk_paged(
                        cfg, pl, xc, pool, tables, write_tables + base,
                        context_lens, n_new, shard, impl=attn_impl,
                        kv_spec=kv_spec, null_page=base,
                    )
                elif spec_verify:
                    xc, pool = _blk.verify_paged(
                        cfg, pl, xc, pool, tables, context_lens, shard,
                        impl=attn_impl, kv_spec=kv_spec,
                    )
                else:
                    xc, pool = _blk.decode_paged(
                        cfg, pl, xc, pool, tables, context_lens, shard,
                        impl=attn_impl, kv_spec=kv_spec, block_pages=block_pages,
                    )
                return (xc, pool), None

            (x, flat), _ = stack_scan(body, (x, flat), (p, jnp.arange(n, dtype=jnp.int32)))
            new_caches.append(jax.tree.map(lambda f, a: f.reshape(a.shape), flat, cache))
        x = apply_norm(cfg, x, params["final_norm"])
        if spec_verify:
            # every row of the verify window needs its logits: row j decides
            # the fate of draft token j+1 (and the last row the bonus token)
            logits = apply_lm_head(cfg, params["embed"], x)
            return logits, new_caches
        if chunk:
            # read hidden state only at each row's requested position before
            # the lm_head: the chunk's other C-1 rows never pay the vocab matmul
            x = jnp.take_along_axis(
                x, jnp.asarray(last_index, jnp.int32)[:, None, None], axis=1
            )
        logits = apply_lm_head(cfg, params["embed"], x)
        return logits[:, 0], new_caches

    def decode_step(self, params, caches, tokens: jax.Array, pos, *,
                    shard: Sharder = NULL_SHARDER):
        """tokens: (B,) current token ids; pos: traced int32 scalar position."""
        cfg = self.cfg
        x = apply_embed(params["embed"], tokens[:, None])
        if cfg.family == "hybrid":
            x = x * jnp.asarray(jnp.sqrt(cfg.d_model), x.dtype)
        new_caches = []
        for (kind, n), p, cache in zip(block_program(cfg), params["blocks"], caches):
            blk = KINDS[kind]

            def body(xc, pc, _blk=blk):
                pl, cl = pc
                return _blk.decode(cfg, pl, xc, cl, pos, shard, ctx=None)

            x, cache = stack_scan(body, x, (p, cache))
            new_caches.append(cache)
        x = apply_norm(cfg, x, params["final_norm"])
        logits = apply_lm_head(cfg, params["embed"], x)
        return logits[:, 0], new_caches
