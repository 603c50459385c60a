"""Mixture-of-Experts block: top-k token-choice routing with sort-based dispatch.

Dispatch is capacity-based (deterministic shapes — required for SPMD lowering):
tokens are ranked within their chosen expert via an argsort over expert ids, then
scattered into an (E, C, D) buffer whose expert dim shards over the "model" axis —
the token→expert all-to-all materializes at this sharding boundary, and the
expert FFN einsums run expert-parallel (EP). Combine is the gather transpose.

Aux load-balance loss (Switch-style) is returned alongside.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.distributed import TensorSpec

from .layers import NULL_SHARDER, Sharder

def moe_specs(cfg, *, quant=None) -> Dict[str, TensorSpec]:
    from .layers import fit_quant

    e, d, f = cfg.n_held_experts, cfg.d_model, cfg.d_ff
    dt = cfg.param_dtype

    def mk(shape, axes):
        q = fit_quant(quant, shape[-1])
        return TensorSpec(shape, axes, dtype=dt, init="fan_in", accessor=q)
    return {
        "router": TensorSpec((d, cfg.n_experts), ("embed", None), dtype=jnp.float32,
                             init="fan_in"),
        "w_gate": mk((e, d, f), ("expert", "embed", "expert_ffn")),
        "w_up": mk((e, d, f), ("expert", "embed", "expert_ffn")),
        "w_down": mk((e, f, d), ("expert", "expert_ffn", "embed")),
    }


def apply_moe_dropless(cfg, p, x: jax.Array, valid: Optional[jax.Array] = None,
                       impl: str = "auto") -> Tuple[jax.Array, jax.Array]:
    """The serving paths' routed layer: no token is ever dropped, so a
    token's output does not depend on what else is in its batch.

    The router scores all ``cfg.n_experts`` experts (softmax, top-k,
    renormalised over the k); of the (token, expert) pairs, only those whose
    expert is held here, experts [expert_rank * held, (expert_rank + 1) *
    held), are computed (``p`` holds those experts' weights). The pairs are
    sorted by expert and multiplied as one grouped product per projection
    (``_grouped``): work in proportion to the held pairs, no capacity buffer.
    What the experts held elsewhere would add is left out; on one chip the
    layer runs without its exchange.

    ``p`` may hold every layer's experts, (L, held, ...), with ``p["layer"]``
    naming this one: the grouped product then reads its layer's experts in
    place, and no step copies a layer's expert weights out of the stack.

    x: (B, S, D); ``valid`` (B, S) marks the tokens the counts count (pad
    rows are computed, but not counted). Returns (y, counts), counts (3,)
    int32: routed pairs, held pairs, and held experts with a pair."""
    b, s, d = x.shape
    t = b * s
    k, held = cfg.top_k, cfg.n_held_experts
    lo = cfg.expert_rank * held
    xt = x.reshape(t, d)
    with jax.named_scope("moe.route"):
        logits = xt.astype(jnp.float32) @ p["router"].astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate, idx = jax.lax.top_k(probs, k)  # (T, k)
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
        local = idx.reshape(-1) - lo  # (T*k,) expert ids of this chip's share
        mine = (local >= 0) & (local < held)
        group = jnp.where(mine, local, held)  # pairs held elsewhere sort last
        order = jnp.argsort(group, stable=True)
        sizes = jnp.bincount(group, length=held + 1)[:held].astype(jnp.int32)
        n_mine = jnp.sum(sizes)
        token_of = order // k  # held pairs first, grouped by expert
        live = jnp.arange(t * k) < n_mine
        w = jnp.where(live, gate.reshape(-1)[order], 0.0)
        ok = jnp.ones((t,), bool) if valid is None else valid.reshape(t)
        ok_pairs = jnp.repeat(ok, k) & mine
        per_expert = jnp.bincount(jnp.where(ok_pairs, local, held), length=held + 1)
        counts = jnp.stack([jnp.sum(ok) * k, jnp.sum(ok_pairs),
                            jnp.sum(per_expert[:held] > 0)]).astype(jnp.int32)
    with jax.named_scope("moe.experts"):
        layer = p.get("layer")
        rows = xt[token_of]  # (T*k, D)
        g = _grouped(rows, p["w_gate"], sizes, layer, cfg, impl)
        u = _grouped(rows, p["w_up"], sizes, layer, cfg, impl)
        h = (jax.nn.silu(g) * u).astype(x.dtype)
        y = _grouped(h, p["w_down"], sizes, layer, cfg, impl)
        # rows past the held pairs belong to no group: never read them
        y = jnp.where(live[:, None], y, 0.0) * w[:, None]
        # back to (token, choice) order by gathering, then each token's k
        # choices summed in the router's order
        unsort = jnp.zeros_like(order).at[order].set(jnp.arange(t * k, dtype=order.dtype))
        out = y[unsort].reshape(t, k, d).sum(axis=1)
    return out.astype(x.dtype).reshape(b, s, d), counts


def _grouped(rows, w, sizes, layer, cfg, impl: str) -> jax.Array:
    """ops.grouped_matmul over this layer's held experts; quantized experts
    are dequantized first."""
    from repro.kernels import ops

    if isinstance(w, dict):
        if layer is not None:
            w = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, layer, keepdims=False), w)
            layer = None
        w = _deq(w, cfg)
    return ops.grouped_matmul(rows, w.astype(rows.dtype), sizes, layer=layer, impl=impl)


def _capacity(cfg, n_tokens: int) -> int:
    c = int(n_tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts) + 1
    return -(-c // 8) * 8  # sublane-aligned


def apply_moe(
    cfg, p, x: jax.Array, shard: Sharder = NULL_SHARDER
) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (y, aux_loss)."""
    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.top_k
    cap = _capacity(cfg, t)
    xt = x.reshape(t, d)
    xt = shard(xt, "tokens", None)

    logits = (xt.astype(jnp.float32) @ p["router"]).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, k)  # (T, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # Switch aux loss: E * sum_e f_e * P_e
    ohot = jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32)  # top-1 fraction
    f_e = jnp.mean(ohot, axis=0)
    p_e = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(f_e * p_e)

    # rank within expert via stable sort over expert ids
    eflat = idx.reshape(-1)  # (T*k,)
    order = jnp.argsort(eflat)  # stable
    sorted_e = eflat[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(e), side="left")  # (E,)
    ranks_sorted = jnp.arange(t * k) - starts[sorted_e]
    ranks = jnp.zeros_like(ranks_sorted).at[order].set(ranks_sorted)

    slot = eflat * cap + ranks
    valid = ranks < cap
    safe_slot = jnp.where(valid, slot, e * cap)  # out-of-range -> dropped

    token_of = jnp.arange(t * k) // k
    buf = jnp.zeros((e * cap, d), x.dtype)
    buf = buf.at[safe_slot].set(xt[token_of], mode="drop")
    buf = buf.reshape(e, cap, d)
    buf = shard(buf, "expert", None, None)  # ← token→expert all-to-all boundary

    # expert FFN (SwiGLU), expert-parallel batched einsums
    g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"].astype(x.dtype) if not isinstance(p["w_gate"], dict) else _deq(p["w_gate"], cfg))
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"].astype(x.dtype) if not isinstance(p["w_up"], dict) else _deq(p["w_up"], cfg))
    h = (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)).astype(x.dtype)
    h = shard(h, "expert", None, "expert_ffn")
    y = jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(x.dtype) if not isinstance(p["w_down"], dict) else _deq(p["w_down"], cfg))
    y = y.reshape(e * cap, d)

    # combine: gather back and weight
    gathered = y[jnp.where(valid, slot, 0)]  # (T*k, D)
    w = (gate_vals.reshape(-1) * valid.astype(jnp.float32)).astype(x.dtype)
    out = (gathered * w[:, None]).reshape(t, k, d).sum(axis=1)
    out = shard(out, "tokens", None)
    return out.reshape(b, s, d), aux


# ------------------------------------------------------------------------------------
# Expert-parallel dispatch via shard_map (§Perf hillclimb #1)
#
# The pure-SPMD scatter/gather dispatch above lets GSPMD choose the collectives, and
# it chooses disastrously at 384-expert scale: the dispatch scatter materializes and
# ALL-GATHERS a (T·k, D) u32 index tensor (~240 GB/device/layer on the kimi-k2 train
# cell — measured, see EXPERIMENTS.md §Perf). The shard_map formulation makes the
# data movement explicit and minimal:
#
#   * tokens are sharded over the batch axes and REPLICATED over "model", so every
#     model-rank routes identically and just SLICES its own experts' buffers — the
#     dispatch itself moves zero bytes;
#   * each rank computes its experts' outputs and the gate-weighted COMBINE for its
#     expert subset; one bf16 psum over "model" (activation-sized, T_loc × D) merges
#     the contributions — this is the only forward collective;
#   * FSDP weight gathers still happen at the shard_map boundary (declared in_specs),
#     where XLA can overlap them with the previous layer.
# ------------------------------------------------------------------------------------
MOE_IMPL = "auto"  # "auto" -> shard_map when a mesh with a "model" axis is present


def set_moe_impl(impl: str) -> None:
    global MOE_IMPL
    assert impl in ("auto", "einsum", "shard_map")
    MOE_IMPL = impl


def use_shard_map(shard) -> bool:
    if MOE_IMPL == "einsum":
        return False
    mesh = getattr(shard, "mesh", None)
    return mesh is not None and "model" in mesh.shape and mesh.shape["model"] > 1


def apply_moe_ep(cfg, p, x: jax.Array, shard) -> Tuple[jax.Array, jax.Array]:
    """shard_map expert-parallel MoE. x: (B, S, D) sharded (batch→batch axes)."""
    from jax.sharding import PartitionSpec as P

    mesh = shard.mesh
    ep = mesh.shape["model"]
    tok_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    n_tok = 1
    for a in tok_axes:
        n_tok *= mesh.shape[a]
    b, s, d = x.shape
    t = b * s
    assert t % n_tok == 0
    t_loc = t // n_tok
    e, k = cfg.n_experts, cfg.top_k
    assert e % ep == 0
    e_loc = e // ep
    cap = -(-(int(t_loc * k * cfg.capacity_factor / e) + 1) // 8) * 8  # ceil to 8

    def local_fn(xt, router_w, wg, wu, wd):
        # xt: (T_loc, D); router_w: (D, E); wg/wu: (e_loc, D, F); wd: (e_loc, F, D)
        f32 = jnp.float32
        logits = xt.astype(f32) @ router_w.astype(f32)  # (T_loc, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, idx = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)
        ohot = jax.nn.one_hot(idx[:, 0], e, dtype=f32)
        aux = e * jnp.sum(jnp.mean(ohot, 0) * jnp.mean(probs, 0))
        aux = jax.lax.pmean(aux, tok_axes) if tok_axes else aux

        # local slot assignment (all ints are (T_loc*k,) — nothing big)
        eflat = idx.reshape(-1)
        order = jnp.argsort(eflat)
        sorted_e = eflat[order]
        starts = jnp.searchsorted(sorted_e, jnp.arange(e), side="left")
        ranks_sorted = jnp.arange(t_loc * k) - starts[sorted_e]
        ranks = jnp.zeros_like(ranks_sorted).at[order].set(ranks_sorted)
        slot = eflat * cap + ranks
        valid = ranks < cap
        token_of = jnp.arange(t_loc * k) // k

        # dispatch rows for MY experts only: slice the slot table, gather locally
        my = jax.lax.axis_index("model")
        src = jnp.full((e * cap,), t_loc * k, jnp.int32)
        src = src.at[jnp.where(valid, slot, e * cap)].set(
            jnp.arange(t_loc * k, dtype=jnp.int32), mode="drop"
        )
        src_my = jax.lax.dynamic_slice_in_dim(src, my * e_loc * cap, e_loc * cap, 0)
        live = src_my < t_loc * k
        rows = jnp.where(
            live[:, None], xt[token_of[jnp.minimum(src_my, t_loc * k - 1)]], 0
        )  # (e_loc*cap, D)
        buf = rows.reshape(e_loc, cap, d)

        wg_, wu_, wd_ = (
            _deq(w, cfg) if isinstance(w, dict) else w.astype(x.dtype)
            for w in (wg, wu, wd)
        )
        g = jnp.einsum("ecd,edf->ecf", buf, wg_)
        u = jnp.einsum("ecd,edf->ecf", buf, wu_)
        h = (jax.nn.silu(g.astype(f32)) * u.astype(f32)).astype(x.dtype)
        y = jnp.einsum("ecf,efd->ecd", h, wd_).reshape(e_loc * cap, d)

        # combine MY experts' contributions at their source tokens, then psum
        w_gate_flat = (gate_vals.reshape(-1) * valid.astype(f32)).astype(x.dtype)
        contrib = jnp.zeros((t_loc, d), x.dtype)
        src_tok = jnp.where(live, token_of[jnp.minimum(src_my, t_loc * k - 1)], t_loc)
        src_w = jnp.where(live, w_gate_flat[jnp.minimum(src_my, t_loc * k - 1)], 0)
        contrib = contrib.at[src_tok].add(y * src_w[:, None], mode="drop")
        out = jax.lax.psum(contrib, "model")
        return out, aux

    xt = x.reshape(t, d)
    tok = tok_axes if len(tok_axes) > 1 else (tok_axes[0] if tok_axes else None)
    wspec3 = P("model", None, None)  # prefix-matches quantized {"q","scale"} leaves too
    out, aux = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(tok, None), P(None, None), wspec3, wspec3, wspec3),
        out_specs=(P(tok, None), P()),
        check_vma=False,
    )(xt, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return out.reshape(b, s, d), aux


def _deq(wbufs, cfg):
    """Expert weights stored quantized: dequantize at use (serving path).

    NOTE: expert matmuls dominate MoE compute; the Pallas quant path covers 2-D
    weights — batched-expert quantized einsum falls back to dequant-then-einsum
    (HBM still holds int8; dequant is at the compute boundary)."""
    from repro.core.accessors import QuantizedAccessor
    from repro.core.distributed import dequantize_array

    # accessor metadata travels on the spec; bits inferred from buffer dtypes
    acc = QuantizedAccessor(cfg.param_dtype, bits=8, block=wbufs["q"].shape[-1] // wbufs["scale"].shape[-1])
    return dequantize_array(wbufs, acc)


def apply_moe_dispatch(cfg, p, x, shard) -> Tuple[jax.Array, jax.Array]:
    """Entry point: shard_map EP when a model axis exists (hillclimbed path),
    pure-SPMD einsum dispatch otherwise (single-host smoke paths, baselines)."""
    if use_shard_map(shard):
        return apply_moe_ep(cfg, p, x, shard)
    return apply_moe(cfg, p, x, shard)
