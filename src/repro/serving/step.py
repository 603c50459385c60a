"""serve_step / prefill factories (batched decode against sharded KV caches).

The paged factories implement the DEVICE-RESIDENT decode hot path: token
selection (serving/sampling.py policies via ops.sample_tokens) is fused into
the step so logits never leave the device, the per-slot lengths advance on
device (the step returns ``context_lens + active`` for the engine to adopt as
its persistent mirror), and ``make_paged_serve_multistep`` runs K such
iterations in one on-device ``lax.scan`` — the sampled token feeds straight
back into the next embedding lookup, amortizing one dispatch and one (K, B)
ids transfer over K generated tokens.

The speculative sibling lives in serving/speculative.py:
``make_paged_serve_spec_multistep`` scans S draft->verify->accept WINDOWS
instead of S single-token steps, committing 1..K+1 tokens per window through
the same fused sampling and lens plumbing — an engine with ``spec_tokens>0``
swaps that factory in where this module's multistep would go, and everything
else here (prefill buckets, chunked prefill, the plain step it falls back to
under backoff) is shared between the two regimes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.models.layers import Sharder


def make_serve_step(model, mesh=None, rules=None):
    shard = Sharder(mesh, rules)

    def serve_step(params, caches, tokens, pos):
        """tokens: (B,) int32; pos: int32 scalar -> (logits (B, Vp), new caches)."""
        return model.decode_step(params, caches, tokens, pos, shard=shard)

    return serve_step


def top_logprobs(logits, vocab: int, k: int):
    """(vals (B, k), ids (B, k)): the top-k log-probabilities of each row's
    next-token distribution, computed ON DEVICE from the same logits the
    sampler consumes (pad columns excluded). The (B, k) pair rides the same
    per-step D2H fetch as the sampled ids — no extra sync point."""
    lp = jax.nn.log_softmax(logits[:, :vocab].astype(jnp.float32), axis=-1)
    return jax.lax.top_k(lp, k)


def _fused_decode(model, shard, attn_impl, kv_spec, vocab, params, caches,
                  tokens, block_tables, context_lens, slot_f32, slot_i32,
                  grammar=None, block_pages=None):
    """One fused decode iteration: append -> attend -> sample, all on device.

    The per-slot policy rides in TWO packed vectors (device_put on this
    backend costs ~1ms per array regardless of size, so the engine uploads
    exactly two on a slot-composition change, never six):
      slot_f32 (2, B) f32: [temperature, top_p]
      slot_i32 (3, B) i32: [active, top_k, seed-bits (uint32 reinterpreted)]
    ``active`` is the phase bitmap (masked slots null-route on device — see
    decode_step_paged); the sampled position folds ``context_lens + 1``, the
    length of the context the new token extends, so sampling is invariant
    under preemption-recompute and batch recomposition.

    ``grammar`` (None or (gstate (B,) i32, gmask (S, vocab) f32, gtrans
    (S, vocab) i32)) is the constrained-decoding stage: each slot's mask row is
    gathered by its automaton state and ADDED to the logits inside the sampler,
    and the state advances by the token just sampled — the grammar walks
    entirely on device, preserving the decode loop's zero-D2H property. Row 0
    of the tables is the reserved unconstrained state (all-zero mask,
    self-loops), so ungated slots ride the same program.

    Returns (next_tokens (B,) i32, logits (B, Vp), new_lens (B,) i32, caches,
    chosen_lp (B,) f32[, new_gstate (B,) i32 when grammar][, routing counts
    (3,) i32 when the model routes experts, last]). ``chosen_lp`` is
    log P(next_token | prefix) under the UNMASKED distribution — the per-branch
    cumulative score best-of-n ranks by (a grammar constrains selection, not
    the score).
    """
    active = slot_i32[0]
    stats = model.routes_experts
    out = model.decode_step_paged(
        params, caches, tokens, block_tables, context_lens,
        shard=shard, attn_impl=attn_impl, kv_spec=kv_spec, active=active,
        block_pages=block_pages, stats=stats,
    )
    logits, caches = out[:2]
    routed = out[2:]
    mask = None
    if grammar is not None:
        gstate, gmask, gtrans = grammar
        mask = gmask[gstate]  # (B, vocab) per-slot additive penalty rows
    nxt = ops.sample_tokens(
        logits, slot_f32[0], slot_i32[1], slot_f32[1],
        slot_i32[2].astype(jnp.uint32),  # i32 -> u32 wraps: bit-identical
        context_lens + 1, vocab=vocab, mask=mask,
    )
    new_lens = context_lens + jnp.where(active > 0, 1, 0).astype(context_lens.dtype)
    lp = jax.nn.log_softmax(logits[:, :vocab].astype(jnp.float32), axis=-1)
    chosen_lp = jnp.take_along_axis(lp, nxt[:, None], axis=-1)[:, 0]
    if grammar is None:
        return (nxt, logits, new_lens, caches, chosen_lp) + routed
    new_gstate = jnp.where(active > 0, gtrans[gstate, nxt], gstate)
    return (nxt, logits, new_lens, caches, chosen_lp, new_gstate) + routed


def make_paged_serve_step(model, mesh=None, rules=None, attn_impl="auto",
                          kv_spec=None, vocab=None, logprobs_k=0,
                          grammar=False, block_pages=None):
    shard = Sharder(mesh, rules)

    if vocab is None:
        # legacy unfused step: logits come back to the host (kept for external
        # callers and as the reference semantics the fused path must reproduce)
        def paged_serve_step(params, caches, tokens, block_tables, context_lens):
            """tokens: (B,) int32; block_tables: (B, max_pages) int32;
            context_lens: (B,) int32 per-sequence positions -> (logits (B, Vp),
            new page pools). Each row scatters its token's KV at page
            block_tables[b, lens[b]//ps], slot lens[b] % ps; the caller must
            have made every targeted page private (CoW on the host) first."""
            return model.decode_step_paged(
                params, caches, tokens, block_tables, context_lens,
                shard=shard, attn_impl=attn_impl, kv_spec=kv_spec,
                block_pages=block_pages,
            )

        return paged_serve_step

    def fused_serve_step(params, caches, tokens, block_tables, context_lens,
                         slot_f32, slot_i32, *g):
        """The device-resident decode step: one batched token per active slot,
        SAMPLED on device (greedy/temperature/top-k/top-p per slot, packed in
        slot_f32/slot_i32 — see _fused_decode). The only per-token D2H traffic
        is the (B,) next_tokens output; logits are returned for the opt-in
        record_logits slow path and cost nothing when the host never fetches
        them. ``context_lens`` is the engine's device-resident lens mirror
        (donated); ``new_lens`` is its successor — the LayoutPaged
        index->offset state advances beside the pool it indexes, no host
        round-trip. ``chosen_lp`` always rides the output pytree (same fetch
        round as the ids; free when the host ignores it). With ``logprobs_k >
        0`` the step additionally returns the per-slot (vals, ids) top-k
        logprob pair (compile-time width). With ``grammar`` the factory adds
        three positional args — gstate (B,) i32 (donated, like the lens
        mirror), gmask (S, vocab) f32, gtrans (S, vocab) i32 — and returns the
        advanced gstate after chosen_lp."""
        out = _fused_decode(
            model, shard, attn_impl, kv_spec, vocab, params, caches,
            tokens, block_tables, context_lens, slot_f32, slot_i32,
            grammar=tuple(g) if grammar else None, block_pages=block_pages,
        )
        routed = out[6 if grammar else 5:]  # the routing counts stay last
        out = out[:len(out) - len(routed)]
        if logprobs_k:
            out = out + (top_logprobs(out[1], vocab, logprobs_k),)
        return out + routed

    return fused_serve_step


def make_paged_serve_multistep(model, k_steps: int, mesh=None, rules=None,
                               attn_impl="auto", kv_spec=None, vocab=None,
                               logprobs_k=0, grammar=False, block_pages=None):
    """K fused decode iterations in one on-device loop (jax.lax.scan).

    Legal only over an event-free horizon (Scheduler.event_free_horizon): no
    admission, no page-boundary crossing past owned capacity, no CoW, no
    max-token finish within K — so the loop body never needs the host. Each
    iteration appends the current token's KV, attends, samples, and feeds the
    sampled token into the next iteration's embedding lookup; lengths advance
    on device. Returns (tokens_per_step (K, B) i32, last_tokens (B,),
    new_lens (B,), caches, chosen_lps (K, B) f32) — one dispatch and one
    (K, B) fetch round per K generated tokens. With ``grammar`` the per-slot
    automaton state rides the scan CARRY exactly like the lengths do (the K
    masks and transitions all happen inside the loop — constrained decoding
    costs zero extra host round-trips even fused), and the advanced gstate is
    returned after the chosen_lps. With ``logprobs_k > 0`` the scan
    additionally stacks the per-step top-k logprob pair ((K, B, k) vals +
    ids), fetched in the same round as the ids. A routed model's counts of
    the K steps come last, summed.
    """
    shard = Sharder(mesh, rules)

    def fused_multistep(params, caches, tokens, block_tables, context_lens,
                        slot_f32, slot_i32, *g):
        def body(carry, _):
            toks, lens, gs, cs = carry
            out = _fused_decode(
                model, shard, attn_impl, kv_spec, vocab, params, cs,
                toks, block_tables, lens, slot_f32, slot_i32,
                grammar=(gs, g[1], g[2]) if grammar else None,
                block_pages=block_pages,
            )
            nxt, logits, new_lens, cs, chosen_lp = out[:5]
            new_gs = out[5] if grammar else gs
            y = (nxt, chosen_lp, out[6 if grammar else 5:])
            if logprobs_k:
                y = y + (top_logprobs(logits, vocab, logprobs_k),)
            return (nxt, new_lens, new_gs, cs), y

        gs0 = g[0] if grammar else jnp.zeros_like(context_lens)
        (last, new_lens, gs, caches), ys = jax.lax.scan(
            body, (tokens, context_lens, gs0, caches), None, length=k_steps
        )
        toks, lps = ys[0], ys[1]
        out = (toks, last, new_lens, caches, lps)
        if grammar:
            out = out + (gs,)
        if logprobs_k:
            out = out + (ys[3],)
        return out + tuple(c.sum(axis=0) for c in ys[2])

    return fused_multistep


def make_chunked_prefill_step(model, mesh=None, rules=None, attn_impl="auto",
                              kv_spec=None):
    shard = Sharder(mesh, rules)

    def chunk_prefill_step(params, caches, tokens, block_tables, write_tables,
                           cursors, n_new, last_index):
        """The mixed step's prefill half: tokens (B, C) — one prefill chunk per
        row, C the engine's chunk bucket -> (logits (B, Vp) at last_index, new
        page pools). ``cursors`` (chunk start), ``n_new`` and ``last_index``
        are traced, so ONE compile serves every chunk position of every prompt
        length in the bucket — there is no per-prompt-length prefill compile in
        the chunked engine. ``block_tables`` is the read view of each row's
        pages (shared prefix included: the compute-skip path attends the
        donor's KV); ``write_tables`` nulls the non-writable entries so the
        chunk's scatter never lands in a page another sequence reads — the CoW
        obligation discharged by table surgery instead of a copy. A routed
        model returns its routing counts as a third item."""
        return model.decode_step_paged(
            params, caches, tokens, block_tables, cursors,
            shard=shard, attn_impl=attn_impl, kv_spec=kv_spec,
            write_tables=write_tables, n_new=n_new, last_index=last_index,
            stats=model.routes_experts,
        )

    return chunk_prefill_step


def make_prefill(model, mesh=None, rules=None, max_len=None):
    shard = Sharder(mesh, rules)

    def prefill(params, tokens, batch_inputs=None, last_index=None):
        return model.prefill(
            params, tokens, batch_inputs=batch_inputs, shard=shard,
            max_len=max_len, last_index=last_index,
        )

    return prefill
