"""Step-level admission/eviction policy for the continuous-batching engine.

Each engine step the scheduler:
  1. admits queued requests FIFO while a batch slot is free AND the pool can
     hold the whole context plus a one-page decode headroom (watermark) — never
     admitting a request it would immediately have to preempt. Admission cost
     counts only the NEW pages the request must pop from the free list: pages
     its prompt prefix can adopt from the cache's prefix index are free, so
     bursts of shared-prefix requests admit far deeper batches than the pool's
     raw size suggests;
  2. guarantees every running sequence a page it may WRITE for its next token:
     appending a page when the sequence crosses a page boundary, and
     copy-on-write-privatizing the target page when prefix sharing left it
     refcount>1 — in both cases preempting the MOST RECENTLY admitted other
     sequence when the pool runs dry (LIFO victim choice keeps the oldest
     requests making progress, so total recompute work is bounded); preempted
     sequences release all pages (shared ones survive with their co-owners) and
     requeue at the FRONT with their generated tokens kept — on re-admission
     the full context is re-prefilled and may re-share any of its prefix pages
     that stayed alive. With a host tier configured
     (EngineConfig.host_pool_pages) preemption becomes SWAP instead: complete
     pages demote to host RAM before freeing, and re-admission promotes them
     back (prefetch) so only the tail is recomputed.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from .cache import PagedKVCache
from .request import DECODING, BranchGroup, RequestQueue, RequestState


@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    max_batch: int
    watermark_pages: int = 1  # free pages kept back at admission for decode growth


class Scheduler:
    def __init__(self, cache: PagedKVCache, config: SchedulerConfig):
        self.cache = cache
        self.config = config
        # slot -> state, in admission order (dict preserves insertion order)
        self.running: Dict[int, RequestState] = {}
        # lifecycle trace (serving/telemetry.EngineTrace), attached by the
        # engine; preemption and rejection decisions are emitted here, at the
        # point the policy makes them
        self.trace = None

    # -- admission -----------------------------------------------------------------
    def _chain_of(self, state: RequestState):
        """The state's memoized prefix keys — None when sharing is off, so the
        non-sharing configuration pays no hashing at all."""
        if not self.cache.prefix_sharing:
            return None
        return state.hash_chain(self.cache.page_size)

    def free_slots(self) -> List[int]:
        return [s for s in range(self.config.max_batch) if s not in self.running]

    def fits(self, state: RequestState) -> bool:
        # ServeEngine.submit() already rejected any request whose EVENTUAL
        # footprint (pages_for(prompt + max_new_tokens), invariant under
        # preemption/requeue) exceeds max_pages_per_seq, so only page
        # availability is decided here. Only pages the request cannot adopt
        # from the prefix index count against the free list (the state memoizes
        # its hash chain, so a queued request re-checked every step hashes once).
        need = self.cache.new_pages_needed(state.context, chain=self._chain_of(state))
        # no watermark when the batch is empty: an unadmittable head request with
        # nothing running would deadlock, and with no co-tenants there is nothing
        # for decode growth to collide with
        watermark = self.config.watermark_pages if self.running else 0
        return need + watermark <= self.cache.num_free

    def _group_need(self, group: BranchGroup) -> int:
        """Free-list pages a whole branch group needs at admission. Fresh
        siblings fork the primary's pages, so each costs at most ONE fresh page
        (the +1-token decode headroom when the prompt fills its last page, or
        the eventual CoW privatization of a shared partial page — never both at
        once); re-admitted siblings re-prefill their own diverged contexts and
        are costed like any request (their chains re-adopt whatever prefix
        pages survived, including each other's)."""
        need = 0
        for st in group.branches:
            if st.done:
                continue
            if st.await_fork:
                need += 1
            else:
                need += self.cache.new_pages_needed(
                    st.context, chain=self._chain_of(st)
                )
        return need

    def impossible(self, state: RequestState) -> bool:
        """True when this request can NEVER admit: its context needs more pages
        than the whole pool holds even with every page free and no co-tenant.
        Prefix sharing cannot rescue it — adopted pages still occupy the pool,
        and the one-page decode headroom must come from somewhere. Without this
        check such a request sits at the queue head forever, wedging everything
        behind it (fits() keeps returning False each step, the engine keeps
        spinning). The engine fails it with a clear error instead."""
        return (
            self.cache.pages_for(len(state.context) + 1) > self.cache.num_pages - 1
        )

    def reject_impossible(self, queue: RequestQueue) -> List[RequestState]:
        """Pop every queue-head request that impossible() condemns (arrival
        order scans until the first servable head), stamping .error. Covers
        both fresh submissions that slipped past submit()'s static check (a
        preempted request's context GROWS by its generated tokens, so a
        request servable at submit time can outgrow the pool) and keeps FIFO
        semantics for everything behind the failed head."""
        failed = []
        while queue:
            state = queue.peek()
            if not self.impossible(state):
                break
            queue.pop()
            state.error = (
                f"request {state.request.rid} needs "
                f"{self.cache.pages_for(len(state.context) + 1)} pages for its "
                f"{len(state.context)}-token context but the pool only has "
                f"{self.cache.num_pages - 1} — raise num_pages or shorten the request"
            )
            if self.trace is not None:
                self.trace.instant(
                    "reject", rid=state.request.rid,
                    context=len(state.context),
                )
            failed.append(state)
        return failed

    def admit(self, queue: RequestQueue, now: float,
              publish: bool = True) -> List[Tuple[int, RequestState]]:
        """Pop admissible requests, allocate their prompt pages (+1 headroom page
        so the first decode token always has a slot), bind batch slots.
        ``publish=False`` defers prefix-index registration to
        cache.publish_prefix (chunked prefill: pages fill over many steps)."""
        admitted = []
        slots = self.free_slots()
        while queue and slots:
            state = queue.peek()
            if state.request.arrival_time > now:
                break
            group = state.group
            if group is not None:
                # a branch group admits AS A UNIT: one slot per live branch,
                # pages for every re-prefilling member plus fork headroom for
                # the fresh ones — or not at all (partial groups would let a
                # sibling's admission preempt its own primary)
                live = [st for st in group.branches if not st.done]
                watermark = self.config.watermark_pages if self.running else 0
                if (len(slots) < len(live)
                        or self._group_need(group) + watermark > self.cache.num_free):
                    break
                queue.pop()
                group.pending_rows.clear()
                for st in live:
                    slot = slots.pop(0)
                    if not st.await_fork:
                        ctx = st.context
                        self.cache.allocate(
                            slot, self.cache.pages_for(len(ctx) + 1), tokens=ctx,
                            chain=self._chain_of(st), publish=publish,
                        )
                    st.slot = slot
                    if st.admit_time is None:  # first admission only
                        st.admit_time = now
                    self.running[slot] = st
                    admitted.append((slot, st))
                continue
            if not self.fits(state):
                break
            queue.pop()
            slot = slots.pop(0)
            ctx = state.context
            self.cache.allocate(
                slot, self.cache.pages_for(len(ctx) + 1), tokens=ctx,
                chain=self._chain_of(state), publish=publish,
            )
            state.slot = slot
            if state.admit_time is None:  # first admission only
                state.admit_time = now
            self.running[slot] = state
            admitted.append((slot, state))
        return admitted

    # -- decode-page guarantee -------------------------------------------------------
    def _preempt_one(self, queue: RequestQueue, keep_slot: int) -> Optional[RequestState]:
        keep_group = (
            self.running[keep_slot].group if keep_slot in self.running else None
        )
        victims = [
            s for s, st in self.running.items()
            if s != keep_slot
            and (keep_group is None or st.group is not keep_group)
        ]
        if not victims:
            return None
        slot = victims[-1]  # most recently admitted
        state = self.running.pop(slot)
        group = state.group
        # a group member's eviction evicts the WHOLE group: its siblings alias
        # its pages (sample) or advance in lockstep with it (beam), so leaving
        # them running would either pin the pages eviction was meant to free or
        # stall the joint step. The group requeues as its primary — re-admission
        # re-prefills every diverged branch and re-forks the fresh ones.
        members = [state]
        if group is not None:
            for s in [s for s, st in list(self.running.items()) if st.group is group]:
                members.append(self.running.pop(s))
            group.pending_rows.clear()
        if self.trace is not None:
            self.trace.instant(
                "preempt", slot, rid=state.request.rid,
                n_preemptions=state.n_preemptions + 1, keep_slot=keep_slot,
                group_size=len(members),
            )
        for st in members:
            if st.slot is not None:
                # preemption as swap: demote the victim's complete pages to
                # the host tier (no-op without one) BEFORE freeing, so
                # re-admission prefetches instead of recomputing prefill
                self.cache.demote_slot(st.slot, self._chain_of(st))
                self.cache.free_slot(st.slot)
            st.release()  # drops the slot AND any mid-prefill chunk cursor
        head = state if group is None else group.primary
        head.n_preemptions += 1
        queue.requeue_front(head)
        return head

    def preempt_slot(self, slot: int, queue: RequestQueue) -> Optional[RequestState]:
        """Targeted eviction of ONE specific slot (the broken-twin recovery
        path: its donor died before covering its adopted pages, so those
        pages hold garbage). Same whole-group semantics as _preempt_one but
        NEVER demotes — garbage pages must not enter the host tier."""
        if slot not in self.running:
            return None
        state = self.running.pop(slot)
        group = state.group
        members = [state]
        if group is not None:
            for s in [s for s, st in list(self.running.items()) if st.group is group]:
                members.append(self.running.pop(s))
            group.pending_rows.clear()
        if self.trace is not None:
            self.trace.instant(
                "preempt", slot, rid=state.request.rid,
                n_preemptions=state.n_preemptions + 1, keep_slot=-1,
                group_size=len(members),
            )
        for st in members:
            if st.slot is not None:
                self.cache.free_slot(st.slot)
            st.release()
        head = state if group is None else group.primary
        head.n_preemptions += 1
        queue.requeue_front(head)
        return head

    def ensure_decode_page(self, slot: int, queue: RequestQueue) -> None:
        """Make sure ``slot`` owns a WRITABLE page covering position lens[slot]
        (where the next token's KV lands): append a page at page boundaries, and
        copy-on-write the target page if prefix sharing left it refcount>1 —
        preempting later arrivals if either needs a page the pool cannot give."""
        pos = int(self.cache.lens[slot])
        while pos >= len(self.cache.pages_of[slot]) * self.cache.page_size:
            if self.cache.append_page(slot):
                continue
            if self._preempt_one(queue, keep_slot=slot) is None:
                raise RuntimeError(
                    "KV pool exhausted with a single running sequence — "
                    "num_pages is too small for this request"
                )
        while self.cache.needs_cow(slot):
            if self.cache.cow_page(slot):
                continue
            # a shared page always has >= 2 holders, so a victim must exist
            if self._preempt_one(queue, keep_slot=slot) is None:
                raise RuntimeError(
                    "KV pool exhausted while copy-on-write needed a page — "
                    "num_pages is too small for this request"
                )

    # -- fused-decode horizon --------------------------------------------------------
    def reserve_decode_tokens(self, slot: int, n_tokens: int) -> bool:
        """Best-effort page pre-append: grow ``slot``'s owned pages until it
        can take ``n_tokens`` MORE tokens beyond lens[slot] with no further
        host intervention — the horizon-aware pre-append that lets a fused (or
        speculative) window prove its whole page budget UP FRONT instead of
        shrinking to whatever the current page has left. Never preempts: a dry
        pool or the per-seq page cap returns False and the caller degrades
        (smaller window / non-speculative path). Appended pages are ordinary
        owned pages — freed with the slot, filled by later decode either way,
        so a failed window wastes nothing."""
        cache = self.cache
        while cache.capacity_tokens(slot) < n_tokens:
            if len(cache.pages_of[slot]) >= cache.max_pages_per_seq:
                return False
            if not cache.append_page(slot):
                return False
        return True

    def event_free_horizon(self, queue: RequestQueue,
                           tokens_per_step: int = 1) -> int:
        """Largest K such that the next K decode steps provably need NO
        scheduler intervention — the precondition for running them as one
        on-device fused loop (make_paged_serve_multistep). A pure function of
        host-mirrored state: no admission (queue must be empty — free pages
        only shrink during decode, so nothing unadmittable becomes admittable
        mid-horizon), every slot DECODING, no CoW pending, and per slot at
        least K steps' worth of both owned page capacity (no page-boundary
        append; reserve_decode_tokens can raise capacity first) and
        max_new_tokens budget (no max-token finish). EOS finishes are NOT
        predictable; a fused window may overrun an EOS by up to K-1 tokens —
        the driver discards them, and the overrun writes stay inside the
        slot's owned pages because K never exceeds its remaining capacity.

        ``tokens_per_step`` is the per-step token footprint: 1 for plain
        decode, K_draft+1 for a speculative window (every window may append
        up to the full present, and the max-new budget must cover a fully
        accepted window — the speculative driver commits at most
        ``remaining`` tokens by the same overrun-discard rule)."""
        if queue or not self.running:
            return 0
        k = 1 << 30
        for slot, state in self.running.items():
            if state.phase != DECODING or self.cache.needs_cow(slot):
                return 0
            if state.group is not None and state.group.mode == "beam":
                # beam steps interleave host-side candidate selection and
                # block-table reorders between decodes — never fusable
                return 0
            capacity = self.cache.capacity_tokens(slot)
            remaining = state.request.max_new_tokens - len(state.generated)
            k = min(k, capacity // tokens_per_step,
                    max(remaining, 0) // tokens_per_step)
        return max(k, 0)

    def finish(self, slot: int) -> RequestState:
        state = self.running.pop(slot)
        self.cache.free_slot(slot)
        state.release()
        return state
