"""Requests, branch groups, and the FIFO admission queue for the engine.

A Request names WHAT to generate from (rid + prompt + arrival time); its
GenerationParams (serving/params.py) names HOW. Requests whose params ask for
parallel generation (n > 1 or beam_width > 0) expand into a BranchGroup of
RequestStates — one per branch — that the scheduler admits and preempts as a
UNIT and whose block-table rows fork one prompt's pages (cache.fork_slot).

Back-compat: the pre-redesign kwargs (``max_new_tokens=``, ``eos_id=``,
``sampling=``, ``logprobs=``) still construct a Request through a shim that
builds the equivalent GenerationParams and emits a DeprecationWarning; the
read-side properties (``request.max_new_tokens`` etc.) remain as plain
delegations and are not deprecated.
"""
from __future__ import annotations

import dataclasses
import warnings
from collections import deque
from typing import Deque, Dict, List, Optional, Sequence as Seq, Tuple

from repro.serving.params import (
    FINISH_EOS,
    FINISH_LENGTH,
    GenerationParams,
    Sequence,
)
from repro.serving.sampling import SamplingParams


def page_hash_chain(tokens: Seq[int], page_size: int) -> List[Tuple]:
    """Chain hashes of page-granular token chunks — the prefix-sharing keys.

    Entry ``i`` identifies the CONTENT of logical page ``i`` given everything
    before it: chaining makes equal keys imply equal full token prefixes, so two
    requests whose chains agree on a leading run can alias those physical pages.
    Full pages hash their page_size chunk; a trailing partial chunk (if any)
    gets a final entry keyed by its exact tokens — two identical prompts share
    even their last, partially filled page (copy-on-write resolves the first
    divergent append). Keys are tuples (not raw ints) so accidental collision
    with user data is impossible; the index lives in-process only.
    """
    chain: List[Tuple] = []
    h: Tuple = ("kv-prefix", page_size)
    n_full = len(tokens) // page_size
    for i in range(n_full):
        h = (hash(h), tuple(int(t) for t in tokens[i * page_size : (i + 1) * page_size]))
        chain.append(h)
    rem = tokens[n_full * page_size :]
    if rem:
        chain.append((hash(h), tuple(int(t) for t in rem), "partial"))
    return chain


_LEGACY_SENTINEL = object()


class Request:
    """One generation request as submitted by a client: identity (rid), prompt,
    arrival time, and a GenerationParams policy record."""

    def __init__(self, rid: int, prompt: Seq[int],
                 params: Optional[GenerationParams] = None, *,
                 arrival_time: float = 0.0,
                 max_new_tokens=_LEGACY_SENTINEL, eos_id=_LEGACY_SENTINEL,
                 sampling=_LEGACY_SENTINEL, logprobs=_LEGACY_SENTINEL):
        legacy = {
            k: v for k, v in (
                ("max_new_tokens", max_new_tokens), ("eos_id", eos_id),
                ("sampling", sampling), ("logprobs", logprobs),
            ) if v is not _LEGACY_SENTINEL
        }
        if isinstance(params, int):
            # pre-redesign positional call: Request(rid, prompt, max_new_tokens)
            legacy.setdefault("max_new_tokens", params)
            params = None
        if legacy:
            if params is not None:
                raise ValueError(
                    "pass either params=GenerationParams(...) or the legacy "
                    f"kwargs {sorted(legacy)}, not both"
                )
            warnings.warn(
                f"Request kwargs {sorted(legacy)} are deprecated — pass "
                "params=GenerationParams(...) instead",
                DeprecationWarning, stacklevel=2,
            )
            params = GenerationParams.from_legacy(**legacy)
        self.rid = int(rid)
        self.prompt = [int(t) for t in prompt]
        self.params = params if params is not None else GenerationParams()
        self.arrival_time = float(arrival_time)
        if not self.prompt:
            raise ValueError("empty prompt")

    # plain delegations — the read surface the engine/scheduler/tests use
    @property
    def max_new_tokens(self) -> int:
        return self.params.max_new_tokens

    @property
    def eos_id(self) -> Optional[int]:
        return self.params.eos_id

    @property
    def sampling(self) -> SamplingParams:
        return self.params.sampling

    @property
    def logprobs(self) -> int:
        return self.params.logprobs

    def __repr__(self):
        return (
            f"Request(rid={self.rid}, prompt=<{len(self.prompt)} tokens>, "
            f"params={self.params})"
        )


# RequestState.phase values — the mixed-step lifecycle. QUEUED -> PREFILLING
# (admitted, context KV materializing chunk by chunk — or, for a branch-group
# sibling, awaiting the fork of its primary's pages) -> DECODING (context
# resident, one token per step). The monolithic engine only observes
# PREFILLING on awaiting siblings: it admits and fully prefills in one step.
QUEUED = "queued"
PREFILLING = "prefilling"
DECODING = "decoding"


@dataclasses.dataclass
class RequestState:
    """Engine-side lifecycle of one BRANCH of a request (survives preemption).
    A plain n=1 request is a single branch with no group."""

    request: Request
    generated: List[int] = dataclasses.field(default_factory=list)
    # generated-token index -> [(token_id, logprob), ...] of the top
    # request.logprobs candidates at that position (empty unless requested).
    # Keyed like logits_of — by token index, not step — so preemption-recompute
    # overwrites deterministically.
    logprobs: Dict[int, List[Tuple[int, float]]] = dataclasses.field(
        default_factory=dict
    )
    slot: Optional[int] = None  # batch slot while running, None while queued
    # chunked prefill: tokens of context whose KV is computed AND resident for
    # the current residency (page-aligned except at completion); None once the
    # prefill completes (or always, in the monolithic engine). Reset by
    # release(): preemption is recompute-style, the cursor does not survive.
    chunk_cursor: Optional[int] = None
    admit_time: Optional[float] = None  # first admission; re-admissions keep it
    first_token_time: Optional[float] = None
    finish_time: Optional[float] = None
    n_preemptions: int = 0
    error: Optional[str] = None  # set when the engine fails the request
    # parallel generation: which branch of which group this state is (branch 0
    # of a group is the PRIMARY — it prefills the prompt; siblings fork its
    # pages). None/0 for ordinary single-branch requests.
    group: Optional["BranchGroup"] = None
    branch: int = 0
    # True while a fresh sibling waits (slot bound, no pages) for its primary's
    # prefill to complete so it can fork the prompt pages — masked out of both
    # the chunk scheduler and the batched decode meanwhile
    await_fork: bool = False
    # beam search: True while this branch's top candidates sit in the group's
    # pending_rows awaiting the JOINT selection (re-admitted branches finish
    # their recompute prefills on different steps under chunked prefill) —
    # masked out of decode like await_fork, but with pages resident
    hold: bool = False
    # why generation stopped: "eos" | "length" | "error" (params.FINISH_*);
    # None while running. Replaces the old implicit hit-max-tokens inference.
    finish_reason: Optional[str] = None
    # sum of log P(token | prefix) over generated tokens (the per-branch score
    # best-of-n ranks by; beam search maintains it through its own candidates)
    cum_logprob: float = 0.0
    # constrained decoding: the branch's GLOBAL grammar-state id inside the
    # engine's stacked mask/transition tables (None = unconstrained). The host
    # mirror of the device-resident per-slot state vector.
    grammar_state: Optional[int] = None
    # memoized prefix-sharing keys: (page_size, len(context)) -> chain. The
    # context is append-only per request, so its length identifies its content
    # and a queued request re-checked every engine step hashes only once.
    _chain_key: Optional[Tuple[int, int]] = dataclasses.field(
        default=None, repr=False, compare=False
    )
    _chain: List[Tuple] = dataclasses.field(
        default_factory=list, repr=False, compare=False
    )

    def hash_chain(self, page_size: int) -> List[Tuple]:
        """Prefix-sharing keys for the context as it would be (re-)prefilled
        now; recomputed only when the context has grown (admission retries while
        queued are O(1))."""
        key = (page_size, len(self.context))
        if self._chain_key != key:
            self._chain_key = key
            self._chain = page_hash_chain(self.context, page_size)
        return self._chain

    @property
    def context(self) -> List[int]:
        """Tokens that must be in the KV cache: prompt + everything generated.
        After preemption this whole sequence is re-prefilled (recompute policy)."""
        return self.request.prompt + self.generated

    @property
    def sampling(self) -> SamplingParams:
        """The branch's EFFECTIVE sampling policy: branch b draws from the
        stream of seed + b, so a branch is token-exact with a serial n=1
        request submitted with that seed (and the same rid)."""
        sp = self.request.sampling
        if self.branch:
            sp = dataclasses.replace(sp, seed=sp.seed + self.branch)
        return sp

    @property
    def phase(self) -> str:
        """QUEUED / PREFILLING / DECODING — where the mixed step routes this
        request: a PREFILLING slot receives prefill chunks (or, awaiting a
        group fork, nothing) and is masked out of the batched decode; a
        DECODING slot appends one token per step."""
        if self.slot is None:
            return QUEUED
        return (
            PREFILLING
            if (self.chunk_cursor is not None or self.await_fork or self.hold)
            else DECODING
        )

    def release(self) -> None:
        """Drop residency state on preemption: the slot binding and the chunk
        cursor (recompute policy — a re-admitted request restarts its prefill,
        re-adopting whatever prefix pages survived). A fresh sibling goes back
        to awaiting its fork; a started one re-prefills its own context."""
        self.slot = None
        self.chunk_cursor = None
        self.hold = False
        self.await_fork = self.group is not None and self.branch > 0 and not self.generated

    @property
    def done(self) -> bool:
        if self.finish_reason is not None:
            return True
        if len(self.generated) >= self.request.max_new_tokens:
            return True
        eos = self.request.eos_id
        return eos is not None and bool(self.generated) and self.generated[-1] == eos

    def finished_reason(self) -> str:
        """The reason ``done`` holds (records it if not yet stamped)."""
        if self.finish_reason is None:
            eos = self.request.eos_id
            self.finish_reason = (
                FINISH_EOS if eos is not None and self.generated
                and self.generated[-1] == eos else FINISH_LENGTH
            )
        return self.finish_reason

    def own_sequence(self) -> Sequence:
        return Sequence(
            tokens=list(self.generated),
            logprobs=dict(self.logprobs),
            cumulative_logprob=self.cum_logprob,
            finish_reason=self.finish_reason,
        )

    @property
    def sequences(self) -> List[Sequence]:
        """The request's per-branch results — a one-element list for plain
        n=1 requests, the group's branches (or surviving beam hypotheses)
        otherwise. This is the ONE results surface; the engine's results dict
        maps rid -> the primary state, and everything per-branch lives here."""
        if self.group is not None:
            return self.group.sequences()
        return [self.own_sequence()]


class BranchGroup:
    """N branches of one request, admitted/preempted as a unit and aliasing one
    prompt's pages. mode "sample" (best-of-n: branches decode independently on
    forked streams) or "beam" (joint per-step candidate selection + block-table
    row reorder)."""

    def __init__(self, request: Request):
        self.request = request
        self.mode = "beam" if request.params.beam_width else "sample"
        n = request.params.n_branches
        self.branches: List[RequestState] = [
            RequestState(request, group=self, branch=b, await_fork=b > 0)
            for b in range(n)
        ]
        # beam search: hypotheses that reached eos (moved out of the live
        # branches), as finished Sequence records ranked by cumulative_logprob
        self.finished: List[Sequence] = []
        # beam re-admission: per-branch top-k candidate rows collected while
        # the group's branches finish their recompute prefills; the beam step
        # resumes once every live branch has reported
        self.pending_rows: Dict[int, Tuple] = {}

    @property
    def primary(self) -> RequestState:
        return self.branches[0]

    @property
    def n_branches(self) -> int:
        return len(self.branches)

    @property
    def all_done(self) -> bool:
        return all(st.done for st in self.branches)

    def sequences(self) -> List[Sequence]:
        if self.mode == "beam":
            ranked = sorted(
                self.finished, key=lambda s: -s.cumulative_logprob
            )
            return ranked[: self.request.params.n]
        return [st.own_sequence() for st in self.branches]


class RequestQueue:
    """FIFO with front-requeue for preempted requests."""

    def __init__(self):
        self._q: Deque[RequestState] = deque()

    def push(self, state: RequestState) -> None:
        self._q.append(state)

    def requeue_front(self, state: RequestState) -> None:
        self._q.appendleft(state)

    def peek(self) -> Optional[RequestState]:
        return self._q[0] if self._q else None

    def pop(self) -> RequestState:
        return self._q.popleft()

    def __len__(self) -> int:
        return len(self._q)

    def __bool__(self) -> bool:
        return bool(self._q)
