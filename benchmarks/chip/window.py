"""What a run measured, handed to the metric readers (``metrics/*.py``).

``Record`` is one window request as served; ``Snapshot`` is the engine's
progress on every request at one instant, taken at the two ends of the traced
sub-window; ``Run`` holds everything a reader may look at.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class Record:
    rid: int
    prompt_len: int
    new_tokens: int  # asked for
    arrival_s: float
    first_token_s: Optional[float]
    finish_s: Optional[float]
    generated: Tuple[int, ...]
    error: Optional[str]

    @property
    def ok(self) -> bool:
        return (self.error is None and self.first_token_s is not None
                and len(self.generated) == self.new_tokens)


@dataclasses.dataclass(frozen=True)
class Progress:
    prompt_len: int
    computed: Optional[int]  # positions whose KV is computed; None if queued
    skip: int  # leading positions adopted from the prefix cache


Snapshot = Dict[int, Progress]


def positions_between(a: Snapshot, b: Snapshot) -> List[Tuple[int, int, int]]:
    """(prompt_len, first, end): the positions each request processed between
    two snapshots. A request queued at ``a`` starts at its adopted prefix."""
    out = []
    for rid, pb in b.items():
        if pb.computed is None:
            continue
        pa = a.get(rid)
        start = pa.computed if pa is not None and pa.computed is not None else pb.skip
        if pb.computed > start:
            out.append((pb.prompt_len, start, pb.computed))
    return out


@dataclasses.dataclass
class Run:
    cell: Dict
    model: Dict  # the configuration's model keys
    engine: Dict  # the configuration's engine sizing
    device_kind: str
    seconds: float
    setup_s: float
    records: List[Record]
    engine_metrics: Dict[str, float]
    trace: Optional[Dict] = None  # trace.reduce() of the traced sub-window
    traced: Optional[List[Tuple[int, int, int]]] = None  # positions in it
