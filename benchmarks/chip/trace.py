"""Profiler trace of a sub-window, and its reduction to device metrics.

``SubWindow`` starts the JAX profiler from a side thread some seconds into
the window and stops it a few seconds later, snapshotting the engine's
progress on every request at both ends (what the readers need to price the
work done in between). ``reduce`` turns the ``.xplane.pb`` into the busy
time (union of device-op intervals, averaged over the chips), device time per
operation name, the longest device ops and the longest idle gaps, each gap
named by the host activity that covers it.
"""
from __future__ import annotations

import gzip
import json
import re
import shutil
import threading
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Dict, List, Tuple

from jax import monitoring

from benchmarks.chip.window import Progress, Snapshot, positions_between

_LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILED = "/jax/core/compile/backend_compile_duration"
DEVICE_OPS_LINE = "XLA Ops"
TOP = 10
# ops that only hold others (a layer scan's while loop): their time is
# their children's, so they are left out of the top ops
CONTAINER = re.compile(r"^%(while|conditional)\.|[)}\]] (while|call|conditional)\(")


class CompileCounter:
    """Programs lowered and compiled by XLA since start-up (JAX monitoring)."""

    def __init__(self):
        self.n = {"lowered": 0, "compiled": 0}
        monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event: str, duration: float, **_):
        if event == _LOWERED:
            self.n["lowered"] += 1
        elif event == _COMPILED:
            self.n["compiled"] += 1

    def snapshot(self) -> Dict[str, int]:
        return dict(self.n)

    def since(self, before: Dict[str, int]) -> Dict[str, int]:
        return {k: self.n[k] - before[k] for k in self.n}


def progress(engine) -> Snapshot:
    """Each request's computed KV positions, read from the engine's state
    (lists and dicts copied whole, which the interpreter lock makes atomic)."""
    ps = engine.cache.page_size
    snap: Snapshot = {}
    for slot, st in list(engine.scheduler.running.items()):
        p, g = len(st.request.prompt), len(st.generated)
        cur = st.chunk_cursor
        skip = min(engine.cache.adopted_pages(slot) * ps, ((p - 1) // ps) * ps)
        snap[st.request.rid] = Progress(p, cur if cur is not None else p + g - 1,
                                        skip)
    for rid, st in list(engine.results.items()):
        p, g = len(st.request.prompt), len(st.generated)
        snap[rid] = Progress(p, p + g - 1 if g else None, 0)
    return snap


class SubWindow(threading.Thread):
    def __init__(self, engine, at_s: float, for_s: float, out_dir: Path):
        super().__init__(daemon=True)
        self.engine, self.at_s, self.for_s = engine, at_s, for_s
        self.out_dir = Path(out_dir)
        self.window_s = 0.0
        self.a: Snapshot = {}
        self.b: Snapshot = {}

    def run(self):
        import jax

        shutil.rmtree(self.out_dir, ignore_errors=True)
        time.sleep(self.at_s)
        jax.profiler.start_trace(str(self.out_dir))
        t0 = time.perf_counter()
        self.a = progress(self.engine)
        time.sleep(self.for_s)
        self.b = progress(self.engine)
        self.window_s = time.perf_counter() - t0
        jax.profiler.stop_trace()

    def path(self) -> Path:
        return max(self.out_dir.glob("plugins/profile/*/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)

    def positions(self) -> List[Tuple[int, int, int]]:
        return positions_between(self.a, self.b)


def _union(iv: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _host_spans(planes) -> List[Tuple[int, int, str]]:
    spans = []
    for pl in planes:
        if not pl.name.startswith("/host"):
            continue
        for line in pl.lines:
            for ev in line.events:
                spans.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
    return spans


LONGEST_SPAN_NS = 1e9
PROGRAM_FRAMES = re.compile(r"(engine|scheduler|cache|request|step)\.py:")


def _name_gap(spans, s: int, e: int) -> str:
    """The host span of the program's serving modules that overlaps the gap
    most (the shorter on a tie), else any host span that does."""
    best = {}
    for hs, he, name in spans:
        over = min(he, e) - max(hs, s)
        if over <= 0 or he - hs > LONGEST_SPAN_NS:
            continue  # no overlap, or the side thread's sleep through the window
        score = (over, hs - he)
        for key in ("any", "program") if PROGRAM_FRAMES.search(name) else ("any",):
            if key not in best or score > best[key][0]:
                best[key] = (score, name)
    pick = best.get("program") or best.get("any")
    return pick[1] if pick else "no host span"


def load_planes(path) -> list:
    """The planes of an ``.xplane.pb``, or of a ``.json.gz`` holding the same
    planes, lines and events (name, start_ns, duration_ns) as plain lists:
    the form the committed test fixture is cut to."""
    path = str(path)
    if path.endswith(".json.gz"):
        with gzip.open(path, "rt") as f:
            raw = json.load(f)["planes"]
        ev = lambda e: SimpleNamespace(name=e[0], start_ns=e[1], duration_ns=e[2])
        return [SimpleNamespace(name=p["name"], lines=[
            SimpleNamespace(name=l["name"], events=[ev(e) for e in l["events"]])
            for l in p["lines"]]) for p in raw]
    from jax.profiler import ProfileData

    return list(ProfileData.from_file(path).planes)


def reduce(path, *, window_s: float) -> Dict:
    """Busy seconds, per-op device seconds, top ops and idle gaps of a trace."""
    planes = load_planes(path)
    devices = [p for p in planes if p.name.startswith("/device:TPU")]
    ops: Dict[str, float] = {}
    busy, gaps = 0.0, []
    for pl in devices:
        iv = []
        for line in pl.lines:
            if line.name != DEVICE_OPS_LINE:
                continue
            for ev in line.events:
                iv.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                ops[ev.name] = ops.get(ev.name, 0.0) + ev.duration_ns * 1e-9
        u = _union(iv)
        busy += sum(e - s for s, e in u) * 1e-9
        gaps += [(u[i][1], u[i + 1][0]) for i in range(len(u) - 1)]
    n = max(len(devices), 1)
    spans = _host_spans(planes)
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    return {
        "busy_s": busy / n,
        "window_s": window_s,
        "ops": ops,
        "top_ops": sorted(([short_name(k), v] for k, v in ops.items()
                           if not CONTAINER.search(k)),
                          key=lambda kv: -kv[1])[:TOP],
        "idle_gaps": [[_name_gap(spans, s, e), (e - s) * 1e-9] for s, e in longest],
    }


def kernel_seconds(trace: Dict, out_shape: str) -> float:
    """Device seconds of the Mosaic kernel calls whose output is
    ``out_shape`` (the paged kernels carry no name of their own: each is an
    HLO ``custom-call`` to ``tpu_custom_call``, told apart by its output)."""
    head = f" = {out_shape}"
    return sum(v for k, v in trace["ops"].items()
               if head in k.split(" custom-call(")[0] and "tpu_custom_call" in k)


_OP = re.compile(r"^(%[\w.\-]+) = (\S+?)(?:\{[^ ]*\})? ([\w\-]+)\(")


def short_name(op: str) -> str:
    """``%name = shape opcode`` of an HLO op's text, without its layout."""
    m = _OP.match(op)
    return f"{m.group(1)} = {m.group(2)} {m.group(3)}" if m else op[:80]
