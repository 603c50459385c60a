"""Share of the traced sub-window in which the device is idle while the
serving loop's host thread works: inside a ``serve.tick`` span but not inside
a ``serve.fetch`` (a blocking device-to-host copy), in %. The idle the host's
own work causes. Engine / scheduler layer.

The device's busy intervals, and the gaps between them, are built as
``trace.reduce`` builds them. The program's ``serve.*`` spans (profiler
annotations on the host thread, on the trace's clock) cut each gap, and each
piece goes to the innermost span over it. The two other parts of the
inter-op idle, inside ``serve.fetch`` (transfer and launch) and outside any
tick (no work), and the host part by innermost span go to stderr. None when
the run was not traced or its trace holds no ``serve.tick`` (a program
without the spans).
"""
import sys
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from benchmarks.chip import trace

TRACE_DIR = Path(__file__).resolve().parents[1] / "out" / "trace"
PARTS = ("host", "fetch", "no_tick")


def _host_segments(planes) -> List[Tuple[int, int, str, bool, bool]]:
    """The serving thread's nested ``serve.*`` spans cut into disjoint
    pieces: (start, end, innermost span, inside a tick, inside a fetch)."""
    spans = []
    for pl in planes:
        if not pl.name.startswith("/host"):
            continue
        for line in pl.lines:
            evs = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                   for ev in line.events if ev.name.startswith("serve.")]
            if any(name == "serve.tick" for _, _, name in evs):
                spans += evs
    # ends before starts at one instant; of two starts, the outer first
    marks = sorted([(s, 1, s - e, i) for i, (s, e, _) in enumerate(spans)]
                   + [(e, 0, 0, i) for i, (_, e, _) in enumerate(spans)])
    active: List[int] = []  # open spans in start order: the last is innermost
    out, prev = [], None
    for t, opens, _, i in marks:
        if active and t > prev:
            names = {spans[j][2] for j in active}
            out.append((prev, t, spans[active[-1]][2], "serve.tick" in names,
                        "serve.fetch" in names))
        if opens:
            active.append(i)
        else:
            active.remove(i)
        prev = t
    return out


def _device_gaps(planes) -> Tuple[List[Tuple[int, int]], int]:
    """Gaps between the union of device-op intervals, every device's, and
    the number of devices."""
    devices = [p for p in planes if p.name.startswith("/device:TPU")]
    gaps = []
    for pl in devices:
        iv = [(ev.start_ns, ev.start_ns + ev.duration_ns) for line in pl.lines
              if line.name == trace.DEVICE_OPS_LINE for ev in line.events]
        u = trace._union(iv)
        gaps += [(u[i][1], u[i + 1][0]) for i in range(len(u) - 1)]
    return sorted(gaps), max(len(devices), 1)


def idle_parts(planes) -> Optional[Dict]:
    """Inter-op device idle in ns, per device, by what the serving thread
    did: ``host`` (in a tick, not in a fetch), ``fetch``, ``no_tick``, and
    ``by_span`` (the host part by innermost span). None without a tick."""
    segs = _host_segments(planes)
    if not segs:
        return None
    gaps, n = _device_gaps(planes)
    parts = dict.fromkeys(PARTS, 0.0)
    by_span: Dict[str, float] = {}
    j = 0
    for s, e in gaps:
        while j < len(segs) and segs[j][1] <= s:
            j += 1
        covered, k = 0, j
        while k < len(segs) and segs[k][0] < e:
            a, b, name, in_tick, in_fetch = segs[k]
            over = min(b, e) - max(a, s)
            covered += over
            part = "fetch" if in_fetch else "host" if in_tick else "no_tick"
            parts[part] += over
            if part == "host":
                by_span[name] = by_span.get(name, 0.0) + over
            k += 1
        parts["no_tick"] += (e - s) - covered
    return {**{p: v / n for p, v in parts.items()},
            "by_span": {k: v / n for k, v in by_span.items()}}


def shares(parts: Dict, window_s: float) -> Dict:
    """The parts as % of the window."""
    pct = lambda ns: 100.0 * ns * 1e-9 / window_s
    return {**{p: pct(parts[p]) for p in PARTS},
            "by_span": {k: pct(v) for k, v in parts["by_span"].items()}}


def read(run):
    t = run.trace
    paths = list(TRACE_DIR.glob("plugins/profile/*/*.xplane.pb"))
    if not t or t["window_s"] <= 0 or not paths:
        return None
    parts = idle_parts(trace.load_planes(max(paths, key=lambda p: p.stat().st_mtime)))
    if parts is None:
        return None
    pct = shares(parts, t["window_s"])
    spans = ", ".join(f"{k} {v:.3f}" for k, v in
                      sorted(pct["by_span"].items(), key=lambda kv: -kv[1]))
    print(f"device idle between ops, % of the traced window: host {pct['host']:.3f}"
          f" ({spans}); in serve.fetch {pct['fetch']:.3f}; outside any tick "
          f"{pct['no_tick']:.3f}", file=sys.stderr)
    return pct["host"]
