"""Cut a recorded chip trace down to a test fixture.

    python benchmarks/chip/cut_trace.py <trace.xplane.pb> <out.json.gz> \
        --at <s> --for <s> --source "<what was recorded>"

Keeps the ``--for`` seconds that start ``--at`` seconds after the first
device op: every device's ops (``trace.DEVICE_OPS_LINE``, under their short
names) and XLA modules, and the host threads' ``serve.*`` spans that overlap
the cut. Writes the planes, lines and events (name, start_ns, duration_ns)
as gzipped JSON, the form ``trace.load_planes`` reads.
"""
from __future__ import annotations

import argparse
import gzip
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks.chip import trace  # noqa: E402

KEEP_DEVICE_LINES = (trace.DEVICE_OPS_LINE, "XLA Modules")


def cut(planes, at_s: float, for_s: float) -> list:
    devices = [p for p in planes if p.name.startswith("/device:TPU")]
    t0 = min(ev.start_ns for p in devices for line in p.lines
             if line.name == trace.DEVICE_OPS_LINE for ev in line.events)
    lo, hi = t0 + at_s * 1e9, t0 + (at_s + for_s) * 1e9

    def keep(events, rename=lambda n: n):
        return [[rename(ev.name), ev.start_ns, ev.duration_ns] for ev in events
                if ev.start_ns < hi and ev.start_ns + ev.duration_ns > lo]

    out = []
    for pl in devices:
        out.append({"name": pl.name, "lines": [
            {"name": line.name,
             "events": keep(line.events, trace.short_name
                            if line.name == trace.DEVICE_OPS_LINE else str)}
            for line in pl.lines if line.name in KEEP_DEVICE_LINES]})
    for pl in planes:
        if pl.name.startswith("/host"):
            lines = [{"name": line.name, "events": keep(
                ev for ev in line.events if ev.name.startswith("serve."))}
                for line in pl.lines]
            out.append({"name": pl.name,
                        "lines": [line for line in lines if line["events"]]})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("out")
    ap.add_argument("--at", type=float, required=True)
    ap.add_argument("--for", dest="for_s", type=float, required=True)
    ap.add_argument("--source", required=True)
    a = ap.parse_args(argv)
    planes = cut(trace.load_planes(a.trace), a.at, a.for_s)
    with gzip.open(a.out, "wt") as f:
        json.dump({"source": a.source, "planes": planes}, f)
    n = sum(len(line["events"]) for p in planes for line in p["lines"])
    print(f"{a.out}: {n} events", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
