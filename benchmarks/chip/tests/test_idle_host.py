"""``device_idle_host.steady``: the device's idle between ops put down to what
the serving loop's host thread did, on intervals by hand and on a trace
recorded on the chip (a chat run, its ``serve.*`` host spans kept beside the
device's ops)."""
import importlib.util
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from benchmarks.chip import trace

HERE = Path(__file__).resolve().parent
FIXTURE = HERE / "fixtures" / "qwen_serve_spans.json.gz"


def metric(name):
    spec = importlib.util.spec_from_file_location(
        name, HERE.parent / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


IDLE_HOST = metric("device_idle_host.steady")


def ev(name, start, end):
    return NS(name=name, start_ns=start, duration_ns=end - start)


def test_gaps_go_to_the_innermost_span():
    dev = NS(name="/device:TPU:0", lines=[NS(name=trace.DEVICE_OPS_LINE, events=[
        ev("a", 0, 10), ev("b", 20, 30), ev("c", 50, 60), ev("d", 100, 110)])])
    host = NS(name="/host:CPU", lines=[NS(name="python3", events=[
        ev("serve.tick", 5, 40), ev("serve.fetch", 5, 25),
        ev("serve.commit", 26, 28), ev("serve.tick", 45, 95),
        ev("serve.gc", 70, 80), ev("$engine.py:1 _tick", 0, 200)])])
    # gaps 10-20 (fetch), 30-50 (tick 30-40, none 40-45, tick 45-50),
    # 60-100 (tick, gc 70-80, tick to 95, none 95-100)
    assert IDLE_HOST.idle_parts([dev, host]) == {
        "host": 50.0, "fetch": 10.0, "no_tick": 10.0,
        "by_span": {"serve.tick": 40.0, "serve.gc": 10.0}}
    assert IDLE_HOST.idle_parts([dev]) is None  # a program without the spans


def test_a_trace_without_serving_spans_reads_none():
    assert IDLE_HOST.idle_parts(trace.load_planes(HERE / "fixtures" / "qwen_steps.json.gz")) is None
    assert IDLE_HOST.read(NS(trace=None)) is None


def test_host_idle_is_a_part_of_the_device_idle_on_a_chip_trace():
    planes = trace.load_planes(FIXTURE)
    ops = [e for p in planes if p.name.startswith("/device:TPU") for line in p.lines
           if line.name == trace.DEVICE_OPS_LINE for e in line.events]
    # the cut's window: its first op's start to its last op's end, so the
    # inter-op idle is all of the idle
    window_s = (max(e.start_ns + e.duration_ns for e in ops)
                - min(e.start_ns for e in ops)) * 1e-9
    idle = metric("device_idle.steady").read(
        NS(trace=trace.reduce(FIXTURE, window_s=window_s)))
    pct = IDLE_HOST.shares(IDLE_HOST.idle_parts(planes), window_s)
    assert 0 < pct["host"] <= idle
    assert sum(pct[p] for p in IDLE_HOST.PARTS) == pytest.approx(idle, abs=0.1)
    assert sum(pct["by_span"].values()) == pytest.approx(pct["host"])
    assert set(pct["by_span"]) <= {"serve.tick", "serve.admit", "serve.chunk",
                                   "serve.sync", "serve.decode", "serve.dispatch",
                                   "serve.commit", "serve.gc"}
