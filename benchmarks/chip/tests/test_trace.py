"""The trace reduction, on a trace recorded on the chip (a few decode and
prefill-chunk steps of the Qwen configuration) and on intervals by hand."""
from pathlib import Path

import importlib.util
from types import SimpleNamespace

from benchmarks.chip import trace
from benchmarks.chip.window import Progress, positions_between

HERE = Path(__file__).resolve().parent
FIXTURE = HERE / "fixtures" / "qwen_steps.json.gz"


# the recorded configuration: Qwen2.5-3B at batch 64 and 128-token pages
RUN = SimpleNamespace(model={"n_heads": 16, "n_kv_heads": 2, "d_head": 128},
                      engine={"max_batch": 64, "page_size": 128})


def kernel_out(metric):
    spec = importlib.util.spec_from_file_location(
        metric, HERE.parent / "metrics" / f"{metric}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.kernel_out(RUN)


def test_union_merges_overlaps_and_keeps_gaps():
    assert trace._union([(5, 9), (0, 3), (2, 4), (9, 10), (12, 13)]) == [
        (0, 4), (5, 10), (12, 13)]


def test_positions_between_snapshots():
    a = {1: Progress(100, 64, 0), 2: Progress(50, 55, 0)}
    b = {1: Progress(100, 100, 0), 2: Progress(50, 60, 0),
         3: Progress(300, 288, 256), 4: Progress(10, None, 0)}
    assert sorted(positions_between(a, b)) == [
        (50, 55, 60), (100, 64, 100), (300, 256, 288)]


def test_reduce_a_recorded_chip_trace():
    r = trace.reduce(FIXTURE, window_s=10.0)
    assert 0 < r["busy_s"] < 10.0
    assert len(r["top_ops"]) <= trace.TOP and len(r["idle_gaps"]) <= trace.TOP
    secs = [s for _, s in r["top_ops"]]
    assert secs == sorted(secs, reverse=True) and secs[0] > 0
    assert sum(r["ops"].values()) >= r["busy_s"] * 0.999
    # the chunk kernel, by its reader's match, and the decode kernel, by its
    # output (max_batch, Hkv, Hq / Hkv, head_dim): told apart by shape
    assert trace.kernel_seconds(r, kernel_out("prefill_attn_roofline")) > 0
    assert trace.kernel_seconds(r, "bf16[64,2,8,128]") > 0
    # the layer scans' while loops hold the other ops: not among the top
    assert not any(n.startswith("%while") for n, _ in r["top_ops"])
    assert all(s > 0 and isinstance(n, str) for n, s in r["idle_gaps"])
    assert any("engine.py" in n for n, _ in r["idle_gaps"])
