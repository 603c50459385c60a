"""The device-trace readers that price work, ``mfu.steady`` and
``prefill_attn_roofline``, on a trace recorded on the chip (a few decode and
prefill-chunk steps of the Qwen configuration) and a fixed set of positions,
for each committed configuration: they read what the readers of one dense
layer times ``n_layers`` read before the counts went per layer.

``BEFORE`` was computed with ``metrics/`` and ``peaks.py`` of commit 539e1b3,
whose counts took the model keys alone, on this same run: the fixture reduced
with ``window_s=4.0``, the chunk kernel's output renamed to the
configuration's KV-head count (the fixture holds Qwen's), ``TRACED``, and the
device kind "TPU v5 lite"."""
import importlib.util
from pathlib import Path

import pytest

from benchmarks.chip import arch, trace
from benchmarks.chip import engine_cell as ec
from benchmarks.chip.window import Run

HERE = Path(__file__).resolve().parent
FIXTURE = HERE / "fixtures" / "qwen_steps.json.gz"
# (prompt length, first, end): one 256-token prefill chunk, and 64 rows
# decoding three positions each
TRACED = [(1000, 0, 256)] + [(100 + 37 * i, 105 + 37 * i, 108 + 37 * i)
                             for i in range(64)]
BEFORE = {  # config: (mfu.steady, prefill_attn_roofline)
    "qwen2.5-3b": (11.00820743802113, 14.838362953400871),
    "granite-8b": (14.6170337841536, 16.48706994822319),
}


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name, HERE.parent / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("config", sorted(BEFORE))
def test_the_readers_read_what_they_read_before_the_per_layer_counts(config):
    cfg = ec.load_config(config)
    t = trace.reduce(FIXTURE, window_s=4.0)
    hkv = cfg["model"]["n_kv_heads"]
    t["ops"] = {k.replace(" = bf16[1,2,", f" = bf16[1,{hkv},"): v
                for k, v in t["ops"].items()}
    run = Run(cell={"name": config}, model=cfg["model"], engine=cfg["engine"],
              layers=arch.of(cfg).layers(cfg["model"]),
              device_kind="TPU v5 lite", seconds=51.0, setup_s=0.0, records=[],
              engine_metrics={}, trace=t, traced=TRACED)
    mfu, roof = BEFORE[config]
    assert reader("mfu.steady")(run) == pytest.approx(mfu, rel=1e-12)
    assert reader("prefill_attn_roofline")(run) == pytest.approx(roof, rel=1e-12)
