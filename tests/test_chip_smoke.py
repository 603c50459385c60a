"""chip_smoke.py's phases, run on the CPU at the smoke config.

The kernels run in the Pallas interpreter here, so this guards the script's
control flow and its checks, not the chip: only ``python chip_smoke.py`` on a
TPU is a chip run.
"""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

import pytest

_PATH = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # dataclasses look their module up here
    spec.loader.exec_module(mod)
    return mod


def test_phases_pass_at_smoke_size(chip_smoke, capsys):
    cs = chip_smoke
    model, params = cs.build(smoke=True, seed=0)
    sizes = cs.Sizes(requests=3, prompt_lens=(9, 20), new_tokens=4, page_size=4,
                     max_batch=2, kernel_ctx=24, chunk=8, verify=4)
    checks = cs.run_checks(model, params, sizes, seed=0, on_chip=False,
                           clock=cs.CompileClock())
    out = capsys.readouterr().out
    for phase in "abcde":
        assert f"[{phase}: " in out
    assert [c.name for c in checks if not c.ok] == []
    names = " ".join(c.name for c in checks)
    for rep in ("bf16", "int8", "int4"):
        for kernel in ("decode", "chunk C=8", "verify C=4"):
            assert f"{rep} {kernel}" in names
    # the engine compared with its unpaged reference, and the paths with (a)
    assert "Model.forward" in names and "jnp - logit Pallas" in names
    assert "int8 - logit bf16" in names


def test_main_refuses_a_non_tpu_device(chip_smoke, capsys):
    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert "'cpu'" in err
    assert '"ok"' not in out  # no result line


@pytest.mark.parametrize("value, ok", [(0.5, True), (1.0, True), (1.5, False),
                                       (float("nan"), False),
                                       (float("inf"), False)])
def test_check_holds_value_to_bound_and_fails_nonfinite(chip_smoke, value, ok):
    check = chip_smoke.at_most("err", value, 1.0)
    assert check.ok is ok
    assert check.line().lstrip().startswith("ok" if ok else "FAIL")
