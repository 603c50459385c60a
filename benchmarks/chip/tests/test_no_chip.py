import json
import os
import subprocess
import sys
from pathlib import Path

RUN = Path(__file__).resolve().parents[1] / "run.py"


def test_a_run_without_a_tpu_exits_non_zero_and_prints_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(RUN), "--workload", "qwen2.5-3b.chat", "--seed",
         str(2**33 + 1), "--seconds", "5", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300, cwd=tmp_path)
    assert p.returncode != 0
    assert "TPU" in p.stderr
    for line in p.stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        raise AssertionError(f"printed a result: {line}")
