"""Multi-device behaviours (shard_map EP MoE, elastic restart) exercised in
SUBPROCESSES with a forced 8-device CPU topology — the main test process keeps
the default single-device view (per the dry-run isolation rule)."""
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def run_script(code: str, devices: int = 8, timeout: int = 420):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(ROOT / "src")
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=timeout, env=env
    )
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    return r.stdout


def test_shard_map_moe_matches_einsum_path():
    out = run_script(
        """
import dataclasses, jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType
from repro.models import get_config
from repro.models.moe import moe_specs, apply_moe, apply_moe_ep
from repro.models.layers import Sharder
from repro.launch.sharding import train_rules
from repro.core.distributed import tree_initialize

cfg = dataclasses.replace(get_config("kimi-k2-1t-a32b", smoke=True), dtype="float32",
                          capacity_factor=8.0)  # no drops -> exact equality
mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
rules = train_rules(cfg)
p = tree_initialize(moe_specs(cfg), jax.random.key(0))
x = jax.random.normal(jax.random.key(1), (8, 16, cfg.d_model))
with mesh:
    y1, _ = jax.jit(lambda p, x: apply_moe(cfg, p, x, Sharder(None, None)))(p, x)
    y2, _ = jax.jit(lambda p, x: apply_moe_ep(cfg, p, x, Sharder(mesh, rules)))(p, x)
    g1 = jax.jit(jax.grad(lambda p, x: apply_moe(cfg, p, x, Sharder(None, None))[0].sum()))(p, x)
    g2 = jax.jit(jax.grad(lambda p, x: apply_moe_ep(cfg, p, x, Sharder(mesh, rules))[0].sum()))(p, x)
np.testing.assert_allclose(np.array(y2), np.array(y1), rtol=2e-4, atol=2e-4)
for k in g1:
    np.testing.assert_allclose(np.array(g2[k]), np.array(g1[k]), rtol=5e-3, atol=5e-3)
print("EP-OK")
"""
    )
    assert "EP-OK" in out


def test_elastic_restart_after_device_loss():
    out = run_script(
        """
import tempfile
from repro.runtime import RunConfig, TrainerLoop, simulate_failure
with tempfile.TemporaryDirectory() as d:
    run = RunConfig(arch="llama3.2-1b", smoke=True, steps=10, batch=8, seq=16,
                    ckpt_dir=d, ckpt_every=2, log_every=100)
    fail = simulate_failure(at_step=5)
    loop = TrainerLoop(run, failure_hook=fail.maybe_fail)
    n0 = len(loop.devices)
    out = loop.run_loop()
    assert len(loop.devices) < n0, "must re-mesh onto fewer devices"
    assert out["final_step"] == 10
    assert any(h["step"] == 9 for h in out["history"])
print("ELASTIC-OK")
"""
    )
    assert "ELASTIC-OK" in out


def test_sharded_train_step_matches_single_device():
    """DP+TP sharded train step computes the same loss as unsharded (exactness of
    the distribution layer, modulo bf16 reduction order)."""
    out = run_script(
        """
import dataclasses, jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType
from repro.models import get_config, build_model
from repro.models.layers import Sharder
from repro.launch.sharding import train_rules
from repro.optim import AdamWConfig
from repro.train import make_train_step
from repro.core.distributed import tree_initialize, tree_shardings

cfg = dataclasses.replace(get_config("llama3.2-1b", smoke=True), dtype="float32")
model = build_model(cfg)
batch = {"tokens": jax.random.randint(jax.random.key(2), (8, 17), 0, cfg.vocab)}

losses = {}
for shard_it in (False, True):
    mesh = (jax.make_mesh((4, 2), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
            if shard_it else None)
    rules = train_rules(cfg) if shard_it else None
    step, ps, ss = make_train_step(model, AdamWConfig(lr=1e-3), mesh=mesh, rules=rules)
    params = tree_initialize(ps, jax.random.key(0))
    opt = tree_initialize(ss, jax.random.key(1))
    if shard_it:
        params = jax.device_put(params, tree_shardings(ps, mesh, rules))
        opt = jax.device_put(opt, tree_shardings(ss, mesh, rules))
        with mesh:
            _, _, m = jax.jit(step)(params, opt, batch)
    else:
        _, _, m = jax.jit(step)(params, opt, batch)
    losses[shard_it] = float(m["loss"])
assert abs(losses[True] - losses[False]) < 1e-3, losses
print("SHARD-OK", losses)
"""
    )
    assert "SHARD-OK" in out
