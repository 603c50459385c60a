"""The benchmark's weight tree has the layout, shapes and dtypes of the
program's ``Model.param_specs`` at the committed widths, for every committed
configuration through its architecture module, so the engine serves exactly
the arrays that the reference reads."""
import hashlib
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import arch, weights
from benchmarks.chip import engine_cell as ec

CONFIGS = sorted(p.stem for p in (Path(ec.HERE) / "configs").glob("*.json"))
# parameters without the vocabulary's pad rows, as committed: Qwen2.5-3B
# whole (its card: 3.09 B), Granite-8B's first 18 of 36 layers
COUNT = {"qwen2.5-3b": 3_085_938_688, "granite-8b": 4_328_673_280}
# sha256 of the tiny widths' weights drawn from seed 2**33 + 3, leaf by leaf,
# as the committed cells have drawn them since the benchmark began
TINY = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_head=16,
            d_ff=128, vocab=512)
DRAW = {
    "qwen2.5-3b": "25e5095251214641e12eca952c235aa9fffb9ec70f18478184eef720dc210c16",
    "granite-8b": "1f3c685219e40467b843ecdd1cff228d978a72bbbf2fcde6493051e5b10dae20",
}


def _specs(cfg):
    specs = ec.build_model(cfg).param_specs()
    return jax.tree.map(lambda s: (tuple(s.shape), jnp.dtype(s.dtype)), specs,
                        is_leaf=lambda x: hasattr(x, "shape"))


def test_every_architecture_has_a_committed_configuration():
    used = {arch.of(ec.load_config(c)).__name__ for c in CONFIGS}
    assert used == {f"benchmarks.chip.arch.{n}" for n in arch.names()}


@pytest.mark.parametrize("tied", [True, False])
@pytest.mark.parametrize("config", CONFIGS)
def test_weight_tree_matches_the_programs_param_specs(config, tied):
    cfg = ec.load_config(config)
    committed = cfg["model"]["tie_embeddings"]
    cfg["model"]["tie_embeddings"] = tied
    tree = arch.of(cfg).shapes(cfg["model"])
    drawn = jax.eval_shape(weights.make_init(tree), weights.weight_key(1))
    got = jax.tree.map(lambda a: (tuple(a.shape), jnp.dtype(a.dtype)), drawn)
    assert got == _specs(cfg)
    head = cfg["model"]["vocab"] * cfg["model"]["d_model"]
    n = weights.param_count(cfg["model"], tree)
    assert n == COUNT[config] + (committed - tied) * head


@pytest.mark.parametrize("config", sorted(DRAW))
def test_a_seed_draws_the_weights_it_always_drew(config):
    cfg = ec.load_config(config)
    cfg["model"].update(TINY)
    params = weights.make_init(arch.of(cfg).shapes(cfg["model"]))(
        weights.weight_key(2**33 + 3))
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(params):
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == DRAW[config]
