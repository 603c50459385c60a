"""The benchmark's weight tree has the layout, shapes and dtypes of the
program's ``Model.param_specs`` at the committed widths, so the engine serves
exactly the arrays that the reference reads."""
import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import engine_cell as ec, weights


def _specs(cfg):
    specs = ec.build_model(cfg).param_specs()
    return jax.tree.map(lambda s: (tuple(s.shape), jnp.dtype(s.dtype)), specs,
                        is_leaf=lambda x: hasattr(x, "shape"))


@pytest.mark.parametrize("tied", [True, False])
def test_weight_tree_matches_the_programs_param_specs(tied):
    cfg = ec.load_config("qwen2.5-3b")
    cfg["model"]["tie_embeddings"] = tied
    drawn = jax.eval_shape(weights.make_init(cfg["model"]), weights.weight_key(1))
    got = jax.tree.map(lambda a: (tuple(a.shape), jnp.dtype(a.dtype)), drawn)
    assert got == _specs(cfg)
    n = weights.param_count(cfg["model"])
    assert (3.0e9 < n < 3.2e9) if tied else n > 3.2e9
