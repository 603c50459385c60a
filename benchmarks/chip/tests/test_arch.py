"""Which architecture a configuration is: the registry of ``arch/``."""
import pytest

from benchmarks.chip import arch, reference
from benchmarks.chip import engine_cell as ec
from benchmarks.chip.arch import dense_gqa


def test_a_configuration_without_an_arch_key_is_dense_gqa():
    for name in ("qwen2.5-3b", "granite-8b"):
        cfg = ec.load_config(name)
        assert "arch" not in cfg
        assert arch.of(cfg) is dense_gqa
    assert dense_gqa.served_rows is reference.served_rows
    assert arch.of({"arch": "dense_gqa"}) is dense_gqa


def test_an_unknown_arch_is_an_error_that_names_the_known_ones():
    with pytest.raises(ValueError, match="'no_such_arch'.*known: .*dense_gqa"):
        arch.of({"name": "x", "arch": "no_such_arch"})
    assert "dense_gqa" in arch.names() and "__init__" not in arch.names()
