"""Which architecture a configuration is: one module per architecture.

A configuration file names its architecture under ``"arch"``: the name of a
module in this directory. Without the key it is ``dense_gqa``. Each module
imports nothing of the program and provides:

- ``shapes(m)``: the weight tree that ``weights.make_init`` draws, leaf
  name -> (shape, dtype, init std or 'norm'/'bias'), nested like the
  program's parameter tree;
- ``served_rows(params, m, eps, prompt, served, *, fp8=False, lookups=())``:
  the plain float32 reference, teacher-forced over a prompt and its served
  tokens, and with ``fp8`` its control (``check.py`` reads both);
- ``layers(m)``: one ``peaks.Layer`` per layer, the work a token does there,
  which the metric readers price.

A new architecture is a new module file here, and its configurations name it.
"""
from __future__ import annotations

import importlib
from pathlib import Path
from types import ModuleType
from typing import Dict, List

DEFAULT = "dense_gqa"


def names() -> List[str]:
    """The architectures there are: the module files in this directory."""
    return sorted(p.stem for p in Path(__file__).parent.glob("*.py")
                  if p.stem != "__init__")


def of(cfg: Dict) -> ModuleType:
    """The architecture module of configuration ``cfg``."""
    name = cfg.get("arch", DEFAULT)
    known = names()
    if name not in known:
        raise ValueError(f"configuration {cfg.get('name')!r} names the "
                         f"architecture {name!r}; known: {known}")
    return importlib.import_module(f"{__name__}.{name}")
