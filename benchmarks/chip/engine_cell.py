"""The system under test: the program's ``ServeEngine`` built from a
configuration file. This is the only module of the benchmark that imports the
program (``repro``, under ``src/``)."""
from __future__ import annotations

import json
import sys
from pathlib import Path
from typing import Dict

import jax

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

from repro.models.config import ModelConfig  # noqa: E402
from repro.models.transformer import Model  # noqa: E402
from repro.serving import GenerationParams  # noqa: E402
from repro.serving.engine import EngineConfig, Request, ServeEngine  # noqa: E402


def load_config(name: str) -> Dict:
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def build_model(cfg: Dict) -> Model:
    return Model(ModelConfig(**cfg["model"]))


def engine_config(cfg: Dict) -> EngineConfig:
    """The file's sizing; every other field at the program's default except
    chunked prefill (one compile per chunk bucket, not per prompt length)."""
    e = cfg["engine"]
    return EngineConfig(
        num_pages=e["num_pages"], page_size=e["page_size"],
        max_batch=e["max_batch"], max_pages_per_seq=e["max_pages_per_seq"],
        chunked_prefill=True,
    )


def pool_shapes(model: Model, num_pages: int, page_size: int):
    """The KV pools' shapes and dtypes, as the engine allocates them."""
    return jax.eval_shape(lambda: model.init_paged_cache(num_pages, page_size))


def pool_bytes(model: Model, num_pages: int, page_size: int) -> int:
    return sum(a.size * a.dtype.itemsize
               for a in jax.tree.leaves(pool_shapes(model, num_pages, page_size)))


def build_engine(model: Model, params, econf: EngineConfig) -> ServeEngine:
    return ServeEngine(model, params, econf)


def make_requests(items, rid0: int = 0):
    """Greedy requests with no end token, so each yields exactly its length.
    ``items``: (prompt ids, new tokens, arrival seconds)."""
    return [
        Request(rid0 + i, list(p), GenerationParams(max_new_tokens=int(n)),
                arrival_time=float(t))
        for i, (p, n, t) in enumerate(items)
    ]
