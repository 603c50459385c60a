"""Compile a configuration's decode step and prefill-chunk buckets for one
chip of a described TPU v5e, without the chip, and print what
``memory_analysis()`` gives: the rehearsal that sizes ``num_pages``.

    JAX_PLATFORMS=cpu python benchmarks/chip/compile_v5e.py --config qwen2.5-3b
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks.chip import engine_cell as ec  # noqa: E402
from benchmarks.chip import weights  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--page-size", type=int, default=0)
    a = ap.parse_args(argv)
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.kernels import paged_attention
    from repro.serving.step import make_chunked_prefill_step, make_paged_serve_step

    # the kernels pick the interpreter off a TPU backend; this compile is for
    # the chip, so they must lower to Mosaic
    paged_attention.use_interpret = lambda: False
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    cfg = ec.load_config(a.config)
    e, m = cfg["engine"], cfg["model"]
    if a.page_size:  # the same context and pool bytes at another page size
        ctx = (e["max_pages_per_seq"] - 1) * e["page_size"]
        pool = e["num_pages"] * e["page_size"]
        e.update(page_size=a.page_size, max_pages_per_seq=-(-ctx // a.page_size) + 1,
                 num_pages=pool // a.page_size)
    model = ec.build_model(cfg)
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=chip)
    params = jax.tree.map(lambda x: sds(x.shape, x.dtype),
                          jax.eval_shape(weights.make_init(m), jax.random.key(0)))
    L, hkv, ps, dh = m["n_layers"], m["n_kv_heads"], e["page_size"], m["d_head"]
    page = sds((L, e["num_pages"], hkv, ps, dh), jnp.bfloat16)
    pools = [{"k": page, "v": page}]
    b, mp = e["max_batch"], e["max_pages_per_seq"]
    i32 = jnp.int32
    out = {"config": a.config, "page_size": ps, "max_pages_per_seq": mp,
           "num_pages": e["num_pages"],
           "params_bytes": weights.param_bytes(m),
           "pool_bytes": 2 * L * e["num_pages"] * hkv * ps * dh * 2}
    step = jax.jit(make_paged_serve_step(model, attn_impl="pallas", vocab=m["vocab"]),
                   donate_argnums=(1, 2, 4))
    progs = {"decode": (step, (params, pools, sds((b,), i32), sds((b, mp), i32),
                               sds((b,), i32), sds((2, b), jnp.float32),
                               sds((3, b), i32)))}
    chunk = jax.jit(make_chunked_prefill_step(model, attn_impl="pallas"),
                    donate_argnums=(1,))
    for c in (ps, 2 * ps):
        progs[f"chunk{c}"] = (chunk, (params, pools, sds((1, c), i32),
                                      sds((1, mp), i32), sds((1, mp), i32),
                                      sds((1,), i32), sds((1,), i32), sds((1,), i32)))
    for name, (fn, args) in progs.items():
        t0 = time.perf_counter()
        comp = fn.lower(*args).compile()
        ma = comp.memory_analysis()
        out[name] = {
            "compile_s": round(time.perf_counter() - t0, 1),
            "temp_bytes": ma.temp_size_in_bytes,
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "tpu_custom_calls": comp.as_text().count("tpu_custom_call"),
        }
        print(name, out[name], flush=True)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
