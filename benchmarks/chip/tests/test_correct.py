"""``correct`` on a tiny configuration on the CPU: the whole run after the
look for a chip, sound and with the timed path broken underneath, and the
fp8 control against the same limit."""
import json
import sys
import time
import types

import jax
import numpy as np
import pytest

from benchmarks.chip import arch, check, reference, run, traffic, weights
from benchmarks.chip import engine_cell as ec
from benchmarks.chip.arch import dense_gqa

CELL = {"name": "tiny.chat", "config": "qwen2.5-3b", "traffic": "chat", "chips": 1}


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def tiny(shared=False, wide=False):
    """The committed configuration and mix at toy sizes; ``shared`` deals the
    prompts a few shared prefixes, so prefix adoption is on the timed path;
    ``wide`` takes widths at which fp8 rounding reads as it does at full size
    (a toy of width 64 rounds too little to part the control from bf16)."""
    cfg = ec.load_config("qwen2.5-3b")
    if wide:
        cfg["model"].update(n_layers=4, d_model=256, n_heads=4, n_kv_heads=2,
                            d_head=64, d_ff=1024, vocab=2048)
    else:
        cfg["model"].update(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                            d_head=16, d_ff=128, vocab=512)
    cfg["engine"].update(page_size=16, max_batch=4, num_pages=400,
                         max_pages_per_seq=40)
    mx = traffic.load_mix("chat")
    if shared:
        mx["shared_prefix"] = {"len": 48, "count": 3, "zipf_s": 1.1}
    mx["prompt_len"].update(median=60, max=200, min=8)
    mx["output_len"].update(median=8, max=24, min=2)
    mx["rate_per_s"] = 4.0
    return cfg, mx


def run_tiny(capsys, cfg, mix, seed=2**33 + 3, *, warm_window=False):
    """One run's result line; with ``warm_window``, also that the warm-up
    left the window nothing to compile."""
    run.T_START = time.perf_counter()
    assert run.execute(CELL, seed, 2.0, False, [("ttft_s_p85", "s")],
                       cfg=cfg, mix=mix) == 0
    cap = capsys.readouterr()
    if warm_window:
        assert "in the window 0, compiled 0" in cap.err
    return json.loads(cap.out.strip().splitlines()[-1])


@pytest.mark.parametrize("shared,wide,seed", [
    (False, False, 2**33 + 3), (True, False, 2**33 + 3),
    # a seed whose shared prefixes cover a warm-up prompt's first page
    (True, False, 12), (False, True, 2**33 + 3)])
def test_a_sound_run_is_correct(capsys, shared, wide, seed):
    cfg, mix = tiny(shared, wide)
    if wide:  # as the control's run below
        mix["output_len"].update(median=32, max=64, min=16)
    out = run_tiny(capsys, cfg, mix, seed, warm_window=True)
    assert out["correct"] is True and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert out["checks"]["logit_gap"]["value"] <= out["checks"]["logit_gap"]["limit"]


def test_a_token_altered_where_it_is_produced_is_not_correct(capsys, monkeypatch):
    from repro.kernels import ops

    orig = ops.sample_tokens

    def altered(*a, **k):
        tok = orig(*a, **k)
        return tok.at[0].set((tok[0] + 1) % k["vocab"])

    monkeypatch.setattr(ops, "sample_tokens", altered)
    out = run_tiny(capsys, *tiny())
    assert out["correct"] is False
    assert out["checks"]["logit_gap"]["value"] > out["checks"]["logit_gap"]["limit"]


def test_an_unserved_request_is_not_correct(capsys, monkeypatch):
    from repro.serving.engine import ServeEngine

    orig = ServeEngine.run

    def drop_one(self, requests=None):
        res = orig(self, requests)
        if res:
            res.pop(max(res))
        return res

    monkeypatch.setattr(ServeEngine, "run", drop_one)
    out = run_tiny(capsys, *tiny())
    assert out["correct"] is False and out["checks"]["unserved"]["value"] >= 1


def test_the_fp8_control_in_the_programs_place_is_not_correct(capsys, monkeypatch):
    """The control: the reference in fp8, decoding greedily in the program's
    place, serves every window request; the harness's own comparison then
    reads ``correct`` false on the same limit that sound runs meet."""
    from repro.serving.engine import ServeEngine

    cfg, mix = tiny(wide=True)
    mix["output_len"].update(median=32, max=64, min=16)  # some hundreds checked
    m, eps = cfg["model"], cfg["rms_norm_eps"]
    orig = ServeEngine.run

    def fp8_decode(self, requests=None):
        res = orig(self, requests)
        for req in requests or ():
            st, out = res[req.rid], []
            for _ in st.generated:
                _, _, arg, _ = reference.served_rows(
                    self.params, m, eps, req.prompt, out + [0], fp8=True)
                out.append(int(arg[-1]))
            st.generated[:] = out
        return res

    monkeypatch.setattr(ServeEngine, "run", fp8_decode)
    out = run_tiny(capsys, cfg, mix)
    assert out["failed"] == 0
    assert out["correct"] is False
    assert out["checks"]["logit_gap"]["value"] > out["checks"]["logit_gap"]["limit"]


def test_the_configurations_architecture_gives_weights_reference_and_counts(
        capsys, monkeypatch):
    """A module registered under a new name, a dense copy that notes its
    calls, is what a run of a configuration naming it draws its weights
    from, checks against, and prices the work with."""
    calls = []
    spy = types.ModuleType("benchmarks.chip.arch.spy")

    def noted(name):
        def call(*a, **k):
            calls.append(name)
            return getattr(dense_gqa, name)(*a, **k)
        return call

    for name in ("shapes", "served_rows", "layers"):
        setattr(spy, name, noted(name))
    known = arch.names()
    monkeypatch.setattr(arch, "names", lambda: known + ["spy"])
    monkeypatch.setitem(sys.modules, spy.__name__, spy)
    monkeypatch.setattr(run, "reader", lambda name: lambda r: len(r.layers))
    cfg, mix = tiny()
    cfg["arch"] = "spy"
    run.T_START = time.perf_counter()
    assert run.execute(CELL, 2**33 + 3, 2.0, False, [("layers", "1")],
                       cfg=cfg, mix=mix) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True
    assert out["metrics"]["layers"]["value"] == cfg["model"]["n_layers"]
    assert {"shapes", "served_rows", "layers"} <= set(calls)


@pytest.mark.parametrize("fp8", [False, True])
def test_the_reference_through_the_registry_is_the_dense_reference(fp8):
    """``check`` reaches the reference through the registry; on the tiny
    configuration it reads bit for bit what ``reference.py`` reads."""
    cfg, _ = tiny()
    m, eps = cfg["model"], cfg["rms_norm_eps"]
    params = weights.make_init(arch.of(cfg).shapes(m))(weights.weight_key(7))
    rng = np.random.default_rng(7)
    prompt = rng.integers(1, m["vocab"], 40).tolist()
    served = rng.integers(1, m["vocab"], 12).tolist()
    lookups = [rng.integers(1, m["vocab"], 12).tolist()]
    got = arch.of(cfg).served_rows(params, m, eps, prompt, served, fp8=fp8,
                                   lookups=lookups)
    want = reference.served_rows(params, m, eps, prompt, served, fp8=fp8,
                                 lookups=lookups)
    for g, w in zip([*got[:3], *got[3]], [*want[:3], *want[3]]):
        assert np.array_equal(g, w)
    if not fp8:
        best, tok, _, _ = want
        items = [types.SimpleNamespace(prompt=prompt)]
        records = [types.SimpleNamespace(generated=served)]
        assert np.array_equal(check.gaps(params, cfg, items, records, [0]),
                              best - tok)
