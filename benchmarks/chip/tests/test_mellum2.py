"""Mellum2's architecture module (``arch/mellum2.py``) on the CPU: its weight
tree is the program's at the committed widths, its layers are the published
order, its float32 reference agrees at tiny widths with the program's own
reference (``repro.models.reference``, written apart from it), and the three
readers it brings read what they should from a run."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import peaks, weights
from benchmarks.chip import engine_cell as ec
from benchmarks.chip.arch import mellum2
from benchmarks.chip.tests.test_readers import reader
from benchmarks.chip.window import Run

NAME = "mellum2-12b-a2.5b"
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
TINY = dict(n_layers=8, d_model=64, n_heads=4, n_kv_heads=2, d_head=16, d_ff=32,
            n_experts=16, top_k=4, experts_held=4, vocab=512, window=40,
            layer_types=PERIOD * 2, dtype="float32")


def test_weight_tree_is_the_programs_and_holds_3_83e9_parameters():
    cfg = ec.load_config(NAME)
    m = cfg["model"]
    tree = mellum2.shapes(m)
    drawn = jax.eval_shape(weights.make_init(tree), weights.weight_key(1))
    got = jax.tree.map(lambda a: (tuple(a.shape), jnp.dtype(a.dtype)), drawn)
    specs = jax.tree.map(lambda s: (tuple(s.shape), jnp.dtype(s.dtype)),
                         ec.build_model(cfg).param_specs(),
                         is_leaf=lambda x: hasattr(x, "shape"))
    assert got == specs
    # 28 x (21.2 M attention + 0.15 M router + 16 x 6.19 M experts), and the
    # untied embedding and head: 7.66 GB in bf16
    assert weights.param_count(m, tree) == 3_826_319_616
    assert weights.param_bytes(tree) == 7_661_159_424


def test_layers_are_three_windowed_then_one_full_with_the_held_experts():
    m = ec.load_config(NAME)["model"]
    layers = mellum2.layers(m)
    assert [ly.span for ly in layers] == [1024, 1024, 1024, None] * 7
    assert {ly.ffn_params for ly in layers} == {peaks.routed_ffn(2304, 896, 64, 16, 8)}


@pytest.fixture(scope="module")
def tiny():
    cfg = ec.load_config(NAME)
    cfg["model"].update(TINY)
    params = weights.make_init(mellum2.shapes(cfg["model"]))(weights.weight_key(7))
    return cfg, params


def test_reference_agrees_with_the_programs_reference(tiny):
    """Two references written apart, both float32: the benchmark's blocked
    one and the program's plain one read the same best logits and the same
    logits of the served tokens (prompts past the window, YaRN on)."""
    from repro.models import reference
    from repro.models.config import ModelConfig

    cfg, params = tiny
    m = cfg["model"]
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, m["vocab"], 90).tolist()
    served = rng.integers(1, m["vocab"], 12).tolist()
    best, got, _, _ = mellum2.served_rows(params, m, cfg["rms_norm_eps"], prompt, served)
    logits = np.asarray(reference.logits(ModelConfig(**m), params,
                                         prompt + served[:-1]))[len(prompt) - 1:]
    np.testing.assert_allclose(best, logits.max(axis=-1), rtol=0, atol=1e-4)
    np.testing.assert_allclose(got, logits[np.arange(12), served], rtol=0, atol=1e-4)


def test_the_fp8_control_moves_the_logits(tiny):
    cfg, params = tiny
    m = cfg["model"]
    prompt = np.random.default_rng(1).integers(1, m["vocab"], 60).tolist()
    served = list(range(1, 9))
    best, got, _, _ = mellum2.served_rows(params, m, 1e-6, prompt, served)
    best8, got8, _, _ = mellum2.served_rows(params, m, 1e-6, prompt, served, fp8=True)
    assert np.abs(got8 - got).max() > 1e-2


def _run(ops, metrics, traced):
    m = ec.load_config(NAME)["model"]
    return Run(cell={"name": NAME}, model=m,
               engine={"max_batch": 32, "page_size": 256},
               layers=mellum2.layers(m), device_kind="TPU v5 lite", seconds=51.0,
               setup_s=0.0, records=[], engine_metrics=metrics,
               trace={"ops": ops, "busy_s": 1.0, "window_s": 4.0}, traced=traced)


WINDOW_DECODE = ('%paged_decode.3 = bf16[32,4,8,128]{3,2,1,0} custom-call(...), '
                 'custom_call_target="tpu_custom_call", '
                 'frontend_attributes={kernel_metadata={"window":"1024"}}')
FULL_DECODE = WINDOW_DECODE.replace('{"window":"1024"}', "{}")
GMM = '%gmm.4 = f32[256,896]{1,0} custom-call(...), custom_call_target="tpu_custom_call"'
CHUNK_GMM = GMM.replace("%gmm.4 = f32[256,", "%gmm.16 = f32[4096,")
# 100 decode steps of 12 held experts read in each of 28 layers
COUNTS = {"moe_pairs_routed": 8 * 28 * 1000, "moe_pairs_held": 2 * 28 * 1000,
          "decode_steps": 100, "moe_decode_experts_read": 12 * 28 * 100}


def test_held_route_share_reads_the_counters():
    assert reader("held_route_share")(_run({}, COUNTS, [])) == 25.0
    assert reader("held_route_share")(_run({}, {}, [])) is None


def test_window_decode_roofline_prices_the_windowed_kernel_only():
    # one row decoding positions 2000..2009: 10 calls of 21 window layers
    traced = [(1500, 2000, 2010)]
    run = _run({WINDOW_DECODE: 1e-3, FULL_DECODE: 5.0}, {}, traced)
    windowed = [ly for ly in run.layers if ly.span]
    ops, byt = peaks.decode_attn(windowed, range(2001, 2011), 256, kv_bytes=2)
    want = 100.0 * max(ops / 197e12, byt / 819e9) / 1e-3
    assert reader("window_decode_roofline")(run) == pytest.approx(want, rel=1e-12)
    assert reader("window_decode_roofline")(_run({FULL_DECODE: 5.0}, {}, traced)) is None


def test_expert_ffn_roofline_prices_the_decode_steps_of_the_sub_window():
    # one request decodes 40 positions of the sub-window, so it held 40 decode
    # steps; another prefills 20 positions, then decodes 20; the chunk's
    # grouped products are not decode time
    traced = [(1500, 1600, 1640), (300, 280, 320)]
    run = _run({GMM: 2e-3, CHUNK_GMM: 5.0}, COUNTS, traced)
    expert = 3 * 2304 * 896
    ops = 2.0 * expert * 60 * 8 * 28 * 0.25
    byt = 2.0 * expert * 40 * 12 * 28
    want = 100.0 * max(ops / 197e12, byt / 819e9) / 2e-3
    assert reader("expert_ffn_roofline")(run) == pytest.approx(want, rel=1e-12)
    assert reader("expert_ffn_roofline")(_run({GMM: 2e-3}, {}, traced)) is None
    assert reader("expert_ffn_roofline")(_run({CHUNK_GMM: 5.0}, COUNTS, traced)) is None
