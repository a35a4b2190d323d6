"""The DeepSeek-V2 LM module (``bench/lm/deepseek_v2.py``) and its
configuration: the harness loads, serves, checks and counts a tiny
DeepSeek-V2-shaped cell through it; its operation and byte counts by
hand; the two metrics of the latent attention and the experts against a
hand-made trace."""
from __future__ import annotations

import json
import pathlib
import sys
import types

import jax
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "tests"))

import devtrace  # noqa: E402
import run  # noqa: E402
import tiny  # noqa: E402

CELL = "dsv2_lite.syn2048.poisson_long"

#: DeepSeek-V2's block at CPU widths: a dense first layer, then MoE layers
#: of 8 experts (top 2, unrenormalised) and one shared, latent attention
MODEL = {"lm": "deepseek_v2", "n_layers": 3, "d_model": 64, "n_heads": 4,
         "n_kv_heads": 4, "d_ff": 96, "vocab_size": 512, "block": "moe",
         "n_experts": 8, "top_k": 2, "d_expert": 32, "n_shared_experts": 1,
         "first_k_dense": 1, "norm_topk_prob": False,
         "routed_scaling_factor": 1.0, "kv_lora_rank": 32,
         "qk_nope_head_dim": 16, "qk_rope_head_dim": 16, "v_head_dim": 16,
         "rope_theta": 10000.0, "yarn_factor": 40.0,
         "yarn_original_max_position": 4096, "yarn_beta_fast": 32.0,
         "yarn_beta_slow": 1.0, "yarn_mscale": 0.707,
         "yarn_mscale_all_dim": 0.707, "norm_eps": 1e-6, "init_scale": 0.02,
         "tie_embeddings": False}
CONFIG = dict(tiny.CONFIG, name="tiny-dsv2", model=MODEL)


def module():
    return run.lm_module(BENCH.parent, "deepseek_v2")


def test_tiny_deepseek_cell_is_served_checked_and_counted(
        tmp_path, monkeypatch, capsys):
    """The tiny cell through ``run.main``: correct against the module's
    float32 reference (the same limits as the dense tiny cell), and the
    whole-step share counts its tokens through the module."""
    held = {}

    class Context(run.Context):
        def __init__(self, **kw):
            super().__init__(**kw)
            held["ctx"] = self
    monkeypatch.setattr(run, "require_chips", lambda n: jax.devices()[:n])
    monkeypatch.setattr(run, "compile_cache", lambda: None)
    monkeypatch.setattr(run, "Context", Context)
    rc = run.main(["--workload", tiny.WORKLOAD, "--seed", "3000000019",
                   "--seconds", "2", "--trace", "0"],
                  root=tiny.make_root(tmp_path, config=CONFIG))
    assert rc == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True and res["failed"] == 0, res["checks"]
    ctx = held["ctx"]
    assert pathlib.Path(ctx.lm.__file__).name == "deepseek_v2.py"
    ctx.peak, ctx.win = {"bf16_flops": 1e12}, (0.0, 1e9)
    assert run.reader("step_mfu_pct")(ctx) > 0


def test_load_cell_takes_the_configuration_and_every_key_reaches_the_engine():
    """(g) The cell loads with its module, and the engine's ``ModelConfig``
    carries every MLA, YaRN and MoE key of the file."""
    _, cell, cfg, traffic, lm = run.load_cell(BENCH.parent, CELL)
    assert cell["chips"] == 1 and lm.__name__.endswith("deepseek_v2")
    mc = run.model_config(cfg)
    for key, value in cfg["model"].items():
        if key not in run.HARNESS_MODEL_KEYS:
            assert getattr(mc, key) == value, key
    assert (mc.kv_lora_rank, mc.qk_nope_head_dim, mc.qk_rope_head_dim,
            mc.v_head_dim) == (512, 128, 64, 128)
    assert (mc.n_experts, mc.top_k, mc.d_expert, mc.n_shared_experts,
            mc.first_k_dense, mc.norm_topk_prob) == (64, 6, 1408, 2, 1,
                                                     False)
    assert mc.latent_width == 576 and mc.d_ff == 10944
    # the file keeps the published config's numbers under its own keys
    assert (cfg["hidden_size"], cfg["moe_intermediate_size"],
            cfg["num_hidden_layers"]) == (2048, 1408, 6)
    m = lm.Model.from_config(cfg["model"])
    assert traffic["prompt_len"]["median"] == 1024
    assert cfg["datastore"]["dim"] == m.d_model


@pytest.mark.parametrize("change,name", [
    ({"kv_lora_rank": 0}, "kv_lora_rank"),
    ({"tie_embeddings": True}, "tie_embeddings"),
    ({"window": 64, "layer_pattern": ["local"]}, "window"),
    ({"block": "dense"}, "block")])
def test_the_module_refuses_what_it_does_not_write_out(change, name):
    with pytest.raises(ValueError) as e:
        module().Model.from_config(dict(MODEL, **change))
    assert name in str(e.value)


def test_yarn_of_the_published_config():
    """(e) YaRN at DeepSeek-V2-Lite's settings, by hand: mscale
    0.1 * 0.707 * ln 40 + 1, the correction range (10, 23) of 32
    frequencies, the frequencies at both ends."""
    import math
    lm = module()
    _, _, cfg, _, _ = run.load_cell(BENCH.parent, CELL)
    m = lm.Model.from_config(cfg["model"])
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert lm.softmax_scale(m) == pytest.approx(192 ** -0.5 * mscale ** 2)
    assert lm.softmax_scale(m) == pytest.approx(0.114721, abs=1e-6)
    assert lm.correction_range(m) == (10, 23)
    inv = lm.inv_freq(m)
    assert float(inv[0]) == pytest.approx(1.0)               # kept
    assert float(inv[31]) == pytest.approx(1e4 ** (-62 / 64) / 40)
    assert float(inv[10]) == pytest.approx(1e4 ** (-20 / 64))


def test_operations_and_bytes_by_hand():
    """(f) ``lm_token``, ``prefill`` and ``latent_attn_bytes`` of a small
    block: one dense layer and one MoE layer."""
    lm = module()
    b = {"n_layers": 2, "first_k_dense": 1, "d_model": 8, "n_heads": 2,
         "kv_lora_rank": 4, "qk_nope_head_dim": 3, "qk_rope_head_dim": 2,
         "v_head_dim": 5, "d_ff": 6, "d_expert": 7, "n_experts": 4,
         "top_k": 2, "n_shared_experts": 1, "vocab_size": 10}
    proj = 8 * 2 * 5 + 8 * 6 + 2 * 5 * 8            # q, kv_a, o
    dense, moe = 3 * 8 * 6, 8 * 4 + 2 * 3 * 8 * 7 + 3 * 8 * 7
    # decode at 9 cached rows: per layer the projections, W_UK and W_UV
    # absorbed, scores over 6-wide rows and the weighted 4-wide latents
    attn = 2 * (proj + 2 * 3 * 4 + 2 * 4 * 5) + 2 * 2 * 9 * 6 \
        + 2 * 2 * 9 * 4
    assert lm.lm_token(b, 9) == 2 * dense + 2 * moe + 2 * attn + 2 * 8 * 10
    # prefill of 3 positions: kv_b on every latent, 6 causal pairs
    layer = 2 * 3 * (proj + 4 * 2 * 8) + 2 * 2 * 6 * 5 + 2 * 2 * 6 * 5
    assert lm.prefill(b, 3) == 2 * layer + 2 * 3 * (dense + moe) \
        + 2 * 8 * 10
    assert lm.latent_attn_bytes(b, 9) == 2 * 9 * 6 * 2
    assert lm.expert_flops(b, 5) == 5 * 2 * 3 * 8 * 7
    assert lm.expert_bytes(b, 3) == 3 * 3 * 8 * 7 * 2


TPU0 = "/device:TPU:0"


def trace_ctx():
    """Window [0, 100] on one chip: gmm ops [0, 10] and [5, 20] (overlap
    counted once), two latent kernels of 4 and 6."""
    trace = {TPU0: {devtrace.OPS: [
        ("%gmm.1 = bf16[256,1408] custom-call(%x)", 0.0, 10.0),
        ("%gmm.2 = bf16[256,1408] custom-call(%x)", 5.0, 15.0),
        ("%decode_attn_latent.3 = f32[1,16,512] custom-call(%gmm.2)",
         30.0, 4.0),
        ("%decode_attn_latent.4 = f32[1,16,512] custom-call(%q)",
         50.0, 6.0),
        ("%while.2 = (s32[]) while(%t)", 60.0, 30.0)]}}
    req = types.SimpleNamespace(request=types.SimpleNamespace(prompt=[0] * 7))
    model = {"n_layers": 6, "kv_lora_rank": 512, "qk_rope_head_dim": 64,
             "d_model": 2048, "n_heads": 16, "qk_nope_head_dim": 128,
             "v_head_dim": 128}
    return types.SimpleNamespace(
        trace=trace, planes=[TPU0], win=(0.0, 100.0), t0=0.0, t_end=1.0,
        cfg={"model": model}, lm=module(),
        peak={"hbm_bytes_per_s": 1e12},
        tokens=lambda lo, hi: [(req, 0, 0.1), (req, 1, 0.2), (req, 2, 0.3)])


def test_moe_busy_and_latent_roofline_of_a_hand_made_trace():
    ctx = trace_ctx()
    assert run.reader("moe_busy_pct")(ctx) == pytest.approx(20.0)
    # tokens 1 and 2 read 8 and 9 rows of 576 bf16 values in 6 layers,
    # over 10 ns of the kernel at 1e12 bytes/s
    need = 6 * (8 + 9) * 576 * 2
    assert run.reader("latent_attn_roofline_pct")(ctx) == \
        pytest.approx(100.0 * need / (10e-9 * 1e12))


@pytest.mark.parametrize("name", ["moe_busy_pct", "latent_attn_roofline_pct"])
def test_the_new_readers_find_nothing_in_a_dense_trace(name):
    """A program with no such kernel (the parent, a dense model) reads
    nothing, and an LM module without latent bytes reads nothing."""
    ctx = trace_ctx()
    ctx.trace = {TPU0: {devtrace.OPS: [("%while.2 = (s32[]) while(%t)",
                                        0.0, 50.0)]}}
    assert run.reader(name)(ctx) is None
    ctx = trace_ctx()
    ctx.win = None
    assert run.reader(name)(ctx) is None
    ctx = trace_ctx()
    ctx.lm = types.SimpleNamespace()
    if name == "latent_attn_roofline_pct":
        assert run.reader(name)(ctx) is None
