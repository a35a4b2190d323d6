"""A configuration names the LM it serves: the harness loads
``bench/lm/<name>.py`` from the root it runs in, makes the weights,
samples the datastore, checks and counts through that module, and builds
the engine's ``ModelConfig`` from every key of the ``model`` block."""
from __future__ import annotations

import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "tests"))

import reference as ref  # noqa: E402
import run  # noqa: E402
import tiny  # noqa: E402

#: appended to a copy of ``dense.py``: every call of the module's
#: functions is written to ``<module>.called``, next to the module
RECORDER = '''

def _recorded(name, fn):
    called = __import__("pathlib").Path(__file__).with_suffix(".called")

    def call(*a, **kw):
        with called.open("a") as f:
            f.write(name + "\\n")
        return fn(*a, **kw)
    return call


for _name in ("make_weights", "forward_hidden", "lm_logits", "lm_token",
              "prefill"):
    globals()[_name] = _recorded(_name, globals()[_name])
'''


def serve(root, monkeypatch, capsys, seed=20261019):
    """The tiny cell of ``root`` through ``run.main`` with the look for a
    chip and the persistent compile cache switched off. Returns the last
    stdout line as JSON, and what the run held once its window closed:
    its weights, its datastore tables, the reference's logits at the last
    position of a fixed prompt, and the metric readers' context."""
    held = {}
    close = run.Server.close

    def keep(self):
        prompt = jnp.arange(12, dtype=jnp.int32)[None] * 37 \
            % self.model.vocab_size
        h = self.lm.forward_hidden(self.params, prompt, self.model,
                                   ref.REFERENCE)
        held.update(params=self.params, tables=self.tables,
                    logits=self.lm.lm_logits(self.params, h[:, -1],
                                             self.model, ref.REFERENCE))
        close(self)

    class Context(run.Context):
        def __init__(self, **kw):
            super().__init__(**kw)
            held["ctx"] = self
    with monkeypatch.context() as patch:
        patch.setattr(run, "require_chips", lambda n: jax.devices()[:n])
        patch.setattr(run, "compile_cache", lambda: None)
        patch.setattr(run.Server, "close", keep)
        patch.setattr(run, "Context", Context)
        rc = run.main(["--workload", tiny.WORKLOAD, "--seed", str(seed),
                       "--seconds", "2", "--trace", "0"], root=root)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1]), held


def bits(tree):
    return [(x.dtype, x.shape, np.asarray(x).tobytes())
            for x in jax.tree.leaves(tree)]


def test_naming_the_dense_lm_is_the_default(tmp_path, monkeypatch, capsys):
    """``"lm": "dense"`` serves the tiny cell end to end with weights,
    datastore tables and reference logits bitwise equal to those of the
    same cell with the key absent."""
    named = dict(tiny.CONFIG, model=dict(tiny.CONFIG["model"], lm="dense"))
    runs = [serve(tiny.make_root(tmp_path / sub, config=cfg), monkeypatch,
                  capsys)
            for sub, cfg in (("absent", None), ("named", named))]
    for res, _ in runs:
        assert res["correct"] is True and res["failed"] == 0
    (_, a), (_, b) = runs
    assert a["ctx"].lm is not b["ctx"].lm      # each root's own file
    for part in ("params", "tables", "logits"):
        assert bits(a[part]) == bits(b[part]), part


def test_an_lm_that_only_the_root_has_is_served_checked_and_counted(
        tmp_path, monkeypatch, capsys):
    """A configuration naming an LM file that exists only in its root (a
    copy of ``dense.py`` that records its calls) is served, checked and
    counted through that file, with no other file of the harness
    edited."""
    root = tiny.make_root(tmp_path, config=dict(
        tiny.CONFIG, model=dict(tiny.CONFIG["model"], lm="probe")))
    probe = root / "bench" / "lm" / "probe.py"
    probe.write_text((BENCH / "lm" / "dense.py").read_text() + RECORDER)
    assert not (BENCH / "lm" / "probe.py").exists()
    res, held = serve(root, monkeypatch, capsys)
    assert res["correct"] is True and res["failed"] == 0
    ctx = held["ctx"]
    assert pathlib.Path(ctx.lm.__file__) == probe.resolve()
    # the whole-step share counts the served tokens through the module
    ctx.peak, ctx.win = {"bf16_flops": 1e12}, (0.0, 1e9)
    assert run.reader("step_mfu_pct")(ctx) > 0
    called = set(probe.with_suffix(".called").read_text().split())
    assert called == {"make_weights", "forward_hidden", "lm_logits",
                      "lm_token", "prefill"}


@pytest.mark.parametrize("model,names", [
    ({"lm": "nowhere"}, "nowhere.py"),
    ({"experts_per_tok": 8}, "experts_per_tok"),
    ({"block": "moe", "n_experts": 4, "top_k": 2}, "block")])
def test_a_configuration_the_harness_cannot_serve_is_refused_first(
        model, names, tmp_path, monkeypatch):
    """A missing LM module, a ``model`` key the engine has no field for,
    or a model block the named module does not write out: refused with
    the path or the key in the message, before the look for a chip."""
    root = tiny.make_root(tmp_path, config=dict(
        tiny.CONFIG, model=dict(tiny.CONFIG["model"], **model)))

    def chips(n):
        raise AssertionError("reached the device")
    monkeypatch.setattr(run, "require_chips", chips)
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", tiny.WORKLOAD, "--seed", "1", "--seconds",
                  "1", "--trace", "0"], root=root)
    assert names in str(e.value)
    if "lm" in model:
        assert str((root / "bench" / "lm" / "nowhere.py").resolve()) \
            in str(e.value)


def test_the_engine_model_carries_every_key_of_the_file():
    """Keys the harness once dropped reach the engine's ``ModelConfig``,
    lists as tuples; the harness's own keys do not."""
    mc = run.model_config({"name": "wide", "model": dict(
        tiny.CONFIG["model"], lm="moe", layer_pattern=["local"] * 3
        + ["global"], window=1024, block="moe", n_experts=64, top_k=8,
        tie_embeddings=False)})
    assert mc.name == "wide"
    assert mc.layer_pattern == ("local", "local", "local", "global")
    assert (mc.window, mc.block, mc.n_experts, mc.top_k) == \
        (1024, "moe", 64, 8)
    assert mc.tie_embeddings is False and mc.d_ff == 128


def test_dec_s_engine_model_is_the_one_the_harness_built_before():
    """Dec-S's file gives the engine the ``ModelConfig`` that the harness
    built field by field before every key was passed through."""
    from repro.models.config import ModelConfig
    cfg = json.loads((BENCH / "configs" / "dec_s-syn512.json").read_text())
    mc = cfg["model"]
    assert run.model_config(cfg) == ModelConfig(
        name=cfg["name"], n_layers=mc["n_layers"], d_model=mc["d_model"],
        n_heads=mc["n_heads"], n_kv_heads=mc["n_kv_heads"],
        d_ff=mc["d_ff"], vocab_size=mc["vocab_size"], d_head=mc["d_head"],
        rope_theta=mc["rope_theta"], norm_eps=mc["norm_eps"],
        tie_embeddings=True)

