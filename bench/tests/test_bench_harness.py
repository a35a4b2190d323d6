"""The harness on the CPU: one tiny cell end to end, its refusal of a host
without a TPU, and the pieces that need no model (traffic, work counts,
peaks, trace reduction)."""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "tests"))

import devtrace  # noqa: E402
import loadgen  # noqa: E402
import peaks  # noqa: E402
import run  # noqa: E402
import tiny  # noqa: E402
import work  # noqa: E402

dense = run.lm_module(BENCH.parent, "dense")

FIXTURE = BENCH / "tests" / "trace_fixture.json"


def run_tiny(tmp_path, monkeypatch, capsys, seed=20261016):
    """The tiny cell through ``run.main`` with the look for a chip and the
    persistent compile cache switched off; returns (exit code, the last
    stdout line as JSON, stderr)."""
    import jax
    monkeypatch.setattr(run, "require_chips",
                        lambda n: jax.devices()[:n])
    monkeypatch.setattr(run, "compile_cache", lambda: None)
    root = tiny.make_root(tmp_path)
    rc = run.main(["--workload", tiny.WORKLOAD, "--seed", str(seed),
                   "--seconds", "2", "--trace", "0"], root=root)
    out, err = capsys.readouterr()
    return rc, json.loads(out.strip().splitlines()[-1]), err


def test_tiny_cell_end_to_end(tmp_path, monkeypatch, capsys):
    rc, res, err = run_tiny(tmp_path, monkeypatch, capsys)
    assert rc == 0
    assert list(res)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(res)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == round(tiny.TRAFFIC["rate_per_s"] * 2)
    assert {"ttft_p95_ms", "itl_p95_ms", "setup_s"} <= set(res["metrics"])
    assert res["device"]["platform"] == "cpu"
    assert set(res["checks"]) == set(tiny.CONFIG["limits"])
    # the compared numbers are the last lines on standard error
    last = err.strip().splitlines()[-len(res["checks"]):]
    assert all(line.startswith("check ") and " limit " in line
               for line in last)


def test_refuses_a_host_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload",
                        "dec_s.syn512.poisson", "--seed", "1", "--seconds",
                        "1", "--trace", "0"], capture_output=True, text=True,
                       env=env, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_refuses_a_directory_with_only_the_benchmark(tmp_path):
    tiny.copy_bench(tmp_path)
    (tmp_path / "BENCHMARK.json").write_text(
        (BENCH.parent / "BENCHMARK.json").read_text())
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "dec_s.syn512.poisson", "--seed", "1", "--seconds",
                        "1", "--trace", "0"], capture_output=True, text=True,
                       cwd=tmp_path, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu",
                                PYTHONPATH=""))
    assert p.returncode != 0 and p.stdout.strip() == ""


# -- traffic ---------------------------------------------------------------

def test_seeds_share_the_work_and_change_its_order():
    """Seeds share the sizes, their order and the arrivals (one schedule
    for every seed, so the seed cannot move the tails); what a seed
    changes is the order of the prompts' tokens."""
    a = loadgen.plan(tiny.TRAFFIC, 10, 1, 512)
    b = loadgen.plan(tiny.TRAFFIC, 10, 2**33 + 5, 512)
    key = [(len(r.prompt), r.max_tokens, r.due_s) for r in a]
    assert key == [(len(r.prompt), r.max_tokens, r.due_s) for r in b]
    assert len({len(r.prompt) for r in a}) > 1
    assert [r.prompt for r in a] != [r.prompt for r in b]
    for plan in (a, b):
        due = [r.due_s for r in plan]
        assert due == sorted(due) and 0 <= due[0] and due[-1] < 10
        for r in plan:
            p, o = tiny.TRAFFIC["prompt_len"], tiny.TRAFFIC["output_len"]
            assert p["min"] <= len(r.prompt) <= p["max"]
            assert o["min"] <= r.max_tokens <= o["max"]
    assert [r.prompt for r in a] == \
        [r.prompt for r in loadgen.plan(tiny.TRAFFIC, 10, 1, 512)]


def test_lengths_are_not_snapped_to_a_grid():
    traffic = json.loads((BENCH / "traffic" / "poisson.json").read_text())
    plan = loadgen.plan(traffic, 51, 1, 50000)
    assert len(plan) == 38
    # every quantile its own length, as users send them
    assert len({len(r.prompt) for r in plan}) >= 30
    assert len({r.max_tokens for r in plan}) >= 30
    assert max(len(r.prompt) for r in plan) == 512


def test_warmup_covers_every_prompt_wave_size_and_attention_length():
    traffic = json.loads((BENCH / "traffic" / "poisson.json").read_text())
    plan = loadgen.plan(traffic, 51, 4100000001, 50000)
    rounds = loadgen.warmup(plan, 64, 1024, 50000)
    assert sorted(len(r.prompt) for r in rounds[0]) == \
        sorted({len(r.prompt) for r in plan})
    assert set(loadgen.length_pairs(plan)) == \
        {(len(r.prompt), r.max_tokens) for r in plan}
    sweep = rounds[1]
    # wave sizes 64, 63, ..., 1 on successive decode steps
    assert [sum(r.max_tokens > s for r in sweep) for s in range(64)] == \
        list(range(64, 0, -1))
    shapes = loadgen.decode_shapes(plan, 64, 1024, 256)
    assert {b for b, _ in shapes} == {1, 2, 4, 8, 16, 32, 64}
    # positions run from the shortest prompt to at most 512 + 256 (the
    # longest prompt and output): attention lengths 256, 512, 768
    assert {k for _, k in shapes} == {256, 512, 768}
    assert len(shapes) == 21


# -- work, peaks -----------------------------------------------------------

MODEL = {"n_layers": 2, "d_model": 8, "n_heads": 2, "n_kv_heads": 1,
         "d_head": 4, "d_ff": 16, "vocab_size": 10}


def test_lm_and_prefill_work_by_hand():
    block = 8 * 8 + 2 * 8 * 4 + 8 * 8 + 3 * 8 * 16
    assert dense.lm_token(MODEL, kv_len=3) == \
        2 * 2 * block + 2 * 8 * 10 + 2 * 4 * 3 * 2 * 4
    # prefill of 3 positions: attention over 1 + 2 + 3 positions
    assert dense.prefill(MODEL, 3) == \
        2 * 2 * block * 3 + 2 * 4 * 2 * 4 * 6 + 2 * 8 * 10
    assert work.retrieval_token({"nlist": 10, "m": 4, "ksub": 16}, 8) == \
        2 * 10 * 8 + 2 * 4 * 16 * 2


def test_step_mfu_by_hand():
    """Two tokens of one request in a traced window of 2 s on one chip:
    the first pays its prompt's prefill, the second a decode step; each
    pays one query's search."""
    import importlib.util
    import types
    spec = importlib.util.spec_from_file_location(
        "step_mfu_pct", BENCH / "metrics" / "step_mfu_pct.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    ds = {"nlist": 10, "m": 4, "ksub": 16}
    req = types.SimpleNamespace(request=types.SimpleNamespace(prompt=[0] * 3))
    ctx = types.SimpleNamespace(
        cfg={"model": MODEL, "datastore": dict(ds, dim=8)}, lm=dense,
        peak={"bf16_flops": 1e6}, chips=1, win=(0.0, 2e9), t0=0.0,
        t_end=2.0, tokens=lambda lo, hi: [(req, 0, 0.5), (req, 1, 1.0)])
    ops = (dense.prefill(MODEL, 3) + dense.lm_token(MODEL, 4)
           + 2 * work.retrieval_token(ds, 8))
    assert mod.read(ctx) == pytest.approx(100.0 * ops / 2.0 / 1e6)
    ctx.win = None
    assert mod.read(ctx) is None


def test_compile_counter_sees_a_program_built():
    """``window_compiles`` reads the counter's delta: a program JAX builds
    counts, a call that finds it already built does not."""
    import jax
    import jax.numpy as jnp
    counter = run.CompileCounter()
    before = counter.count
    f = jax.jit(lambda x: x * 3 + 1)
    f(jnp.ones((7, 5))).block_until_ready()
    built = counter.count - before
    f(jnp.ones((7, 5))).block_until_ready()
    assert built >= 1 and counter.count - before == built


def test_peaks_of_a_v5e_and_an_unknown_device():
    v5e = peaks.lookup("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["int8_ops"] == 393e12
    with pytest.raises(KeyError):
        peaks.lookup("TPU v9 imaginary")


# -- trace reduction -------------------------------------------------------

SYNTHETIC = {
    "/host:CPU": {"python": [("bench.window", 0.0, 100.0),
                             ("bench.step", 10.0, 50.0),
                             ("bench.prefill", 20.0, 10.0)]},
    "/device:TPU:0": {
        devtrace.OPS: [("fusion.1", 5.0, 10.0), ("fusion.2", 12.0, 6.0),
                       ("_chamvs_scan_kernel", 40.0, 20.0),
                       ("fusion.1", 95.0, 10.0)]},
}


def test_reduction_of_a_hand_made_trace():
    t = SYNTHETIC
    assert devtrace.devices(t) == ["/device:TPU:0"]
    lo, hi = devtrace.window(t)
    assert (lo, hi) == (0.0, 100.0)
    assert devtrace.aligned(t, "/device:TPU:0", (lo, hi)) == (lo, hi)
    # a device on another clock: its own first and last operations
    assert devtrace.aligned(t, "/device:TPU:0", (1e6, 2e6)) == (5.0, 105.0)
    # busy: [5, 18] + [40, 60] + [95, 100] = 13 + 20 + 5
    assert devtrace.busy_ns(t, "/device:TPU:0", lo, hi) == 38.0
    top = dict(devtrace.top_ops(t, "/device:TPU:0", lo, hi))
    assert top["fusion.1"] == pytest.approx(15e-9)
    # idle: [0,5] other, [18,40] mid 29 in prefill? no: prefill ends at 30,
    # mid 29 -> prefill; [60,95] mid 77.5 -> other
    gaps = dict(devtrace.idle_gaps(t, "/device:TPU:0", lo, hi))
    assert gaps["bench.prefill"] == pytest.approx(22e-9)
    assert gaps["other"] == pytest.approx(40e-9)


def test_reduction_of_a_recorded_trace():
    """A trace recorded by ``run.py --trace 1 --keep-trace`` of the tiny
    cell on the CPU (the host spans kept, a few of the CPU client's
    operations): the window and the harness's spans are found, and a host
    thread is never taken for a device."""
    t = devtrace.read_json(str(FIXTURE))
    assert devtrace.devices(t) == []
    lo, hi = devtrace.window(t)
    spans = devtrace.host_spans(t)
    names = {name for name, _, _ in spans}
    assert {"bench.step", "bench.prefill", "bench.decode_wave",
            "bench.finish_wave", "bench.search_flush"} <= names
    steps = [(s, s + d) for name, s, d in spans if name == "bench.step"]
    assert steps and all(lo <= a and b <= hi + 1e9 for a, b in steps)
    # every prefill and decode wave runs inside some scheduler step
    for name, s, d in spans:
        if name in ("bench.decode_wave", "bench.finish_wave"):
            assert any(a <= s and s + d <= b for a, b in steps)
