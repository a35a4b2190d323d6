"""The comparison that decides ``correct`` fails what it should: a run of
the tiny cell with the timed path broken underneath, once per fault a
one-chip serving cell can have, and the lower-precision control."""
from __future__ import annotations

import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "tests"))

import tiny  # noqa: E402

#: the tiny mix with requests long and close enough that about half the
#: engine's waves hold two rows, every request sampled: a fault that acts
#: on a wave's rows then reaches the check whatever the host's speed (at
#: the tiny mix's own rate an idle host serves every request alone)
OVERLAP = dict(tiny.TRAFFIC, rate_per_s=8.0,
               output_len={"median": 12, "sigma": 0.3, "min": 8, "max": 16},
               sample_requests=16)


def run_tiny(tmp_path, monkeypatch, capsys, seed=7):
    import run
    monkeypatch.setattr(run, "require_chips", lambda n: jax.devices()[:n])
    monkeypatch.setattr(run, "compile_cache", lambda: None)
    rc = run.main(["--workload", tiny.WORKLOAD, "--seed", str(seed),
                   "--seconds", "2", "--trace", "0"],
                  root=tiny.make_root(tmp_path, traffic=OVERLAP))
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def state_unchanged(monkeypatch):
    """A decode wave that hands the KV pool back as it found it."""
    from repro.serve import engine
    inner = engine._jit_decode_wave

    def wave(params, cfg, caches, *a, **kw):
        kept = jax.tree.map(jnp.copy, caches)
        logits, _, hidden = inner(params, cfg, caches, *a, **kw)
        return logits, kept, hidden
    monkeypatch.setattr(engine, "_jit_decode_wave", wave)


def half_the_batch(monkeypatch):
    """A retrieval wave that searches the first half of its rows and
    hands the second half the first half's answers."""
    from repro.retrieval import service
    inner = service.LocalPipeline.scan

    def scan(self, queries):
        n = queries.shape[0]
        if n < 2:
            return inner(self, queries)
        h = (n + 1) // 2
        d, i = inner(self, queries[:h])
        rep = jnp.arange(n) % h
        return d[:, rep], i[:, rep]
    monkeypatch.setattr(service.LocalPipeline, "scan", scan)


def half_the_neighbours(monkeypatch):
    """A retrieval wave that leaves out the nearer half of each row's
    neighbours and hands the farther half back twice."""
    from repro.retrieval import service
    inner = service.LocalPipeline.scan

    def scan(self, queries):
        d, i = inner(self, queries)
        k = d.shape[-1]
        keep = jnp.concatenate([jnp.arange(k // 2, k)] * 2)[:k]
        return d[..., keep], i[..., keep]
    monkeypatch.setattr(service.LocalPipeline, "scan", scan)


def token_altered(monkeypatch):
    """Every sampled token replaced by its neighbour in the vocabulary
    where the engine produces it."""
    from repro.serve.engine import RalmEngine
    inner = RalmEngine._emit

    def emit(self, seq, nxt):
        return inner(self, seq, (nxt + 1) % self.cfg.vocab_size)
    monkeypatch.setattr(RalmEngine, "_emit", emit)


def search_skipped(monkeypatch):
    """Every other token served by the LM alone, its search never
    issued."""
    from repro.serve.engine import RalmEngine
    inner = RalmEngine._retrieval_due

    def due(self, step):
        return inner(self, step) and step % 2 == 0
    monkeypatch.setattr(RalmEngine, "_retrieval_due", due)


@pytest.mark.parametrize("fault,number", [(state_unchanged, "query_err"),
                                          (half_the_batch, "dist_err"),
                                          (half_the_neighbours, "scan_gap"),
                                          (token_altered, "mix_gap"),
                                          (search_skipped, "missing")])
def test_a_broken_timed_path_is_not_correct(fault, number, tmp_path,
                                            monkeypatch, capsys):
    fault(monkeypatch)
    res = run_tiny(tmp_path, monkeypatch, capsys)
    assert res["correct"] is False
    # each fault is caught by the number that covers its layer
    assert res["checks"][number]["value"] > res["checks"][number]["limit"]


def test_the_control_fails_where_the_program_passes(tmp_path, monkeypatch,
                                                    capsys):
    """The reference a precision lower than the configuration states,
    read on the prompts and tokens of a tiny run, exceeds a limit that
    the program's own readings keep."""
    import check
    import run
    captured = {}
    real = run.Server.readings

    def readings(self, results, rows):
        out = real(self, results, rows)
        captured["control"] = check.control_readings(self.reference)
        return out
    monkeypatch.setattr(run.Server, "readings", readings)
    res = run_tiny(tmp_path, monkeypatch, capsys, seed=11)
    assert res["correct"] is True
    ok, _ = check.judge(captured["control"], tiny.CONFIG["limits"])
    assert not ok
