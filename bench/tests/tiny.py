"""A tiny cell for CPU tests: the harness's own files under a temporary
root, with a reduced configuration and a short open-loop mix."""
from __future__ import annotations

import json
import pathlib
import shutil

BENCH = pathlib.Path(__file__).resolve().parents[1]
WORKLOAD = "tiny.open"

CONFIG = {
    "name": "tiny",
    "source": "reduced widths for CPU tests",
    "model": {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
              "d_head": 16, "d_ff": 128, "vocab_size": 512,
              "rope_theta": 10000.0, "norm_eps": 1e-5, "init_scale": 0.02,
              "tie_embeddings": True},
    "rag": {"mode": "knnlm", "interval": 1, "k": 8, "lam": 0.25,
            "temperature": 10.0},
    "datastore": {"vectors": 8192, "dim": 64, "m": 8, "ksub": 256,
                  "nlist": 256, "nprobe": 4, "sample_doc_len": 16,
                  "spread_sample_tokens": 1024, "list_sigma": 0.34},
    "engine": {"kv_slots": 2, "max_seq": 32, "attn_seq_block": 32},
    "limits": {"query_err": 0.05, "dist_err": 1e-3, "scan_gap": 0.05,
               "mix_gap": 1.0, "missing": 0},
}

TRAFFIC = {
    "rate_per_s": 3.0,
    "prompt_len": {"median": 10, "sigma": 0.5, "min": 8, "max": 16},
    "output_len": {"median": 4, "sigma": 0.5, "min": 2, "max": 6},
    "sample_requests": 3,
}


def make_root(tmp: pathlib.Path, traffic=None, config=None) -> pathlib.Path:
    """A root holding BENCHMARK.json with the one tiny cell, its
    configuration (``CONFIG`` unless given), its traffic file and a copy
    of the harness's LM modules (the metric readers are the harness's
    own)."""
    (tmp / "bench" / "configs").mkdir(parents=True)
    (tmp / "bench" / "traffic").mkdir()
    shutil.copytree(BENCH / "lm", tmp / "bench" / "lm",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp / "bench" / "configs" / "tiny.json").write_text(
        json.dumps(config or CONFIG))
    (tmp / "bench" / "traffic" / "open.json").write_text(
        json.dumps(traffic or TRAFFIC))
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "tests",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "CPU tests"}]
    bench["workloads"] = [{"name": WORKLOAD, "config": "tiny",
                           "traffic": "open", "chips": 1, "why": "tests"}]
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            if "workloads" in m:
                m["workloads"] = [WORKLOAD]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def copy_bench(dst: pathlib.Path) -> None:
    shutil.copytree(BENCH, dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".traces"))
