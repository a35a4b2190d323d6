"""``chip_smoke.py`` rehearsed on the CPU: its phases at the reduced
Dec-S size with the Pallas kernels interpreted, its refusal to report a
result without a TPU, and where the compile cache goes."""
import json
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))          # chip_smoke.py sits at the root

import chip_smoke  # noqa: E402


def _env(tmp_path, **extra):
    return dict(PATH="/usr/bin:/bin", HOME=str(tmp_path),
                PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu",
                JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
                **extra)


def _phases(out: str) -> dict:
    return {d["phase"]: d for d in (json.loads(line) for line in
                                    out.splitlines() if line.startswith("{"))
            if "phase" in d}


def test_no_tpu_exits_nonzero_without_result(tmp_path):
    p = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, timeout=300,
                       env=_env(tmp_path))
    assert p.returncode != 0
    assert '"ok"' not in p.stdout
    assert "needs a TPU" in p.stderr


def test_serve_phase_rehearsal(capsys):
    chip_smoke.serve_phase(0, reduced=True, backend="pallas")
    phases = _phases(capsys.readouterr().out)
    assert phases["engine"]["scan"] == phases["engine"]["attn"] == "pallas"
    assert phases["serve"]["requests"] == chip_smoke.N_REQUESTS
    assert phases["scan_check"]["ids_equal_frac"] == 1.0
    assert phases["decode_attn_check"]["max_abs_err"] < 2e-2
    assert phases["fallbacks"]["count"] == 0


def test_four_chip_phase_rehearsal(tmp_path):
    code = ("import chip_smoke\n"
            "chip_smoke.four_chip_phase(0, reduced=True, backend='pallas')\n")
    p = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=900, cwd=str(ROOT), env=_env(
            tmp_path, XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert p.returncode == 0, p.stderr[-3000:]
    phases = _phases(p.stdout)
    assert phases["disaggregated"]["retrieval_devices"] == [1, 2, 3]
    assert phases["parity"]["tokens_equal"]
    assert phases["parity"]["retrieved_ids_equal"]


def test_compile_cache_location(monkeypatch):
    from repro.launch import cache

    calls = []
    monkeypatch.setattr(cache.jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert cache.setup_compile_cache() == "/elsewhere"
    assert calls == []                     # JAX reads the variable itself
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    here = str(ROOT / ".jax_cache")
    assert cache.setup_compile_cache() == here
    assert calls == [("jax_compilation_cache_dir", here)]
