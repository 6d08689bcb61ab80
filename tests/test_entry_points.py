"""Guards of the entry points that run on the chip: ``chip_smoke.py``
refuses to report success without a TPU, and the compile cache lives
where ``repro.launch.compile_cache`` says."""

import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch import compile_cache

REPO = Path(__file__).resolve().parents[1]


def _run_smoke(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_chip_smoke_fails_without_a_tpu():
    r = _run_smoke(REPO)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "needs a TPU" in r.stderr


def test_chip_smoke_fails_without_the_repo(tmp_path):
    """Alone in a directory, the script cannot import the system and
    must not pass for it."""
    shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    r = _run_smoke(tmp_path)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout


def test_compile_cache_honours_the_environment(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/shared/jax-cache")
    assert compile_cache.compile_cache_dir() == "/shared/jax-cache"
    assert compile_cache.enable_compile_cache() == "/shared/jax-cache"
    assert calls == []              # JAX reads the variable itself


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = str(REPO / ".jax_cache")
    assert compile_cache.compile_cache_dir() == path
    assert compile_cache.enable_compile_cache() == path
    assert calls == [("jax_compilation_cache_dir", path)]
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().splitlines()
