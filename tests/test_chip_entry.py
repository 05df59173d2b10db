"""The chip entry points on the CPU: compile-cache placement, and a smoke
script that refuses to run anywhere but on a TPU."""

import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

REPO = Path(__file__).resolve().parent.parent


class TestCompileCache:
    @pytest.fixture
    def updates(self, monkeypatch):
        """Record config updates instead of turning the cache on here."""
        seen = []
        monkeypatch.setattr(jax.config, "update", lambda k, v: seen.append((k, v)))
        return seen

    def test_env_var_wins(self, monkeypatch, tmp_path, updates):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert updates == []  # JAX reads the variable itself

    def test_default_is_the_fixed_checkout_path(self, monkeypatch, updates):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = compile_cache.enable_compile_cache()
        assert path == str(REPO / ".jax_cache")
        assert updates == [("jax_compilation_cache_dir", path)]

    def test_checkout_cache_is_ignored_by_git(self):
        assert ".jax_cache/" in (REPO / ".gitignore").read_text().splitlines()


def _run_smoke(cwd: Path, tmp_path: Path) -> subprocess.CompletedProcess:
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=240)


class TestChipSmokeOffTheChip:
    def test_cpu_exits_nonzero_without_a_result(self, tmp_path):
        res = _run_smoke(REPO, tmp_path)
        assert res.returncode != 0
        assert "platform=cpu" in res.stdout
        assert '"ok"' not in res.stdout

    def test_alone_without_the_repo_fails(self, tmp_path):
        alone = tmp_path / "alone"
        alone.mkdir()
        shutil.copy(REPO / "chip_smoke.py", alone)
        res = _run_smoke(alone, tmp_path)
        assert res.returncode != 0
        assert '"ok"' not in res.stdout
