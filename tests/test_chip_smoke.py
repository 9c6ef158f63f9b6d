"""CPU-side guards of the chip entry points: ``chip_smoke.py`` refuses to
run without a TPU, and the compile-cache helper places the cache where
the environment or the checkout says."""
import os
import shutil
import subprocess
import sys

import jax
import pytest

from _subproc import subprocess_env
from repro import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")
CACHE_OPTIONS = (
    "jax_compilation_cache_dir",
    "jax_persistent_cache_min_compile_time_secs",
    "jax_persistent_cache_min_entry_size_bytes",
)


def _run_smoke(cwd, script):
    return subprocess.run(
        [sys.executable, script], cwd=cwd, env=subprocess_env(),
        capture_output=True, text=True, timeout=300,
    )


def _assert_refused(proc):
    assert proc.returncode != 0, proc.stdout
    assert '"ok"' not in proc.stdout, proc.stdout


def test_chip_smoke_fails_without_tpu():
    proc = _run_smoke(ROOT, SMOKE)
    _assert_refused(proc)
    assert "needs a TPU" in proc.stderr


def test_chip_smoke_fails_without_the_repo(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    _assert_refused(_run_smoke(str(tmp_path), "chip_smoke.py"))


@pytest.fixture
def restore_cache_config():
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = {name: getattr(jax.config, name) for name in CACHE_OPTIONS}
    yield
    for name, value in before.items():
        jax.config.update(name, value)
    cc.reset_cache()


def test_compile_cache_honours_env(monkeypatch, tmp_path, restore_cache_config):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    dir_before = jax.config.jax_compilation_cache_dir
    assert compile_cache.setup_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the helper sets no other directory
    assert jax.config.jax_compilation_cache_dir == dir_before
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1


def test_compile_cache_defaults_to_fixed_checkout_path(
    monkeypatch, restore_cache_config
):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first = compile_cache.setup_compile_cache()
    assert compile_cache.setup_compile_cache() == first
    assert first == os.path.join(ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
