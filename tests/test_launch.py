"""Entry-point plumbing: device selection and the persistent compile cache."""

import os
import subprocess
import sys

import jax
import pytest

from repro import compile_cache
from repro.launch.integrate import select_devices

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_select_devices_takes_exactly_n():
    assert select_devices(1) == jax.devices()[:1]
    with pytest.raises(SystemExit, match="--devices 2 but 1 device"):
        select_devices(len(jax.devices()) + 1)
    with pytest.raises(SystemExit):
        select_devices(0)


def test_integrate_cli_runs_on_exactly_n_of_the_visible_devices():
    """``--devices 2`` with 4 visible devices runs on 2, in this process."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    proc = subprocess.run(
        [
            sys.executable, "-m", "repro.launch.integrate",
            "--devices", "2", "--integrand", "f4", "--d", "2",
            "--rel-tol", "1e-5", "--capacity", str(1 << 10), "--strict",
        ],
        capture_output=True,
        text=True,
        timeout=600,
        cwd=_REPO,
        env=env,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "devices=2 " in proc.stdout, proc.stdout


@pytest.fixture
def cache_dir_config():
    """Restore the process-wide cache settings after a test changes them."""
    before = jax.config.jax_compilation_cache_dir
    min_s = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", min_s)


def test_compile_cache_honours_env(monkeypatch, cache_dir_config, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # dir not set
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0


def test_compile_cache_defaults_to_fixed_repo_path(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(_REPO, ".jax_cache")
    assert compile_cache.DEFAULT_DIR == want
    assert compile_cache.enable() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    assert compile_cache.enable() == want  # same path every call
