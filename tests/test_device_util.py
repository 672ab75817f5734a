"""Device-backend facts and compile-cache placement (utils/device.py), and
the no-fallback contract of the chip entry points: with no accelerator,
bench.py and chip_smoke.py fail instead of carrying on with the CPU."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import jax

from celestia_tpu.utils import device

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_config():
    """Restore the process's compile-cache directory after a test."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("env_set", [True, False], ids=["env-set", "env-unset"])
def test_compile_cache_placement(monkeypatch, tmp_path, cache_config, env_set):
    before = jax.config.jax_compilation_cache_dir
    if env_set:
        # JAX reads the variable itself: nothing is set in code
        monkeypatch.setenv(device.ENV_CACHE_DIR, str(tmp_path))
        assert device.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
    else:
        # the one fixed path inside the checkout, never a temporary name
        monkeypatch.delenv(device.ENV_CACHE_DIR, raising=False)
        want = str(REPO / ".jax_cache")
        assert device.enable_compile_cache() == want
        assert device.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


def test_host_regime_raises_on_broken_backend(monkeypatch):
    """A backend that fails to initialize is an error, never read as the
    host regime — and nothing is cached, so a later call asks again."""
    monkeypatch.setattr(device, "_host_regime", None)

    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "default_backend", broken)
    with pytest.raises(RuntimeError, match="initialize backend"):
        device.host_regime()
    assert device._host_regime is None
    monkeypatch.undo()
    assert device.host_regime() is True  # the CPU test backend


def _run(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, *args], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=120,
    )


@pytest.mark.parametrize(
    "script,needle",
    [("chip_smoke.py", "no TPU found"), ("bench.py", "no accelerator found")],
)
def test_chip_entry_points_refuse_the_cpu(script, needle):
    out = _run([script], REPO)
    assert out.returncode != 0
    assert needle in out.stderr
    assert not out.stdout.strip()  # no result line


def test_chip_smoke_alone_fails(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    out = _run(["chip_smoke.py"], tmp_path)
    assert out.returncode != 0
    lines = out.stdout.strip().splitlines()
    assert not any(line.startswith("{") and json.loads(line).get("ok") for line in lines)
