"""The chip path, checked on a CPU host: where compiled programs are
cached, interpret mode decided when a kernel is traced (never when a
module is imported), and chip_smoke.py refusing to run without a TPU."""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]


def _python(code: str, tmp_path, **env):
    """Run ``code`` in a fresh interpreter on the CPU, from ``tmp_path``."""
    child_env = {k: v for k, v in os.environ.items()
                 if k != "JAX_COMPILATION_CACHE_DIR"}
    child_env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"), **env)
    return subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                          env=child_env, capture_output=True, text=True,
                          timeout=120)


@pytest.mark.parametrize("env_set", [False, True])
def test_compile_cache_goes_to_the_env_dir_else_the_fixed_one(tmp_path,
                                                              env_set):
    code = (
        "import jax\n"
        "from repro.compile_cache import enable_compile_cache\n"
        "print(enable_compile_cache('fixed'))\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: x * 2 + 1)(jax.numpy.ones(3)).block_until_ready()\n"
    )
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "env")} if env_set else {}
    proc = _python(code, tmp_path, **env)
    assert proc.returncode == 0, proc.stderr
    used, unused = ((tmp_path / "env", tmp_path / "fixed") if env_set
                    else (tmp_path / "fixed", tmp_path / "env"))
    assert proc.stdout.strip() == str(used)
    assert any(used.iterdir())
    assert not unused.exists()


def test_enable_compile_cache_leaves_jax_alone_when_env_is_set(monkeypatch,
                                                               tmp_path):
    from repro.compile_cache import enable_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache(tmp_path / "fixed") == str(tmp_path / "env")
    assert jax.config.jax_compilation_cache_dir == before


def test_importing_the_kernels_starts_no_backend(tmp_path):
    code = (
        "import repro.apps.radar, repro.core.autotune, repro.kernels.cases\n"
        "import repro.serve.session_engine\n"
        "from repro.kernels.cases import cases\n"
        "cases()\n"
        "from jax._src import xla_bridge\n"
        "print(len(xla_bridge._backends))\n"
    )
    proc = _python(code, tmp_path)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "0"


@pytest.mark.parametrize("platform, interpret", [("cpu", True),
                                                 ("tpu", False)])
def test_interpret_mode_follows_the_platform_at_trace_time(monkeypatch,
                                                           platform,
                                                           interpret):
    from repro.kernels import resolve_interpret

    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    assert resolve_interpret() is interpret
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False


def test_chip_smoke_refuses_without_a_tpu(tmp_path):
    proc = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")], cwd=tmp_path,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=120)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    for line in proc.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def test_chip_smoke_radar_phase_on_the_cpu():
    """The radar checks at a reduced SAR scale on the CPU device, so the
    script's own logic is exercised between chip runs."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    chip_smoke.radar_phase(jax.devices()[0], sar_scale=64)
