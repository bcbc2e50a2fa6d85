"""The command-line entry points: compile-cache placement, the device mesh
of a non-smoke serve run, and chip_smoke.py's refusal to run off the chip."""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import cache
from repro.launch.mesh import make_device_mesh

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_leaves_env_dir_alone(monkeypatch, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/jax-cache")
    before = jax.config.jax_compilation_cache_dir
    assert cache.enable_compile_cache() == "/elsewhere/jax-cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    d = cache.enable_compile_cache()
    assert d == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == d
    assert cache.enable_compile_cache() == d            # same dir every call


def test_importing_the_library_sets_no_cache_dir():
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax, repro, repro.ops, repro.serving; "
         "print(jax.config.jax_compilation_cache_dir)"],
        env=env, capture_output=True, text=True, check=True)
    assert out.stdout.strip() == "None"


def test_device_mesh_spans_the_devices_present():
    mesh = make_device_mesh()
    assert mesh.axis_names == ("data", "model")
    assert mesh.devices.size == len(jax.devices())


@pytest.mark.parametrize("interpret", [False, True])
def test_chip_smoke_refuses_to_run_off_the_chip(interpret, monkeypatch, capsys,
                                                restore_cache_dir):
    sys.path.insert(0, str(ROOT))
    try:
        import chip_smoke
    finally:
        sys.path.remove(str(ROOT))
    if interpret:
        monkeypatch.setenv("REPRO_INTERPRET", "1")
    else:
        monkeypatch.delenv("REPRO_INTERPRET", raising=False)
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert not any(line.startswith("{") and json.loads(line).get("ok")
                   for line in out.splitlines())
