"""Where the program runs: interpret mode from the backend, the launchers'
mesh over the devices jax sees, and the compile cache's directory."""
import os

import jax
import pytest

from repro import compile_cache, kernels
from repro.launch.mesh import launcher_mesh

AXES = ("clusters", "data", "model")


def test_pallas_interpret_follows_the_backend(monkeypatch):
    assert kernels.pallas_interpret()              # this lane: the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert not kernels.pallas_interpret()
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        kernels.pallas_interpret()


def test_launcher_mesh_spans_every_device():
    n = jax.device_count()
    mesh = launcher_mesh((n, 1, 1), AXES, devices=n)
    assert mesh.devices.size == n and mesh.axis_names == AXES


def test_launcher_mesh_takes_the_first_devices_when_smaller():
    mesh = launcher_mesh((1, 1, 1), AXES)
    assert list(mesh.devices.flat) == jax.devices()[:1]


@pytest.mark.parametrize("extra_in", ["devices", "shape"])
def test_launcher_mesh_refuses_a_device_mismatch(extra_in):
    n = jax.device_count()
    shape = (n + (extra_in == "shape"), 1, 1)
    devices = n + 1 if extra_in == "devices" else None
    with pytest.raises(SystemExit):
        launcher_mesh(shape, AXES, devices=devices)


def test_compile_cache_follows_the_environment(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert os.environ[compile_cache.ENV] == str(tmp_path)


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compile_cache.DEFAULT_DIR == os.path.join(root, ".jax_cache")
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    try:
        assert compile_cache.enable() == compile_cache.DEFAULT_DIR
        assert jax.config.jax_compilation_cache_dir == \
            compile_cache.DEFAULT_DIR
        # exported, so child processes use the same directory
        assert os.environ[compile_cache.ENV] == compile_cache.DEFAULT_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
