"""Start-up rules: nothing on the way to the device may hide which device it
is. ``master="tpu"`` means TPU or raise, the compile cache is placed from
outside or at one fixed in-checkout path, the peaks table knows exact device
kinds only, the package never chooses the Pallas interpreter, and
``chip_smoke.py`` refuses to run without a chip."""

import os
import subprocess
import sys
import types

import jax
import numpy as np
import pytest

from cycloneml_tpu import mesh as mesh_mod
from cycloneml_tpu.observe import costs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_tpu_master_without_tpu_raises():
    with pytest.raises(RuntimeError, match="needs an attached TPU"):
        mesh_mod.MeshRuntime("tpu")
    with pytest.raises(RuntimeError, match="local-mesh"):
        mesh_mod.probe_device_count("tpu")


class _ConfigRecorder:
    def __init__(self):
        self.updates = {}

    def update(self, key, value):
        self.updates[key] = value


def test_compile_cache_placed_from_outside_is_left_alone(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert mesh_mod.compilation_cache_dir() == "/some/dir"
    # a real CPU mesh: the package must not touch the directory jax holds
    before = jax.config.jax_compilation_cache_dir
    mesh_mod.MeshRuntime("local-mesh[1]")
    assert jax.config.jax_compilation_cache_dir == before
    # and on an accelerator it still sets no directory, only thresholds
    fake = types.SimpleNamespace(config=_ConfigRecorder())
    mesh_mod._configure_compilation_cache(fake, "tpu")
    assert "jax_compilation_cache_dir" not in fake.config.updates
    assert fake.config.updates  # the persistence thresholds


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = mesh_mod.compilation_cache_dir()
    assert path == mesh_mod.DEFAULT_COMPILATION_CACHE_DIR
    assert path == os.path.join(REPO, ".jax_compilation_cache")
    # git-ignored: the cache is never part of what a checkout carries
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_compilation_cache/" in f.read().split()
    before = jax.config.jax_compilation_cache_dir
    mesh_mod.MeshRuntime("local-mesh[1]")   # host platform: cache stays off
    assert jax.config.jax_compilation_cache_dir == before
    fake = types.SimpleNamespace(config=_ConfigRecorder())
    mesh_mod._configure_compilation_cache(fake, "tpu")
    assert fake.config.updates["jax_compilation_cache_dir"] == path


def test_backend_peaks_exact_device_kind_only():
    assert costs.backend_peaks() == (None, None)   # the CPU test platform
    v5e = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert costs.backend_peaks(v5e) == (197e12, 819e9)
    # a kind that merely CONTAINS a known name gets nothing borrowed
    for kind in ("TPU v5", "TPU v5p", "TPU v99"):
        unknown = types.SimpleNamespace(platform="tpu", device_kind=kind)
        with pytest.raises(KeyError, match="no published peaks"):
            costs.backend_peaks(unknown)


def test_kernel_wrappers_never_interpret_on_their_own():
    from cycloneml_tpu.ops import (fused_binary_logistic,
                                   fused_kmeans_assign,
                                   fused_moment_gramian)
    rng = np.random.RandomState(0)
    x = rng.randn(64, 8).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float32)
    w = np.ones(64, np.float32)
    for call in (lambda: fused_binary_logistic(x, y, w, np.zeros(9), 8),
                 lambda: fused_moment_gramian(
                     x.astype("bfloat16"), y, w, feature_major=True,
                     lane_tile=64, weighted=False),
                 lambda: fused_kmeans_assign(x, x[:4])):
        with pytest.raises(ValueError, match="[Oo]nly interpret mode"):
            call()


def test_chip_smoke_refuses_to_run_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")], cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert "no TPU attached" in proc.stderr
    assert proc.stdout == ""
