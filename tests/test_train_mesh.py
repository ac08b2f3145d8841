"""launch.train places its workers by the device count, and keeps its
compile cache where JAX_COMPILATION_CACHE_DIR says or at one fixed path.

The placement test runs ``repro.launch.train.main`` in subprocesses: the
device count is fixed when JAX starts, so one child sees 4 virtual CPU
devices (one worker per device, ``shard_map`` on a 'data' mesh) and one
sees a single device (the workers vmapped on it). gs-SGD with the tree
all-reduce must give the same loss history on both.
"""

import json
import os
import subprocess
import sys

import jax
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = r"""
import json, sys
from repro.launch import train
out = train.main(sys.argv[1:])
print(json.dumps(out["history"]))
"""

ARGV = ["--arch", "qwen3-4b", "--smoke", "--workers", "4", "--steps", "3",
        "--batch", "8", "--seq", "16", "--compressor", "gs-sgd",
        "--width", "2048", "--k", "1024", "--allreduce-mode", "tree"]


def _run(n_devices: int) -> tuple[str, list]:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count="
                         f"{n_devices}",
               PYTHONPATH=os.path.join(REPO, "src") + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run([sys.executable, "-c", _SCRIPT, *ARGV], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout, json.loads(out.stdout.strip().splitlines()[-1])


def test_mesh_and_vmap_placements_give_the_same_losses():
    mesh_out, mesh = _run(4)
    vmap_out, vmapped = _run(1)
    assert "4 workers: one per device on a 'data' mesh" in mesh_out
    assert "4 workers: vmapped on one device" in vmap_out
    assert len(mesh) == 3 and np.all(np.isfinite(mesh))
    np.testing.assert_allclose(mesh, vmapped, rtol=1e-6, atol=1e-6)


def test_compile_cache_location(monkeypatch):
    from repro.launch import compile_cache as cc
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert cc.configure_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before  # JAX reads env
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert cc.configure_compile_cache() is None            # CPU: no cache
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        assert cc.configure_compile_cache() == cc.CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == cc.CACHE_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert cc.CACHE_DIR == os.path.join(REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
