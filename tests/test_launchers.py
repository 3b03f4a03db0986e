"""Launcher CLI integration tests (subprocess, reduced configs)."""
import json
import os
import re
import subprocess
import sys

import jax

from repro.launch import compile_cache

BASE = os.path.join(os.path.dirname(__file__), "..")


def run_cli(args, n_devices=0, timeout=120):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(BASE, "src")
    if n_devices:
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={n_devices}")
    return subprocess.run([sys.executable, "-m"] + args, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_train_cli_single_device(tmp_path):
    p = run_cli(["repro.launch.train", "--arch", "qwen3-0.6b", "--reduced",
                 "--steps", "4", "--batch", "2", "--seq", "32",
                 "--log-every", "2",
                 "--ckpt-dir", str(tmp_path)])
    assert p.returncode == 0, p.stderr[-2000:]
    assert "done: steps=4" in p.stdout
    assert any(f.startswith("step_4") for f in os.listdir(tmp_path))


def test_train_cli_sharded_mesh(tmp_path):
    p = run_cli(["repro.launch.train", "--arch", "phi3-mini-3.8b",
                 "--reduced", "--steps", "2", "--batch", "4", "--seq", "32",
                 "--mesh", "2x2", "--fake-devices", "4",
                 "--ckpt-dir", str(tmp_path)], n_devices=4)
    assert p.returncode == 0, p.stderr[-2000:]
    assert "mesh {'data': 2, 'model': 2}" in p.stdout
    # The state really is spread: four near-equal shares of the total.
    m = re.search(r"state bytes: total (\d+), per device \[([\d, ]+)\]",
                  p.stdout)
    total, shares = int(m.group(1)), [int(v) for v in m.group(2).split(",")]
    assert len(shares) == 4
    assert all(0.2 <= b / total <= 0.35 for b in shares), (total, shares)


def test_serve_cli(tmp_path):
    out = tmp_path / "tokens.json"
    p = run_cli(["repro.launch.serve", "--arch", "qwen3-0.6b", "--reduced",
                 "--slots", "2", "--requests", "3", "--max-new", "4",
                 "--max-len", "64", "--tokens-out", str(out)])
    assert p.returncode == 0, p.stderr[-2000:]
    assert "served 3/3 requests" in p.stdout
    tokens = json.loads(out.read_text())
    assert sorted(tokens) == ["0", "1", "2"]
    assert all(len(t) == 4 for t in tokens.values())


def test_compile_cache_placement(monkeypatch):
    """The entry points' cache: $JAX_COMPILATION_CACHE_DIR when set (left
    to JAX, nothing set in code), else the fixed <checkout>/.jax_cache."""
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV, "/elsewhere/cache")
        assert compile_cache.use_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv(compile_cache.ENV)
        want = os.path.join(os.path.abspath(BASE), ".jax_cache")
        assert compile_cache.use_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_chip_smoke_refuses_without_tpu():
    """chip_smoke.py is a chip check: on the CPU it fails before any phase
    and never prints its ok line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(BASE, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"ok": true' not in p.stdout
    assert "no TPU" in p.stderr
