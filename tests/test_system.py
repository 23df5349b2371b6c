"""End-to-end behaviour tests: train a reduced model to decreasing loss, then
serve from it; dry-run artifact sanity."""
import json
import os

import jax
import numpy as np
import pytest


def test_train_driver_end_to_end(tmp_path):
    from repro.launch.train import main

    losses, step_times = main([
        "--arch", "qwen1.5-0.5b", "--reduce", "16", "--steps", "12",
        "--batch", "4", "--seq", "64", "--ckpt-dir", str(tmp_path / "ck"),
        "--ckpt-every", "6",
    ])
    assert len(losses) == len(step_times) == 12
    assert np.mean(losses[-3:]) < np.mean(losses[:3])
    # checkpoint written
    assert any(d.startswith("step_") for d in os.listdir(tmp_path / "ck"))


def test_compile_cache_dir_is_placed_from_outside(monkeypatch):
    from repro.launch.train import DEFAULT_COMPILE_CACHE, enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/cache/from/env")
        assert enable_compile_cache() == "/cache/from/env"
        assert jax.config.jax_compilation_cache_dir == before  # JAX reads env
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(DEFAULT_COMPILE_CACHE)
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_COMPILE_CACHE)
        # a fixed directory at the root of the checkout
        assert (DEFAULT_COMPILE_CACHE.parent / "chip_smoke.py").exists()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_serve_driver_end_to_end():
    from repro.launch.serve import main

    reqs = main(["--arch", "qwen1.5-0.5b", "--reduce", "32", "--slots", "2",
                 "--max-len", "32", "--new-tokens", "4", "--requests", "3"])
    assert all(len(r.out) == 4 for r in reqs)


def test_dryrun_single_cell_artifact(tmp_path):
    """The dry-run entry point works end-to-end in a subprocess (512 fake
    devices must not leak into this session)."""
    import subprocess
    import sys

    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    out = str(tmp_path)
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "qwen1.5-0.5b",
         "--shape", "decode_32k", "--single-pod-only", "--out", out],
        env=env, capture_output=True, text=True, timeout=1200,
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    art = json.load(open(os.path.join(out, "qwen1.5-0.5b_decode_32k_pod16x16.json")))
    assert art["status"] == "ok"
    assert art["memory"]["peak_est_bytes"] < 16e9  # fits a v5e chip
    assert art["flops_per_dev"] > 0
    # this session still sees exactly 1 device
    assert len(jax.devices()) == 1
