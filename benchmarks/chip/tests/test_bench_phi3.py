"""The Phi-3 family (``families/phi3_lm.py``) against the program.

A tiny Phi-4-mini (three q heads per kv head, rotary on 0.75 of the head,
eps 1e-5) computed in float32 on both sides, through ``spmd_partition`` on
one device and on a 2x2 mesh of four virtual devices: its loss, its first
gradient and three Adafactor steps match the reference far inside the
cell's limits, and a program that rotates the whole head does not.
"""
import json
import math
import os
import subprocess
import sys
import textwrap

import jax
import pytest

from benchmarks.chip import run as run_mod
from benchmarks.chip.cell import Cell, _module
from benchmarks.chip.families import dense_lm, phi3_lm
from benchmarks.chip.tests.bench_tiny import (ROOT, TINY_TRAFFIC,
                                              make_checkout)

TINY_PHI = {
    "name": "tinyphi", "family": "phi3_lm", "source": "test",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 6,
    "num_hidden_layers": 2, "num_key_value_heads": 2, "head_dim": 16,
    "partial_rotary_factor": 0.75, "rms_norm_eps": 1e-05,
    "rope_theta": 10000.0, "tie_word_embeddings": True, "vocab_size": 512,
    "qkv_bias": False, "compute_dtype": "float32", "param_dtype": "float32",
}
# float32 on both sides: what is left is the order of sums (the chunked
# attention's online softmax, the blocked loss), 1.6e-6 at most over the
# seeds below on the CPU; rotating the whole head reads 5e-3 / 3.4e-2 /
# 3.1e-2 or more
CLOSE = 2e-5


def l16():
    return json.loads((ROOT / "benchmarks" / "chip" / "configs" /
                       "phi4-mini-3.8b-L16.json").read_text())


def test_program_config_runs_the_file():
    from repro.configs.base import ModelConfig

    cfg = ModelConfig(**phi3_lm.program_config(
        l16(), json.loads((ROOT / "benchmarks" / "chip" / "traffic" /
                           "2x2.b8s1k.json").read_text())))
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.dh, cfg.d_ff, cfg.vocab_size) == (16, 3072, 24, 8, 128, 8192,
                                                  200064)
    assert (cfg.rotary_dims, cfg.rope_base, cfg.norm_eps) == (96, 1e4, 1e-5)
    assert (cfg.xent_chunk, cfg.attn_chunk, cfg.qkv_bias) == (0, 1024, False)


def test_the_cell_is_112_tflop_per_step_and_2_23b_parameters():
    c = l16()
    got = phi3_lm.step_flops(c, 8, 1024)
    assert got == dense_lm.step_flops(c, 8, 1024)
    assert got / 1e12 == pytest.approx(111.85, abs=0.01)
    specs = dense_lm.weight_specs(c)
    # 16 x (2 x 3072^2 + 2 x 3072 x 1024 + 3 x 3072 x 8192 + 2 x 3072)
    # + 200064 x 3072 + 3072
    assert sum(math.prod(s) for s, _, _ in specs.values()) == (
        16 * (2 * 3072**2 + 2 * 3072 * 1024 + 3 * 3072 * 8192 + 2 * 3072)
        + 200064 * 3072 + 3072)


def test_weights_are_the_programs_parameters():
    from repro.configs.base import ModelConfig, get_strategy
    from repro.train.loop import TrainConfig, init_state
    from repro.train.optimizer import get_optimizer

    c = l16()
    cfg = ModelConfig(**phi3_lm.program_config(c, {}))
    st, opt = get_strategy("2d_finalized"), get_optimizer("adafactor")
    params = jax.eval_shape(lambda: init_state(
        cfg, st, opt, TrainConfig(), jax.random.PRNGKey(0)))["params"]
    want = {n: x.shape for n, x in zip(run_mod._names(params),
                                       jax.tree_util.tree_leaves(params))}
    made = jax.eval_shape(lambda k: phi3_lm.make_weights(c, k),
                          jax.random.PRNGKey(0))
    assert {n: x.shape for n, x in made.items()} == want


def test_reference_rotates_the_leading_dims_only():
    import jax.numpy as jnp

    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 2, 16))
    out = phi3_lm._rope(x, 1e4, 12)
    assert (out[..., 12:] == x[..., 12:]).all()
    assert not jnp.allclose(out[..., :12], x[..., :12])


@pytest.fixture
def tiny_phi(tmp_path):
    make_checkout(tmp_path, config=TINY_PHI,
                  traffic=dict(TINY_TRAFFIC, name="b8", batch=8))
    return Cell(tmp_path, "tinyphi.b8")


@pytest.mark.parametrize("seed", [7, 2**31 + 5])
def test_tiny_phi_matches_the_reference_on_one_device(tiny_phi, seed):
    res = run_mod.run_cell(tiny_phi, jax.devices()[:1], seed, 0.5,
                           trace=False, log=lambda *_: None)
    assert res["correct"] is True
    assert all(v["value"] < CLOSE for v in res["checks"].values()), \
        res["checks"]


def test_a_program_that_rotates_the_whole_head_does_not_match(tiny_phi,
                                                              monkeypatch):
    fam = tiny_phi.family
    config = fam.program_config
    monkeypatch.setattr(fam, "program_config", lambda c, t: dict(
        config(c, t), rope_fraction=1.0))
    res = run_mod.run_cell(tiny_phi, jax.devices()[:1], 7, 0.5, trace=False,
                           log=lambda *_: None)
    assert max(v["value"] for v in res["checks"].values()) > 100 * CLOSE


SCRIPT = textwrap.dedent("""
    import json, pathlib, sys, tempfile
    sys.path[:0] = [{root!r}, {src!r}]
    import jax
    from benchmarks.chip.tests.bench_tiny import TINY_TRAFFIC, make_checkout
    from benchmarks.chip.tests.test_bench_phi3 import TINY_PHI
    from benchmarks.chip import run as run_mod
    from benchmarks.chip.cell import Cell

    traffic = dict(TINY_TRAFFIC, name="b8x4", batch=8,
                   mesh={{"data": 2, "model": 2}})
    dest = pathlib.Path(tempfile.mkdtemp())
    make_checkout(dest, config=TINY_PHI, traffic=traffic, chips=4)
    cell = Cell(dest, "tinyphi.b8x4")
    res = run_mod.run_cell(cell, jax.devices()[:4], 2**31 + 5, 0.5,
                           trace=False, log=lambda *_: None)
    from repro.core.partitioner import process_plan_cache_entries
    (entry,) = process_plan_cache_entries()
    print(json.dumps({{"correct": res["correct"], "checks": res["checks"],
                      "fallbacks": entry.plan.stats.fallbacks,
                      "sharded_gathers": entry.plan.stats.sharded_gathers}}))
""")


def test_tiny_phi_matches_the_reference_on_a_2x2_mesh():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    script = SCRIPT.format(root=str(ROOT), src=str(ROOT / "src"))
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["correct"] is True
    assert all(v["value"] < CLOSE for v in got["checks"].values()), got
    assert "gather" not in got["fallbacks"]
    assert "scatter-add" not in got["fallbacks"]
    assert got["sharded_gathers"] == 4


def test_fallback_gather_mb_reads_the_plan_cache(monkeypatch):
    from repro.core import partitioner

    class Entry:
        def __init__(self, b):
            self.plan = type("Plan", (), {"stats": type(
                "Stats", (), {"fallback_bytes": b})()})()

    read = _module(ROOT / "benchmarks/chip/metrics/fallback_gather_mb.py").read
    monkeypatch.setattr(partitioner, "process_plan_cache_entries",
                        lambda: [Entry(16384.0)])
    assert read({}) == 0.016384
    monkeypatch.setattr(partitioner, "process_plan_cache_entries",
                        lambda: [Entry(1.0), Entry(2.0)])
    assert read({}) is None
    # a program whose plans count no such bytes (the parent) reads nothing
    monkeypatch.setattr(partitioner, "process_plan_cache_entries",
                        lambda: [type("E", (), {"plan": type(
                            "P", (), {"stats": object()})()})()])
    assert read({}) is None
