"""The ``flash_fwd_calls`` reader, on the plan of a reduced Qwen train step
traced for the chip (the kernel path) on the CPU, and on plans that count
no kernels."""
import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip.cell import _module
from benchmarks.chip.tests.bench_tiny import ROOT
from repro.core import partitioner
from repro.core.compat import make_jax_mesh
from repro.core.partitioner import clear_process_plan_cache, spmd_partition
from repro.core.sharding import Mesh


@pytest.fixture
def reader():
    clear_process_plan_cache()
    yield _module(ROOT / "benchmarks/chip/metrics/flash_fwd_calls.py").read
    clear_process_plan_cache()


def _plan_train_step(cfg):
    """Build, and cache, the plan of ``cfg``'s train step on a 1x1 mesh."""
    from repro.configs.base import get_strategy
    from repro.train.loop import TrainConfig, init_state, make_train_step
    from repro.train.optimizer import get_optimizer

    st, tc = get_strategy("2d_finalized"), TrainConfig()
    opt = get_optimizer("adafactor", lr=0.01)
    state = jax.eval_shape(
        lambda: init_state(cfg, st, opt, tc, jax.random.PRNGKey(0)))
    batch = {k: jax.ShapeDtypeStruct((2, 256), jnp.int32)
             for k in ("tokens", "labels")}
    step = make_train_step(cfg, st, opt, tc)
    leaves, tdef = jax.tree_util.tree_flatten((state, batch))
    runner = spmd_partition(
        lambda *xs: step(*jax.tree_util.tree_unflatten(tdef, xs))[1]["loss"],
        make_jax_mesh((1, 1), ("data", "model")),
        Mesh.create((1, 1), ("data", "model")))
    jax.eval_shape(runner, *leaves)


@pytest.mark.parametrize("remat", ["dots", "none"])
def test_reads_one_forward_call_per_layer(reader, monkeypatch, remat):
    """Under ``remat="dots"`` the backward reads the forward's saved
    residuals, and without remat there is nothing to recompute: one call
    per layer either way."""
    from repro.configs.registry import get_config
    from repro.kernels import ops
    from repro.launch.train import reduced_config

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = reduced_config(get_config("qwen1.5-0.5b"), 16).with_(
        d_model=128, xent_chunk=0, attn_chunk=256, remat=remat)
    assert (cfg.num_layers, cfg.num_heads, cfg.dh) == (2, 2, 64)
    _plan_train_step(cfg)
    assert reader({}) == 2.0


def test_reads_nothing_without_one_counting_plan(reader, monkeypatch):
    def entry(stats):
        return type("E", (), {"plan": type("P", (), {"stats": stats})()})()

    counting = type("S", (), {"kernel_calls": {"_flash_attention_kernel": 48,
                                               "other": 1}})()
    monkeypatch.setattr(partitioner, "process_plan_cache_entries",
                        lambda: [entry(counting)])
    assert reader({}) == 48.0
    monkeypatch.setattr(partitioner, "process_plan_cache_entries",
                        lambda: [entry(counting), entry(counting)])
    assert reader({}) is None
    # a plan of the chunked loop counts no forward kernel
    monkeypatch.setattr(partitioner, "process_plan_cache_entries",
                        lambda: [entry(type("S", (), {"kernel_calls": {}})())])
    assert reader({}) == 0.0
    # a program whose plans count no kernels (the parent) reads nothing
    monkeypatch.setattr(partitioner, "process_plan_cache_entries",
                        lambda: [entry(object())])
    assert reader({}) is None
