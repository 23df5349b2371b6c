"""Elastic recovery on 8 fake devices: the plan-lowered reshard restore is
bit-identical to the host-mediated path, and an injected device loss
mid-training recovers in-process onto a *smaller* derived mesh with a
continuous loss curve (no replayed or skipped batches)."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from repro.configs.base import ModelConfig, get_strategy
from repro.core.compat import assert_close, make_jax_mesh, set_mesh
from repro.core.sharding import Mesh
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.launch.elastic import (
    ElasticCoordinator,
    FaultInjector,
    derive_mesh,
    sharding_problem,
    specs_by_key,
)
from repro.models import api
from repro.models.layers import tree_init
from repro.train import checkpoint as ckpt
from repro.train.loop import TrainConfig, TrainLoop, state_partition_specs
from repro.train.optimizer import get_optimizer

st = get_strategy("2d_finalized")
CFG = ModelConfig(
    name="t", family="dense", num_layers=2, d_model=32, num_heads=4,
    num_kv_heads=2, d_ff=64, vocab_size=64, attn_chunk=16, remat="none",
    qkv_bias=True,
)


def test_reshard_program_restore_bit_identical(tmp_path):
    """Save sharded on the full (2,4) mesh; restore onto a shrunk (2,2) mesh
    over the first 4 devices via the compiled reshard program — every leaf
    bit-identical to the host-mediated device_put restore."""
    jmesh = make_jax_mesh((2, 4), ("data", "model"))
    params = tree_init(api.param_tree(CFG, st), jax.random.PRNGKey(0))
    d = str(tmp_path / "ck")
    with set_mesh(jmesh):
        sharded = jax.jit(lambda p: p)(params)
        ckpt.save(d, 1, sharded)

    small_mesh, small_jmesh = derive_mesh(
        devices=jax.devices()[:4], model_parallel=2)
    assert small_mesh.shape == (2, 2)
    opt = get_optimizer("adafactor", lr=0.05)
    specs = specs_by_key(
        state_partition_specs(CFG, st, opt, TrainConfig()))
    pspecs = {k[len("params/"):]: v for k, v in specs.items()
              if k.startswith("params/")}
    restored, manifest, report = ckpt.restore_resharded(
        d, params, small_mesh, small_jmesh, target_specs=pspecs)
    assert report["step"] == 1 and report["leaves"] > 0

    with set_mesh(small_jmesh):
        host_mediated, _ = ckpt.restore(d, params)

    flat_a = jax.tree_util.tree_leaves(restored)
    flat_b = jax.tree_util.tree_leaves(host_mediated)
    flat_ref = jax.tree_util.tree_leaves(params)
    for a, b, r in zip(flat_a, flat_b, flat_ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(r))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # sharded restore I/O (per-shard byte-range reads) is bit-identical too,
    # and reads strictly less than leaves × full-size (shards share slices)
    shard_io, _, rep = ckpt.restore_resharded(
        d, params, small_mesh, small_jmesh, target_specs=pspecs,
        sharded_io=True)
    for a, r in zip(jax.tree_util.tree_leaves(shard_io), flat_ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(r))
    assert rep["sharded_io"] is True
    assert rep["io"]["unique_slices"] >= rep["io"]["leaves"]


def test_device_loss_recovers_on_smaller_mesh_in_process(tmp_path):
    """Lose 4 of 8 devices at step 5: the coordinator re-derives a (2,2)
    mesh, warm re-solves, reshard-restores, swaps the plan, and finishes —
    the loss curve has one loss per step and tracks the uninterrupted
    8-device run within partitioning tolerance."""
    from repro import autoshard

    steps = 10
    opt = get_optimizer("adafactor", lr=0.05)
    tc = TrainConfig(steps=steps, ckpt_dir=str(tmp_path / "ck"),
                     ckpt_every=2, keep_ckpts=3, log_every=1000)
    pipe = TokenPipeline(DataConfig(CFG.vocab_size, 16, 8, seed=7))
    inj = FaultInjector(device_loss_at=5, lose=4)
    co = ElasticCoordinator(
        CFG, st, opt, tc, pipe, model_parallel=2, injector=inj,
        autoshard_config=autoshard.AutoshardConfig(
            top_n=2, sa_steps=2, max_candidates=6),
        max_recoveries=2)
    assert co.mesh.shape == (4, 2)
    state, losses = co.run()
    assert len(losses) == steps
    assert len(co.recoveries) == 1
    ev = co.recoveries[0]
    assert ev["mesh"]["to"] == [2, 2]
    assert ev["warm_started"] and not ev["degraded"]
    assert ev["reshard"]["leaves"] > 0

    # uninterrupted reference on the original mesh
    tc_ref = TrainConfig(steps=steps, ckpt_dir=str(tmp_path / "ref"),
                         ckpt_every=2, keep_ckpts=3, log_every=1000)
    pipe_ref = TokenPipeline(DataConfig(CFG.vocab_size, 16, 8, seed=7))
    _, jmesh_full = derive_mesh(model_parallel=2)
    with set_mesh(jmesh_full):
        _, ref = TrainLoop(CFG, st, opt, tc_ref, pipe_ref,
                           rng=jax.random.PRNGKey(0)).run()
    assert_close(losses, ref, "loss_curve")


def test_fail_at_step_restart_on_smaller_mesh(tmp_path):
    """Process-restart flavor (satellite): TrainLoop with fail_at_step dies;
    a fresh loop on a smaller derived mesh resumes from the checkpoint data
    cursor — combined curve continues within tolerance, nothing replayed or
    skipped."""
    import pytest

    steps = 10
    opt = get_optimizer("adafactor", lr=0.05)
    d = str(tmp_path / "ck")
    pipe = TokenPipeline(DataConfig(CFG.vocab_size, 16, 8, seed=7))
    _, jmesh_full = derive_mesh(model_parallel=4)
    tc1 = TrainConfig(steps=steps, ckpt_dir=d, ckpt_every=2, keep_ckpts=3,
                      log_every=1000, fail_at_step=6)
    with set_mesh(jmesh_full):
        loop1 = TrainLoop(CFG, st, opt, tc1, pipe, rng=jax.random.PRNGKey(0))
        first = []
        loop1.hooks["metrics"] = lambda s, l: first.append((s, l))
        with pytest.raises(RuntimeError, match="injected failure"):
            loop1.run()

    # "restarted process": new loop, smaller mesh over 4 surviving devices
    _, jmesh_small = derive_mesh(devices=jax.devices()[:4], model_parallel=2)
    tc2 = TrainConfig(steps=steps, ckpt_dir=d, ckpt_every=2, keep_ckpts=3,
                      log_every=1000)
    pipe2 = TokenPipeline(DataConfig(CFG.vocab_size, 16, 8, seed=7))
    with set_mesh(jmesh_small):
        loop2 = TrainLoop(CFG, st, opt, tc2, pipe2,
                          rng=jax.random.PRNGKey(1))
        second = []
        loop2.hooks["metrics"] = lambda s, l: second.append((s, l))
        loop2.run()

    # resume point = data cursor of the last checkpoint (step 6), so the
    # combined per-step curve covers 0..steps-1 exactly once
    assert second[0][0] == 6
    combined = dict(first)
    combined.update(dict(second))
    assert sorted(combined) == list(range(steps))

    tc_ref = TrainConfig(steps=steps, ckpt_dir=str(tmp_path / "ref"),
                         ckpt_every=2, keep_ckpts=3, log_every=1000)
    pipe_ref = TokenPipeline(DataConfig(CFG.vocab_size, 16, 8, seed=7))
    with set_mesh(jmesh_full):
        _, ref = TrainLoop(CFG, st, opt, tc_ref, pipe_ref,
                           rng=jax.random.PRNGKey(0)).run()
    got = [combined[s] for s in range(steps)]
    assert_close(got, ref, "loss_curve")


def test_shrink_train_regrow_drill_continuous_curve(tmp_path):
    """Tentpole drill: 8 devices → lose 4 at step 4 (mesh (4,2)→(2,2)) →
    train → regain 4 at step 9 (regrow to (4,2)) → train to completion.
    Both re-solves warm-start (the regrow via expand_assignment), the regrow
    costs strictly fewer evals than a cold solve on the grown mesh, and the
    loss curve is continuous — one loss per step, tracking the uninterrupted
    8-device run within partitioning tolerance."""
    from repro import autoshard, obs

    obs.reset_control_events()
    steps = 14
    opt = get_optimizer("adafactor", lr=0.05)
    tc = TrainConfig(steps=steps, ckpt_dir=str(tmp_path / "ck"),
                     ckpt_every=2, keep_ckpts=3, log_every=1000)
    pipe = TokenPipeline(DataConfig(CFG.vocab_size, 16, 8, seed=7))
    inj = FaultInjector(schedule=[
        {"kind": "device_loss", "step": 4, "lose": 4},
        {"kind": "device_return", "step": 9, "gain": 4},
    ])
    cfgs = autoshard.AutoshardConfig(top_n=2, sa_steps=2, max_candidates=6)
    co = ElasticCoordinator(CFG, st, opt, tc, pipe, model_parallel=2,
                            injector=inj, autoshard_config=cfgs,
                            max_recoveries=3)
    assert co.mesh.shape == (4, 2)
    state, losses = co.run()
    assert co.mesh.shape == (4, 2)  # regrown back to the full world
    assert len(losses) == steps     # continuous: one loss per step
    shrink, regrow = co.recoveries
    assert shrink["classes"] == ["device_loss"]
    assert shrink["mesh"] == {"from": [4, 2], "to": [2, 2]}
    assert regrow["classes"] == ["device_return"]
    assert regrow["mesh"] == {"from": [2, 2], "to": [4, 2]}
    assert shrink["warm_started"] and regrow["warm_started"]
    assert regrow["reshard"]["leaves"] > 0

    # the regrow warm start beats a cold solve on the grown mesh
    closed, baseline = sharding_problem(CFG, st, co.mesh,
                                        pipe.local_batch, 16)
    cold = autoshard.solve_problem(closed, co.mesh, cfgs, baseline=baseline)
    assert regrow["evals"] < cold.evals

    names = [e["name"] for e in obs.control_events()]
    assert "mesh_shrink" in names and "mesh_grow" in names
    assert names.count("restore") == 2

    # uninterrupted 8-device reference
    tc_ref = TrainConfig(steps=steps, ckpt_dir=str(tmp_path / "ref"),
                         ckpt_every=2, keep_ckpts=3, log_every=1000)
    pipe_ref = TokenPipeline(DataConfig(CFG.vocab_size, 16, 8, seed=7))
    _, jmesh_full = derive_mesh(model_parallel=2)
    with set_mesh(jmesh_full):
        _, ref = TrainLoop(CFG, st, opt, tc_ref, pipe_ref,
                           rng=jax.random.PRNGKey(0)).run()
    assert_close(losses, ref, "loss_curve")


def test_combined_nan_and_device_loss_single_pass_multidev(tmp_path):
    """Coincident NaN burst + device loss on the real 8-device mesh: one
    classification, one mesh shrink, exactly one reshard-restore."""
    from repro import autoshard, obs
    from repro.core.plan import GuardConfig

    obs.reset_control_events()
    steps = 12
    opt = get_optimizer("adafactor", lr=0.05)
    tc = TrainConfig(steps=steps, ckpt_dir=str(tmp_path / "ck"),
                     ckpt_every=2, keep_ckpts=3, log_every=1000,
                     guard=GuardConfig(rewind_after=2))
    pipe = TokenPipeline(DataConfig(CFG.vocab_size, 16, 8, seed=7))
    inj = FaultInjector(nan_at_step=5, numeric_steps=2,
                        device_loss_at=6, lose=4)
    co = ElasticCoordinator(
        CFG, st, opt, tc, pipe, model_parallel=2, injector=inj,
        autoshard_config=autoshard.AutoshardConfig(
            top_n=2, sa_steps=2, max_candidates=6),
        max_recoveries=2)
    state, losses = co.run()
    assert len(co.recoveries) == 1
    ev = co.recoveries[0]
    assert ev["classes"] == ["device_loss", "numerics"]
    assert ev["mesh"] == {"from": [4, 2], "to": [2, 2]}
    assert "restored_from" in ev
    events = obs.control_events()
    names = [e["name"] for e in events]
    assert names.count("restore") == 1
    assert names.count("combined_recovery") == 1
    narr = obs.recovery_narrative(events)
    assert len(narr) == 1 and narr[0]["restores"] == 1
