"""Guarded execution: numerics sentinels, skip/rewind recovery, checkpoint
self-verification (single device).

The multidev drill (guarded TrainLoop surviving an injected NaN batch and a
K-consecutive-fault rewind on 8 fake devices) lives in
tests/multidev/test_guard_multidev.py; here the same machinery is exercised
on one device: the plan-lowered guard epilogue, the runner-side NumericsFault,
the in-jit skip select, the coordinator rewind path, and the offline
checkpoint verifier CLI.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))

from repro.configs.base import ModelConfig, get_strategy
from repro.core import Mesh, annotate
from repro.core.partitioner import spmd_partition
from repro.core.plan import (GuardConfig, NumericsFault, compile_plan,
                             guard_faults)
from repro.core.propagation import propagate
from repro.core.sharding import Sharding
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.train import checkpoint as ckpt
from repro.train.loop import (NumericFaultSpec, TrainConfig, TrainLoop,
                              guard_leaf_names)
from repro.train.optimizer import get_optimizer

st = get_strategy("2d_finalized")
TINY = ModelConfig(
    name="tiny", family="dense", num_layers=2, d_model=32, num_heads=4,
    num_kv_heads=4, d_ff=64, vocab_size=128, attn_chunk=16, remat="none",
)


def _pipe():
    return TokenPipeline(DataConfig(vocab_size=128, seq_len=8, global_batch=4,
                                    seed=1))


# ---------------------------------------------------------------------------------
# guard_faults decode + plan-lowered guard epilogue
# ---------------------------------------------------------------------------------


def test_guard_faults_decode():
    gc = GuardConfig(max_abs=10.0)
    stats = np.array([[0.0, 1.0],    # clean
                      [3.0, np.nan],  # non-finite
                      [0.0, 99.0]])   # abs-max breach
    faults = guard_faults(gc, stats, ("a", "b", "c"))
    kinds = {f["leaf"]: f["kind"] for f in faults}
    assert kinds == {"b": "nonfinite", "c": "absmax"}
    assert guard_faults(gc, np.array([[0.0, 1.0]]), ("a",)) == []


def test_append_guard_steps_structure():
    mesh = Mesh.create((1,), ("x",))
    closed = jax.make_jaxpr(lambda a, b: (jnp.tanh(a @ b), a + 1.0))(
        jax.ShapeDtypeStruct((8, 8), jnp.float32),
        jax.ShapeDtypeStruct((8, 8), jnp.float32))
    prop = propagate(closed, mesh).result()
    plan = compile_plan(closed, prop, mesh, optimize=False, guard=GuardConfig())
    gi = plan.guard
    assert gi is not None and gi.leaves == ("out[0]", "out[1]")
    # the sentinel vector is a first-class replicated output...
    assert len(plan.out_keys) == len(plan.out_shardings) == 3
    assert gi.out_index == 2
    # ...and its reduction is a first-class collective step, priced like any
    stat_ops = [s for s in plan.steps if s.op == "guard-stat"]
    pmaxes = [s for s in plan.steps
              if s.kind in ("collective", "fused") and s.reduce_op == "max"]
    assert len(stat_ops) == 2 and len(pmaxes) >= 1
    assert all(s.flops > 0 for s in stat_ops)


def test_spmd_partition_guard_raises():
    mesh = Mesh.create((1,), ("x",))
    jmesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("x",))

    def f(a, b):
        a = annotate(a, Sharding(mesh, (("x",), ())))
        c = jnp.tanh(a @ b)
        return c.sum(), c

    r = spmd_partition(f, jmesh, mesh, guard=GuardConfig())
    a = jnp.ones((8, 4), jnp.float32)
    b = jnp.ones((4, 8), jnp.float32)
    loss, c = r(a, b)  # clean call: guard vector stripped, outputs intact
    assert np.isfinite(float(loss)) and c.shape == (8, 8)
    with pytest.raises(NumericsFault) as ei:
        r(a.at[0, 0].set(jnp.nan), b)
    assert any(f["kind"] == "nonfinite" for f in ei.value.faults)


# ---------------------------------------------------------------------------------
# train-step skip semantics + escalation
# ---------------------------------------------------------------------------------


def test_train_loop_skips_nan_batch(tmp_path):
    opt = get_optimizer("adafactor", lr=0.05)
    tc = TrainConfig(steps=10, ckpt_dir=str(tmp_path / "ck"), ckpt_every=4,
                     guard=GuardConfig(rewind_after=3),
                     numeric_fault=NumericFaultSpec(nan_at_step=4))
    events = []
    loop = TrainLoop(TINY, st, opt, tc, _pipe(),
                     hooks={"numerics_fault":
                            lambda s, f, c: events.append((s, c))})
    state, losses = loop.run()
    # the poisoned batch is dropped, the curve stays finite and continuous
    assert len(losses) == 9 and all(np.isfinite(losses))
    assert loop.skipped_steps == [4]
    assert loop.guard_counters == {"faults": 1, "skips": 1, "rewinds": 0}
    assert events == [(4, 1)]
    # params survived the poisoned step: the final state is finite
    for leaf in jax.tree_util.tree_leaves(state["params"]):
        assert bool(jnp.all(jnp.isfinite(leaf)))
    # counters ride in the manifest extra
    m = ckpt._load_manifest(str(tmp_path / "ck"),
                            ckpt.latest_step(str(tmp_path / "ck")))
    assert m["extra"]["guard"]["faults"] == 1


def test_train_loop_escalates_after_k_consecutive(tmp_path):
    opt = get_optimizer("adafactor", lr=0.05)
    tc = TrainConfig(steps=10, ckpt_dir=str(tmp_path / "ck"), ckpt_every=3,
                     guard=GuardConfig(rewind_after=3),
                     numeric_fault=NumericFaultSpec(nan_at_step=4, steps=5))
    loop = TrainLoop(TINY, st, opt, tc, _pipe())
    with pytest.raises(NumericsFault) as ei:
        loop.run()
    assert ei.value.consecutive == 3 and ei.value.step == 6
    assert loop.guard_counters["faults"] == 3
    assert loop.guard_counters["skips"] == 2


def test_grad_spike_caught_by_max_abs():
    opt = get_optimizer("adafactor", lr=0.05)
    tc = TrainConfig(steps=6, guard=GuardConfig(max_abs=1e6, rewind_after=99),
                     numeric_fault=NumericFaultSpec(grad_spike_at_step=3,
                                                    spike_factor=1e12))
    events = []
    loop = TrainLoop(TINY, st, opt, tc, _pipe(),
                     hooks={"numerics_fault":
                            lambda s, f, c: events.append((s, f))})
    _, losses = loop.run()
    assert len(losses) == 5 and all(np.isfinite(losses))
    (step, faults), = events
    assert step == 3 and any(f["kind"] == "absmax" for f in faults)


def test_guard_leaf_names_match_metrics_order():
    opt = get_optimizer("adafactor", lr=0.05)
    gc = GuardConfig(moments=True)
    tc = TrainConfig(steps=1, guard=gc)
    loop = TrainLoop(TINY, st, opt, tc, _pipe())
    state, _ = loop.run()
    names = guard_leaf_names(gc, state)
    assert names[0] == "loss"
    assert any(n.startswith("grads/") for n in names)
    assert any(n.startswith("opt/") for n in names)
    # one (nonfinite, absmax) pair per guarded leaf
    batch = {k: jnp.asarray(v) for k, v in _pipe().batch_at(0).items()}
    _, metrics = loop.step_fn(state, batch)
    assert metrics["guard"].shape == (2 * len(names),)


# ---------------------------------------------------------------------------------
# coordinator rewind drill (single device)
# ---------------------------------------------------------------------------------


def test_coordinator_rewinds_after_consecutive_faults(tmp_path):
    from repro.launch.elastic import ElasticCoordinator, FaultInjector

    opt = get_optimizer("adafactor", lr=0.05)
    tc = TrainConfig(steps=12, ckpt_dir=str(tmp_path / "ck"), ckpt_every=3,
                     guard=GuardConfig(rewind_after=2))
    inj = FaultInjector(nan_at_step=5, numeric_steps=4)
    coord = ElasticCoordinator(TINY, st, opt, tc, _pipe(), n_devices=1,
                               injector=inj, max_recoveries=2)
    state, losses = coord.run()
    # 12 steps, one skipped batch, zero process restarts
    assert len(losses) == 11 and all(np.isfinite(losses))
    (ev,) = [e for e in coord.recoveries if e.get("numerics")]
    assert ev["consecutive"] == 2 and ev["faults"]
    assert "rewound_to" in ev
    assert coord.loop.guard_counters["rewinds"] == 1
    # injection was disarmed on rewind: training completed
    assert tc.numeric_fault is None
    m = ckpt._load_manifest(str(tmp_path / "ck"),
                            ckpt.latest_step(str(tmp_path / "ck")))
    assert m["extra"]["guard"]["rewinds"] == 1


# ---------------------------------------------------------------------------------
# checkpoint manifest self-checksum + offline verify CLI
# ---------------------------------------------------------------------------------


def _save_two_steps(d):
    state = {"a": jnp.arange(6.0).reshape(2, 3),
             "n": {"b": jnp.ones(4, jnp.int32)}}
    ckpt.save(d, 5, state, extra={"data_cursor": 6})
    ckpt.save(d, 7, state)
    return state


def test_manifest_self_checksum_detects_edit(tmp_path):
    d = str(tmp_path / "ck")
    _save_two_steps(d)
    mp = os.path.join(d, "step_00000007", "manifest.json")
    m = json.load(open(mp))
    m["step"] = 999  # silent manifest edit
    json.dump(m, open(mp, "w"))
    with pytest.raises(ckpt.CheckpointCorruptError) as ei:
        ckpt._load_manifest(d, 7)
    assert "self-checksum" in str(ei.value)
    # restore (no pinned step) falls back to the intact step 5
    state = _save_two_steps(str(tmp_path / "ref"))
    out, manifest = ckpt.restore(d, state)
    assert manifest["step"] == 5
    assert manifest["restore_report"]["fell_back_from"] == [7]


def test_verify_cli(tmp_path):
    d = str(tmp_path / "ck")
    _save_two_steps(d)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    cmd = [sys.executable, "-m", "repro.train.checkpoint", "verify", d]
    r = subprocess.run(cmd, capture_output=True, text=True, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "step 5: ok" in r.stdout and "step 7: ok" in r.stdout
    # flip a byte in a leaf: CLI must fail and name the leaf
    p = os.path.join(d, "step_00000005", "a.npy")
    arr = np.load(p)
    arr[0, 0] += 1
    np.save(p, arr)
    r = subprocess.run(cmd, capture_output=True, text=True, env=env)
    assert r.returncode == 1
    assert "CORRUPT" in r.stdout and "leaf 'a'" in r.stdout
    # pinning the intact step still passes
    r = subprocess.run(cmd + ["--step", "7"], capture_output=True, text=True,
                       env=env)
    assert r.returncode == 0


def test_verify_dir_api(tmp_path):
    d = str(tmp_path / "ck")
    _save_two_steps(d)
    rep = ckpt.verify_dir(d)
    assert rep["ok"] and [r["step"] for r in rep["steps"]] == [5, 7]
    assert all(r["leaves"] == 2 for r in rep["steps"])
