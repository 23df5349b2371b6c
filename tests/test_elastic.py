"""Elastic coordinator: in-process fault recovery on a single device, plus
pure-planning warm-vs-cold autoshard comparisons on multi-device mesh shapes
(no devices needed for cost-only solves).  The real 8-device mesh-shrink
recovery runs in tests/multidev/test_elastic_multidev.py."""
import dataclasses
import os

import jax
import numpy as np
import pytest

from repro import autoshard
from repro.configs.base import ModelConfig, get_strategy
from repro.core.sharding import Mesh
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.launch.elastic import (
    DeviceLossError,
    ElasticCoordinator,
    FaultInjector,
    derive_mesh,
    sharding_problem,
    specs_by_key,
)
from repro.train.loop import TrainConfig, TrainLoop, state_partition_specs
from repro.train.optimizer import get_optimizer

st = get_strategy("2d_finalized")
TINY = ModelConfig(
    name="tiny", family="dense", num_layers=2, d_model=32, num_heads=4,
    num_kv_heads=4, d_ff=64, vocab_size=128, attn_chunk=16, remat="none",
)


CHEAP = autoshard.AutoshardConfig(top_n=2, sa_steps=2, max_candidates=6)


def make_coordinator(tmp_path, steps=10, injector=None, **kw):
    opt = get_optimizer("adafactor", lr=0.05)
    tc = TrainConfig(steps=steps, ckpt_dir=str(tmp_path / "ck"), ckpt_every=2,
                     keep_ckpts=3, log_every=1000)
    pipe = TokenPipeline(DataConfig(TINY.vocab_size, 16, 4, seed=7))
    kw.setdefault("autoshard_config", CHEAP)
    return ElasticCoordinator(TINY, st, opt, tc, pipe, injector=injector, **kw)


def test_derive_mesh_shapes_and_clamp():
    mesh, jmesh = derive_mesh(n_devices=1)
    assert mesh.shape == (1, 1) and tuple(jmesh.devices.shape) == (1, 1)
    assert mesh.axis_names == ("data", "model")
    # model_parallel larger than the world clamps to a divisor
    mesh, _ = derive_mesh(n_devices=1, model_parallel=4)
    assert mesh.shape == (1, 1)


def test_device_loss_recovery_matches_uninterrupted_run(tmp_path):
    """Fault at step 5 → restore from the last checkpoint, warm re-solve,
    plan swap, resume: the returned loss curve is one loss per step and
    bitwise-matches an uninterrupted run (same seeds, same batches — nothing
    replayed into the curve, nothing skipped)."""
    inj = FaultInjector(device_loss_at=5, lose=0)  # 1-device world: lose none
    co = make_coordinator(tmp_path, steps=10, injector=inj, max_recoveries=2)
    state, losses = co.run()
    assert len(losses) == 10
    assert len(co.recoveries) == 1
    ev = co.recoveries[0]
    assert ev["warm_started"] and not ev["degraded"]
    assert ev["reshard"]["leaves"] > 0

    # uninterrupted reference
    opt = get_optimizer("adafactor", lr=0.05)
    tc = TrainConfig(steps=10, ckpt_dir=str(tmp_path / "ref"), ckpt_every=2,
                     keep_ckpts=3, log_every=1000)
    pipe = TokenPipeline(DataConfig(TINY.vocab_size, 16, 4, seed=7))
    _, ref = TrainLoop(TINY, st, opt, tc, pipe,
                       rng=jax.random.PRNGKey(0)).run()
    np.testing.assert_allclose(losses, ref, rtol=1e-6)


def test_exhausted_recoveries_reraise(tmp_path):
    inj = FaultInjector(device_loss_at=5, lose=0)
    co = make_coordinator(tmp_path, steps=10, injector=inj, max_recoveries=0)
    with pytest.raises(DeviceLossError):
        co.run()


def test_crash_mid_save_resumes_from_intact_step(tmp_path):
    inj = FaultInjector(crash_save_at_leaf=3)
    co = make_coordinator(tmp_path, steps=8, injector=inj, max_recoveries=2)
    state, losses = co.run()
    assert len(losses) == 8
    assert any(r.get("crash_save") for r in co.recoveries)
    # the final checkpoint committed; no orphan tmp dirs break latest_step
    from repro.train import checkpoint as ckpt
    assert ckpt.latest_step(str(tmp_path / "ck")) == 8


def test_straggler_stall_trips_watchdog(tmp_path):
    events = []
    inj = FaultInjector(straggler_at=9, stall_s=0.3)
    co = make_coordinator(
        tmp_path, steps=12, injector=inj,
        hooks={"straggler": lambda step, dt, med: events.append(step)})
    co.tc.straggler_factor = 2.0
    co.loop.tc.straggler_factor = 2.0
    co.run()
    assert 9 in events


def test_warm_start_fewer_evals_than_cold():
    """Automap-style warm start across a mesh shrink: strictly fewer cost
    lowerings, no worse score (pure planning, no devices)."""
    cfgs = CHEAP
    old = Mesh.create((2, 4), ("data", "model"))
    closed, baseline = sharding_problem(TINY, st, old, 4, 16)
    prior = autoshard.solve_problem(closed, old, cfgs, baseline=baseline)
    assert not prior.warm_started

    new = Mesh.create((2, 2), ("data", "model"))
    closed2, baseline2 = sharding_problem(TINY, st, new, 4, 16)
    shapes = [tuple(v.aval.shape) for v in closed2.jaxpr.invars]
    warm = autoshard.remap_assignment(prior.assignment, new, shapes)
    warm_res = autoshard.solve_problem(closed2, new, cfgs, baseline=baseline2,
                                       warm_start=warm)
    cold_res = autoshard.solve_problem(closed2, new, cfgs, baseline=baseline2)
    assert warm_res.warm_started
    assert warm_res.evals < cold_res.evals
    assert warm_res.evaluation.score <= cold_res.evaluation.score * (1 + 1e-6)


def test_warm_start_roundtrips_through_json_dump(tmp_path):
    cfgs = CHEAP
    old = Mesh.create((2, 4), ("data", "model"))
    closed, baseline = sharding_problem(TINY, st, old, 4, 16)
    prior = autoshard.solve_problem(closed, old, cfgs, baseline=baseline)
    p = str(tmp_path / "assignment.json")
    prior.dump(p)
    _, loaded = autoshard.load(p)
    new = Mesh.create((2, 2), ("data", "model"))
    closed2, baseline2 = sharding_problem(TINY, st, new, 4, 16)
    shapes = [tuple(v.aval.shape) for v in closed2.jaxpr.invars]
    warm = autoshard.remap_assignment(loaded, new, shapes)
    res = autoshard.solve_problem(closed2, new, cfgs, baseline=baseline2,
                                  warm_start=warm)
    assert res.warm_started and res.to_json()["warm_started"]


def test_infeasible_budget_degrades_to_data_parallel(tmp_path):
    """A budget no assignment can satisfy must not abort: the coordinator
    falls back to the data-parallel-only restriction of the baseline."""
    co = make_coordinator(
        tmp_path, steps=2,
        autoshard_config=autoshard.AutoshardConfig(
            top_n=2, sa_steps=2, budget_bytes=1.0))
    res = co.solve_assignment()
    assert co.degraded
    for s in res.assignment:
        if s is None:
            continue
        axes = {a for dim in s.dims_mapping for a in dim}
        assert axes <= {"data"}, s
    assert os.path.exists(co.dump_path)


def test_state_partition_specs_cover_state(tmp_path):
    from repro.train.loop import init_state

    opt = get_optimizer("adafactor", lr=0.05)
    tc = TrainConfig(steps=1)
    state = init_state(TINY, st, opt, tc, jax.random.PRNGKey(0))
    from repro.train.checkpoint import _flatten_with_paths

    keys = {k for k, _ in _flatten_with_paths(state)[0]}
    specs = specs_by_key(state_partition_specs(TINY, st, opt, tc))
    assert keys == set(specs)


def test_recovery_story_reconstructable_from_trace(tmp_path):
    """Satellite drill: run a FaultInjector-driven rewind and reconstruct the
    whole fault → skip → rewind → plan-swap story purely from the exported
    control-lane trace events — no coordinator state consulted."""
    from repro import obs
    from repro.core.plan import GuardConfig

    obs.reset_control_events()
    opt = get_optimizer("adafactor", lr=0.05)
    tc = TrainConfig(steps=12, ckpt_dir=str(tmp_path / "ck"), ckpt_every=3,
                     guard=GuardConfig(rewind_after=2), log_every=1000)
    pipe = TokenPipeline(DataConfig(TINY.vocab_size, 16, 4, seed=7))
    inj = FaultInjector(nan_at_step=5, numeric_steps=4)
    co = ElasticCoordinator(TINY, st, opt, tc, pipe, n_devices=1,
                            injector=inj, max_recoveries=2,
                            autoshard_config=CHEAP)
    _, losses = co.run()
    assert len(losses) == 11  # one skipped batch, training completed

    doc = obs.export_control_trace()
    assert obs.validate_trace_events(doc["traceEvents"]) == []
    instants = sorted(
        (e for e in doc["traceEvents"] if e["ph"] == "i"),
        key=lambda e: e["ts"])
    names = [e["name"] for e in instants]
    # the full recovery story, in causal order, from the trace alone
    first_fault = names.index("numerics_fault")
    skip = names.index("skip_step")
    rewind = names.index("rewind")
    swap = names.index("plan_swap")
    assert first_fault < skip < rewind < swap
    # two consecutive faults tripped the rewind threshold
    faults = [e for e in instants if e["name"] == "numerics_fault"]
    assert faults[-1]["args"]["consecutive"] == 2
    assert [e["args"]["step"] for e in faults[:2]] == [5, 6]
    # the skip names the dropped batch, the swap says why it happened
    (skip_ev,) = [e for e in instants if e["name"] == "skip_step"]
    assert skip_ev["args"]["step"] == 5
    swap_ev = instants[swap]
    assert swap_ev["args"]["reason"] == "rewind"
    # counters landed in the unified registry alongside the trace
    snap = obs.snapshot()
    assert snap["counters"]["train.guard.faults"] >= 2
    assert snap["counters"]["train.guard.rewinds"] >= 1


def test_expand_assignment_regrow_warm_fewer_evals():
    """Regrow counterpart of the shrink warm start: a DP-only (2,1)
    assignment (the post-shrink / degraded shape — model axis collapsed)
    lifts onto (2,4) via expand_assignment, which re-proposes the freed
    model axis instead of merely name-projecting (remap would leave every
    leaf DP-only forever), and the warm solve costs strictly fewer evals."""
    small = Mesh.create((2, 1), ("data", "model"))
    closed_s, base_s = sharding_problem(TINY, st, small, 4, 16)
    shapes_s = [tuple(v.aval.shape) for v in closed_s.jaxpr.invars]
    # the DP-only restriction is exactly what a degraded coordinator dumps
    prior = autoshard.restrict_assignment(base_s, small, shapes_s)

    big = Mesh.create((2, 4), ("data", "model"))
    closed_b, base_b = sharding_problem(TINY, st, big, 4, 16)
    shapes = [tuple(v.aval.shape) for v in closed_b.jaxpr.invars]
    warm = autoshard.expand_assignment(prior, big, shapes)
    remap = autoshard.remap_assignment(prior, big, shapes)
    dms = lambda a: [None if s is None else s.dims_mapping for s in a]
    assert dms(warm) != dms(remap)  # the lift re-proposed freed capacity
    warm_res = autoshard.solve_problem(closed_b, big, CHEAP, baseline=base_b,
                                       warm_start=warm)
    cold_res = autoshard.solve_problem(closed_b, big, CHEAP, baseline=base_b)
    assert warm_res.warm_started
    assert warm_res.evals < cold_res.evals


def test_schedule_json_round_trip_and_validation(tmp_path):
    sched = [{"kind": "device_loss", "step": 3, "lose": 0},
             {"kind": "nan_burst", "step": 7, "steps": 1}]
    inj = FaultInjector(schedule=sched)
    p = str(tmp_path / "campaign.json")
    doc = inj.dump_schedule(p)
    assert doc["version"] == 1
    assert FaultInjector.load_schedule(p).schedule == sched
    assert FaultInjector.load_schedule(doc).schedule == sched
    assert FaultInjector.load_schedule(sched).schedule == sched
    with pytest.raises(ValueError, match="unknown schedule"):
        FaultInjector(schedule=[{"kind": "meteor", "step": 1}])
    with pytest.raises(ValueError, match="missing step"):
        FaultInjector(schedule=[{"kind": "nan_burst"}])


def test_shrink_then_regrow_drill_continuous_curve(tmp_path):
    """Tentpole drill (1-device edition; the 8-device mesh-shape version is
    in tests/multidev): schedule-driven shrink → train → regrow → train,
    both recoveries warm-started, one restore each, continuous loss curve,
    and the whole campaign reconstructable from the exported trace alone."""
    from repro import obs

    obs.reset_control_events()
    sched = [{"kind": "device_loss", "step": 3, "lose": 0},
             {"kind": "device_return", "step": 7, "gain": 0}]
    inj = FaultInjector(schedule=sched)
    co = make_coordinator(tmp_path, steps=12, injector=inj, max_recoveries=3)
    state, losses = co.run()
    assert len(losses) == 12  # one loss per step, continuous across both
    assert [r["classes"] for r in co.recoveries] == [
        ["device_loss"], ["device_return"]]
    assert all(r["warm_started"] and not r["degraded"]
               for r in co.recoveries)
    events = obs.control_events()
    names = [e["name"] for e in events]
    assert "mesh_shrink" in names and "mesh_grow" in names
    assert names.count("restore") == 2  # one restore pass per recovery
    # injections are distinguishable from the reactions they caused
    chaos = [e["args"]["kind"] for e in events if e["name"] == "chaos_event"]
    assert chaos == ["device_loss", "device_return"]
    # the campaign narrative rebuilds from the trace alone
    narr = obs.recovery_narrative(events)
    assert [ep["classes"] for ep in narr] == [
        ["device_loss"], ["device_return"]]
    assert all(ep["restores"] == 1 for ep in narr)


def test_combined_nan_and_device_loss_single_restore(tmp_path):
    """Coincident NumericsFault + device loss resolve in ONE recovery pass:
    one classification, one mesh change, one restore_resharded — asserted
    from the control lane, and the provenance lands in the manifest."""
    from repro import obs
    from repro.core.plan import GuardConfig
    from repro.train import checkpoint as ckpt

    obs.reset_control_events()
    opt = get_optimizer("adafactor", lr=0.05)
    tc = TrainConfig(steps=12, ckpt_dir=str(tmp_path / "ck"), ckpt_every=2,
                     guard=GuardConfig(rewind_after=2), log_every=1000)
    pipe = TokenPipeline(DataConfig(TINY.vocab_size, 16, 4, seed=7))
    inj = FaultInjector(nan_at_step=5, numeric_steps=2,
                        device_loss_at=6, lose=0)
    co = ElasticCoordinator(TINY, st, opt, tc, pipe, n_devices=1,
                            injector=inj, max_recoveries=2,
                            autoshard_config=CHEAP)
    _, losses = co.run()
    assert len(co.recoveries) == 1
    ev = co.recoveries[0]
    assert ev["classes"] == ["device_loss", "numerics"]
    assert "restored_from" in ev and ev["reshard"]["leaves"] > 0
    events = obs.control_events()
    names = [e["name"] for e in events]
    assert names.count("restore") == 1        # exactly one restore pass
    assert names.count("combined_recovery") == 1
    (comb,) = [e for e in events if e["name"] == "combined_recovery"]
    assert comb["args"]["classes"] == ["device_loss", "numerics"]
    # the narrative sees one episode covering both classes
    narr = obs.recovery_narrative(events)
    assert len(narr) == 1 and narr[0]["restores"] == 1
    assert narr[0]["classes"] == ["device_loss", "numerics"]
    # provenance reached the next manifest's extra
    d = str(tmp_path / "ck")
    man = ckpt._load_manifest(d, ckpt.latest_step(d))
    rec = man["extra"]["recovery"]
    assert rec["count"] == 1
    assert rec["last"]["classes"] == ["device_loss", "numerics"]
