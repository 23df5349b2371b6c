"""Static plan verifier: seeded plan corruptions must be caught.

Each test builds a genuinely valid plan through the normal compile path (so
it verifies clean), then applies one surgical mutation of the kind a broken
optimizer pass would produce — dropped reshard, swapped spec, dep-violating
schedule, dangling alias, corrupted perm/cost/stats — and asserts
``verify_plan`` flags it.  This proves the verifier wired into
``compile_plan`` / ``spmd_partition`` / ``compile_state_reshard`` would have
caught the pass bug before any numerics drifted.
"""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))

from repro.core import Mesh, annotate, mesh_split, propagate
from repro.core.plan import (GuardConfig, compile_plan, compile_state_reshard,
                             lower_for_cost)
from repro.core.plan_verify import (PlanVerifyError, verify_plan,
                                    verify_state_reshard, verify_telemetry)

mesh = Mesh.create((4, 8), ("x", "y"))


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _plan(f, *avals, optimize=True, verify=True):
    closed = jax.make_jaxpr(f)(*avals)
    prop = propagate(closed, mesh).result()
    return compile_plan(closed, prop, mesh, optimize=optimize, verify=verify)


def _mlp(a, w1, w2):
    # a must reshard to contract with the "y"-row-sharded weights, and the
    # sharded contraction emits a psum — the plan has reshards + collectives
    a = annotate(a, mesh_split(2, mesh, ["y", -1]))
    w1 = annotate(w1, mesh_split(2, mesh, ["y", -1]))
    w2 = annotate(w2, mesh_split(2, mesh, ["y", -1]))
    return (a @ w1) + (a @ w2)


MLP_AVALS = (_f32(64, 64), _f32(64, 64), _f32(64, 64))


def _violations(plan):
    return verify_plan(plan, strict=False).violations


# ---------------------------------------------------------------------------------
# clean plans verify OK
# ---------------------------------------------------------------------------------


def test_clean_plans_verify_ok():
    for optimize in (False, True):
        plan = _plan(_mlp, *MLP_AVALS, optimize=optimize, verify=False)
        rep = verify_plan(plan)
        assert rep.ok and rep.plans >= 1 and rep.steps >= len(plan.steps)


def test_clean_scan_plan_verifies_inner():
    def f(x, w):
        x = annotate(x, mesh_split(2, mesh, ["x", -1]))
        w = annotate(w, mesh_split(2, mesh, [-1, "y"]))

        def body(c, _):
            return jnp.tanh(c @ w), ()

        c, _ = jax.lax.scan(body, x, None, length=3)
        return c

    plan = _plan(f, _f32(32, 64), _f32(64, 64), verify=False)
    rep = verify_plan(plan)
    assert rep.ok
    assert rep.plans >= 2  # top level + at least the scan body


def test_guarded_plan_verifies_ok():
    closed = jax.make_jaxpr(lambda a, b: jnp.tanh(a @ b))(_f32(16, 16),
                                                          _f32(16, 16))
    prop = propagate(closed, mesh).result()
    plan = compile_plan(closed, prop, mesh, guard=GuardConfig(), verify=False)
    assert plan.guard is not None
    assert verify_plan(plan).ok


def test_telemetry_counts():
    before = verify_telemetry()
    _plan(_mlp, *MLP_AVALS, verify=True)
    after = verify_telemetry()
    assert after["plans_verified"] > before["plans_verified"]
    assert after["violations"] == before["violations"]


# ---------------------------------------------------------------------------------
# seeded mutations — each must be caught
# ---------------------------------------------------------------------------------


def test_dropped_reshard_caught():
    plan = _plan(_mlp, *MLP_AVALS, verify=False)
    idx = [i for i, s in enumerate(plan.steps) if s.kind == "reshard"]
    assert idx, "expected at least one reshard step in the MLP plan"
    del plan.steps[idx[0]]
    v = _violations(plan)
    assert v, "dropping a reshard step must be flagged"
    assert any("before it is produced" in x or "never produced" in x
               or "recomputed" in x for x in v), v
    with pytest.raises(PlanVerifyError):
        verify_plan(plan)


def test_swapped_spec_caught():
    """An epilogue reshard whose program was swapped to the wrong layout pair
    (the 'swapped spec' pass bug) must disagree with out_shardings."""

    def f(a, b):
        a = annotate(a, mesh_split(2, mesh, ["x", -1]))
        b = annotate(b, mesh_split(2, mesh, [-1, "y"]))
        return annotate(a @ b, mesh_split(2, mesh, [-1, -1]))

    plan = _plan(f, _f32(64, 64), _f32(64, 64), verify=False)
    rs = [s for s in plan.steps if s.kind == "reshard"]
    assert rs, "expected an epilogue reshard"
    tgt = rs[-1]
    # swap the program's endpoints: src<->dst
    tgt.program = dataclasses.replace(
        tgt.program, src=tgt.program.dst, dst=tgt.program.src)
    v = _violations(plan)
    assert v, "swapped reshard endpoints must be flagged"
    with pytest.raises(PlanVerifyError):
        verify_plan(plan)


def test_dep_violating_schedule_caught():
    """Reordering a step before its producer (a broken overlap scheduler)
    breaks the produced-before-use walk — the step list IS the schedule."""
    plan = _plan(_mlp, *MLP_AVALS, verify=False)
    # find a step that reads another step's write, and hoist it to the front
    written = set()
    mover = None
    for i, s in enumerate(plan.steps):
        if any(id(r) in written for r in s.reads):
            mover = i
            break
        written.update(id(w) for w in s.writes)
    assert mover is not None
    step = plan.steps.pop(mover)
    plan.steps.insert(0, step)
    v = _violations(plan)
    assert any("before it is produced" in x for x in v), v
    with pytest.raises(PlanVerifyError):
        verify_plan(plan)


def test_dangling_alias_caught():
    """Deleting a producer whose value is still read (a bad DCE / alias-sink
    interaction) leaves a dangling read."""
    plan = _plan(_mlp, *MLP_AVALS, verify=False)
    read_ids = set()
    for s in plan.steps:
        read_ids.update(id(r) for r in s.reads)
    victim = None
    for i, s in enumerate(plan.steps):
        if any(id(w) in read_ids for w in s.writes):
            victim = i
            break
    assert victim is not None
    del plan.steps[victim]
    v = _violations(plan)
    assert any("before it is produced" in x or "never produced" in x
               for x in v), v


def test_double_write_caught():
    plan = _plan(_mlp, *MLP_AVALS, verify=False)
    writers = [s for s in plan.steps if s.writes]
    dup = writers[0]
    plan.steps.append(dup)  # replay the same step: SSA violation
    v = _violations(plan)
    assert any("SSA" in x or "twice" in x for x in v), v


def test_bad_ppermute_perm_caught():
    """A ppermute whose perm has a duplicated destination (a fusion pass that
    merged incompatible shifts) is not a permutation."""
    from repro.core.shift import stage_shift

    smesh = Mesh.create((4,), ("stage",))

    def f(state, x):
        state = annotate(state, mesh_split(3, smesh, ["stage", -1, -1]))
        return stage_shift(state, x)

    closed = jax.make_jaxpr(f)(_f32(4, 8, 16), _f32(8, 16))
    prop = propagate(closed, smesh).result()
    plan = compile_plan(closed, prop, smesh, cost_only=True, verify=False)

    def find_pp(p):
        for s in p.steps:
            if s.kind == "collective" and s.op == "ppermute":
                return s
            if s.inner is not None:
                got = find_pp(s.inner)
                if got is not None:
                    return got
        return None

    pp = find_pp(plan)
    assert pp is not None, [s.op for s in plan.steps]
    assert verify_plan(plan).ok
    pp.call = dict(pp.call, perm=((0, 1), (1, 1), (2, 3)))  # dst 1 twice
    v = _violations(plan)
    assert any("not a permutation" in x for x in v), v
    pp.call = dict(pp.call, perm=((0, 9),))  # out of range
    assert any("out of range" in x for x in _violations(plan))


def test_collective_axis_not_in_mesh_caught():
    def f(a, w):
        # contracting dim sharded on both sides: partial result + psum step
        a = annotate(a, mesh_split(2, mesh, [-1, "y"]))
        w = annotate(w, mesh_split(2, mesh, ["y", -1]))
        return a @ w

    plan = _plan(f, _f32(64, 64), _f32(64, 64), verify=False)
    cols = [s for s in plan.steps if s.kind in ("collective", "fused")]
    assert cols, "expected a psum from the sharded contraction"
    cols[0].axes = ("ghost",)
    v = _violations(plan)
    assert any("'ghost' not in mesh" in x for x in v), v


def test_negative_cost_fields_caught():
    plan = _plan(_mlp, *MLP_AVALS, verify=False)
    plan.steps[0].flops = -5.0
    plan.steps[0].transient_bytes = -1.0
    v = _violations(plan)
    assert any("negative flops" in x for x in v), v
    assert any("negative transient_bytes" in x for x in v), v


def test_negative_stats_counter_caught():
    plan = _plan(_mlp, *MLP_AVALS, verify=False)
    plan.stats.collectives["all-reduce"] = -2
    v = _violations(plan)
    assert any("negative planned-collective" in x for x in v), v


def test_cost_bytes_mismatch_caught():
    plan = _plan(_mlp, *MLP_AVALS, verify=False)
    rs = [s for s in plan.steps if s.kind == "reshard"]
    assert rs
    rs[0].program = dataclasses.replace(
        rs[0].program, cost_bytes=rs[0].program.cost_bytes * 7 + 1234.0)
    v = _violations(plan)
    assert any("cost_bytes" in x or "recomputed" in x for x in v), v


def test_wire_accounting_mismatch_caught():
    """Deleting a collective after the optimizer recorded wire_bytes_after
    breaks whole-program accounting even when nothing dangles."""
    plan = _plan(_mlp, *MLP_AVALS, verify=False)
    assert plan.opt_report is not None
    # corrupt the recorded number rather than the steps: pure accounting drift
    plan.opt_report.wire_bytes_after = plan.opt_report.wire_bytes_after * 3 + 1e6
    v = _violations(plan)
    assert any("wire_bytes_after" in x for x in v), v


# ---------------------------------------------------------------------------------
# state-reshard (elastic restore) verification
# ---------------------------------------------------------------------------------


def _state_items():
    src = mesh_split(2, mesh, ["x", -1])
    dst = mesh_split(2, mesh, [-1, "y"])
    return [("w", src, dst, (64, 64), "float32"),
            ("b", mesh_split(1, mesh, [-1]), mesh_split(1, mesh, ["y"]),
             (64,), "float32")]


def test_state_reshard_clean_and_corrupt():
    plan = compile_state_reshard(_state_items(), mesh, verify=False)
    assert verify_state_reshard(plan).ok
    bad = dataclasses.replace(
        plan.leaves[0],
        program=dataclasses.replace(plan.leaves[0].program,
                                    cost_bytes=-10.0))
    plan.leaves[0] = bad
    rep = verify_state_reshard(plan, strict=False)
    assert any("cost_bytes" in x for x in rep.violations), rep.violations
    with pytest.raises(PlanVerifyError):
        verify_state_reshard(plan)


def test_state_reshard_wrong_dst_caught():
    plan = compile_state_reshard(_state_items(), mesh, verify=False)
    leaf = plan.leaves[0]
    # a pass that retargeted the program without updating the leaf record
    plan.leaves[0] = dataclasses.replace(
        leaf, program=dataclasses.replace(leaf.program, dst=leaf.src))
    rep = verify_state_reshard(plan, strict=False)
    assert any("program.dst" in x for x in rep.violations), rep.violations
    # ...and a leaf whose recorded dst drifted from the program's real target
    plan2 = compile_state_reshard(_state_items(), mesh, verify=False)
    l2 = plan2.leaves[0]
    plan2.leaves[0] = dataclasses.replace(l2, dst=l2.src)
    rep2 = verify_state_reshard(plan2, strict=False)
    assert any("does not reach" in x or "program.dst" in x
               for x in rep2.violations), rep2.violations


# ---------------------------------------------------------------------------------
# wiring: the default compile path verifies (and raises) on corruption
# ---------------------------------------------------------------------------------


def test_compile_paths_verify_by_default():
    # compile_plan / lower_for_cost run the verifier by default — a clean
    # lowering must not raise and must bump telemetry
    before = verify_telemetry()["plans_verified"]
    closed = jax.make_jaxpr(_mlp)(*MLP_AVALS)
    prop = propagate(closed, mesh).result()
    compile_plan(closed, prop, mesh)
    lower_for_cost(closed, [None] * 3, mesh)
    compile_state_reshard(_state_items(), mesh)
    assert verify_telemetry()["plans_verified"] >= before + 3


def test_verify_flag_disables():
    plan = _plan(_mlp, *MLP_AVALS, verify=False)
    del plan.steps[0]
    # re-lowering with verify=False must not raise even though the plan is
    # mutilated — the flag is honored end to end
    rep = verify_plan(plan, strict=False)
    assert not rep.ok


# ---------------------------------------------------------------------------------
# recursive inner-plan accounting (post-hoist refresh + seeded staleness)
# ---------------------------------------------------------------------------------


def _scan_with_invariant_gather(hoistable=True):
    """Whole-program scan whose body reshards an invariant const.  With
    ``hoistable`` the gather lifts out of the body (the hoist pass mutates
    the inner step list in place); adding a direct unresharded reader of the
    const pins the reshard inside the body."""
    from jax import lax

    wsh = mesh_split(2, mesh, ["y", -1])
    rep = mesh_split(2, mesh, [-1, -1])

    def f(xs, w, c0):
        w = annotate(w, wsh)

        def body(c, x):
            wg = annotate(annotate(w, wsh), rep)
            out = jnp.tanh(c + x @ wg)
            if not hoistable:
                out = out + jnp.sum(w)
            return out, ()

        c, _ = lax.scan(body, c0, xs)
        return c

    return f, [_f32(4, 64, 64), _f32(64, 64), _f32(64, 64)]


def test_hoisted_scan_plan_verifies_clean():
    """The hoist pass edits an already-optimized inner plan; its refreshed
    opt_report must keep the recursive byte/peak accounting green."""
    f, avals = _scan_with_invariant_gather(hoistable=True)
    plan = _plan(f, *avals)
    (scan,) = [s for s in plan.steps if s.op == "scan"]
    # precondition: the hoist actually fired (body is reshard-free)
    assert sum(1 for s in scan.inner.steps if s.kind == "reshard") == 0
    rep = verify_plan(plan, strict=False)
    assert rep.ok, rep.violations
    # the inner report reflects the *edited* body, not the pre-hoist one
    inner_rep = scan.inner.opt_report
    assert inner_rep is not None
    assert inner_rep.steps_after == len(scan.inner.steps)
    from repro.core.plan_opt import whole_wire_bytes

    assert inner_rep.wire_bytes_after == pytest.approx(
        whole_wire_bytes(scan.inner))


def test_stale_inner_plan_accounting_caught():
    """Seeded regression: mutate an optimized inner plan's step list without
    refreshing its report — exactly the pre-fix hoist bug — and the verifier
    must flag it with the inner path."""
    f, avals = _scan_with_invariant_gather(hoistable=False)
    plan = _plan(f, *avals)
    (scan,) = [s for s in plan.steps if s.op == "scan"]
    inner = scan.inner
    reshards = [i for i, s in enumerate(inner.steps) if s.kind == "reshard"]
    assert reshards, "pinned reshard should remain in the body"
    assert verify_plan(plan, strict=False).ok
    del inner.steps[reshards[0]]  # a buggy pass dropping an inner step
    rep = verify_plan(plan, strict=False)
    assert not rep.ok
    assert any(".inner." in v for v in rep.violations), rep.violations
