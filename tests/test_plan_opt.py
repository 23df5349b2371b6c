"""Whole-plan optimizer unit tests (single device, pure planning).

The pass pipeline (``core/plan_opt.py``) and the lattice reshard search
(``collective_planner._candidate_search``) are pure functions of the plan /
shardings, so their structure is tested here on pod-size meshes without any
devices.  Execution parity (CSE / fused collectives produce identical
numerics) lives in tests/multidev/test_plan_opt_multidev.py.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

sys.path.insert(0, os.path.dirname(__file__))
try:
    from hypothesis import given, settings, strategies as hs
except ImportError:  # container lacks hypothesis; deterministic fallback
    from _hypo_stub import given, settings, strategies as hs

from repro.core import Mesh, annotate, mesh_split, propagate
from repro.core.collective_planner import PlanError, plan_reshard, simulate
from repro.core.plan import compile_plan
from repro.core.plan_opt import optimize_plan

mesh = Mesh.create((4, 8), ("x", "y"))
R = mesh_split(2, mesh, [-1, -1])


def _plans(f, *avals):
    """Compile the same propagated jaxpr twice: raw and optimized."""
    closed = jax.make_jaxpr(f)(*avals)
    prop = propagate(closed, mesh).result()
    return (
        compile_plan(closed, prop, mesh, optimize=False),
        compile_plan(closed, prop, mesh, optimize=True),
    )


def _reshards(plan):
    return [s for s in plan.steps if s.kind == "reshard"]


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _pass(plan, name):
    (rep,) = [p for p in plan.opt_report.passes if p.name == name]
    return rep


# ---------------------------------------------------------------------------------
# pass 1: reshard CSE
# ---------------------------------------------------------------------------------


def test_cse_shared_operand_reshards_once():
    """A shared operand consumed by two einsums needing the same reshard must
    reshard exactly once after CSE."""

    def f(a, w1, w2):
        a = annotate(a, mesh_split(2, mesh, ["y", -1]))
        w1 = annotate(w1, mesh_split(2, mesh, ["y", -1]))
        w2 = annotate(w2, mesh_split(2, mesh, ["y", -1]))
        return (a @ w1) + (a @ w2)

    raw, opt = _plans(f, _f32(64, 64), _f32(64, 64), _f32(64, 64))
    # the builder emits one reshard of `a` per consuming einsum
    assert len(_reshards(raw)) == 2
    assert len(_reshards(opt)) == 1
    rep = opt.opt_report
    cse = _pass(opt, "reshard-cse")
    assert cse.removed_steps == 1
    assert cse.wire_bytes_saved > 0
    assert rep.wire_bytes_after < rep.wire_bytes_before
    assert rep.collectives_after < rep.collectives_before


def test_cse_duplicate_feeding_output_becomes_alias():
    """When the duplicate reshard's result is a jaxpr output, CSE must keep
    the env write (as a free alias), not drop the value."""
    tgt = mesh_split(2, mesh, [-1, "y"])

    def f(a):
        a = annotate(a, mesh_split(2, mesh, ["x", -1]))
        b = annotate(a, tgt)
        c = annotate(a, tgt)
        return b, c

    raw, opt = _plans(f, _f32(64, 64))
    assert len(_reshards(raw)) == 2
    assert len(_reshards(opt)) == 1
    aliases = [s for s in opt.steps if s.kind == "compute" and s.op == "alias"]
    assert len(aliases) == 1
    # both outputs still written
    writes = {id(w) for s in opt.steps for w in s.writes}
    for v in opt.jaxpr.outvars:
        assert id(v) in writes


# ---------------------------------------------------------------------------------
# pass 2: dead-reshard elimination
# ---------------------------------------------------------------------------------


def test_dead_reshard_eliminated():
    """An annotation whose resharded value is never consumed must not emit
    collectives."""

    def f(a):
        a1 = annotate(a, mesh_split(2, mesh, ["x", -1]))
        _dead = annotate(a1, mesh_split(2, mesh, [-1, "y"]))
        return jnp.tanh(a1)

    raw, opt = _plans(f, _f32(64, 64))
    # the dead [x,-1] -> [-1,y] move, plus the (first-class) output-epilogue
    # reshard — the dead annotate's locked seed leaks into the propagated
    # output sharding, so the epilogue reshards the output back
    dead = [s for s in _reshards(raw) if s.writes[0] not in raw.out_keys]
    assert len(dead) == 1
    assert dead[0].program.cost_bytes > 0
    # DCE drops the dead reshard; the epilogue reshard (a root) survives
    assert [s for s in _reshards(opt) if s.writes[0] not in opt.out_keys] == []
    dce = _pass(opt, "dead-reshard-elim")
    assert dce.removed_steps == 1
    assert dce.wire_bytes_saved > 0


def test_noop_reshard_never_emitted():
    """Source already matching the target: the builder emits an alias, never
    a reshard program (so DCE has nothing to do and execution is free)."""

    def f(a):
        a = annotate(a, mesh_split(2, mesh, ["x", -1]))
        a = annotate(a, mesh_split(2, mesh, ["x", -1]))  # no-op
        return a

    raw, _ = _plans(f, _f32(64, 64))
    assert len(_reshards(raw)) == 0


# ---------------------------------------------------------------------------------
# pass 4: collective fusion / bucketing
# ---------------------------------------------------------------------------------


def _fanout_psum(k=4, n=64):
    """k independent matmuls with a contracted-sharded operand: k trailing
    AllReduces on independent values."""

    def f(a, *ws):
        a = annotate(a, mesh_split(2, mesh, ["y", -1]))
        outs = []
        for w in ws:
            w = annotate(w, mesh_split(2, mesh, ["y", -1]))
            outs.append(annotate(a @ w, R))
        return tuple(outs)

    return f, [_f32(n, n)] * (k + 1)


def test_fused_allreduce_bucket():
    f, avals = _fanout_psum()
    raw, opt = _plans(f, *avals)
    assert sum(1 for s in raw.steps if s.kind == "collective") == 4
    fused = [s for s in opt.steps if s.kind == "fused"]
    assert len(fused) == 1 and fused[0].op == "fused-all-reduce"
    assert len(fused[0].reads) == 4
    assert opt.opt_report.fused_buckets == 1
    assert opt.opt_report.collectives_after < opt.opt_report.collectives_before
    assert opt.stats.collectives.get("fused-all-reduce") == 1


def test_fused_gather_hoists_independent_members():
    """Two fallback gathers of independent inputs fuse by hoisting the second
    up to the first (its input is a plan input, available from the start)."""

    def f(a, b):
        a = annotate(a, mesh_split(2, mesh, ["x", -1]))
        b = annotate(b, mesh_split(2, mesh, ["x", -1]))
        return lax.rev(a, (0,)) + lax.rev(b, (0,))

    raw, opt = _plans(f, _f32(64, 32), _f32(64, 32))
    fused = [s for s in opt.steps if s.kind == "fused"]
    assert len(fused) == 1 and fused[0].op == "fused-all-gather"
    # the fused gather must come before both rev compute steps
    idx = {id(s): i for i, s in enumerate(opt.steps)}
    revs = [s for s in opt.steps if s.op == "rev"]
    assert all(idx[id(fused[0])] < idx[id(r)] for r in revs)


def test_fusion_respects_dependency_chain():
    """Chained psums (h2 depends on h1 through the second matmul) must not
    fuse — neither hoist (late input) nor sink (intervening reader) is
    legal."""

    def f(a, w1, w2):
        a = annotate(a, mesh_split(2, mesh, ["y", -1]))
        w1 = annotate(w1, mesh_split(2, mesh, ["y", -1]))
        h1 = annotate(a @ w1, R)
        h1 = annotate(h1, mesh_split(2, mesh, ["y", -1]))
        w2 = annotate(w2, mesh_split(2, mesh, ["y", -1]))
        return annotate(h1 @ w2, R)

    _, opt = _plans(f, _f32(64, 64), _f32(64, 64), _f32(64, 64))
    assert [s for s in opt.steps if s.kind == "fused"] == []
    assert sum(1 for s in opt.steps if s.kind == "collective") == 2


def _check_write_before_read(plan):
    """Every step's reads must be produced by an earlier step or be a plan
    input/const/literal — the invariant every pass must preserve."""
    from jax.extend import core as excore

    avail = {id(v) for v in plan.jaxpr.invars}
    avail |= {id(v) for v in plan.jaxpr.constvars}
    for i, s in enumerate(plan.steps):
        for r in s.reads:
            if isinstance(r, excore.Literal):
                continue
            assert id(r) in avail, (
                f"step {i} ({s.kind}/{s.op}) reads a value produced later"
            )
        for w in s.writes:
            avail.add(id(w))
    writes = {id(w) for s in plan.steps for w in s.writes}
    for v in plan.jaxpr.outvars:
        if not isinstance(v, excore.Literal):
            assert id(v) in writes


def test_fusion_never_hoists_above_sunk_producer():
    """Regression: a hoist-mode bucket must not anchor above a *sink*-mode
    bucket that produces one of its inputs.  Here the two gather-y reshards
    form a sinking bucket (the second one's input arrives late) anchored at
    the second member, while the gather-x of the first gather-y's result
    looks hoistable by original positions — fusing it early would read a
    value that now only exists after the sunk anchor."""
    stacked = mesh_split(2, mesh, [("x", "y"), -1])
    xonly = mesh_split(2, mesh, ["x", -1])

    def f(u, a, v):
        u = annotate(u, stacked)
        u1 = annotate(u, xonly)        # gather-y (bucket Y member 1)
        b = annotate(a, xonly)
        r1 = lax.rev(b, (0,))          # gather-x of b (bucket X member 1)
        v = annotate(v, stacked)
        v1 = annotate(v, xonly)        # gather-y joins Y -> sink-anchored here
        r2 = lax.rev(u1, (0,))         # gather-x of u1: must NOT hoist into X
        return r1, v1, r2

    raw, opt = _plans(f, _f32(64, 16), _f32(64, 16), _f32(64, 16))
    _check_write_before_read(raw)
    _check_write_before_read(opt)
    # the legal fusion (the two gather-y reshards) still happens
    fused = [s for s in opt.steps if s.kind == "fused"]
    assert any(s.op == "fused-all-gather" and s.axes == ("y",) for s in fused)


def test_all_passes_preserve_write_before_read():
    """The SSA/order invariant holds on every optimized plan in this file's
    benchmark programs."""

    def shared(a, w1, w2):
        a = annotate(a, mesh_split(2, mesh, ["y", -1]))
        w1 = annotate(w1, mesh_split(2, mesh, ["y", -1]))
        w2 = annotate(w2, mesh_split(2, mesh, ["y", -1]))
        return (a @ w1) + (a @ w2)

    for fn, avals in [
        (shared, [_f32(64, 64)] * 3),
        (_fanout_psum()[0], _fanout_psum()[1]),
    ]:
        raw, opt = _plans(fn, *avals)
        _check_write_before_read(raw)
        _check_write_before_read(opt)


def test_bucket_cap_limits_fusion():
    """With a byte cap below one member's size, nothing fuses; the default
    roofline cap fuses all four."""
    f, avals = _fanout_psum()
    closed = jax.make_jaxpr(f)(*avals)
    prop = propagate(closed, mesh).result()
    raw = compile_plan(closed, prop, mesh, optimize=False)
    member_bytes = max(
        s.in_bytes for s in raw.steps if s.kind == "collective"
    )
    capped = optimize_plan(
        compile_plan(closed, prop, mesh, optimize=False),
        bucket_bytes=member_bytes / 2,
    )
    assert [s for s in capped.steps if s.kind == "fused"] == []
    full = optimize_plan(compile_plan(closed, prop, mesh, optimize=False))
    assert [len(s.reads) for s in full.steps if s.kind == "fused"] == [4]


# ---------------------------------------------------------------------------------
# pass 1/2: jit inlining + scan-invariant hoisting (whole-program plans)
# ---------------------------------------------------------------------------------

R_ = mesh_split(2, mesh, [-1, -1])
WSH = mesh_split(2, mesh, ["y", -1])


def _two_jit_shared_gather():
    """Two jit bodies each gathering the same param *inside* the body: the
    duplicate collective is invisible to the optimizer until inlining."""

    def block(x, w):
        wg = annotate(annotate(w, WSH), R_)
        return x @ wg

    blk = jax.jit(block)

    def f(x, w):
        return blk(x, w) + blk(jnp.sin(x), w)

    return f, [_f32(64, 64), _f32(64, 64)]


def test_inline_pjit_enables_cross_boundary_cse():
    from repro.core.plan_opt import whole_collective_launches, whole_wire_bytes

    f, avals = _two_jit_shared_gather()
    raw, opt = _plans(f, *avals)
    # raw: two opaque jit steps, one in-body gather each
    jits = [s for s in raw.steps if s.op == "jit"]
    assert len(jits) == 2
    assert all(
        sum(1 for t in s.inner.steps if t.kind == "reshard") == 1
        for s in jits
    )
    # optimized: bodies spliced, the duplicated gather CSE'd to one launch
    assert [s for s in opt.steps if s.op == "jit"] == []
    assert sum(1 for s in opt.steps if s.kind == "reshard") == 1
    assert _pass(opt, "inline-jit").inlined_bodies == 2
    assert whole_collective_launches(opt) < whole_collective_launches(raw)
    assert whole_wire_bytes(opt) < whole_wire_bytes(raw)
    rep = opt.opt_report
    assert rep.wire_bytes_after < rep.wire_bytes_before
    assert rep.collectives_after < rep.collectives_before
    _check_write_before_read(raw)
    _check_write_before_read(opt)


def test_inline_threads_flops_through_spliced_steps():
    """total_flops must be exact after inlining (the jit step's aggregate is
    replaced by the constituent steps' own annotations), and the removed call
    step's stale inner-plan transient must not survive anywhere."""
    f, avals = _two_jit_shared_gather()
    raw, opt = _plans(f, *avals)
    assert opt.total_flops() == pytest.approx(raw.total_flops())
    assert all(s.transient_bytes == 0.0 for s in opt.steps)
    assert opt.peak_bytes > 0.0


def test_inline_skips_nontrivial_bodies():
    """A jit body containing control flow (scan) must stay a call step."""

    def block(x):
        def body(c, _):
            return jnp.tanh(c), ()

        c, _ = lax.scan(body, x, None, length=3)
        return c

    blk = jax.jit(block)

    def f(x):
        return blk(x) * 2.0

    raw, opt = _plans(f, _f32(16, 16))
    assert [s.op for s in raw.steps if s.op == "jit"] == ["jit"]
    assert [s.op for s in opt.steps if s.op == "jit"] == ["jit"]
    assert _pass(opt, "inline-jit").inlined_bodies == 0


def _scan_invariant_gather(trips=4):
    def f(xs, w, c0):
        w = annotate(w, WSH)

        def body(c, x):
            wg = annotate(annotate(w, WSH), R_)
            return jnp.tanh(c + x @ wg), ()

        c, _ = lax.scan(body, c0, xs)
        return c

    return f, [_f32(trips, 64, 64), _f32(64, 64), _f32(64, 64)]


def test_scan_hoist_lifts_invariant_reshard():
    from repro.core.plan_opt import whole_wire_bytes

    f, avals = _scan_invariant_gather()
    raw, opt = _plans(f, *avals)

    def scan_step(p):
        (s,) = [s for s in p.steps if s.op == "scan"]
        return s

    assert sum(
        1 for s in scan_step(raw).inner.steps if s.kind == "reshard"
    ) == 1
    # hoisted: body is reshard-free, the gather runs once in the outer plan
    assert sum(
        1 for s in scan_step(opt).inner.steps if s.kind == "reshard"
    ) == 0
    assert _pass(opt, "scan-hoist").hoisted_reshards == 1
    idx = {id(s): i for i, s in enumerate(opt.steps)}
    gathers = [s for s in opt.steps if s.kind == "reshard"
               and any(ps.op == "all_gather" for ps in s.program.steps)]
    assert len(gathers) == 1
    assert idx[id(gathers[0])] < idx[id(scan_step(opt))]
    # the scan step reads the hoisted result
    assert any(r is gathers[0].writes[0] for r in scan_step(opt).reads)
    # whole-program wire bytes drop by (trips - 1) gathers
    assert whole_wire_bytes(opt) == pytest.approx(whole_wire_bytes(raw) / 4)
    # the scan step's transient was recomputed against the edited body
    # (satellite: no stale inner-plan peak survives the hoist) — note the
    # body's resident set can legitimately *grow*: the const now arrives
    # pre-gathered, so the replicated param is live for the whole body
    assert scan_step(opt).transient_bytes == scan_step(opt).inner.peak_bytes
    _check_write_before_read(opt)


def test_scan_hoist_skips_const_with_direct_reader():
    """A const the body also reads *unresharded* cannot be rebound."""

    def f(xs, w, c0):
        w = annotate(w, WSH)

        def body(c, x):
            wg = annotate(annotate(w, WSH), R_)  # in-body gather of the const
            return jnp.tanh(c + x @ wg) + jnp.sum(w), ()

        c, _ = lax.scan(body, c0, xs)
        return c

    _, opt = _plans(f, _f32(4, 64, 64), _f32(64, 64), _f32(64, 64))
    assert _pass(opt, "scan-hoist").hoisted_reshards == 0
    (s,) = [s for s in opt.steps if s.op == "scan"]
    assert sum(1 for t in s.inner.steps if t.kind == "reshard") >= 1


# ---------------------------------------------------------------------------------
# pass 7: overlap-aware list scheduling
# ---------------------------------------------------------------------------------


def _overlap_prog():
    def f(a, w1, w2, p):
        a = annotate(a, mesh_split(2, mesh, ["x", -1]))
        h = jnp.tanh(a @ w1) @ w2  # compute chain, no collectives
        p = annotate(p, WSH)
        pg = annotate(p, R_)  # independent gather
        return h + pg

    return f, [_f32(256, 256)] * 4


def test_schedule_overlap_issues_collective_early():
    f, avals = _overlap_prog()
    raw, opt = _plans(f, *avals)
    _check_write_before_read(opt)
    ov = opt.opt_report.overlap
    assert ov is not None
    assert 0.0 < ov["ratio"] < 1.0  # some comm time is hidden
    assert ov["overlapped_s"] <= ov["serial_s"]
    assert ov["overlapped_s"] >= max(ov["compute_s"], ov["comm_s"]) - 1e-12
    # the gather must be scheduled before the compute chain's second matmul
    idx_gather = min(
        i for i, s in enumerate(opt.steps) if s.kind == "reshard"
        and any(ps.op == "all_gather" for ps in s.program.steps)
    )
    dots = [i for i, s in enumerate(opt.steps) if s.op == "dot_general"]
    assert idx_gather < dots[-1]


def test_schedule_overlap_deterministic():
    f, avals = _overlap_prog()
    _, opt1 = _plans(f, *avals)
    _, opt2 = _plans(f, *avals)
    assert [(s.kind, s.op) for s in opt1.steps] == [
        (s.kind, s.op) for s in opt2.steps
    ]


def test_plan_cost_max_of_terms_objective():
    """The autoshard score is the overlap-aware max-of-terms roofline."""
    from repro.analysis.roofline import overlap_time_s
    from repro.core.plan import PlanCost

    c = PlanCost(wire_bytes=1e9, launches=10, flops_per_device=1e12,
                 ideal_flops_per_device=5e11, peak_bytes=1e9, steps=7)
    assert c.total_s == pytest.approx(
        overlap_time_s(c.compute_s, c.collective_s)
    )
    # dominant-term behavior: growing the hidden term barely moves the total
    c2 = PlanCost(wire_bytes=1e9, launches=10, flops_per_device=2e12,
                  ideal_flops_per_device=5e11, peak_bytes=1e9, steps=7)
    assert c2.total_s > c.total_s
    assert c.collective_s > c.compute_s  # comm-dominated here
    assert c2.total_s - c.total_s < (c2.compute_s - c.compute_s)


# ---------------------------------------------------------------------------------
# lattice search (branch-and-bound over the step lattice)
# ---------------------------------------------------------------------------------

mesh3 = Mesh.create((2, 2, 4), ("x", "y", "z"))
AXES3 = [(), ("x",), ("y",), ("z",), ("x", "y"), ("y", "z"), ("z", "x"),
         ("z", "y"), ("x", "y", "z")]


def test_lattice_strictly_beats_greedy_on_stacked_target():
    """Moving x out of the way via AllToAll so the slices happen first is
    cheaper than greedy's AllGather; search finds it, greedy cannot."""
    src = mesh_split(2, mesh3, [-1, "x"])
    dst = mesh_split(2, mesh3, [-1, ("z", "x")])
    local = (64, 32)
    greedy = plan_reshard(src, dst, local, 4, search=False)
    lat = plan_reshard(src, dst, local, 4, search=True)
    assert lat.strategy == "lattice"
    assert lat.cost_bytes < greedy.cost_bytes
    # the chosen program must still validate under simulation
    assert simulate(src, dst, list(lat.steps), local, 4) == lat.cost_bytes


@given(
    hs.sampled_from(AXES3), hs.sampled_from(AXES3),
    hs.sampled_from(AXES3), hs.sampled_from(AXES3),
)
@settings(max_examples=40, deadline=None)
def test_lattice_never_worse_than_pr1_planner(d0, d1, e0, e1):
    """Property (satellite): over random 3-axis layouts the search-enabled
    planner never returns a costlier program than the PR 1 candidates."""
    if set(d0) & set(d1) or set(e0) & set(e1):
        return
    src = mesh_split(2, mesh3, [d0 or -1, d1 or -1])
    dst = mesh_split(2, mesh3, [e0 or -1, e1 or -1])
    local = tuple(64 // src.num_shards(i) for i in (0, 1))
    try:
        greedy = plan_reshard(src, dst, local, 4, search=False)
    except PlanError:
        return
    lat = plan_reshard(src, dst, local, 4, search=True)
    assert lat.cost_bytes <= greedy.cost_bytes + 1e-9
    assert simulate(src, dst, list(lat.steps), local, 4) == pytest.approx(
        lat.cost_bytes
    )


# ---------------------------------------------------------------------------------
# process-level plan cache
# ---------------------------------------------------------------------------------


@pytest.mark.parametrize("verify2", [{}, {"verify": True}],
                         ids=["default", "verify_true"])
def test_process_cache_shared_across_runners(verify2):
    from repro.core.compat import make_jax_mesh
    from repro.core.partitioner import (
        clear_process_plan_cache, process_plan_cache_stats, spmd_partition,
    )

    jmesh = make_jax_mesh((1, 1), ("x", "y"))
    m = Mesh.create((1, 1), ("x", "y"))

    def make_fn():
        # distinct Python callables per runner: the digest, not identity,
        # must be what shares the plan
        def f(a, b):
            a = annotate(a, mesh_split(2, m, ["x", -1]))
            return jnp.tanh(a @ b) * 3.0

        return f

    clear_process_plan_cache()
    x = np.ones((4, 4), np.float32)
    r1 = spmd_partition(make_fn(), jmesh, m)
    out1 = r1(x, x)
    assert process_plan_cache_stats().as_dict()["misses"] == 1
    # passing the default explicitly must not key a second entry
    r2 = spmd_partition(make_fn(), jmesh, m, **verify2)
    out2 = r2(x, x)
    st = process_plan_cache_stats().as_dict()
    assert st["hits"] == 1 and st["misses"] == 1
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    # shared entry: both runners hold the same plan object
    (e1,) = r1.plans.values()
    (e2,) = r2.plans.values()
    assert e1.plan is e2.plan
    clear_process_plan_cache()


def test_process_cache_distinguishes_different_programs():
    from repro.core.compat import make_jax_mesh
    from repro.core.partitioner import (
        clear_process_plan_cache, process_plan_cache_stats, spmd_partition,
    )

    jmesh = make_jax_mesh((1, 1), ("x", "y"))
    m = Mesh.create((1, 1), ("x", "y"))
    clear_process_plan_cache()
    x = np.ones((4, 4), np.float32)
    spmd_partition(lambda a: a * 2.0, jmesh, m)(x)
    spmd_partition(lambda a: a * 3.0, jmesh, m)(x)  # different const payload
    st = process_plan_cache_stats().as_dict()
    assert st["misses"] == 2 and st["hits"] == 0
    clear_process_plan_cache()


# ---------------------------------------------------------------------------------
# report plumbing
# ---------------------------------------------------------------------------------


def test_opt_report_as_dict_schema():
    f, avals = _fanout_psum()
    _, opt = _plans(f, *avals)
    d = opt.opt_report.as_dict()
    for k in ("passes", "steps_before", "steps_after", "collectives_before",
              "collectives_after", "wire_bytes_before", "wire_bytes_after",
              "fused_buckets", "launch_s_saved"):
        assert k in d, k
    assert d["steps_after"] <= d["steps_before"]
    assert d["collectives_after"] <= d["collectives_before"]
    assert d["wire_bytes_after"] <= d["wire_bytes_before"]
    assert [p["name"] for p in d["passes"]] == [
        "inline-jit", "scan-hoist", "reshard-cse", "dead-reshard-elim",
        "alias-sink", "collective-fusion", "overlap-schedule",
    ]
    assert d["overlap"] is not None
    assert 0.0 < d["overlap"]["ratio"] <= 1.0 + 1e-9
    for k in ("compute_s", "comm_s", "serial_s", "overlapped_s"):
        assert k in d["overlap"], k
