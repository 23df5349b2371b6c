"""Pipeline subsystem tests (§3.3 stage-stacked pipelining over plans).

Single-device: semantics (bit-identity vs the plain stack, fwd + grads),
plan structure (one first-class ppermute per tick, priced into PlanCost),
ppermute fusion, the schedule cost model, the pipeline decision space, the
soft-memory objective term, and the grad-of-scan (reverse) lowering fix.
Execution parity on real collectives lives in
tests/multidev/test_pipeline_multidev.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Mesh, annotate, mesh_split
from repro.core.plan import compile_plan, plan_cost
from repro.core.propagation import propagate
from repro.core.shift import stage_shift, take_stage_row
from repro.pipeline import (
    PipelineConfig,
    bubble_fraction,
    pipeline_ticks,
    pipelined_apply,
    plan_ppermute_bytes,
    stage_stack_params,
)
from repro.pipeline.schedule import PipelineDecision

rng = np.random.default_rng(0)

L, D, M, MB = 4, 8, 4, 2
WS = jnp.asarray(rng.standard_normal((L, D, D)).astype(np.float32) * 0.3)
XS = jnp.asarray(rng.standard_normal((M, MB, D)).astype(np.float32))


def layer(lp, x, _):
    return jnp.tanh(x @ lp)


def ref_fn(ws, xs):
    def f(h):
        for i in range(ws.shape[0]):
            h = jnp.tanh(h @ ws[i])
        return h

    return jnp.stack([f(xs[m]) for m in range(xs.shape[0])])


# ---------------------------------------------------------------------------------
# semantics
# ---------------------------------------------------------------------------------


def test_stage_stack_layout_is_contiguous_gpipe():
    stk = stage_stack_params(WS, 2)
    assert stk.shape == (2, 2, D, D)
    np.testing.assert_array_equal(np.asarray(stk[1, 0]), np.asarray(WS[2]))


@pytest.mark.parametrize("S", [1, 2, 4])
def test_pipelined_apply_bit_identical_to_stack(S):
    got = jax.jit(
        lambda w, x: pipelined_apply(layer, w, x, num_stages=S)
    )(stage_stack_params(WS, S), XS)
    ref = jax.jit(ref_fn)(WS, XS)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_pipelined_apply_grads_bit_identical():
    def loss(w, x):
        return jnp.mean(pipelined_apply(layer, w, x, num_stages=2) ** 2)

    def loss_ref(w, x):
        return jnp.mean(ref_fn(w, x) ** 2)

    gw, gx = jax.jit(jax.grad(loss, argnums=(0, 1)))(stage_stack_params(WS, 2), XS)
    rw, rx = jax.jit(jax.grad(loss_ref, argnums=(0, 1)))(WS, XS)
    np.testing.assert_array_equal(np.asarray(gw).reshape(L, D, D), np.asarray(rw))
    np.testing.assert_array_equal(np.asarray(gx), np.asarray(rx))


# ---------------------------------------------------------------------------------
# plan structure: the per-tick ppermute is a first-class, priced step
# ---------------------------------------------------------------------------------


def _pipelined_plan(S=4, M=4):
    mesh = Mesh.create((S,), ("stage",))
    xs = jnp.asarray(rng.standard_normal((M, MB, D)).astype(np.float32))

    def fn(wstk, xs):
        wstk = annotate(wstk, mesh_split(4, mesh, ["stage", -1, -1, -1]))
        ys = pipelined_apply(layer, wstk, xs, num_stages=S,
                             mesh=mesh, stage_axis="stage")
        return jnp.mean(ys ** 2)

    closed = jax.make_jaxpr(fn)(
        jax.ShapeDtypeStruct((S, L // S, D, D), jnp.float32),
        jax.ShapeDtypeStruct((M, MB, D), jnp.float32),
    )
    prop = propagate(closed, mesh).result()
    return compile_plan(closed, prop, mesh, cost_only=True), mesh


def _scan_step(plan):
    steps = [s for s in plan.steps if s.op == "scan" and s.inner is not None]
    assert len(steps) == 1, [s.op for s in plan.steps]
    return steps[0]


def test_each_tick_issues_exactly_one_ppermute():
    plan, _ = _pipelined_plan(S=4, M=4)
    scan = _scan_step(plan)
    assert scan.call["trips"] == pipeline_ticks(4, 4)
    pperms = [s for s in scan.inner.steps
              if s.kind == "collective" and s.op == "ppermute"]
    assert len(pperms) == 1
    (pp,) = pperms
    assert pp.axes == ("stage",)
    # GPipe forward shift: each device sends its boundary row right
    assert pp.call["perm"] == tuple((i, i + 1) for i in range(3))
    # the per-tick output collection is a first-class psum, also one per tick
    psums = [s for s in scan.inner.steps if s.kind == "collective"
             and s.op != "ppermute"]
    assert len(psums) == 1 and psums[0].reduce_op == "add"


def test_ppermute_priced_into_plan_cost():
    plan, _ = _pipelined_plan(S=4, M=4)
    scan = _scan_step(plan)
    ticks = scan.call["trips"]
    (pp,) = [s for s in scan.inner.steps
             if s.kind == "collective" and s.op == "ppermute"]
    # boundary row: one stage slot of the local state
    assert pp.in_bytes == MB * D * 4
    pbytes, launches = plan_ppermute_bytes(plan)
    assert launches == ticks
    assert pbytes == pytest.approx(ticks * pp.in_bytes)
    cost = plan_cost(plan)
    # whole-program collective pricing (trip-multiplied) must cover them
    assert cost.wire_bytes >= pbytes
    assert cost.launches >= launches


def test_same_perm_ppermutes_fuse():
    """Two independent boundary hops with the same (axis, perm) share one
    fused launch once adjacent (the pass's own placement legality applies)."""
    from repro.core.plan_opt import fuse_collectives

    mesh = Mesh.create((4,), ("stage",))

    def fn(a, b, x, y):
        a = annotate(a, mesh_split(2, mesh, ["stage", -1]))
        b = annotate(b, mesh_split(2, mesh, ["stage", -1]))
        return stage_shift(a, x) + stage_shift(b, y)

    closed = jax.make_jaxpr(fn)(
        jax.ShapeDtypeStruct((4, 3), jnp.float32),
        jax.ShapeDtypeStruct((4, 3), jnp.float32),
        jax.ShapeDtypeStruct((3,), jnp.float32),
        jax.ShapeDtypeStruct((3,), jnp.float32),
    )
    prop = propagate(closed, mesh).result()
    plan = compile_plan(closed, prop, mesh, cost_only=True, optimize=False)
    # emission interleaves slice/ppermute/stitch per shift; reorder the two
    # shifts' steps so the ppermutes are adjacent (write-before-read holds:
    # aliases, then both boundary slices, then both hops, then consumers)
    order = {("compute", "annotate"): 0, ("compute", "alias"): 0,
             ("compute", "shift-boundary"): 1, ("collective", "ppermute"): 2}
    plan.steps.sort(key=lambda s: order.get((s.kind, s.op), 3))
    rep = fuse_collectives(plan)
    assert rep.fused_buckets == 1 and rep.fused_members == 2
    fused = [s for s in plan.steps if s.op == "fused-ppermute"]
    assert len(fused) == 1
    assert fused[0].call["perm"] == tuple((i, i + 1) for i in range(3))


def test_grad_of_scan_lowers_reverse():
    """Regression: grad-of-scan is a reverse scan; the plan runner must
    replay it back to front (found by the pipeline backward, which reads a
    different cotangent microbatch every tick)."""
    from jax import lax

    mesh = Mesh.create((1,), ("x",))

    def f(xs):
        def body(c, x):
            return c * 0.5 + x, c

        c, ys = lax.scan(body, jnp.float32(0.0), xs)
        return c + jnp.sum(ys * jnp.arange(4.0, dtype=jnp.float32))

    xs = jnp.arange(4.0, dtype=jnp.float32)
    closed = jax.make_jaxpr(jax.grad(f))(xs)
    prop = propagate(closed, mesh).result()
    plan = compile_plan(closed, prop, mesh)
    (got,) = plan.execute(xs)
    (want,) = (jax.grad(f)(xs),)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


# ---------------------------------------------------------------------------------
# schedule cost model
# ---------------------------------------------------------------------------------


def test_bubble_fraction_closed_form():
    assert bubble_fraction(4, 4) == pytest.approx(3 / 7)
    assert bubble_fraction(1, 8) == 0.0
    assert pipeline_ticks(4, 4) == 7
    d = PipelineDecision("stage", 4, 4)
    assert d.bubble == pytest.approx(3 / 7) and d.ticks == 7


def test_bubble_shows_up_as_compute_inflation():
    """All stages compute every tick, so modeled per-device FLOPs of the
    pipelined plan are (M + S − 1)/M × the useful per-microbatch work."""
    plan4, _ = _pipelined_plan(S=4, M=4)
    plan4b, _ = _pipelined_plan(S=4, M=8)
    f4 = plan_cost(plan4).flops_per_device
    f4b = plan_cost(plan4b).flops_per_device
    # per-tick flops are equal; tick counts are 7 vs 11
    assert f4b / f4 == pytest.approx(11 / 7, rel=0.02)


def test_schedule_cost_summary():
    from repro.pipeline.schedule import schedule_cost

    S, M = 4, 4
    mesh = Mesh.create((S,), ("stage",))
    dec = PipelineDecision("stage", S, M)

    def fn(wstk, xs):
        wstk = annotate(wstk, mesh_split(4, mesh, ["stage", -1, -1, -1]))
        ys = pipelined_apply(layer, wstk, xs, num_stages=S,
                             mesh=mesh, stage_axis="stage")
        return jnp.mean(ys ** 2)

    closed = jax.make_jaxpr(fn)(
        jax.ShapeDtypeStruct((S, L // S, D, D), jnp.float32),
        jax.ShapeDtypeStruct((M, MB, D), jnp.float32),
    )
    sc = schedule_cost(closed, [None, None], mesh, dec,
                       state_shape=(S, MB, D))
    assert sc.bubble == pytest.approx(bubble_fraction(S, M))
    assert sc.ppermute_launches == pipeline_ticks(S, M)
    assert sc.ppermute_bytes > 0
    # stage dim sharded: one stage row per device
    assert sc.microbatch_activation_bytes == MB * D * 4
    assert sc.total_s > 0
    rec = sc.as_dict()
    assert rec["bubble_fraction"] == sc.bubble


# ---------------------------------------------------------------------------------
# decision space + memory term
# ---------------------------------------------------------------------------------


def test_pipeline_decisions_enumeration():
    from repro.autoshard.space import pipeline_decisions

    mesh = Mesh.create((2, 4), ("data", "model"))
    decs = pipeline_decisions(mesh, num_layers=4, batch=8,
                              pcfg=PipelineConfig(max_stages=4))
    got = {(d.stage_axis, d.num_stages, d.num_microbatches) for d in decs}
    # data(2): S in {2, 4}; model(4): S = 4; M in {2, 4}; all divide L=4, B=8
    assert got == {
        ("data", 2, 2), ("data", 2, 4), ("data", 4, 2), ("data", 4, 4),
        ("model", 4, 2), ("model", 4, 4),
    }
    # stage counts must divide the layer count
    decs3 = pipeline_decisions(mesh, num_layers=6, batch=8,
                               pcfg=PipelineConfig(max_stages=4))
    assert {(d.stage_axis, d.num_stages) for d in decs3} == {("data", 2)}
    # microbatches must divide the batch
    decs5 = pipeline_decisions(mesh, num_layers=4, batch=6,
                               pcfg=PipelineConfig(max_stages=2))
    assert all(d.num_microbatches == 2 for d in decs5)


def test_solve_with_pipeline_returns_mixed_assignment():
    """ISSUE-5 acceptance: ``autoshard.solve(..., pipeline=PipelineConfig
    (max_stages=4))`` on a 2×4 mesh returns a pipeline+tensor point whose
    modeled cost is at or below the best pure-tensor assignment.  The budget
    sits below the pure-tensor search's feasible floor (its activation peak
    cannot fit), while the pipelined rewrite fits — the §3.3 microbatched
    shifting buffer holds one microbatch per stage row."""
    from repro import autoshard

    mesh = Mesh.create((2, 4), ("data", "model"))
    # pure tensor parallelism peaks at 27.3 MB at best, the pipelined point
    # at 23.0 MB (modeled; the embedding lookups run per shard)
    cfg = autoshard.AutoshardConfig(
        budget_bytes=25e6, top_n=2, sa_steps=2, beam_width=2,
        max_candidates=6,
    )
    kw = dict(batch=4, seq=32, reduce_k=6)
    pure = autoshard.solve("qwen1.5-0.5b", mesh, cfg, **kw)
    res = autoshard.solve(
        "qwen1.5-0.5b", mesh, cfg, **kw,
        pipeline=PipelineConfig(max_stages=4, num_microbatches=2,
                                stage_axes=("model",)),
    )
    assert res.pipeline is not None, "no pipeline decision chosen"
    assert res.evaluation.feasible
    assert res.evaluation.score <= pure.evaluation.score
    assert res.pipeline["stage_axis"] == "model"
    assert res.pipeline["num_stages"] == 4
    assert res.pipeline["bubble_fraction"] == pytest.approx(
        bubble_fraction(4, 2))
    assert res.pipeline["ppermute_launches"] == pipeline_ticks(4, 2)
    # mixed pipeline+tensor: the assignment tensor-shards on a non-stage axis
    assert any(
        s is not None and any(
            a != "model" for dm in s.dims_mapping for a in dm)
        for s in res.assignment
    )
    # the decision round-trips through the JSON dump
    rec = res.to_json()
    assert rec["pipeline"]["num_microbatches"] == 2


def test_mem_term_breaks_pipeline_search_tie():
    """Satellite: the soft-memory objective term.  A pipelined step that
    threads the NEXT microbatch buffer through untouched (prefetch) has a
    genuine roofline tie: sharding the buffer moves zero wire bytes and zero
    FLOPs, so with the term off the greedy sweep keeps the replication
    default; with the term on, the lower-peak assignment strictly wins."""
    from repro import autoshard

    S, M_ = 4, 4
    mesh = Mesh.create((S,), ("stage",))

    def fn(wstk, xs, prefetch):
        ys = pipelined_apply(layer, wstk, xs, num_stages=S,
                             mesh=mesh, stage_axis="stage")
        return jnp.mean(ys ** 2)

    closed = jax.make_jaxpr(fn)(
        jax.ShapeDtypeStruct((S, L // S, D, D), jnp.float32),
        jax.ShapeDtypeStruct((M_, MB, D), jnp.float32),
        jax.ShapeDtypeStruct((64, MB, D), jnp.float32),  # largest invar
    )
    cfg = dict(top_n=1, sa_steps=0, max_candidates=8)
    off = autoshard.solve_problem(
        closed, mesh, autoshard.AutoshardConfig(**cfg))
    on = autoshard.solve_problem(
        closed, mesh,
        autoshard.AutoshardConfig(mem_weight=1.0, soft_budget_bytes=0.0,
                                  **cfg))
    assert off.evaluation.cost.mem_s == 0.0
    assert on.evaluation.cost.mem_s > 0.0
    # the tie: scores identical under the pure roofline objective...
    base_terms = off.evaluation.cost
    picked = on.evaluation.cost
    assert picked.wire_bytes == base_terms.wire_bytes
    assert picked.flops_per_device == base_terms.flops_per_device
    # ...so only the memory term separates them, and it picks the lower peak
    assert picked.peak_bytes < base_terms.peak_bytes
    # with the term off, the prefetch buffer stayed with propagation (None)
    assert off.assignment[2] is None
    assert on.assignment[2] is not None
