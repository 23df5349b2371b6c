"""Whole-plan optimizer execution parity on fake devices (2×2 mesh).

The passes are semantics-preserving by construction; these tests check it on
real collectives: CSE'd plans match the unpartitioned oracle, fused AllReduce
is *bit-identical* to unfused (the fused psum sums the same elements in the
same device order, only batched through one launch), and dead-reshard
elimination does not disturb the live dataflow.  Run via
test_multidev_launcher.py (REPRO_MULTIDEV=1, 8 fake CPU devices).
"""
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import Mesh, annotate, mesh_split
from repro.core.compat import assert_close, make_jax_mesh
from repro.core.partitioner import spmd_partition

jmesh = make_jax_mesh((2, 2), ("x", "y"))
mesh = Mesh.create((2, 2), ("x", "y"))
R = mesh_split(2, mesh, [-1, -1])
rng = np.random.default_rng(7)


def _runner(f, optimize):
    # these tests compare plan *structure* across optimize settings;
    # ``optimize`` is part of the process-cache key, so the two never alias
    return spmd_partition(f, jmesh, mesh, optimize=optimize)


def _the_plan(runner):
    (entry,) = runner.plans.values()
    return entry.plan


def _pass(plan, name):
    (rep,) = [p for p in plan.opt_report.passes if p.name == name]
    return rep


def test_cse_shared_operand_reshards_once_and_matches():
    def f(a, w1, w2):
        a = annotate(a, mesh_split(2, mesh, ["y", -1]))
        w1 = annotate(w1, mesh_split(2, mesh, ["y", -1]))
        w2 = annotate(w2, mesh_split(2, mesh, ["y", -1]))
        return (a @ w1) + (a @ w2)

    x = rng.standard_normal((8, 8)).astype(np.float32)
    w1 = rng.standard_normal((8, 8)).astype(np.float32)
    w2 = rng.standard_normal((8, 8)).astype(np.float32)
    r = _runner(f, True)
    got = np.asarray(r(x, w1, w2))
    assert_close(got, (x @ w1) + (x @ w2), "f32_dot")
    plan = _the_plan(r)
    assert sum(1 for s in plan.steps if s.kind == "reshard") == 1
    assert _pass(plan, "reshard-cse").removed_steps == 1


def test_dead_reshard_eliminated_and_matches():
    def f(a):
        a1 = annotate(a, mesh_split(2, mesh, ["x", -1]))
        _dead = annotate(a1, mesh_split(2, mesh, [-1, "y"]))
        return jnp.tanh(a1)

    x = rng.standard_normal((8, 8)).astype(np.float32)
    r = _runner(f, True)
    assert_close(r(x), np.tanh(x), "f32")
    plan = _the_plan(r)
    # only the (first-class) output-epilogue reshard survives; the dead
    # [x,-1] -> [-1,y] body reshard is eliminated
    body = [s for s in plan.steps
            if s.kind == "reshard" and s.writes[0] not in plan.out_keys]
    assert body == []
    assert _pass(plan, "dead-reshard-elim").removed_steps == 1


def test_fused_allreduce_bit_identical_to_unfused():
    """Satellite acceptance: fused AllReduce output on a 2×2 mesh is
    bit-identical to the unfused plan (same per-element device summation
    order, one launch instead of four)."""

    def f(a, w1, w2, w3, w4):
        a = annotate(a, mesh_split(2, mesh, ["y", -1]))
        outs = []
        for w in (w1, w2, w3, w4):
            w = annotate(w, mesh_split(2, mesh, ["y", -1]))
            outs.append(annotate(a @ w, R))
        return tuple(outs)

    args = [rng.standard_normal((8, 8)).astype(np.float32) for _ in range(5)]
    r_opt = _runner(f, True)
    r_raw = _runner(f, False)
    got_opt = r_opt(*args)
    got_raw = r_raw(*args)
    plan = _the_plan(r_opt)
    fused = [s for s in plan.steps if s.kind == "fused"]
    assert len(fused) == 1 and len(fused[0].reads) == 4
    for o, u in zip(got_opt, got_raw):
        o, u = np.asarray(o), np.asarray(u)
        assert o.dtype == u.dtype and o.shape == u.shape
        assert o.tobytes() == u.tobytes(), "fused psum must be bit-identical"
    # and both match the oracle
    a = args[0]
    for o, w in zip(got_opt, args[1:]):
        assert_close(o, a @ w, "f32_dot")


def test_fused_allgather_matches_oracle():
    def f(a, b):
        a = annotate(a, mesh_split(2, mesh, ["x", -1]))
        b = annotate(b, mesh_split(2, mesh, ["x", -1]))
        return lax.rev(a, (0,)) + lax.rev(b, (0,))

    x = rng.standard_normal((8, 4)).astype(np.float32)
    y = rng.standard_normal((8, 4)).astype(np.float32)
    r = _runner(f, True)
    got = np.asarray(r(x, y))
    plan = _the_plan(r)
    fused = [s for s in plan.steps if s.kind == "fused"]
    assert len(fused) == 1 and fused[0].op == "fused-all-gather"
    assert_close(got, x[::-1] + y[::-1], "f32")


def _scan_bodies(closed):
    """All scan-body jaxprs reachable from ``closed`` (jit bodies walked)."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            sub = eqn.params.get("jaxpr") if eqn.params else None
            if sub is None:
                continue
            inner = getattr(sub, "jaxpr", sub)
            if eqn.primitive.name == "scan":
                found.append(inner)
            walk(inner)

    walk(closed.jaxpr)
    return found


def test_pjit_inline_fused_psums_bit_identical():
    """Tentpole acceptance: two jit bodies each ending in an AllReduce can
    only share a fusion bucket after inlining dissolves the call boundary —
    and the fused execution is bit-identical to the unoptimized plan."""

    def block(x, w):
        return annotate(x @ w, R)  # contracted over y -> in-body psum

    blk = jax.jit(block)

    def f(x, w1, w2):
        x = annotate(x, mesh_split(2, mesh, [-1, "y"]))
        w1 = annotate(w1, mesh_split(2, mesh, ["y", -1]))
        w2 = annotate(w2, mesh_split(2, mesh, ["y", -1]))
        return blk(x, w1), blk(x, w2)

    args = [rng.standard_normal((8, 8)).astype(np.float32) for _ in range(3)]
    r_opt = _runner(f, True)
    r_raw = _runner(f, False)
    got_opt = r_opt(*args)
    got_raw = r_raw(*args)
    plan = _the_plan(r_opt)
    raw_plan = _the_plan(r_raw)
    # raw: both psums live inside opaque jit steps — nothing to fuse
    assert sum(1 for s in raw_plan.steps if s.op == "jit") == 2
    assert [s for s in raw_plan.steps if s.kind in ("collective", "fused")] == []
    # optimized: bodies inlined, the two psums share one fused launch
    assert [s for s in plan.steps if s.op == "jit"] == []
    fused = [s for s in plan.steps if s.kind == "fused"]
    assert len(fused) == 1 and fused[0].op == "fused-all-reduce"
    assert len(fused[0].reads) == 2
    for o, u in zip(got_opt, got_raw):
        o, u = np.asarray(o), np.asarray(u)
        assert o.tobytes() == u.tobytes(), "inlined+fused psum must be bit-identical"
    x = args[0]
    for o, w in zip(got_opt, args[1:]):
        assert_close(o, x @ w, "f32_dot")


def test_scan_hoisted_gather_executes_once():
    """Satellite acceptance: the loop-invariant param gather leaves the scan
    body — the compiled program launches it once, not per iteration (checked
    on the traced jaxpr: no all_gather remains inside the scan body), and the
    result is bit-identical to the unhoisted plan."""
    from jax import lax as jlax

    Wsh = mesh_split(2, mesh, ["y", -1])

    def f(xs, w, c0):
        w = annotate(w, Wsh)

        def body(c, x):
            wg = annotate(annotate(w, Wsh), R)  # per-iteration gather
            return jnp.tanh(c + x @ wg), ()

        c, _ = jlax.scan(body, c0, xs)
        return c

    xs = rng.standard_normal((4, 8, 8)).astype(np.float32)
    w = rng.standard_normal((8, 8)).astype(np.float32)
    c0 = rng.standard_normal((8, 8)).astype(np.float32)
    r_opt = _runner(f, True)
    r_raw = _runner(f, False)
    got_opt = np.asarray(r_opt(xs, w, c0))
    got_raw = np.asarray(r_raw(xs, w, c0))
    assert got_opt.tobytes() == got_raw.tobytes()
    c = c0
    for i in range(4):
        c = np.tanh(c + xs[i] @ w)
    assert_close(got_opt, c, "f32_dot")
    # plan structure: the gather moved out of the body
    plan = _the_plan(r_opt)
    (scan_step,) = [s for s in plan.steps if s.op == "scan"]
    assert [s for s in scan_step.inner.steps if s.kind == "reshard"] == []
    hoisted = [s for s in plan.steps if s.kind == "reshard"
               and any(ps.op == "all_gather" for ps in s.program.steps)]
    assert len(hoisted) == 1
    # launch counter on the traced program: the optimized scan body issues
    # zero gathers (1x outside), the raw body one per iteration
    (entry_opt,) = r_opt.plans.values()
    (entry_raw,) = r_raw.plans.values()
    opt_bodies = _scan_bodies(jax.make_jaxpr(entry_opt.call)(xs, w, c0))
    raw_bodies = _scan_bodies(jax.make_jaxpr(entry_raw.call)(xs, w, c0))
    assert sum(str(b).count("all_gather") for b in opt_bodies) == 0
    assert sum(str(b).count("all_gather") for b in raw_bodies) >= 1


def test_lattice_planned_program_executes_correctly():
    """A reshard the lattice search rewrites (AllToAll detour instead of
    AllGather) must still produce the right data movement end to end."""
    from repro.core.collective_planner import execute_program, plan_reshard
    from repro.core.compat import shard_map
    from jax.sharding import PartitionSpec as P

    src = mesh_split(2, mesh, [-1, "x"])
    dst = mesh_split(2, mesh, [-1, ("y", "x")])
    xg = rng.standard_normal((4, 8)).astype(np.float32)
    prog = plan_reshard(src, dst, (4, 4), dtype_bytes=4)

    def local(x):
        return execute_program(x, prog)

    got = shard_map(
        local, mesh=jmesh, in_specs=P(None, "x"), out_specs=P(None, ("y", "x")),
    )(xg)
    np.testing.assert_array_equal(np.asarray(got), xg)
