"""Partition-plan compilation + reshard planner unit tests (single device).

Pure-decision tests: the planner and the plan cache are exercised without any
collective execution (that lives in tests/multidev/test_reshard.py), so these
run in the default 1-device session.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.roofline import collective_wire_bytes
from repro.core import Mesh, annotate, mesh_split
from repro.core.collective_planner import (
    plan_reshard, simulate, _candidate_gather_all, _candidate_legacy,
)
from repro.core.compat import make_jax_mesh
from repro.core.einsum_rules import compile_einsum, plan_einsum

mesh = Mesh.create((2, 4), ("x", "y"))


# ---------------------------------------------------------------------------------
# reshard planner decisions
# ---------------------------------------------------------------------------------


def test_dim_move_uses_alltoall_at_fraction_of_allgather():
    """A mesh axis moving between dims must lower to AllToAll: (n-1)/n·B wire
    bytes instead of the greedy AllGather's (n-1)·B."""
    src = mesh_split(2, mesh, ["y", -1])
    dst = mesh_split(2, mesh, [-1, "y"])
    local = (2, 16)
    prog = plan_reshard(src, dst, local, dtype_bytes=4)
    assert [s.op for s in prog.steps] == ["all_to_all"]
    n = mesh.axis_size("y")
    bytes_local = 2 * 16 * 4
    assert prog.cost_bytes == collective_wire_bytes("all-to-all", n, bytes_local)
    # AllGather + DynamicSlice expression of the same move costs n× more
    gather = _candidate_gather_all(src, dst, local)
    gather_cost = simulate(src, dst, gather, local, 4)
    assert gather_cost == collective_wire_bytes("all-gather", n, bytes_local)
    assert prog.cost_bytes < gather_cost
    assert prog.cost_bytes == pytest.approx(gather_cost / n)


def test_slice_before_gather_ordering():
    """Slicing the target's new axis first shrinks every later gather."""
    src = mesh_split(2, mesh, ["x", -1])
    dst = mesh_split(2, mesh, [-1, "y"])
    local = (4, 16)
    prog = plan_reshard(src, dst, local, dtype_bytes=4)
    ops = [s.op for s in prog.steps]
    assert ops == ["dynamic_slice", "all_gather"], ops
    # legacy gathers first (256B on the wire), planner slices first (64B)
    legacy_cost = simulate(src, dst, _candidate_legacy(src, dst, local), local, 4)
    assert prog.cost_bytes < legacy_cost
    assert prog.cost_bytes == pytest.approx(legacy_cost / mesh.axis_size("y"))


def test_stacked_axes_gather_innermost_first():
    """Dropping the outer axis of a stacked dim must gather the inner one
    first (tiled collectives only operate on the innermost position)."""
    src = mesh_split(2, mesh, [("x", "y"), -1])
    dst = mesh_split(2, mesh, [-1, -1])
    prog = plan_reshard(src, dst, (1, 8), dtype_bytes=4)
    assert [(s.op, s.axis) for s in prog.steps] == [
        ("all_gather", "y"), ("all_gather", "x"),
    ]


def test_stacked_inner_axis_moves_via_alltoall():
    """d0=(x,y) -> d0=(x,), d1=(y,): the inner axis moves directly."""
    src = mesh_split(2, mesh, [("x", "y"), -1])
    dst = mesh_split(2, mesh, ["x", "y"])
    prog = plan_reshard(src, dst, (1, 8), dtype_bytes=4)
    assert [s.op for s in prog.steps] == ["all_to_all"]


def test_identity_reshard_is_free():
    s = mesh_split(2, mesh, ["x", "y"])
    prog = plan_reshard(s, s, (4, 2), dtype_bytes=4)
    assert prog.is_identity and prog.cost_bytes == 0.0


def test_planner_never_worse_than_legacy():
    """Over an exhaustive grid of (src, dst) sharding pairs the chosen program
    validates and never costs more than the greedy baseline."""
    opts = [(), ("x",), ("y",), ("x", "y")]
    shardings = []
    for d0 in opts:
        for d1 in opts:
            if set(d0) & set(d1):
                continue
            shardings.append(mesh_split(2, mesh, [d0 or -1, d1 or -1]))
    local_global = (8, 16)
    for src in shardings:
        for dst in shardings:
            local = tuple(
                g // src.num_shards(i) for i, g in enumerate(local_global)
            )
            prog = plan_reshard(src, dst, local, dtype_bytes=4)
            # simulate() revalidates and reprices the chosen steps
            assert simulate(src, dst, list(prog.steps), local, 4) == prog.cost_bytes
            legacy = _candidate_legacy(src, dst, local)
            if legacy is not None:
                assert prog.cost_bytes <= simulate(src, dst, legacy, local, 4) + 1e-9


# ---------------------------------------------------------------------------------
# einsum compilation
# ---------------------------------------------------------------------------------


def test_compile_einsum_reports_reduce_scatter():
    """Contracting-matched einsum whose requested output shards the psum axis
    must choose ReduceScatter and report it."""
    lhs = mesh_split(2, mesh, [-1, "y"])
    rhs = mesh_split(2, mesh, ["y", -1])
    out = mesh_split(2, mesh, ["y", -1])
    plan = compile_einsum("bd,df->bf", lhs, rhs, out, (8, 2), (2, 8))
    assert plan.compiled
    assert plan.scatter == (("y", 0),)
    assert plan.reduce_axes == ()
    assert any(c.startswith("reduce-scatter") for c in plan.collectives())
    # without a requested output it stays an AllReduce
    plan_ar = compile_einsum("bd,df->bf", lhs, rhs, None, (8, 2), (2, 8))
    assert plan_ar.reduce_axes == ("y",)
    assert any(c.startswith("all-reduce") for c in plan_ar.collectives())


def test_plan_einsum_one_sided_batch_dim_no_gather():
    """Satellite fix: an lhs-only batch sharding must not flag a rhs gather —
    the unsharded rhs is sliced (zero wire bytes), not gathered."""
    lhs = mesh_split(3, mesh, ["x", -1, -1])
    rhs = mesh_split(3, mesh, [-1, -1, -1])
    plan = plan_einsum("ebm,emh->ebh", lhs, rhs)
    assert plan.lhs_local.dims_mapping[0] == ("x",)
    assert plan.rhs_local.dims_mapping[0] == ("x",)
    compiled = compile_einsum("ebm,emh->ebh", lhs, rhs, None, (1, 4, 8), (2, 4, 8))
    assert compiled.rhs_program is not None
    assert [s.op for s in compiled.rhs_program.steps] == ["dynamic_slice"]
    assert compiled.rhs_program.cost_bytes == 0.0


# ---------------------------------------------------------------------------------
# plan cache: steady-state calls skip tracing + propagation entirely
# ---------------------------------------------------------------------------------


def test_plan_cache_zero_repropagation(monkeypatch):
    from repro.core import partitioner as pt

    jmesh = make_jax_mesh((1, 1), ("x", "y"))
    m = Mesh.create((1, 1), ("x", "y"))
    calls = {"propagate": 0, "trace": 0}
    real_propagate = pt.propagate
    real_make_jaxpr = jax.make_jaxpr

    def counting_propagate(*a, **kw):
        calls["propagate"] += 1
        return real_propagate(*a, **kw)

    def counting_make_jaxpr(*a, **kw):
        calls["trace"] += 1
        return real_make_jaxpr(*a, **kw)

    monkeypatch.setattr(pt, "propagate", counting_propagate)
    monkeypatch.setattr(pt.jax, "make_jaxpr", counting_make_jaxpr)

    def f(a, b):
        a = annotate(a, mesh_split(2, m, ["x", -1]))
        return jnp.tanh(a @ b)

    runner = pt.spmd_partition(f, jmesh, m)
    x = np.ones((4, 4), np.float32)
    y = np.ones((4, 4), np.float32)
    r1 = runner(x, y)
    assert calls == {"propagate": 1, "trace": 1}
    r2 = runner(x + 1, y)  # same avals -> cache hit, no re-trace/re-propagation
    assert calls == {"propagate": 1, "trace": 1}
    assert runner.cache_stats.hits == 1 and runner.cache_stats.misses == 1
    np.testing.assert_allclose(
        np.asarray(r2), np.tanh((x + 1) @ y), rtol=1e-6
    )
    runner(np.ones((8, 4), np.float32), y)  # new avals -> one more compile
    assert calls == {"propagate": 2, "trace": 2}
    assert runner.cache_stats.misses == 2


def test_plan_records_collective_stats():
    jmesh = make_jax_mesh((1, 1), ("x", "y"))
    m = Mesh.create((1, 1), ("x", "y"))

    def f(a, b):
        a = annotate(a, mesh_split(2, m, ["x", -1]))
        b = annotate(b, mesh_split(2, m, [-1, "y"]))
        return a @ b

    runner = __import__("repro.core.partitioner", fromlist=["spmd_partition"]).spmd_partition(
        f, jmesh, m
    )
    runner(np.ones((2, 2), np.float32), np.ones((2, 2), np.float32))
    (entry,) = runner.plans.values()
    stats = entry.plan.stats.as_dict()
    assert stats["eqns"] >= 3 and stats["steps"] >= 3
    assert isinstance(stats["collectives"], dict)


def test_plan_counts_fallback_equations():
    """A nested ``jax.jit`` lowers through its own call step (the installed
    jax names the primitive ``jit``); only the op with no rule — concatenate
    — is counted as gathered by the fallback."""
    from repro.core.partitioner import spmd_partition

    jmesh = make_jax_mesh((1, 1), ("x", "y"))
    m = Mesh.create((1, 1), ("x", "y"))
    inner = jax.jit(lambda a: a * 2.0)

    def f(a, b):
        return jnp.concatenate([inner(a), b], axis=1)

    runner = spmd_partition(f, jmesh, m, optimize=False)
    out = runner(np.ones((2, 2), np.float32), np.ones((2, 2), np.float32))
    np.testing.assert_array_equal(np.asarray(out)[:, :2], 2.0)
    (entry,) = runner.plans.values()
    assert [s.op for s in entry.plan.steps if s.inner is not None] == ["jit"]
    assert entry.plan.stats.as_dict()["fallbacks"] == {"concatenate": 1}


# ---------------------------------------------------------------------------------
# fallback partial gather (pure analysis)
# ---------------------------------------------------------------------------------


def test_fallback_keeps_unmodified_dims():
    from repro.core.plan import fallback_keep_sharding

    def f(a, b):
        return jax.lax.concatenate([a, b], 1)

    closed = jax.make_jaxpr(f)(
        jnp.ones((8, 4), jnp.float32), jnp.ones((8, 6), jnp.float32)
    )
    (eqn,) = [e for e in closed.jaxpr.eqns if e.primitive.name == "concatenate"]
    sh = mesh_split(2, mesh, ["y", "x"])
    keep = fallback_keep_sharding(eqn, [sh, sh], mesh)
    assert keep is not None
    kept, _ = keep
    # dim 0 sharding survives; the concat dim is gathered
    assert kept.dims_mapping == (("y",), ())
