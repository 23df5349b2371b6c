"""Gather and scatter-add in the partitioner, at mesh sizes this process
does not have: the propagation rules, the per-shard steps of the cost-only
plan (``lower_plan``: the lowering a runner makes, with stub runners), the
plan's counters and the verifier's re-simulation of the steps.  The numbers
the steps compute are checked on virtual devices in
``tests/multidev/test_index_rules_multidev.py``."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.core import annotate, mesh_split
from repro.core.compat import trace_for
from repro.core.plan import lower_plan
from repro.core.plan_verify import verify_plan
from repro.core.propagation import propagate
from repro.core.sharding import Mesh

MESH = Mesh.create((2, 2), ("data", "model"))
V, M, B, S = 64, 16, 4, 8


def _lookup_loss(table, tokens, labels):
    """The model's two lookups: the embedding and the loss's label logit."""
    table = annotate(table, mesh_split(2, MESH, ["model", "data"]))
    tokens = annotate(tokens, mesh_split(2, MESH, ["data", -1]))
    x = jnp.take(table, tokens, axis=0)
    logits = annotate(jnp.einsum("bsm,vm->bsv", x, table),
                      mesh_split(3, MESH, ["data", -1, "model"]))
    return -jnp.mean(jnp.take_along_axis(logits, labels[..., None], -1))


def _avals():
    return (jax.ShapeDtypeStruct((V, M), jnp.float32),
            jax.ShapeDtypeStruct((B, S), jnp.int32),
            jax.ShapeDtypeStruct((B, S), jnp.int32))


def _eqns(jaxpr, name):
    for e in jaxpr.eqns:
        if e.primitive.name == name:
            yield e
        for k in ("jaxpr", "call_jaxpr"):
            if k in e.params:
                sub = e.params[k]
                yield from _eqns(getattr(sub, "jaxpr", sub), name)


def _walk(plan):
    for s in plan.steps:
        yield s
        if s.inner is not None:
            yield from _walk(s.inner)


def _grad_plan():
    closed = trace_for(MESH, jax.grad(_lookup_loss), *_avals())
    return lower_plan(closed, None, MESH)


def _gather_outputs(prop, jaxpr):
    """Propagated sharding of each gather's output, by its shape."""
    out = {}
    for e in jaxpr.eqns:
        if e.primitive.name == "gather":
            out[tuple(e.outvars[0].aval.shape)] = prop.get(e.outvars[0])
        inner = prop.sub.get(id(e))
        if inner is not None:
            out.update(_gather_outputs(inner, inner.jaxpr))
    return out


def test_propagation_keeps_window_and_batch_dims_not_the_indexed_dim():
    closed = trace_for(MESH, _lookup_loss, *_avals())
    got = _gather_outputs(propagate(closed, MESH), closed.jaxpr)
    # the embedding's rows keep the table's embed axis; the vocabulary's
    # axis ("model") stays behind, and "data" cannot also split the batch
    assert got[(B, S, M)].dims_mapping == ((), (), ("data",))
    # the label logit: the batch's axis, not the vocabulary's
    assert got[(B, S, 1)].dims_mapping == (("data",), (), ())


def test_label_logit_follows_the_batch_and_drops_the_vocabulary():
    def pick(logits, labels):
        logits = annotate(logits, mesh_split(3, MESH, ["data", -1, "model"]))
        return jnp.take_along_axis(logits, labels[..., None], -1)

    closed = trace_for(MESH, pick, jax.ShapeDtypeStruct((B, S, V),
                                                        jnp.float32),
                       jax.ShapeDtypeStruct((B, S), jnp.int32))
    prop = propagate(closed, MESH)
    (take,) = [e for e in closed.jaxpr.eqns if e.primitive.name == "jit"]
    inner = prop.sub[id(take)]
    (g,) = list(_eqns(inner.jaxpr, "gather"))
    assert inner.get(g.outvars[0]).dims_mapping == (("data",), (), ())


def test_lookups_and_their_gradients_lower_per_shard():
    plan = _grad_plan()
    assert "gather" not in plan.stats.fallbacks
    assert "scatter-add" not in plan.stats.fallbacks
    steps = [s for s in _walk(plan) if s.index is not None]
    assert sorted(s.op for s in steps) == ["gather", "gather",
                                           "scatter-add", "scatter-add"]
    assert plan.stats.sharded_gathers == 4
    # the vocabulary split over "model": the gathers leave partial sums
    # over it, which a psum adds; the scatter-adds exchange nothing
    for s in steps:
        assert s.index.partial_axes == (("model",) if s.op == "gather"
                                        else ())
    assert plan.stats.fallback_bytes == 0.0
    assert verify_plan(plan).ok


@pytest.mark.parametrize("fault", ["offset", "partial", "count", "bytes"])
def test_verifier_catches_a_planted_fault_in_a_lookup(fault):
    plan = _grad_plan()
    step = next(s for s in _walk(plan) if s.op == "gather"
                and s.index.partial_axes)
    if fault == "offset":  # the shard's rows start one row off
        step.index.strides = tuple(n + 1 for n in step.index.strides)
        want = "taken from shard"
    elif fault == "partial":
        step.index.partial_axes = ()
        want = "partial sums"
    elif fault == "count":
        plan.stats.sharded_gathers += 1
        want = "sharded_gathers"
    else:
        plan.stats.fallback_bytes += 1e6
        want = "fallback_bytes"
    report = verify_plan(plan, strict=False)
    assert not report.ok
    assert any(want in v for v in report.violations), report.violations


def test_phi4_mini_train_step_on_2x2_gathers_no_logits():
    """At Phi-4-mini's published widths (two of its layers) on a 2x2 mesh
    the train step's plan lowers the embedding, the label logit and their
    gradients per shard: the fallback receives under 1% of the f32 logits
    (8 x 1,024 x 200,064 x 4 B = 6.55 GB) per step."""
    from repro.configs.base import get_strategy
    from repro.configs.registry import get_config
    from repro.core.sharding import from_partition_spec
    from repro.train.loop import (TrainConfig, init_state, make_train_step,
                                  state_partition_specs)
    from repro.train.optimizer import get_optimizer

    cfg = get_config("phi4-mini-3.8b").with_(num_layers=2, xent_chunk=0,
                                             attn_chunk=1024)
    st, tc = get_strategy("2d_finalized"), TrainConfig()
    opt = get_optimizer("adafactor", lr=0.01)
    with jax.sharding.use_abstract_mesh(AbstractMesh((2, 2),
                                                     ("data", "model"))):
        specs = state_partition_specs(cfg, st, opt, tc)
    state = jax.eval_shape(lambda: init_state(cfg, st, opt, tc,
                                              jax.random.PRNGKey(0)))
    batch = {k: jax.ShapeDtypeStruct((8, 1024), jnp.int32)
             for k in ("tokens", "labels")}
    seeds = jax.tree_util.tree_map(
        lambda s, x: from_partition_spec(MESH, len(x.shape), s), specs,
        state, is_leaf=lambda x: isinstance(x, P))
    step = make_train_step(cfg, st, opt, tc)

    def annotated(state, batch):
        state = jax.tree_util.tree_map(annotate, state, seeds)
        batch = {k: annotate(v, mesh_split(2, MESH, ["data", -1]))
                 for k, v in batch.items()}
        return step(state, batch)

    plan = lower_plan(trace_for(MESH, annotated, state, batch), None, MESH)
    assert "gather" not in plan.stats.fallbacks
    assert "scatter-add" not in plan.stats.fallbacks
    assert plan.stats.sharded_gathers == 4
    assert plan.stats.fallback_bytes < 0.01 * 8 * 1024 * 200064 * 4
