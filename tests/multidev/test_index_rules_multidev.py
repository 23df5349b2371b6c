"""Gather and scatter-add per shard (``core/plan.py`` ``_index_op``) against
the unpartitioned program on 2x2, 1x4 and 4x1 meshes of virtual devices.

The lookups are the model's: ``jnp.take(table, tokens, axis=0)`` (the
embedding) and ``jnp.take_along_axis`` (the loss's label logit), with the
table split on the indexed dim (vocabulary), on the dims that pass through
and on batch dims, indices at the shard edges, and their gradients (the
scatter-adds).  Run via test_multidev_launcher.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Mesh, annotate, mesh_split
from repro.core.compat import assert_close, make_jax_mesh
from repro.core.partitioner import spmd_partition

AXES = ("data", "model")
MESHES = [(2, 2), (1, 4), (4, 1)]
V, M, B, S = 16, 8, 4, 6
rng = np.random.default_rng(15)
# every shard edge of 16 rows over 2 or 4 shards, and the ends
EDGES = np.array([0, 3, 4, 7, 8, 11, 12, 15])


def _tokens():
    t = rng.integers(0, V, (B, S)).astype(np.int32)
    t.flat[:len(EDGES)] = EDGES
    return t


def _run(shape, f, *args):
    """``f(mesh, *args)`` partitioned over ``shape``: (outputs, plan stats)."""
    jm, mesh = make_jax_mesh(shape, AXES), Mesh.create(shape, AXES)
    runner = spmd_partition(lambda *a: f(mesh, *a), jm, mesh)
    out = jax.tree_util.tree_map(np.asarray, runner(*args))
    (entry,) = runner.plans.values()
    return out, entry.plan.stats


def _no_index_fallback(stats):
    assert "gather" not in stats.fallbacks, stats.fallbacks
    assert "scatter-add" not in stats.fallbacks, stats.fallbacks
    assert stats.sharded_gathers >= 1


@pytest.mark.parametrize("shape", MESHES, ids=["2x2", "1x4", "4x1"])
@pytest.mark.parametrize("layout", [
    (-1, -1, "model"),         # vocabulary split: masked gather + psum
    ("data", -1, -1),          # batch split: operand batching dims
    ("data", -1, "model"),     # both
])
def test_take_along_axis_and_its_gradient(shape, layout):
    logits = rng.standard_normal((B, S, V)).astype(np.float32)
    labels = _tokens()
    w = rng.standard_normal((B, S)).astype(np.float32)

    def pick(mesh, logits, labels):
        logits = annotate(logits, mesh_split(3, mesh, list(layout)))
        labels = annotate(labels, mesh_split(2, mesh, [layout[0], -1]))
        return jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]

    def f(mesh, logits, labels, w):
        val, grad = jax.value_and_grad(
            lambda x: jnp.sum(w * pick(mesh, x, labels)))(logits)
        return pick(mesh, logits, labels), val, grad

    (got, val, grad), stats = _run(shape, f, logits, labels, w)
    want = np.take_along_axis(logits, labels[..., None], -1)[..., 0]
    assert_close(got, want, "exact")
    assert_close(val, np.sum(w * want), "f32_dot")  # the sum is split
    want_grad = np.zeros_like(logits)
    np.put_along_axis(want_grad, labels[..., None], w[..., None], -1)
    assert_close(grad, want_grad, "exact")
    _no_index_fallback(stats)


@pytest.mark.parametrize("shape", MESHES, ids=["2x2", "1x4", "4x1"])
@pytest.mark.parametrize("table_layout,token_layout", [
    (("model", "data"), ("data", -1)),   # 2d_finalized: vocab and embed split
    (("model", -1), ("data", -1)),       # vocab split, tokens on batch
    ((-1, "model"), ("data", -1)),       # embed split only: no mask
    (("data", "model"), (-1, -1)),       # vocab over the other axis
])
def test_embedding_take_and_its_gradient(shape, table_layout, token_layout):
    table = rng.standard_normal((V, M)).astype(np.float32)
    tokens = _tokens()
    ct = rng.standard_normal((B, S, M)).astype(np.float32)

    def look(mesh, table, tokens):
        table = annotate(table, mesh_split(2, mesh, list(table_layout)))
        tokens = annotate(tokens, mesh_split(2, mesh, list(token_layout)))
        return jnp.take(table, tokens, axis=0)

    def f(mesh, table, tokens, ct):
        out, vjp = jax.vjp(lambda t: look(mesh, t, tokens), table)
        return out, vjp(ct)[0]

    (got, grad), stats = _run(shape, f, table, tokens, ct)
    assert_close(got, table[tokens], "exact")
    want_grad = np.zeros_like(table)
    np.add.at(want_grad, tokens, ct)
    assert_close(grad, want_grad, "f32")  # partial sums added by a psum
    _no_index_fallback(stats)


@pytest.mark.parametrize("mode", ["fill", "clip"])
def test_out_of_range_rows_follow_the_mode(mode):
    """``fill``: a row outside the table reads NaN and its update is
    dropped; ``clip``: it reads (and updates) the nearest row."""
    table = rng.standard_normal((V, M)).astype(np.float32)
    tokens = _tokens()
    tokens[0, :3] = [V, -V - 1, V + 5]
    ct = rng.standard_normal((B, S, M)).astype(np.float32)

    def f(mesh, table, tokens, ct):
        def look(t):
            t = annotate(t, mesh_split(2, mesh, ["model", "data"]))
            return jnp.take(t, tokens, axis=0, mode=mode)

        out, vjp = jax.vjp(look, table)
        return out, vjp(ct)[0]

    (got, grad), stats = _run((2, 2), f, table, tokens, ct)
    want, want_grad = _unpartitioned(mode, table, tokens, ct)
    assert_close(got, want, "exact", equal_nan=True)
    assert_close(grad, want_grad, "f32")
    _no_index_fallback(stats)


def _unpartitioned(mode, table, tokens, ct):
    out, vjp = jax.vjp(lambda t: jnp.take(t, tokens, axis=0, mode=mode),
                       table)
    return np.asarray(out), np.asarray(vjp(ct)[0])
