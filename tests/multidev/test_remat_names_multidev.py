"""Remat tags and Pallas kernels in partitioned programs on a 2x2 mesh of
virtual devices: ``checkpoint_name`` lowers per shard as an identity, and
``PlanStats.kernel_calls`` counts each ``pallas_call`` at the trip count of
the scans around it.  Run via test_multidev_launcher.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from repro.core import Mesh, annotate, mesh_split
from repro.core.compat import assert_close, make_jax_mesh
from repro.core.partitioner import spmd_partition

AXES = ("data", "model")
rng = np.random.default_rng(16)


def _run(f, *args):
    """``f(mesh, *args)`` partitioned over 2x2, and its plan's stats."""
    jm, mesh = make_jax_mesh((2, 2), AXES), Mesh.create((2, 2), AXES)
    runner = spmd_partition(lambda *a: f(mesh, *a), jm, mesh)
    out = jax.tree_util.tree_map(np.asarray, runner(*args))
    (entry,) = runner.plans.values()
    return out, entry.plan.stats


def test_checkpoint_name_lowers_per_shard():
    """The gradient of a layer scan under a policy that saves a tagged
    activation: the tag lowers per shard, not through the fallback."""
    x = rng.standard_normal((8, 16)).astype(np.float32)
    w = rng.standard_normal((3, 16, 16)).astype(np.float32) * 0.3

    def loss(mesh, w, x):
        if mesh is not None:
            x = annotate(x, mesh_split(2, mesh, ["data", "model"]))

        def body(c, wi):
            h = checkpoint_name(jnp.tanh(c @ wi), "h")
            return jnp.sin(h) * c, None

        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.save_only_these_names("h"))
        return jnp.sum(jax.lax.scan(body, x, w)[0] ** 2)

    grad = jax.grad(loss, argnums=(1, 2))
    got, stats = _run(grad, w, x)
    assert "name" not in stats.fallbacks, stats.fallbacks
    for g, r in zip(got, grad(None, w, x), strict=True):
        assert_close(g, r, "f32_dot")


def _double(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0


def test_kernel_calls_count_scan_bodies_at_trip_count():
    """One ``pallas_call`` in a scan of 3 inside a scan of 2, and one
    outside: seven calls per execution."""
    x = rng.standard_normal((8, 128)).astype(np.float32)

    def double(x):
        return pl.pallas_call(
            _double, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=True)(x)

    def f(mesh, x):
        x = annotate(x, mesh_split(2, mesh, ["data", "model"]))

        def inner(c, _):
            return double(c) * 0.5 + 1.0, None

        def outer(c, _):
            return jax.lax.scan(inner, c, None, length=3)[0], None

        return double(jax.lax.scan(outer, x, None, length=2)[0])

    got, stats = _run(f, x)
    assert stats.kernel_calls == {"_double": 7}
    assert stats.as_dict()["kernel_calls"] == {"_double": 7}
    assert_close(got, 2.0 * (x + 6.0))
