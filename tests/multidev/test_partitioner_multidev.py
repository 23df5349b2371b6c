"""Reference SPMD partitioner vs unpartitioned oracle on 8 fake devices.

The GSPMD core guarantee (§4): the partitioned program is mathematically
equivalent to the original.  Run via test_multidev_launcher.py.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
try:
    from hypothesis import given, settings, strategies as hs
except ImportError:  # container lacks hypothesis; deterministic fallback
    from _hypo_stub import given, settings, strategies as hs
from jax.sharding import PartitionSpec as P

from repro.core import Mesh, annotate, mesh_split
from repro.core.compat import assert_close, make_jax_mesh, shard_map
from repro.core.halo import sharded_conv_nd
from repro.core.partitioner import spmd_partition
from repro.core.einsum_rules import plan_einsum

jmesh = make_jax_mesh((2, 4), ("x", "y"))
mesh = Mesh.create((2, 4), ("x", "y"))
rng = np.random.default_rng(0)


def run(f, *args):
    return np.asarray(spmd_partition(f, jmesh, mesh)(*args))


def test_dp_mp_matmul():
    def f(bd, df):
        bd = annotate(bd, mesh_split(2, mesh, ["x", -1]))
        df = annotate(df, mesh_split(2, mesh, [-1, "y"]))
        return jax.nn.relu(jnp.einsum("bd,df->bf", bd, df))

    a = rng.standard_normal((8, 16)).astype(np.float32)
    b = rng.standard_normal((16, 32)).astype(np.float32)
    assert_close(run(f, a, b), np.maximum(a @ b, 0), "f32_dot")


def test_contracting_allreduce():
    def f(x, w):
        x = annotate(x, mesh_split(2, mesh, ["x", "y"]))
        w = annotate(w, mesh_split(2, mesh, ["y", -1]))
        return jnp.einsum("bd,df->bf", x, w)

    x = rng.standard_normal((4, 8)).astype(np.float32)
    w = rng.standard_normal((8, 6)).astype(np.float32)
    assert_close(run(f, x, w), x @ w, "f32_chain")


def test_recursive_grouping_expert_dim():
    """§4.4 Figure 6: batch-dim grouping + inner partitioning."""

    def f(e1, e2):
        e1 = annotate(e1, mesh_split(3, mesh, ["x", -1, "y"]))
        e2 = annotate(e2, mesh_split(3, mesh, ["x", "y", -1]))
        return jnp.einsum("ebm,emh->ebh", e1, e2)

    e1 = rng.standard_normal((2, 4, 8)).astype(np.float32)
    e2 = rng.standard_normal((2, 8, 16)).astype(np.float32)
    assert_close(run(f, e1, e2), np.einsum("ebm,emh->ebh", e1, e2), "f32_chain")


def test_mlp_forward_and_reduction():
    def f(x, w1, w2):
        x = annotate(x, mesh_split(2, mesh, ["x", -1]))
        w1 = annotate(w1, mesh_split(2, mesh, [-1, "y"]))
        w2 = annotate(w2, mesh_split(2, mesh, ["y", -1]))
        h = jnp.tanh(x @ w1)
        return jnp.sum((h @ w2) ** 2)

    x = rng.standard_normal((4, 8)).astype(np.float32)
    w1 = rng.standard_normal((8, 16)).astype(np.float32)
    w2 = rng.standard_normal((16, 8)).astype(np.float32)
    ref = np.sum((np.tanh(x @ w1) @ w2) ** 2)
    assert_close(run(f, x, w1, w2), ref, "f32_chain")


@pytest.mark.parametrize("stride,pads", [(1, (2, 2)), (2, (1, 2)), (3, (0, 2))])
def test_halo_conv(stride, pads):
    xg = rng.standard_normal((2, 3, 48)).astype(np.float32)
    wk = rng.standard_normal((4, 3, 5)).astype(np.float32)
    out_len = (48 + sum(pads) - 5) // stride + 1
    if out_len % 4:
        pytest.skip("output not divisible by axis")
    ref = jax.lax.conv_general_dilated(xg, wk, (stride,), [pads])

    def conv_local(xl, wl):
        return sharded_conv_nd(xl, wl, sharded=[(2, "y")],
                               window_strides=(stride,), padding=[pads])

    got = shard_map(
        conv_local, mesh=jmesh,
        in_specs=(P(None, None, "y"), P(None, None, None)),
        out_specs=P(None, None, "y"),
    )(xg, wk)
    assert_close(got, ref, "f32_chain")


def test_halo_conv_2d_spatial():
    """Two spatial dims sharded on different axes (§4.4 recursion)."""
    xg = rng.standard_normal((1, 2, 16, 16)).astype(np.float32)
    wk = rng.standard_normal((4, 2, 3, 3)).astype(np.float32)
    ref = jax.lax.conv_general_dilated(xg, wk, (1, 1), [(1, 1), (1, 1)])

    def conv_local(xl, wl):
        return sharded_conv_nd(
            xl, wl, sharded=[(2, "x"), (3, "y")],
            window_strides=(1, 1), padding=[(1, 1), (1, 1)],
        )

    got = shard_map(
        conv_local, mesh=jmesh,
        in_specs=(P(None, None, "x", "y"), P(None, None, None, None)),
        out_specs=P(None, None, "x", "y"),
    )(xg, wk)
    assert_close(got, ref, "f32_chain")


# property: partitioned einsum == oracle over random shardings
DIMS = {"b": 8, "d": 8, "f": 8, "e": 2}
AXES = [None, "x", "y"]


@given(
    hs.sampled_from(["bd,df->bf", "ebd,edf->ebf", "bd,bd->b", "bde,dfe->bfe"]),
    hs.lists(hs.sampled_from(AXES), min_size=6, max_size=6),
)
@settings(max_examples=25, deadline=None)
def test_einsum_partition_property(spec, axes):
    lhs, rhs = spec.split("->")[0].split(",")
    la, ra = axes[: len(lhs)], axes[3 : 3 + len(rhs)]
    axis_size = {"x": 2, "y": 4}

    def uniq(ax, labels):
        seen = set()
        out = []
        for a, c in zip(ax, labels):
            # reference partitioner requires evenly-divisible shardings (§4.1
            # padding is handled at the model layer, not in the reference)
            if a is None or a in seen or DIMS[c] % axis_size[a]:
                out.append(-1)
            else:
                seen.add(a)
                out.append(a)
        return out

    la, ra = uniq(la, lhs), uniq(ra, rhs)

    def f(x, y):
        x = annotate(x, mesh_split(len(lhs), mesh, la))
        y = annotate(y, mesh_split(len(rhs), mesh, ra))
        return jnp.einsum(spec, x, y)

    x = rng.standard_normal([DIMS[c] for c in lhs]).astype(np.float32)
    y = rng.standard_normal([DIMS[c] for c in rhs]).astype(np.float32)
    assert_close(run(f, x, y), jnp.einsum(spec, x, y), "coarse")


def test_self_attention_on_a_2x2_mesh_keeps_the_chunked_loop(monkeypatch):
    """With the backend check patched to a TPU, a reduced-Qwen loss
    partitioned for a 2x2 mesh holds no ``pallas_call`` (the partitioner
    would gather the kernel's operands), counts the chunked path, and
    matches the unpartitioned loss."""
    from repro.configs.base import get_strategy
    from repro.configs.registry import get_config
    from repro.kernels import ops
    from repro.launch.train import reduced_config
    from repro.models import transformer
    from repro.models.layers import tree_init
    from repro.obs import metrics

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    cfg = reduced_config(get_config("qwen1.5-0.5b"), 16).with_(
        d_model=128, xent_chunk=0, attn_chunk=128)  # 2 heads of 64
    st = get_strategy("2d_finalized")
    params = tree_init(transformer.param_tree(cfg, st), jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 128), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}

    def loss(p, b):
        return transformer.loss_fn(cfg, st, p, b)

    jm, m = make_jax_mesh((2, 2), ("data", "model")), Mesh.create(
        (2, 2), ("data", "model"))
    before = metrics.snapshot(include_sources=False)["counters"]
    leaves, tdef = jax.tree_util.tree_flatten((params, batch))
    runner = spmd_partition(
        lambda *xs: loss(*jax.tree_util.tree_unflatten(tdef, xs)), jm, m)
    got = float(runner(*leaves))
    after = metrics.snapshot(include_sources=False)["counters"]
    (entry,) = runner.plans.values()

    def prims(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn.primitive.name
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from prims(sub)

    assert "pallas_call" not in set(prims(entry.plan.jaxpr))
    took = {k: after.get(k, 0) - before.get(k, 0) for k in
            ("attention.flash_kernel", "attention.xla_chunked")}
    assert took == {"attention.flash_kernel": 0, "attention.xla_chunked": 1}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "on_tpu", lambda: False)
        want = float(jax.jit(loss)(params, batch))
    assert abs(got - want) <= 1e-3 * abs(want)
