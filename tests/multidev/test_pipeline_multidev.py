"""Stage-stacked pipeline execution parity on fake devices (§3.3).

The ISSUE-5 acceptance contract: the pipelined plan runs on a real
stage-sharded mesh (boundary-row ppermute + collection psum inside the tick
scan) and its forward loss is **bit-identical** to the unpipelined
single-plan reference.  Grads flow through the transposed pipeline (the
opposite-direction ppermute in a reverse scan); their *math* is bit-identical
— verified against the unpartitioned oracle of the same pipelined program —
while the partitioned values sit within float32 ULPs of the reference (XLA
executes the stage-local batch-1 einsums of the backward with a different
accumulation order than the full-batch reference dots; the same effect exists
for any batch-sharded einsum in this suite, pipeline or not).
Run via test_multidev_launcher.py (REPRO_MULTIDEV=1, 8 fake CPU devices).
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Mesh, annotate, mesh_split
from repro.core.compat import assert_close, make_jax_mesh
from repro.core.partitioner import spmd_partition
from repro.pipeline import pipelined_apply, pipeline_ticks, stage_stack_params

jmesh = make_jax_mesh((4, 2), ("stage", "model"))
mesh = Mesh.create((4, 2), ("stage", "model"))
rng = np.random.default_rng(3)

L, D, M, MB = 4, 8, 4, 2
WS = rng.standard_normal((L, D, D)).astype(np.float32) * 0.3
XS = rng.standard_normal((M, MB, D)).astype(np.float32)


def layer(lp, x, _):
    return jnp.tanh(x @ lp)


def pipelined_loss(wstk, xs):
    wstk = annotate(wstk, mesh_split(4, mesh, ["stage", -1, -1, -1]))
    ys = pipelined_apply(layer, wstk, xs, num_stages=4,
                         mesh=mesh, stage_axis="stage")
    return jnp.mean(ys ** 2)


def ref_loss(ws, xs):
    def f(h):
        for i in range(ws.shape[0]):
            h = jnp.tanh(h @ ws[i])
        return h

    ys = jnp.stack([f(xs[m]) for m in range(xs.shape[0])])
    return jnp.mean(ys ** 2)


def test_pipelined_loss_and_grads_match_unpipelined_reference():
    wstk = np.asarray(stage_stack_params(jnp.asarray(WS), 4))
    vp, gp = spmd_partition(
        jax.value_and_grad(pipelined_loss), jmesh, mesh)(wstk, XS)
    vr, gr = spmd_partition(
        jax.value_and_grad(ref_loss), jmesh, mesh)(WS, XS)
    # forward loss: bit-identical across 4-way pipelining
    np.testing.assert_array_equal(np.asarray(vp), np.asarray(vr))
    gp = np.asarray(gp).reshape(L, D, D)
    gr = np.asarray(gr)
    # pipeline math is exact: the unpartitioned oracle of the SAME pipelined
    # program is bit-identical to the reference grads...
    go = np.asarray(jax.grad(pipelined_loss)(
        jnp.asarray(stage_stack_params(jnp.asarray(WS), 4)),
        jnp.asarray(XS))).reshape(L, D, D)
    np.testing.assert_array_equal(go, gr)
    # ...and the partitioned backward agrees to float32 ULPs (batch-1 local
    # einsum accumulation order; see module docstring)
    assert_close(gp, gr, "ulp")


def test_pipelined_plan_issues_one_ppermute_per_tick():
    wstk = np.asarray(stage_stack_params(jnp.asarray(WS), 4))
    r = spmd_partition(pipelined_loss, jmesh, mesh)
    loss = r(wstk, XS)
    assert np.isfinite(np.asarray(loss))
    (entry,) = r.plans.values()
    scans = [s for s in entry.plan.steps
             if s.op == "scan" and s.inner is not None]
    assert len(scans) == 1
    (scan,) = scans
    assert scan.call["trips"] == pipeline_ticks(4, M)
    pperms = [s for s in scan.inner.steps
              if s.kind == "collective" and s.op == "ppermute"]
    assert len(pperms) == 1
    assert pperms[0].axes == ("stage",)


def test_mixed_pipeline_plus_tensor_parallelism_matches():
    """The headline §3.3 generality claim on one mesh: stage dim pipelined
    over `stage`, the layer's feature dim Megatron-split over `model` — one
    partition plan, both parallelism kinds."""
    def mixed_loss(wstk, xs):
        wstk = annotate(wstk, mesh_split(4, mesh, ["stage", -1, -1, "model"]))
        ys = pipelined_apply(layer, wstk, xs, num_stages=4,
                             mesh=mesh, stage_axis="stage")
        return jnp.mean(ys ** 2)

    wstk = np.asarray(stage_stack_params(jnp.asarray(WS), 4))
    got = spmd_partition(mixed_loss, jmesh, mesh)(wstk, XS)
    want = ref_loss(jnp.asarray(WS), jnp.asarray(XS))
    assert_close(got, want, "f32_dot")
