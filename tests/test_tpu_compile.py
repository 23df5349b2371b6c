"""Compile the Pallas kernels for a described (not attached) TPU v5e chip.

Nothing runs: the TPU compiler lowers each kernel at the published widths of
the model it serves, which catches what interpret mode cannot (block shapes
off the (8, 128) tiling, VMEM overuse).  The topology is described inside a
module fixture, never at import, so every pytest worker collects the same
tests and only the worker that runs this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("S", [1024, 4096])
@pytest.mark.parametrize(
    "hq,hkv,d",
    [(16, 16, 64),    # qwen1.5-0.5b: 16 heads x 64
     (24, 8, 128)],   # phi4-mini-3.8b: GQA 24/8 heads x 128
    ids=["qwen1.5-0.5b", "phi4-mini-3.8b"],
)
def test_flash_attention_compiles_for_v5e(one_chip, hq, hkv, d, S):
    """Forward and backward, with the blocks the rule picks for the shape
    (4 x 1,024 or 1 x 4,096 tokens)."""
    B = 4096 // S
    q = _shape(one_chip, (B, hq, S, d))
    kv = _shape(one_chip, (B, hkv, S, d))

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True).astype(
            jnp.float32))

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile()
    # the forward, the dK/dV and the dQ kernels
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 3


def test_ssd_scan_compiles_for_v5e(one_chip):
    # mamba2-130m: d_inner 1536 = 24 heads x 64, d_state 128
    Bb, S, H, hd, ds = 1, 4096, 24, 64, 128
    compiled = ssd_scan.lower(
        _shape(one_chip, (Bb, S, H, hd)),
        _shape(one_chip, (Bb, S, H)),
        _shape(one_chip, (Bb, S, ds)),
        _shape(one_chip, (Bb, S, ds)),
        _shape(one_chip, (H,), jnp.float32),
        chunk=128, interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_partitioned_train_step_keeps_the_scopes_for_v5e(one_chip):
    """The reduced qwen1.5-0.5b train step through ``spmd_partition``,
    compiled for a described v5e: every matmul sits under one of the
    model's four scopes, forward and backward (``transpose(...)``)."""
    import re

    import numpy as np

    from repro.configs.base import get_strategy
    from repro.configs.registry import get_config
    from repro.core.partitioner import spmd_partition
    from repro.core.sharding import Mesh
    from repro.launch.train import reduced_config
    from repro.train.loop import TrainConfig, init_state, make_train_step
    from repro.train.optimizer import get_optimizer

    (device,) = one_chip.device_set
    jmesh = jax.sharding.Mesh(np.asarray([[device]]), ("data", "model"))
    cfg = reduced_config(get_config("qwen1.5-0.5b"), 16).with_(
        xent_chunk=0, attn_chunk=64)
    st, opt, tc = (get_strategy("2d_finalized"),
                   get_optimizer("adafactor", lr=0.01), TrainConfig())
    state = jax.eval_shape(lambda: init_state(cfg, st, opt, tc,
                                              jax.random.PRNGKey(0)))
    batch = {k: jax.ShapeDtypeStruct((2, 128), jnp.int32)
             for k in ("tokens", "labels")}
    leaves, tdef = jax.tree_util.tree_flatten((state, batch))
    step = make_train_step(cfg, st, opt, tc)
    runner = spmd_partition(
        lambda *xs: step(*jax.tree_util.tree_unflatten(tdef, xs))[0],
        jmesh, Mesh.create((1, 1), ("data", "model")))
    args = [_shape(one_chip, x.shape, x.dtype) for x in leaves]
    jax.eval_shape(runner, *args)
    (entry,) = runner.plans.values()
    hlo = entry.call.lower(*args).compile().as_text()
    names = re.findall(r'op_name="([^"]+)"', hlo)
    dots = [n for n in names if n.endswith("dot_general")]
    scoped = re.compile(r"\b(attention|mlp|head|optimizer)\b")
    assert dots and all(scoped.search(n) for n in dots)
    for scope in ("attention", "mlp", "head"):
        assert any(scope in n and "transpose(" not in n for n in dots), scope
        assert any(scope in n and "transpose(" in n for n in dots), scope
    assert any(re.search(r"\boptimizer\b", n) for n in names)


def test_partitioned_train_step_runs_the_flash_kernel_for_v5e(
        one_chip, monkeypatch):
    """The same step at 2 heads of 64, traced as on the chip (the backend
    check patched, since this process's backend is the CPU): the flash
    kernel's custom calls sit under ``attention`` in the forward pass and
    under ``transpose(...)``, and no f32 score tensor is left."""
    import re

    import numpy as np

    from repro.configs.base import get_strategy
    from repro.configs.registry import get_config
    from repro.core.partitioner import spmd_partition
    from repro.core.sharding import Mesh
    from repro.kernels import ops
    from repro.launch.train import reduced_config
    from repro.train.loop import TrainConfig, init_state, make_train_step
    from repro.train.optimizer import get_optimizer

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    (device,) = one_chip.device_set
    jmesh = jax.sharding.Mesh(np.asarray([[device]]), ("data", "model"))
    S = 256
    cfg = reduced_config(get_config("qwen1.5-0.5b"), 16).with_(
        d_model=128, xent_chunk=0, attn_chunk=S)
    st, opt, tc = (get_strategy("2d_finalized"),
                   get_optimizer("adafactor", lr=0.01), TrainConfig())
    state = jax.eval_shape(lambda: init_state(cfg, st, opt, tc,
                                              jax.random.PRNGKey(0)))
    batch = {k: jax.ShapeDtypeStruct((2, S), jnp.int32)
             for k in ("tokens", "labels")}
    leaves, tdef = jax.tree_util.tree_flatten((state, batch))
    step = make_train_step(cfg, st, opt, tc)
    runner = spmd_partition(
        lambda *xs: step(*jax.tree_util.tree_unflatten(tdef, xs))[0],
        jmesh, Mesh.create((1, 1), ("data", "model")))
    args = [_shape(one_chip, x.shape, x.dtype) for x in leaves]
    jax.eval_shape(runner, *args)
    (entry,) = runner.plans.values()
    hlo = entry.call.lower(*args).compile().as_text()
    kernels = [m.group(1) for line in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line
               for m in [re.search(r'op_name="([^"]+)"', line)] if m]
    assert kernels and all(re.search(r"\battention\b", n) for n in kernels)
    assert any("transpose(" not in n for n in kernels)
    assert any("transpose(" in n for n in kernels)
    # the chunked loop's [B, S, heads, 1, S] f32 scores are gone
    assert f"f32[2,{S},2,1,{S}]" not in hlo
