"""Which attention path ``self_attention`` takes, and that the paths which
bypass the flash kernel stay as they were.

The kernel path needs a TPU backend; the tests patch
``repro.kernels.ops.on_tpu``, and on the CPU the kernels run in the Pallas
TPU interpreter."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.configs.base import get_strategy
from repro.configs.registry import get_config
from repro.core.compat import make_jax_mesh, trace_for
from repro.core.partitioner import spmd_partition
from repro.core.plan import kernel_calls
from repro.core.sharding import Mesh
from repro.kernels import ops
from repro.launch.train import reduced_config
from repro.models import attention as attn
from repro.models import layers
from repro.models.layers import tree_init
from repro.obs import metrics

ST = get_strategy("2d_finalized")
PATHS = ("attention.flash_kernel", "attention.xla_chunked")
FWD = "_flash_attention_kernel"  # the shipped forward kernel


@pytest.fixture
def tpu(monkeypatch):
    monkeypatch.setattr(ops, "on_tpu", lambda: True)


def _counts():
    snap = metrics.snapshot(include_sources=False)["counters"]
    return {p: snap.get(p, 0.0) for p in PATHS}


def _took(before):
    after = _counts()
    return {p: after[p] - before[p] for p in PATHS}


def _pallas_names(jaxpr, outer=""):
    """Name stacks of every ``pallas_call`` in ``jaxpr``, nested ones too,
    each under the stacks of the equations that hold it."""
    out = []
    for eqn in jaxpr.eqns:
        name = "/".join(filter(None, (outer, str(eqn.source_info.name_stack))))
        if eqn.primitive.name == "pallas_call":
            out.append(name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _pallas_names(sub, name)
    return out


def _cfg(**kw):
    return reduced_config(get_config("qwen1.5-0.5b"), 16).with_(
        d_model=128, xent_chunk=0, **kw)  # 2 heads of 64


def _train_step(cfg):
    from repro.train.loop import TrainConfig, init_state, make_train_step
    from repro.train.optimizer import get_optimizer

    opt, tc = get_optimizer("adafactor", lr=0.01), TrainConfig()
    state = init_state(cfg, ST, opt, tc, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}
    step = make_train_step(cfg, ST, opt, tc)
    return step, state, batch


def _partitioned(step, state, batch):
    """``spmd_partition`` of the train step's loss on a 1x1 mesh."""
    leaves, tdef = jax.tree_util.tree_flatten((state, batch))
    runner = spmd_partition(
        lambda *xs: step(*jax.tree_util.tree_unflatten(tdef, xs))[1]["loss"],
        make_jax_mesh((1, 1), ("data", "model")),
        Mesh.create((1, 1), ("data", "model")))
    return runner, leaves


def _plan_jaxpr(runner, leaves):
    jax.eval_shape(runner, *leaves)
    (entry,) = runner.plans.values()
    return entry.plan.jaxpr


def test_train_step_traces_the_kernel_under_the_attention_scope(
        tpu, monkeypatch):
    """A reduced-Qwen train step through ``spmd_partition`` on a 1x1 mesh,
    traced as for the chip: the kernel's forward and its dK/dV and dQ
    kernels all sit under ``attention``, the backward under
    ``transpose(...)``.  Layer remat keeps the forward's residuals, so the
    plan runs each kernel once per layer and the forward never under
    ``transpose(...)``."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = _cfg(attn_chunk=128)
    before = _counts()
    runner, leaves = _partitioned(*_train_step(cfg))
    names = _pallas_names(_plan_jaxpr(runner, leaves))
    took = _took(before)
    assert took["attention.flash_kernel"] > 0
    assert took["attention.xla_chunked"] == 0
    assert len(names) == 3 and all("attention" in n for n in names), names
    assert sum("transpose(" in n for n in names) == 2
    (entry,) = runner.plans.values()
    assert entry.plan.stats.kernel_calls == {
        k: cfg.num_layers for k in (FWD, "_flash_attention_dkv_kernel",
                                    "_flash_attention_dq_kernel")}


def _matmul_policy(remat):
    return jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims


@pytest.mark.parametrize("scan_layers", [True, False],
                         ids=["scan", "loop"])
def test_layer_remat_saves_the_forward_kernel_residuals(
        tpu, monkeypatch, scan_layers):
    """Two layers under ``remat="dots"`` on the kernel path: with
    ``remat_policy`` the gradient's program holds one forward kernel per
    layer body (the backward reads the saved o, l and m), with the matmul
    policy alone two; loss and gradients are the same to the bit.  The
    kernels run in Pallas's HLO interpreter: the TPU interpreter's
    callbacks cannot be rematerialised."""
    from repro.models.api import loss_fn, param_tree

    cfg = _cfg(attn_chunk=128, scan_layers=scan_layers)
    assert cfg.remat == "dots" and cfg.num_layers == 2
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    params = tree_init(param_tree(cfg, ST), jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}

    def run():
        jax.clear_caches()
        vg = jax.value_and_grad(lambda p: loss_fn(cfg, ST, p, batch))
        with pltpu.force_tpu_interpret_mode(True):
            calls = kernel_calls(jax.make_jaxpr(vg)(params).eqns)
            out = jax.block_until_ready(jax.jit(vg)(params))
        return calls, jax.tree_util.tree_leaves(out)

    bodies = 1 if scan_layers else cfg.num_layers
    bwd = {"_flash_attention_dkv_kernel": bodies,
           "_flash_attention_dq_kernel": bodies}
    calls, got = run()
    assert calls == {FWD: bodies, **bwd}
    monkeypatch.setattr(layers, "remat_policy", _matmul_policy)
    calls, want = run()
    assert calls == {FWD: 2 * bodies, **bwd}
    for g, w in zip(got, want, strict=True):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_layer_remat_leaves_the_chunked_loop_as_it_was(tpu):
    """A Phi-shaped step (24 q over 8 kv heads, cut to 6 over 2: G = 3)
    traced for a 2x2 mesh takes the chunked loop, which saves no kernel
    residuals: its differentiated program under ``remat_policy("dots")`` is
    the one the matmul policy alone gives, as a string, once the policy
    the remat equations name is left out."""
    cfg = reduced_config(get_config("phi4-mini-3.8b"), 8).with_(xent_chunk=0)
    assert (cfg.num_heads, cfg.num_kv_heads) == (6, 2)

    def program():
        from repro.train.loop import TrainConfig, init_state, make_train_step
        from repro.train.optimizer import get_optimizer

        jax.clear_caches()
        opt, tc = get_optimizer("adafactor", lr=0.01), TrainConfig()
        state = jax.eval_shape(
            lambda: init_state(cfg, ST, opt, tc, jax.random.PRNGKey(0)))
        batch = {k: jax.ShapeDtypeStruct((4, 256), jnp.int32)
                 for k in ("tokens", "labels")}
        before = _counts()
        text = str(trace_for(Mesh.create((2, 2), ("data", "model")),
                             make_train_step(cfg, ST, opt, tc), state, batch))
        assert _took(before)["attention.flash_kernel"] == 0
        return text

    new = program()
    with pytest.MonkeyPatch.context() as m:
        m.setattr(layers, "remat_policy", _matmul_policy)
        old = program()
    assert "save_from_both_policies" in new and "pallas_call" not in new
    assert new != old
    policy = re.compile(r"policy=<function [^\n]*")
    assert policy.sub("policy", new) == policy.sub("policy", old)


def test_train_step_through_the_kernel_matches_the_chunked_loop(tpu):
    """The same step run in the Pallas TPU interpreter (without remat: the
    interpreter's callbacks cannot be rematerialised) gives the chunked
    loop's loss and parameters."""
    cfg = _cfg(attn_chunk=128, remat="none")
    step, state, batch = _train_step(cfg)
    runner, leaves = _partitioned(step, state, batch)
    assert _pallas_names(_plan_jaxpr(runner, leaves))
    loss = float(runner(*leaves))
    with pytest.MonkeyPatch.context() as m:
        m.setattr(ops, "on_tpu", lambda: False)
        runner, leaves = _partitioned(step, state, batch)
        assert not _pallas_names(_plan_jaxpr(runner, leaves))
        chunked = float(runner(*leaves))
    assert loss == pytest.approx(chunked, rel=2e-3)


def _layer(cfg):
    from repro.models.transformer import layer_param_tree

    lp = tree_init(layer_param_tree(cfg, ST), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 256, cfg.d_model),
                          jnp.bfloat16)
    positions = jnp.broadcast_to(jnp.arange(256), (2, 256))
    return lp["attn"], x, positions


def _self_attention(cfg):
    p, x, pos = _layer(cfg)
    return lambda: attn.self_attention(cfg, ST, p, x, pos, causal=True)


def _prefill(cfg):
    p, x, pos = _layer(cfg)
    return lambda: attn.prefill_attention(cfg, ST, p, x, pos)


def _decode(cfg):
    p, x, _ = _layer(cfg)
    B, T = 2, 256
    ck = jnp.zeros((B, T, cfg.num_kv_heads, cfg.dh), jnp.bfloat16)
    return lambda: attn.decode_attention(cfg, ST, p, x[:, :1], ck, ck, 7)


def _cross(cfg):
    p, x, _ = _layer(cfg)
    ek, ev = attn.encode_kv(cfg, ST, p, x)
    return lambda: attn.cross_attention(cfg, ST, p, x, ek, ev)


def _abstract_mesh(shape):
    return jax.sharding.use_abstract_mesh(jax.sharding.AbstractMesh(
        shape, ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2))


def _tracer(context):
    """How a case traces: plainly, under an ambient mesh, or for a mesh of
    ``n`` devices through ``trace_for`` (as partitioners do)."""
    if context.startswith("ambient_mesh"):
        shape = {"1x1": (1, 1), "2x2": (2, 2), "data_2": (2, 1),
                 "model_2": (1, 2)}[context.split("_", 2)[2]]

        def trace(call):
            with _abstract_mesh(shape):
                return jax.make_jaxpr(call)()
        return trace
    if context.startswith("partitioned_for"):
        shape = {"partitioned_for_4_devices": (2, 2),
                 "partitioned_for_1_device": (1, 1)}[context]
        return lambda call: trace_for(Mesh.create(shape, ("data", "model")),
                                      call)
    return lambda call: jax.make_jaxpr(call)()


@pytest.mark.parametrize("case", [
    "cpu_backend", "ambient_mesh_2x2", "ambient_mesh_data_2",
    "ambient_mesh_model_2", "partitioned_for_4_devices", "length_not_128",
    "head_dim_32", "gqa", "prefill", "decode", "cross_attention"])
def test_paths_that_bypass_the_kernel(monkeypatch, case):
    """No ``pallas_call`` in the traced program; self-attention counts
    ``attention.xla_chunked`` and the result is the chunked loop's."""
    cfg = _cfg(attn_chunk=64)
    if case != "cpu_backend":
        monkeypatch.setattr(ops, "on_tpu", lambda: True)
    fn = {"prefill": _prefill, "decode": _decode,
          "cross_attention": _cross}.get(case, _self_attention)
    if case == "head_dim_32":
        cfg = cfg.with_(d_model=64)
    if case == "gqa":
        cfg = cfg.with_(d_model=256, num_heads=4, num_kv_heads=2)
    call = fn(cfg)
    if case == "length_not_128":
        p, x, pos = _layer(cfg)
        x, pos = x[:, :192], pos[:, :192]
        call = lambda: attn.self_attention(cfg, ST, p, x, pos)  # noqa: E731
    before = _counts()
    jaxpr = _tracer(case)(call)
    assert not _pallas_names(jaxpr.jaxpr)
    took = _took(before)
    assert took["attention.flash_kernel"] == 0
    if fn is _self_attention:
        assert took["attention.xla_chunked"] == 1


@pytest.mark.parametrize("context", ["none", "ambient_mesh_1x1",
                                     "partitioned_for_1_device"])
def test_unsharded_self_attention_takes_the_kernel(tpu, context):
    call = _self_attention(_cfg(attn_chunk=64))
    before = _counts()
    jaxpr = _tracer(context)(call)
    assert len(_pallas_names(jaxpr.jaxpr)) == 1
    assert _took(before) == {"attention.flash_kernel": 1,
                             "attention.xla_chunked": 0}


def _propagation_for(mesh):
    from repro.core.apply import gspmd_jit

    cfg = _cfg(attn_chunk=64)
    p, x, pos = _layer(cfg)
    fn = gspmd_jit(lambda x: attn.self_attention(cfg, ST, p, x, pos),
                   None, mesh)
    return fn.propagation_for(x).jaxpr


def _sharding_problem(mesh):
    from repro.launch.elastic import sharding_problem

    return sharding_problem(_cfg(attn_chunk=64), ST, mesh, 2, 128)[0].jaxpr


def _registry_problem(mesh):
    from repro.autoshard import registry_problem

    # the registry's Qwen uncut: 16 heads of 64, as the chip cell runs
    return registry_problem("qwen1.5-0.5b", mesh, batch=2, seq=128,
                            reduce_k=1)[0].jaxpr


@pytest.mark.parametrize("shape", [(2, 2), (1, 1)], ids=["2x2", "1x1"])
@pytest.mark.parametrize("site", [_propagation_for, _sharding_problem,
                                  _registry_problem],
                         ids=lambda f: f.__name__.lstrip("_"))
def test_traces_for_a_mesh_see_its_devices(tpu, site, shape):
    """The sharding searches and ``gspmd_jit``'s propagation trace the
    program that runs on their mesh: for four devices it holds no kernel,
    for one it holds the kernel, as the partitioned step does."""
    before = _counts()
    names = _pallas_names(site(Mesh.create(shape, ("data", "model"))))
    took = _took(before)
    if shape == (1, 1):
        assert names and took["attention.flash_kernel"] > 0
        assert took["attention.xla_chunked"] == 0
    else:
        assert not names and took["attention.flash_kernel"] == 0
        assert took["attention.xla_chunked"] > 0


def test_counted_once_per_trace_not_per_step(tpu):
    cfg = _cfg(attn_chunk=64)
    p, x, pos = _layer(cfg)
    f = jax.jit(lambda x: attn.self_attention(cfg, ST, p, x, pos))
    before = _counts()
    for _ in range(3):
        out = f(x)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(f(x), np.float32))
    assert _took(before)["attention.flash_kernel"] == 1
