"""Which attention path ``self_attention`` takes, and that the paths which
bypass the flash kernel stay as they were.

The kernel path needs a TPU backend; the tests patch
``repro.kernels.ops.on_tpu``, and on the CPU the kernels run in the Pallas
TPU interpreter."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_strategy
from repro.configs.registry import get_config
from repro.core.compat import make_jax_mesh, trace_for
from repro.core.partitioner import spmd_partition
from repro.core.sharding import Mesh
from repro.kernels import ops
from repro.launch.train import reduced_config
from repro.models import attention as attn
from repro.models.layers import tree_init
from repro.obs import metrics

ST = get_strategy("2d_finalized")
PATHS = ("attention.flash_kernel", "attention.xla_chunked")


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
    traced as for the chip: the kernel's forward, rematerialised forward
    and backward all sit under ``attention``, the backward under
    ``transpose(...)``."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    before = _counts()
    names = _pallas_names(_plan_jaxpr(*_partitioned(
        *_train_step(_cfg(attn_chunk=128)))))
    took = _took(before)
    assert took["attention.flash_kernel"] > 0
    assert took["attention.xla_chunked"] == 0
    assert len(names) >= 4 and all("attention" in n for n in names), names
    assert any("transpose(" in n for n in names)
    assert any("transpose(" not in n for n in names)


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
