"""The program's own measurement: the scopes the partitioned program keeps
in its op names, the ``repro.*`` profiler spans, and the runner's counters.

The scopes are read from the compiled HLO's ``metadata={op_name=...}``, the
spans from a CPU ``jax.profiler`` trace (host plane only: a CPU trace holds
no device plane)."""
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.compat import make_jax_mesh
from repro.core.partitioner import spmd_partition
from repro.core.sharding import Mesh
from repro.obs import metrics

SCOPES = ("attention", "mlp", "head", "optimizer")
OP_NAME = re.compile(r'op_name="([^"]+)"')
DOT_OP_NAME = re.compile(r"= \S+ dot\(.*?op_name=\"([^\"]+)\"")
# the partitioner's einsum runs under a scope named by its subscripts
EINSUM = re.compile(r"^[a-z]*(,[a-z]*)*->[a-z]*$")


def _mesh():
    return make_jax_mesh((1, 1), ("data", "model")), Mesh.create(
        (1, 1), ("data", "model"))


def _op_names(hlo_text):
    """Op names without the top-level ``jit(...)`` and einsum scopes."""
    out = set()
    for name in OP_NAME.findall(hlo_text):
        parts = [p for p in name.split("/")[1:] if not EINSUM.match(p)]
        out.add("/".join(parts))
    return out


def _partitioned(step, *args):
    """(runner, its one cache entry, flat args) for ``step(*args)``."""
    leaves, tdef = jax.tree_util.tree_flatten(args)
    jm, mesh = _mesh()
    runner = spmd_partition(
        lambda *xs: step(*jax.tree_util.tree_unflatten(tdef, xs)), jm, mesh)
    jax.eval_shape(runner, *leaves)
    (entry,) = runner.plans.values()
    return runner, entry, leaves


def _layer(x, w):
    with jax.named_scope("attention"):
        h = jnp.tanh(x @ w["a"])
    x = x + h
    with jax.named_scope("mlp"):
        h = jnp.maximum(x @ w["m"], 0.0)
    return x + h


def _loss(params, x):
    with jax.named_scope("head"):
        x = x @ params["e"]

    def body(c, w):
        return jax.checkpoint(_layer)(c, w), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    with jax.named_scope("head"):
        return jnp.mean((x @ params["e"].T) ** 2)


def _grad_step(params, x):
    loss, g = jax.value_and_grad(_loss)(params, x)
    with jax.named_scope("optimizer"):
        norm = jnp.sqrt(sum(jnp.sum(v * v) for v in jax.tree_util.tree_leaves(g)))
        new = jax.tree_util.tree_map(lambda p, v: p - 0.1 * v / norm, params, g)
    return new, loss


def _grad_args():
    params = {"e": jnp.ones((8, 8)),
              "layers": {"a": jnp.ones((3, 8, 8)), "m": jnp.ones((3, 8, 8))}}
    return params, jnp.ones((4, 8))


def test_partitioned_grad_step_keeps_the_scopes_of_plain_jit():
    args = _grad_args()
    plain = _op_names(jax.jit(_grad_step).lower(*args).compile().as_text())
    _, entry, leaves = _partitioned(_grad_step, *args)
    part = _op_names(entry.call.lower(*leaves).compile().as_text())
    dots = lambda names: {n for n in names if n.endswith("dot_general")}  # noqa: E731
    assert dots(part) == dots(plain)
    # forward, backward and the rematerialised forward of the scanned layer
    for scope in ("attention", "mlp"):
        assert f"jvp()/while/body/closed_call/{scope}/dot_general" in part
        assert ("transpose(jvp())/while/body/closed_call/checkpoint/"
                f"{scope}/dot_general") in part
        assert ("transpose(jvp())/while/body/closed_call/checkpoint/"
                f"rematted_computation/{scope}/dot_general") in part
    assert {"jvp(head)/dot_general", "transpose(jvp(head))/dot_general"} <= part
    assert any(n.startswith("optimizer/") for n in part)
    assert any(n.startswith("optimizer/") for n in plain)


def test_reduced_qwen_train_step_has_every_dot_under_a_scope():
    from repro.configs.base import get_strategy
    from repro.configs.registry import get_config
    from repro.launch.train import reduced_config
    from repro.train.loop import TrainConfig, init_state, make_train_step
    from repro.train.optimizer import get_optimizer

    cfg = reduced_config(get_config("qwen1.5-0.5b"), 16).with_(
        xent_chunk=0, attn_chunk=32)
    st = get_strategy("2d_finalized")
    opt = get_optimizer("adafactor", lr=0.01)
    tc = TrainConfig()
    state = jax.eval_shape(lambda: init_state(cfg, st, opt, tc,
                                              jax.random.PRNGKey(0)))
    batch = {k: jax.ShapeDtypeStruct((2, 64), jnp.int32)
             for k in ("tokens", "labels")}
    step = make_train_step(cfg, st, opt, tc)
    _, entry, leaves = _partitioned(lambda s, b: step(s, b)[0], state, batch)
    hlo = entry.call.lower(*leaves).compile().as_text()
    dots = DOT_OP_NAME.findall(hlo)
    assert dots
    unscoped = [n for n in dots
                if not re.search(r"\b(%s)\b" % "|".join(SCOPES), n)]
    assert not unscoped, unscoped[:5]
    names = "\n".join(OP_NAME.findall(hlo))
    for scope in SCOPES:
        assert re.search(r"\b%s\b" % scope, names), scope
    assert "transpose(" in names


def _host_events(trace_dir, with_stats=("train",)):
    """``(name, stats)`` of every event on the trace's host planes; stats
    are read for the events named in ``with_stats`` only."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    pd = ProfileData.from_file(path)
    return [(e.name, dict(e.stats) if e.name in with_stats else {})
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def test_runner_call_lands_its_spans_in_a_profiler_trace(tmp_path):
    jm, mesh = _mesh()
    runner = spmd_partition(lambda x: jnp.tanh(x) * 2.0, jm, mesh)
    x = jnp.ones((8, 8))
    jax.profiler.start_trace(str(tmp_path))
    try:
        runner(x)  # a miss: build, then the first call
        runner(x)  # a hit
    finally:
        jax.profiler.stop_trace()
    names = [n for n, _ in _host_events(str(tmp_path))]
    assert names.count("repro.partition.call") == 2
    assert names.count("repro.partition.lookup") == 2
    assert names.count("repro.partition.dispatch") == 2
    assert names.count("repro.partition.build") == 1
    for phase in ("make_jaxpr", "propagate", "compile_plan", "lower",
                  "optimize", "verify", "jit"):
        assert f"repro.partition.{phase}" in names, phase


def test_build_phases_and_first_call_s_are_recorded():
    jm, mesh = _mesh()
    runner = spmd_partition(lambda x: jnp.tanh(x) @ x, jm, mesh)
    x = jnp.ones((8, 8))
    jax.eval_shape(runner, x)  # traces the plan: no concrete call
    (entry,) = runner.plans.values()
    assert entry.first_call_s is None
    assert set(entry.phases) == {"make_jaxpr", "propagate", "compile_plan",
                                 "lower", "optimize", "verify", "jit"}
    assert all(t >= 0.0 for t in entry.phases.values())
    assert entry.phases["compile_plan"] >= entry.phases["lower"]
    assert sum(entry.phases[k] for k in ("make_jaxpr", "propagate",
                                         "compile_plan", "jit")) \
        <= entry.build_s
    out = runner(x)
    first = entry.first_call_s
    assert first is not None and first > 0.0
    np.testing.assert_allclose(np.asarray(runner(x)), np.asarray(out))
    assert entry.first_call_s == first  # later calls leave it as it is


def test_runner_hits_do_not_write_to_the_metrics_registry():
    jm, mesh = _mesh()
    runner = spmd_partition(lambda x: x + 1.0, jm, mesh)
    x = jnp.ones((4,))
    runner(x)
    before = metrics.snapshot(include_sources=False)
    for _ in range(5):
        runner(x)
    assert metrics.snapshot(include_sources=False) == before
    assert runner.cache_stats.hits == 5 and runner.cache_stats.misses == 1


def test_train_loop_steps_and_batches_land_in_a_profiler_trace(tmp_path):
    from repro.configs.base import get_strategy
    from repro.configs.registry import get_config
    from repro.data.pipeline import DataConfig, TokenPipeline
    from repro.launch.train import reduced_config
    from repro.train.loop import TrainConfig, TrainLoop
    from repro.train.optimizer import get_optimizer

    cfg = reduced_config(get_config("qwen1.5-0.5b"), 32).with_(attn_chunk=16)
    loop = TrainLoop(cfg, get_strategy("2d_finalized"),
                     get_optimizer("adafactor", lr=0.01), TrainConfig(steps=2),
                     TokenPipeline(DataConfig(cfg.vocab_size, 32, 2)))
    loop.run()  # compiles the step outside the profiler, which slows it
    jax.profiler.start_trace(str(tmp_path))
    try:
        _, losses = loop.run()
    finally:
        jax.profiler.stop_trace()
    assert len(losses) == 2
    events = _host_events(str(tmp_path))
    steps = sorted(s["step_num"] for n, s in events
                   if n == "train" and "step_num" in s)
    assert steps == [0, 1]
    assert sum(n == "repro.data.batch_at" for n, _ in events) == 2
