"""Prove that the trainer and this repo's partitioner run on a TPU chip.

    python chip_smoke.py               # one chip: trainer + partitioner
    python chip_smoke.py --four-chips  # 2x2 mesh: sharded step + partitioner

qwen1.5-0.5b at its published widths (24 layers, d_model 1024, 16 heads,
d_ff 2816, vocabulary 151,936), random weights from a seed.

One chip:
  * trainer: ``repro.launch.train.main`` for a few steps; every loss must be
    finite, and the first loss must match the same forward pass computed on
    the host CPU backend in this process;
  * partitioner: ``spmd_partition`` of ``value_and_grad`` of the loss on a
    1x1 mesh of the chip, against ``jax.jit`` of the same function.

Four chips (``--four-chips``, and nothing else): the train step on the
(data, model) 2x2 mesh under the model's default strategy against the
one-chip loss, and ``spmd_partition`` of the loss on that mesh, seeded with
the strategy's weight layout, against ``jax.jit`` with ``NamedSharding``.

Runs in one process and starts none.  Exits non-zero, before printing any
result, when JAX finds no TPU or when any check fails.  The last line of
standard output is the JSON result.
"""
from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys
import time

ARCH = "qwen1.5-0.5b"
SEED = 0
BATCH, SEQ = 4, 1024  # 4,096 tokens per step
STEPS = 6
# bf16 compute: the TPU's MXU and XLA's CPU backend round matmul operands
# and partial sums differently; sharded programs also psum in another order
TOL = "coarse"


def _require_tpu():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (first device: {dev.platform})")
    return dev


def _import_repo():
    src = pathlib.Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        sys.exit(f"chip_smoke: no repository sources at {src}")
    sys.path.insert(0, str(src))


def _tag(devices) -> str:
    d = devices[0]
    return f"[{d.platform} {d.device_kind} x{len(devices)}]"


def _model(arch: str, reduce: int):
    from repro.configs.base import get_strategy
    from repro.configs.registry import default_strategy, get_config
    from repro.launch.train import reduced_config

    return (reduced_config(get_config(arch), reduce),
            get_strategy(default_strategy(arch)))


def _inputs(cfg, st, batch: int, seq: int):
    """The trainer's step-0 parameters and batch, rebuilt from the seed."""
    import jax
    import jax.numpy as jnp

    from repro.data.pipeline import DataConfig, TokenPipeline
    from repro.models import api
    from repro.models.layers import tree_init

    params = tree_init(api.param_tree(cfg, st), jax.random.PRNGKey(SEED))
    pipe = TokenPipeline(DataConfig(cfg.vocab_size, seq, batch, seed=SEED))
    return params, {k: jnp.asarray(v) for k, v in pipe.batch_at(0).items()}


def _peak_bytes(device):
    return (device.memory_stats() or {}).get("peak_bytes_in_use")


def _grad_norm(grads) -> float:
    import jax
    import jax.numpy as jnp

    return float(jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                              for g in jax.tree_util.tree_leaves(grads))))


def _check(name: str, got: float, want: float) -> None:
    from repro.core.compat import assert_close

    assert_close(got, want, TOL, err_msg=name)
    print(f"{name}: {got!r} vs {want!r} (|diff| {abs(got - want):.3g}) "
          f"within {TOL!r}")


def trainer_phase(arch=ARCH, reduce=1, batch=BATCH, seq=SEQ, steps=STEPS):
    """Train through the normal entry point; check losses and CPU parity."""
    import jax

    from repro.launch.train import main as train_main
    from repro.models import api

    tag = _tag(jax.devices())
    losses, times = train_main([
        "--arch", arch, "--reduce", str(reduce), "--steps", str(steps),
        "--batch", str(batch), "--seq", str(seq), "--seed", str(SEED),
    ])
    for i, loss in enumerate(losses):
        print(f"trainer step {i}: loss {loss!r}")
    print(f"{tag} trainer step 0 (compile included): {times[0]:.3f} s")
    print(f"{tag} trainer steady steps: "
          + ", ".join(f"{t:.4f}" for t in times[1:]) + " s")
    print(f"{tag} peak_bytes_in_use: {_peak_bytes(jax.devices()[0])}")
    if len(losses) != steps or not all(math.isfinite(v) for v in losses):
        raise AssertionError(f"trainer losses not all finite: {losses}")

    cfg, st = _model(arch, reduce)
    params, batch_ = _inputs(cfg, st, batch, seq)
    cpu = jax.devices("cpu")[0]
    params_cpu, batch_cpu = jax.device_put((params, batch_), cpu)
    del params, batch_
    t0 = time.perf_counter()
    cpu_loss = float(jax.jit(lambda p, b: api.loss_fn(cfg, st, p, b))(
        params_cpu, batch_cpu))
    print(f"[cpu x1] step-0 forward loss on the host: "
          f"{time.perf_counter() - t0:.1f} s")
    _check("step-0 loss, chip trainer vs host CPU", losses[0], cpu_loss)


def partitioner_phase(arch=ARCH, reduce=1, batch=BATCH, seq=SEQ):
    """spmd_partition of loss+grad on a 1x1 mesh of the chip vs jax.jit."""
    import jax

    from repro.core.compat import make_jax_mesh
    from repro.core.partitioner import spmd_partition
    from repro.core.sharding import Mesh
    from repro.models import api

    tag = _tag(jax.devices()[:1])
    cfg, st = _model(arch, reduce)
    params, batch_ = _inputs(cfg, st, batch, seq)
    vg = jax.value_and_grad(lambda p, b: api.loss_fn(cfg, st, p, b))
    leaves, tdef = jax.tree_util.tree_flatten((params, batch_))

    def flat(*xs):
        loss, grads = vg(*jax.tree_util.tree_unflatten(tdef, xs))
        return (loss, *jax.tree_util.tree_leaves(grads))

    runner = spmd_partition(flat, make_jax_mesh((1, 1), ("data", "model")),
                            Mesh.create((1, 1), ("data", "model")))
    t0 = time.perf_counter()
    out = jax.block_until_ready(runner(*leaves))
    first = time.perf_counter() - t0
    (entry,) = runner.plans.values()
    t0 = time.perf_counter()
    out = jax.block_until_ready(runner(*leaves))
    again = time.perf_counter() - t0
    part_loss, part_gnorm = float(out[0]), _grad_norm(out[1:])
    del out
    print(f"[host] plan build (trace + propagate + lower): {entry.build_s:.2f} s")
    print(f"plan: {len(entry.plan.steps)} steps, "
          f"fallback equations {sum(entry.plan.stats.fallbacks.values())} "
          f"{entry.plan.stats.fallbacks}")
    print(f"{tag} spmd_partition first call (build + compile + run): "
          f"{first:.3f} s; second call: {again:.4f} s")

    t0 = time.perf_counter()
    ref_loss, ref_grads = jax.block_until_ready(jax.jit(vg)(params, batch_))
    print(f"{tag} jax.jit first call (compile + run): "
          f"{time.perf_counter() - t0:.3f} s")
    _check("loss, spmd_partition vs jax.jit", part_loss, float(ref_loss))
    _check("grad norm, spmd_partition vs jax.jit", part_gnorm,
           _grad_norm(ref_grads))


def four_chip_phase(arch=ARCH, reduce=1, batch=BATCH, seq=SEQ):
    """The sharded train step and spmd_partition on a (data, model) 2x2 mesh."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.autoshard import sharding_from_spec
    from repro.core.annotate import annotate
    from repro.core.compat import set_mesh
    from repro.core.partitioner import spmd_partition
    from repro.data.pipeline import DataConfig, TokenPipeline
    from repro.launch.elastic import derive_mesh
    from repro.models import api
    from repro.models.layers import tree_specs
    from repro.train.loop import TrainConfig, TrainLoop
    from repro.train.optimizer import get_optimizer

    mesh, jmesh = derive_mesh(4, model_parallel=2)
    devices = list(jmesh.devices.flat)
    tag = _tag(devices)
    cfg, st = _model(arch, reduce)
    print(f"mesh {dict(zip(jmesh.axis_names, jmesh.devices.shape))}, "
          f"strategy {st.name}")
    loss_of = lambda p, b: api.loss_fn(cfg, st, p, b)

    params, batch_ = _inputs(cfg, st, batch, seq)
    one_chip = float(jax.jit(loss_of)(params, batch_))  # device 0 alone

    pipe = TokenPipeline(DataConfig(cfg.vocab_size, seq, batch, seed=SEED))
    with set_mesh(jmesh):
        loop = TrainLoop(cfg, st, get_optimizer("adafactor", lr=1e-2),
                         TrainConfig(steps=2), pipe,
                         rng=jax.random.PRNGKey(SEED))
        t0 = time.perf_counter()
        state, losses = loop.run()
        print(f"{tag} 2x2 trainer: 2 steps in {time.perf_counter() - t0:.3f} s "
              f"(compile included), losses {losses}")
        pspecs = tree_specs(api.param_tree(cfg, st))
    for d in devices:
        held = sum(s.data.nbytes for leaf in jax.tree_util.tree_leaves(
            state["params"]) for s in leaf.addressable_shards if s.device == d)
        print(f"{tag} device {d.id}: parameter bytes {held}, "
              f"peak_bytes_in_use {_peak_bytes(d)}")
    del state
    _check("step-0 loss, 2x2 trainer vs one chip", losses[0], one_chip)

    # this repo's partitioner, seeded with the strategy's weight layout
    weight_seeds = jax.tree_util.tree_map(
        lambda spec, x: sharding_from_spec(mesh, spec, x.shape), pspecs,
        params, is_leaf=lambda x: x is None or isinstance(x, P))
    leaves, tdef = jax.tree_util.tree_flatten((params, batch_))

    def seeded_loss(*xs):
        p, b = jax.tree_util.tree_unflatten(tdef, xs)
        p = jax.tree_util.tree_map(annotate, p, weight_seeds)
        b = {k: annotate(v, sharding_from_spec(mesh, P("data"), v.shape))
             for k, v in b.items()}
        return loss_of(p, b)

    runner = spmd_partition(seeded_loss, jmesh, mesh)
    t0 = time.perf_counter()
    part = float(runner(*leaves))
    (entry,) = runner.plans.values()
    print(f"[host] plan build (trace + propagate + lower): {entry.build_s:.2f} s")
    print(f"{tag} spmd_partition first call (build + compile + run): "
          f"{time.perf_counter() - t0:.3f} s")
    print(f"plan collectives {entry.plan.stats.collectives}, fallback "
          f"equations {entry.plan.stats.fallbacks}")

    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(jmesh, s if s is not None else P()), pspecs,
        is_leaf=lambda x: x is None or isinstance(x, P))
    with set_mesh(jmesh):
        ref = float(jax.jit(loss_of, in_shardings=(
            shardings, NamedSharding(jmesh, P("data"))))(params, batch_))
    _check("loss on 2x2, spmd_partition vs jax.jit + NamedSharding", part, ref)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2-mesh phase (needs 4 chips)")
    args = ap.parse_args(argv)

    import jax

    dev = _require_tpu()
    _import_repo()
    from repro.launch.train import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    if args.four_chips:
        four_chip_phase()
    else:
        trainer_phase()
        partitioner_phase()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
