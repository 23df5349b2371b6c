"""Compile each cell's timed step for a described TPU, with no chip attached.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py [--workload <name>]

For every workload of ``BENCHMARK.json`` (or the one named): describe a
``v5e:2x2`` host and take as many of its chips as the cell asks for, build the cell's ``spmd_partition`` runner over those devices, lower
its jitted program for the state and batch shapes with the strategy's
shardings, compile it with the TPU compiler and print ``memory_analysis()``
per device.  Where the runner's program cannot be lowered for described
devices, the same step is compiled under ``jax.jit`` with the strategy's
``NamedSharding``s instead, and the line says so.  Nothing runs, so this
says nothing of times or results; it shows what the chip's compiler
refuses (a step that does not fit, a layout it cannot take) before a chip
call is spent on it.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
if sys.path and sys.path[0] and pathlib.Path(sys.path[0]).resolve() == HERE:
    sys.path.pop(0)
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

TOPOLOGY = "v5e:2x2"  # one host; a one-chip cell takes its first chip


def _gib(n) -> str:
    return f"{n / 2**30:.2f} GiB"


def abstract_args(run):
    """State and batch as ShapeDtypeStructs with their shardings."""
    import jax
    import jax.numpy as jnp

    from repro.train.loop import init_state

    state = jax.eval_shape(lambda: init_state(
        run.cfg, run.st, run.opt, run.tc, jax.random.PRNGKey(0)))
    state = jax.tree_util.tree_map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        state, run.state_shardings)
    batch = [jax.ShapeDtypeStruct((run.B, run.S), jnp.int32,
                                  sharding=run.batch_sharding)] * 2
    return jax.tree_util.tree_leaves(state) + batch, state


def rehearse(cell, devices) -> dict:
    import jax

    from benchmarks.chip.run import TrainRun
    from repro.train.loop import make_train_step

    run = TrainRun(cell, devices)
    args, state = abstract_args(run)
    t0 = time.perf_counter()
    path = "spmd_partition"
    try:
        try:
            run.partitioned(*args)  # builds the plan, then cannot execute
        except Exception:  # noqa: BLE001 - a described chip runs nothing
            if not run.partitioned.plans:
                raise
        entry = run.plan_entry()
        build_s = time.perf_counter() - t0
        compiled = entry.call.lower(*args).compile()
        extra = {"plan_build_s": build_s, "plan_steps": len(entry.plan.steps),
                 "fallbacks": entry.plan.stats.fallbacks,
                 "collectives": entry.plan.stats.collectives}
    except Exception as e:  # noqa: BLE001
        path = f"jax.jit with NamedSharding (spmd_partition: {type(e).__name__}: {e})"
        step = make_train_step(run.cfg, run.st, run.opt, run.tc)
        batch = {"labels": args[-2], "tokens": args[-1]}
        compiled = jax.jit(step).lower(state, batch).compile()
        extra = {}
    ma = compiled.memory_analysis()
    return {"workload": cell.name, "path": path,
            "compile_s": time.perf_counter() - t0,
            "argument": ma.argument_size_in_bytes,
            "output": ma.output_size_in_bytes,
            "temp": ma.temp_size_in_bytes,
            "alias": ma.alias_size_in_bytes,
            "peak": ma.peak_memory_in_bytes,
            "code": ma.generated_code_size_in_bytes, **extra}


def main(argv=None) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", default=None)
    args = ap.parse_args(argv)

    import jax
    from jax.experimental import topologies

    from benchmarks.chip.cell import Cell

    jax.config.update("jax_enable_compilation_cache", False)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = [w["name"] for w in bench["workloads"]
             if args.workload in (None, w["name"])]
    for name in names:
        cell = Cell(ROOT, name)
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name=TOPOLOGY)
        r = rehearse(cell, topo.devices[:cell.chips])
        print(f"{name}: {r['path']}, per device: arguments "
              f"{_gib(r['argument'])}, outputs {_gib(r['output'])}, temp "
              f"{_gib(r['temp'])}, aliased {_gib(r['alias'])}, peak "
              f"{_gib(r['peak'])} (compile {r['compile_s']:.1f} s)")
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
