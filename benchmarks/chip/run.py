"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cell is a training step of this repo's partitioner: ``spmd_partition``
of the program's own ``make_train_step`` (``train/loop.py``) over the
traffic's ``(data, model)`` mesh, seeded with the strategy's layout of the
state (``state_partition_specs``) and of the batch.  Set-up builds the plan,
makes the weights from the seed on the devices in one jitted call, in the
layout the runner returns the state in, then drives the runner through the
traffic's first steps (the first compiles, or loads the compile cache; the
others must not lower anything, so that every step runs one executable),
which the reference follows.
The window then runs steps in a closed loop for ``--seconds``: the batch
from ``TokenPipeline.batch_at(step)``, placed on the mesh, and the next
dispatch; no host read of its own; it closes with ``block_until_ready`` on
the last state.  After it, the program's state is freed and the plain
float32 reference (``families/<family>.py``) runs the first steps again;
``compare.py`` decides ``correct``.

With ``--trace 0`` the result carries the cell's end-to-end metrics
(``tokens_per_s``, ``setup_s``); with ``--trace 1`` the window runs under
``jax.profiler`` and the result carries the per-layer metrics, read by
``metrics/<name>.py`` from the run's record and the reduced trace
(``trace.py``), with ``device.busy_s``/``window_s`` and a ``breakdown``.

Exits 2, printing no result, when JAX finds no TPU or fewer chips than the
cell asks for, or when the program under test is not in the checkout; exits
1 when something compiles inside the window, or the step lowers again after
its first call.  The last line of standard
output is the result; each number compared is printed beside its limit on
the last lines of standard error and under ``checks``, the result's last
key.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
if sys.path and sys.path[0] and pathlib.Path(sys.path[0]).resolve() == HERE:
    sys.path.pop(0)  # this directory's trace.py would shadow the stdlib's
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

# lowerings of a jitted program (each is a compile or a compile-cache load)
# and the persistent compile cache's hits and misses
COUNTS = {"lowerings": 0, "cache_hits": 0, "cache_misses": 0}
EVENTS = {"/jax/core/compile/jaxpr_to_mlir_module_duration": "lowerings",
          "/jax/compilation_cache/cache_hits": "cache_hits",
          "/jax/compilation_cache/cache_misses": "cache_misses"}


def fail(msg: str, code: int = 2):
    print(f"benchmark: {msg}", file=sys.stderr)
    sys.exit(code)


def require_program(root=ROOT):
    if not (pathlib.Path(root) / "src" / "repro").is_dir():
        fail(f"no program under test at {root}/src/repro")


def require_chips(chips: int):
    """The first ``chips`` TPU devices; exits when JAX has no TPU or fewer."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        fail(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def enable_compile_cache(root=ROOT):
    """JAX's persistent compile cache: ``$JAX_COMPILATION_CACHE_DIR`` where
    that is set, else ``<checkout>/.jax_cache``, the rule the program's
    ``launch.train.enable_compile_cache`` follows.  The benchmark keeps its
    own copy because the yardstick takes nothing from the program but the
    system under test; it also caches every program, however small."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(pathlib.Path(root) / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _count(name, *_, **__):
    if name in EVENTS:
        COUNTS[EVENTS[name]] += 1


def count_compiles():
    """Listen to JAX's compile events (once per process)."""
    import jax.monitoring

    if not getattr(_count, "registered", False):
        jax.monitoring.register_event_listener(_count)
        jax.monitoring.register_event_duration_secs_listener(_count)
        _count.registered = True


def _names(tree):
    import jax

    return [jax.tree_util.keystr(p, simple=True, separator="/")
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def ref_placement(devices, shape):
    """Reference placement: one chip, or each array split on its largest
    dim that the devices divide (batch rows for the batch)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(devices), ("d",))
    n = len(devices)
    dims = [i for i in range(len(shape)) if shape[i] % n == 0]
    if n == 1 or not dims:
        return NamedSharding(mesh, P())
    i = max(dims, key=lambda i: (shape[i], -i))
    return NamedSharding(mesh, P(*([None] * i + ["d"])))


class TrainRun:
    """The runner of one training cell, its state and its first steps.

    ``step_wrapper(flat_step, run)`` replaces the step that goes to
    ``spmd_partition`` (the faults of ``faults.py``); None is the program
    as it is.
    """

    def __init__(self, cell, devices, step_wrapper=None):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.autoshard import sharding_from_spec
        from repro.configs.base import ModelConfig, get_strategy
        from repro.core.annotate import annotate
        from repro.core.compat import set_mesh
        from repro.core.partitioner import (clear_process_plan_cache,
                                            spmd_partition)
        from repro.core.sharding import to_partition_spec
        from repro.data.pipeline import DataConfig, TokenPipeline
        from repro.launch.elastic import derive_mesh
        from repro.train.loop import (TrainConfig, init_state,
                                      make_train_step, state_partition_specs)
        from repro.train.optimizer import get_optimizer

        self.cell, self.devices = cell, list(devices)
        tr = cell.traffic
        if tr["kind"] != "train":
            raise ValueError(f"benchmark: traffic kind {tr['kind']!r}")
        self.B, self.S = tr["batch"], tr["seq"]
        self.first_steps = int(tr["first_steps"])
        self.cfg = ModelConfig(**cell.family.program_config(cell.config, tr))
        self.st = get_strategy(tr["strategy"])
        o = dict(tr["optimizer"])
        if o["name"] != "adafactor":
            raise ValueError("benchmark: the comparison reads Adafactor's "
                             f"state; optimizer {o['name']!r} has no reader")
        self.opt = get_optimizer(o.pop("name"), **o)
        self.tc = TrainConfig()
        shape = (tr["mesh"]["data"], tr["mesh"]["model"])
        if shape[0] * shape[1] != len(self.devices):
            raise ValueError(f"benchmark: mesh {shape} on "
                             f"{len(self.devices)} devices")
        self.mesh, self.jmesh = derive_mesh(devices=self.devices,
                                            model_parallel=shape[1])
        with set_mesh(self.jmesh):
            specs = state_partition_specs(self.cfg, self.st, self.opt, self.tc)
        abstract = jax.eval_shape(lambda: init_state(
            self.cfg, self.st, self.opt, self.tc, jax.random.PRNGKey(0)))
        is_p = lambda x: isinstance(x, P)  # noqa: E731
        seeds = jax.tree_util.tree_map(
            lambda s, x: sharding_from_spec(self.mesh, s, x.shape), specs,
            abstract, is_leaf=is_p)
        bseed = sharding_from_spec(self.mesh, P("data"), (self.B, self.S))
        self.batch_sharding = NamedSharding(self.jmesh, P("data"))
        batch = {k: jax.ShapeDtypeStruct((self.B, self.S), jnp.int32)
                 for k in ("tokens", "labels")}
        self.tdef = jax.tree_util.tree_structure((abstract, batch))
        self.state_def = jax.tree_util.tree_structure(abstract)
        self.n_state = self.state_def.num_leaves
        self.state_names = _names(abstract)
        self.param_def = jax.tree_util.tree_structure(abstract["params"])
        self.param_names = _names(abstract["params"])
        self.param_shapes = {n: x.shape for n, x in zip(
            self.param_names, jax.tree_util.tree_leaves(abstract["params"]))}
        made = jax.eval_shape(lambda k: cell.family.make_weights(
            cell.config, k), jax.random.PRNGKey(0))
        got = {n: x.shape for n, x in made.items()}
        if got != self.param_shapes:
            raise ValueError(
                "benchmark: the family's weights do not match the program's "
                f"parameters: {sorted(set(got) ^ set(self.param_shapes))} "
                f"{[n for n in got if got[n] != self.param_shapes.get(n)]}")
        step = make_train_step(self.cfg, self.st, self.opt, self.tc)

        def flat(*xs):
            s, b = jax.tree_util.tree_unflatten(self.tdef, xs)
            s = jax.tree_util.tree_map(annotate, s, seeds)
            b = {k: annotate(v, bseed) for k, v in b.items()}
            new, metrics = step(s, b)
            return (*jax.tree_util.tree_leaves(new), metrics["loss"])

        self.flat = step_wrapper(flat, self) if step_wrapper else flat
        # a plan built for a planted fault shares the program's cache key
        clear_process_plan_cache()
        self.partitioned = spmd_partition(self.flat, self.jmesh, self.mesh)
        self.runner = self.partitioned
        context = getattr(self.flat, "trace_context", None)
        if context is not None:
            def runner(*args):
                with context():
                    return self.partitioned(*args)

            self.runner = runner
        # The state is made in the layout the runner returns it in, so that
        # the first step already runs the executable of every later step
        # (the one the window drives and the comparison reads).  The plan's
        # output layouts are known once it is built, which tracing alone
        # does: nothing is lowered or compiled here.
        jax.eval_shape(self.runner, *(
            jax.ShapeDtypeStruct(x.shape, x.dtype)
            for x in jax.tree_util.tree_leaves((abstract, batch))))
        out_specs = [to_partition_spec(sh) for sh in
                     self.plan_entry().plan.out_shardings[:self.n_state]]
        self.state_shardings = jax.tree_util.tree_unflatten(
            self.state_def, [NamedSharding(self.jmesh, s) for s in out_specs])
        V = self.cfg.vocab_size
        self.pipeline = lambda seed: TokenPipeline(
            DataConfig(V, self.S, self.B, seed=seed))
        c, opt = cell.config, self.opt

        def make_state(key):
            w = cell.family.make_weights(c, key)
            params = jax.tree_util.tree_unflatten(
                self.param_def, [w[n] for n in self.param_names])
            return {"params": params, "opt": opt.init(params),
                    "step": jnp.zeros((), jnp.int32)}

        self._make_state = jax.jit(make_state,
                                   out_shardings=self.state_shardings)

        def grad_norms(opt_state):
            # Adafactor at step 1: v = g^2 + eps, vr = mean over cols
            out = {}
            for n in self.param_names:
                s = opt_state["mu"]
                for part in n.split("/"):
                    s = s[part]
                sq = (jnp.sum(s["v"]) if "v" in s else
                      jnp.sum(s["vr"]) * self.param_shapes[n][-1])
                out[n] = jnp.sqrt(sq)
            return out

        self._grad_norms = jax.jit(grad_norms)

        placed = dict(zip(self.param_names, jax.tree_util.tree_leaves(
            self.state_shardings["params"])))

        def change_norms(params, key):
            w0 = {n: jax.lax.with_sharding_constraint(x, placed[n])
                  for n, x in cell.family.make_weights(c, key).items()}
            flat_p = dict(zip(self.param_names,
                              jax.tree_util.tree_leaves(params)))
            return {n: jnp.sqrt(jnp.sum(jnp.square(flat_p[n] - w0[n])))
                    for n in self.param_names}

        self._change_norms = jax.jit(change_norms)

    # -- the program's side ---------------------------------------------------
    def batch(self, pipe, step: int):
        import jax

        b = pipe.batch_at(step)
        return jax.device_put(jax.tree_util.tree_leaves(b),
                              self.batch_sharding)

    def start(self, seed: int):
        """State from the seed, driven through the first steps by the
        runner.  Returns ``(state leaves, pipeline, first)``; ``first``
        holds the program's readings as device arrays."""
        import jax

        key = jax.random.PRNGKey(seed)
        leaves = jax.tree_util.tree_leaves(self._make_state(key))
        pipe = self.pipeline(seed)
        losses, grad_norms = [], None
        for i in range(self.first_steps):
            b = self.batch(pipe, i)
            if i == 1:
                lowered = COUNTS["lowerings"]
            out = self.runner(*leaves, *b)
            leaves, loss = list(out[:self.n_state]), out[self.n_state]
            losses.append(loss)
            if grad_norms is None:
                grad_norms = self._grad_norms(jax.tree_util.tree_unflatten(
                    self.state_def, leaves)["opt"])
        # lowerings by the steps after the first: a second executable of
        # the step, which the first step (read by the comparison) never ran
        self.relowered = (COUNTS["lowerings"] - lowered
                          if self.first_steps > 1 else 0)
        state = jax.tree_util.tree_unflatten(self.state_def, leaves)
        first = {"losses": losses, "grad_norms": grad_norms,
                 "change_norms": self._change_norms(state["params"], key)}
        return leaves, pipe, first

    def window(self, leaves, pipe, seconds: float, trace_dir=None):
        """Closed-loop steps for ``seconds``; returns the record."""
        import jax

        rec = {"input_s": [], "ticks": [], "losses": []}
        if trace_dir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        lowered = COUNTS["lowerings"]
        step = self.first_steps
        span = jax.profiler.TraceAnnotation
        with span("bench.window"):
            t0 = time.perf_counter()
            t_end = t0 + seconds
            while time.perf_counter() < t_end:
                with span("bench.batch"):
                    a = time.perf_counter()
                    b = self.batch(pipe, step)
                    rec["input_s"].append(time.perf_counter() - a)
                with span("bench.dispatch"):
                    out = self.runner(*leaves, *b)
                leaves = out[:self.n_state]
                rec["losses"].append(out[self.n_state])
                rec["ticks"].append(time.perf_counter())
                step += 1
            with span("bench.wait"):
                jax.block_until_ready(leaves)
            t1 = time.perf_counter()
        if trace_dir:
            jax.profiler.stop_trace()
        rec.update(t0=t0, t1=t1, steps=step - self.first_steps,
                   compiles=COUNTS["lowerings"] - lowered)
        return leaves, rec

    def plan_entry(self):
        (entry,) = self.partitioned.plans.values()
        return entry

    # -- the reference's side -------------------------------------------------
    def reference(self, seed: int, matmul: str = "f32"):
        import jax

        from benchmarks.chip import tokens

        c = self.cell.config
        fam = self.cell.family
        shapes = jax.eval_shape(lambda k: fam.make_weights(c, k),
                                jax.random.PRNGKey(0))
        shard = {n: ref_placement(self.devices, x.shape)
                 for n, x in shapes.items()}
        w0 = jax.jit(lambda k: fam.make_weights(c, k), out_shardings=shard)(
            jax.random.PRNGKey(seed))
        bs = ref_placement(self.devices, (self.B, self.S))
        batches = tokens.batches(self.cell.traffic, seed, self.first_steps,
                                 c["vocab_size"], bs)
        with jax.default_matmul_precision("highest"):
            return fam.reference_steps(c, self.cell.traffic["optimizer"], w0,
                                       batches, matmul)


def to_host(first) -> dict:
    import jax

    got = jax.device_get(first)
    return {"losses": [float(x) for x in got["losses"]],
            "grad_norms": {n: float(x) for n, x in got["grad_norms"].items()},
            "change_norms": {n: float(x) for n, x in
                             got["change_norms"].items()}}


def peak_bytes(devices):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    return max((p for p in peaks if p is not None), default=None)


def run_cell(cell, devices, seed: int, seconds: float, trace: bool,
             step_wrapper=None, log=print) -> dict:
    """One run of one cell; returns the result line's object."""
    import jax

    from benchmarks.chip import compare
    from benchmarks.chip import trace as trace_mod

    count_compiles()
    phases = {"backend": time.perf_counter() - T_START}
    run = TrainRun(cell, devices, step_wrapper)
    phases["runner"] = time.perf_counter() - T_START
    leaves, pipe, first = run.start(seed)
    jax.block_until_ready((leaves, first))
    phases["first_steps"] = time.perf_counter() - T_START
    setup_counts = dict(COUNTS)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        setup_s = time.perf_counter() - T_START
        leaves, rec = run.window(leaves, pipe, seconds, trace_dir)
        memory = peak_bytes(devices)
        losses = [float(x) for x in jax.device_get(rec["losses"])]
        del leaves
        summary = None
        if trace_dir:
            summary = trace_mod.summarize(*trace_mod.load_xplane(trace_dir))
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    entry = run.plan_entry()
    stats = entry.plan.stats
    window_s = rec["t1"] - rec["t0"]
    tokens = rec["steps"] * run.B * run.S
    dts = [b - a for a, b in zip(rec["ticks"], rec["ticks"][1:])]
    log(f"setup_s {setup_s!r}; window {window_s!r} s, {rec['steps']} steps, "
        f"{tokens} tokens")
    log("set-up phases, seconds from process start: " + ", ".join(
        f"{k} {v:.3f}" for k, v in phases.items()) + f"; plan build "
        f"{entry.build_s:.3f} inside the runner phase; lowerings "
        f"and compile-cache events: {setup_counts}")
    if len(dts) >= 2:
        q = statistics.quantiles(dts, n=10)
        log(f"step time (host, between dispatches) median "
            f"{statistics.median(dts)!r} s, p90 {q[-1]!r} s over "
            f"{len(dts)} intervals")
    if rec["input_s"]:
        log(f"host time inside bench.batch (batch_at, its sync, the "
            f"transfer): mean {1e3 * statistics.fmean(rec['input_s'])!r} ms "
            "per step")
    log(f"lowerings of the step after its first call: {run.relowered} in "
        f"set-up, {rec['compiles']} inside the window")
    log(f"plan: build {entry.build_s!r} s, {len(entry.plan.steps)} steps, "
        f"collectives {stats.collectives}, fallbacks {stats.fallbacks}")
    log("memory_stats peak_bytes_in_use per device (as reported, "
        "unverified): " + ", ".join(
            f"{d.id}: {(d.memory_stats() or {}).get('peak_bytes_in_use')}"
            for d in devices))
    if rec["compiles"]:
        fail(f"{rec['compiles']} lowering(s) inside the window", code=1)
    if run.relowered:
        fail(f"the step lowered {run.relowered} time(s) after its first "
             "call: the first step ran another executable than the window",
             code=1)

    program = to_host(first)
    t_ref = time.perf_counter()
    reference = run.reference(seed)
    log(f"reference: {time.perf_counter() - t_ref:.3f} s")
    flops = cell.family.step_flops(cell.config, run.B, run.S)
    del run
    values = compare.readings(program, reference)
    limits = cell.limits
    correct = compare.judge(values, limits)
    log(f"first losses: program {program['losses']}, reference "
        f"{reference['losses']}")
    log(f"left out of update_norm_gap: {compare.left_out(reference)}")

    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": memory}
    failed = sum(1 for x in losses if not math.isfinite(x))
    result = {"correct": bool(correct and not failed),
              "attempted": rec["steps"], "failed": failed}
    if trace:
        peaks = cell.peaks(d0.device_kind)
        record = {
            "plan_build_s": entry.build_s, "fallbacks": stats.fallbacks,
            "flops_per_step": flops,
            "chips": len(devices), "peak_flops": peaks["bf16_flops"],
            "steps_traced": rec["steps"],
            "trace": summary,
        }
        metrics = {}
        for m, read in cell.readers():
            v = read(record)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        result.update(metrics=metrics, device=device, breakdown={
            "device_ops": summary["top_ops"],
            "idle_gaps": summary["idle_gaps"]})
    else:
        units = {m["name"]: m["unit"] for m in cell.metrics(trace=False)}
        result.update(metrics={
            "tokens_per_s": {"value": tokens / window_s,
                             "unit": units["tokens_per_s"]},
            "setup_s": {"value": setup_s, "unit": units["setup_s"]},
        }, device=device)
    result["checks"] = {k: {"value": values[k], "limit": limits[k]}
                        for k in compare.NAMES}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    require_program()
    from benchmarks.chip.cell import Cell

    cell = Cell(ROOT, args.workload)
    devices = require_chips(cell.chips)
    enable_compile_cache()
    result = run_cell(cell, devices, args.seed, args.seconds,
                      bool(args.trace))
    for k, v in result["checks"].items():
        print(f"check {k}: {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
