"""Split one cell's window by the program's own scopes and spans.

    python3 benchmarks/chip/scope_report.py --workload <name> --seed <n> \
        --seconds <s> [--out <path.json>]

Set-up is ``run.py``'s: the cell's runner, its state from the seed and its
first steps.  Then two closed-loop windows of ``--seconds`` each, the same
as ``run.py``'s: one without the profiler and one under it.  From the traced
window's device trace it reports, per window step:

* device milliseconds per scope of the program (``scopes.py``: attention,
  MLP, head, optimizer, the rest ``unscoped``), their share of the device's
  busy time, and the largest unscoped operations with their op names;
* device idle inside each of the program's ``repro.*`` host spans and the
  harness's ``bench.*`` spans;
* ``trace.py``'s numbers of the window (busy, idle, top operations);

and from the program: the plan's build seconds per phase, and
``first_call_s``.  The two windows' steps per second give what the
profiler costs while it runs.  Where the device events carry no op name,
op names come from the compiled HLO text of the step, lowered and compiled
again after the windows.  The step compiles in a compile cache of this
process's own: JAX's cache key leaves out op-name metadata, so a shared
cache can hand back an executable compiled from another commit's module,
whose op names lack the scopes.  The correctness check is ``run.py``'s;
this script makes none.  The last line of standard output is the report, which
``--out`` also writes.  Exits 2 where JAX finds no TPU.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
if sys.path and sys.path[0] and pathlib.Path(sys.path[0]).resolve() == HERE:
    sys.path.pop(0)  # this directory's trace.py would shadow the stdlib's
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def _per_step(seconds: float, steps: int) -> float:
    return 1e3 * seconds / steps


def reduce_trace(trace_dir: str, steps: int, hlo_text=None) -> dict:
    """The traced window's numbers per step; ``hlo_text()``, called only
    where a device event carries no op name, gives the step's compiled
    HLO text."""
    from benchmarks.chip import scopes, trace

    devices, spans, op_names, samples = scopes.load_program_trace(trace_dir)
    out = {"samples": [[n[:300], s] for n, s in samples]}
    if not devices:
        out["devices"] = 0  # a CPU trace holds no device plane
        out["program_spans"] = sorted({n for n, _, _ in spans
                                       if n.startswith("repro.")})
        return out
    missing = [n for n, op in op_names.items() if op is None]
    out["op_names_from"] = "trace"
    if missing and hlo_text is not None:
        hlo = scopes.hlo_op_names(hlo_text())
        for n in missing:
            op_names[n] = scopes.op_name_of(n, None, hlo)
        out["op_names_from"] = "trace and compiled HLO"
    summary = trace.summarize(*trace.load_xplane(trace_dir))
    split = scopes.scope_times(devices, spans, op_names)
    idle = scopes.idle_in_spans(devices, spans)
    scoped = sum(v for k, v in split["seconds"].items()
                 if k != scopes.UNSCOPED)
    out.update(
        devices=summary["devices"],
        window_s=summary["window_s"],
        busy_s=summary["busy_s"],
        idle_share=summary["idle_share"],
        scope_ms={k: _per_step(v, steps)
                  for k, v in split["seconds"].items()},
        scoped_share_of_busy=scoped / summary["busy_s"],
        named_share=split["named_share"],
        unscoped_top=[[n, _per_step(t, steps), op]
                      for n, t, op in split["unscoped_top"]],
        idle_ms={**{k: _per_step(v, steps) for k, v in idle.items()},
                 **{k: _per_step(v, steps)
                    for k, v in summary["idle_in_span_s"].items()}},
        top_ops=[[n, _per_step(t, steps)] for n, t in summary["top_ops"]],
        idle_gaps=summary["idle_gaps"],
    )
    return out


def report(cell, devices, seed: int, seconds: float, log=print) -> dict:
    import jax

    from benchmarks.chip import run as run_mod

    run_mod.count_compiles()
    run = run_mod.TrainRun(cell, devices)
    leaves, pipe, first = run.start(seed)
    jax.block_until_ready((leaves, first))
    setup_s = time.perf_counter() - T_START
    entry = run.plan_entry()
    windows = {}
    trace_dir = tempfile.mkdtemp(prefix="scope-trace-")
    try:
        for name, tdir in (("untraced", None), ("traced", trace_dir)):
            leaves, rec = run.window(leaves, pipe, seconds, tdir)
            windows[name] = {"steps": rec["steps"],
                             "steps_per_s": rec["steps"] / (rec["t1"] - rec["t0"]),
                             "compiles": rec["compiles"]}
            log(f"{name} window: {windows[name]}")
        steps = windows["traced"]["steps"]

        def hlo_text():
            batch = run.batch(pipe, 0)
            return entry.call.lower(*leaves, *batch).compile().as_text()

        traced = reduce_trace(trace_dir, steps, hlo_text)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    d0 = devices[0]
    return {
        "workload": cell.name, "seed": seed,
        "device": {"platform": d0.platform, "kind": d0.device_kind,
                   "count": len(devices)},
        "setup_s": setup_s,
        "plan": {"build_s": entry.build_s,
                 "phases": getattr(entry, "phases", None),
                 "first_call_s": getattr(entry, "first_call_s", None)},
        "windows": windows,
        "tracing_cost": 1.0 - (windows["traced"]["steps_per_s"]
                               / windows["untraced"]["steps_per_s"]),
        "trace": traced,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from benchmarks.chip import run as run_mod

    run_mod.require_program()
    import jax

    from benchmarks.chip.cell import Cell

    cell = Cell(ROOT, args.workload)
    devices = run_mod.require_chips(cell.chips)
    cache_dir = tempfile.mkdtemp(prefix="scope-cache-")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        result = report(cell, devices, args.seed, args.seconds,
                        log=lambda *a: print(*a, file=sys.stderr))
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)
    line = json.dumps(result, default=str)
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.out).write_text(line + "\n")
    print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
