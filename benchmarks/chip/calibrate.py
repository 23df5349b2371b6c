"""Readings that the limits of a cell's comparison are set from.

    python3 benchmarks/chip/calibrate.py --workload <name> \
        --seeds 101,102,... --control-seeds 201,202,203 \
        --faults half_batch,altered_update --fault-seeds 301,302,303 \
        [--out chiprun_out/calibrate.<name>.jsonl]

In one process, at the cell's own sizes, on the chips the cell asks for:
the program's first steps on every seed of ``--seeds`` against the float32
reference; the control (the reference with its matmuls in fp8, in the
program's place) on ``--control-seeds``; and each planted fault of
``faults.py`` on ``--fault-seeds``.  Each reading is a JSON line, printed
and appended to ``--out``.  No measured window: training's readings need
none.  The benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import json
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


def _seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def readings_for(cell, devices, seeds, control_seeds=(), faults=(),
                 fault_seeds=(), emit=print):
    """Yield ``{"kind", "seed", "values", ...}`` for each reading."""
    import jax

    from benchmarks.chip import compare
    from benchmarks.chip import faults as faults_mod
    from benchmarks.chip.run import TrainRun, to_host

    refs = {}

    def reference(run, seed):
        if seed not in refs:
            refs[seed] = run.reference(seed)
        return refs[seed]

    def program(run, seed):
        leaves, _, first = run.start(seed)
        del leaves
        return to_host(first)

    run = TrainRun(cell, devices)
    for seed in seeds:
        t0 = time.perf_counter()
        got = program(run, seed)
        values = compare.readings(got, reference(run, seed))
        emit({"kind": "program", "seed": seed, "values": values,
              "losses": got["losses"], "relowered": run.relowered,
              "left_out": compare.left_out(refs[seed]),
              "seconds": time.perf_counter() - t0})
    for seed in control_seeds:
        t0 = time.perf_counter()
        want = reference(run, seed)
        with jax.default_matmul_precision("highest"):
            got = run.reference(seed, matmul="fp8")
        emit({"kind": "control", "seed": seed,
              "values": compare.readings(got, want),
              "seconds": time.perf_counter() - t0})
    del run
    for name in faults:
        run = TrainRun(cell, devices, faults_mod.FAULTS[name])
        for seed in fault_seeds:
            t0 = time.perf_counter()
            got = program(run, seed)
            emit({"kind": f"fault:{name}", "seed": seed,
                  "values": compare.readings(got, reference(run, seed)),
                  "seconds": time.perf_counter() - t0})
        del run
        jax.clear_caches()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, default=[])
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", type=_seeds, default=[])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from benchmarks.chip import run as run_mod
    from benchmarks.chip.cell import Cell

    run_mod.require_program()
    cell = Cell(ROOT, args.workload)
    devices = run_mod.require_chips(cell.chips)
    run_mod.enable_compile_cache()
    run_mod.count_compiles()
    out = open(args.out, "a") if args.out else None

    def emit(rec):
        rec = {"workload": args.workload, **rec}
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    readings_for(cell, devices, args.seeds, args.control_seeds,
                 [f for f in args.faults.split(",") if f], args.fault_seeds,
                 emit)
    return 0


if __name__ == "__main__":
    sys.exit(main())
