"""Shared benchmark helpers: timing, dry-run subprocess calls, artifact IO."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(__file__)
ROOT = os.path.abspath(os.path.join(HERE, ".."))
ART = os.path.join(ROOT, "artifacts", "dryrun")
BENCH_ART = os.path.join(ROOT, "artifacts", "bench")


def time_call(fn, *args, warmup: int = 1, iters: int = 3):
    for _ in range(warmup):
        fn(*args)
    t0 = time.perf_counter()
    for _ in range(iters):
        fn(*args)
    return (time.perf_counter() - t0) / iters * 1e6  # us


def artifact(arch: str, shape: str, mesh: str = "pod16x16", tag: str = ""):
    key = f"{arch}_{shape}_{mesh}" + (f"_{tag}" if tag else "")
    path = os.path.join(ART, key + ".json")
    if os.path.exists(path):
        rec = json.load(open(path))
        if rec.get("status") == "ok":
            return rec
    return None


def dryrun_cell(arch: str, shape: str, *, strategy=None, overrides=None,
                tag: str = "", out_dir: str = None, multi_pod: bool = False,
                force: bool = False):
    """Compile one cell in a subprocess (512 fake devices) and return the
    artifact record.  Cached by tag."""
    out_dir = out_dir or BENCH_ART
    os.makedirs(out_dir, exist_ok=True)
    mesh = "pod2x16x16" if multi_pod else "pod16x16"
    key = f"{arch}_{shape}_{mesh}" + (f"_{tag}" if tag else "")
    path = os.path.join(out_dir, key + ".json")
    if not force and os.path.exists(path):
        rec = json.load(open(path))
        if rec.get("status") in ("ok", "skipped"):
            return rec
    code = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import json
from repro.launch.dryrun import run_cell
rec = run_cell({arch!r}, {shape!r}, multi_pod={multi_pod!r}, out_dir={out_dir!r},
               strategy={strategy!r}, cfg_overrides={overrides!r}, tag={tag!r},
               verbose=False)
print("STATUS", rec["status"])
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"  # fake-device dry run; never claims a chip
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=3000)
    if proc.returncode != 0:
        raise RuntimeError(f"dryrun {key} failed:\n{proc.stdout[-1500:]}\n{proc.stderr[-1500:]}")
    return json.load(open(path))


def roofline_row(rec):
    from repro.analysis.roofline import terms_from_artifact

    t = terms_from_artifact(rec)
    return t
