"""Runs of the tiny cell with the timed path broken underneath: each
planted fault of ``faults.py`` makes ``correct`` false."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest

from benchmarks.chip import faults
from benchmarks.chip import run as run_mod
from benchmarks.chip.cell import Cell
from benchmarks.chip.tests.bench_tiny import ROOT


@pytest.mark.parametrize("fault", ["unchanged", "half_batch",
                                   "altered_update"])
def test_fault_on_one_chip_is_not_correct(tiny_checkout, fault):
    cell = Cell(tiny_checkout, "tiny.tiny")
    res = run_mod.run_cell(cell, jax.devices()[:1], 7, 0.5, trace=False,
                           step_wrapper=faults.FAULTS[fault],
                           log=lambda *_: None)
    assert res["correct"] is False, res["checks"]


SCRIPT = textwrap.dedent("""
    import json, pathlib, sys, tempfile
    sys.path[:0] = [{root!r}, {src!r}]
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from benchmarks.chip.tests.bench_tiny import TINY_TRAFFIC, make_checkout
    from benchmarks.chip import faults
    from benchmarks.chip import run as run_mod
    from benchmarks.chip.cell import Cell
    from repro.core.compat import set_mesh
    from repro.train.loop import state_partition_specs

    traffic = dict(TINY_TRAFFIC, name="tiny2x2", batch=8,
                   mesh={{"data": 2, "model": 2}},
                   model={{"xent_chunk": 16, "attn_chunk": 32}})
    dest = pathlib.Path(tempfile.mkdtemp())
    make_checkout(dest, traffic=traffic, chips=4)
    cell = Cell(dest, "tiny.tiny2x2")
    out = {{}}
    for name in ("sound", "no_exchange"):
        res = run_mod.run_cell(cell, jax.devices()[:4], 2**31 + 3, 0.5,
                               trace=False, log=lambda *_: None,
                               step_wrapper=faults.FAULTS.get(name))
        out[name] = res["correct"]

    class Relaid(run_mod.TrainRun):
        # the state made in the strategy's layout, not the runner's: the
        # second step runs another executable than the first
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            with set_mesh(self.jmesh):
                specs = state_partition_specs(self.cfg, self.st, self.opt,
                                              self.tc)
            strategy = jax.tree_util.tree_map(
                lambda s: NamedSharding(self.jmesh, s), specs,
                is_leaf=lambda x: isinstance(x, P))
            made = self._make_state
            self._make_state = lambda k: jax.device_put(made(k), strategy)

    run_mod.TrainRun = Relaid
    try:
        run_mod.run_cell(cell, jax.devices()[:4], 2**31 + 3, 0.5,
                         trace=False, log=lambda *_: None)
        out["relaid_exit"] = 0
    except SystemExit as e:
        out["relaid_exit"] = e.code
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def on_a_2x2_mesh():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    script = SCRIPT.format(root=str(ROOT), src=str(ROOT / "src"))
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_exchange_left_out_on_a_2x2_mesh_is_not_correct(on_a_2x2_mesh):
    assert on_a_2x2_mesh["sound"] is True
    assert on_a_2x2_mesh["no_exchange"] is False


def test_a_step_that_lowers_again_after_its_first_call_fails(on_a_2x2_mesh):
    # the comparison reads the first step; the window must run its executable
    assert on_a_2x2_mesh["relaid_exit"] == 1
