"""The ``first_call_s`` reader, on runners of the program on the CPU."""
import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip.cell import _module
from benchmarks.chip.tests.bench_tiny import ROOT
from repro.core.compat import make_jax_mesh
from repro.core.partitioner import clear_process_plan_cache, spmd_partition
from repro.core.sharding import Mesh


@pytest.fixture
def reader():
    clear_process_plan_cache()
    yield _module(ROOT / "benchmarks/chip/metrics/first_call_s.py").read
    clear_process_plan_cache()


def _runner(fn):
    return spmd_partition(fn, make_jax_mesh((1, 1), ("data", "model")),
                          Mesh.create((1, 1), ("data", "model")))


def test_first_call_s_reads_the_first_concrete_call(reader):
    runner = _runner(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((8, 8))
    jax.eval_shape(runner, x)  # the harness's plan build: nothing compiles
    assert reader({}) is None
    runner(x)
    (entry,) = runner.plans.values()
    assert reader({}) == entry.first_call_s > 0.0
    runner(x)
    assert reader({}) == entry.first_call_s


def test_first_call_s_reads_nothing_where_two_plans_ran(reader):
    for fn in (jnp.sin, jnp.cos):
        _runner(fn)(jnp.ones((4,)))
    assert reader({}) is None
