"""The benchmark's tests run on the CPU, with the program under test
imported from this checkout's ``src`` and the benchmark as the package
``benchmarks.chip``."""
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[3]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import jax  # noqa: E402

from benchmarks.chip.tests.bench_tiny import make_checkout  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)


@pytest.fixture
def tiny_checkout(tmp_path):
    return make_checkout(tmp_path)
