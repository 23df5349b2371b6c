"""The control: the reference in fp8, in the program's place, is not
correct, on the tiny cell with the tiny cell's limits."""
import jax
import pytest

from benchmarks.chip import compare, tokens
from benchmarks.chip.cell import Cell


def first_steps(cell, seed, matmul):
    c, fam = cell.config, cell.family
    w0 = jax.jit(lambda k: fam.make_weights(c, k))(jax.random.PRNGKey(seed))
    batches = tokens.batches(cell.traffic, seed, cell.traffic["first_steps"],
                             c["vocab_size"])
    with jax.default_matmul_precision("highest"):
        return fam.reference_steps(c, cell.traffic["optimizer"], w0, batches,
                                   matmul)


@pytest.mark.parametrize("seed", [5, 2**31 + 1])
def test_fp8_control_is_not_correct(tiny_checkout, seed):
    cell = Cell(tiny_checkout, "tiny.tiny")
    ref = first_steps(cell, seed, "f32")
    values = compare.readings(first_steps(cell, seed, "fp8"), ref)
    assert not compare.judge(values, cell.limits), values
    # and the reference against itself reads nought
    same = compare.readings(first_steps(cell, seed, "f32"), ref)
    assert compare.judge(same, cell.limits)
    assert max(same.values()) < 1e-6
