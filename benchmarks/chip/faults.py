"""Faults planted under the timed path, to show that the comparison catches
them; the benchmark's own runs plant none.

Each is a ``step_wrapper(flat_step, run)`` for ``run.TrainRun``: it returns
the step that goes to ``spmd_partition`` in place of the program's.

* ``unchanged``: the step returns its state unchanged;
* ``half_batch``: the step sees the first half of the batch rows, so the
  loss and gradients are means over the rest;
* ``no_exchange``: every all-reduce of the partitioned program is left out
  (``jax.lax.psum`` returns its operand) while it is traced; on one chip
  there is no exchange to leave out;
* ``altered_update``: the answer altered where it is produced: the update
  of the embedding (the largest leaf) is applied twice.
"""
from __future__ import annotations

import contextlib

import jax


def unchanged(flat, run):
    def step(*xs):
        out = flat(*xs)
        return (*xs[:run.n_state], out[run.n_state])

    return step


def half_batch(flat, run):
    def step(*xs):
        half = run.B // 2
        return flat(*xs[:run.n_state], *(b[:half] for b in xs[run.n_state:]))

    return step


def altered_update(flat, run):
    i = run.state_names.index("params/embed/embedding")

    def step(*xs):
        out = list(flat(*xs))
        out[i] = xs[i] + 2 * (out[i] - xs[i])
        return tuple(out)

    return step


@contextlib.contextmanager
def _no_psum():
    saved = jax.lax.psum
    jax.lax.psum = lambda x, *a, **k: x
    try:
        yield
    finally:
        jax.lax.psum = saved


def no_exchange(flat, run):
    def step(*xs):
        return flat(*xs)

    step.trace_context = _no_psum
    return step


FAULTS = {f.__name__: f for f in (unchanged, half_batch, no_exchange,
                                  altered_update)}
