"""The benchmark's own copy of the training batches, for the reference.

The window feeds the program's ``TokenPipeline.batch_at(step)``; the
reference does not take those arrays, it makes the same batches here from
the traffic's definition: for pattern ``uniform``, ``B x (S + 1)`` token ids
drawn uniformly from ``[0, V)`` with threefry keyed by ``(seed, step,
process 0)``; the inputs are the first ``S`` columns and the labels the last
``S``.  A pipeline that feeds anything else fails the comparison.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def uniform_batch(seed: int, step: int, batch: int, seq: int, vocab: int,
                  sharding=None):
    @jax.jit
    def make(step):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
        key = jax.random.fold_in(key, 0)
        toks = jax.random.randint(key, (batch, seq + 1), 0, vocab, jnp.int32)
        return toks[:, :-1], toks[:, 1:]

    tokens, labels = make(jnp.int32(step))
    if sharding is not None:
        tokens, labels = jax.device_put((tokens, labels), sharding)
    return tokens, labels


GENERATORS = {"uniform": uniform_batch}


def batches(traffic, seed: int, steps: int, vocab: int, sharding=None):
    gen = GENERATORS[traffic["pattern"]]
    return [gen(seed, t, traffic["batch"], traffic["seq"], vocab, sharding)
            for t in range(steps)]
