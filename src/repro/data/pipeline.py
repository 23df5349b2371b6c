"""Deterministic, resumable data pipeline.

Synthetic LM token streams are generated counter-based (threefry on (seed, step,
position)), so `skip to step N` after a restart reproduces exactly the batches a
non-interrupted run would have seen — the property checkpoint/restart tests
assert.  A file-backed variant memory-maps a token file.  Per-host sharding:
each process materializes only its slice of the global batch.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.trace import span


@dataclasses.dataclass
class DataConfig:
    vocab_size: int
    seq_len: int
    global_batch: int
    seed: int = 0
    path: Optional[str] = None  # file-backed when set
    pattern: str = "uniform"    # uniform | arithmetic (learnable: t+1 = t+step)


class TokenPipeline:
    def __init__(self, cfg: DataConfig, process_index: int = 0, process_count: int = 1):
        self.cfg = cfg
        self.process_index = process_index
        self.process_count = process_count
        assert cfg.global_batch % process_count == 0
        self.local_batch = cfg.global_batch // process_count
        self._mm = None
        if cfg.path:
            self._mm = np.memmap(cfg.path, dtype=np.int32, mode="r")

    def batch_at(self, step: int) -> Dict[str, np.ndarray]:
        """The batch for global step ``step`` (deterministic), made inside
        the profiler span ``repro.data.batch_at``."""
        with span("repro.data.batch_at"):
            c = self.cfg
            B, S = self.local_batch, c.seq_len
            row0 = step * c.global_batch + self.process_index * B
            if self._mm is not None:
                need = B * (S + 1)
                start = (row0 * (S + 1)) % max(len(self._mm) - need, 1)
                flat = np.asarray(self._mm[start : start + need])
                toks = flat.reshape(B, S + 1)
            elif c.pattern == "arithmetic":
                # fully learnable: token[t+1] = (token[t] + stride) mod V
                rng = np.random.default_rng(c.seed + step * 1000 + self.process_index)
                start = rng.integers(0, c.vocab_size, (B, 1))
                stride = rng.integers(1, 17, (B, 1))
                toks = ((start + stride * np.arange(S + 1)) % c.vocab_size).astype(np.int32)
            else:
                key = jax.random.fold_in(jax.random.PRNGKey(c.seed), step)
                key = jax.random.fold_in(key, self.process_index)
                toks = np.asarray(
                    jax.random.randint(key, (B, S + 1), 0, c.vocab_size, jnp.int32)
                )
            return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        step = 0
        while True:
            yield self.batch_at(step)
            step += 1
