"""Resharding (paper §4.5) — executed *inside* a shard_map region.

GSPMD always produces a valid partitioned graph; when operand shardings don't
match an op's supported cases it inserts resharding:

* AllGather   — replicate a sharded dimension,
* AllToAll    — switch which dimension a mesh axis shards,
* DynamicSlice— shard a replicated dimension (offset = f(partition id)),
* CollectivePermute — change device order (not needed here: one canonical mesh).

Which sequence of those steps to use is no longer decided greedily here: the
cost-model planner (``collective_planner.plan_reshard``) enumerates candidate
sequences, prices them with the roofline wire-byte model, and returns the
cheapest valid :class:`~repro.core.collective_planner.ReshardProgram`.  In
particular a mesh axis moving between dims lowers to a direct AllToAll at
(n-1)/n of the operand bytes instead of AllGather + DynamicSlice at (n-1)×,
and DynamicSlices run before AllGathers so gathered operands are as small as
possible.  On 3+-axis and stacked layouts the planner additionally runs a
bounded branch-and-bound over step interleavings (lattice search) with the
greedy result as the incumbent, finding e.g. AllToAll detours that park an
axis on another dim so slices can shrink it before it returns.

``reshard_local(x, cur, tgt)`` is the plan-then-execute convenience used by
the dynamic reference partitioner; the compiled-plan path
(``core/plan.py``) calls ``plan_reshard`` once at plan time, emits the result
as a first-class reshard step, and replays the program on every execution —
whether the step executes where the builder put it is then the whole-program
optimizer's business (``core/plan_opt.py``: CSE across call boundaries once
jit bodies are inlined, hoisting out of scan bodies, fusion, overlap
scheduling).  All dims are assumed evenly divisible (uneven dims are padded
to multiples beforehand, §4.1 — see sharding.pad_to_multiple).
"""
from __future__ import annotations

from typing import Tuple

from .collective_planner import execute_program, plan_reshard
from .sharding import Sharding


def reshard_local(x, cur: Sharding, tgt: Sharding):
    """Transform local shard ``x`` from sharding ``cur`` to ``tgt``.

    Runs under shard_map; uses collective ops over mesh axis names.  The
    collective sequence is chosen by the cost-model planner.
    """
    assert cur.rank == tgt.rank == x.ndim, (cur, tgt, x.shape)
    prog = plan_reshard(cur, tgt, tuple(x.shape), dtype_bytes=x.dtype.itemsize)
    return execute_program(x, prog)


def shard_shape(global_shape: Tuple[int, ...], s: Sharding) -> Tuple[int, ...]:
    return tuple(
        dim // s.num_shards(i) for i, dim in enumerate(global_shape)
    )
