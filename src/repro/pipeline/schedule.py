"""Pipeline schedule cost model (GSPMD §3.3 / JaxPP arXiv:2412.14374 terms).

The stage-stacked pipeline executes ``T = M + S − 1`` ticks for ``M``
microbatches over ``S`` stages; every tick runs all stages (vmap over the
stage dim), so ``S − 1`` ticks' worth of slots compute garbage — the bubble:

    bubble_fraction(S, M) = (S − 1) / (M + S − 1)

The compute inflation shows up *organically* in ``PlanCost`` (the tick scan's
trip-multiplied FLOPs are exactly ``(1 + bubble)`` × the useful work), and the
per-tick collectives (one boundary ppermute per shifting-buffer leaf, one
psum for output collection) are whole-program priced there too.  This module
supplies the *analytic* schedule vocabulary on top — bubble fraction, tick
count, per-tick ppermute wire bytes, per-microbatch activation memory — as a
:class:`ScheduleCost` that wraps the plan-level :class:`~repro.core.plan
.PlanCost`, for the autoshard pipeline search, the benchmark cells, and the
reports.

:class:`PipelineConfig` is the user-facing search knob
(``autoshard.solve(..., pipeline=PipelineConfig(max_stages=4))``);
:class:`PipelineDecision` is one point of the decision space (which mesh axis
carries the stage dim, how many stages, how many microbatches) — enumerated
by ``repro.autoshard.space.pipeline_decisions`` and priced jointly with the
tensor-sharding assignment.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    """Pipeline decision-variable bounds for the autoshard search.

    ``max_stages`` caps the stage count; ``num_microbatches`` pins M (or
    ``None`` to search ``microbatch_options``); ``stage_axes`` restricts
    which mesh axes may carry the stage dim (``None`` = any).  Stage counts
    are multiples of the chosen axis size (even local stage rows) that divide
    the layer count.
    """

    max_stages: int = 4
    num_microbatches: Optional[int] = None
    microbatch_options: Tuple[int, ...] = (2, 4)
    stage_axes: Optional[Tuple[str, ...]] = None

    def as_dict(self) -> Dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class PipelineDecision:
    """One point in the pipeline decision space."""

    stage_axis: str
    num_stages: int
    num_microbatches: int

    @property
    def ticks(self) -> int:
        return pipeline_ticks(self.num_stages, self.num_microbatches)

    @property
    def bubble(self) -> float:
        return bubble_fraction(self.num_stages, self.num_microbatches)

    def as_dict(self) -> Dict:
        return {
            "stage_axis": self.stage_axis,
            "num_stages": self.num_stages,
            "num_microbatches": self.num_microbatches,
            "ticks": self.ticks,
            "bubble_fraction": self.bubble,
        }


def pipeline_ticks(num_stages: int, num_microbatches: int) -> int:
    """GPipe schedule length: M + S − 1 shifting-buffer ticks."""
    return num_microbatches + num_stages - 1


def bubble_fraction(num_stages: int, num_microbatches: int) -> float:
    """Idle-slot share of the stage×tick grid: (S − 1) / (M + S − 1)."""
    return (num_stages - 1) / float(pipeline_ticks(num_stages, num_microbatches))


def plan_ppermute_bytes(plan) -> Tuple[float, int]:
    """(whole-program ppermute wire bytes, launches) of a lowered plan —
    inner jit/scan plans at trip count, fused ppermutes included."""
    from repro.core.plan_opt import _collective_step_wire_bytes

    total, launches = 0.0, 0
    for s in plan.steps:
        if s.kind == "collective" and s.op == "ppermute":
            total += _collective_step_wire_bytes(plan.mesh, s)
            launches += 1
        elif s.kind == "fused" and s.op == "fused-ppermute":
            total += getattr(s, "_wire_bytes", 0.0)
            launches += 1
        if s.inner is not None:
            b, n = plan_ppermute_bytes(s.inner)
            trips = s.call.get("trips", 1)
            total += trips * b
            launches += trips * n
    return total, launches


@dataclasses.dataclass
class ScheduleCost:
    """Analytic schedule terms around one pipelined plan's PlanCost.

    ``ppermute_bytes`` / ``ppermute_launches`` are whole-program (per-tick ×
    tick count); ``microbatch_activation_bytes`` is the shifting buffer's
    per-device live size — the memory the microbatch split buys back vs the
    full-batch activation; ``total_s`` is the plan-level objective (which
    already contains the bubble-inflated compute and the tick-multiplied
    collectives)."""

    decision: PipelineDecision
    ppermute_bytes: float
    ppermute_launches: int
    microbatch_activation_bytes: float
    plan_cost: Optional[object] = None  # PlanCost of the pipelined plan

    @property
    def bubble(self) -> float:
        return self.decision.bubble

    @property
    def total_s(self) -> float:
        return self.plan_cost.total_s if self.plan_cost is not None else 0.0

    def as_dict(self) -> Dict:
        return {
            **self.decision.as_dict(),
            "ppermute_bytes": self.ppermute_bytes,
            "ppermute_launches": self.ppermute_launches,
            "microbatch_activation_bytes": self.microbatch_activation_bytes,
            "plan_cost": (self.plan_cost.as_dict()
                          if self.plan_cost is not None else None),
        }


def schedule_cost(closed, assignment, mesh, decision: PipelineDecision,
                  state_shape=None, dtype_bytes: int = 4,
                  verify: bool = True) -> ScheduleCost:
    """Price one pipelined (jaxpr, assignment) pair: cost-only lower it and
    read the ppermute traffic off the plan, plus the analytic terms.

    ``state_shape`` (global shifting-buffer shape, leading stage dim) sizes
    the per-device microbatch activation; when omitted it is inferred as 0.
    The cost-only lowering runs the static plan verifier (unless
    ``verify=False``) — pipelined plans get the same well-formedness
    guarantees as executable ones.
    """
    from repro.core.plan import compile_plan, plan_cost
    from repro.core.propagation import propagate
    from repro.core.reshard import shard_shape
    from repro.core.sharding import Sharding

    prop = propagate(closed, mesh, in_shardings=list(assignment or []))
    plan = compile_plan(closed, prop.result(), mesh, cost_only=True,
                        verify=verify)
    pbytes, plaunches = plan_ppermute_bytes(plan)
    act = 0.0
    if state_shape is not None:
        # shifting buffer sharded on the stage axis: per-device live bytes
        s = Sharding(mesh, ((decision.stage_axis,),)
                     + ((),) * (len(state_shape) - 1))
        act = float(dtype_bytes)
        for d in shard_shape(tuple(state_shape), s):
            act *= d
    return ScheduleCost(
        decision=decision,
        ppermute_bytes=pbytes,
        ppermute_launches=plaunches,
        microbatch_activation_bytes=act,
        plan_cost=plan_cost(plan),
    )
