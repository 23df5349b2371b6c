"""Autoshard entry points: annotation-free sharding for jaxprs and registry
configs.

Two front doors:

* ``spmd_partition(fn, jmesh, mesh, autoshard=AutoshardConfig(...))``
  (``repro.core.partitioner``) — the traced jaxpr's input shardings are
  searched instead of read from ``annotate`` seeds; the assignment is cached
  process-wide by jaxpr digest + mesh + config.
* :func:`solve` — search a **model-registry config**: traces the family's
  ``loss_fn`` on a reduced config with *zero* ``Strategy.constrain``
  annotations (no mesh context active while tracing, so every constraint is
  a no-op), searches the input/parameter assignment, and compares against
  the hand-annotated baseline (the config's default Table-1 ``Strategy``
  applied to the same invars).

Assignments serialize to JSON (:meth:`AutoshardResult.to_json` /
:func:`result_from_json`) for reproducibility: the dump pins the mesh shape
and axis names, the per-invar dims_mapping (or null = left to propagation),
the search config, and both modeled costs.
"""
from __future__ import annotations

import dataclasses
import json
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.sharding import Mesh, Sharding, replicated

from .evaluate import Evaluation, Evaluator
from .search import SearchResult, search
from .space import MaybeSharding


@dataclasses.dataclass(frozen=True)
class AutoshardConfig:
    """Search knobs (all deterministic under ``seed``).

    ``budget_bytes`` is the per-device live-memory budget (params + peak
    activations under the plan-level memory model); ``None`` disables the
    constraint.  ``top_n`` bounds how many (largest) inputs are searched —
    the rest are left to propagation.
    """

    budget_bytes: Optional[float] = None
    top_n: int = 6
    beam_width: int = 4
    sa_steps: int = 16
    seed: int = 0
    max_candidates: int = 16
    optimize: bool = True  # run plan_opt passes inside cost-only scoring
    # optional memory *term* (not the hard budget): overshoot above
    # ``soft_budget_bytes`` is priced into the objective at ``mem_weight``
    # (PlanCost.mem_s) so tied assignments rank by live memory.  Off by
    # default — zero weight leaves every existing score bit-identical.
    mem_weight: float = 0.0
    soft_budget_bytes: Optional[float] = None
    # calibrated roofline constants (repro.analysis.roofline.RooflineParams):
    # every cost-only lowering the search performs is priced with them, so
    # the objective ranks candidates by *this machine's* modeled seconds.
    # None = module defaults, scores bit-identical to an unprofiled search.
    # (Frozen-dataclass-in-frozen-dataclass: cache_key stays hashable.)
    profile: Optional["RooflineParams"] = None

    def cache_key(self) -> tuple:
        return dataclasses.astuple(self)

    def as_dict(self) -> Dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class AutoshardResult:
    """A searched assignment plus its modeled cost context."""

    mesh: Mesh
    assignment: List[MaybeSharding]  # one per jaxpr invar; None = inferred
    evaluation: Evaluation
    config: AutoshardConfig
    evals: int = 0
    searched_invars: Tuple[int, ...] = ()
    baseline: Optional[Evaluation] = None
    arch: str = ""
    # pipeline search outcome: None for pure-tensor assignments, else the
    # chosen decision + schedule terms (repro.pipeline ScheduleCost dict)
    pipeline: Optional[Dict] = None
    # True when the search was warm-started from a prior assignment and the
    # warm point was feasible (elastic recovery path — see launch/elastic.py)
    warm_started: bool = False

    @property
    def cost(self):
        return self.evaluation.cost

    @property
    def baseline_cost(self):
        return self.baseline.cost if self.baseline is not None else None

    @property
    def ratio_vs_baseline(self) -> float:
        """Searched / hand-annotated modeled seconds (≤ 1.0 is the contract
        when the baseline itself was scored as a search point)."""
        if self.baseline is None or not self.baseline.feasible:
            return 0.0
        base = self.baseline.score
        return self.evaluation.score / base if base else 1.0

    # -- JSON round trip ----------------------------------------------------
    def to_json(self) -> Dict:
        return {
            "version": 1,
            "arch": self.arch,
            "mesh": {
                "shape": list(self.mesh.shape),
                "axes": list(self.mesh.axis_names),
            },
            "assignment": [
                None if s is None else [list(axes) for axes in s.dims_mapping]
                for s in self.assignment
            ],
            "config": self.config.as_dict(),
            "evals": self.evals,
            "searched_invars": list(self.searched_invars),
            "cost": self.cost.as_dict() if self.cost is not None else None,
            "baseline_cost": (
                self.baseline_cost.as_dict()
                if self.baseline_cost is not None else None
            ),
            "pipeline": dict(self.pipeline) if self.pipeline else None,
            "warm_started": self.warm_started,
        }

    def dump(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_json(), f, indent=1)
        return path


def assignment_from_json(rec: Dict) -> Tuple[Mesh, List[MaybeSharding]]:
    """Rebuild (mesh, assignment) from a :meth:`AutoshardResult.to_json`
    record.  The mesh is reconstructed with row-major device order
    (``Mesh.create``) — dumps of meshes with a custom device permutation
    reshard identically but place shards on different physical devices.
    """
    m = rec["mesh"]
    mesh = Mesh.create(tuple(m["shape"]), tuple(m["axes"]))
    assignment: List[MaybeSharding] = []
    for ent in rec["assignment"]:
        if ent is None:
            assignment.append(None)
        else:
            assignment.append(
                Sharding(mesh, tuple(tuple(axes) for axes in ent))
            )
    return mesh, assignment


def load(path: str) -> Tuple[Mesh, List[MaybeSharding]]:
    with open(path) as f:
        return assignment_from_json(json.load(f))


def remap_assignment(assignment: Sequence[MaybeSharding], mesh: Mesh,
                     shapes: Sequence[Sequence[int]]) -> List[MaybeSharding]:
    """Re-express a (possibly foreign-mesh) assignment on ``mesh`` by name:
    axes absent from the new mesh, reused, or no longer dividing the dim are
    dropped (→ propagation handles them).  This is how a prior solve's JSON
    dump becomes a warm start after an elastic mesh shrink/regrow."""
    from repro.core.sharding import project_dims_mapping

    out: List[MaybeSharding] = []
    for s, shape in zip(assignment, shapes):
        if s is None:
            out.append(None)
        else:
            out.append(project_dims_mapping(mesh, s.dims_mapping, tuple(shape)))
    out += [None] * (len(shapes) - len(out))
    return out


def restrict_assignment(assignment: Sequence[MaybeSharding], mesh: Mesh,
                        shapes: Sequence[Sequence[int]],
                        keep_axes: Sequence[str] = ("data",),
                        ) -> List[MaybeSharding]:
    """Degrade an assignment to only ``keep_axes`` (default: data-parallel
    only) — the graceful-fallback layout when a warm re-solve is infeasible
    under the shrunk mesh's memory budget."""
    from repro.core.sharding import project_dims_mapping

    keep = set(keep_axes)
    out: List[MaybeSharding] = []
    for s, shape in zip(assignment, shapes):
        if s is None:
            out.append(None)
            continue
        dm = tuple(tuple(a for a in axes if a in keep)
                   for axes in s.dims_mapping)
        out.append(project_dims_mapping(mesh, dm, tuple(shape)))
    out += [None] * (len(shapes) - len(out))
    return out


def expand_assignment(assignment: Sequence[MaybeSharding], mesh: Mesh,
                      shapes: Sequence[Sequence[int]],
                      ) -> List[MaybeSharding]:
    """Lift a smaller-mesh assignment onto a *grown* ``mesh`` — the regrow
    counterpart of :func:`restrict_assignment`.

    Projection by name (:func:`remap_assignment`) keeps every axis that still
    divides, but an assignment that was shrunk or DP-degraded has *lost*
    structure the grown mesh could use: mesh axes it no longer references.
    This pass re-adds them greedily — for each tensor, each unused mesh axis
    of size > 1 is appended to the largest dim where divisibility holds — so
    a post-regrow warm start proposes model parallelism again instead of
    replicating the returned devices.  The search then refines from it
    (warm-started: no greedy sweep, strictly fewer evals than cold)."""
    out = remap_assignment(assignment, mesh, shapes)
    for i, (s, shape) in enumerate(zip(out, shapes)):
        if s is None:
            continue
        shape = tuple(shape)
        used = set(s.sharded_axes)
        free = [a for a in mesh.axis_names
                if a not in used and mesh.axis_size(a) > 1]
        if not free:
            continue
        dm = [list(axes) for axes in s.dims_mapping]
        for a in free:
            best = None
            for d in sorted(range(len(shape)), key=lambda d: -shape[d]):
                n = int(np.prod([mesh.axis_size(x) for x in dm[d]] or [1]))
                if shape[d] % (n * mesh.axis_size(a)) == 0:
                    best = d
                    break
            if best is not None:
                dm[best].append(a)
        out[i] = Sharding(mesh, tuple(tuple(x) for x in dm))
    return out


# ---------------------------------------------------------------------------------
# jaxpr-level solve + the process-level assignment cache
# ---------------------------------------------------------------------------------


def solve_problem(closed, mesh: Mesh,
                  config: AutoshardConfig = AutoshardConfig(),
                  baseline: Optional[Sequence[MaybeSharding]] = None,
                  arch: str = "",
                  warm_start: Optional[Sequence[MaybeSharding]] = None,
                  ) -> AutoshardResult:
    """Search one traced (closed) jaxpr, optionally against a hand-annotated
    ``baseline`` assignment scored as an extra search point — the returned
    result never costs more than the baseline (it is a valid point in the
    searched space).  This is the shared core of :func:`solve` (registry
    configs) and :func:`solve_jaxpr` (bare jaxprs).

    ``warm_start`` (an assignment on ``mesh``, typically a prior result's
    dump remapped via :func:`remap_assignment`) seeds the search: when the
    warm point is feasible the greedy sweep is skipped entirely, so a warm
    solve performs strictly fewer cost lowerings than a cold one."""
    from repro.obs import metrics as obs_metrics

    ev = Evaluator(closed, mesh, budget_bytes=config.budget_bytes,
                   optimize=config.optimize, mem_weight=config.mem_weight,
                   soft_budget_bytes=config.soft_budget_bytes,
                   profile=config.profile)
    t0 = time.perf_counter()
    base_ev = ev(list(baseline)) if baseline is not None else None
    res = search(
        ev, mesh,
        top_n=config.top_n, beam_width=config.beam_width,
        sa_steps=config.sa_steps, seed=config.seed,
        max_candidates=config.max_candidates,
        init_assignment=warm_start,
    )
    obs_metrics.inc("autoshard.solves")
    obs_metrics.observe("autoshard.search_ms",
                        (time.perf_counter() - t0) * 1e3)
    assignment, final = res.assignment, res.evaluation
    if base_ev is not None and base_ev.score < final.score:
        assignment, final = list(baseline), base_ev
    return AutoshardResult(
        mesh=mesh, assignment=assignment, evaluation=final, config=config,
        evals=ev.lowerings, searched_invars=res.searched_invars,
        baseline=base_ev, arch=arch, warm_started=res.warm_used,
    )


def solve_jaxpr(closed, mesh: Mesh,
                config: AutoshardConfig = AutoshardConfig()) -> AutoshardResult:
    """Search the input-sharding assignment of one traced (closed) jaxpr."""
    return solve_problem(closed, mesh, config)


_ASSIGNMENT_CACHE: Dict[tuple, AutoshardResult] = {}
_ASSIGNMENT_LOCK = threading.Lock()


def solve_jaxpr_cached(closed, mesh: Mesh,
                       config: AutoshardConfig) -> AutoshardResult:
    """Process-level cache front of :func:`solve_jaxpr`, keyed like the plan
    cache (jaxpr content digest + mesh + config) so repeated
    ``spmd_partition`` call sites pay for the search once."""
    from repro.core.partitioner import _jaxpr_digest

    key = (_jaxpr_digest(closed), mesh.structural_key(), config.cache_key())
    with _ASSIGNMENT_LOCK:
        hit = _ASSIGNMENT_CACHE.get(key)
    if hit is not None:
        return hit
    res = solve_jaxpr(closed, mesh, config)
    with _ASSIGNMENT_LOCK:
        _ASSIGNMENT_CACHE[key] = res
    return res


def clear_assignment_cache() -> None:
    with _ASSIGNMENT_LOCK:
        _ASSIGNMENT_CACHE.clear()


# ---------------------------------------------------------------------------------
# registry-level solve (annotation-free model sharding)
# ---------------------------------------------------------------------------------


def sharding_from_spec(mesh: Mesh, spec, shape: Sequence[int]) -> Sharding:
    """PartitionSpec → Sharding, dropping axes absent from ``mesh`` (e.g.
    "pod" on a single-pod mesh), already-used axes, and axes that do not
    divide the dim (§4.1 fallback) — mirrors ``configs.base
    .filter_spec_by_shape`` but lands on the reference Sharding type."""
    shape = tuple(int(s) for s in shape)
    if spec is None:
        return replicated(mesh, len(shape))
    entries = list(spec) + [None] * (len(shape) - len(spec))
    dm: List[Tuple[str, ...]] = []
    used: set = set()
    for i, e in enumerate(entries[:len(shape)]):
        axes = () if e is None else ((e,) if isinstance(e, str) else tuple(e))
        kept: List[str] = []
        n = 1
        for a in axes:
            if a in mesh.axis_names and a not in used \
                    and shape[i] % (n * mesh.axis_size(a)) == 0:
                kept.append(a)
                used.add(a)
                n *= mesh.axis_size(a)
        dm.append(tuple(kept))
    return Sharding(mesh, tuple(dm))


def registry_problem(arch: str, mesh: Mesh, batch: int = 8, seq: int = 32,
                     reduce_k: int = 16):
    """Trace one registry config's loss annotation-free and derive the
    hand-annotated baseline assignment from its default Strategy.

    Returns ``(closed_jaxpr, baseline_assignment)``.  The model is reduced
    (``launch.train.reduced_config``) so each cost-only lowering stays in the
    tens of milliseconds; sharding decisions transfer because the jaxpr
    structure (per-layer scan body) is the same as the full config's.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.configs.base import get_strategy
    from repro.configs.registry import default_strategy, get_config
    from repro.core.compat import trace_for
    from repro.launch.train import reduced_config
    from repro.models import api as model_api
    from repro.models.layers import tree_shapes, tree_specs

    cfg = reduced_config(get_config(arch), reduce_k).with_(
        attn_chunk=16, remat="none"
    )
    st = get_strategy(default_strategy(arch))
    tree = model_api.param_tree(cfg, st)
    shapes = tree_shapes(tree)
    batch_in = {
        "tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
        "labels": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
    }
    if cfg.family == "vlm":
        batch_in["patches"] = jax.ShapeDtypeStruct(
            (batch, cfg.num_prefix_tokens, cfg.d_model), jnp.bfloat16
        )
    if cfg.family == "encdec":
        batch_in["frames"] = jax.ShapeDtypeStruct(
            (batch, max(seq // 2, 16), cfg.d_model), jnp.bfloat16
        )
    closed = trace_for(
        mesh, lambda p, b: model_api.loss_fn(cfg, st, p, b), shapes, batch_in)
    # hand-annotated baseline: the Strategy's Table-1 specs on the same invars
    batch_specs = {k: P(("data",)) for k in batch_in}
    spec_leaves = jax.tree_util.tree_leaves(
        (tree_specs(tree), batch_specs),
        is_leaf=lambda x: x is None or isinstance(x, P),
    )
    assert len(spec_leaves) == len(closed.jaxpr.invars), (
        len(spec_leaves), len(closed.jaxpr.invars)
    )
    baseline = [
        sharding_from_spec(mesh, s, tuple(v.aval.shape))
        for s, v in zip(spec_leaves, closed.jaxpr.invars)
    ]
    return closed, baseline


def registry_pipeline_problem(arch: str, mesh: Mesh, decision,
                              batch: int = 8, seq: int = 32,
                              reduce_k: int = 16):
    """Trace one registry config's loss in §3.3 stage-stacked pipelined form
    (``repro.pipeline.stages.pipelined_loss_fn`` under ``decision``) and
    derive the pipelined hand-annotated baseline: stacked-layer leaves get
    the stage axis on their leading dim, then the Table-1 spec on the body
    dims (axes the stage dim already uses are dropped); every other invar
    keeps its unpipelined Table-1 spec.

    Returns ``(closed_jaxpr, baseline_assignment, state_shape)`` —
    ``state_shape`` is the global shifting-buffer shape for the schedule
    cost model's activation-memory term.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.configs.base import get_strategy
    from repro.configs.registry import default_strategy, get_config
    from repro.core.compat import trace_for
    from repro.launch.train import reduced_config
    from repro.models import api as model_api
    from repro.models.layers import is_param, tree_shapes, tree_specs
    from repro.pipeline.stages import pipelined_loss_fn

    cfg = reduced_config(get_config(arch), reduce_k).with_(
        attn_chunk=16, remat="none"
    )
    if cfg.num_layers % decision.num_stages:
        raise ValueError(
            f"{arch}: {cfg.num_layers} layers not divisible into "
            f"{decision.num_stages} stages"
        )
    st = get_strategy(default_strategy(arch))
    if model_api.pipeline_boundary(cfg, st) is None:
        raise ValueError(f"{arch}: no stackable-layer boundary")
    tree = model_api.param_tree(cfg, st)
    S = decision.num_stages

    def stage_stack_decl(p):
        # (L, ...) declaration -> (S, L/S, ...); specs gain the stage axis on
        # dim 0 (the leading None came from models.layers.stacked)
        L = p["shape"][0]
        spec = p["spec"]
        entries = tuple(spec) if spec is not None else (None,)
        return {
            **p,
            "shape": (S, L // S) + tuple(p["shape"][1:]),
            "spec": P(*((decision.stage_axis,) + entries)),
        }

    tree["layers"] = jax.tree_util.tree_map(
        stage_stack_decl, tree["layers"], is_leaf=is_param
    )
    shapes = tree_shapes(tree)
    batch_in = {
        "tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
        "labels": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
    }
    closed = trace_for(
        mesh, lambda p, b: pipelined_loss_fn(cfg, st, p, b, decision, mesh),
        shapes, batch_in)
    batch_specs = {k: P(("data",)) for k in batch_in}
    spec_leaves = jax.tree_util.tree_leaves(
        (tree_specs(tree), batch_specs),
        is_leaf=lambda x: x is None or isinstance(x, P),
    )
    assert len(spec_leaves) == len(closed.jaxpr.invars), (
        len(spec_leaves), len(closed.jaxpr.invars)
    )
    baseline = [
        sharding_from_spec(mesh, s, tuple(v.aval.shape))
        for s, v in zip(spec_leaves, closed.jaxpr.invars)
    ]
    mb = batch // decision.num_microbatches
    state_shape = (S, mb, seq, cfg.d_model)
    return closed, baseline, state_shape


def solve(arch: str, mesh: Optional[Mesh] = None,
          config: AutoshardConfig = AutoshardConfig(),
          batch: int = 8, seq: int = 32, reduce_k: int = 16,
          pipeline=None, warm_start=None) -> AutoshardResult:
    """Annotation-free sharding for a registry config on ``mesh``.

    Searches the input/parameter assignment for the (reduced) config's loss
    step, scores the hand-annotated Table-1 baseline as an extra search
    point, and returns the winner — by construction the searched assignment's
    modeled cost never exceeds the baseline's.

    With ``pipeline`` (a :class:`repro.pipeline.PipelineConfig`) the decision
    space widens to §3.3 stage-stacked pipelining: every (stage axis, stage
    count, microbatch count) point is rewritten via
    ``repro.pipeline.stages.pipelined_loss_fn`` and searched *jointly* with
    tensor sharding over the remaining axes; the cheapest feasible point —
    pipelined or pure-tensor — wins (a pipelined point also wins exact ties,
    it strictly reduces live activation memory).  The chosen decision and its
    schedule terms land in ``result.pipeline``.
    """
    mesh = mesh if mesh is not None else Mesh.create((2, 4), ("data", "model"))
    closed, baseline = registry_problem(arch, mesh, batch, seq, reduce_k)
    if warm_start is not None:
        # a prior-mesh assignment (e.g. ``load(dump_path)[1]``): remap by name
        shapes = [tuple(v.aval.shape) for v in closed.jaxpr.invars]
        warm_start = remap_assignment(warm_start, mesh, shapes)
    best = solve_problem(closed, mesh, config, baseline=baseline, arch=arch,
                         warm_start=warm_start)
    if pipeline is None:
        return best
    from repro.configs.registry import get_config
    from repro.launch.train import reduced_config
    from repro.pipeline.schedule import schedule_cost

    from .space import pipeline_decisions

    cfg = reduced_config(get_config(arch), reduce_k)
    for dec in pipeline_decisions(mesh, cfg.num_layers, batch, pipeline):
        try:
            closed_p, baseline_p, state_shape = registry_pipeline_problem(
                arch, mesh, dec, batch, seq, reduce_k
            )
        except ValueError:
            continue
        res = solve_problem(closed_p, mesh, config, baseline=baseline_p,
                            arch=arch)
        if not res.evaluation.feasible:
            continue
        if res.evaluation.score <= best.evaluation.score:
            sched = schedule_cost(closed_p, res.assignment, mesh, dec,
                                  state_shape=state_shape)
            res.pipeline = sched.as_dict()
            best = res
    return best
