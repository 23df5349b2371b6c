"""Whole-program plan optimizer: passes over a lowered ``PartitionPlan``.

PR 1 made each reshard *locally* cost-optimal (``collective_planner``); this
module is the layer that optimizes the *whole* partitioned program before it
is jitted — the plan-level analogue of GSPMD's CollectivePermute/AllToAll
compiler optimizations and of the grouped/bucketed collectives production
partitioners emit.  Since PR 4 the pipeline is *whole-program*: trivial
``jit`` call boundaries are dissolved (PartIR-style whole-program lowering)
and loop-invariant reshards leave ``scan`` bodies, so every later pass prices
and rewrites one flat step list.  ``compile_plan`` runs
:func:`optimize_plan` by default.

Passes (in pipeline order):

1. **jit inlining** (:func:`inline_jit`) — splices a trivial ``jit`` step's
   body (no nested control flow, ≤ ``INLINE_MAX_STEPS`` steps) into the outer
   step list with :class:`~repro.core.plan.ProxyVar` renaming, so
   cross-boundary reshards and collectives become visible to every later
   pass (two bodies gathering the same param CSE into one gather; their
   psums can share a fusion bucket).
2. **scan-invariant hoisting** (:func:`hoist_scan_invariants`) — a reshard of
   a loop-invariant scan input (a scan *const* whose only body reader is the
   reshard) moves out of the body into the outer plan, executing once instead
   of once per iteration; the body reads the pre-resharded value.
3. **reshard CSE** (:func:`reshard_cse`) — memoizes identical
   ``(source value, target dims_mapping)`` reshards across consumers,
   rewiring later readers to the first result.  Duplicates whose result is a
   jaxpr output become free aliases.
4. **dead-reshard elimination** (:func:`dead_reshard_elim`) — drops reshard
   steps whose result no step (and no jaxpr output) ever reads, iterating
   backwards so chains of dead reshards cascade.
5. **output-alias sinking** (:func:`sink_output_aliases`) — free aliases read
   only by the output epilogue move to the plan tail so they stop pinning
   fusion buckets (pure reordering).
6. **collective fusion / bucketing** (:func:`fuse_collectives`) — coalesces
   same-key collectives on independent values into a single launch over a
   flattened, concatenated buffer: trailing AllReduces (psum/pmax/pmin split
   out of einsum/reduce lowering), single-AllGather reshard steps, and
   CollectivePermutes with identical (axis, permutation) — the §3.3 pipeline
   shift emits one ppermute per shifting-buffer leaf per tick, and leaves of
   the same tick share a launch.  ppermute enters as a first-class
   ``collective`` step at lowering time (inside the pipeline scan body), so
   the ordering invariants below apply to it unchanged: it reaches fusion as
   ordinary bucketable work and the overlap scheduler afterwards prices it on
   the interconnect resource like any other wire step.  The
   bucket size is capped by the roofline-priced threshold
   (:func:`repro.analysis.roofline.fusion_bucket_bytes`): fusing trades one
   collective launch per member for an extra HBM round-trip of the bucket, so
   it only pays while the bucket is small enough that launch overhead
   dominates.  Members sink *down* to the last member's position, which is
   legal exactly when no intervening step reads an earlier member's result —
   enforced during the scan.
7. **overlap scheduling** (:func:`schedule_overlap`) — list-schedules the
   final step list onto a two-resource (compute, interconnect) machine,
   reordering dataflow-independent steps so collectives issue as early as
   their inputs allow and compute fills the wire time.  Slot times use the
   max-of-terms roofline (:func:`repro.analysis.roofline.overlap_time_s`):
   ``max(compute_s, comm_s)`` plus the unhidden sliver of the smaller term.
   The modeled makespan, the serial reference, and their ratio land in
   ``plan.opt_report.overlap``.

Pass-ordering invariants
------------------------
* Inlining must run **first**: every later pass only sees what is in the
  flat step list, and inlining is what puts inner-body collectives there.
  Hoisting runs immediately after so lifted reshards are CSE candidates
  against outer reshards of the same value.
* CSE must run **before** DCE: rewiring consumers is what orphans duplicate
  reshards (and annotate-created reshards of unused values) for DCE to drop.
* Alias sinking must run **after** CSE (which creates the output aliases) and
  **before** fusion (whose bucketing it unblocks).
* Fusion must run after every rewrite pass: it consumes the final dataflow;
  CSE/DCE change step adjacency and read-sets, and no other pass understands
  ``fused`` steps.
* Scheduling must run **last**: it permutes the final step list (pure
  reordering — zero bytes or launches change) and any later rewrite would
  invalidate the modeled makespan recorded in the report.
* Every pass must preserve: SSA (each env key written exactly once), write-
  before-read order, the set of jaxpr-output writes, and ``plan.stats``
  consistency (use ``PlanStats.remove_program`` when deleting a reshard).
* Passes mutate ``plan.steps`` in place so inner plans captured by
  jit/scan closures see the optimized list; :func:`hoist_scan_invariants`
  relies on the same aliasing in the other direction when it edits a scan
  body's ``inner.steps``.

Verifier contract (``core/plan_verify.py``)
-------------------------------------------
Every plan leaving ``compile_plan`` is re-checked by the static plan
verifier (on by default; ``verify=False`` disables).  A new pass
therefore does not get to *assume* it preserved the invariants above — the
verifier re-derives them from the final step list and
raises :class:`~repro.core.plan_verify.PlanVerifyError` on the first plan
that breaks one:

* **dataflow**: every read was written earlier (or is a plan input/const),
  each key written exactly once (SSA), every ``out_key`` produced;
* **specs**: reshard programs re-simulated src→dst with matching cost,
  collective axes exist in the mesh, ppermute perms are permutations,
  layout chains land on the recorded ``out_shardings``;
* **accounting**: non-negative flops/wbytes/transient_bytes, ``plan.stats``
  counters matching the step list, ``opt_report.wire_bytes_after`` and
  ``plan.peak_bytes`` matching an independent recomputation.

So a pass that deletes a reshard must call ``PlanStats.remove_program``, a
pass that adds/fuses collectives must keep ``plan.stats`` and the
whole-program byte totals consistent, and a pass that reorders steps must
preserve write-before-read — or ``compile_plan`` will refuse the plan.
Mutation coverage for the verifier itself lives in
``tests/test_plan_verify.py``; when writing a new pass, run those tests
plus the plan/optimizer suites before trusting a green bench run.

Every pass reports its savings; :func:`optimize_plan` attaches an
:class:`OptReport` (whole-program bytes and collective-launch counts
before/after — inner jit/scan plans priced at trip count via
:func:`whole_wire_bytes` / :func:`whole_collective_launches` — plus per-pass
detail and the overlap-schedule model) to the plan for the benchmark layer
(``BENCH_plan.json``).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np
from jax import core, lax
from jax.extend import core as excore

from repro.analysis.roofline import (
    COLLECTIVE_LAUNCH_S, ICI_BW, PEAK_FLOPS, RooflineParams,
    collective_wire_bytes, fusion_bucket_bytes, overlap_time_s,
)

from .plan import (
    PartitionPlan, PlanStep, ProxyVar, _alias_run, _read, _write,
)

__all__ = [
    "OptReport", "PassReport", "optimize_plan",
    "inline_jit", "hoist_scan_invariants",
    "reshard_cse", "dead_reshard_elim", "sink_output_aliases",
    "fuse_collectives", "schedule_overlap",
    "whole_wire_bytes", "whole_collective_launches",
    "step_features", "step_class", "modeled_timeline",
]


def _plan_params(plan: PartitionPlan) -> Optional[RooflineParams]:
    """The calibrated machine profile attached at compile time (or None for
    the default constants).  Every pricing site in this module resolves the
    SAME params through here, so the overlap schedule, the modeled timeline,
    and the pass savings accounting can never disagree about the machine."""
    return getattr(plan, "params", None)


def _launch_s(plan: PartitionPlan) -> float:
    p = _plan_params(plan)
    return p.collective_launch_s if p is not None else COLLECTIVE_LAUNCH_S

# Inlining cap: a jit body longer than this stays a call step.  The point of
# the bound is compile time, not correctness — splicing is O(steps), but every
# spliced step re-enters CSE/fusion/scheduling, and giant bodies (full model
# layers) rarely share cross-boundary reshards worth the pass time.
INLINE_MAX_STEPS = 64


# ---------------------------------------------------------------------------------
# reporting
# ---------------------------------------------------------------------------------


@dataclasses.dataclass
class PassReport:
    name: str
    removed_steps: int = 0
    wire_bytes_saved: float = 0.0
    fused_buckets: int = 0
    fused_members: int = 0
    launch_s_saved: float = 0.0
    inlined_bodies: int = 0  # inline-jit only
    hoisted_reshards: int = 0  # scan-hoist only
    moved_steps: int = 0  # overlap-schedule only
    overlap_ratio: float = 1.0  # overlap-schedule only: makespan / serial
    detail: Dict = dataclasses.field(default_factory=dict)

    def as_dict(self) -> Dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class OptReport:
    """Before/after accounting for one run of the pass pipeline.

    Byte/launch counts are *whole-program*: inner jit/scan plans contribute
    at trip count (:func:`whole_wire_bytes`), so inlining a body or hoisting
    a per-iteration reshard shows up as a delta instead of moving cost in and
    out of visibility.  ``overlap`` carries the overlap scheduler's model:
    total compute/comm seconds, the serial reference, the scheduled makespan,
    and their ratio.
    """

    passes: List[PassReport]
    steps_before: int
    steps_after: int
    collectives_before: int  # whole-program collective launches
    collectives_after: int
    wire_bytes_before: float
    wire_bytes_after: float
    overlap: Optional[Dict] = None

    @property
    def fused_buckets(self) -> int:
        return sum(p.fused_buckets for p in self.passes)

    @property
    def launch_s_saved(self) -> float:
        return sum(p.launch_s_saved for p in self.passes)

    @property
    def inlined_bodies(self) -> int:
        return sum(p.inlined_bodies for p in self.passes)

    @property
    def hoisted_reshards(self) -> int:
        return sum(p.hoisted_reshards for p in self.passes)

    @property
    def overlap_ratio(self) -> float:
        return self.overlap["ratio"] if self.overlap else 1.0

    def as_dict(self) -> Dict:
        return {
            "passes": [p.as_dict() for p in self.passes],
            "steps_before": self.steps_before,
            "steps_after": self.steps_after,
            "collectives_before": self.collectives_before,
            "collectives_after": self.collectives_after,
            "wire_bytes_before": self.wire_bytes_before,
            "wire_bytes_after": self.wire_bytes_after,
            "fused_buckets": self.fused_buckets,
            "launch_s_saved": self.launch_s_saved,
            "inlined_bodies": self.inlined_bodies,
            "hoisted_reshards": self.hoisted_reshards,
            "overlap": dict(self.overlap) if self.overlap else None,
        }


def count_collective_launches(steps: List[PlanStep]) -> int:
    """Collective launches a plan will issue (wire collectives only;
    DynamicSlice is local addressing, not a launch).  Output-epilogue
    reshards are ordinary steps since the out_keys refactor, so the step list
    is the whole program.

    A psum over stacked axes is ONE launch (``lax.psum`` over the axes tuple
    reduces over the product group in one collective); note this differs from
    ``PlanStats.collectives``, which counts per-axis collective *ops* — the
    legacy reporting convention shared with the dynamic partitioner."""
    n = 0
    for s in steps:
        if s.kind == "reshard" and s.program is not None:
            n += sum(1 for ps in s.program.steps if ps.op != "dynamic_slice")
        elif s.kind in ("collective", "fused"):
            n += 1
    return n


def whole_wire_bytes(plan: PartitionPlan) -> float:
    """Modeled wire bytes of one whole-program execution: this plan's steps
    plus every inner jit/scan plan's, multiplied by its trip count — the
    number the inline/hoist passes actually move."""
    total = _wire_bytes(plan)
    for s in plan.steps:
        if s.inner is not None:
            total += s.call.get("trips", 1) * whole_wire_bytes(s.inner)
    return total


def whole_collective_launches(plan: PartitionPlan) -> int:
    """Collective launches of one whole-program execution (inner jit/scan
    plans at trip count)."""
    total = count_collective_launches(plan.steps)
    for s in plan.steps:
        if s.inner is not None:
            total += s.call.get("trips", 1) * whole_collective_launches(s.inner)
    return total


# ---------------------------------------------------------------------------------
# pass 1: jit inlining
# ---------------------------------------------------------------------------------


def _const_write_run(val):
    def run(env, reads, writes, val=val):
        _write(env, writes[0], val)

    return run


def _join_name_stacks(outer, inner):
    """The name stack of a body step moved out of its call step."""
    if outer is None or inner is None:
        return inner if outer is None else outer
    return outer + inner


def _splice_body(step: PlanStep) -> List[PlanStep]:
    """Rewrite one trivial jit step's inner plan as outer steps.

    Every inner env key is renamed: invars map to the call's operand keys,
    uniquely-produced out keys map straight onto the call's outvars, and all
    other keys get fresh :class:`ProxyVar`s — mandatory, because two jit
    eqns of the same traced function share jaxpr ``Var`` objects, and
    splicing both bodies unrenamed would collide in the outer env.
    Spliced steps run under the call's name stack followed by their own.
    """
    inner = step.inner
    outer_ns = step.name_stack
    ren: Dict[int, object] = {}
    for iv, outer_key in zip(inner.jaxpr.invars, step.reads):
        ren[id(iv)] = outer_key
    spliced: List[PlanStep] = []
    for cv, c in zip(inner.jaxpr.constvars, inner.consts):
        p = ProxyVar("inline.const")
        ren[id(cv)] = p
        spliced.append(PlanStep(
            "compute", (), (p,), _const_write_run(c), op="const",
            wbytes=(float(np.asarray(c).nbytes),), name_stack=outer_ns,
        ))
    # outputs: an out key written by the body and not yet mapped takes the
    # outer outvar as its name; literals, passthrough inputs/consts, and
    # duplicated keys need a tail write instead
    tail: List[Tuple[object, object]] = []
    for ov, ik in zip(step.writes, inner.out_keys):
        if isinstance(ov, core.DropVar):
            continue
        if isinstance(ik, excore.Literal) or id(ik) in ren:
            tail.append((ik, ov))
        else:
            ren[id(ik)] = ov
    for s in inner.steps:
        reads = tuple(
            r if isinstance(r, excore.Literal) else ren.get(id(r), r)
            for r in s.reads
        )
        writes = []
        for w in s.writes:
            if isinstance(w, core.DropVar):
                writes.append(w)
                continue
            nk = ren.get(id(w))
            if nk is None:
                nk = ProxyVar(f"inline.{s.op or s.kind}")
                ren[id(w)] = nk
            writes.append(nk)
        ns = dataclasses.replace(s, reads=reads, writes=tuple(writes),
                                 name_stack=_join_name_stacks(outer_ns,
                                                              s.name_stack))
        if hasattr(s, "_wire_bytes"):
            ns._wire_bytes = s._wire_bytes  # noqa: SLF001 - fused-step annotation
        spliced.append(ns)
    for ik, ov in tail:
        if isinstance(ik, excore.Literal):
            spliced.append(PlanStep(
                "compute", (), (ov,), _const_write_run(ik.val), op="const",
                wbytes=(float(np.asarray(ik.val).nbytes),), name_stack=outer_ns,
            ))
        else:
            spliced.append(PlanStep(
                "compute", (ren.get(id(ik), ik),), (ov,), _alias_run, op="alias",
                name_stack=outer_ns,
            ))
    return spliced


def inline_jit(plan: PartitionPlan) -> PassReport:
    """Splice trivial jit bodies into the outer step list.

    Trivial = no nested control flow left in the body (a nested *trivial*
    jit was already inlined when the body itself was optimized, so any
    surviving ``inner`` means scan or a big call) and at most
    ``INLINE_MAX_STEPS`` steps.  Inlined steps keep their ``flops``/``wbytes``
    annotations, so ``total_flops`` is unchanged and ``plan_peak_bytes`` now
    sees the body's intermediates directly instead of a pre-aggregated
    ``transient_bytes`` peak.
    """
    rep = PassReport("inline-jit")
    out: List[PlanStep] = []
    for step in plan.steps:
        if (step.kind != "compute" or step.op != "jit" or step.inner is None
                or len(step.inner.steps) > INLINE_MAX_STEPS
                or any(s.inner is not None for s in step.inner.steps)):
            out.append(step)
            continue
        spliced = _splice_body(step)
        out.extend(spliced)
        rep.inlined_bodies += 1
    if rep.inlined_bodies:
        plan.steps[:] = out
    return rep


# ---------------------------------------------------------------------------------
# pass 2: loop-invariant reshard hoisting out of scan bodies
# ---------------------------------------------------------------------------------


def hoist_scan_invariants(plan: PartitionPlan) -> PassReport:
    """Lift reshards of loop-invariant scan inputs out of the body.

    A scan *const* is bound once and reused every iteration; when the body's
    **only** use of a const invar is a reshard step (the classic per-iteration
    param gather), replaying that collective
    per iteration is pure waste: the pass moves the reshard into the outer
    plan just before the scan (executed once), feeds the scan the
    pre-resharded value, and rewires the body's consumers to read the invar
    directly.  Carries and xs change per
    iteration and are never hoisted.  The body edit mutates ``inner.steps``
    in place — the scan's run closure holds the same plan object.
    """
    rep = PassReport("scan-hoist")
    out: List[PlanStep] = []
    for step in plan.steps:
        if step.kind != "compute" or step.op != "scan" or step.inner is None:
            out.append(step)
            continue
        inner = step.inner
        nc = int(step.call.get("num_consts", 0))
        trips = int(step.call.get("trips", 1))
        # resolve free-alias chains: a const routed through annotate aliases
        # before its reshard is still loop-invariant
        canon: Dict[int, object] = {}
        for s in inner.steps:
            if _is_free_alias(s):
                _canon_insert(canon, s)
        out_ids = {id(k) for k in inner.out_keys
                   if not isinstance(k, excore.Literal)}
        new_reads = list(step.reads)
        drop: set = set()
        for i in range(min(nc, len(inner.jaxpr.invars))):
            bv = inner.jaxpr.invars[i]
            if id(bv) in out_ids:
                continue
            chain_ids = {id(bv)} | {
                wid for wid, root in canon.items() if root is bv
            }
            if chain_ids & out_ids:
                continue
            # exactly one reshard may consume the const (hoisting rebinds the
            # body invar to the resharded value, so a second reshard with a
            # different target would read the wrong source)
            cands = [
                j for j, s in enumerate(inner.steps)
                if s.kind == "reshard" and s.program is not None
                and not isinstance(s.reads[0], excore.Literal)
                and id(s.reads[0]) in chain_ids
            ]
            if len(cands) != 1:
                continue
            j = cands[0]
            rs = inner.steps[j]
            if id(rs.writes[0]) in out_ids:
                continue
            # every other reader of the const (or of a chain alias) must be a
            # chain alias itself — anything else sees the pre-reshard value
            hoistable = True
            for j2, s2 in enumerate(inner.steps):
                if j2 == j:
                    continue
                reads_chain = any(
                    not isinstance(r, excore.Literal) and id(r) in chain_ids
                    for r in s2.reads
                )
                if reads_chain and not (
                    _is_free_alias(s2) and id(s2.writes[0]) in chain_ids
                ):
                    hoistable = False
                    break
            if not hoistable:
                continue
            proxy = ProxyVar("hoist.const")
            out.append(dataclasses.replace(
                rs, reads=(new_reads[i],), writes=(proxy,),
                name_stack=_join_name_stacks(step.name_stack, rs.name_stack),
            ))
            new_reads[i] = proxy
            # body consumers of the reshard result now read its (aliased)
            # source, which after the rebind holds the resharded value
            w, src = rs.writes[0], rs.reads[0]
            for s2 in inner.steps:
                if any(r is w for r in s2.reads):
                    s2.reads = tuple(src if r is w else r for r in s2.reads)
            inner.in_shardings[i] = rs.program.dst
            drop.add(j)
            rep.hoisted_reshards += 1
            rep.wire_bytes_saved += max(trips - 1, 0) * rs.program.cost_bytes
            rep.launch_s_saved += max(trips - 1, 0) * _launch_s(plan) * sum(
                1 for ps in rs.program.steps if ps.op != "dynamic_slice"
            )
        if drop:
            inner.steps[:] = [
                s for j, s in enumerate(inner.steps) if j not in drop
            ]
            from .plan import plan_peak_bytes

            inner.peak_bytes = plan_peak_bytes(inner)
            step.transient_bytes = inner.peak_bytes
            step.reads = tuple(new_reads)
            _refresh_inner_report(inner)
        out.append(step)
    if rep.hoisted_reshards:
        plan.steps[:] = out
    return rep


def _refresh_inner_report(inner: PartitionPlan) -> None:
    """Re-sync an inner plan's :class:`OptReport` after a later outer pass
    (hoist) mutated its step list in place.

    The inner plan was optimized — and its report recorded — before the
    outer pipeline ran, so dropping a body reshard leaves ``steps_after`` /
    ``collectives_after`` / ``wire_bytes_after`` and the overlap model
    counting a step that no longer exists; ``plan_verify``'s recursive
    accounting would (correctly) flag that as a mutation.  Re-run the
    overlap scheduler (pure reordering — the scan's run closure holds this
    same plan object) and recompute the after-side accounting.
    """
    rep = inner.opt_report
    if rep is None:
        return
    sched = schedule_overlap(inner)
    rep.steps_after = len(inner.steps)
    rep.collectives_after = whole_collective_launches(inner)
    rep.wire_bytes_after = whole_wire_bytes(inner)
    rep.overlap = dict(sched.detail, ratio=sched.overlap_ratio)


# ---------------------------------------------------------------------------------
# pass 1: reshard CSE
# ---------------------------------------------------------------------------------


def _roots(plan: PartitionPlan) -> set:
    """Env keys execution reads at the end: must stay written (out_keys
    covers both plain body outputs and epilogue-reshard proxies)."""
    return {k for k in plan.out_keys if not isinstance(k, excore.Literal)}


def _is_free_alias(step: PlanStep) -> bool:
    """A pure env copy: annotate-with-matching-sharding or a CSE alias."""
    return (step.kind == "compute" and step.op in ("alias", "annotate")
            and len(step.reads) == 1 and len(step.writes) == 1
            and not isinstance(step.reads[0], excore.Literal))


def _canon_insert(canon: Dict[int, object], step: PlanStep) -> None:
    """Record a free alias in a value-root map (``id(write) -> root``).

    Roots are resolved at insert time, so chains stay depth-1 and lookups are
    ``canon.get(id(k), k)`` loops of at most one hop.  Shared by alias-aware
    CSE and scan-invariant hoisting so both passes agree on which env keys
    name the same value.
    """
    r = step.reads[0]
    while id(r) in canon:
        r = canon[id(r)]
    canon[id(step.writes[0])] = r


def reshard_cse(plan: PartitionPlan) -> PassReport:
    """Memoize identical (value, target-sharding) reshards across consumers.

    The builder emits one reshard step per consuming op; two consumers of the
    same value needing the same target sharding therefore duplicate the full
    collective sequence.  This pass keeps the first occurrence and rewires
    later readers to its result.  A duplicate whose result is a jaxpr output
    is replaced by a free alias (the env write must still happen).

    Reshard sources resolve through free-alias chains to a canonical root
    (an alias is the same value under another env key), so two inlined jit
    bodies that each route the same param through their own annotate alias
    before gathering it still CSE into one gather.
    """
    rep = PassReport("reshard-cse")
    roots = _roots(plan)
    seen: Dict[Tuple[int, tuple], object] = {}
    rewrite: Dict[int, object] = {}
    canon: Dict[int, object] = {}  # alias write -> resolved value root
    keepalive: List[object] = []  # hold replaced keys so id()s stay unique

    def _root(k):
        while id(k) in canon:
            k = canon[id(k)]
        return k

    out: List[PlanStep] = []
    for step in plan.steps:
        if rewrite:
            step.reads = tuple(rewrite.get(id(k), k) for k in step.reads)
        if _is_free_alias(step):
            _canon_insert(canon, step)
        if step.kind == "reshard" and step.program is not None:
            key = (id(_root(step.reads[0])), step.program.dst.dims_mapping)
            prior = seen.get(key)
            if prior is not None:
                rep.removed_steps += 1
                rep.wire_bytes_saved += step.program.cost_bytes
                rep.launch_s_saved += _launch_s(plan) * sum(
                    1 for ps in step.program.steps if ps.op != "dynamic_slice"
                )
                plan.stats.remove_program(step.program)
                w = step.writes[0]
                if w in roots:
                    out.append(PlanStep("compute", (prior,), (w,), _alias_run, op="alias"))
                else:
                    rewrite[id(w)] = prior
                    keepalive.append(w)
                continue
            seen[key] = step.writes[0]
        out.append(step)
    plan.steps[:] = out
    del keepalive
    return rep


# ---------------------------------------------------------------------------------
# pass 2: dead-reshard elimination
# ---------------------------------------------------------------------------------


def dead_reshard_elim(plan: PartitionPlan) -> PassReport:
    """Drop reshard steps (and free aliases) whose result nothing reads.

    Arises from user annotations on values the program never consumes and
    from CSE orphaning duplicates — alias-aware CSE in particular leaves
    behind dead alias copies when it rewires a reshard past an inlined
    body's annotate chain.  Iterates backwards so a chain of reshards
    feeding only a dead reshard dies with it.  No-op reshards (source already
    matching the target) are never emitted by the builder, so this pass only
    sees real collectives (plus zero-cost aliases).
    """
    rep = PassReport("dead-reshard-elim")
    roots = _roots(plan)
    nreads: Dict[int, int] = {}
    for step in plan.steps:
        for k in step.reads:
            nreads[id(k)] = nreads.get(id(k), 0) + 1
    keep = [True] * len(plan.steps)
    for i in range(len(plan.steps) - 1, -1, -1):
        step = plan.steps[i]
        is_reshard = step.kind == "reshard" and step.program is not None
        if not is_reshard and not _is_free_alias(step):
            continue
        w = step.writes[0]
        if w in roots or nreads.get(id(w), 0) > 0:
            continue
        keep[i] = False
        rep.removed_steps += 1
        if is_reshard:
            rep.wire_bytes_saved += step.program.cost_bytes
            rep.launch_s_saved += _launch_s(plan) * sum(
                1 for ps in step.program.steps if ps.op != "dynamic_slice"
            )
            plan.stats.remove_program(step.program)
        for k in step.reads:
            nreads[id(k)] -= 1
    plan.steps[:] = [s for s, f in zip(plan.steps, keep) if f]
    return rep


# ---------------------------------------------------------------------------------
# pass 3: output-alias sinking
# ---------------------------------------------------------------------------------


def sink_output_aliases(plan: PartitionPlan) -> PassReport:
    """Sink free alias steps down to just before their first reader (or to
    the plan tail when nothing reads them).

    CSE leaves aliases for duplicate reshards that feed plan outputs, and
    annotate ops with matching shardings lower to aliases; when such an alias
    immediately follows a collective it *reads*, it pins that collective's
    bucket (nothing may sink past a reader).  An alias is an env copy: it can
    run arbitrarily late as long as it precedes its own readers — typically
    the output-epilogue reshard steps at the tail — so sinking it re-exposes
    the adjacency the fusion pass needs.  Pure reordering — zero collectives
    or bytes change.
    """
    rep = PassReport("alias-sink")
    steps = plan.steps
    n = len(steps)
    # one linear pass builds the reader map and the epilogue-step set
    # (epilogue reshard steps write the proxy out_keys)
    epi_writes = {id(k) for k in plan.out_keys if not isinstance(k, excore.Literal)}
    epi_steps = set()
    readers: Dict[int, List[int]] = {}
    for j, s in enumerate(steps):
        for k in s.reads:
            readers.setdefault(id(k), []).append(j)
        if s.kind == "reshard" and any(id(w) in epi_writes for w in s.writes):
            epi_steps.add(j)
    # stable-sort placement: unmoved step i keeps key (i, 0); a sinking alias
    # gets key (first_reader, -1, i) — just before its first reader, after
    # every unmoved step at first_reader-1, original order among ties.  All
    # moves are downward (SSA: readers follow writers), so reads stay
    # produced-before-consumed; a chain of sinking aliases keeps its internal
    # write→read order because the reader's key is never below the writer's.
    keys: List[tuple] = []
    moved = False
    for i, s in enumerate(steps):
        key = (i, 0, i)
        # alias steps only: annotate-without-reshard lowers to op="annotate",
        # CSE duplicates to op="alias" (identified by op, not the run closure,
        # so cost-only plans — whose runners are stubs — sink identically)
        if s.kind == "compute" and s.op in ("alias", "annotate"):
            rd = readers.get(id(s.writes[0]), [])
            # sink only when every reader is output epilogue (an epilogue
            # reshard runs as late as its inputs allow anyway) or nothing
            # reads the alias; sinking past arbitrary steps would break
            # fusion hoist adjacency
            if all(j in epi_steps for j in rd):
                first = rd[0] if rd else n
                if first > i + 1:
                    key = (first, -1, i)
                    moved = True
        keys.append(key)
    if moved:
        order = sorted(range(n), key=lambda i: keys[i])
        steps[:] = [steps[i] for i in order]
    return rep


# ---------------------------------------------------------------------------------
# pass 4: collective fusion / bucketing
# ---------------------------------------------------------------------------------


def _fused_psum_run(axes, reduce_op, shapes):
    sizes = [int(np.prod(s)) if s else 1 for s in shapes]

    def run(env, reads, writes, axes=axes, reduce_op=reduce_op,
            shapes=shapes, sizes=sizes):
        flats = [jnp.ravel(_read(env, k)) for k in reads]
        buf = jnp.concatenate(flats) if len(flats) > 1 else flats[0]
        if reduce_op == "add":
            buf = lax.psum(buf, axes)
        elif reduce_op == "max":
            buf = lax.pmax(buf, axes)
        else:
            buf = lax.pmin(buf, axes)
        off = 0
        for w, shp, n in zip(writes, shapes, sizes):
            _write(env, w, jnp.reshape(buf[off:off + n], shp))
            off += n

    return run


def _fused_gather_run(axis, n, specs):
    # specs: per member (local shape, gather dim)
    sizes = [int(np.prod(s)) if s else 1 for s, _ in specs]

    def run(env, reads, writes, axis=axis, n=n, specs=specs, sizes=sizes):
        flats = [jnp.ravel(_read(env, k)) for k in reads]
        buf = jnp.concatenate(flats) if len(flats) > 1 else flats[0]
        g = lax.all_gather(buf, axis, axis=0, tiled=True)  # (n * total,)
        per = jnp.reshape(g, (n, -1))
        off = 0
        for w, (shp, d), m in zip(writes, specs, sizes):
            seg = jnp.reshape(per[:, off:off + m], (n,) + tuple(shp))
            _write(env, w, jnp.concatenate([seg[i] for i in range(n)], axis=d))
            off += m

    return run


def _fused_ppermute_run(axis, perm, shapes):
    sizes = [int(np.prod(s)) if s else 1 for s in shapes]

    def run(env, reads, writes, axis=axis, perm=perm, shapes=shapes,
            sizes=sizes):
        flats = [jnp.ravel(_read(env, k)) for k in reads]
        buf = jnp.concatenate(flats) if len(flats) > 1 else flats[0]
        buf = lax.ppermute(buf, axis, list(perm))
        off = 0
        for w, shp, n in zip(writes, shapes, sizes):
            _write(env, w, jnp.reshape(buf[off:off + n], shp))
            off += n

    return run


def _fuse_key(step: PlanStep, mesh) -> Optional[tuple]:
    """Bucket key, or None when the step is not fusable."""
    if step.kind == "collective":
        if step.op == "ppermute":
            # only identical permutations batch into one launch (same axis,
            # same source→dest pairs — e.g. several pytree leaves of one
            # shifting buffer moving the same pipeline tick)
            return ("ppermute", step.axes, step.call.get("perm"), step.dtype)
        return ("psum", step.axes, step.reduce_op, step.dtype)
    if step.kind == "reshard" and step.program is not None:
        ps = step.program.steps
        if len(ps) == 1 and ps[0].op == "all_gather":
            return ("gather", ps[0].axis, step.dtype)
    return None


def fuse_collectives(plan: PartitionPlan, bucket_bytes: Optional[float] = None) -> PassReport:
    """Bucket independent same-key collectives into single fused launches.

    Two legal placements exist for a bucket's single fused launch:

    * **hoist** — at the *first* member's position, legal iff every member's
      inputs are produced before that point (member writes only move earlier,
      which no SSA reader can observe);
    * **sink** — at the *last* member's position, legal iff no intervening
      step reads an earlier member's result.

    The scan tracks both: a bucket stays ``hoistable`` while every joined
    member's reads precede the first member; a reader of a member's result
    *pins* a hoistable bucket (further members must keep it hoistable) and
    finalizes a non-hoistable one.  The bucket is capped at ``bucket_bytes``
    (default: the roofline threshold where the extra HBM round-trip of
    concatenating the bucket stops paying for the saved launches).
    """
    rep = PassReport("collective-fusion")
    cap = (bucket_bytes if bucket_bytes is not None
           else fusion_bucket_bytes(_plan_params(plan)))
    mesh = plan.mesh
    steps = plan.steps
    # open buckets: key -> dict(members=[index], bytes, hoistable, pinned)
    open_buckets: Dict[tuple, Dict] = {}
    fused_at: Dict[int, List[int]] = {}  # anchor index -> member indices
    pos_written: Dict[int, int] = {}  # id(env key) -> producing step index
    # Fused members *move*: their writes land at the bucket anchor, not their
    # original index.  The hoist-legality check must therefore use a value's
    # EFFECTIVE position: unknown while its producer's bucket is still open
    # (the anchor may yet sink), the finalized anchor once decided.
    open_member_writes: Dict[int, tuple] = {}  # id(write) -> bucket key
    final_anchor: Dict[int, int] = {}  # id(write) -> fused anchor index

    def finalize(key) -> None:
        b = open_buckets.pop(key, None)
        if b is None:
            return
        for mi in b["members"]:
            for w in steps[mi].writes:
                open_member_writes.pop(id(w), None)
        if len(b["members"]) < 2:
            return  # singleton: the step stays put, pos_written is accurate
        anchor = b["members"][0] if b["hoistable"] else b["members"][-1]
        fused_at[anchor] = b["members"]
        for mi in b["members"]:
            for w in steps[mi].writes:
                final_anchor[id(w)] = anchor

    def available_before(r, first: int) -> bool:
        """Is value ``r`` produced before step index ``first`` in the OUTPUT
        plan?  Open-bucket producers are unsafe (their anchor may still
        sink); fused producers live at their anchor; everything else at its
        original index (absent = plan input/const/literal)."""
        if id(r) in open_member_writes:
            return False
        a = final_anchor.get(id(r))
        if a is not None:
            return a < first
        return pos_written.get(id(r), -1) < first

    for j, s in enumerate(steps):
        # a reader of an open-bucket member's result: harmless for a hoistable
        # bucket (the fused write lands at the first member, still before this
        # step) but it *pins* it — later members may only join if the bucket
        # stays hoistable.  A non-hoistable bucket must finalize here so no
        # member sinks past its reader.  This applies to fusable steps too.
        read_ids = {id(k) for k in s.reads}
        for k in list(open_buckets):
            if any(id(m_w) in read_ids
                   for mi in open_buckets[k]["members"]
                   for m_w in steps[mi].writes):
                if open_buckets[k]["hoistable"]:
                    open_buckets[k]["pinned"] = True
                else:
                    finalize(k)
        key = _fuse_key(s, mesh)
        if key is None:
            for w in s.writes:
                pos_written[id(w)] = j
            continue
        nb = s.in_bytes
        b = open_buckets.get(key)
        if b is not None:
            first = b["members"][0]
            cand_hoistable = all(available_before(r, first) for r in s.reads)
            joinable = cand_hoistable or not b["pinned"]
            if not joinable or b["bytes"] + nb > cap:
                finalize(key)
                b = None
        if b is None:
            b = open_buckets[key] = {
                "members": [j], "bytes": nb, "hoistable": True, "pinned": False,
            }
        else:
            b["members"].append(j)
            b["bytes"] += nb
            b["hoistable"] = b["hoistable"] and cand_hoistable
        for w in s.writes:
            pos_written[id(w)] = j
            open_member_writes[id(w)] = key
    for k in list(open_buckets):
        finalize(k)

    if not fused_at:
        return rep

    removed: set = set()
    replacement: Dict[int, PlanStep] = {}
    for anchor, members in fused_at.items():
        group = [steps[i] for i in members]
        key = _fuse_key(group[0], mesh)
        reads = tuple(g.reads[0] for g in group)
        writes = tuple(g.writes[0] for g in group)
        total_bytes = sum(g.in_bytes for g in group)
        if key[0] == "psum":
            axes, reduce_op, dtype = key[1], key[2], key[3]
            run = _fused_psum_run(axes, reduce_op, [g.lshape for g in group])
            wire = _psum_wire_bytes(mesh, axes, total_bytes)
            fused = PlanStep(
                "fused", reads, writes, run, op="fused-all-reduce", axes=axes,
                reduce_op=reduce_op, lshape=(int(sum(
                    int(np.prod(g.lshape)) if g.lshape else 1 for g in group)),),
                dbytes=group[0].dbytes, dtype=dtype,
                # psum outputs keep each member's local size (memory model)
                wbytes=tuple(g.in_bytes for g in group),
            )
            # stats: k psum launches (one count per axis each) become one
            plan.stats.count("all-reduce", -len(group) * len(axes))
            plan.stats.count("fused-all-reduce", 1)
        elif key[0] == "ppermute":
            axes, perm, dtype = key[1], key[2], key[3]
            run = _fused_ppermute_run(axes[0], perm,
                                      [g.lshape for g in group])
            n = mesh.axis_size(axes[0])
            wire = collective_wire_bytes("collective-permute", n, total_bytes)
            fused = PlanStep(
                "fused", reads, writes, run, op="fused-ppermute", axes=axes,
                lshape=(int(sum(
                    int(np.prod(g.lshape)) if g.lshape else 1 for g in group)),),
                dbytes=group[0].dbytes, dtype=dtype,
                wbytes=tuple(g.in_bytes for g in group),
                call={"perm": perm},
            )
            plan.stats.count("collective-permute", -len(group))
            plan.stats.count("fused-collective-permute", 1)
        else:
            axis, dtype = key[1], key[2]
            n = mesh.axis_size(axis)
            specs = [(g.lshape, g.program.steps[0].dim) for g in group]
            run = _fused_gather_run(axis, n, specs)
            wire = collective_wire_bytes("all-gather", n, total_bytes)
            fused = PlanStep(
                "fused", reads, writes, run, op="fused-all-gather", axes=(axis,),
                lshape=(int(sum(
                    int(np.prod(g.lshape)) if g.lshape else 1 for g in group)),),
                dbytes=group[0].dbytes, dtype=dtype,
                # each gathered output is n× its member's local size
                wbytes=tuple(n * g.in_bytes for g in group),
            )
            plan.stats.count("all-gather", -len(group))
            plan.stats.count("fused-all-gather", 1)
        fused._wire_bytes = wire  # noqa: SLF001 - plan-local annotation
        replacement[anchor] = fused
        removed.update(m for m in members if m != anchor)
        rep.fused_buckets += 1
        rep.fused_members += len(group)
        rep.launch_s_saved += (len(group) - 1) * _launch_s(plan)
    rep.removed_steps = len(removed)
    plan.steps[:] = [
        replacement.get(i, s) for i, s in enumerate(steps) if i not in removed
    ]
    return rep


# ---------------------------------------------------------------------------------
# pass 7: overlap-aware list scheduling
# ---------------------------------------------------------------------------------


def step_features(step: PlanStep, mesh) -> Tuple[float, float, float]:
    """(flops, wire_bytes, launches) of one step — the machine-independent
    cost features every time model in this repo is linear in.

    This is the feature extractor the machine-profile fitter
    (:func:`repro.obs.profile.fit_profile`) regresses measured step times
    against, and the SAME features :func:`_step_durations` divides by the
    roofline constants — so a fitted :class:`RooflineParams` reprices exactly
    the quantities the fit observed.  Inner jit/scan plans contribute at
    trip count, matching :func:`whole_wire_bytes`.
    """
    if step.kind == "reshard" and step.program is not None:
        launches = sum(
            1 for ps in step.program.steps if ps.op != "dynamic_slice"
        )
        return 0.0, step.program.cost_bytes, float(launches)
    if step.kind == "collective":
        if step.op == "ppermute":
            n = mesh.axis_size(step.axes[0]) if step.axes else 1
            return 0.0, collective_wire_bytes(
                "collective-permute", n, step.in_bytes), 1.0
        return 0.0, _collective_step_wire_bytes(mesh, step), 1.0
    if step.kind == "fused":
        return 0.0, getattr(step, "_wire_bytes", 0.0), 1.0
    wire = launches = 0.0
    if step.inner is not None:
        trips = step.call.get("trips", 1)
        wire = trips * whole_wire_bytes(step.inner)
        launches = trips * whole_collective_launches(step.inner)
    return step.flops, wire, launches


def _step_durations(step: PlanStep, mesh,
                    params: Optional[RooflineParams] = None
                    ) -> Tuple[float, float]:
    """(compute_s, comm_s) of one step under the roofline constants.

    Wire steps occupy the interconnect; compute steps occupy the FLOPs unit;
    a jit/scan call step occupies *both* for the duration of its (trip-
    multiplied) inner program, since its internal schedule is opaque here.
    ``params`` swaps in a calibrated machine profile (None = defaults).
    """
    flops, wire, launches = step_features(step, mesh)
    if params is None:
        return flops / PEAK_FLOPS, wire / ICI_BW + launches * COLLECTIVE_LAUNCH_S
    return (flops / params.peak_flops,
            wire / params.ici_bw + launches * params.collective_launch_s)


def schedule_overlap(plan: PartitionPlan) -> PassReport:
    """Reorder dataflow-independent steps to hide collective time behind
    compute, and record the max-of-terms overlap model.

    Greedy list scheduling onto a two-resource machine (compute unit,
    interconnect): among the dependency-ready steps, always place the one
    that can start earliest, preferring a wire step on ties so collectives
    issue as soon as their inputs exist and compute fills the wire time.
    Slot times come from :func:`repro.analysis.roofline.overlap_time_s` —
    a call step running compute and inner collectives concurrently costs
    ``max`` of the two terms plus the unhidden sliver, not their sum.

    Pure reordering: zero bytes or launches change, and the emitted order is
    a topological order of the dataflow, so execution semantics are
    untouched.  The report carries ``overlap_ratio`` = modeled makespan over
    the serial reference (1.0 = nothing hidden) and the term totals in
    ``detail``.
    """
    rep = PassReport("overlap-schedule")
    steps = plan.steps
    n = len(steps)
    mesh = plan.mesh
    params = _plan_params(plan)
    durs = [_step_durations(s, mesh, params) for s in steps]
    producer: Dict[int, int] = {}
    for j, s in enumerate(steps):
        for w in s.writes:
            producer[id(w)] = j
    deps: List[set] = []
    for j, s in enumerate(steps):
        d = set()
        for r in s.reads:
            if isinstance(r, excore.Literal):
                continue
            p = producer.get(id(r))
            if p is not None and p != j:
                d.add(p)
        deps.append(d)
    succs: List[List[int]] = [[] for _ in range(n)]
    indeg = [0] * n
    for j, d in enumerate(deps):
        indeg[j] = len(d)
        for p in d:
            succs[p].append(j)
    finish = [0.0] * n
    dep_ready = [0.0] * n  # max finish over scheduled deps, kept incrementally
    ready = [j for j in range(n) if indeg[j] == 0]
    tc = tm = 0.0  # resource availability: compute, interconnect
    order: List[int] = []
    while ready:
        # the resource clocks move every iteration, so candidate start times
        # cannot be precomputed — but dep_ready can, which keeps the pick
        # loop O(|ready|) instead of O(|ready| · deps)
        best = None
        for j in ready:
            dc, dm = durs[j]
            start = dep_ready[j]
            if dc > 0.0:
                start = max(start, tc)
            if dm > 0.0:
                start = max(start, tm)
            dur = (overlap_time_s(dc, dm, params)
                   if (dc > 0.0 and dm > 0.0) else dc + dm)
            key = (start, 0 if (dm > 0.0 and dc == 0.0) else 1, j)
            if best is None or key < best[0]:
                best = (key, j, start + dur)
        key, j, f = best
        ready.remove(j)
        order.append(j)
        finish[j] = f
        dc, dm = durs[j]
        if dc > 0.0:
            tc = f
        if dm > 0.0:
            tm = f
        for k in succs[j]:
            indeg[k] -= 1
            if finish[j] > dep_ready[k]:
                dep_ready[k] = finish[j]
            if indeg[k] == 0:
                ready.append(k)
    assert len(order) == n, "schedule_overlap: dependency cycle in plan steps"
    compute_total = sum(d[0] for d in durs)
    comm_total = sum(d[1] for d in durs)
    serial = sum(
        overlap_time_s(dc, dm, params) if (dc > 0.0 and dm > 0.0) else dc + dm
        for dc, dm in durs
    )
    makespan = max(finish, default=0.0)
    rep.moved_steps = sum(1 for pos, j in enumerate(order) if pos != j)
    rep.overlap_ratio = makespan / serial if serial > 0.0 else 1.0
    rep.detail = {
        "compute_s": compute_total,
        "comm_s": comm_total,
        "serial_s": serial,
        "overlapped_s": makespan,
    }
    if rep.moved_steps:
        plan.steps[:] = [steps[j] for j in order]
    return rep


# ---------------------------------------------------------------------------------
# schedule export: step taxonomy + modeled timeline (repro.obs)
# ---------------------------------------------------------------------------------


def step_class(step: PlanStep) -> str:
    """Step taxonomy shared by the modeled timeline, measured tracing, and
    the calibration report (:mod:`repro.obs.calibrate`).

    Classes: ``reshard``, ``collective`` (psum family), ``ppermute``,
    ``fused``, ``call:scan`` / ``call:jit`` (opaque inner plans), ``guard``
    (sentinel stat/pack epilogue steps), ``compute`` (everything else).
    """
    if step.kind == "reshard":
        return "reshard"
    if step.kind == "collective":
        return "ppermute" if step.op == "ppermute" else "collective"
    if step.kind == "fused":
        return "fused"
    if step.inner is not None:
        return f"call:{step.op}"
    op = step.op or ""
    if op.startswith("guard"):
        return "guard"
    return "compute"


def modeled_timeline(plan: PartitionPlan) -> List[Dict]:
    """The overlap schedule as an explicit timeline: one row per step with
    modeled start/duration seconds and the lane it occupies.

    Replays exactly the timing rules :func:`schedule_overlap` scheduled
    with — the same :func:`_step_durations` prices, the same two resource
    clocks, the same ``overlap_time_s`` slot rule — over the *final* step
    order (which on an optimized plan IS the schedule the list scheduler
    emitted), so the resulting makespan equals
    ``opt_report.overlap["overlapped_s"]`` bit for bit.  Works on raw and
    cost-only plans too (their list order is the serial program order).

    Rows: ``{"index", "name", "cls", "lane", "start_s", "dur_s",
    "compute_s", "comm_s"}`` with ``lane`` ∈ {``compute``,
    ``interconnect``} — a step lands on the interconnect lane when the
    scheduler charges it to the communication resource only.  Per-lane
    spans never overlap by construction (each resource clock serializes its
    lane); :mod:`repro.obs.trace` converts rows into Chrome trace events.
    """
    steps = plan.steps
    mesh = plan.mesh
    params = _plan_params(plan)
    n = len(steps)
    producer: Dict[int, int] = {}
    for j, s in enumerate(steps):
        for w in s.writes:
            producer[id(w)] = j
    finish = [0.0] * n
    tc = tm = 0.0
    rows: List[Dict] = []
    for j, s in enumerate(steps):
        dc, dm = _step_durations(s, mesh, params)
        start = 0.0
        for r in s.reads:
            if isinstance(r, excore.Literal):
                continue
            p = producer.get(id(r))
            if p is not None and p < j:
                start = max(start, finish[p])
        if dc > 0.0:
            start = max(start, tc)
        if dm > 0.0:
            start = max(start, tm)
        dur = (overlap_time_s(dc, dm, params)
               if (dc > 0.0 and dm > 0.0) else dc + dm)
        f = start + dur
        finish[j] = f
        if dc > 0.0:
            tc = f
        if dm > 0.0:
            tm = f
        name = f"{s.kind}:{s.op}" if s.op else s.kind
        rows.append({
            "index": j,
            "name": name,
            "cls": step_class(s),
            "lane": "interconnect" if (dm > 0.0 and dc == 0.0) else "compute",
            "start_s": start,
            "dur_s": dur,
            "compute_s": dc,
            "comm_s": dm,
        })
    return rows


# ---------------------------------------------------------------------------------
# the pipeline
# ---------------------------------------------------------------------------------


def _psum_wire_bytes(mesh, axes, in_bytes: float) -> float:
    """Per-axis AllReduce pricing, matching ``einsum_rules.compile_einsum``
    (which prices each remaining psum axis independently) so the opt-report
    byte deltas live in the same cost model the planner decided with."""
    return sum(
        collective_wire_bytes("all-reduce", mesh.axis_size(a), in_bytes)
        for a in axes
    )


def _collective_step_wire_bytes(mesh, step: PlanStep) -> float:
    """Wire bytes of one ``collective`` step: ppermute moves its payload once
    along the stage axis (``collective_wire_bytes("collective-permute")``);
    everything else is an AllReduce priced per axis."""
    if step.op == "ppermute":
        n = mesh.axis_size(step.axes[0]) if step.axes else 1
        return collective_wire_bytes("collective-permute", n, step.in_bytes)
    return _psum_wire_bytes(mesh, step.axes, step.in_bytes)


def _wire_bytes(plan: PartitionPlan) -> float:
    total = 0.0
    mesh = plan.mesh
    for s in plan.steps:
        if s.kind == "reshard" and s.program is not None:
            total += s.program.cost_bytes
        elif s.kind == "collective":
            total += _collective_step_wire_bytes(mesh, s)
        elif s.kind == "fused":
            total += getattr(s, "_wire_bytes", 0.0)
    return total


def optimize_plan(plan: PartitionPlan,
                  bucket_bytes: Optional[float] = None) -> PartitionPlan:
    """Run the whole-program pass pipeline (inline → hoist → CSE → DCE →
    alias-sink → fusion → overlap-schedule) on ``plan``.

    Mutates ``plan.steps``/``plan.stats`` in place (inner jit/scan plans are
    captured by reference in step closures) and attaches an :class:`OptReport`
    with before/after whole-program wire bytes and collective-launch counts
    plus the overlap-schedule model.
    """
    steps_before = len(plan.steps)
    coll_before = whole_collective_launches(plan)
    bytes_before = whole_wire_bytes(plan)
    reports = [
        inline_jit(plan),
        hoist_scan_invariants(plan),
        reshard_cse(plan),
        dead_reshard_elim(plan),
        sink_output_aliases(plan),
        fuse_collectives(plan, bucket_bytes),
        schedule_overlap(plan),
    ]
    sched = reports[-1]
    plan.stats.steps = len(plan.steps)
    plan.opt_report = OptReport(
        passes=reports,
        steps_before=steps_before,
        steps_after=len(plan.steps),
        collectives_before=coll_before,
        collectives_after=whole_collective_launches(plan),
        wire_bytes_before=bytes_before,
        wire_bytes_after=whole_wire_bytes(plan),
        overlap=dict(sched.detail, ratio=sched.overlap_ratio),
    )
    from .plan import plan_peak_bytes

    plan.peak_bytes = plan_peak_bytes(plan)
    return plan
