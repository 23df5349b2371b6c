"""Static plan verifier: machine-checkable validity for compiled plans.

The optimizer pipeline (``plan_opt``: inline → hoist → CSE → DCE → alias-sink
→ fusion → overlap-schedule) rewrites a :class:`~repro.core.plan.PartitionPlan`
in place while promising to preserve a set of structural invariants.  Until
this module, those promises could only be falsified by wrong numerics
surfacing in the multidev suite.  :func:`verify_plan` checks them directly, in
one linear walk over the step list, cheap enough to leave on for every
compile (it is the default in ``compile_plan`` / ``spmd_partition`` /
``lower_for_cost``, switchable with ``REPRO_PLAN_VERIFY=0``):

**Dataflow well-formedness**
  * every ``reads`` key is produced before use (plan inputs/consts, or an
    earlier step's write) — this also certifies the overlap schedule, since
    the final step list *is* the schedule;
  * writes are SSA: no env key written twice, no shadowing of plan inputs —
    alias-sunk buffers therefore cannot be read after their producing alias
    moved past a reader;
  * every ``out_keys`` entry is produced.

**Spec consistency**
  * every reshard step's program is *replayed through the collective
    simulator* (``collective_planner.simulate``): the step sequence must
    actually take ``program.src`` to ``program.dst``, and the recorded
    ``cost_bytes`` must match the simulated wire bytes;
  * layout chains: where a reshard's input layout is known (plan inputs,
    upstream reshards, layout-preserving collectives/aliases), it must equal
    ``program.src``; known output layouts must match ``plan.out_shardings``;
  * collective axes must exist in the mesh; ppermute ``perm``s must be
    (partial) permutations — unique sources, unique destinations, in range.

**Index ops** (gather / scatter-add run per shard, ``plan.IndexShards``)
  * each indexed dim's stride is its global size over its shards, and the
    rows at every shard boundary are re-simulated through the step's
    offsets: each must land on exactly the shard the layout puts it on;
  * a gather's partial axes are the axes of its indexed dims, a scatter's
    none of the operand's, and the step's result goes next to a psum over
    exactly those axes;
  * ``PlanStats.sharded_gathers`` counts the index steps, and
    ``PlanStats.fallback_bytes`` equals the fallback steps' operand gathers
    re-simulated (scan bodies at trip count).

**Schedule / cost sanity**
  * ``flops`` / ``wbytes`` / ``transient_bytes`` / ``dbytes`` non-negative;
  * planned-collective counts in ``plan.stats`` non-negative (fusion
    decrements them — going negative means double-removal);
  * whole-program byte accounting: ``opt_report.wire_bytes_after`` (recorded
    when the pass pipeline finished) must match an independent recomputation
    over the current steps incl. ``inner`` plans at trip count, and
    ``plan.peak_bytes`` must match a fresh liveness walk — a step list
    mutated after optimization without repricing fails here.

Inner jit/scan plans are verified recursively — dataflow/spec/kind checks
*and* the byte/peak accounting checks: every inner plan's ``opt_report`` and
``peak_bytes`` must match fresh recomputations too (the hoist pass rewrites
inner step lists after their own ``OptReport`` was recorded, and re-syncs
the report via ``plan_opt._refresh_inner_report`` — this check is what keeps
that honest).  Only the ``plan.stats`` counter checks stay top-level, since
inner plans share the top-level ``PlanStats`` object.

Failures raise :class:`PlanVerifyError` carrying every violation found (the
walk does not stop at the first), so a broken optimizer pass shows all of its
damage at once.  ``tests/test_plan_verify.py`` seeds plan corruptions —
dropped reshard, swapped spec, dep-violating schedule, dangling alias — and
asserts each is caught.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Dict, List, Optional, Tuple

from jax import core
from jax.extend import core as excore

from .collective_planner import PlanError, simulate

# module switch: default on; REPRO_PLAN_VERIFY=0 disables everywhere
VERIFY_DEFAULT = os.environ.get("REPRO_PLAN_VERIFY", "1") != "0"

# telemetry consumed by benchmarks/plan_smoke.py → BENCH_plan.json: how many
# top-level plans this process verified and how many violations were found
# (violations also raise, so a clean bench run must report 0 here)
_TELEMETRY = {"plans_verified": 0, "violations": 0}

_REL_TOL = 1e-3  # byte-accounting tolerance (float accumulation order)


def verify_enabled(flag: Optional[bool]) -> bool:
    """Resolve a tri-state ``verify=`` argument against the module default."""
    return VERIFY_DEFAULT if flag is None else bool(flag)


def verify_telemetry() -> Dict[str, int]:
    return dict(_TELEMETRY)


class PlanVerifyError(PlanError):
    """A compiled plan failed static verification."""

    def __init__(self, violations: List[str]):
        self.violations = list(violations)
        head = "\n  - ".join(self.violations[:20])
        more = len(self.violations) - 20
        super().__init__(
            f"plan verification failed ({len(self.violations)} violation(s)):"
            f"\n  - {head}" + (f"\n  … and {more} more" if more > 0 else "")
        )


@dataclasses.dataclass
class VerifyReport:
    """What one :func:`verify_plan` call covered."""

    plans: int = 0  # top-level + inner plans walked
    steps: int = 0  # steps checked across all of them
    violations: List[str] = dataclasses.field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.violations


def _key_name(k) -> str:
    if isinstance(k, excore.Literal):
        return f"lit:{k.val!r}"
    return repr(k)


def _close(a: float, b: float, rel: float = _REL_TOL) -> bool:
    return abs(a - b) <= rel * max(abs(a), abs(b), 1.0)


def _check_perm(perm, axis_size: int, where: str, out: List[str]) -> None:
    """A ppermute perm must be a partial permutation of [0, axis_size)."""
    if perm is None:
        out.append(f"{where}: ppermute step carries no perm in call metadata")
        return
    srcs = [p[0] for p in perm]
    dsts = [p[1] for p in perm]
    if len(set(srcs)) != len(srcs) or len(set(dsts)) != len(dsts):
        out.append(f"{where}: perm {perm} is not a permutation "
                   "(duplicate source or destination)")
    bad = [p for p in perm
           if not (0 <= p[0] < axis_size and 0 <= p[1] < axis_size)]
    if bad:
        out.append(f"{where}: perm entries {bad} out of range for axis size "
                   f"{axis_size}")


def _wire_bytes_acct(plan) -> float:
    """Independent whole-program wire-byte accounting (inner plans at trip
    count) — deliberately re-derived here rather than calling
    ``plan_opt.whole_wire_bytes`` so the verifier cross-checks the recorded
    ``opt_report`` numbers with its own arithmetic."""
    from .plan_opt import _collective_step_wire_bytes

    total = 0.0
    for s in plan.steps:
        if s.kind == "reshard" and s.program is not None:
            total += s.program.cost_bytes
        elif s.kind == "collective":
            total += _collective_step_wire_bytes(plan.mesh, s)
        elif s.kind == "fused":
            total += getattr(s, "_wire_bytes", 0.0)
        if s.inner is not None:
            total += s.call.get("trips", 1) * _wire_bytes_acct(s.inner)
    return total


def _check_index_step(plan, i: int, step, known_sh, out: List[str],
                      where: str) -> None:
    """Re-simulate where a per-shard gather / scatter-add finds its rows."""
    spec = step.index
    mesh = plan.mesh
    names = set(mesh.axis_names)
    masked = []
    for k, d in enumerate(spec.indexed):
        axes = spec.operand_dims[d]
        if any(a not in names for a in axes):
            out.append(f"{where}: indexed dim {d} on axes {axes} not in mesh "
                       f"{mesh.axis_names}")
            return
        masked += axes
        n = 1
        for a in axes:
            n *= mesh.axis_size(a)
        g, stride = spec.operand_shape[d], spec.strides[k]
        if g % n or stride != g // n:
            out.append(f"{where}: indexed dim {d} of {g} rows over {n} "
                       f"shard(s) holds {g // n} per shard, the step offsets "
                       f"by {stride}")
        rows = {0, g - 1}
        for j in range(1, n):
            rows.update((j * (g // n) - 1, j * (g // n)))
        for c in sorted(r for r in rows if 0 <= r < g):
            held = [s for s in range(n) if 0 <= c - s * stride < stride]
            if held != [c // (g // n)]:
                out.append(f"{where}: row {c} of dim {d} is taken from "
                           f"shard(s) {held}; the layout holds it on shard "
                           f"{c // (g // n)}")
    src = known_sh.get(id(step.reads[0])) if step.reads else None
    if src is not None and src != spec.operand_dims:
        out.append(f"{where}: operand layout {src} disagrees with the step's "
                   f"{spec.operand_dims}")
    partial = spec.partial_axes
    used = {a for axes in spec.operand_dims for a in axes}
    if step.op == "gather" and tuple(partial) != tuple(masked):
        out.append(f"{where}: gather leaves partial sums over {partial}, its "
                   f"indexed dims are split over {tuple(masked)}")
    if step.op == "scatter-add" and used & set(partial):
        out.append(f"{where}: scatter-add's partial axes {partial} split its "
                   "operand")
    if partial:
        w = id(step.writes[0])
        nxt = next((s for s in plan.steps[i + 1:]
                    if any(id(r) == w for r in s.reads)), None)
        if (nxt is None or nxt.kind not in ("collective", "fused")
                or nxt.reduce_op != "add" or set(nxt.axes) != set(partial)):
            out.append(f"{where}: its partial sums over {partial} are not "
                       "read by a psum over those axes")


def _index_steps(plan) -> int:
    return sum((s.index is not None)
               + (_index_steps(s.inner) if s.inner is not None else 0)
               for s in plan.steps)


def _fallback_bytes(plan, trips: int = 1) -> float:
    """The fallback steps' operand gathers, re-simulated, per execution."""
    total = 0.0
    for s in plan.steps:
        for prog, lshape, dbytes in s.gathered:
            total += trips * simulate(prog.src, prog.dst, list(prog.steps),
                                      lshape, dbytes)
        if s.inner is not None:
            total += _fallback_bytes(s.inner, trips * s.call.get("trips", 1))
    return total


def _accounting_checks(plan, out: List[str], path: str) -> None:
    """Byte/peak accounting for one plan, recursing into inner plans.

    Each plan — top-level and inner alike — carries its own ``opt_report``
    and ``peak_bytes``; a step list mutated after those were recorded (the
    pre-fix hoist-pass behaviour) fails here with the plan's path in the
    message."""
    rep = plan.opt_report
    if rep is not None:
        try:
            recomputed = _wire_bytes_acct(plan)
        except Exception as e:  # unpriceable step (e.g. bogus axis): its own
            out.append(f"{path}accounting: whole-program bytes not "
                       f"recomputable ({e})")
        else:
            if not _close(recomputed, rep.wire_bytes_after):
                out.append(
                    f"{path}accounting: opt_report.wire_bytes_after "
                    f"{rep.wire_bytes_after:.1f} != recomputed whole-program "
                    f"bytes {recomputed:.1f} (steps mutated after "
                    f"optimization?)")
    if plan.peak_bytes:
        from .plan import plan_peak_bytes

        try:
            peak = plan_peak_bytes(plan)
        except Exception as e:
            out.append(f"{path}accounting: liveness peak not recomputable "
                       f"({e})")
        else:
            if not _close(peak, plan.peak_bytes):
                out.append(
                    f"{path}accounting: plan.peak_bytes {plan.peak_bytes:.1f}"
                    f" != recomputed liveness peak {peak:.1f}")
    for i, s in enumerate(plan.steps):
        if s.inner is not None:
            _accounting_checks(s.inner, out, f"{path}step[{i}].inner.")


def _verify_body(plan, report: VerifyReport, path: str) -> None:
    """Dataflow + spec + per-step sanity for one plan (recurses into inner)."""
    import numpy as np

    report.plans += 1
    out = report.violations
    mesh = plan.mesh
    axis_names = set(mesh.axis_names)
    defined: set = set()
    known_sh: Dict[int, Tuple] = {}  # id(key) -> dims_mapping where tracked
    for v, s in zip(plan.jaxpr.invars, plan.in_shardings):
        defined.add(id(v))
        known_sh[id(v)] = s.dims_mapping
    for v in plan.jaxpr.constvars:
        defined.add(id(v))

    for i, step in enumerate(plan.steps):
        report.steps += 1
        where = f"{path}step[{i}] ({step.kind}:{step.op or '?'})"
        # -- dataflow ---------------------------------------------------------
        for r in step.reads:
            if isinstance(r, excore.Literal):
                continue
            if id(r) not in defined:
                out.append(f"{where}: reads {_key_name(r)} before it is "
                           "produced (dangling or reordered past its "
                           "producer)")
        for w in step.writes:
            if isinstance(w, core.DropVar):
                continue
            if id(w) in defined:
                out.append(f"{where}: writes {_key_name(w)} twice "
                           "(SSA violation / shadows a plan input)")
            defined.add(id(w))
        # -- cost sanity ------------------------------------------------------
        if step.flops < 0:
            out.append(f"{where}: negative flops {step.flops}")
        if step.transient_bytes < 0:
            out.append(f"{where}: negative transient_bytes "
                       f"{step.transient_bytes}")
        if step.dbytes < 0:
            out.append(f"{where}: negative dbytes {step.dbytes}")
        if any(b < 0 for b in (step.wbytes or ())):
            out.append(f"{where}: negative write bytes {step.wbytes}")
        # -- kind-specific spec checks ---------------------------------------
        if step.kind == "reshard" and step.program is not None:
            prog = step.program
            for ps in prog.steps:
                if ps.axis not in axis_names:
                    out.append(f"{where}: program step {ps.op} uses axis "
                               f"'{ps.axis}' not in mesh {mesh.axis_names}")
            src_known = known_sh.get(id(step.reads[0])) if step.reads else None
            if src_known is not None and src_known != prog.src.dims_mapping:
                out.append(f"{where}: input layout {src_known} disagrees "
                           f"with program.src {prog.src.dims_mapping}")
            lshape = tuple(step.lshape)
            if len(lshape) == prog.src.rank:
                try:
                    cost = simulate(prog.src, prog.dst, list(prog.steps),
                                    lshape, step.dbytes or 1)
                    if step.dbytes and not _close(cost, prog.cost_bytes):
                        out.append(
                            f"{where}: recorded cost_bytes "
                            f"{prog.cost_bytes:.1f} != simulated {cost:.1f}")
                except PlanError as e:
                    out.append(f"{where}: program does not reach its dst "
                               f"({e})")
            if step.writes:
                known_sh[id(step.writes[0])] = prog.dst.dims_mapping
        elif step.kind == "collective":
            for a in step.axes:
                if a not in axis_names:
                    out.append(f"{where}: collective axis '{a}' not in mesh "
                               f"{mesh.axis_names}")
            if step.op == "ppermute":
                n = mesh.axis_size(step.axes[0]) if step.axes else 1
                _check_perm(step.call.get("perm"), n, where, out)
            elif step.reduce_op not in ("add", "max", "min"):
                out.append(f"{where}: unknown reduce_op "
                           f"'{step.reduce_op}'")
            # collectives move data between devices but preserve layout
            if step.reads and step.writes:
                k = known_sh.get(id(step.reads[0]))
                if k is not None:
                    known_sh[id(step.writes[0])] = k
        elif step.kind == "fused":
            for a in step.axes:
                if a not in axis_names:
                    out.append(f"{where}: fused axis '{a}' not in mesh "
                               f"{mesh.axis_names}")
            if len(step.reads) != len(step.writes):
                out.append(f"{where}: fused step arity mismatch "
                           f"({len(step.reads)} reads, "
                           f"{len(step.writes)} writes)")
            if step.op == "fused-ppermute":
                n = mesh.axis_size(step.axes[0]) if step.axes else 1
                _check_perm(step.call.get("perm"), n, where, out)
        elif (step.kind == "compute" and step.op in ("alias", "annotate")
              and len(step.reads) == 1 and len(step.writes) == 1
              and not isinstance(step.reads[0], excore.Literal)):
            k = known_sh.get(id(step.reads[0]))
            if k is not None:
                known_sh[id(step.writes[0])] = k
        if step.index is not None:
            _check_index_step(plan, i, step, known_sh, out, where)
        # -- inner plans ------------------------------------------------------
        if step.inner is not None:
            trips = step.call.get("trips", 1)
            if trips < 0:
                out.append(f"{where}: negative trip count {trips}")
            _verify_body(step.inner, report, f"{path}step[{i}].inner.")

    # -- outputs --------------------------------------------------------------
    for idx, k in enumerate(plan.out_keys):
        if isinstance(k, excore.Literal):
            continue
        if id(k) not in defined:
            out.append(f"{path}out_keys[{idx}]: {_key_name(k)} is never "
                       "produced")
        known = known_sh.get(id(k))
        want = plan.out_shardings[idx].dims_mapping
        if known is not None and known != want:
            out.append(f"{path}out_keys[{idx}]: layout {known} disagrees "
                       f"with out_shardings {want}")
    if len(plan.out_keys) != len(plan.out_shardings):
        out.append(f"{path}out_keys/out_shardings length mismatch "
                   f"({len(plan.out_keys)} vs {len(plan.out_shardings)})")
    _ = np  # keep the lazy import referenced


def verify_plan(plan, strict: bool = True) -> VerifyReport:
    """Statically verify one compiled :class:`PartitionPlan`.

    Runs the dataflow / spec / cost checks documented in the module
    docstring over ``plan`` and every ``inner`` plan.  With ``strict=True``
    (default) raises :class:`PlanVerifyError` on any violation; with
    ``strict=False`` returns the :class:`VerifyReport` for the caller to
    inspect.  Works on executable, cost-only, optimized, and raw plans alike
    (accounting checks only fire where the corresponding record exists).
    """
    report = VerifyReport()
    _verify_body(plan, report, "")
    out = report.violations
    # stats counters are top-level only: inner plans share this object
    for kind, n in plan.stats.collectives.items():
        if n < 0:
            out.append(f"stats: negative planned-collective count "
                       f"{kind}={n} (double removal in an optimizer pass)")
    n_index = _index_steps(plan)
    if n_index != plan.stats.sharded_gathers:
        out.append(f"stats: sharded_gathers {plan.stats.sharded_gathers} != "
                   f"{n_index} index steps in the plan")
    try:
        fb = _fallback_bytes(plan)
    except PlanError as e:
        out.append(f"stats: a fallback's gather does not replay ({e})")
    else:
        if not _close(fb, plan.stats.fallback_bytes):
            out.append(f"stats: fallback_bytes {plan.stats.fallback_bytes:.1f}"
                       f" != {fb:.1f} re-simulated from the fallback steps")
    _accounting_checks(plan, out, "")
    _TELEMETRY["plans_verified"] += 1
    if report.violations:
        _TELEMETRY["violations"] += len(report.violations)
        if strict:
            raise PlanVerifyError(report.violations)
    return report


def verify_state_reshard(plan, strict: bool = True) -> VerifyReport:
    """Verify a :class:`~repro.core.plan.StateReshardPlan` (elastic restore).

    Per leaf: the source/target shardings must live on the plan's mesh with
    rank matching the global shape, and the leaf's program must replay
    through the simulator from ``src`` to ``dst`` at the recorded cost.
    """
    import numpy as np

    report = VerifyReport()
    report.plans = 1
    out = report.violations
    axis_names = set(plan.mesh.axis_names)
    for leaf in plan.leaves:
        report.steps += 1
        where = f"leaf '{leaf.key}'"
        for s, nm in ((leaf.src, "src"), (leaf.dst, "dst")):
            if s.rank != len(leaf.global_shape):
                out.append(f"{where}: {nm} rank {s.rank} != shape rank "
                           f"{len(leaf.global_shape)}")
            for dim_axes in s.dims_mapping:
                for a in dim_axes:
                    if a not in axis_names:
                        out.append(f"{where}: {nm} uses axis '{a}' not in "
                                   f"mesh {plan.mesh.axis_names}")
        if leaf.program.cost_bytes < 0:
            out.append(f"{where}: negative cost_bytes "
                       f"{leaf.program.cost_bytes}")
        if leaf.program.src.dims_mapping != leaf.src.dims_mapping:
            out.append(f"{where}: program.src "
                       f"{leaf.program.src.dims_mapping} disagrees with leaf "
                       f"src {leaf.src.dims_mapping}")
        if leaf.program.dst.dims_mapping != leaf.dst.dims_mapping:
            out.append(f"{where}: program.dst "
                       f"{leaf.program.dst.dims_mapping} disagrees with leaf "
                       f"dst {leaf.dst.dims_mapping}")
        from .reshard import shard_shape

        local = shard_shape(leaf.global_shape, leaf.src)
        db = int(np.dtype(leaf.dtype).itemsize)
        try:
            cost = simulate(leaf.src, leaf.dst, list(leaf.program.steps),
                            local, db)
            if not _close(cost, leaf.program.cost_bytes):
                out.append(f"{where}: recorded cost_bytes "
                           f"{leaf.program.cost_bytes:.1f} != simulated "
                           f"{cost:.1f}")
        except PlanError as e:
            out.append(f"{where}: program does not reach its dst ({e})")
    _TELEMETRY["plans_verified"] += 1
    if report.violations:
        _TELEMETRY["violations"] += len(report.violations)
        if strict:
            raise PlanVerifyError(report.violations)
    return report
