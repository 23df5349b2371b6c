"""Compiled partition plans: plan-once / execute-many for the reference
partitioner (paper §4, PartIR-style decision/execution split).

The dynamic reference path (``SpmdPartitioner``) re-dispatches every equation
through Python on every trace: read shardings, classify the op, decide the
reshard, emit collectives.  All of those decisions depend only on the jaxpr,
the mesh, and the propagated shardings — never on data — so they can be made
exactly once.  This module lowers a propagated jaxpr into a
:class:`PartitionPlan`: a flat list of per-equation *steps*, each a
:class:`PlanStep` over pre-resolved decisions —

* the handler for the op (einsum / elementwise / reduce / conv / …),
* operand reshard **programs** (cost-model-chosen collective sequences from
  ``collective_planner.plan_reshard``), emitted as *first-class reshard steps*
  so the whole-plan optimizer (``core/plan_opt.py``) can CSE, eliminate, and
  fuse them,
* the ReduceScatter-vs-AllReduce choice for partial sums
  (``einsum_rules.compile_einsum``), with trailing AllReduces emitted as
  first-class *collective steps* so independent ones can be bucketed,
* the output sharding.

Every step declares its dataflow (``reads`` / ``writes`` env keys) and its
runner reads operands *through* those tuples, so optimizer passes can rewire
consumers without touching closures.  Values produced mid-plan (a resharded
operand, a pre-psum partial sum) live under :class:`ProxyVar` keys — plan-local
SSA names that never collide with jaxpr vars.

Inner ``jit``/``scan`` bodies lower to their own plans, but not opaquely:
the call step exposes the inner plan (``PlanStep.inner``) and its static call
metadata (``PlanStep.call``), so the whole-program passes can splice trivial
jit bodies into the outer step list, hoist loop-invariant reshards out of
scan bodies, and price inner collectives at trip count.

Executing a plan is a straight walk of the step list with a dict environment;
no propagation, no per-op classification, no reshard search.
``spmd_partition`` (partitioner.py) caches plans keyed by input avals + mesh
(and process-wide by jaxpr digest), so steady-state calls skip ``make_jaxpr``,
propagation, and all per-equation Python dispatch.

Output-epilogue reshards (jaxpr outputs whose propagated sharding differs from
the sharding the body leaves them in) are *first-class steps* too: the plan
records, per output, an env key (``out_keys``) that execution reads at the end,
and the epilogue reshard writes a :class:`ProxyVar` key like any other reshard.
That makes epilogue collectives visible to CSE / DCE / fusion.

The plan also carries :class:`PlanStats` — planned-collective counts and the
modeled reshard wire bytes — and, after optimization, an
``opt_report`` (:class:`repro.core.plan_opt.OptReport`) with per-pass savings,
consumed by the analysis/benchmark layer (``benchmarks/plan_smoke.py`` →
``BENCH_plan.json``).

Cost-only lowering
------------------
:func:`lower_for_cost` runs the same propagation → lowering → optimizer
pipeline but swaps every step's runner for a raising stub — no shard_map, no
jit, no execution — and returns a :class:`PlanCost`: modeled collective wire
bytes + launches, per-device compute FLOPs vs the ideal (flops/num_devices)
balance point, and a per-device live-memory peak from a liveness walk over the
step list.  This is the scoring function the autoshard search
(``repro.autoshard``) minimizes; each candidate evaluation is pure planning.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np
from jax import core, lax
from jax.extend import core as excore

from .annotate import annotate_p
from .collective_planner import (
    PlanError, ReshardProgram, execute_program, plan_reshard,
)
from .compat import eqn_name_stack, under_name_stack
from .einsum_rules import compile_einsum, execute_einsum
from .propagation import Propagation, PropagationResult, _subjaxpr
from .reshard import shard_shape
from .rules import ELEMENTWISE, IndexDims, index_dims
from .sharding import Mesh, Sharding, merge_shardings, replicated

Env = Dict[object, object]


# ---------------------------------------------------------------------------------
# env keys and structured steps
# ---------------------------------------------------------------------------------


class ProxyVar:
    """A plan-local SSA value key (a resharded operand, a pre-psum partial).

    jaxpr vars name the values of the *source* program; optimizer passes need
    names for the intermediate values the partitioner itself introduces.  Env
    keys only need identity hash/eq, so a bare object per value suffices.
    """

    __slots__ = ("note",)

    def __init__(self, note: str = ""):
        self.note = note

    def __repr__(self):  # pragma: no cover - debugging aid
        return f"<proxy:{self.note}>"


@dataclasses.dataclass
class PlanStep:
    """One resolved execution step with explicit dataflow.

    ``run(env, reads, writes)`` must read operands positionally from ``reads``
    and write results positionally to ``writes`` — never from captured keys —
    so optimizer passes can rewire dataflow by editing the tuples.

    Kinds:
      * ``compute``    — a local op (einsum, elementwise, reduce, …);
      * ``reshard``    — replay of one :class:`ReshardProgram` (CSE/DCE/fusion
                         candidates);
      * ``collective`` — a standalone trailing collective (psum/pmax/pmin)
                         split out of its producing op so independent ones can
                         be bucketed;
      * ``fused``      — a fusion-pass product: one launch over a flattened
                         concatenation of several members' buffers.
    """

    kind: str
    reads: Tuple[object, ...]
    writes: Tuple[object, ...]
    run: Callable[[Env, Tuple, Tuple], None]
    op: str = ""  # primitive name / collective kind
    program: Optional[ReshardProgram] = None  # reshard steps only
    axes: Tuple[str, ...] = ()  # collective steps only
    reduce_op: str = ""  # "add" | "max" | "min"
    lshape: Tuple[int, ...] = ()  # local shape of reads[0] on entry
    dbytes: int = 0
    dtype: str = ""
    # -- cost-model annotations (consumed by lower_for_cost / PlanCost) -----
    flops: float = 0.0  # per-device local FLOPs of this step
    wbytes: Tuple[float, ...] = ()  # local bytes of each write (memory model)
    transient_bytes: float = 0.0  # inner-plan live peak (scan/jit steps)
    # -- call steps (op == "jit" / "scan") ---------------------------------
    # The inner plan is exposed structurally (not just captured by the run
    # closure) so whole-program passes can inline trivial jit bodies, hoist
    # loop-invariant reshards out of scan bodies, and price inner collectives
    # at trip count.  ``call`` carries the static call metadata the passes
    # need: {"trips": int} for jit (always 1), plus
    # {"num_consts", "num_carry"} for scan.
    inner: Optional["PartitionPlan"] = None
    call: Dict = dataclasses.field(default_factory=dict)
    # the source equation's name stack (its ``jax.named_scope`` scopes and
    # jvp/transpose transforms), which execute() binds the step under so the
    # partitioned program's op names keep them; None binds under the caller's
    name_stack: Optional[object] = None
    # gather / scatter-add steps run per shard: the layout they index into
    index: Optional["IndexShards"] = None
    # fallback steps: (program, local shape, dtype bytes) of each operand
    # reshard the fallback gathered, which ``PlanStats.fallback_bytes`` counts
    gathered: Tuple[Tuple[ReshardProgram, Tuple[int, ...], int], ...] = ()

    @property
    def in_bytes(self) -> float:
        b = float(self.dbytes)
        for s in self.lshape:
            b *= s
        return b


def _nbytes_of(shape: Tuple[int, ...], dbytes: int) -> float:
    b = float(dbytes)
    for s in shape:
        b *= s
    return b


def kernel_calls(eqns) -> Dict[str, int]:
    """Kernel name -> ``pallas_call`` equations among ``eqns`` and in the
    jaxprs they hold (a ``custom_vjp_call``'s primal), each counted once.
    A kernel's name is the one it was given, else its kernel function's
    (``_flash_attention_kernel``)."""
    out: Dict[str, int] = {}
    for eqn in eqns:
        if eqn.primitive.name == "pallas_call":
            dbg = eqn.params["jaxpr"].debug_info
            found = {eqn.params.get("name")
                     or (dbg.func_name if dbg else "pallas_call"): 1}
        else:
            found = kernel_calls([e for sub in core.jaxprs_in_params(eqn.params)
                                  for e in sub.eqns])
        for k, n in found.items():
            out[k] = out.get(k, 0) + n
    return out


def _read(env: Env, v):
    if isinstance(v, excore.Literal):
        return v.val
    return env[v]


def _write(env: Env, v, val) -> None:
    if isinstance(v, core.DropVar):
        return
    env[v] = val


def _alias_run(env, reads, writes):
    _write(env, writes[0], _read(env, reads[0]))


def _reshard_run(prog: ReshardProgram):
    def run(env, reads, writes, prog=prog):
        _write(env, writes[0], execute_program(_read(env, reads[0]), prog))

    return run


def _cost_only_run(env, reads, writes):  # pragma: no cover - guard rail
    raise RuntimeError(
        "cost-only plan executed: this plan was lowered via lower_for_cost "
        "and carries no runnables"
    )


def _collective_run(axes: Tuple[str, ...], reduce_op: str):
    def run(env, reads, writes, axes=axes, reduce_op=reduce_op):
        x = _read(env, reads[0])
        if reduce_op == "add":
            x = lax.psum(x, axes)
        elif reduce_op == "max":
            x = lax.pmax(x, axes)
        else:
            x = lax.pmin(x, axes)
        _write(env, writes[0], x)

    return run


# ---------------------------------------------------------------------------------
# stats
# ---------------------------------------------------------------------------------


@dataclasses.dataclass
class PlanStats:
    """Planned-collective accounting for one compiled plan."""

    collectives: Dict[str, int] = dataclasses.field(default_factory=dict)
    reshard_bytes: float = 0.0  # modeled wire bytes of planned reshards
    # Reference costs price the reshard set the *unoptimized* pipeline would
    # execute — every builder-emitted reshard, including ones CSE/DCE later
    # eliminate (the reference schedules had no whole-plan optimizer) — under
    # the AllGather-first and pre-planner greedy schedules respectively.
    # reshard_bytes vs these therefore captures both the per-reshard planner
    # win (PR 1) and the optimizer-pass win (PR 2).
    baseline_bytes: float = 0.0  # reference: AllGather-first (replicate+slice)
    legacy_bytes: float = 0.0  # reference: pre-planner greedy schedule
    eqns: int = 0
    steps: int = 0
    # lattice-search telemetry delta accumulated while this plan compiled
    # (searches run / node-budget exhaustions / depth-cap prunes); filled by
    # compile_plan from collective_planner.search_telemetry()
    lattice: Dict[str, int] = dataclasses.field(default_factory=dict)
    # primitive name -> equations lowered by the gather-op-reshard fallback
    # (inner jit/scan bodies included: they share their caller's stats)
    fallbacks: Dict[str, int] = dataclasses.field(default_factory=dict)
    # gather / scatter-add equations lowered to per-shard steps
    sharded_gathers: int = 0
    # bytes one device receives per execution from the fallback's operand
    # gathers, as lowered (scan bodies at trip count)
    fallback_bytes: float = 0.0
    # Pallas kernel name -> ``pallas_call`` equations run per execution
    # (scan bodies at trip count)
    kernel_calls: Dict[str, int] = dataclasses.field(default_factory=dict)

    def count(self, kind: str, n: int = 1) -> None:
        self.collectives[kind] = self.collectives.get(kind, 0) + n

    def add_program(self, prog: Optional[ReshardProgram]) -> None:
        if prog is None or prog.is_identity:
            return
        for s in prog.steps:
            self.count(s.op.replace("_", "-"))
        self.reshard_bytes += prog.cost_bytes

    def remove_program(self, prog: Optional[ReshardProgram]) -> None:
        """Revert the *planned* accounting of :meth:`add_program` — used by
        optimizer passes when a planned reshard is eliminated (CSE /
        dead-reshard elimination).  Deliberately leaves ``baseline_bytes`` /
        ``legacy_bytes`` untouched: the reference pipelines had no CSE/DCE
        and would still execute the eliminated reshard, so keeping it in the
        reference cost is what makes the planned-vs-reference delta reflect
        the optimizer's win."""
        if prog is None or prog.is_identity:
            return
        for s in prog.steps:
            self.count(s.op.replace("_", "-"), -1)
        self.reshard_bytes -= prog.cost_bytes

    def as_dict(self) -> Dict:
        return {
            "collectives": dict(self.collectives),
            "reshard_bytes": self.reshard_bytes,
            "baseline_bytes": self.baseline_bytes,
            "legacy_bytes": self.legacy_bytes,
            "eqns": self.eqns,
            "steps": self.steps,
            "lattice": dict(self.lattice),
            "fallbacks": dict(self.fallbacks),
            "sharded_gathers": self.sharded_gathers,
            "fallback_bytes": self.fallback_bytes,
            "kernel_calls": dict(self.kernel_calls),
        }


# ---------------------------------------------------------------------------------
# the compiled plan
# ---------------------------------------------------------------------------------


@dataclasses.dataclass
class PartitionPlan:
    """A fully resolved partitioning of one jaxpr over one mesh.

    ``out_keys`` holds one env key per jaxpr output: the outvar itself when the
    body already leaves it in the propagated output sharding, or the
    :class:`ProxyVar` written by the output-epilogue reshard *step* otherwise
    (epilogue reshards live in ``steps`` like every other collective, so the
    optimizer passes see them).
    """

    jaxpr: excore.Jaxpr
    consts: Tuple
    mesh: Mesh
    steps: List[PlanStep]
    in_shardings: List[Sharding]
    out_shardings: List[Sharding]
    out_keys: List[object]
    stats: PlanStats
    opt_report: Optional[object] = None  # plan_opt.OptReport after optimization
    peak_bytes: float = 0.0  # modeled per-device live-memory peak (cost model)
    guard: Optional["GuardInfo"] = None  # sentinel epilogue metadata
    params: Optional[object] = None  # roofline.RooflineParams (None = defaults)

    def execute(self, *args, tracer=None):
        """Run the plan on local shards (inside a shard_map region).

        ``tracer`` (an :class:`repro.obs.trace.Tracer`) switches to the
        traced walk — per-step measured spans, only meaningful under eager
        (non-jitted) shard_map; see the tracing contract in
        :mod:`repro.obs.trace`.  The untraced path reads no timers; it binds
        each step under the step's name stack, which under ``jit`` costs
        trace time only.
        """
        if tracer is not None:
            return self._execute_traced(args, tracer)
        env: Env = {}
        for v, c in zip(self.jaxpr.constvars, self.consts):
            env[v] = c
        for v, a in zip(self.jaxpr.invars, args):
            env[v] = a
        for step in self.steps:
            if step.name_stack is None:
                step.run(env, step.reads, step.writes)
            else:
                with under_name_stack(step.name_stack):
                    step.run(env, step.reads, step.writes)
        return tuple(_read(env, k) for k in self.out_keys)

    def _execute_traced(self, args, tracer):
        """The traced step walk: a perf_counter pair brackets each step, and
        with ``tracer.config.sync`` the span blocks on the step's writes so
        device time lands inside it (dispatch-only otherwise).

        ``tracer.config.timing == "tight"`` switches to the calibration walk
        (:meth:`_execute_tight`): each step is re-run min-of-K with
        ``block_until_ready``, so the recorded span is a measurement-quality
        lower bound rather than an eager dispatch-inclusive upper bound."""
        import jax

        if getattr(tracer.config, "timing", "eager") == "tight":
            return self._execute_tight(args, tracer)
        sync = tracer.config.sync
        call = tracer.begin_call()
        env: Env = {}
        for v, c in zip(self.jaxpr.constvars, self.consts):
            env[v] = c
        for v, a in zip(self.jaxpr.invars, args):
            env[v] = a
        for idx, step in enumerate(self.steps):
            t0 = tracer.now_us()
            step.run(env, step.reads, step.writes)
            if sync:
                for w in step.writes:
                    out = env.get(w)
                    if out is not None:
                        try:
                            jax.block_until_ready(out)
                        except Exception:  # non-array env values (specs etc.)
                            pass
            tracer.record_step(idx, step, t0, tracer.now_us(), call)
        outs = tuple(_read(env, k) for k in self.out_keys)
        if sync:
            try:
                jax.block_until_ready(outs)
            except Exception:
                pass
        return outs

    def _execute_tight(self, args, tracer):
        """Calibration-grade step walk: every step is warmed up once, then
        re-run ``tracer.config.repeats`` times with ``block_until_ready``
        after each, and the **minimum** elapsed time becomes the span —
        the min-of-K discipline ``benchmarks/perf.py`` uses.  Re-running is
        sound because steps are pure functions of their env reads.  Span
        timestamps are a synthetic monotonic cursor (sum of minima), so
        lanes stay non-overlapping even though wall time ran K× longer."""
        import time

        import jax

        def _block(step):
            for w in step.writes:
                out = env.get(w)
                if out is not None:
                    try:
                        jax.block_until_ready(out)
                    except Exception:  # non-array env values (specs etc.)
                        pass

        reps = max(1, int(getattr(tracer.config, "repeats", 3)))
        call = tracer.begin_call()
        env: Env = {}
        for v, c in zip(self.jaxpr.constvars, self.consts):
            env[v] = c
        for v, a in zip(self.jaxpr.invars, args):
            env[v] = a
        cursor = tracer.now_us()
        for idx, step in enumerate(self.steps):
            step.run(env, step.reads, step.writes)  # warmup (populates env)
            _block(step)
            best = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                step.run(env, step.reads, step.writes)
                _block(step)
                best = min(best, time.perf_counter() - t0)
            best_us = best * 1e6
            tracer.record_step(idx, step, cursor, cursor + best_us, call)
            cursor += best_us
        outs = tuple(_read(env, k) for k in self.out_keys)
        try:
            jax.block_until_ready(outs)
        except Exception:
            pass
        return outs

    def total_flops(self) -> float:
        """Modeled per-device FLOPs of one plan execution (scan bodies are
        already multiplied by trip count at emit time)."""
        return sum(s.flops for s in self.steps)


# ---------------------------------------------------------------------------------
# runtime numerics sentinels: plan-lowered guard epilogue steps
# ---------------------------------------------------------------------------------
#
# A guarded plan appends a fused non-finite / abs-max check over selected
# outputs as *first-class steps*: one local stat step per guarded tensor, one
# pack step, and one cross-device pmax collective — priced by the roofline and
# visible to collective fusion and the overlap scheduler like any other
# collective.  The guard vector becomes an extra plan output (replicated,
# shape ``(2 * n_leaves,)``: per leaf ``[nonfinite_count, abs_max]``); the
# host side turns a tripped guard into a typed :class:`NumericsFault` with
# per-leaf provenance (``guard_faults``).  Under pmax the non-finite count
# reduces to the max per-device count — still > 0 iff any shard anywhere held
# a non-finite value — which lets one launch carry both stats.


@dataclasses.dataclass(frozen=True)
class GuardConfig:
    """Selects the tensors the numerics sentinel watches and its thresholds.

    Plan-level fields (``append_guard_steps`` / ``spmd_partition(guard=)``):
    ``outputs`` picks plan output indices (``None`` = all), ``names`` labels
    them for provenance.  Train-level fields (``make_train_step``): ``grads``
    / ``loss`` / ``moments`` select state leaves; ``max_grad_norm`` bounds
    the global gradient norm.  ``rewind_after`` is the skip/rewind policy
    knob: K consecutive faulted steps escalate from batch-skip to
    rewind-to-last-intact-checkpoint (``train/loop.py`` + ``launch/elastic``).
    """

    outputs: Optional[Tuple[int, ...]] = None
    names: Optional[Tuple[str, ...]] = None
    max_abs: float = float("inf")
    grads: bool = True
    loss: bool = True
    moments: bool = False
    max_grad_norm: float = float("inf")
    rewind_after: int = 3


@dataclasses.dataclass
class GuardInfo:
    """Provenance attached to a guarded plan: which leaves the guard vector's
    rows describe, and where the vector lands in the plan outputs."""

    leaves: Tuple[str, ...]
    config: GuardConfig
    out_index: int


class NumericsFault(RuntimeError):
    """A runtime numerics sentinel tripped.

    ``faults`` carries per-leaf provenance: dicts with ``leaf`` (name),
    ``kind`` (``nonfinite`` / ``absmax`` / ``grad_norm``), and ``value``.
    ``consecutive`` counts back-to-back faulted steps (the skip/rewind
    escalation counter).
    """

    def __init__(self, step: int, faults, consecutive: int = 1):
        self.step = int(step)
        self.faults = tuple(faults)
        self.consecutive = int(consecutive)
        leaves = ", ".join(
            f"{f['leaf']}[{f['kind']}={f['value']:.3g}]" for f in self.faults
        ) or "<none>"
        super().__init__(
            f"numerics fault at step {self.step} "
            f"({self.consecutive} consecutive): {leaves}"
        )


def guard_faults(config: GuardConfig, stats, leaves) -> List[Dict]:
    """Decode a guard vector into per-leaf fault records (empty = clean).

    ``stats`` is the plan's guard output — ``(2k,)`` packed as
    ``[nonfinite, absmax]`` per leaf — already reduced across devices.
    """
    a = np.asarray(stats, dtype=np.float64).reshape(len(leaves), 2)
    faults: List[Dict] = []
    for name, (nonfin, amax) in zip(leaves, a):
        if nonfin > 0 or not np.isfinite(amax):
            faults.append({"leaf": name, "kind": "nonfinite",
                           "value": float(nonfin)})
        elif amax > config.max_abs:
            faults.append({"leaf": name, "kind": "absmax",
                           "value": float(amax)})
    return faults


def _guard_stat_run(env, reads, writes):
    from jax import numpy as jnp

    x = jnp.asarray(_read(env, reads[0]))
    nonfin = jnp.sum(~jnp.isfinite(x)).astype(jnp.float32)
    amax = (jnp.max(jnp.abs(x.astype(jnp.float32)))
            if x.size else jnp.float32(0.0))
    _write(env, writes[0], jnp.stack([nonfin, amax]))


def _guard_pack_run(env, reads, writes):
    from jax import numpy as jnp

    _write(env, writes[0], jnp.concatenate([_read(env, r) for r in reads]))


def append_guard_steps(plan: PartitionPlan, guard: GuardConfig,
                       cost_only: bool = False) -> PartitionPlan:
    """Append the numerics-sentinel epilogue to ``plan`` (in place).

    Runs *before* the optimizer pipeline so the guard's pmax is fused and
    scheduled like any other collective.  Adds one plan output (the guard
    vector) and records :class:`GuardInfo` on the plan; outputs selected by
    ``guard.outputs`` (``None`` = all non-literal outputs).
    """
    from .reshard import shard_shape as _shard_shape

    n_out = len(plan.out_keys)
    sel = guard.outputs if guard.outputs is not None else tuple(range(n_out))
    entries = []
    for pos, i in enumerate(sel):
        if not 0 <= i < n_out:
            raise ValueError(f"guard output index {i} out of range 0..{n_out - 1}")
        k = plan.out_keys[i]
        if isinstance(k, excore.Literal):
            continue
        if i >= len(plan.jaxpr.outvars):
            continue  # already-appended guard output (double guard)
        v = plan.jaxpr.outvars[i]
        name = (guard.names[pos]
                if guard.names is not None and pos < len(guard.names)
                else f"out[{i}]")
        lshape = _shard_shape(tuple(v.aval.shape), plan.out_shardings[i])
        db = int(np.dtype(v.aval.dtype).itemsize)
        entries.append((name, k, lshape, db, str(np.dtype(v.aval.dtype))))
    if not entries:
        return plan
    stat_keys = []
    for name, k, lshape, db, dt in entries:
        p = ProxyVar(f"guard:{name}")
        step = PlanStep(
            "compute", (k,), (p,), _guard_stat_run, op="guard-stat",
            lshape=lshape, dbytes=db, dtype=dt,
            # two reduction passes over the local shard (isfinite-count + absmax)
            flops=2.0 * float(np.prod(lshape or (1,))),
            wbytes=(8.0,),
        )
        if cost_only:
            step.run = _cost_only_run
        plan.steps.append(step)
        stat_keys.append(p)
    k2 = 2 * len(entries)
    packed = ProxyVar("guard:pack")
    pack = PlanStep(
        "compute", tuple(stat_keys), (packed,), _guard_pack_run,
        op="guard-pack", lshape=(k2,), dbytes=4, dtype="float32",
        wbytes=(4.0 * k2,),
    )
    if cost_only:
        pack.run = _cost_only_run
    plan.steps.append(pack)
    axes = tuple(plan.mesh.axis_names)
    gout = ProxyVar("guard:out")
    coll = PlanStep(
        "collective", (packed,), (gout,), _collective_run(axes, "max"),
        op="all-reduce", axes=axes, reduce_op="max",
        lshape=(k2,), dbytes=4, dtype="float32",
        wbytes=(4.0 * k2,),
    )
    if cost_only:
        coll.run = _cost_only_run
    plan.stats.count("all-reduce", len(axes))
    plan.steps.append(coll)
    plan.out_keys.append(gout)
    plan.out_shardings.append(replicated(plan.mesh, 1))
    plan.stats.steps = len(plan.steps)
    plan.guard = GuardInfo(
        leaves=tuple(e[0] for e in entries), config=guard,
        out_index=len(plan.out_keys) - 1,
    )
    return plan


# ---------------------------------------------------------------------------------
# fallback analysis: which dims does a formatting op actually modify?
# ---------------------------------------------------------------------------------
#
# §4.5: pad/slice/split/concatenate/rev only rewrite data along *some* dims; every
# other dim is elementwise, so its sharding can be kept.  The fallback then
# gathers only the mesh axes on modified dims instead of fully replicating.


@dataclasses.dataclass
class FallbackSpec:
    modified_dims: Tuple[int, ...]
    params: Dict  # possibly rewritten for local execution


def _slice_fallback(eqn, in_shapes) -> Optional[FallbackSpec]:
    start = tuple(eqn.params["start_indices"])
    limit = tuple(eqn.params["limit_indices"])
    strides = eqn.params.get("strides")
    strides = tuple(strides) if strides is not None else (1,) * len(start)
    shape = in_shapes[0]
    modified = tuple(
        d for d in range(len(start))
        if not (start[d] == 0 and limit[d] == shape[d] and strides[d] == 1)
    )
    return FallbackSpec(modified, dict(eqn.params))


_FALLBACK_DIMS: Dict[str, Callable] = {
    "concatenate": lambda eqn, shp: FallbackSpec(
        (eqn.params["dimension"],), dict(eqn.params)
    ),
    "rev": lambda eqn, shp: FallbackSpec(
        tuple(eqn.params["dimensions"]), dict(eqn.params)
    ),
    "pad": lambda eqn, shp: FallbackSpec(
        tuple(
            d for d, (lo, hi, interior) in enumerate(eqn.params["padding_config"])
            if lo or hi or interior
        ),
        dict(eqn.params),
    ),
    "slice": _slice_fallback,
    "split": lambda eqn, shp: FallbackSpec(
        (eqn.params["axis"],), dict(eqn.params)
    ),
}


def fallback_keep_sharding(eqn, in_shardings, mesh: Mesh) -> Optional[Tuple[Sharding, Dict]]:
    """If the op only modifies some dims, return (operand target sharding with
    unmodified dims kept, locally-rewritten params); else None (gather all).

    Only applies when every same-rank operand can agree on the kept dims (the
    merged sharding) and any rewritten params stay exact under sharding.
    """
    name = eqn.primitive.name
    fn = _FALLBACK_DIMS.get(name)
    if fn is None:
        return None
    rank = getattr(eqn.outvars[0].aval, "ndim", None)
    if rank is None or rank == 0:
        return None
    in_shapes = [getattr(v.aval, "shape", ()) for v in eqn.invars]
    spec = fn(eqn, in_shapes)
    if spec is None:
        return None
    modified = set(spec.modified_dims)
    # merge operand shardings on the kept dims
    kept: Optional[Sharding] = None
    for v, s in zip(eqn.invars, in_shardings):
        if getattr(v.aval, "ndim", None) != rank:
            continue
        masked = Sharding(
            mesh,
            tuple(
                () if d in modified else s.dims_mapping[d] for d in range(rank)
            ),
        )
        if kept is None:
            kept = masked
        else:
            m = merge_shardings(kept, masked)
            kept = m if m is not None else kept
    if kept is None or kept.is_fully_replicated():
        return None  # nothing to keep; plain gather-all is equivalent
    params = spec.params
    if name == "slice":
        # rewrite full-dim slices to local extents on kept sharded dims
        start = list(params["start_indices"])
        limit = list(params["limit_indices"])
        for d in range(rank):
            n = kept.num_shards(d)
            if d not in modified and n > 1:
                if in_shapes[0][d] % n:
                    return None
                limit[d] = in_shapes[0][d] // n
        params = dict(params, start_indices=tuple(start), limit_indices=tuple(limit))
    return kept, params


# ---------------------------------------------------------------------------------
# gather / scatter-add per shard (GSPMD's partitioning of index ops)
# ---------------------------------------------------------------------------------
#
# A gather reads whole rows of its operand at run-time indices; scatter-add,
# its transpose, adds rows in.  Operand dims the indices do not address pass
# through and operand batching dims pair with index batch dims, so both keep
# their sharding.  A mesh axis that splits an *indexed* dim leaves each shard
# a range of rows: the shard offsets the indices by the range's start and
# masks those outside it, so a gather yields zeros there and a trailing psum
# over the axis adds the one shard that held each row; scatter-add drops the
# masked updates and exchanges nothing.  Updates split over an axis the
# operand does not use leave partial sums, which the trailing psum adds.


@dataclasses.dataclass
class IndexShards:
    """How one gather / scatter-add step finds its rows per shard; its run
    reads it while traced, and ``plan_verify`` re-simulates it."""

    operand_shape: Tuple[int, ...]  # global
    operand_dims: Tuple[Tuple[str, ...], ...]  # the operand's dims_mapping
    indexed: Tuple[int, ...]  # operand dim of each index-vector component
    strides: Tuple[int, ...]  # per component: shard i holds rows from i*stride
    partial_axes: Tuple[str, ...]  # the step leaves partial sums over these


def _shard_index(mesh: Mesh, axes: Tuple[str, ...]):
    """Position of this device's shard along a dim split over ``axes``
    (the first axis major, as ``execute_program`` lays stacked axes)."""
    i = 0
    for a in axes:
        i = i * mesh.axis_size(a) + lax.axis_index(a)
    return i


def _localize(idx, spec: IndexShards, mesh: Mesh, clip: bool):
    """Indices into this shard's rows, the mask of those it holds (None
    where no indexed dim is split) and, for ``clip=False``, the mask of
    those in bounds of the whole dim.  Components on a split dim are
    clamped to the whole dim first where ``clip``; the others keep their
    global index, which the local op's mode treats as the unsplit op's."""
    from jax import numpy as jnp

    cols, held, inside = [], None, None

    def both(a, b):
        return b if a is None else a & b

    for k, d in enumerate(spec.indexed):
        c = idx[..., k]
        axes = spec.operand_dims[d]
        if axes and clip:
            c = jnp.clip(c, 0, spec.operand_shape[d] - 1)
        elif axes:
            inside = both(inside, (c >= 0) & (c < spec.operand_shape[d]))
        if axes:
            c = c - _shard_index(mesh, axes) * spec.strides[k]
            held = both(held, (c >= 0) & (c < spec.strides[k]))
            c = jnp.clip(c, 0, spec.strides[k] - 1)
        cols.append(c)
    return jnp.stack(cols, -1).astype(idx.dtype), held, inside


def _gather_run(spec: IndexShards, mesh: Mesh, prim, bind_params, rest,
                fill):
    """Local gather of the rows this shard holds, zeros elsewhere; where a
    global index is out of bounds the first shard gives ``fill``."""
    from jax import numpy as jnp

    clip = bind_params["mode"] != lax.GatherScatterMode.FILL_OR_DROP

    def run(env, reads, writes):
        op, idx = _read(env, reads[0]), _read(env, reads[1])
        idx, held, inside = _localize(idx, spec, mesh, clip)
        out = prim.bind(op, idx, **bind_params)
        if held is not None:
            out = jnp.where(lax.broadcast_in_dim(held, out.shape, rest), out,
                            jnp.zeros((), out.dtype))
        if inside is not None:
            axes = [a for d in spec.indexed for a in spec.operand_dims[d]]
            first = sum(lax.axis_index(a) for a in axes) == 0
            out = jnp.where(lax.broadcast_in_dim(inside, out.shape, rest),
                            out, jnp.where(first, fill, 0).astype(out.dtype))
        _write(env, writes[0], out)

    return run


def _scatter_run(spec: IndexShards, mesh: Mesh, prim, subfuns, bind_params,
                 rest):
    """Local scatter-add of the updates whose rows this shard holds."""
    from jax import numpy as jnp

    clip = bind_params["mode"] == lax.GatherScatterMode.CLIP

    def run(env, reads, writes):
        op, idx, upd = (_read(env, r) for r in reads)
        idx, held, _ = _localize(idx, spec, mesh, clip)
        if held is not None:
            upd = jnp.where(lax.broadcast_in_dim(held, upd.shape, rest), upd,
                            jnp.zeros((), upd.dtype))
        _write(env, writes[0], prim.bind(*subfuns, op, idx, upd,
                                         **bind_params))

    return run


# ---------------------------------------------------------------------------------
# the builder: abstract interpretation over shardings, emitting steps
# ---------------------------------------------------------------------------------


# calls lowered to inner plans (``PlanBuilder._jit``) whose XLA lowering
# runs the body under a scope of its own
CALL_SCOPES = {"closed_call": "closed_call", "remat2": "checkpoint"}


class PlanBuilder:
    """Walks a propagated jaxpr once and emits resolved execution steps.

    Mirrors ``SpmdPartitioner``'s per-op semantics, but every decision that
    the dynamic path makes while tracing (merge targets, reshard sequences,
    psum-vs-scatter, fallback gathers) is made here, at plan time, from
    shardings and static shapes alone.

    Reshards of operands and trailing partial-sum collectives are emitted as
    *separate* steps (not folded into compute closures) so the optimizer
    pipeline in ``plan_opt`` can CSE, eliminate, and bucket them.
    """

    def __init__(
        self,
        jaxpr: excore.Jaxpr,
        consts,
        prop: PropagationResult,
        mesh: Mesh,
        stats: Optional[PlanStats] = None,
        optimize: bool = True,
        cost_only: bool = False,
        trips: int = 1,
    ):
        self.jaxpr = jaxpr
        self.consts = tuple(consts)
        self.prop = prop
        self.mesh = mesh
        self.sh: Dict[excore.Var, Sharding] = {}
        self.steps: List[PlanStep] = []
        self.stats = stats if stats is not None else PlanStats()
        self.optimize = optimize
        self.cost_only = cost_only
        self.trips = trips  # executions of this body per plan execution

    # -- sharding/shape bookkeeping ---------------------------------------------
    def sharding_of(self, v) -> Sharding:
        if isinstance(v, excore.Literal):
            return replicated(self.mesh, np.ndim(v.val))
        return self.sh[v]

    def _gshape(self, v) -> Tuple[int, ...]:
        if isinstance(v, excore.Literal):
            return tuple(np.shape(v.val))
        return tuple(v.aval.shape)

    def _lshape(self, v) -> Tuple[int, ...]:
        return shard_shape(self._gshape(v), self.sharding_of(v))

    def _dbytes(self, v) -> int:
        if isinstance(v, excore.Literal):
            return int(np.asarray(v.val).dtype.itemsize)
        return int(np.dtype(v.aval.dtype).itemsize)

    def _dtype(self, v) -> str:
        if isinstance(v, excore.Literal):
            return str(np.asarray(v.val).dtype)
        return str(np.dtype(v.aval.dtype))

    def set_sharding(self, v, s: Sharding) -> None:
        if isinstance(v, core.DropVar):
            return
        self.sh[v] = s

    def _account(self, prog, lshape, dbytes) -> None:
        self.stats.add_program(prog)
        # price the same move under both reference schedules so
        # BENCH_plan.json can track honest deltas: the AllGather-first
        # expression (replicate, then re-slice) and the pre-planner greedy
        # schedule (which already used AllToAll for innermost moves)
        from .collective_planner import (
            _candidate_gather_all, _candidate_legacy, simulate,
        )

        for attr, gen in (
            ("baseline_bytes", _candidate_gather_all),
            ("legacy_bytes", _candidate_legacy),
        ):
            cost = prog.cost_bytes  # candidate inexpressible: no claimed saving
            try:
                steps = gen(prog.src, prog.dst, lshape)
                if steps is not None:
                    cost = simulate(prog.src, prog.dst, steps, lshape, dbytes)
            except PlanError:
                pass
            setattr(self.stats, attr, getattr(self.stats, attr) + cost)

    # -- step emission helpers ---------------------------------------------------
    def emit(self, step: PlanStep) -> None:
        if self.cost_only:
            step.run = _cost_only_run
        if not step.wbytes:
            # memory model: local bytes of each written value.  Vars with a
            # recorded sharding are exact; proxies without an explicit hint
            # from the handler fall back to the step's input bytes.
            wb = []
            for w in step.writes:
                if (not isinstance(w, (ProxyVar, core.DropVar))
                        and w in self.sh
                        and hasattr(w, "aval")):
                    wb.append(_nbytes_of(
                        shard_shape(tuple(w.aval.shape), self.sh[w]),
                        self._dbytes(w)))
                else:
                    wb.append(step.in_bytes)
            step.wbytes = tuple(wb)
        self.steps.append(step)

    def emit_reshard(self, src_key, out_key, prog: ReshardProgram,
                     lshape: Tuple[int, ...], dbytes: int, dtype: str) -> None:
        # local size after the program: gathers grow the shard, slices shrink it
        factor = 1.0
        for s in prog.steps:
            n = self.mesh.axis_size(s.axis)
            if s.op == "all_gather":
                factor *= n
            elif s.op == "dynamic_slice":
                factor /= n
        self.emit(PlanStep(
            "reshard", (src_key,), (out_key,), _reshard_run(prog),
            op="reshard", program=prog, lshape=lshape, dbytes=dbytes, dtype=dtype,
            wbytes=(_nbytes_of(lshape, dbytes) * factor,),
        ))

    def emit_collective(self, src_key, out_key, axes: Tuple[str, ...],
                        reduce_op: str, lshape: Tuple[int, ...], dbytes: int,
                        dtype: str) -> None:
        self.emit(PlanStep(
            "collective", (src_key,), (out_key,), _collective_run(axes, reduce_op),
            op="all-reduce", axes=axes, reduce_op=reduce_op,
            lshape=lshape, dbytes=dbytes, dtype=dtype,
            wbytes=(_nbytes_of(lshape, dbytes),),
        ))

    def reshard_operand(self, v, tgt: Sharding):
        """Reshard operand ``v`` to ``tgt`` via a first-class reshard step.

        Returns the env key holding the resharded value (``v`` itself when the
        current sharding already matches).  Each call emits its own step — CSE
        of duplicates is deliberately left to the optimizer pass so the
        benchmark can report what it saved.
        """
        return self._reshard(v, tgt)[0]

    def _reshard(self, v, tgt: Sharding):
        """:meth:`reshard_operand`, also returning the program (None when
        nothing moves) with the local shape and dtype bytes it ran on."""
        cur = self.sharding_of(v)
        if cur.dims_mapping == tgt.dims_mapping:
            return v, None
        lshape, dbytes = self._lshape(v), self._dbytes(v)
        prog = plan_reshard(cur, tgt, lshape, dbytes)
        self._account(prog, lshape, dbytes)
        proxy = ProxyVar(f"reshard:{cur}->{tgt}")
        self.emit_reshard(v, proxy, prog, lshape, dbytes, self._dtype(v))
        return proxy, (prog, lshape, dbytes)

    def _emit_program(self, src_key, out_key, prog: Optional[ReshardProgram],
                      lshape, dbytes, dtype) -> object:
        """Emit a pre-planned program (already accounted) as a reshard step."""
        if prog is None or prog.is_identity:
            return src_key
        self.emit_reshard(src_key, out_key, prog, lshape, dbytes, dtype)
        return out_key

    # -- driver -------------------------------------------------------------------
    def build(self) -> PartitionPlan:
        for v, c in zip(self.jaxpr.constvars, self.consts):
            self.set_sharding(v, replicated(self.mesh, np.ndim(c)))
        for v in self.jaxpr.invars:
            sh = self.prop.get(v) or replicated(self.mesh, v.aval.ndim)
            self.set_sharding(v, sh)
        in_shardings = [self.sh[v] for v in self.jaxpr.invars]
        for idx, eqn in enumerate(self.jaxpr.eqns):
            self.stats.eqns += 1
            first = len(self.steps)
            self.eqn(idx, eqn)
            # every step emitted for the equation (its reshards and trailing
            # collectives too) carries the equation's scopes
            ns = eqn_name_stack(eqn)
            for step in self.steps[first:]:
                step.name_stack = ns
            scope = CALL_SCOPES.get(eqn.primitive.name)
            if scope and len(self.steps) > first:
                # the call step, emitted last, runs its body under the scope
                # XLA lowering gives it, as plain jit's op names have it
                self.steps[-1].name_stack = eqn.source_info.name_stack.extend(
                    scope)
        # output epilogue: reshards to the propagated output shardings are
        # first-class steps writing proxy keys, so CSE/DCE/fusion price them
        out_shardings: List[Sharding] = []
        out_keys: List[object] = []
        for v in self.jaxpr.outvars:
            cur = self.sharding_of(v)
            want = self.prop.get(v) or replicated(self.mesh, len(self._gshape(v)))
            key: object = v
            if not isinstance(v, excore.Literal) and cur.dims_mapping != want.dims_mapping:
                lshape, dbytes = self._lshape(v), self._dbytes(v)
                prog = plan_reshard(cur, want, lshape, dbytes)
                self._account(prog, lshape, dbytes)
                key = ProxyVar(f"out:{cur}->{want}")
                self.emit_reshard(v, key, prog, lshape, dbytes, self._dtype(v))
            out_keys.append(key)
            out_shardings.append(want)
        self.stats.steps = len(self.steps)
        plan = PartitionPlan(
            self.jaxpr, self.consts, self.mesh, self.steps,
            in_shardings, out_shardings, out_keys, self.stats,
        )
        # the optimizer pipeline recomputes the peak after its passes; only
        # pay for the liveness walk here when no optimization will follow
        if not self.optimize:
            plan.peak_bytes = plan_peak_bytes(plan)
        return plan

    # -- per-equation lowering ----------------------------------------------------
    def eqn(self, idx: int, eqn) -> None:
        prim = eqn.primitive
        name = prim.name
        if prim is annotate_p:
            self._annotate(eqn)
        elif name == "dot_general":
            self._dot(eqn)
        elif name in ELEMENTWISE or name in ("select_n", "convert_element_type"):
            self._elementwise(eqn)
        elif name.startswith("reduce_") and "window" not in name:
            self._reduce(eqn)
        elif name == "transpose":
            self._transpose(eqn)
        elif name == "broadcast_in_dim":
            self._broadcast(eqn)
        elif name == "reshape":
            self._reshape(eqn)
        elif name == "conv_general_dilated":
            self._conv(eqn)
        elif name in ("jit", "closed_call", "remat2"):
            self._jit(idx, eqn)
        elif name == "scan":
            self._scan(idx, eqn)
        elif name == "stage_shift":
            self._stage_shift(eqn)
        elif name == "iota":
            self._iota(eqn)
        elif name in ("gather", "scatter-add") and index_dims(eqn) is not None:
            self._index_op(eqn, index_dims(eqn))
        else:
            calls = self.stats.kernel_calls
            for k, n in kernel_calls([eqn]).items():
                calls[k] = calls.get(k, 0) + n * self.trips
            self._fallback(eqn)

    def _annotate(self, eqn) -> None:
        iv, ov = eqn.invars[0], eqn.outvars[0]
        tgt = eqn.params["sharding"]
        cur = self.sharding_of(iv)
        self.set_sharding(ov, tgt)
        if cur.dims_mapping == tgt.dims_mapping:
            self.emit(PlanStep("compute", (iv,), (ov,), _alias_run, op="annotate"))
            return
        lshape, dbytes = self._lshape(iv), self._dbytes(iv)
        prog = plan_reshard(cur, tgt, lshape, dbytes)
        self._account(prog, lshape, dbytes)
        self.emit_reshard(iv, ov, prog, lshape, dbytes, self._dtype(iv))

    def _dot(self, eqn) -> None:
        import string

        (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
        lv, rv = eqn.invars[0], eqn.invars[1]
        ls, rs = self.sharding_of(lv), self.sharding_of(rv)
        lrank, rrank = len(self._gshape(lv)), len(self._gshape(rv))
        letters = iter(string.ascii_lowercase)
        l_names = [next(letters) for _ in range(lrank)]
        r_names: List[Optional[str]] = [None] * rrank
        for i, j in zip(lb, rb):
            r_names[j] = l_names[i]
        for i, j in zip(lc, rc):
            r_names[j] = l_names[i]
        for j in range(len(r_names)):
            if r_names[j] is None:
                r_names[j] = next(letters)
        l_nc = [i for i in range(len(l_names)) if i not in lc and i not in lb]
        r_nc = [j for j in range(len(r_names)) if j not in rc and j not in rb]
        out_names = (
            [l_names[i] for i in lb] + [l_names[i] for i in l_nc] + [r_names[j] for j in r_nc]
        )
        spec = f"{''.join(l_names)},{''.join(r_names)}->{''.join(out_names)}"
        want = self.prop.get(eqn.outvars[0])
        eplan = compile_einsum(
            spec, ls, rs, want, self._lshape(lv), self._lshape(rv), self._dbytes(lv)
        )
        for prog in (eplan.lhs_program, eplan.rhs_program, eplan.out_program):
            self.stats.add_program(prog)
        for _ in eplan.scatter:
            self.stats.count("reduce-scatter")
        for _ in eplan.reduce_axes:
            self.stats.count("all-reduce")
        pet = eqn.params.get("preferred_element_type")
        ov = eqn.outvars[0]
        self.set_sharding(ov, eplan.final_sharding)
        odt = self._dtype(ov)
        odb = self._dbytes(ov)

        # operand reshards as first-class steps (CSE candidates)
        lk = self._emit_program(lv, ProxyVar("dot.lhs"), eplan.lhs_program,
                                self._lshape(lv), self._dbytes(lv), self._dtype(lv))
        rk = self._emit_program(rv, ProxyVar("dot.rhs"), eplan.rhs_program,
                                self._lshape(rv), self._dbytes(rv), self._dtype(rv))
        # local shape of the partial result at the psum point (post-scatter)
        pre_out_sh = (
            eplan.out_program.src if eplan.out_program is not None
            else eplan.final_sharding
        )
        zshape = shard_shape(tuple(ov.aval.shape), pre_out_sh)
        # per-device local FLOPs: 2 · |local output| · |local contraction|
        k_local = 1.0
        lhs_local = eplan.lhs_local if eplan.lhs_local is not None else ls
        for ci in lc:
            k_local *= self._gshape(lv)[ci] / max(lhs_local.num_shards(ci), 1)
        local_flops = 2.0 * float(np.prod(zshape or (1,))) * k_local
        # einsum + scatter stay in one compute step; trailing AllReduce and the
        # output reshard become their own steps (bucketing / CSE candidates)
        exec_plan = dataclasses.replace(
            eplan, lhs_program=None, rhs_program=None, reduce_axes=(),
            out_program=None,
        )
        tail = bool(eplan.reduce_axes) or eplan.out_program is not None
        mid = ProxyVar("dot.z") if tail else ov

        def run(env, reads, writes, exec_plan=exec_plan, pet=pet):
            z, _ = execute_einsum(exec_plan, _read(env, reads[0]), _read(env, reads[1]), pet)
            _write(env, writes[0], z)

        self.emit(PlanStep("compute", (lk, rk), (mid,), run, op="dot_general",
                           flops=local_flops,
                           wbytes=(_nbytes_of(zshape, odb),)))
        cur_key = mid
        if eplan.reduce_axes:
            nxt = ov if eplan.out_program is None else ProxyVar("dot.psum")
            self.emit_collective(cur_key, nxt, tuple(eplan.reduce_axes), "add",
                                 zshape, odb, odt)
            cur_key = nxt
        if eplan.out_program is not None:
            self.emit_reshard(cur_key, ov, eplan.out_program, zshape, odb, odt)

    def _elementwise(self, eqn) -> None:
        ov0 = eqn.outvars[0]
        rank = ov0.aval.ndim
        out_shape = tuple(ov0.aval.shape)

        def mask_bcast(v, s: Sharding) -> Sharding:
            # a size-1 broadcast dim cannot carry the merged sharding: every
            # shard needs the (single) value, so the dim must stay replicated
            shape = self._gshape(v)
            return Sharding(self.mesh, tuple(
                s.dims_mapping[d] if shape[d] == out_shape[d] else ()
                for d in range(rank)
            ))

        tgt: Optional[Sharding] = None
        for v in eqn.invars:
            if len(self._gshape(v)) == rank:
                s = mask_bcast(v, self.sharding_of(v))
                tgt = s if tgt is None else (merge_shardings(tgt, s) or tgt)
        if tgt is None:
            tgt = replicated(self.mesh, rank)
        keys = tuple(
            self.reshard_operand(v, mask_bcast(v, tgt))
            if len(self._gshape(v)) == rank else v
            for v in eqn.invars
        )
        subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)
        prim, outvars = eqn.primitive, tuple(eqn.outvars)
        for ov in outvars:
            self.set_sharding(ov, tgt)
        local_out = float(np.prod(shard_shape(out_shape, tgt) or (1,)))

        def run(env, reads, writes, prim=prim, subfuns=subfuns, bind_params=bind_params):
            vals = [_read(env, k) for k in reads]
            out = prim.bind(*subfuns, *vals, **bind_params)
            outs = out if prim.multiple_results else [out]
            for w, o in zip(writes, outs):
                _write(env, w, o)

        self.emit(PlanStep("compute", keys, outvars, run, op=prim.name,
                           flops=local_out * len(outvars)))

    def _reduce(self, eqn) -> None:
        iv, ov = eqn.invars[0], eqn.outvars[0]
        sh = self.sharding_of(iv)
        axes = eqn.params["axes"]
        subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)
        prim = eqn.primitive
        psum_axes = tuple(a for d in axes for a in sh.dims_mapping[d])
        kept = [i for i in range(sh.rank) if i not in axes]
        osh = Sharding(self.mesh, tuple(sh.dims_mapping[i] for i in kept))
        name = prim.name
        key = iv
        if psum_axes and name not in ("reduce_sum", "reduce_max", "reduce_min"):
            # prod/and/or: gather the reduced axes first, reduce locally
            key = self.reshard_operand(iv, replicated(self.mesh, sh.rank))
            psum_axes = ()
            osh = replicated(self.mesh, len(kept))
        elif psum_axes:
            self.stats.count("all-reduce", len(psum_axes))
        self.set_sharding(ov, osh)
        mid = ProxyVar("reduce.local") if psum_axes else ov

        def run(env, reads, writes, prim=prim, subfuns=subfuns, bind_params=bind_params):
            _write(env, writes[0], prim.bind(*subfuns, _read(env, reads[0]), **bind_params))

        in_local = (
            shard_shape(self._gshape(iv), replicated(self.mesh, sh.rank))
            if key is not iv else self._lshape(iv)
        )
        self.emit(PlanStep(
            "compute", (key,), (mid,), run, op=name,
            flops=float(np.prod(in_local or (1,))),
            wbytes=(_nbytes_of(shard_shape(tuple(ov.aval.shape), osh),
                               self._dbytes(ov)),),
        ))
        if psum_axes:
            reduce_op = {"reduce_sum": "add", "reduce_max": "max", "reduce_min": "min"}[name]
            self.emit_collective(
                mid, ov, psum_axes, reduce_op,
                shard_shape(tuple(ov.aval.shape), osh), self._dbytes(ov), self._dtype(ov),
            )

    def _transpose(self, eqn) -> None:
        iv, ov = eqn.invars[0], eqn.outvars[0]
        perm = eqn.params["permutation"]
        sh = self.sharding_of(iv)
        osh = Sharding(self.mesh, tuple(sh.dims_mapping[i] for i in perm))
        self.set_sharding(ov, osh)

        def run(env, reads, writes, perm=perm):
            _write(env, writes[0], lax.transpose(_read(env, reads[0]), perm))

        self.emit(PlanStep("compute", (iv,), (ov,), run, op="transpose"))

    def _broadcast(self, eqn) -> None:
        iv, ov = eqn.invars[0], eqn.outvars[0]
        sh = self.sharding_of(iv)
        bcast = eqn.params["broadcast_dimensions"]
        gshape = eqn.params["shape"]
        out_rank = len(gshape)
        dm: List[Tuple[str, ...]] = [() for _ in range(out_rank)]
        in_shape = self._gshape(iv)
        for i, j in enumerate(bcast):
            if in_shape[i] == gshape[j]:
                dm[j] = sh.dims_mapping[i]
        osh = Sharding(self.mesh, tuple(dm))
        local_shape = shard_shape(tuple(gshape), osh)
        self.set_sharding(ov, osh)

        def run(env, reads, writes, local_shape=local_shape, bcast=bcast):
            _write(env, writes[0],
                   lax.broadcast_in_dim(_read(env, reads[0]), local_shape, bcast))

        self.emit(PlanStep("compute", (iv,), (ov,), run, op="broadcast_in_dim"))

    def _reshape(self, eqn) -> None:
        iv, ov = eqn.invars[0], eqn.outvars[0]
        sh = self.sharding_of(iv)
        want = self.prop.get(ov)
        gshape = tuple(eqn.params["new_sizes"])
        dims = eqn.params.get("dimensions")
        if want is not None:
            local = shard_shape(gshape, want)
            if int(np.prod(self._lshape(iv) or (1,))) == int(np.prod(local or (1,))):
                self.set_sharding(ov, want)

                def run(env, reads, writes, local=local, dims=dims):
                    _write(env, writes[0], lax.reshape(_read(env, reads[0]), local, dims))

                self.emit(PlanStep("compute", (iv,), (ov,), run, op="reshape"))
                return
        # fallback: gather, reshape globally, re-slice
        key = self.reshard_operand(iv, replicated(self.mesh, sh.rank))
        osh = want or replicated(self.mesh, len(gshape))
        slice_prog = None
        if osh.dims_mapping != replicated(self.mesh, len(gshape)).dims_mapping:
            slice_prog = plan_reshard(
                replicated(self.mesh, len(gshape)), osh, gshape, self._dbytes(iv)
            )
            self.stats.add_program(slice_prog)
        self.set_sharding(ov, osh)
        mid = ProxyVar("reshape.global") if slice_prog is not None else ov

        def run(env, reads, writes, gshape=gshape, dims=dims):
            _write(env, writes[0], lax.reshape(_read(env, reads[0]), gshape, dims))

        self.emit(PlanStep("compute", (key,), (mid,), run, op="reshape"))
        if slice_prog is not None:
            self.emit_reshard(mid, ov, slice_prog, gshape, self._dbytes(iv), self._dtype(iv))

    def _conv(self, eqn) -> None:
        lv, rv = eqn.invars[0], eqn.invars[1]
        ov = eqn.outvars[0]
        ls, rs = self.sharding_of(lv), self.sharding_of(rv)
        rk = self.reshard_operand(rv, replicated(self.mesh, rs.rank))
        dn = eqn.params["dimension_numbers"]
        assert dn.lhs_spec[0] == 0 and dn.lhs_spec[1] == 1, "NC*spatial layout only"
        strides = eqn.params["window_strides"]
        padding = eqn.params["padding"]
        if ls.dims_mapping[1]:
            # feature-dim sharded: contract locally then psum (Megatron-style)
            ax = ls.dims_mapping[1]
            n = self.mesh.axis_size(ax[0])
            osh = Sharding(
                self.mesh, (ls.dims_mapping[0], ()) + ((),) * (ls.rank - 2)
            )
            # per-axis, matching _reduce/_dot (and the fusion pass's
            # len(group)·len(axes) decrement on bucketing)
            self.stats.count("all-reduce", len(ax))
            self.set_sharding(ov, osh)
            mid = ProxyVar("conv.partial")

            def run(env, reads, writes, ax=ax, n=n, strides=strides, padding=padding):
                lval, rval = _read(env, reads[0]), _read(env, reads[1])
                idx = lax.axis_index(ax[0])
                size = rval.shape[1] // n
                rv_local = lax.dynamic_slice_in_dim(rval, idx * size, size, axis=1)
                out = lax.conv_general_dilated(
                    lval, rv_local, window_strides=strides, padding=padding
                )
                _write(env, writes[0], out)

            rsh = self._gshape(rv)
            k_per_out = (int(np.prod(rsh)) // max(rsh[0], 1)) / max(n, 1)
            out_local = shard_shape(tuple(ov.aval.shape), osh)
            self.emit(PlanStep(
                "compute", (lv, rk), (mid,), run, op="conv",
                flops=2.0 * float(np.prod(out_local or (1,))) * k_per_out,
                wbytes=(_nbytes_of(out_local, self._dbytes(ov)),),
            ))
            self.emit_collective(
                mid, ov, ax, "add",
                shard_shape(tuple(ov.aval.shape), osh), self._dbytes(ov), self._dtype(ov),
            )
            return
        sharded = [
            (d, ls.dims_mapping[d][0]) for d in range(2, ls.rank) if ls.dims_mapping[d]
        ]
        self.set_sharding(ov, Sharding(self.mesh, tuple(ls.dims_mapping)))

        def run(env, reads, writes, sharded=sharded, strides=strides, padding=padding):
            from .halo import sharded_conv_nd

            lval, rval = _read(env, reads[0]), _read(env, reads[1])
            _write(
                env, writes[0],
                sharded_conv_nd(
                    lval, rval, sharded=sharded,
                    window_strides=strides, padding=padding,
                ),
            )

        rsh = self._gshape(rv)
        out_local = shard_shape(tuple(ov.aval.shape), self.sharding_of(ov))
        self.emit(PlanStep(
            "compute", (lv, rk), (ov,), run, op="conv",
            flops=2.0 * float(np.prod(out_local or (1,)))
            * (int(np.prod(rsh)) // max(rsh[0], 1)),
        ))

    def _stage_shift(self, eqn) -> None:
        """§3.3 shifting buffer: ``out[0]=x, out[s]=state[s-1]`` (or the
        mirror image under ``reverse``).

        * stage dim replicated — one local concatenate, no communication;
        * stage dim on ONE mesh axis — three steps: slice the boundary stage
          row, ppermute it one position along the axis (a first-class
          ``collective`` step, so plan_opt prices/schedules/fuses it), and
          stitch the received row in front of the remaining local rows (the
          injection row replaces the received one on the edge device);
        * stage dim on stacked axes — gather the stage dim first (correct
          fallback; the pipeline subsystem never emits this layout).
        """
        from jax import numpy as jnp

        sv, xv = eqn.invars[0], eqn.invars[1]
        ov = eqn.outvars[0]
        reverse = bool(eqn.params["reverse"])
        s = self.sharding_of(sv)
        # the injected row must agree with the state's trailing dims and be
        # replicated along the stage axis (it enters on one edge device)
        x_tgt = Sharding(self.mesh, s.dims_mapping[1:])
        xk = self.reshard_operand(xv, x_tgt)
        axes = s.dims_mapping[0]
        n = 1
        for a in axes:
            n *= self.mesh.axis_size(a)
        if n > 1 and len(axes) > 1:
            # stacked stage axes: fall back to an unsharded stage dim
            s = s.with_dim(0, ())
            sk = self.reshard_operand(sv, s)
            axes, n = (), 1
        else:
            sk = sv
        self.set_sharding(ov, s)
        lshape = shard_shape(self._gshape(sv), s)
        dbytes, dtype = self._dbytes(sv), self._dtype(sv)
        out_bytes = _nbytes_of(lshape, dbytes)
        if n <= 1:
            # local shift: the full stage dim lives on every device
            def run(env, reads, writes, reverse=reverse):
                st, x = _read(env, reads[0]), _read(env, reads[1])
                if reverse:
                    _write(env, writes[0],
                           jnp.concatenate([st[1:], x[None]], axis=0))
                else:
                    _write(env, writes[0],
                           jnp.concatenate([x[None], st[:-1]], axis=0))

            self.emit(PlanStep(
                "compute", (sk, xk), (ov,), run, op="stage_shift",
                lshape=lshape, dbytes=dbytes, dtype=dtype,
                flops=float(np.prod(lshape or (1,))), wbytes=(out_bytes,),
            ))
            return
        ax = axes[0]
        bshape = (1,) + tuple(lshape[1:])
        bbytes = _nbytes_of(bshape, dbytes)
        # step 1: boundary row (last local stage row forward, first reverse)
        bproxy = ProxyVar("shift.boundary")

        def run_b(env, reads, writes, reverse=reverse):
            st = _read(env, reads[0])
            _write(env, writes[0], st[:1] if reverse else st[-1:])

        self.emit(PlanStep(
            "compute", (sk,), (bproxy,), run_b, op="shift-boundary",
            lshape=lshape, dbytes=dbytes, dtype=dtype, wbytes=(bbytes,),
        ))
        # step 2: one neighbor hop along the stage axis — a pure collective
        perm = tuple(
            (i + 1, i) for i in range(n - 1)
        ) if reverse else tuple((i, i + 1) for i in range(n - 1))
        rproxy = ProxyVar("shift.recv")

        def run_p(env, reads, writes, ax=ax, perm=perm):
            _write(env, writes[0], lax.ppermute(_read(env, reads[0]), ax,
                                                list(perm)))

        self.stats.count("collective-permute")
        self.emit(PlanStep(
            "collective", (bproxy,), (rproxy,), run_p, op="ppermute",
            axes=(ax,), lshape=bshape, dbytes=dbytes, dtype=dtype,
            wbytes=(bbytes,), call={"perm": perm},
        ))
        # step 3: stitch — edge device takes the injected row instead
        def run_c(env, reads, writes, ax=ax, n=n, reverse=reverse):
            recv, st, x = (_read(env, reads[0]), _read(env, reads[1]),
                           _read(env, reads[2]))
            idx = lax.axis_index(ax)
            if reverse:
                row = jnp.where(idx == n - 1, x, recv[0])
                out = jnp.concatenate([st[1:], row[None]], axis=0)
            else:
                row = jnp.where(idx == 0, x, recv[0])
                out = jnp.concatenate([row[None], st[:-1]], axis=0)
            _write(env, writes[0], out)

        self.emit(PlanStep(
            "compute", (rproxy, sk, xk), (ov,), run_c, op="shift-stitch",
            lshape=lshape, dbytes=dbytes, dtype=dtype,
            flops=float(np.prod(lshape or (1,))), wbytes=(out_bytes,),
        ))

    def _index_op(self, eqn, dims: IndexDims) -> None:
        """A gather or scatter-add per shard (see :class:`IndexShards`).

        The operand keeps its layout (a scatter's is its output's, as
        propagated); batching dims take the operand's axes, else the
        indices'; window dims the operand's; every other index dim keeps
        the axes it has (a scatter's updates', else the indices'), less
        those the operand or an earlier dim already uses.  The indices and
        the updates are resharded to that."""
        scatter = eqn.primitive.name == "scatter-add"
        opv, iv = eqn.invars[0], eqn.invars[1]
        ov = eqn.outvars[0]
        rv = eqn.invars[2] if scatter else ov  # the updates / the output
        op_sh = self.sharding_of(opv)
        if scatter:
            op_sh = self.prop.get(ov) or op_sh
        op_dm = list(op_sh.dims_mapping)
        idx_cur = self.sharding_of(iv).dims_mapping
        have = self.sharding_of(rv).dims_mapping if scatter else None
        idx_dm = [()] * len(idx_cur)
        res_dm = [()] * len(self._gshape(rv))
        used = set(op_sh.sharded_axes)

        def free(axes):
            kept = tuple(a for a in axes if a not in used)
            used.update(kept)
            return kept

        for d, i, j in dims.batching:
            if not op_dm[d] and not scatter:
                op_dm[d] = free(idx_cur[i])
            idx_dm[i] = res_dm[j] = op_dm[d]
        for d, j in dims.window:
            res_dm[j] = op_dm[d]
        for i, j in dims.batch:
            idx_dm[i] = res_dm[j] = free(have[j] if scatter else idx_cur[i])
        op_sh = Sharding(self.mesh, tuple(op_dm))
        masked = tuple(a for d in dims.indexed for a in op_dm[d])
        partial = (tuple(a for i, _ in dims.batch for a in idx_dm[i])
                   if scatter else masked)
        gshape = self._gshape(opv)
        spec = IndexShards(
            operand_shape=gshape, operand_dims=tuple(op_dm),
            indexed=dims.indexed,
            strides=tuple(gshape[d] // op_sh.num_shards(d)
                          for d in dims.indexed),
            partial_axes=partial,
        )
        res_sh = Sharding(self.mesh, tuple(res_dm))
        keys = [self.reshard_operand(opv, op_sh),
                self.reshard_operand(iv, Sharding(self.mesh, tuple(idx_dm)))]
        if scatter:
            keys.append(self.reshard_operand(rv, res_sh))
        out_sh = op_sh if scatter else res_sh
        self.set_sharding(ov, out_sh)
        lshape = shard_shape(tuple(ov.aval.shape), out_sh)
        rest = tuple(sorted(j for _, j in dims.rows))
        prim = eqn.primitive
        subfuns, bind_params = prim.get_bind_params(eqn.params)
        if scatter:
            run = _scatter_run(spec, self.mesh, prim, subfuns, bind_params,
                               rest)
            flops = float(np.prod(shard_shape(self._gshape(rv), res_sh)
                                  or (1,)))
        else:
            local = shard_shape(gshape, op_sh)
            sizes = list(bind_params["slice_sizes"])
            for d, _ in dims.window:
                sizes[d] = local[d]
            bind_params = dict(bind_params, slice_sizes=tuple(sizes))
            run = _gather_run(spec, self.mesh, prim, bind_params, rest,
                              eqn.params.get("fill_value"))
            flops = float(np.prod(lshape or (1,)))
        self.stats.sharded_gathers += 1
        mid = ProxyVar(f"{prim.name}.partial") if partial else ov
        db, dt = self._dbytes(ov), self._dtype(ov)
        self.emit(PlanStep("compute", tuple(keys), (mid,), run, op=prim.name,
                           flops=flops, wbytes=(_nbytes_of(lshape, db),),
                           index=spec))
        if partial:
            self.stats.count("all-reduce", len(partial))
            self.emit_collective(mid, ov, partial, "add", lshape, db, dt)

    def _iota(self, eqn) -> None:
        prim, params, ov = eqn.primitive, eqn.params, eqn.outvars[0]
        self.set_sharding(ov, replicated(self.mesh, len(params["shape"])))

        def run(env, reads, writes, prim=prim, params=params):
            _write(env, writes[0], prim.bind(**params))

        self.emit(PlanStep("compute", (), (ov,), run, op="iota"))

    # -- calls ---------------------------------------------------------------------
    def _inner_result(self, idx: int, closed) -> PropagationResult:
        res = self.prop.sub.get(idx)
        if res is None:
            p = Propagation(closed.jaxpr, self.mesh)
            p.seed_annotations()
            res = p.result()
        return res

    def _optimize_inner(self, plan: "PartitionPlan") -> "PartitionPlan":
        if not self.optimize:
            return plan
        from .plan_opt import optimize_plan

        return optimize_plan(plan)

    def _jit(self, idx: int, eqn) -> None:
        """A call whose value is its body's: ``jit``, ``closed_call`` and
        ``remat2`` (a differentiated checkpoint, whose saved residuals the
        jaxpr already fixes), lowered to an inner plan over local shards."""
        sub = _subjaxpr(eqn)
        inner_res = self._inner_result(idx, sub)
        # seed inner input shardings from ours where propagation left them open
        env = dict(inner_res.env)
        keys: List[object] = []
        for outer_v, iv in zip(eqn.invars, sub.jaxpr.invars):
            declared = inner_res.get(iv)
            if declared is None:
                env[iv] = self.sharding_of(outer_v)
                keys.append(outer_v)
            else:
                keys.append(self.reshard_operand(outer_v, declared))
        inner_res = PropagationResult(inner_res.jaxpr, self.mesh, env, inner_res.sub)
        builder = PlanBuilder(
            sub.jaxpr, sub.consts, inner_res, self.mesh, stats=self.stats,
            optimize=self.optimize, cost_only=self.cost_only,
            trips=self.trips,
        )
        inner_plan = self._optimize_inner(builder.build())
        for ov, osh in zip(eqn.outvars, inner_plan.out_shardings):
            self.set_sharding(ov, osh)
        outvars = tuple(eqn.outvars)

        def run(env, reads, writes, plan=inner_plan):
            outs = plan.execute(*[_read(env, k) for k in reads])
            for w, o in zip(writes, outs):
                _write(env, w, o)

        self.emit(PlanStep(
            "compute", tuple(keys), outvars, run, op="jit",
            flops=inner_plan.total_flops(),
            transient_bytes=inner_plan.peak_bytes,
            inner=inner_plan, call={"trips": 1},
        ))

    def _scan(self, idx: int, eqn) -> None:
        p = eqn.params
        nc, nk = p["num_consts"], p["num_carry"]
        closed = p["jaxpr"]
        body = closed.jaxpr
        inner_res = self._inner_result(idx, closed)

        def drop0(s: Optional[Sharding]) -> Optional[Sharding]:
            if s is None or s.rank == 0:
                return None
            return Sharding(self.mesh, s.dims_mapping[1:])

        # body input shardings: propagation's answer, else derived from ours
        env = dict(inner_res.env)
        keys: List[object] = []
        for i, (outer_v, bv) in enumerate(zip(eqn.invars, body.invars)):
            declared = inner_res.get(bv)
            ours = self.sharding_of(outer_v)
            if i >= nc + nk:
                ours = drop0(ours) or replicated(self.mesh, max(ours.rank - 1, 0))
            if declared is None:
                env[bv] = ours
                keys.append(outer_v)
            else:
                # reshard the outer operand to the body's declared sharding
                # (xs get the leading scan dim re-attached)
                tgt = declared
                if i >= nc + nk:
                    tgt = Sharding(self.mesh, ((),) + declared.dims_mapping)
                elif i >= nc:
                    tgt = declared
                keys.append(self.reshard_operand(outer_v, tgt))
        inner_res = PropagationResult(inner_res.jaxpr, self.mesh, env, inner_res.sub)
        builder = PlanBuilder(
            body, closed.consts, inner_res, self.mesh, stats=self.stats,
            optimize=self.optimize, cost_only=self.cost_only,
            trips=self.trips * (p.get("length") or 1),
        )
        inner_plan = self._optimize_inner(builder.build())
        # carry consistency: carry-out must leave the body in the carry-in
        # sharding, or iteration 2 would misread it.  PlanBuilder.build already
        # reshards body outputs to the body's *propagated* shardings; propagate's
        # carry fixed point makes those match the carry-in side.
        carry_fix: List[Optional[ReshardProgram]] = []
        for i in range(nk):
            cin_sh = inner_plan.in_shardings[nc + i]
            cout_sh = inner_plan.out_shardings[i]
            if cin_sh.dims_mapping != cout_sh.dims_mapping:
                gshape = tuple(body.outvars[i].aval.shape)
                prog = plan_reshard(
                    cout_sh, cin_sh, shard_shape(gshape, cout_sh),
                    int(np.dtype(body.outvars[i].aval.dtype).itemsize),
                )
                self.stats.add_program(prog)
                carry_fix.append(prog)
            else:
                carry_fix.append(None)
        # outer output shardings: index-based (ys get a leading unsharded dim)
        outvars = tuple(eqn.outvars)
        for i, ov in enumerate(outvars):
            if i < nk:
                osh = inner_plan.in_shardings[nc + i]
            else:
                ysh = inner_plan.out_shardings[i]
                osh = Sharding(self.mesh, ((),) + ysh.dims_mapping)
            self.set_sharding(ov, osh)
        length = p.get("length")
        reverse = bool(p.get("reverse", False))

        def run(env, reads, writes, plan=inner_plan, carry_fix=carry_fix,
                nc=nc, nk=nk, length=length, reverse=reverse):
            vals = [_read(env, k) for k in reads]
            consts = vals[:nc]
            init = tuple(vals[nc : nc + nk])
            xs = tuple(vals[nc + nk :])

            def body_fn(carry, x):
                outs = plan.execute(*consts, *carry, *x)
                new_carry = tuple(
                    execute_program(o, f) if f is not None else o
                    for o, f in zip(outs[:nk], carry_fix)
                )
                return new_carry, tuple(outs[nk:])

            # grad-of-scan lowers to a reverse scan: xs are consumed (and ys
            # emitted) back to front — replaying it forward silently permutes
            # every per-trip value
            carry, ys = lax.scan(body_fn, init, xs, length=length,
                                 reverse=reverse)
            for w, o in zip(writes, list(carry) + list(ys)):
                _write(env, w, o)

        trips = length if length is not None else 1
        self.emit(PlanStep(
            "compute", tuple(keys), outvars, run, op="scan",
            flops=trips * inner_plan.total_flops(),
            transient_bytes=inner_plan.peak_bytes,
            inner=inner_plan,
            call={"trips": int(trips), "num_consts": nc, "num_carry": nk},
        ))

    # -- fallback --------------------------------------------------------------------
    def _fallback(self, eqn) -> None:
        """Gather → op → reshard (§4.5), but only gathering the dims the op
        actually modifies when the primitive's touched-dims are known."""
        in_shardings = [self.sharding_of(v) for v in eqn.invars]
        keep = fallback_keep_sharding(eqn, in_shardings, self.mesh)
        prim = eqn.primitive
        self.stats.fallbacks[prim.name] = self.stats.fallbacks.get(prim.name, 0) + 1
        invars, outvars = list(eqn.invars), list(eqn.outvars)
        gathered = []

        def gather(v, tgt):
            key, moved = self._reshard(v, tgt)
            if moved is not None:
                gathered.append(moved)
                self.stats.fallback_bytes += self.trips * moved[0].cost_bytes
            return key

        if keep is not None:
            kept_sh, params = keep
            rank = kept_sh.rank
            keys = tuple(
                gather(v, kept_sh)
                if len(self._gshape(v)) == rank
                else gather(v, replicated(self.mesh, len(self._gshape(v))))
                for v in invars
            )
            subfuns, bind_params = prim.get_bind_params(params)
            mids: List[object] = []
            post: List[Tuple[object, object, ReshardProgram, Tuple[int, ...], int, str]] = []
            for ov in outvars:
                osh = Sharding(
                    self.mesh,
                    tuple(
                        kept_sh.dims_mapping[d] if d < rank else ()
                        for d in range(getattr(ov.aval, "ndim", 0))
                    ),
                )
                want = self.prop.get(ov) or osh
                self.set_sharding(ov, osh)
                if osh.dims_mapping != want.dims_mapping:
                    gshape = tuple(ov.aval.shape)
                    lshape = shard_shape(gshape, osh)
                    prog = plan_reshard(
                        osh, want, lshape, int(np.dtype(ov.aval.dtype).itemsize),
                    )
                    self.stats.add_program(prog)
                    self.set_sharding(ov, want)
                    mid = ProxyVar("fallback.out")
                    mids.append(mid)
                    post.append((mid, ov, prog, lshape,
                                 int(np.dtype(ov.aval.dtype).itemsize),
                                 str(np.dtype(ov.aval.dtype))))
                else:
                    mids.append(ov)

            def run(env, reads, writes, prim=prim, subfuns=subfuns, bind_params=bind_params):
                vals = [_read(env, k) for k in reads]
                out = prim.bind(*subfuns, *vals, **bind_params)
                outs = out if prim.multiple_results else [out]
                for w, o in zip(writes, outs):
                    _write(env, w, o)

            self.emit(PlanStep(
                "compute", keys, tuple(mids), run, op=prim.name,
                flops=float(sum(
                    np.prod(shard_shape(tuple(ov.aval.shape), self.sh[ov]) or (1,))
                    if ov in self.sh else 1.0
                    for ov in outvars if hasattr(ov, "aval")
                )),
                gathered=tuple(gathered),
            ))
            for mid, ov, prog, lshape, db, dt in post:
                self.emit_reshard(mid, ov, prog, lshape, db, dt)
            return
        # unknown op: full gather, global op, re-slice to the propagated sharding
        keys = tuple(
            gather(v, replicated(self.mesh, len(self._gshape(v))))
            for v in invars
        )
        subfuns, bind_params = prim.get_bind_params(eqn.params)
        mids = []
        post = []
        for ov in outvars:
            rank = getattr(ov.aval, "ndim", 0)
            want = self.prop.get(ov) or replicated(self.mesh, rank)
            self.set_sharding(ov, want)
            if want.is_fully_replicated():
                mids.append(ov)
            else:
                gshape = tuple(ov.aval.shape)
                prog = plan_reshard(
                    replicated(self.mesh, rank), want, gshape,
                    int(np.dtype(ov.aval.dtype).itemsize),
                )
                self.stats.add_program(prog)
                mid = ProxyVar("fallback.out")
                mids.append(mid)
                post.append((mid, ov, prog, gshape,
                             int(np.dtype(ov.aval.dtype).itemsize),
                             str(np.dtype(ov.aval.dtype))))

        def run(env, reads, writes, prim=prim, subfuns=subfuns, bind_params=bind_params):
            vals = [_read(env, k) for k in reads]
            out = prim.bind(*subfuns, *vals, **bind_params)
            outs = out if prim.multiple_results else [out]
            for w, o in zip(writes, outs):
                _write(env, w, o)

        self.emit(PlanStep(
            "compute", keys, tuple(mids), run, op=prim.name,
            flops=float(sum(
                np.prod(tuple(ov.aval.shape) or (1,))
                for ov in outvars if hasattr(ov, "aval")
            )),
            gathered=tuple(gathered),
        ))
        for mid, ov, prog, lshape, db, dt in post:
            self.emit_reshard(mid, ov, prog, lshape, db, dt)


# ---------------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------------


def compile_plan(
    closed: excore.ClosedJaxpr,
    prop: PropagationResult,
    mesh: Mesh,
    optimize: bool = True,
    cost_only: bool = False,
    verify: Optional[bool] = None,
    guard: Optional[GuardConfig] = None,
    profile: Optional[object] = None,
    phases: Optional[Dict[str, float]] = None,
) -> PartitionPlan:
    """Lower a propagated (closed) jaxpr into an executable PartitionPlan.

    With ``optimize=True`` (the default) the lowered plan is run through the
    whole-program optimizer pipeline (``plan_opt.optimize_plan``): jit
    inlining, scan-invariant reshard hoisting, reshard CSE, dead-reshard
    elimination, collective fusion, and overlap-aware scheduling.  The passes
    are semantics-preserving; ``optimize=False`` keeps the raw per-equation
    plan (used by benchmarks to measure what the pipeline saves).
    ``cost_only=True`` replaces every step's runner with a raising stub — the
    plan can be priced but never executed (autoshard candidate scoring).

    ``guard`` appends the numerics-sentinel epilogue
    (:func:`append_guard_steps`) *before* optimization, so the guard
    collective is fused/scheduled like any other.  ``verify`` runs the static
    plan verifier (``plan_verify.verify_plan``) on the finished plan;
    ``None`` means the module default (on unless ``REPRO_PLAN_VERIFY=0``) —
    cheap enough to leave on everywhere, including cost-only autoshard
    lowerings.

    ``profile`` attaches a calibrated
    :class:`repro.analysis.roofline.RooflineParams` to the plan *before*
    optimization, so the overlap scheduler, fusion-bucket sizing, and every
    downstream :class:`PlanCost` price with the fitted machine constants.
    ``None`` keeps the module-default constants bit-identically.

    The lowering, the optimizer passes and the verifier run inside the
    profiler spans ``repro.partition.{lower,optimize,verify}``; ``phases``,
    where given, gets their seconds under those three names.
    """
    from repro.obs.trace import span

    from .collective_planner import thread_search_telemetry

    t0 = thread_search_telemetry()
    with span("repro.partition.lower", phases):
        builder = PlanBuilder(
            closed.jaxpr, closed.consts, prop, mesh, optimize=optimize,
            cost_only=cost_only,
        )
        plan = builder.build()
        if profile is not None:
            plan.params = profile
        if guard is not None:
            append_guard_steps(plan, guard, cost_only=cost_only)
    if optimize:
        from .plan_opt import optimize_plan

        with span("repro.partition.optimize", phases):
            plan = optimize_plan(plan)
    elif guard is not None:
        # build() priced the peak before the guard epilogue existed
        plan.peak_bytes = plan_peak_bytes(plan)
    t1 = thread_search_telemetry()
    plan.stats.lattice = {k: t1[k] - t0[k] for k in t1}
    from .plan_verify import verify_enabled

    if verify_enabled(verify):
        from .plan_verify import verify_plan

        with span("repro.partition.verify", phases):
            verify_plan(plan)
    return plan


# ---------------------------------------------------------------------------------
# cost-only lowering (the autoshard scoring function)
# ---------------------------------------------------------------------------------


def plan_peak_bytes(plan: PartitionPlan) -> float:
    """Modeled per-device live-memory peak of one plan execution.

    Inputs and consts are resident for the whole step (params are not
    donated); intermediates are allocated at their producing step (each
    step's ``wbytes``) and freed after their last reader.  ``scan``/``jit``
    steps add their inner plan's peak as a transient while they run.
    """
    sizes: Dict[int, float] = {}
    resident = 0.0
    for v, s in zip(plan.jaxpr.invars, plan.in_shardings):
        b = _nbytes_of(shard_shape(tuple(v.aval.shape), s),
                       int(np.dtype(v.aval.dtype).itemsize))
        sizes[id(v)] = b
        resident += b
    for v, c in zip(plan.jaxpr.constvars, plan.consts):
        b = float(np.asarray(c).nbytes) if np.ndim(c) else float(
            np.asarray(c).dtype.itemsize)
        sizes[id(v)] = b
        resident += b
    pinned = set(sizes)  # inputs/consts never free
    last_read: Dict[int, int] = {}
    for i, step in enumerate(plan.steps):
        for k in step.reads:
            last_read[id(k)] = i
    for i, k in enumerate(plan.out_keys):
        last_read[id(k)] = len(plan.steps)  # outputs stay live to the end
    live = resident
    peak = live
    alive: Dict[int, float] = {}
    for i, step in enumerate(plan.steps):
        for w, b in zip(step.writes, step.wbytes or ()):
            if id(w) in pinned or isinstance(w, core.DropVar):
                continue
            alive[id(w)] = b
            live += b
        peak = max(peak, live + step.transient_bytes)
        for k in list(alive):
            if last_read.get(k, -1) <= i:
                live -= alive.pop(k)
    return peak


@dataclasses.dataclass
class PlanCost:
    """Whole-program modeled cost of one lowered plan (cost-only mode).

    The scalar objective (:attr:`total_s`) is **max-of-terms**: the roofline
    overlap time of the per-device compute term (FLOPs / peak — the actual
    per-device work, so sharding imbalance raises it directly) and the
    collective term (wire bytes / ICI bandwidth + per-launch overhead),
    combined by :func:`repro.analysis.roofline.overlap_time_s` — the dominant
    term bounds the step, the smaller one is mostly hidden behind it.

    ``wire_bytes`` / ``launches`` are **whole-program**: inner jit/scan plans
    contribute at trip count (a psum a scan body replays L times costs L
    launches here), matching ``total_flops``'s trip-multiplied compute — this
    is what makes pipeline-loop pricing honest (per-tick ppermute/psum × the
    ``M + S − 1`` tick count).

    ``peak_bytes`` is by default a constraint, not a term — the search rejects
    assignments above the hard budget.  With ``mem_weight > 0`` and a
    ``soft_budget_bytes`` set, :attr:`mem_s` additionally prices the overshoot
    above the *soft* budget (overshoot bytes re-streamed at HBM bandwidth,
    scaled by the weight) into :attr:`total_s`, so two otherwise-equal
    assignments rank by live memory.  Off by default (``mem_weight = 0``).
    """

    wire_bytes: float
    launches: int
    flops_per_device: float
    ideal_flops_per_device: float
    peak_bytes: float
    steps: int
    soft_budget_bytes: Optional[float] = None
    mem_weight: float = 0.0
    params: Optional[object] = None  # roofline.RooflineParams (None = defaults)

    @property
    def collective_s(self) -> float:
        if self.params is not None:
            return (self.wire_bytes / self.params.ici_bw
                    + self.launches * self.params.collective_launch_s)
        from repro.analysis.roofline import COLLECTIVE_LAUNCH_S, ICI_BW

        return self.wire_bytes / ICI_BW + self.launches * COLLECTIVE_LAUNCH_S

    @property
    def compute_s(self) -> float:
        if self.params is not None:
            return self.flops_per_device / self.params.peak_flops
        from repro.analysis.roofline import PEAK_FLOPS

        return self.flops_per_device / PEAK_FLOPS

    @property
    def imbalance_s(self) -> float:
        excess = max(self.flops_per_device - self.ideal_flops_per_device, 0.0)
        if self.params is not None:
            return excess / self.params.peak_flops
        from repro.analysis.roofline import PEAK_FLOPS

        return excess / PEAK_FLOPS

    @property
    def mem_s(self) -> float:
        """Soft-budget memory term: overshoot bytes / HBM bandwidth, weighted.
        Zero when disabled (no soft budget / zero weight) or under budget."""
        if not self.mem_weight or self.soft_budget_bytes is None:
            return 0.0
        overshoot = max(self.peak_bytes - self.soft_budget_bytes, 0.0)
        if self.params is not None:
            return self.mem_weight * overshoot / self.params.hbm_bw
        from repro.analysis.roofline import HBM_BW

        return self.mem_weight * overshoot / HBM_BW

    @property
    def total_s(self) -> float:
        from repro.analysis.roofline import overlap_time_s

        return overlap_time_s(self.compute_s, self.collective_s,
                              self.params) + self.mem_s

    def as_dict(self) -> Dict:
        return {
            "wire_bytes": self.wire_bytes,
            "launches": self.launches,
            "flops_per_device": self.flops_per_device,
            "ideal_flops_per_device": self.ideal_flops_per_device,
            "peak_bytes": self.peak_bytes,
            "steps": self.steps,
            "collective_s": self.collective_s,
            "compute_s": self.compute_s,
            "imbalance_s": self.imbalance_s,
            "mem_s": self.mem_s,
            "total_s": self.total_s,
        }


def plan_cost(plan: PartitionPlan) -> PlanCost:
    """Price an already-lowered plan under the roofline cost model.

    Collective terms are whole-program (inner jit/scan bodies at trip count,
    via ``plan_opt.whole_wire_bytes`` / ``whole_collective_launches``) so the
    autoshard objective sees the same cost the overlap scheduler prices — the
    PR 4 open item ("scan-body collectives invisible to the objective") is
    closed here.  A machine profile attached to the plan (``plan.params``, a
    :class:`repro.analysis.roofline.RooflineParams`) carries through to the
    cost's time-valued properties; ``None`` means the module defaults."""
    from repro.analysis.jaxpr_cost import count_flops
    from .plan_opt import whole_collective_launches, whole_wire_bytes

    return PlanCost(
        wire_bytes=whole_wire_bytes(plan),
        launches=whole_collective_launches(plan),
        flops_per_device=plan.total_flops(),
        ideal_flops_per_device=count_flops(plan.jaxpr) / max(plan.mesh.size, 1),
        peak_bytes=plan.peak_bytes,  # filled by build()/optimize_plan()
        steps=len(plan.steps),
        params=plan.params,
    )


def lower_for_cost(
    closed: excore.ClosedJaxpr,
    in_shardings,
    mesh: Mesh,
    optimize: bool = True,
    verify: Optional[bool] = None,
    guard: Optional[GuardConfig] = None,
    profile: Optional[object] = None,
) -> PlanCost:
    """Propagate ``in_shardings`` seeds and lower to a PlanCost — no jit, no
    execution, no runnables (every step runner is a raising stub).

    ``in_shardings`` is one ``Optional[Sharding]`` per jaxpr invar; ``None``
    entries are left for propagation to infer (the GSPMD premise: annotate a
    few tensors, the compiler completes the rest).  Raises
    :class:`~repro.core.collective_planner.PlanError` when the propagated
    program demands a reshard the planner cannot express (infeasible
    candidate — autoshard treats it as infinite cost).  Cost-only lowerings
    are verified too (``verify=None`` = module default); ``guard`` prices the
    numerics-sentinel epilogue into the returned cost (the guard-overhead
    bench cell); ``profile`` prices with calibrated roofline constants
    (:class:`repro.analysis.roofline.RooflineParams`).
    """
    return plan_cost(lower_plan(closed, in_shardings, mesh, optimize=optimize,
                                verify=verify, guard=guard, profile=profile))


def lower_plan(
    closed: excore.ClosedJaxpr,
    in_shardings,
    mesh: Mesh,
    optimize: bool = True,
    verify: Optional[bool] = None,
    guard: Optional[GuardConfig] = None,
    profile: Optional[object] = None,
) -> PartitionPlan:
    """Cost-only lowering that returns the :class:`PartitionPlan` itself
    (step runners are raising stubs — the plan prices, it doesn't run).

    Same contract as :func:`lower_for_cost` but for consumers that need the
    structure, not just the totals: the modeled timeline export
    (``plan_opt.modeled_timeline`` / ``python -m repro.obs trace``) and the
    obs bench cells walk the step list of registry-sized plans on meshes
    bigger than the host.
    """
    from .propagation import propagate

    prop = propagate(closed, mesh, in_shardings=list(in_shardings or []))
    return compile_plan(closed, prop.result(), mesh, optimize=optimize,
                        cost_only=True, verify=verify, guard=guard,
                        profile=profile)


# ---------------------------------------------------------------------------------
# state-reshard plans: cross-topology checkpoint restore as a compiled program
# ---------------------------------------------------------------------------------
#
# Elastic restore ("save on mesh A, restore on mesh B") is a pure layout
# problem: every leaf has a *source* sharding (the manifest's spec projected
# onto the new mesh — axes that no longer exist or divide become replication)
# and a *target* sharding (the new assignment).  Instead of host-mediated
# ``device_put`` of every global array, the restore lowers one reshard
# program per leaf via the cost-model planner and replays them all inside a
# single ``shard_map`` region — priced with the same roofline model and
# reported with the same :class:`PlanCost` as any partition plan.


@dataclasses.dataclass
class LeafReshard:
    """One leaf's planned source→target layout change."""

    key: str
    src: Sharding
    dst: Sharding
    global_shape: Tuple[int, ...]
    dtype: str
    program: ReshardProgram

    @property
    def is_identity(self) -> bool:
        return self.program.is_identity


@dataclasses.dataclass
class StateReshardPlan:
    """A compiled cross-topology restore: per-leaf reshard programs on one
    (target) mesh, priced like any other plan.

    Planning is pure (no devices needed — the bench prices registry-sized
    restores on meshes bigger than the host); :meth:`execute` replays every
    program in a single jitted ``shard_map`` over the actual device mesh.
    """

    mesh: Mesh
    leaves: List[LeafReshard]
    stats: PlanStats
    gather_all_bytes: float = 0.0  # reference: replicate-then-slice restore

    @property
    def wire_bytes(self) -> float:
        return sum(l.program.cost_bytes for l in self.leaves)

    @property
    def launches(self) -> int:
        return sum(
            1 for l in self.leaves for s in l.program.steps
            if s.op != "dynamic_slice"
        )

    @property
    def resharded_leaves(self) -> int:
        return sum(1 for l in self.leaves if not l.is_identity)

    def cost(self) -> PlanCost:
        """Roofline pricing: a restore is all-collective, so ``total_s`` is
        the collective term (wire bytes / ICI + per-launch overhead)."""
        peak = sum(
            max(_nbytes_of(shard_shape(l.global_shape, l.src),
                           int(np.dtype(l.dtype).itemsize)),
                _nbytes_of(shard_shape(l.global_shape, l.dst),
                           int(np.dtype(l.dtype).itemsize)))
            for l in self.leaves
        )
        return PlanCost(
            wire_bytes=self.wire_bytes, launches=self.launches,
            flops_per_device=0.0, ideal_flops_per_device=0.0,
            peak_bytes=peak, steps=len(self.leaves),
        )

    def source_specs(self) -> Dict[str, "Sharding"]:
        """Per-leaf source shardings (the checkpoint's layout).  Together
        with :meth:`target_specs` this is the plan's topology contract: the
        elastic coordinator replays one plan per recovery — shrink *or*
        regrow — and the pair documents exactly which layout transition that
        replay performs (the manifests only record the source side)."""
        return {l.key: l.src for l in self.leaves}

    def target_specs(self) -> Dict[str, "Sharding"]:
        """Per-leaf destination shardings (the new mesh's layout)."""
        return {l.key: l.dst for l in self.leaves}

    def report(self) -> Dict:
        cost = self.cost()
        return {
            "leaves": len(self.leaves),
            "resharded_leaves": self.resharded_leaves,
            "wire_bytes": self.wire_bytes,
            "launches": self.launches,
            "gather_all_bytes": self.gather_all_bytes,
            "ratio_vs_gather_all": (
                self.wire_bytes / self.gather_all_bytes
                if self.gather_all_bytes else 1.0
            ),
            "reshard_s": cost.collective_s,
            "collectives": dict(self.stats.collectives),
        }

    def execute(self, jmesh, arrays):
        """Replay every leaf program in one jitted shard_map region.

        ``arrays`` are device arrays already laid out per the *source*
        shardings (each host feeds its shard slice); the result tuple is laid
        out per the target shardings.  One launch for the whole state — the
        plan-lowered analogue of a per-leaf host-mediated ``device_put``.
        """
        import jax

        from .compat import shard_map
        from .sharding import to_partition_spec

        progs = tuple(l.program for l in self.leaves)

        def run(*xs):
            return tuple(
                execute_program(x, prog) for x, prog in zip(xs, progs)
            )

        f = shard_map(
            run, mesh=jmesh,
            in_specs=tuple(to_partition_spec(l.src) for l in self.leaves),
            out_specs=tuple(to_partition_spec(l.dst) for l in self.leaves),
        )
        return jax.jit(f)(*arrays)


def compile_state_reshard(items, mesh: Mesh,
                          verify: Optional[bool] = None) -> StateReshardPlan:
    """Lower a cross-topology state restore into a :class:`StateReshardPlan`.

    ``items`` is an iterable of ``(key, src, dst, global_shape, dtype)`` with
    both shardings already on ``mesh`` (the *target* mesh — project manifest
    specs with :func:`repro.core.sharding.project_dims_mapping` first).
    Each leaf's program is cost-model-chosen by ``plan_reshard``; the
    replicate-then-slice expression of the same restore is priced as the
    ``gather_all_bytes`` reference.  Raises
    :class:`~repro.core.collective_planner.PlanError` when some leaf layout
    change is inexpressible.  The finished plan is statically verified
    (``plan_verify.verify_state_reshard``) unless ``verify`` disables it.
    """
    from .collective_planner import _candidate_gather_all, simulate

    leaves: List[LeafReshard] = []
    stats = PlanStats()
    gather_bytes = 0.0
    for key, src, dst, shape, dtype in items:
        shape = tuple(int(s) for s in shape)
        db = int(np.dtype(dtype).itemsize)
        local = shard_shape(shape, src)
        prog = plan_reshard(src, dst, local, dtype_bytes=db)
        stats.add_program(prog)
        stats.steps += 1
        ref_steps = _candidate_gather_all(src, dst, local)
        if ref_steps is not None:
            try:
                gather_bytes += simulate(src, dst, ref_steps, local, db)
            except PlanError:  # pragma: no cover - gather-all always simulates
                pass
        leaves.append(LeafReshard(key, src, dst, shape, str(dtype), prog))
    plan = StateReshardPlan(mesh, leaves, stats, gather_bytes)
    from .plan_verify import verify_enabled

    if verify_enabled(verify):
        from .plan_verify import verify_state_reshard

        verify_state_reshard(plan)
    return plan
