"""``spmd_partition``: the runner that partitions a function over a mesh.

XLA's SPMD partitioner (production GSPMD) is what ``jax.jit`` invokes; this
repo partitions the program itself.  ``spmd_partition`` traces ``fn`` against
global shapes, completes its shardings (``propagation``), lowers the result
into a :class:`~repro.core.plan.PartitionPlan` (``plan.compile_plan``; the
per-op rules of paper §4 live there) and runs that plan over local shards
inside one jitted ``shard_map``.  It is validated numerically against the
unpartitioned program — GSPMD's "mathematically equivalent" guarantee — in
tests/multidev/.

Built entries live in two caches:

* the **runner cache**, one per ``spmd_partition`` call, keyed by input
  avals + mesh: steady-state calls skip tracing, propagation and plan
  lowering and go straight to the jitted program;
* the **process cache**, shared by every runner in the process, keyed by the
  traced jaxpr's content digest + mesh + avals + build options, so separate
  call sites partitioning the same program build and compile it once.

Every call runs inside the ``repro.partition.*`` profiler spans listed in
:mod:`repro.obs.trace` (``call`` → ``lookup`` / ``build`` / ``dispatch``; a
build adds ``make_jaxpr``, ``autoshard``, ``propagate``, ``compile_plan`` and
``jit``), and each entry keeps its build seconds per phase.
"""
from __future__ import annotations

import dataclasses
import hashlib
import threading
import time
from typing import Dict, List, Optional

import jax
import numpy as np
from jax import core

from repro.obs.trace import span

from .compat import shard_map, trace_for
from .plan import NumericsFault, PartitionPlan, compile_plan, guard_faults
from .propagation import propagate
from .sharding import Mesh, replicated, to_partition_spec


@dataclasses.dataclass
class PlanCacheStats:
    """Hit/miss counters for a plan cache.

    Increment through :meth:`record_hit` / :meth:`record_miss` — the counters
    are lock-guarded so concurrent runners (and autoshard's repeated
    lowering calls from evaluator threads) cannot drop updates between the
    read and the write of a bare ``+= 1``.
    """

    hits: int = 0
    misses: int = 0
    # scope labels this cache in the unified metrics registry: hits/misses
    # also land in ``plan_cache.<scope>.{hits,misses}`` counters there, so
    # one snapshot covers every cache in the process (None = unlabelled,
    # registry feed off)
    scope: Optional[str] = None
    _lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False, compare=False,
    )

    def record_hit(self) -> None:
        with self._lock:
            self.hits += 1
        if self.scope:
            from repro.obs.metrics import inc

            inc(f"plan_cache.{self.scope}.hits")

    def record_miss(self) -> None:
        with self._lock:
            self.misses += 1
        if self.scope:
            from repro.obs.metrics import inc

            inc(f"plan_cache.{self.scope}.misses")

    def reset(self) -> None:
        with self._lock:
            self.hits = 0
            self.misses = 0

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def as_dict(self):
        return {"hits": self.hits, "misses": self.misses, "hit_rate": self.hit_rate}


@dataclasses.dataclass
class _CacheEntry:
    call: object  # jitted shard_map over the compiled plan
    plan: PartitionPlan
    build_s: float = 0.0  # trace + propagate + plan lowering (no XLA compile)
    # seconds of each build phase, by the name of its repro.partition.* span
    phases: Dict[str, float] = dataclasses.field(default_factory=dict)
    # seconds of the first call with concrete arrays: lowering, XLA compile
    # (or compile-cache load) and the enqueue; None until that call
    first_call_s: Optional[float] = None


def _aval_key(a):
    dt = getattr(a, "dtype", None)
    # Python scalars trace as weak types and can promote differently than
    # strong-typed arrays of the same dtype — key them separately, as jit does.
    weak = dt is None or bool(getattr(a, "weak_type", False))
    if dt is None:
        dt = np.result_type(type(a))
    return (tuple(np.shape(a)), np.dtype(dt).str, weak)


# ---------------------------------------------------------------------------------
# process-level plan cache
# ---------------------------------------------------------------------------------
#
# The per-runner cache below skips re-tracing for repeated *calls*; separate
# ``spmd_partition`` call sites partitioning the same function (train step
# rebuilt per epoch, serve replicas, benchmarks) each used to rebuild and
# re-jit identical plans.  The process cache shares the built entry (optimized
# plan + jitted shard_map) across runners, keyed by the traced jaxpr's
# content digest — structure plus const payloads — so equality means "same
# partitioning problem", not "same Python callable".

_PROCESS_CACHE: Dict[tuple, "_CacheEntry"] = {}
_PROCESS_STATS = PlanCacheStats(scope="process")


def _jaxpr_digest(closed) -> str:
    """Content digest of a ClosedJaxpr: alpha-renamed pretty-print + consts.

    jaxpr printing uses deterministic alpha-renaming, so two traces of the
    same computation print identically; const payloads are hashed too since
    the compiled plan bakes them in.
    """
    h = hashlib.sha256(str(closed.jaxpr).encode())
    for c in closed.consts:
        arr = np.asarray(c)
        h.update(str(arr.dtype).encode())
        h.update(str(arr.shape).encode())
        h.update(arr.tobytes())
    return h.hexdigest()


def _jmesh_key(jmesh) -> tuple:
    return (
        tuple(jmesh.axis_names),
        tuple(jmesh.devices.shape),
        tuple(int(d.id) for d in jmesh.devices.flat),
    )


def process_plan_cache_stats() -> PlanCacheStats:
    return _PROCESS_STATS


def process_plan_cache_entries() -> List[_CacheEntry]:
    """The process-level plan cache's entries (their ``build_s``,
    ``phases`` and ``first_call_s``), oldest first."""
    return list(_PROCESS_CACHE.values())


def clear_process_plan_cache() -> None:
    _PROCESS_CACHE.clear()
    _PROCESS_STATS.reset()


def spmd_partition(fn, jmesh, mesh: Mesh, optimize: bool = True,
                   autoshard=None, verify: bool = True, guard=None, trace=None,
                   profile=None):
    """Partition ``fn`` over ``mesh`` and return a callable that runs the SPMD
    program over ``jmesh`` via shard_map.

    The user writes ``fn`` against global shapes with ``annotate`` hints; we
    trace, complete shardings (propagation pass), then lower the result into a
    :class:`~repro.core.plan.PartitionPlan` — a flat list of resolved
    per-equation steps with cost-model-chosen reshard programs.  Plans are
    cached keyed by (input avals, mesh): steady-state calls skip
    ``make_jaxpr``, propagation, and all per-equation dispatch, going straight
    to the jitted partitioned program.  Built entries are shared across
    runners through the process-level plan cache (keyed by jaxpr digest +
    mesh + avals + the options below); :func:`clear_process_plan_cache`
    forces a fresh build.

    ``optimize=False`` skips the whole-program optimizer passes
    (``plan_opt``: jit inlining, scan-invariant reshard hoisting, reshard
    CSE, dead-reshard elimination, collective fusion, overlap-aware
    scheduling).

    ``autoshard`` (an :class:`repro.autoshard.AutoshardConfig`) makes the
    partitioner *annotation-free*: instead of relying on ``annotate`` seeds in
    ``fn``, the traced jaxpr's input shardings are found by the autoshard
    search (cost-only lowering under the roofline model) and fed to
    propagation as seeds.  The searched assignment is cached process-wide by
    jaxpr digest + mesh + config, so repeat call sites pay for the search
    once.

    ``verify`` runs the static plan verifier
    (:func:`repro.core.plan_verify.verify_plan`) on each built plan.  ``guard``
    (a :class:`repro.core.plan.GuardConfig`) appends runtime numerics-sentinel
    steps to the plan; the runner host-checks the sentinel vector after each
    call and raises :class:`repro.core.plan.NumericsFault` with per-leaf
    provenance when a guarded output is non-finite or exceeds
    ``guard.max_abs``.

    ``trace`` (a :class:`repro.obs.trace.TraceConfig`) opts this runner into
    plan-step tracing.  ``TraceConfig(enabled=False)`` is normalized to "no
    tracing" right here — same cache keys, same jitted callable, provably
    zero overhead.  With tracing on, the runner is excluded from the
    process-level plan cache (the tracer is runner-local state) and, when
    ``trace.measured``, the plan executes **eagerly** (shard_map without
    ``jit``) so per-step host timers mean something — see the tracing
    contract in :mod:`repro.obs.trace` for the dispatch-vs-device-time
    caveats.  The tracer is exposed as ``runner.tracer``
    (``runner.tracer.write(path)`` exports Chrome trace JSON).

    ``profile`` applies calibrated roofline constants to the compiled plan's
    cost model: a :class:`repro.analysis.roofline.RooflineParams`, a fitted
    :class:`repro.obs.profile.MachineProfile`, or a profile JSON path.
    ``None`` falls back to ``$REPRO_MACHINE_PROFILE`` (and, with that unset,
    to the module-default constants — bit-identical plans and cache
    entries).  The resolved profile's digest is part of the process-cache
    key, so calibrated and default plans never collide, and applying one
    emits a ``profile_applied`` control event.

    The returned runner exposes ``runner.cache_stats`` (hits/misses) and
    ``runner.plans`` (cache-key → cache entry) for tests and reporting.
    Each call runs inside the profiler spans ``repro.partition.*`` listed in
    :mod:`repro.obs.trace`; an entry keeps its build seconds (``build_s``,
    and per phase ``phases``) and ``first_call_s``, the seconds of its first
    call with concrete arrays (lowering and XLA compile).
    """
    if trace is not None and not trace.enabled:
        trace = None  # disabled config ≡ no tracing: identical runner
    tracer = None
    if trace is not None:
        from repro.obs.trace import Tracer

        tracer = Tracer(trace)
    # a tracer is runner-local; sharing a traced entry across call sites
    # would cross-wire their spans, so a traced runner keeps to its own cache
    shared = tracer is None
    cache: Dict[tuple, _CacheEntry] = {}
    # runner-local: a hit is counted on every call, so it stays off the
    # process-wide metrics registry (the process cache feeds it per build)
    stats = PlanCacheStats()

    def _build(args):
        from repro.obs.profile import resolve_profile

        # resolved per build so $REPRO_MACHINE_PROFILE edits are picked up;
        # the digest keys the process cache (None = default constants)
        prof = resolve_profile(profile)
        t0 = time.perf_counter()
        phases: Dict[str, float] = {}
        with span("repro.partition.make_jaxpr", phases):
            closed = trace_for(mesh, fn, *args)
        pkey: Optional[tuple] = None
        if shared:
            pkey = (
                _jaxpr_digest(closed), mesh.structural_key(), _jmesh_key(jmesh),
                tuple(_aval_key(a) for a in args), optimize,
                autoshard.cache_key() if autoshard is not None else None,
                verify, guard,
                prof.digest() if prof is not None else None,
            )
            entry = _PROCESS_CACHE.get(pkey)
            if entry is not None:
                _PROCESS_STATS.record_hit()
                return entry
            _PROCESS_STATS.record_miss()
        in_seeds = None
        if autoshard is not None:
            from repro.autoshard.api import solve_jaxpr_cached

            with span("repro.partition.autoshard", phases):
                shard_res = solve_jaxpr_cached(closed, mesh, autoshard)
            if not shard_res.evaluation.feasible:
                # never silently drop the caller's constraints (e.g. an
                # unmeetable memory budget) — fall back explicitly instead
                raise ValueError(
                    "autoshard: no feasible assignment found "
                    f"({shard_res.evaluation.reason or 'search exhausted'}); "
                    "relax AutoshardConfig.budget_bytes or widen the search "
                    "(top_n / sa_steps / max_candidates)"
                )
            in_seeds = shard_res.assignment
        with span("repro.partition.propagate", phases):
            prop = propagate(closed, mesh, in_shardings=in_seeds)
        in_specs = tuple(
            to_partition_spec(prop.get(v) or replicated(mesh, v.aval.ndim))
            for v in closed.jaxpr.invars
        )
        out_specs = tuple(
            to_partition_spec(prop.get(v) or replicated(mesh, v.aval.ndim))
            for v in closed.jaxpr.outvars
        )
        with span("repro.partition.compile_plan", phases):
            plan = compile_plan(closed, prop.result(), mesh,
                                optimize=optimize, verify=verify,
                                guard=guard, profile=prof, phases=phases)
        if prof is not None:
            from repro.obs.trace import control_event

            control_event("profile_applied", digest=prof.digest(),
                          mesh=list(mesh.shape))
        if guard is not None:
            # the guard epilogue appends a sentinel vector output — derive
            # the shard_map out_specs from the plan, not the jaxpr outvars
            out_specs = tuple(
                to_partition_spec(sh) for sh in plan.out_shardings
            )
        if tracer is not None:
            tracer.on_plan(plan)  # modeled lane from the overlap schedule

        step_tracer = tracer if (tracer is not None
                                 and tracer.config.measured) else None

        def local_fn(*local_args):
            outs = plan.execute(*local_args, tracer=step_tracer)
            return outs if len(outs) > 1 else outs[0]

        with span("repro.partition.jit", phases):
            shmapped = shard_map(
                local_fn,
                mesh=jmesh,
                in_specs=in_specs,
                out_specs=out_specs if len(out_specs) > 1 else out_specs[0],
            )
            # measured tracing skips jit: eager shard_map keeps the Python
            # step walk alive at run time so per-step timers observe real
            # dispatch (the whole point — see the tracing contract in
            # repro.obs.trace)
            call = shmapped if step_tracer is not None else jax.jit(shmapped)
        entry = _CacheEntry(call, plan, time.perf_counter() - t0, phases)
        if pkey is not None:
            _PROCESS_CACHE[pkey] = entry
        return entry

    def runner(*args):
        with span("repro.partition.call"):
            return _call(args)

    def _call(args):
        with span("repro.partition.lookup"):
            key = (mesh.structural_key(), tuple(_aval_key(a) for a in args))
            entry = cache.get(key)
        if entry is None:
            stats.record_miss()
            with span("repro.partition.build"):
                entry = _build(args)
            cache[key] = entry
        else:
            stats.record_hit()
        with span("repro.partition.dispatch"):
            if entry.first_call_s is None and not any(
                    isinstance(a, core.Tracer) for a in args):
                t0 = time.perf_counter()
                outs = entry.call(*args)
                entry.first_call_s = time.perf_counter() - t0
            else:
                outs = entry.call(*args)
        if entry.plan.guard is not None:
            gi = entry.plan.guard
            outs = list(outs)
            gvec = outs.pop(gi.out_index)
            faults = guard_faults(gi.config, jax.device_get(gvec), gi.leaves)
            runner.calls += 1
            if faults:
                raise NumericsFault(runner.calls - 1, faults)
            return tuple(outs) if len(outs) > 1 else outs[0]
        return outs

    runner.calls = 0
    runner.cache_stats = stats
    runner.plans = cache
    runner.tracer = tracer
    return runner
