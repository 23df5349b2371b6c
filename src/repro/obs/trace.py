"""Plan-step tracing: profiler spans, measured spans, modeled timelines,
control events.

Profiler spans
--------------

:func:`span` is the one helper for host spans on the profiler's clock: a
``jax.profiler.TraceAnnotation`` that lands on the host plane of the same
``.xplane.pb`` as the device operations, so device idle time can be laid
against what the host was doing.  It costs an inactive ``TraceMe`` until a
profiler session runs, so the spans are always on.  These spans, and the
scopes the partitioned program carries into the device trace (each plan step
is bound under its source equation's name stack), are what time the jitted
program; the *measured* lane below times an eager replay of it instead.

=============================  =================================================
span                           what it covers
=============================  =================================================
``repro.partition.call``       one ``spmd_partition`` runner call, with children
``repro.partition.lookup``     the cache key and the runner's plan cache
``repro.partition.build``      a plan build (a cache miss), with children
                               ``make_jaxpr``, ``autoshard``, ``propagate``,
                               ``compile_plan`` (itself ``lower``, ``optimize``,
                               ``verify``) and ``jit``, each
                               ``repro.partition.<phase>``; their seconds land
                               in ``_CacheEntry.phases``
``repro.partition.dispatch``   the jitted program's call: on the entry's first
                               call with concrete arrays, its lowering and XLA
                               compile (``_CacheEntry.first_call_s``)
``repro.data.batch_at``        ``TokenPipeline.batch_at``
=============================  =================================================

``TrainLoop.run`` also wraps each step in
``jax.profiler.StepTraceAnnotation("train", step_num=step)``.

Tracing contract (read this before trusting a number)
-----------------------------------------------------

A compiled :class:`~repro.core.plan.PartitionPlan` normally executes inside
``jax.jit(shard_map(...))`` — by the time devices run, the Python step walk
is long gone, so there is nothing left for a host-side timer to observe.
Traced *measured* execution therefore runs the plan **eagerly** (shard_map
without the enclosing ``jit``): each ``PlanStep.run`` still dispatches the
same primitives to the same devices, but the step walk happens in Python
where a ``perf_counter`` pair can bracket it.

What a measured span contains, precisely:

* **dispatch time** — Python + JAX tracing/dispatch overhead for the step's
  primitives (always included; this is host time, not device time);
* **device time** — only when :attr:`TraceConfig.sync` is true (default):
  the tracer calls ``jax.block_until_ready`` on the step's outputs before
  closing the span, so the span covers dispatch *plus* device execution.
  With ``sync=False`` spans measure dispatch only and device work overlaps
  asynchronously — useful for spotting host-bound steps, useless for
  calibration.

Eager execution is slower than the jitted path (no XLA fusion across
steps).  Default (``timing="eager"``) measured spans are therefore *upper
bounds* on per-step device time, tightest for steps dominated by real
device work (large collectives, big matmuls) and loosest for tiny ops —
exactly the bias the per-step-class
:class:`~repro.obs.calibrate.CalibrationReport` is designed to expose.
Inner jit/scan plans execute inside their call step's single span (the
scan body is one jitted unit; per-trip spans would perturb what they
measure).

``timing="tight"`` is the calibration mode: each step is warmed up once,
then re-run :attr:`TraceConfig.repeats` times with ``block_until_ready``
after every repetition, and the **minimum** elapsed time becomes the span
(the min-of-K discipline ``benchmarks/perf.py`` uses).  Tight spans are
measurement-quality per-step seconds — dispatch noise, allocator warmup,
and GC pauses are excluded by the min — and are what
:func:`repro.obs.profile.fit_profile` consumes to recover effective
:class:`~repro.analysis.roofline.RooflineParams` for this machine.  Two
caveats: span *timestamps* under tight timing are a synthetic monotonic
cursor (the sum of per-step minima), not wall clock — durations are real,
absolute positions are not, and control-lane events no longer line up with
step spans; and each step runs ``1 + repeats`` times, so tight tracing is
only for calibration runs, never for measuring end-to-end walltime.

The *modeled* timeline has none of these caveats: it is emitted straight
from the overlap schedule (``plan_opt.modeled_timeline``) by replaying the
scheduler's own two-resource timing rules over the final step order, so it
is exactly the timeline the optimizer believed it was building.

Lanes (Chrome trace ``pid``/``tid`` mapping)
--------------------------------------------

========  ===========  ====================================================
pid       process      tids
========  ===========  ====================================================
1         modeled      1 = compute, 2 = interconnect
2         measured     1 = compute, 2 = interconnect
3         control      1 = elastic instant events (fault/skip/rewind/swap)
========  ===========  ====================================================

A step lands on the interconnect lane when the overlap scheduler would
charge it to the communication resource (reshard / collective / fused
steps), on the compute lane otherwise (compute, guard, inner-plan calls).

Control events are process-global (:func:`control_event`), timestamped on
the same ``perf_counter`` epoch as measured spans, so a fault instant lines
up with the step that was running when it fired.  They survive plan swaps —
an elastic recovery writes its whole fault → skip → rewind → swap story
into one trace even though the plan object changed mid-run.

Export is Chrome trace-event JSON (``{"traceEvents": [...]}``, ``ts``/
``dur`` in microseconds) — load the file in Perfetto / ``chrome://tracing``
and the modeled and measured timelines diff side by side.
"""
from __future__ import annotations

import json
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

from jax.profiler import TraceAnnotation

# One perf_counter epoch per process: measured spans and control events share
# it, so cross-source ordering in the merged trace is meaningful.
_EPOCH = time.perf_counter()

MODELED_PID = 1
MEASURED_PID = 2
CONTROL_PID = 3
COMPUTE_TID = 1
INTERCONNECT_TID = 2
CONTROL_TID = 1

# Step kinds the overlap scheduler charges to the communication resource —
# keep in sync with plan_opt._step_durations.
_COMM_KINDS = ("reshard", "collective", "fused")


def _now_us() -> float:
    return (time.perf_counter() - _EPOCH) * 1e6


class _TimedSpan:
    """A profiler span that also adds its host seconds to ``times``."""

    __slots__ = ("_annotation", "_times", "_key", "_t0")

    def __init__(self, name: str, times: Dict[str, float]):
        self._annotation = TraceAnnotation(name)
        self._times = times
        self._key = name.rsplit(".", 1)[-1]

    def __enter__(self):
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        self._times[self._key] = self._times.get(self._key, 0.0) + dt
        return self._annotation.__exit__(*exc)


def span(name: str, times: Optional[Dict[str, float]] = None):
    """Context manager: a host span ``name`` on the profiler's clock (see
    "Profiler spans" above).  With ``times``, the span's seconds are also
    added to ``times`` under the name's last dotted part."""
    if times is None:
        return TraceAnnotation(name)
    return _TimedSpan(name, times)


@dataclass(frozen=True)
class TraceConfig:
    """Opt-in tracing switch for ``spmd_partition(trace=...)``.

    enabled
        Master switch; ``TraceConfig(enabled=False)`` is normalized to "no
        tracing at all" inside ``spmd_partition`` so a disabled config is
        *provably* free (same plan-cache key, same jitted callable).
    modeled
        Emit the modeled timeline from the overlap schedule.
    measured
        Execute eagerly and record per-step measured spans (see the module
        docstring for what those spans mean).
    sync
        Block on each step's outputs before closing its span (device time
        included).  ``False`` measures dispatch only.
    path
        If set, the runner does not auto-write anywhere; callers export via
        ``runner.tracer.write(path)`` — this field just carries the
        caller's intent along.
    timing
        ``"eager"`` (default): one perf_counter pair per step, dispatch
        included.  ``"tight"``: min-of-``repeats`` with ``block_until_ready``
        per step — calibration-grade durations, synthetic timestamps (see
        the module docstring).
    repeats
        Timed repetitions per step under ``timing="tight"`` (after one
        untimed warmup).
    """

    enabled: bool = True
    modeled: bool = True
    measured: bool = True
    sync: bool = True
    path: Optional[str] = None
    timing: str = "eager"
    repeats: int = 3

    @property
    def cache_key(self) -> Tuple:
        return (self.enabled, self.modeled, self.measured, self.sync,
                self.timing, self.repeats)


def step_lane(kind: str) -> int:
    return INTERCONNECT_TID if kind in _COMM_KINDS else COMPUTE_TID


class Tracer:
    """Collects modeled timelines, measured spans, and exports Chrome JSON.

    One tracer per ``spmd_partition`` runner; ``plan.execute(...,
    tracer=...)`` feeds it measured spans, the runner feeds it each compiled
    plan (:meth:`on_plan`) for the modeled lane.  Thread-safe — elastic
    coordinators swap plans from recovery paths while steps run.
    """

    def __init__(self, config: Optional[TraceConfig] = None):
        self.config = config or TraceConfig()
        self._lock = threading.Lock()
        self._modeled: List[Dict[str, Any]] = []  # chrome events, pid 1
        self._measured: List[Dict[str, Any]] = []  # chrome events, pid 2
        self._calls = 0
        self._plans_seen = 0

    # -- modeled lane --------------------------------------------------------
    def on_plan(self, plan) -> None:
        """Emit the modeled timeline for a freshly compiled plan.

        Repeated calls (plan swaps) append further modeled rows offset to
        start after the previous plan's makespan, so swapped plans stay
        distinguishable (``args["plan"]`` carries the ordinal).
        """
        if not self.config.modeled:
            return
        from repro.core.plan_opt import modeled_timeline

        rows = modeled_timeline(plan)
        with self._lock:
            base = 0.0
            for ev in self._modeled:
                base = max(base, ev["ts"] + ev.get("dur", 0.0))
            ordinal = self._plans_seen
            self._plans_seen += 1
            for row in rows:
                self._modeled.append({
                    "name": row["name"],
                    "ph": "X",
                    "ts": base + row["start_s"] * 1e6,
                    "dur": row["dur_s"] * 1e6,
                    "pid": MODELED_PID,
                    "tid": INTERCONNECT_TID
                    if row["lane"] == "interconnect" else COMPUTE_TID,
                    "args": {
                        "class": row["cls"],
                        "index": row["index"],
                        "plan": ordinal,
                        "compute_s": row["compute_s"],
                        "comm_s": row["comm_s"],
                    },
                })

    # -- measured lane -------------------------------------------------------
    def begin_call(self) -> int:
        with self._lock:
            call = self._calls
            self._calls += 1
        return call

    def record_step(self, index: int, step, t0_us: float,
                    t1_us: float, call: int) -> None:
        """One measured span; ``t0_us``/``t1_us`` from :func:`now_us`."""
        from repro.core.plan_opt import step_class

        ev = {
            "name": f"{step.kind}:{getattr(step, 'op', None) or ''}".rstrip(
                ":"),
            "ph": "X",
            "ts": t0_us,
            "dur": max(t1_us - t0_us, 0.0),
            "pid": MEASURED_PID,
            "tid": step_lane(step.kind),
            "args": {
                "class": step_class(step),
                "index": index,
                "call": call,
            },
        }
        with self._lock:
            self._measured.append(ev)

    @staticmethod
    def now_us() -> float:
        return _now_us()

    # -- accessors / export --------------------------------------------------
    @property
    def calls(self) -> int:
        with self._lock:
            return self._calls

    def modeled_events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._modeled)

    def measured_events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._measured)

    def chrome_trace(self, include_control: bool = True) -> Dict[str, Any]:
        events = _lane_metadata()
        events += self.modeled_events()
        events += self.measured_events()
        if include_control:
            events += control_chrome_events()
        return {"traceEvents": events}

    def write(self, path: str, include_control: bool = True) -> str:
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.chrome_trace(include_control=include_control), f,
                      indent=1, default=str)
        return path


def _lane_metadata() -> List[Dict[str, Any]]:
    events: List[Dict[str, Any]] = []
    for pid, pname in ((MODELED_PID, "modeled"), (MEASURED_PID, "measured"),
                       (CONTROL_PID, "control")):
        events.append({
            "name": "process_name", "ph": "M", "pid": pid,
            "args": {"name": pname},
        })
    for pid in (MODELED_PID, MEASURED_PID):
        events.append({
            "name": "thread_name", "ph": "M", "pid": pid,
            "tid": COMPUTE_TID, "args": {"name": "compute"},
        })
        events.append({
            "name": "thread_name", "ph": "M", "pid": pid,
            "tid": INTERCONNECT_TID, "args": {"name": "interconnect"},
        })
    events.append({
        "name": "thread_name", "ph": "M", "pid": CONTROL_PID,
        "tid": CONTROL_TID, "args": {"name": "elastic"},
    })
    return events


# -- control lane (process-global) -------------------------------------------
#
# Elastic/guard events outlive any single runner (a plan swap replaces the
# runner's plan mid-run), so the control log is module-level.  The train loop
# and ElasticCoordinator call control_event(...) unconditionally — appending a
# dict under a lock is cheap enough to leave always-on, and it is the only way
# a post-mortem trace can tell the full recovery story.

_CONTROL_LOCK = threading.Lock()
_CONTROL_EVENTS: List[Dict[str, Any]] = []

# Every control-event kind the elastic/guard/chaos machinery emits.  The set
# is advisory (control_event stays permissive for forward compatibility) but
# narrative reconstruction and the chaos invariants key off these names.
CONTROL_EVENT_KINDS = frozenset({
    "numerics_fault", "skip_step", "rewind",          # guard (train/loop)
    "device_loss", "device_return",                   # world membership
    "mesh_shrink", "mesh_grow",                       # mesh re-derivation
    "combined_recovery", "restore", "ckpt_fallback",  # single-pass recovery
    "plan_swap", "crash_save", "straggler",           # plan/save/watchdog
    "ckpt_save",                                      # committed checkpoints
    "chaos_event",                                    # injected campaign event
    "profile_applied",                                # calibrated RooflineParams
})


def control_event(name: str, **args: Any) -> Dict[str, Any]:
    """Record an instant event (see :data:`CONTROL_EVENT_KINDS`) on the
    control lane."""
    ev = {"name": name, "ts": _now_us(), "args": dict(args)}
    with _CONTROL_LOCK:
        _CONTROL_EVENTS.append(ev)
    return ev


def control_events() -> List[Dict[str, Any]]:
    with _CONTROL_LOCK:
        return [dict(e) for e in _CONTROL_EVENTS]


def reset_control_events() -> None:
    with _CONTROL_LOCK:
        _CONTROL_EVENTS.clear()


def control_chrome_events() -> List[Dict[str, Any]]:
    return [{
        "name": e["name"],
        "ph": "i",
        "s": "g",
        "ts": e["ts"],
        "pid": CONTROL_PID,
        "tid": CONTROL_TID,
        "args": e["args"],
    } for e in control_events()]


def export_control_trace() -> Dict[str, Any]:
    """Standalone Chrome trace of just the control lane (used by tests and
    by runs that never enabled step tracing but still want the elastic
    story)."""
    return {"traceEvents": _lane_metadata() + control_chrome_events()}


# Recovery-*action* instants that open an episode.  Raw fault instants
# (numerics_fault / skip_step) deliberately do not: a skip-only burst that
# never escalates is handled entirely in-jit and triggers no recovery, so it
# must not bleed into a later unrelated episode.
_EPISODE_OPENERS = frozenset(
    {"device_loss", "device_return", "crash_save", "rewind",
     "combined_recovery"})


def recovery_narrative(events: Sequence[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Reconstruct recovery episodes purely from control events.

    ``events`` is either the raw :func:`control_events` list or the instant
    (``ph == "i"``) events of an exported Chrome trace — both carry
    ``name``/``ts``/``args``.  Returns one dict per episode, in time order::

        {"classes": [fault classes handled],    # e.g. ["device_loss", "numerics"]
         "step": the fault step the episode opened at,
         "mesh": {"from": [...], "to": [...]} or None (mesh unchanged),
         "restore_steps": [manifest steps restored from],
         "restores": how many restore passes ran,
         "events": [control-event names, in order]}

    An episode opens at a recovery *action* (device loss/return, rewind,
    combined recovery, crash-mid-save) and closes at the ``plan_swap`` that
    resumes training (a crash-save resume closes at its own instant — no plan
    changes).  This is the machine-checkable form of "the trace tells the
    whole story": the chaos harness asserts each injected fault maps onto an
    episode with the expected classes, and the combined-recovery drill
    asserts coincident faults land in **one** episode with **one** restore.
    """
    inst = sorted(
        (e for e in events if e.get("ph", "i") == "i"),
        key=lambda e: e.get("ts", 0.0))
    episodes: List[Dict[str, Any]] = []
    cur: Optional[Dict[str, Any]] = None
    for e in inst:
        name = e["name"]
        args = e.get("args", {})
        if name not in CONTROL_EVENT_KINDS:
            continue
        if cur is None:
            if name not in _EPISODE_OPENERS:
                continue
            cur = {"classes": [], "step": args.get("step"), "mesh": None,
                   "restore_steps": [], "restores": 0, "events": []}
        cur["events"].append(name)
        if name in ("device_loss", "device_return", "crash_save"):
            if name not in cur["classes"]:
                cur["classes"].append(name)
        elif name == "rewind" and "numerics" not in cur["classes"]:
            cur["classes"].append("numerics")
        elif name == "combined_recovery":
            for c in args.get("classes", []):
                if c not in cur["classes"]:
                    cur["classes"].append(c)
        elif name in ("mesh_shrink", "mesh_grow"):
            cur["mesh"] = {"from": args.get("mesh_from"),
                           "to": args.get("mesh_to")}
        elif name == "restore":
            cur["restores"] += 1
            if args.get("step") is not None:
                cur["restore_steps"].append(args["step"])
        elif name == "ckpt_fallback" and "corrupt_checkpoint" not in cur["classes"]:
            cur["classes"].append("corrupt_checkpoint")
        if name == "plan_swap" or (name == "crash_save"
                                   and args.get("resumed")):
            episodes.append(cur)
            cur = None
    if cur is not None:
        episodes.append(cur)
    return episodes


# -- schema validation --------------------------------------------------------

_VALID_PH = {"X", "i", "M"}
_EPS_US = 1e-3  # float-roundoff slack when checking nesting, in µs


def validate_trace_events(events: Sequence[Dict[str, Any]]) -> List[str]:
    """Validate Chrome trace-event structure; return a list of problems
    (empty ⇒ valid).

    Checks, per the tracing contract:

    * every event has ``name``/``ph``/``pid``; ``ph`` is one of X/i/M;
    * ``X`` (complete) events carry numeric ``ts`` ≥ 0, ``dur`` ≥ 0 and a
      ``tid``; ``i`` (instant) events carry ``ts``;
    * within one ``(pid, tid)`` lane, spans either nest properly or are
      disjoint — partial overlap means two steps claimed the same resource
      at once, which neither the scheduler model nor eager execution can
      produce.
    """
    problems: List[str] = []
    lanes: Dict[Tuple[Any, Any], List[Tuple[float, float, str]]] = {}
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            problems.append(f"event {i}: not a dict")
            continue
        name = ev.get("name")
        ph = ev.get("ph")
        if not isinstance(name, str) or not name:
            problems.append(f"event {i}: missing name")
        if ph not in _VALID_PH:
            problems.append(f"event {i} ({name}): bad ph {ph!r}")
            continue
        if "pid" not in ev:
            problems.append(f"event {i} ({name}): missing pid")
        if ph == "M":
            continue
        ts = ev.get("ts")
        if not isinstance(ts, (int, float)) or ts < 0:
            problems.append(f"event {i} ({name}): bad ts {ts!r}")
            continue
        if ph == "X":
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                problems.append(f"event {i} ({name}): bad dur {dur!r}")
                continue
            if "tid" not in ev:
                problems.append(f"event {i} ({name}): X event missing tid")
                continue
            lanes.setdefault((ev.get("pid"), ev.get("tid")), []).append(
                (float(ts), float(dur), name))
    for (pid, tid), spans in lanes.items():
        # Sort by start; ties broken longest-first so an enclosing span is
        # seen before the spans it contains.
        spans.sort(key=lambda s: (s[0], -s[1]))
        stack: List[Tuple[float, str]] = []  # (end, name) of open spans
        for ts, dur, name in spans:
            end = ts + dur
            while stack and stack[-1][0] <= ts + _EPS_US:
                stack.pop()
            if stack and end > stack[-1][0] + _EPS_US:
                problems.append(
                    f"lane (pid={pid}, tid={tid}): span {name!r} "
                    f"[{ts:.3f}, {end:.3f}] overlaps {stack[-1][1]!r} "
                    f"(ends {stack[-1][0]:.3f}) without nesting")
                continue
            stack.append((end, name))
    return problems
