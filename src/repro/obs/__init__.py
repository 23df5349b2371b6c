"""Plan-native observability: step tracing, metrics, modeled-vs-measured
calibration.

Three layers over the compiled-plan runtime (the GSPMD repro's answer to
"the headline claim is *measured* utilization, but we can only model"):

* :mod:`repro.obs.metrics` — one process-wide registry of thread-safe
  counters / gauges / histograms.  The five pre-existing telemetry surfaces
  (plan-cache hit rates, lattice-search counters, verifier telemetry,
  autoshard search/eval timing, elastic fault/skip/rewind counters) all land
  in — or are joined into — a single :func:`~repro.obs.metrics.snapshot`,
  dumpable as JSON (``REPRO_METRICS_DUMP=path``).
* :mod:`repro.obs.trace` — :func:`~repro.obs.trace.span`, the ``repro.*``
  host spans on the profiler's clock (the runner, its plan build and first
  compile, the input pipeline), which with the scopes the partitioned
  program keeps are what time the jitted program in a device trace; and
  opt-in traced execution for compiled plans
  (``spmd_partition(trace=TraceConfig(...))``): per-step measured spans on
  the two lanes the overlap scheduler models (compute / interconnect), a
  *modeled* timeline emitted straight from the overlap schedule, and elastic
  control events (fault, skip, rewind, mesh shrink, plan swap) as instant
  events — all exported as Chrome trace-event JSON (Perfetto-loadable).
* :mod:`repro.obs.calibrate` — join measured span seconds against the
  roofline's modeled per-step seconds into a per-step-class
  :class:`~repro.obs.calibrate.CalibrationReport` (the groundwork for honest
  Pallas-kernel pricing: a class whose measured/modeled ratio is off by more
  than the tolerance factor is flagged).
* :mod:`repro.obs.profile` — the calibration feedback loop: tight-timed
  spans (``TraceConfig(timing="tight")``) joined with per-step cost features
  are fitted into a :class:`~repro.obs.profile.MachineProfile` of effective
  :class:`~repro.analysis.roofline.RooflineParams`, which route back into
  every costing surface (``spmd_partition(profile=...)``,
  ``AutoshardConfig(profile=...)``, ``REPRO_MACHINE_PROFILE=path``).

``python -m repro.obs summarize <metrics.json>``,
``python -m repro.obs trace <out.json>``, and
``python -m repro.obs profile <out.json>`` give CLI access (see
``__main__``).
"""
from .calibrate import CalibrationReport, attach_profile, calibration_report
from .metrics import (
    MetricsRegistry,
    registry,
    snapshot,
)
from .profile import (
    MachineProfile,
    StepSample,
    collect_samples,
    device_memory_stats,
    fit_profile,
    memory_report,
    rescore_report,
    resolve_profile,
)
from .trace import (
    CONTROL_EVENT_KINDS,
    TraceConfig,
    Tracer,
    control_event,
    control_events,
    export_control_trace,
    recovery_narrative,
    reset_control_events,
    span,
    validate_trace_events,
)

__all__ = [
    "CONTROL_EVENT_KINDS",
    "CalibrationReport",
    "MachineProfile",
    "MetricsRegistry",
    "StepSample",
    "TraceConfig",
    "Tracer",
    "attach_profile",
    "calibration_report",
    "collect_samples",
    "control_event",
    "control_events",
    "device_memory_stats",
    "export_control_trace",
    "fit_profile",
    "memory_report",
    "recovery_narrative",
    "registry",
    "rescore_report",
    "reset_control_events",
    "resolve_profile",
    "snapshot",
    "span",
    "validate_trace_events",
]
