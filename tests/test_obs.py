"""Observability layer: metrics registry, plan-step tracing, calibration.

Covers the PR-8 acceptance surface: thread-safe instruments and JSON
snapshot round-trips, the Chrome trace-event schema validator (including
seeded-invalid events and lane-overlap detection), modeled-timeline /
overlap-schedule consistency, measured tracing on a real (1-device) runner
with numerics unchanged vs the untraced path, per-class calibration joins,
control-event export, and the ``python -m repro.obs`` CLI.
"""
import json
import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))

from repro import obs
from repro.core import Mesh, annotate, mesh_split, propagate
# imported for their snapshot sources (joined lazily via sys.modules)
from repro.core import partitioner as _partitioner  # noqa: F401
from repro.core import plan_verify as _plan_verify  # noqa: F401
from repro.core.plan import compile_plan
from repro.core.plan_opt import modeled_timeline, step_class
from repro.obs import calibrate, metrics, trace

mesh = Mesh.create((4, 8), ("x", "y"))


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _plan(f, *avals):
    closed = jax.make_jaxpr(f)(*avals)
    prop = propagate(closed, mesh).result()
    return compile_plan(closed, prop, mesh)


def _mlp(a, w1, w2):
    a = annotate(a, mesh_split(2, mesh, ["x", -1]))
    w1 = annotate(w1, mesh_split(2, mesh, [-1, "y"]))
    h = jnp.maximum(a @ w1, 0.0)
    h = annotate(h, mesh_split(2, mesh, ["x", -1]))
    return h @ w2


MLP_AVALS = (_f32(64, 32), _f32(32, 64), _f32(64, 16))


# ---------------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------------


def test_counter_thread_safety():
    reg = metrics.MetricsRegistry()
    n_threads, per_thread = 8, 2000

    def work():
        for _ in range(per_thread):
            reg.inc("hits")

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.counter("hits").value == n_threads * per_thread


def test_histogram_concurrent_observe_keeps_count_and_sum():
    reg = metrics.MetricsRegistry()
    n_threads, per_thread = 4, 500

    def work():
        for i in range(per_thread):
            reg.observe("lat", float(i))

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    h = reg.histogram("lat")
    assert h.count == n_threads * per_thread
    assert h.summary()["sum"] == pytest.approx(
        n_threads * sum(range(per_thread)))


def test_histogram_percentiles_match_numpy():
    h = metrics.Histogram("h")
    rng = np.random.RandomState(0)
    vals = rng.exponential(10.0, size=501)
    for v in vals:
        h.observe(float(v))
    for p in (0, 25, 50, 90, 99, 100):
        assert h.percentile(p) == pytest.approx(np.percentile(vals, p))
    s = h.summary()
    assert s["count"] == 501
    assert s["min"] == pytest.approx(vals.min())
    assert s["max"] == pytest.approx(vals.max())
    assert s["mean"] == pytest.approx(vals.mean())


def test_histogram_thinning_keeps_exact_count():
    h = metrics.Histogram("h")
    n = metrics.MAX_SAMPLES + 1000
    for i in range(n):
        h.observe(float(i))
    assert h.count == n                       # count/sum/min/max stay exact
    assert h.summary()["max"] == float(n - 1)
    assert len(h._values) <= metrics.MAX_SAMPLES
    # percentiles stay representative after 2:1 thinning (post-thin samples
    # arrive unthinned, so recent values are slightly over-weighted)
    assert h.percentile(50) == pytest.approx((n - 1) / 2, rel=0.05)


def test_empty_and_single_sample_percentiles():
    h = metrics.Histogram("h")
    assert h.percentile(50) is None
    assert h.summary()["mean"] is None
    h.observe(7.0)
    assert h.percentile(0) == h.percentile(100) == 7.0


def test_snapshot_roundtrips_through_json(tmp_path):
    reg = metrics.MetricsRegistry()
    reg.inc("a.hits", 3)
    reg.set_gauge("mesh.devices", 8)
    for v in (1.0, 2.0, 3.0):
        reg.observe("step_ms", v)
    p = reg.dump(str(tmp_path / "m.json"))
    with open(p) as f:
        snap = json.load(f)
    assert snap["counters"]["a.hits"] == 3
    assert snap["gauges"]["mesh.devices"] == 8
    assert snap["histograms"]["step_ms"]["count"] == 3
    assert snap["histograms"]["step_ms"]["p50"] == 2.0
    # builtin sources joined (core modules are imported by this test session)
    assert "lattice" in snap["sources"]
    assert "plan_verify" in snap["sources"]
    assert "process_plan_cache" in snap["sources"]


def test_broken_source_degrades_to_error_marker():
    reg = metrics.MetricsRegistry()

    def boom():
        raise RuntimeError("source down")

    reg.register_source("flaky", boom)
    snap = reg.snapshot()
    assert snap["sources"]["flaky"] == {"error": "source down"}


def test_reset_clears_instruments_keeps_sources():
    reg = metrics.MetricsRegistry()
    reg.inc("x")
    reg.register_source("s", lambda: {"ok": 1})
    reg.reset()
    snap = reg.snapshot()
    assert snap["counters"] == {}
    assert snap["sources"]["s"] == {"ok": 1}


def test_maybe_dump_env(tmp_path, monkeypatch):
    p = str(tmp_path / "dump.json")
    monkeypatch.setenv(metrics.DUMP_ENV, p)
    metrics.inc("dump.test.marker")
    assert metrics.maybe_dump() == p
    with open(p) as f:
        assert json.load(f)["counters"]["dump.test.marker"] >= 1
    monkeypatch.delenv(metrics.DUMP_ENV)
    assert metrics.maybe_dump() is None


def test_atexit_dump_writes_snapshot_in_subprocess(tmp_path):
    """The ``REPRO_METRICS_DUMP`` atexit hook (registered at import when the
    env var is set) must write a loadable snapshot when the interpreter
    exits normally — the in-process ``maybe_dump`` test above can't cover
    the atexit path itself."""
    import subprocess

    p = str(tmp_path / "atexit.json")
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env = dict(os.environ)
    env[metrics.DUMP_ENV] = p
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + \
        env.get("PYTHONPATH", "")
    code = (
        "from repro.obs import metrics\n"
        "metrics.inc('atexit.test.marker', 2)\n"
        "metrics.set_gauge('atexit.test.gauge', 1.5)\n"
        "metrics.observe('atexit.test.hist', 3.0)\n"
    )
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   timeout=120)
    with open(p) as f:
        snap = json.load(f)
    assert snap["counters"]["atexit.test.marker"] == 2
    assert snap["gauges"]["atexit.test.gauge"] == 1.5
    assert snap["histograms"]["atexit.test.hist"]["count"] == 1


def test_module_level_registry_is_process_wide():
    metrics.inc("proc.wide.marker", 5)
    assert metrics.registry().counter("proc.wide.marker").value >= 5
    assert metrics.snapshot()["counters"]["proc.wide.marker"] >= 5


# ---------------------------------------------------------------------------------
# trace schema validator
# ---------------------------------------------------------------------------------


def _span(name, ts, dur, pid=2, tid=1, **args):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "pid": pid,
            "tid": tid, "args": args}


def test_validator_accepts_valid_events():
    events = [
        {"name": "process_name", "ph": "M", "pid": 1, "args": {"name": "m"}},
        _span("a", 0.0, 10.0),
        _span("b", 10.0, 5.0),
        {"name": "fault", "ph": "i", "s": "g", "ts": 3.0, "pid": 3, "tid": 1},
    ]
    assert trace.validate_trace_events(events) == []


def test_validator_allows_proper_nesting():
    events = [_span("outer", 0.0, 100.0), _span("inner", 10.0, 20.0),
              _span("inner2", 40.0, 50.0)]
    assert trace.validate_trace_events(events) == []


def test_validator_flags_partial_overlap_within_lane():
    events = [_span("a", 0.0, 10.0), _span("b", 5.0, 10.0)]
    problems = trace.validate_trace_events(events)
    assert any("overlaps" in p for p in problems)
    # same spans on *different* lanes are fine (that's what lanes are for)
    events2 = [_span("a", 0.0, 10.0), _span("b", 5.0, 10.0, tid=2)]
    assert trace.validate_trace_events(events2) == []


def test_validator_flags_malformed_events():
    bad_ph = {"name": "x", "ph": "Z", "pid": 1, "ts": 0.0}
    assert any("bad ph" in p for p in trace.validate_trace_events([bad_ph]))
    no_ts = {"name": "x", "ph": "X", "pid": 1, "dur": 1.0, "tid": 1}
    assert any("bad ts" in p for p in trace.validate_trace_events([no_ts]))
    neg_dur = _span("x", 0.0, -1.0)
    assert any("bad dur" in p for p in trace.validate_trace_events([neg_dur]))
    no_tid = {"name": "x", "ph": "X", "pid": 1, "ts": 0.0, "dur": 1.0}
    assert any("missing tid" in p
               for p in trace.validate_trace_events([no_tid]))
    no_name = {"ph": "X", "pid": 1, "ts": 0.0, "dur": 1.0, "tid": 1}
    assert any("missing name" in p
               for p in trace.validate_trace_events([no_name]))
    assert any("not a dict" in p
               for p in trace.validate_trace_events(["nope"]))


# ---------------------------------------------------------------------------------
# modeled timeline
# ---------------------------------------------------------------------------------


def test_modeled_timeline_matches_overlap_schedule():
    plan = _plan(_mlp, *MLP_AVALS)
    rows = modeled_timeline(plan)
    assert len(rows) == len(plan.steps)
    makespan = max(r["start_s"] + r["dur_s"] for r in rows)
    assert makespan == pytest.approx(
        plan.opt_report.overlap["overlapped_s"], rel=1e-9)
    # every row carries the taxonomy class of its step, in final step order
    assert [r["cls"] for r in rows] == [step_class(s) for s in plan.steps]
    assert [r["index"] for r in rows] == list(range(len(plan.steps)))
    # comm-only steps land on the interconnect lane, compute on compute
    for r, s in zip(rows, plan.steps):
        if r["comm_s"] > 0.0 and r["compute_s"] == 0.0:
            assert r["lane"] == "interconnect"
        if r["comm_s"] == 0.0:
            assert r["lane"] == "compute"


def test_tracer_modeled_lane_validates_and_offsets_plan_swaps():
    plan = _plan(_mlp, *MLP_AVALS)
    tr = trace.Tracer(trace.TraceConfig(measured=False))
    tr.on_plan(plan)
    first = tr.modeled_events()
    tr.on_plan(plan)  # a swap: second timeline appended after the first
    events = tr.chrome_trace(include_control=False)["traceEvents"]
    assert trace.validate_trace_events(events) == []
    second = [e for e in tr.modeled_events() if e["args"]["plan"] == 1]
    assert len(second) == len(first)
    end_first = max(e["ts"] + e["dur"] for e in first)
    assert all(e["ts"] >= end_first - 1e-6 for e in second)


def test_step_class_taxonomy():
    plan = _plan(_mlp, *MLP_AVALS)
    classes = {step_class(s) for s in plan.steps}
    assert "compute" in classes
    assert classes & {"reshard", "collective"}
    for s in plan.steps:
        if s.inner is not None:
            assert step_class(s).startswith("call:")


# ---------------------------------------------------------------------------------
# traced execution on a real (1-device) runner
# ---------------------------------------------------------------------------------


def _one_device_runner(trace_cfg):
    from repro.core.partitioner import spmd_partition

    m1 = Mesh.create((1, 1), ("x", "y"))
    jmesh = jax.sharding.Mesh(
        np.array(jax.devices()[:1]).reshape(1, 1), ("x", "y"))

    def f(a, b):
        a = annotate(a, mesh_split(2, m1, ["x", -1]))
        return jnp.tanh(a @ b)

    return spmd_partition(f, jmesh, m1, trace=trace_cfg)


def test_traced_execution_matches_untraced_numerics():
    from repro.core.partitioner import clear_process_plan_cache

    clear_process_plan_cache()
    a = np.random.RandomState(0).randn(8, 8).astype(np.float32)
    b = np.random.RandomState(1).randn(8, 8).astype(np.float32)
    base = _one_device_runner(None)
    traced = _one_device_runner(obs.TraceConfig())
    ref = np.asarray(base(a, b))
    out = np.asarray(traced(a, b))
    np.testing.assert_allclose(out, ref, rtol=1e-6)
    tr = traced.tracer
    assert tr is not None and tr.calls == 1
    (entry,) = traced.plans.values()
    nsteps = len(entry.plan.steps)
    measured = tr.measured_events()
    assert len(measured) == nsteps
    assert {e["args"]["call"] for e in measured} == {0}
    events = tr.chrome_trace()["traceEvents"]
    assert trace.validate_trace_events(events) == []
    # second call appends a second measured pass
    traced(a, b)
    assert tr.calls == 2
    assert len(tr.measured_events()) == 2 * nsteps


def test_disabled_trace_config_is_normalized_away():
    from repro.core.partitioner import (clear_process_plan_cache,
                                        process_plan_cache_stats)

    clear_process_plan_cache()
    a = np.ones((8, 8), np.float32)
    base = _one_device_runner(None)
    off = _one_device_runner(obs.TraceConfig(enabled=False))
    base(a, a)
    off(a, a)  # plans compile lazily on first call: this one must cache-hit
    # disabled config ≡ no tracing: same process-cache entry, no tracer
    assert process_plan_cache_stats().hits >= 1
    assert off.tracer is None and base.tracer is None


def test_trace_write_roundtrip(tmp_path):
    plan = _plan(_mlp, *MLP_AVALS)
    tr = trace.Tracer(trace.TraceConfig(measured=False))
    tr.on_plan(plan)
    p = tr.write(str(tmp_path / "trace.json"))
    with open(p) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    assert trace.validate_trace_events(events) == []
    names = {e["args"]["name"] for e in events if e["ph"] == "M"
             and e["name"] == "process_name"}
    assert names == {"modeled", "measured", "control"}


# ---------------------------------------------------------------------------------
# control events
# ---------------------------------------------------------------------------------


def test_control_events_record_and_export():
    obs.reset_control_events()
    trace.control_event("numerics_fault", step=4, consecutive=1)
    trace.control_event("skip_step", step=4)
    evs = obs.control_events()
    assert [e["name"] for e in evs] == ["numerics_fault", "skip_step"]
    assert evs[0]["ts"] <= evs[1]["ts"]
    doc = obs.export_control_trace()
    instants = [e for e in doc["traceEvents"] if e["ph"] == "i"]
    assert [e["name"] for e in instants] == ["numerics_fault", "skip_step"]
    assert all(e["pid"] == trace.CONTROL_PID for e in instants)
    assert trace.validate_trace_events(doc["traceEvents"]) == []
    obs.reset_control_events()
    assert obs.control_events() == []


# ---------------------------------------------------------------------------------
# calibration
# ---------------------------------------------------------------------------------


def test_calibration_joins_by_class_and_normalizes_by_calls():
    events = [
        # modeled: compute 10 µs, collective 100 µs
        _span("m1", 0, 10.0, pid=trace.MODELED_PID, **{"class": "compute"}),
        _span("m2", 10, 100.0, pid=trace.MODELED_PID, tid=2,
              **{"class": "collective"}),
        # measured, 2 calls: compute 20+20 µs, collective 100+100 µs
        _span("x1", 0, 20.0, pid=trace.MEASURED_PID,
              **{"class": "compute", "call": 0}),
        _span("x2", 20, 100.0, pid=trace.MEASURED_PID, tid=2,
              **{"class": "collective", "call": 0}),
        _span("x3", 200, 20.0, pid=trace.MEASURED_PID,
              **{"class": "compute", "call": 1}),
        _span("x4", 220, 100.0, pid=trace.MEASURED_PID, tid=2,
              **{"class": "collective", "call": 1}),
    ]
    rep = calibrate.calibration_report(events, factor=3.0)
    assert rep.calls == 2 and rep.complete
    comp = rep.row("compute")
    # measured totals are per-call: (20+20)/2 = 20 µs → ratio 2, in band
    assert comp.ratio == pytest.approx(2.0)
    assert not comp.flagged
    coll = rep.row("collective")
    assert coll.ratio == pytest.approx(1.0)
    assert rep.flagged == []
    # a dict export works too
    rep2 = calibrate.calibration_report({"traceEvents": events})
    assert rep2.as_dict()["rows"] == rep.as_dict()["rows"]


def test_calibration_flags_out_of_band_classes():
    events = [
        _span("m", 0, 1.0, pid=trace.MODELED_PID, **{"class": "compute"}),
        _span("x", 0, 10.0, pid=trace.MEASURED_PID,
              **{"class": "compute", "call": 0}),
    ]
    rep = calibrate.calibration_report(events, factor=3.0)
    assert rep.row("compute").ratio == pytest.approx(10.0)
    assert rep.flagged == ["compute"]
    # a generous factor un-flags it
    assert calibrate.calibration_report(events, factor=20.0).flagged == []


def test_calibration_zero_modeled_classes_dont_block_completeness():
    events = [
        _span("m", 0, 0.0, pid=trace.MODELED_PID, **{"class": "reshard"}),
        _span("m2", 0, 5.0, pid=trace.MODELED_PID, **{"class": "compute"}),
        _span("x", 0, 7.0, pid=trace.MEASURED_PID,
              **{"class": "compute", "call": 0}),
        _span("x2", 7, 1.0, pid=trace.MEASURED_PID,
              **{"class": "reshard", "call": 0}),
    ]
    rep = calibrate.calibration_report(events)
    assert rep.complete                       # reshard modeled at 0 excluded
    assert rep.row("reshard").ratio is None
    # ...but a priced class with no measured spans breaks completeness
    rep2 = calibrate.calibration_report(events[:3])
    assert not rep2.complete or rep2.row("compute").ratio is not None


def test_calibration_table_renders():
    events = [
        _span("m", 0, 1.0, pid=trace.MODELED_PID, **{"class": "compute"}),
        _span("x", 0, 2.0, pid=trace.MEASURED_PID,
              **{"class": "compute", "call": 0}),
    ]
    t = calibrate.calibration_report(events).table()
    assert "| class |" in t and "| compute |" in t


# ---------------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------------


def test_cli_summarize(tmp_path, capsys):
    from repro.obs.__main__ import main

    reg = metrics.MetricsRegistry()
    reg.inc("a.hits", 3)
    reg.set_gauge("g", 1.5)
    reg.observe("lat_ms", 2.0)
    p = reg.dump(str(tmp_path / "m.json"))
    assert main(["summarize", p]) == 0
    out = capsys.readouterr().out
    assert "a.hits" in out and "lat_ms" in out and "counters" in out


def test_cli_trace_emits_valid_chrome_json(tmp_path, capsys):
    from repro.obs.__main__ import main

    p = str(tmp_path / "trace.json")
    rc = main(["trace", p, "--mesh", "1x2", "--axes", "data,model",
               "--batch", "2", "--seq", "16", "--reduce-k", "4"])
    assert rc == 0
    with open(p) as f:
        doc = json.load(f)
    assert trace.validate_trace_events(doc["traceEvents"]) == []
    assert any(e["ph"] == "X" and e["pid"] == trace.MODELED_PID
               for e in doc["traceEvents"])
    out = capsys.readouterr().out
    assert "steps=" in out and "makespan=" in out
