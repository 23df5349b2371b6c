"""Generate the EXPERIMENTS.md §Dry-run / §Roofline / §Partition-plans /
§Trace / §Metrics / §Profile tables.

    PYTHONPATH=src python -m repro.analysis.report [--dir artifacts/dryrun]
                                                   [--plan artifacts/bench/BENCH_plan.json]

The §Partition-plans section reads the ``BENCH_plan.json`` artifact written by
``python -m benchmarks.run --smoke`` (see benchmarks/plan_smoke.py): per
reshard cell, the cost-model planner's chosen collective sequence and its
modeled wire bytes vs the greedy AllGather-first baseline, plus the plan-cache
hit rate.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
from typing import Dict, List

from repro.analysis.roofline import terms_from_artifact
from repro.configs.registry import SHAPES, arch_ids


def load(dirname: str) -> List[Dict]:
    recs = []
    for p in sorted(glob.glob(os.path.join(dirname, "*.json"))):
        r = json.load(open(p))
        if not r.get("tag"):
            recs.append(r)
    return recs


def fmt_bytes(b):
    if b is None:
        return "-"
    return f"{b/1e9:.2f}GB"


def dryrun_table(recs: List[Dict]) -> str:
    lines = [
        "| arch | shape | mesh | status | peak mem/dev | compile s | flops/dev | wire/dev |",
        "|---|---|---|---|---|---|---|---|",
    ]
    order = {a: i for i, a in enumerate(arch_ids())}
    sorder = {s: i for i, s in enumerate(SHAPES)}
    recs = sorted(recs, key=lambda r: (order.get(r["arch"], 99),
                                       sorder.get(r["shape"], 9), r["mesh"]))
    for r in recs:
        if r["status"] == "skipped":
            lines.append(
                f"| {r['arch']} | {r['shape']} | {r['mesh']} | SKIP ({r['reason'][:40]}…) | - | - | - | - |"
            )
            continue
        if r["status"] != "ok":
            lines.append(
                f"| {r['arch']} | {r['shape']} | {r['mesh']} | **ERROR** | - | - | - | - |"
            )
            continue
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} | ok "
            f"| {fmt_bytes(r['memory']['peak_est_bytes'])} "
            f"| {r.get('compile_s_u1', 0):.1f} "
            f"| {r.get('flops_per_dev', 0):.2e} "
            f"| {r.get('wire_bytes_per_dev', 0):.2e} |"
        )
    return "\n".join(lines)


MOVE_HINTS = {
    "compute": "raise per-device work quality: cut §4.1 padding waste / causal "
               "overcompute (flash kernel) or lower remat recompute",
    "memory": "fuse/loop the bandwidth hot spot (chunked loss, smaller "
              "activation dtypes) or rebalance batch vs model axes",
    "collective": "reshard to cut gathered bytes: bf16-before-gather norms, "
                  "ReduceScatter instead of AllReduce, smaller Y for narrow dims",
}


def roofline_table(recs: List[Dict]) -> str:
    lines = [
        "| arch | shape | compute s | memory s | collective s | dominant | MODEL_FLOPS | model/HLO | MFU@roofline | what would move it |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    order = {a: i for i, a in enumerate(arch_ids())}
    sorder = {s: i for i, s in enumerate(SHAPES)}
    recs = [r for r in recs if r["mesh"] == "pod16x16"]
    recs = sorted(recs, key=lambda r: (order.get(r["arch"], 99),
                                       sorder.get(r["shape"], 9)))
    for r in recs:
        if r["status"] == "skipped":
            lines.append(f"| {r['arch']} | {r['shape']} | - | - | - | SKIP | - | - | - | sub-quadratic attention required |")
            continue
        if r["status"] != "ok":
            lines.append(f"| {r['arch']} | {r['shape']} | - | - | - | ERROR | - | - | - | - |")
            continue
        t = terms_from_artifact(r)
        lines.append(
            f"| {r['arch']} | {r['shape']} | {t.compute_s:.4f} | {t.memory_s:.4f} "
            f"| {t.collective_s:.4f} | **{t.dominant}** | {t.model_flops_total:.2e} "
            f"| {t.model_flops_ratio:.2f} | {t.mfu:.3f} | {MOVE_HINTS[t.dominant]} |"
        )
    return "\n".join(lines)


def plan_table(path: str) -> str:
    """§Partition-plans: planner-vs-greedy modeled bytes + plan-cache rate."""
    if not os.path.exists(path):
        return f"_(no plan artifact at {path}; run `python -m benchmarks.run --smoke`)_"
    rec = json.load(open(path))
    lines = [
        "| reshard cell | planned collectives | planned B/dev | vs AllGather-first | vs pre-planner greedy | vs PR1 planner |",
        "|---|---|---|---|---|---|",
    ]
    for c in rec.get("cells", []):
        lines.append(
            f"| {c['name']} | {'; '.join(c['planned'])} "
            f"| {c['planned_bytes']:.3e} | {c['ratio_vs_allgather']:.3f} "
            f"| {c['ratio_vs_legacy']:.3f} | {c.get('ratio_vs_pr1', 1.0):.3f} |"
        )
    pc = rec.get("plan_cache", {})
    if pc:
        lines.append("")
        lines.append(
            f"Plan cache: {pc.get('hits', 0)} hits / {pc.get('misses', 0)} misses "
            f"(hit rate {pc.get('hit_rate', 0.0):.2f}) — steady-state "
            "`spmd_partition` calls skip tracing, propagation, and per-equation "
            "dispatch entirely."
        )
    pp = rec.get("process_plan_cache", {})
    if pp:
        lines.append(
            f"Process-level plan cache: {pp.get('hits', 0)} hits / "
            f"{pp.get('misses', 0)} misses (hit rate {pp.get('hit_rate', 0.0):.2f}) "
            "— separate `spmd_partition` call sites share built plans keyed by "
            "jaxpr digest + mesh + avals."
        )
    return "\n".join(lines)


def plan_opt_table(path: str) -> str:
    """§Plan-optimizer: whole-plan pass-pipeline savings per benchmark cell."""
    if not os.path.exists(path):
        return f"_(no plan artifact at {path}; run `python -m benchmarks.run --smoke`)_"
    rec = json.load(open(path))
    cells = rec.get("opt_cells", [])
    if not cells:
        return "_(artifact predates the optimizer cells; re-run the smoke bench)_"
    lines = [
        "| optimizer cell | wire B/dev pre→post | collective launches pre→post | fused buckets | launch s saved | build ms (raw→opt) |",
        "|---|---|---|---|---|---|",
    ]
    for c in cells:
        lines.append(
            f"| {c['name']} "
            f"| {c['wire_bytes_before']:.3e} → {c['wire_bytes_after']:.3e} "
            f"| {c['collectives_before']} → {c['collectives_after']} "
            f"| {c['fused_buckets']} | {c['launch_s_saved']:.1e} "
            f"| {c['build_raw_ms']:.1f} → {c['build_opt_ms']:.1f} |"
        )
    inline = rec.get("inline_cells", [])
    if inline:
        lines.append("")
        lines.append(
            "| whole-program cell | whole wire B pre→post | launches pre→post "
            "| inlined | hoisted | in-body reshards pre→post | overlap ratio |"
        )
        lines.append("|---|---|---|---|---|---|---|")
        for c in inline:
            lines.append(
                f"| {c['name']} "
                f"| {c['whole_wire_bytes_before']:.3e} → "
                f"{c['whole_wire_bytes_after']:.3e} "
                f"| {c['whole_launches_before']} → {c['whole_launches_after']} "
                f"| {c['inlined_bodies']} | {c['hoisted_reshards']} "
                f"| {c['inner_reshards_before']} → {c['inner_reshards_after']} "
                f"| {c['overlap_ratio']:.3f} |"
            )
    lines.append("")
    lines.append(
        "Passes (in order): jit inlining, scan-invariant hoisting, reshard "
        "CSE, dead-reshard elimination, output-alias sinking, collective "
        "fusion/bucketing (roofline-capped), overlap-aware scheduling "
        "(max-of-terms roofline) — see `core/plan_opt.py`."
    )
    return "\n".join(lines)


def trace_table(path: str) -> str:
    """§Trace: modeled/measured lanes + per-class calibration from the obs
    bench cells (see benchmarks/plan_smoke.py `_obs_cells`)."""
    if not os.path.exists(path):
        return f"_(no plan artifact at {path}; run `python -m benchmarks.run --smoke`)_"
    rec = json.load(open(path))
    cells = rec.get("obs_cells", [])
    if not cells:
        return "_(artifact predates the obs cells; re-run the smoke bench)_"
    lines = [
        "| obs cell | steps/spans | classes | schema | modeled=schedule | trace-off overhead |",
        "|---|---|---|---|---|---|",
    ]
    for c in cells:
        spans = c.get("steps", c.get("measured_events", 0))
        classes = ",".join(c.get("classes", [])) or \
            ",".join(r["class"] for r in
                     c.get("calibration", {}).get("rows", []))
        match = c.get("makespan_matches_schedule")
        match_s = "—" if match is None else ("yes" if match else "**NO**")
        lines.append(
            f"| {c['name']} | {spans} | {classes} "
            f"| {'ok' if c.get('schema_ok') else '**BAD**'} | {match_s} "
            f"| {c.get('overhead_ratio', 0.0):.3f} "
            f"(cap {c.get('overhead_cap', 0.0):.2f}) |"
        )
    cal = next((c.get("calibration") for c in cells
                if c.get("calibration")), None)
    if cal:
        lines.append("")
        lines.append("Measured/modeled calibration (per step class, eager "
                     "dispatch included — see the tracing contract in "
                     "`repro/obs/trace.py`; §Profile below uses the "
                     "tight-timed mode, which excludes dispatch):")
        lines.append("")
        lines.append("| class | modeled s | measured s/call | ratio | flagged |")
        lines.append("|---|---|---|---|---|")
        for r in cal.get("rows", []):
            ratio = f"{r['ratio']:.3g}" if r.get("ratio") is not None else "—"
            lines.append(
                f"| {r['class']} | {r['modeled_s']:.3g} "
                f"| {r['measured_s']:.3g} | {ratio} "
                f"| {'⚠' if r.get('flagged') else ''} |")
    return "\n".join(lines)


def metrics_table(path: str) -> str:
    """§Metrics: the unified registry snapshot captured at the end of the
    smoke bench — every pre-PR-8 telemetry surface in one pane."""
    if not os.path.exists(path):
        return f"_(no plan artifact at {path}; run `python -m benchmarks.run --smoke`)_"
    rec = json.load(open(path))
    snap = rec.get("metrics")
    if not snap:
        return "_(artifact predates the metrics snapshot; re-run the smoke bench)_"
    lines = ["| counter | value |", "|---|---|"]
    for k, v in sorted(snap.get("counters", {}).items()):
        lines.append(f"| {k} | {v:g} |")
    hists = snap.get("histograms", {})
    if hists:
        lines.append("")
        lines.append("| histogram | count | mean | p50 | p99 |")
        lines.append("|---|---|---|---|---|")
        for k, h in sorted(hists.items()):
            def f(key):
                v = h.get(key)
                return f"{v:.4g}" if isinstance(v, (int, float)) else "—"
            lines.append(f"| {k} | {h.get('count', 0)} | {f('mean')} "
                         f"| {f('p50')} | {f('p99')} |")
    srcs = snap.get("sources", {})
    if srcs:
        lines.append("")
        lines.append(
            "Joined sources: " + ", ".join(f"`{s}`" for s in sorted(srcs)) +
            " — module-owned telemetry read through the same snapshot "
            "(`python -m repro.obs summarize` renders any dump)."
        )
    return "\n".join(lines)


def profile_table(path: str) -> str:
    """§Profile: the machine-profile feedback loop from the bench cells
    (benchmarks/plan_smoke.py ``_profile_cells``) — fitted roofline
    constants vs defaults, fit residuals, calibrated re-scoring, and the
    memory modeled-vs-measured join."""
    if not os.path.exists(path):
        return f"_(no plan artifact at {path}; run `python -m benchmarks.run --smoke`)_"
    rec = json.load(open(path))
    cells = rec.get("profile_cells", [])
    if not cells:
        return "_(artifact predates the profile cells; re-run the smoke bench)_"
    by = {c["name"]: c for c in cells}
    lines = []

    syn = by.get("profile_fit_synthetic")
    if syn:
        lines.append(
            "Planted-constant recovery (deterministic synthetic spans — the "
            "fitter must invert its own forward model):")
        lines.append("")
        lines.append("| constant | planted | fitted | recovered |")
        lines.append("|---|---|---|---|")
        planted, fitted = syn.get("planted", {}), syn.get("fitted", {})
        for k in sorted(syn.get("fitted_fields", [])):
            lines.append(f"| {k} | {planted.get(k, 0):.4g} "
                         f"| {fitted.get(k, 0):.4g} "
                         f"| {'yes' if syn.get('recovered') else '**NO**'} |")
        lines.append("")
        lines.append(f"Max relative error over fitted constants: "
                     f"{syn.get('max_rel_err', 0):.3g} "
                     f"(samples={syn.get('n_samples')}, "
                     f"outliers dropped={syn.get('dropped')}).")

    loop = by.get("profile_loop_tiny")
    if loop:
        lines.append("")
        lines.append(
            "End-to-end loop on this host (tight-timed spans → fit → "
            "re-score; `python -m repro.obs profile` writes the same "
            "profile JSON for `REPRO_MACHINE_PROFILE`):")
        lines.append("")
        lines.append("| constant | default | fitted | fitted? |")
        lines.append("|---|---|---|---|")
        params = loop.get("params", {})
        defaults = loop.get("defaults", {})
        fitted_fields = set(loop.get("fitted_fields", []))
        for k in sorted(params):
            lines.append(f"| {k} | {defaults.get(k, 0):.4g} "
                         f"| {params[k]:.4g} "
                         f"| {'yes' if k in fitted_fields else ''} |")
        res = loop.get("residuals", {})
        if res:
            lines.append("")
            lines.append("| step class | measured/modeled (fitted) | flagged |")
            lines.append("|---|---|---|")
            flagged = set(loop.get("flagged", []))
            for cls in sorted(res):
                lines.append(f"| {cls} | {res[cls]:.3g} "
                             f"| {'⚠' if cls in flagged else ''} |")
        lines.append("")
        lines.append(
            f"Re-score: every in-band class strictly closer to 1.0 than "
            f"default constants = "
            f"{'yes' if loop.get('improved_all') else '**NO**'} "
            f"({loop.get('in_band_classes')} class(es)); profile-off path "
            f"hits the process plan cache = "
            f"{'yes' if loop.get('off_cache_hit') else '**NO**'}; two "
            f"profiles keep distinct cache entries = "
            f"{'yes' if loop.get('isolation_ok') else '**NO**'}.")
        mem = loop.get("memory") or {}
        if mem.get("measured"):
            lines.append(
                f"Memory: modeled peak {mem.get('modeled_peak_bytes', 0):.4g} B "
                f"vs measured peak {mem.get('measured_peak_bytes', 0):.4g} B "
                f"(allocator stats joined per call).")
        elif mem:
            lines.append(
                f"Memory: modeled peak {mem.get('modeled_peak_bytes', 0):.4g} B "
                "(backend exposes no allocator stats — CPU hosts report "
                "modeled only).")

    qwen = by.get("profile_rescore_qwen")
    if qwen:
        lines.append("")
        lines.append(
            "| re-score cell | total_s (defaults) | total_s (calibrated) "
            "| changed | ratio vs baseline |")
        lines.append("|---|---|---|---|---|")
        lines.append(
            f"| {qwen['name']} | {qwen.get('default_total_s', 0):.3e} "
            f"| {qwen.get('profiled_total_s', 0):.3e} "
            f"| {'yes' if qwen.get('total_s_changed') else '**NO**'} "
            f"| {qwen.get('ratio_vs_baseline', 0):.3f} |")
        lines.append("")
        lines.append(
            "A calibrated profile re-prices every candidate lowering "
            "(`AutoshardConfig(profile=...)` → `lower_for_cost`), so the "
            "searched cost moves with the machine — but the searched "
            "assignment still never loses to the hand-annotated baseline.")
    return "\n".join(lines)


RECOVERY_STATE_MACHINE = """\
Single-pass combined recovery (`ElasticCoordinator._recover_combined`):
coincident faults inside one `coincidence_window` are classified together
and resolved with **exactly one** `restore_resharded` onto the *new* mesh.

| fault class | coordinator action | restore path | control-lane events |
|---|---|---|---|
| `numerics` (NaN/inf, grad spike) | skip up to `rewind_after`, then rewind to last intact step; re-arm sentinel | same-mesh restore unless coincident with a mesh change | `numerics_fault`, `skip_step`, `rewind`, `restore`, `plan_swap` |
| `device_loss` | shrink world, `derive_mesh`, warm re-solve via `remap_assignment` (DP degradation allowed) | `restore_resharded` onto the shrunk mesh | `device_loss`, `mesh_shrink`, `restore`, `plan_swap` |
| `device_return` | grow world, `derive_mesh`, warm re-solve via `expand_assignment` (axis lifting) | `restore_resharded` onto the grown mesh | `device_return`, `mesh_grow`, `restore`, `plan_swap` |
| `corrupt_checkpoint` (discovered mid-restore) | fall back to newest older step that verifies, inside the same pass | fallback restore; replayed steps re-save over the bad dir | `ckpt_fallback`, `restore`, `plan_swap` |
| `crash_save` (torn/failed save) | resume from last durable step; tmp-dir rename keeps partial saves invisible | full restore on resume | `crash_save(resumed)`, `restore`, `plan_swap` |
| any ≥2 of the above | one classification pass, one restore | single `restore_resharded` onto the final mesh | the per-class events plus one `combined_recovery` |

Provenance for every pass lands in the checkpoint manifest `extra`
(classes, source step, mesh) and the control lane (`repro.obs.trace`);
`recovery_narrative(events)` folds the lane back into episodes, and the
chaos harness (`python -m repro.launch.chaos`) asserts
`restores == restoring recoveries` after every seeded campaign."""


def elastic_table(path: str) -> str:
    """§Elastic: the recovery state machine plus the chaos-soak cells from
    the bench artifact (seeded campaign, invariant battery, warm-vs-cold
    re-solve evals, recovery wall-clock)."""
    lines = [RECOVERY_STATE_MACHINE]
    if not os.path.exists(path):
        return "\n".join(lines)
    rec = json.load(open(path))
    cells = rec.get("chaos_cells")
    if not cells:
        return "\n".join(lines)
    lines.append("")
    lines.append("| soak | seed | steps | events | recoveries | restores "
                 "| warm evals | cold evals | violations | recovery ms "
                 "(mean/max) |")
    lines.append("|---|---|---|---|---|---|---|---|---|---|")
    for c in cells:
        lines.append(
            f"| {c['name']} | {c['seed']} | {c['steps']} | {c['n_events']} "
            f"| {c['recoveries']} | {c['restores']} "
            f"| {c['evals_warm_max']} | {c['evals_cold']} "
            f"| {len(c.get('violations', []))} "
            f"| {c.get('recovery_ms_mean', 0):.0f}/"
            f"{c.get('recovery_ms_max', 0):.0f} |")
    return "\n".join(lines)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="artifacts/dryrun")
    ap.add_argument("--plan", default="artifacts/bench/BENCH_plan.json")
    args = ap.parse_args()
    recs = load(args.dir)
    print("## §Dry-run\n")
    print(dryrun_table(recs))
    print("\n## §Roofline (single pod, 256 chips)\n")
    print(roofline_table(recs))
    print("\n## §Partition plans (reshard planner vs greedy baseline)\n")
    print(plan_table(args.plan))
    print("\n## §Plan optimizer (whole-plan pass pipeline)\n")
    print(plan_opt_table(args.plan))
    print("\n## §Trace (modeled vs measured plan timelines)\n")
    print(trace_table(args.plan))
    print("\n## §Metrics (unified registry snapshot)\n")
    print(metrics_table(args.plan))
    print("\n## §Profile (machine-profile fitting → calibrated cost model)\n")
    print(profile_table(args.plan))
    print("\n## §Elastic (recovery state machine + chaos soaks)\n")
    print(elastic_table(args.plan))


if __name__ == "__main__":
    main()
