import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST come first: jax locks the platform and device count on first init.
# The dry run is a CPU tool: its 512 placeholder CPU devices exist ONLY for
# the dry-run meshes, and on a TPU host it must not claim the chip.

"""Multi-pod dry-run: ``.lower().compile()`` every (arch × shape × mesh) cell.

For each cell:
  * build abstract state (ShapeDtypeStructs with NamedShardings — no allocation),
  * lower + compile the train_step / serve_step on the production mesh,
  * print memory_analysis() (proves it fits) and cost_analysis(),
  * parse collective bytes from the compiled HLO,
  * apply the unroll-delta trick (u1 vs u2 scan unroll) for exact
    L-proportional FLOPs/bytes/collective accounting,
  * write a JSON artifact consumed by the roofline report and benchmarks.

Usage:
  python -m repro.launch.dryrun --arch qwen1.5-0.5b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod-only|--single-pod-only]
"""
import argparse
import json
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis.hlo_parse import collective_bytes
from repro.analysis.roofline import (
    ICI_BW, count_params, extrapolate, model_flops,
)
from repro.core.compat import cost_analysis_dict, set_mesh
from repro.configs.base import get_strategy
from repro.configs.registry import (
    SHAPES, arch_ids, cell_supported, default_strategy, get_config, input_specs,
)
from repro.launch.mesh import make_production_mesh
from repro.models import api
from repro.models.layers import tree_shapes, tree_specs
from repro.train.loop import TrainConfig, make_train_step
from repro.train.optimizer import get_optimizer, opt_state_specs

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "artifacts", "dryrun")


def _ns(mesh):
    return lambda spec: NamedSharding(mesh, spec)


def _batch_sharding(mesh, name, shape):
    sizes = dict(zip(mesh.axis_names, mesh.shape.values())) if hasattr(mesh.shape, "values") else dict(zip(mesh.axis_names, mesh.devices.shape))
    axes, n = [], 1
    for a in ("pod", "data"):
        if a in mesh.axis_names and shape[0] % (n * sizes[a]) == 0:
            axes.append(a)
            n *= sizes[a]
    lead = tuple(axes) if len(axes) > 1 else (axes[0] if axes else None)
    spec = P(lead, *([None] * (len(shape) - 1)))
    return NamedSharding(mesh, spec)


def abstract_state(cfg, st, mesh, opt):
    ns = _ns(mesh)
    tree = api.param_tree(cfg, st)
    params = tree_shapes(tree, sharding_for=ns)
    if cfg.param_dtype == "bfloat16":
        # bf16 param storage (§Perf): halves ZeRO gather bytes + param traffic
        params = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16, sharding=s.sharding)
            if s.dtype == jnp.float32 else s,
            params,
        )
    specs = tree_specs(tree)
    opt_shapes = jax.eval_shape(opt.init, params)
    opt_specs = opt_state_specs(opt, specs, params)
    opt_sds = jax.tree_util.tree_map(
        lambda s, sp: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=NamedSharding(mesh, sp)),
        opt_shapes,
        opt_specs,
    )
    return {
        "params": params,
        "opt": opt_sds,
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }


def lower_cell(arch: str, shape: str, *, multi_pod: bool, unroll: int = 1,
               strategy: Optional[str] = None, cfg_overrides: Optional[dict] = None,
               analysis_layers: Optional[int] = None):
    """Lower+compile one cell; returns (compiled, metadata).

    ``analysis_layers``: lower a depth-truncated variant with a *python loop*
    instead of scan (identical per-layer HLO, no scan-body-counted-once issue) —
    used by the layers-delta roofline accounting."""
    cfg = get_config(arch).with_(scan_unroll=unroll, **(cfg_overrides or {}))
    if analysis_layers is not None:
        kw = {"num_layers": analysis_layers, "scan_layers": False}
        if cfg.encoder_layers:
            kw["encoder_layers"] = analysis_layers
        cfg = cfg.with_(**kw)
    case = SHAPES[shape]
    if case.kind == "decode" and case.global_batch < 16:
        # tiny decode batch: shard the kv-cache sequence dim instead (flash-decode)
        cfg = cfg.with_(shard_kv_seq=True)
    st = get_strategy(strategy or default_strategy(arch))
    mesh = make_production_mesh(multi_pod=multi_pod)
    opt = get_optimizer("adafactor")
    with set_mesh(mesh):
        # param/strategy construction must happen inside the mesh context
        if case.kind in ("train", "prefill"):
            state = abstract_state(cfg, st, mesh, opt)
            batch = {
                k: jax.ShapeDtypeStruct(
                    v.shape, v.dtype, sharding=_batch_sharding(mesh, k, v.shape)
                )
                for k, v in input_specs(arch, shape, cfg).items()
            }
            if case.kind == "train":
                accum = getattr(cfg, "_grad_accum", 1)
                step = make_train_step(cfg, st, opt, TrainConfig(grad_accum=accum))
                lowered = jax.jit(step, donate_argnums=(0,)).lower(state, batch)
            else:  # prefill: forward only (inference)
                def fwd(params, b):
                    return api.loss_fn(cfg, st, params, b)

                lowered = jax.jit(fwd).lower(state["params"], batch)
        else:  # decode — serving runs bf16 params (production-realistic)
            tree = api.param_tree(cfg, st)
            params = tree_shapes(tree, sharding_for=_ns(mesh))
            params = jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct(
                    s.shape, jnp.bfloat16, sharding=s.sharding
                ) if s.dtype == jnp.float32 else s,
                params,
            )
            cache = api.abstract_cache(
                cfg, st, case.global_batch, case.seq_len, sharding_for=_ns(mesh)
            )
            token = jax.ShapeDtypeStruct(
                (case.global_batch, 1), jnp.int32,
                sharding=_batch_sharding(mesh, "token", (case.global_batch, 1)),
            )
            pos = jax.ShapeDtypeStruct((), jnp.int32)

            def serve_step(p, t, c, pos):
                return api.decode_step(cfg, st, p, t, c, pos)

            lowered = jax.jit(serve_step, donate_argnums=(2,)).lower(
                params, token, cache, pos
            )
        t0 = time.time()
        compiled = lowered.compile()
        compile_s = time.time() - t0
    return compiled, {"cfg": cfg, "compile_s": compile_s, "mesh": mesh}


def superblock_of(cfg) -> int:
    if cfg.family == "hybrid":
        return cfg.attn_every or 8
    if cfg.moe and cfg.moe_every > 1:
        return cfg.moe_every
    return 1


def scan_length(cfg) -> int:
    return cfg.num_layers // superblock_of(cfg)


def run_cell(arch: str, shape: str, *, multi_pod: bool, out_dir: str,
             strategy: Optional[str] = None, verbose: bool = True,
             cfg_overrides: Optional[dict] = None, tag: str = "") -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    sname = strategy or default_strategy(arch)
    key = f"{arch}_{shape}_{mesh_name}" + (f"_{tag}" if tag else "")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, key + ".json")
    ok, why = cell_supported(arch, shape)
    rec = {
        "arch": arch, "shape": shape, "mesh": mesh_name, "strategy": sname,
        "chips": 512 if multi_pod else 256, "tag": tag,
    }
    if not ok:
        rec.update({"status": "skipped", "reason": why})
        json.dump(rec, open(path, "w"), indent=1)
        if verbose:
            print(f"[SKIP] {key}: {why}")
        return rec
    try:
        cfg = get_config(arch).with_(**(cfg_overrides or {}))
        sb = superblock_of(cfg)
        nb = scan_length(cfg)
        compiled, meta = lower_cell(
            arch, shape, multi_pod=multi_pod, unroll=1, strategy=strategy,
            cfg_overrides=cfg_overrides,
        )
        ma = compiled.memory_analysis()
        ca = cost_analysis_dict(compiled)
        txt = compiled.as_text()
        coll1 = collective_bytes(txt)
        flops1 = float(ca.get("flops", 0.0))
        bytes1 = float(ca.get("bytes accessed", 0.0))
        rec["memory"] = {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_est_bytes": ma.argument_size_in_bytes
            + ma.temp_size_in_bytes
            + ma.output_size_in_bytes
            - ma.alias_size_in_bytes,
        }
        rec["compile_s_u1"] = meta["compile_s"]
        rec["hlo_collectives_u1"] = coll1
        # per-kind modeled seconds on the roofline link bandwidth — the same
        # byte model the reshard planner minimizes, so planner decisions and
        # compiled-HLO accounting are directly comparable
        rec["modeled_collective_s_u1"] = {
            kind: coll1[kind]["wire_bytes"] / ICI_BW
            for kind in ("all-gather", "all-reduce", "reduce-scatter",
                         "all-to-all", "collective-permute")
            if coll1.get(kind, {}).get("count")
        }
        if verbose:
            print(f"[{key}] memory_analysis: {ma}")
            print(f"[{key}] cost_analysis: flops={flops1:.3e} bytes={bytes1:.3e}")
        # layers-delta for exact depth scaling (single-pod analysis only):
        # lower 1-block and 2-block python-loop variants; the difference is one
        # block's exact per-device cost, free of scan-body accounting artifacts.
        if not multi_pod and nb > 1:
            vals = {}
            for n in (1, 2):
                c_n, _ = lower_cell(
                    arch, shape, multi_pod=multi_pod, strategy=strategy,
                    cfg_overrides=cfg_overrides, analysis_layers=n * sb,
                )
                ca_n = cost_analysis_dict(c_n)
                coll_n = collective_bytes(c_n.as_text())
                vals[n] = (
                    float(ca_n.get("flops", 0.0)),
                    float(ca_n.get("bytes accessed", 0.0)),
                    coll_n["wire_bytes"],
                    coll_n["operand_bytes"],
                    coll_n.get("rs_adjusted_wire_bytes", coll_n["wire_bytes"]),
                )
            f1, b1, w1, o1, r1 = vals[1]
            f2, b2, w2, o2, r2 = vals[2]
            rec["flops_per_dev"] = extrapolate(f1, f2, 1, 2, nb)
            rec["bytes_per_dev"] = extrapolate(b1, b2, 1, 2, nb)
            rec["wire_bytes_per_dev"] = extrapolate(w1, w2, 1, 2, nb)
            rec["operand_bytes_per_dev"] = extrapolate(o1, o2, 1, 2, nb)
            rec["rs_wire_bytes_per_dev"] = extrapolate(r1, r2, 1, 2, nb)
            rec["per_block"] = {
                "flops": f2 - f1, "bytes": b2 - b1, "wire_bytes": w2 - w1,
            }
        else:
            rec["flops_per_dev"] = flops1
            rec["bytes_per_dev"] = bytes1
            rec["wire_bytes_per_dev"] = coll1["wire_bytes"]
            rec["operand_bytes_per_dev"] = coll1["operand_bytes"]
            rec["rs_wire_bytes_per_dev"] = coll1.get(
                "rs_adjusted_wire_bytes", coll1["wire_bytes"])
        case = SHAPES[shape]
        cfg_eff = meta["cfg"]
        rec["model_flops"] = model_flops(
            cfg_eff, case.kind, case.global_batch, case.seq_len
        )
        rec["params"] = count_params(cfg_eff)
        rec["status"] = "ok"
    except Exception as e:  # a failure here is a bug in the system
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        if verbose:
            print(f"[FAIL] {key}: {rec['error']}")
    json.dump(rec, open(path, "w"), indent=1)
    if verbose and rec["status"] == "ok":
        print(
            f"[OK] {key} compile={rec['compile_s_u1']:.1f}s "
            f"flops/dev={rec['flops_per_dev']:.3e} wire/dev={rec['wire_bytes_per_dev']:.3e}"
        )
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--strategy", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod-only", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--out", default=os.path.abspath(ARTIFACT_DIR))
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(arch_ids())
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = []
    if not args.multi_pod_only:
        meshes.append(False)
    if not args.single_pod_only:
        meshes.append(True)

    results = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                mesh_name = "pod2x16x16" if mp else "pod16x16"
                path = os.path.join(args.out, f"{arch}_{shape}_{mesh_name}.json")
                if args.skip_existing and os.path.exists(path):
                    rec = json.load(open(path))
                    if rec.get("status") in ("ok", "skipped"):
                        print(f"[CACHED] {arch} {shape} {mesh_name}: {rec['status']}")
                        results.append(rec)
                        continue
                results.append(
                    run_cell(arch, shape, multi_pod=mp, out_dir=args.out,
                             strategy=args.strategy)
                )
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"\n=== dry-run summary: {n_ok} ok, {n_skip} skipped, {n_err} errors ===")
    for r in results:
        if r["status"] == "error":
            print(f"  ERROR {r['arch']} {r['shape']} {r['mesh']}: {r['error']}")
    return 0 if n_err == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
