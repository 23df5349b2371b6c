"""Elastic meshes: fault-tolerant training as mesh re-derivation + reshard.

GSPMD's premise is that a partitioned program is just annotations over a
single-device program — so surviving a device failure is "re-derive the mesh,
re-solve the annotations, reshard the state", not "restart the job".  This
module is that recovery loop:

* :class:`FaultInjector` — deterministic fault hooks for tests and drills:
  one-shot fields (device loss / return, crash mid-save, straggler stall,
  numeric faults baked into the jitted step via ``TrainConfig.numeric_fault``)
  plus a serializable **schedule** of event dicts
  (``dump_schedule``/``load_schedule``) — the replayable campaign format the
  chaos harness (``launch/chaos.py``) composes.
* :func:`derive_mesh` — rebuild a ``(data, model)`` mesh over the current
  device subset; returns both the planner mesh (``repro.core.Mesh``) and the
  runtime ``jax.sharding.Mesh``.  Works in both directions: shrink after a
  loss, **regrow** after a device-return event.
* :class:`ElasticCoordinator` — a single-pass recovery state machine.  Any
  escalated fault (:class:`DeviceLossError`, :class:`DeviceReturnError`,
  ``core.plan.NumericsFault``) is **classified together with every coincident
  armed fault** (a numeric window overlapping the replay region, an
  imminent device event) and handled in one pass: adjust the device world,
  re-derive the mesh (shrink *or* grow), re-solve the sharding assignment
  warm-started from the previous assignment's JSON dump
  (``autoshard.remap_assignment`` on shrink, ``autoshard.expand_assignment``
  on regrow — Automap-style, strictly fewer evals than cold), then exactly
  **one** ``checkpoint.restore_resharded`` from the last intact step onto the
  *new* mesh (corrupt newest steps fall back inside that same pass — no
  rewind-then-reshard double restore), swap the jitted step
  (``TrainLoop.swap_plan``), resume from the manifest's data cursor.  Fault
  and recovery provenance lands in the manifest ``extra`` and on the obs
  control lane (``combined_recovery`` / ``mesh_grow`` / ``restore`` /
  ``ckpt_fallback`` events).  If the warm re-solve fails feasibility, it
  degrades gracefully to a data-parallel-only assignment instead of aborting.

Exercised in tests/test_elastic.py (single device: recovery mechanics, warm
vs cold evals, DP degradation, combined-fault drills),
tests/multidev/test_elastic_multidev.py (8 fake devices: shrink→regrow with a
continuous loss curve, combined recovery in one restore pass) and
tests/test_chaos.py (seeded soak campaigns).
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core.compat import trace_for
from repro.core.plan import NumericsFault
from repro.core.sharding import Mesh

from ..obs import metrics as obs_metrics
from ..obs.trace import control_event
from ..train import checkpoint as ckpt_lib


class DeviceLossError(RuntimeError):
    """Raised (by the fault hook) when devices drop out of the world."""

    def __init__(self, step: int, lost: int = 1):
        self.step, self.lost = step, lost
        super().__init__(f"lost {lost} device(s) at step {step}")


class DeviceReturnError(RuntimeError):
    """Raised (by the fault hook) when devices rejoin the world — the regrow
    trigger.  An exception, like :class:`DeviceLossError`, because it travels
    the same channel: unwind the training loop so the coordinator can
    re-derive a larger mesh and reshard onto it."""

    def __init__(self, step: int, gained: int = 1):
        self.step, self.gained = step, gained
        super().__init__(f"regained {gained} device(s) at step {step}")


# Schedule-event kinds a FaultInjector understands.  Mechanical events fire
# from the host hook; numeric events are baked into the jitted step
# (numeric_spec) because the guard sentinels must catch them in-program.
SCHEDULE_KINDS = ("device_loss", "device_return", "nan_burst", "grad_spike",
                  "straggler", "crash_save", "manifest_corrupt")
_NUMERIC_KINDS = ("nan_burst", "grad_spike")


@dataclasses.dataclass
class FaultInjector:
    """Deterministic fault injection for the elastic recovery loop.

    Each fault fires once.  ``hook`` is installed as ``TrainLoop``'s
    ``"fault"`` hook (called inside the measured step window);
    ``arm_save_fault`` plumbs the crash-mid-save into
    ``checkpoint.set_save_fault``.

    Beyond the legacy one-shot fields, ``schedule`` holds a list of event
    dicts (``{"kind": ..., "step": ..., **params}``, kinds in
    :data:`SCHEDULE_KINDS`) that round-trips through JSON
    (:meth:`dump_schedule` / :meth:`load_schedule`) — a failing chaos soak is
    replayable from its campaign artifact alone.  Every schedule event that
    fires emits a ``chaos_event`` control instant, so the exported trace
    distinguishes *injections* from the recovery *reactions* they cause.
    """

    device_loss_at: int = -1   # step at which devices drop
    lose: int = 1              # how many
    device_return_at: int = -1  # step at which devices rejoin (regrow)
    gain: int = 1               # how many return
    straggler_at: int = -1     # step to stall
    stall_s: float = 0.0       # injected stall duration
    crash_save_at_leaf: int = -1  # raise mid-save after writing k leaves
    nan_at_step: int = -1        # numeric: NaN-poison grads+loss at this step
    grad_spike_at_step: int = -1  # numeric: spike grads at this step
    spike_factor: float = 1e12
    numeric_steps: int = 1       # numeric fault window (consecutive steps)
    schedule: List[Dict] = dataclasses.field(default_factory=list)
    ckpt_dir: Optional[str] = None  # manifest_corrupt events need the dir
    fired: set = dataclasses.field(default_factory=set)

    def __post_init__(self):
        for ev in self.schedule:
            if ev.get("kind") not in SCHEDULE_KINDS:
                raise ValueError(f"unknown schedule event kind: {ev!r}")
            if "step" not in ev:
                raise ValueError(f"schedule event missing step: {ev!r}")

    # -- JSON round trip (replayable campaigns) -----------------------------
    def dump_schedule(self, path: Optional[str] = None) -> Dict:
        doc = {"version": 1, "events": [dict(e) for e in self.schedule]}
        if path:
            with open(path, "w") as f:
                json.dump(doc, f, indent=1)
        return doc

    @classmethod
    def load_schedule(cls, src) -> "FaultInjector":
        """Build an injector from a :meth:`dump_schedule` doc, a bare event
        list, or a path to the JSON artifact."""
        if isinstance(src, str):
            with open(src) as f:
                src = json.load(f)
        events = src["events"] if isinstance(src, dict) else src
        return cls(schedule=[dict(e) for e in events])

    # -- host-hook faults ----------------------------------------------------
    def hook(self, step: int) -> None:
        if step == self.straggler_at and "straggler" not in self.fired:
            self.fired.add("straggler")
            time.sleep(self.stall_s)
        if step == self.device_loss_at and "device_loss" not in self.fired:
            self.fired.add("device_loss")
            raise DeviceLossError(step, self.lose)
        if step == self.device_return_at and "device_return" not in self.fired:
            self.fired.add("device_return")
            raise DeviceReturnError(step, self.gain)
        for i, ev in enumerate(self.schedule):
            tag = f"sched:{i}"
            kind = ev["kind"]
            if tag in self.fired or kind in _NUMERIC_KINDS:
                continue  # numeric events are consumed via numeric_spec/ack
            if step < ev["step"]:
                continue
            self.fired.add(tag)
            control_event("chaos_event", kind=kind, step=step,
                          sched_step=ev["step"])
            if kind == "device_loss":
                raise DeviceLossError(step, ev.get("lose", 1))
            if kind == "device_return":
                raise DeviceReturnError(step, ev.get("gain", 1))
            if kind == "straggler":
                time.sleep(ev.get("stall_s", 0.2))
            elif kind == "crash_save":
                self._arm_sched_save_fault(ev)
            elif kind == "manifest_corrupt":
                ev["corrupted_step"] = self._corrupt_latest_manifest()

    def arm_save_fault(self) -> None:
        if self.crash_save_at_leaf < 0:
            return

        def fault(i: int, key: str) -> None:
            if i >= self.crash_save_at_leaf and "crash_save" not in self.fired:
                self.fired.add("crash_save")
                raise OSError(
                    f"injected crash mid-save (leaf {i}: {key})")

        ckpt_lib.set_save_fault(fault)

    def _arm_sched_save_fault(self, ev: Dict) -> None:
        at_leaf = ev.get("at_leaf", 0)
        once = {"done": False}

        def fault(i: int, key: str) -> None:
            if i >= at_leaf and not once["done"]:
                once["done"] = True
                raise OSError(
                    f"injected crash mid-save (leaf {i}: {key})")

        ckpt_lib.set_save_fault(fault)

    def _corrupt_latest_manifest(self) -> Optional[int]:
        """Flip a byte in the newest committed manifest (deterministic: the
        middle byte) — the self-checksum catches it on the next restore, which
        then falls back to the previous intact step in the same pass."""
        if not self.ckpt_dir:
            return None
        last = ckpt_lib.latest_step(self.ckpt_dir)
        if last is None:
            return None
        path = os.path.join(self.ckpt_dir, f"step_{last:08d}", "manifest.json")
        with open(path, "r+b") as f:
            data = bytearray(f.read())
            data[len(data) // 2] ^= 0xFF
            f.seek(0)
            f.write(bytes(data))
        return last

    def disarm(self) -> None:
        ckpt_lib.set_save_fault(None)

    # -- numeric faults (in-jit, via TrainConfig.numeric_fault) --------------
    def numeric_spec(self):
        """The :class:`repro.train.loop.NumericFaultSpec` for the armed
        numeric modes, or None when no numeric fault is pending.  Numeric
        faults are baked into the jitted step (static step window), not fired
        from the host hook — they must poison tensors *inside* the program
        where the guard sentinels watch.  Legacy one-shot fields win; else
        the earliest un-acked numeric schedule event is armed (one window per
        plan generation — the next event arms at the next plan rebuild)."""
        from ..train.loop import NumericFaultSpec

        if self.nan_at_step >= 0 or self.grad_spike_at_step >= 0:
            return NumericFaultSpec(
                nan_at_step=self.nan_at_step,
                grad_spike_at_step=self.grad_spike_at_step,
                spike_factor=self.spike_factor,
                steps=self.numeric_steps,
            )
        pend = [(i, ev) for i, ev in enumerate(self.schedule)
                if ev["kind"] in _NUMERIC_KINDS
                and f"sched:{i}" not in self.fired]
        if not pend:
            return None
        i, ev = min(pend, key=lambda t: t[1]["step"])
        if ev["kind"] == "nan_burst":
            return NumericFaultSpec(nan_at_step=ev["step"],
                                    steps=ev.get("steps", 1))
        return NumericFaultSpec(grad_spike_at_step=ev["step"],
                                spike_factor=ev.get("factor", 1e12),
                                steps=ev.get("steps", 1))

    def ack_numeric(self, upto_step: int) -> None:
        """Consume every armed numeric fault whose window opened at or before
        ``upto_step`` (legacy fields and schedule events): after a recovery
        restores behind such a window, replaying it must not re-poison."""
        self.nan_at_step = -1
        self.grad_spike_at_step = -1
        for i, ev in enumerate(self.schedule):
            tag = f"sched:{i}"
            if (ev["kind"] in _NUMERIC_KINDS and tag not in self.fired
                    and ev["step"] <= upto_step):
                self.fired.add(tag)
                control_event("chaos_event", kind=ev["kind"],
                              step=ev["step"], sched_step=ev["step"])

    def numeric_coincident(self, step: int, window: int = 1,
                           floor: Optional[int] = None) -> bool:
        """True when an armed numeric window could poison the recovery: it
        opens at or before ``step + window`` and has not fully elapsed before
        ``floor`` (the restore point — a window entirely behind the last
        intact checkpoint cannot be replayed into)."""
        spec = self.numeric_spec()
        if spec is None:
            return False
        at = spec.nan_at_step if spec.nan_at_step >= 0 else spec.grad_spike_at_step
        if at > step + window:
            return False
        if floor is not None and at + spec.steps <= floor:
            return False
        return True

    def take_device_event(self, step: int, window: int = 1):
        """Consume an armed-but-unfired device loss/return whose step falls
        at or before ``step + window`` — the coincident-fault fold: when a
        numerics rewind is about to restore and a device event is imminent,
        handling both in one pass avoids a second restore moments later.
        Returns ``("device_loss", lost)`` / ``("device_return", gained)`` or
        ``None``."""
        if (self.device_loss_at >= 0 and "device_loss" not in self.fired
                and self.device_loss_at <= step + window):
            self.fired.add("device_loss")
            return ("device_loss", self.lose)
        if (self.device_return_at >= 0 and "device_return" not in self.fired
                and self.device_return_at <= step + window):
            self.fired.add("device_return")
            return ("device_return", self.gain)
        for i, ev in enumerate(self.schedule):
            tag = f"sched:{i}"
            if tag in self.fired:
                continue
            if (ev["kind"] in ("device_loss", "device_return")
                    and ev["step"] <= step + window):
                self.fired.add(tag)
                control_event("chaos_event", kind=ev["kind"], step=step,
                              sched_step=ev["step"])
                if ev["kind"] == "device_loss":
                    return ("device_loss", ev.get("lose", 1))
                return ("device_return", ev.get("gain", 1))
        return None


def derive_mesh(n_devices: Optional[int] = None,
                model_parallel: Optional[int] = None,
                devices: Optional[Sequence] = None,
                ) -> Tuple[Mesh, "jax.sharding.Mesh"]:
    """Largest ``(data, model)`` mesh over the surviving devices.

    Returns ``(planner_mesh, jax_mesh)``.  ``devices`` pins an explicit
    subset (the post-loss world); otherwise the first ``n_devices`` of
    ``jax.devices()`` are used.  ``model_parallel`` is clamped to the largest
    divisor of the world size ≤ the requested value, so a mesh that lost a
    node still derives.
    """
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            devices = devices[:n_devices]
    devices = list(devices)
    n = len(devices)
    mp = model_parallel or min(16, n)
    mp = min(mp, n)
    while n % mp:
        mp -= 1
    shape = (n // mp, mp)
    mesh = Mesh.create(shape, ("data", "model"))
    jmesh = jax.sharding.Mesh(
        np.asarray(devices).reshape(shape), ("data", "model"))
    return mesh, jmesh


def specs_by_key(spec_state) -> Dict[str, Any]:
    """Flatten a spec tree to the checkpoint's ``/``-joined leaf keys."""
    flat, _ = ckpt_lib._flatten_with_paths(spec_state)
    return dict(flat)


def sharding_problem(cfg, st, mesh: Mesh, local_batch: int, seq_len: int):
    """Trace ``cfg``'s loss annotation-free and build the Table-1 baseline
    assignment on ``mesh`` (mirrors ``autoshard.registry_problem`` for a
    config that need not live in the registry).  Pure — needs no devices, so
    warm-vs-cold solve comparisons run on any mesh shape."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro import autoshard
    from ..models import api
    from ..models.layers import tree_shapes, tree_specs

    tree = api.param_tree(cfg, st)
    shapes = tree_shapes(tree)
    batch_in = {
        "tokens": jax.ShapeDtypeStruct((local_batch, seq_len), jnp.int32),
        "labels": jax.ShapeDtypeStruct((local_batch, seq_len), jnp.int32),
    }
    closed = trace_for(
        mesh, lambda p, b: api.loss_fn(cfg, st, p, b), shapes, batch_in)
    spec_leaves = jax.tree_util.tree_leaves(
        (tree_specs(tree), {k: P(("data",)) for k in batch_in}),
        is_leaf=lambda x: x is None or isinstance(x, P),
    )
    baseline = [
        autoshard.sharding_from_spec(mesh, s, tuple(v.aval.shape))
        for s, v in zip(spec_leaves, closed.jaxpr.invars)
    ]
    return closed, baseline


class ElasticCoordinator:
    """Drive a :class:`~repro.train.loop.TrainLoop` through injected faults.

    One instance owns the device world, the current mesh pair, the last
    autoshard assignment (dumped to JSON next to the checkpoints), and the
    recovery log.  ``run()`` returns ``(state, losses)`` exactly like
    ``TrainLoop.run`` — with ``losses`` continuous across recoveries.
    """

    def __init__(self, cfg, st, opt, tc, pipeline, *,
                 n_devices: Optional[int] = None,
                 model_parallel: Optional[int] = None,
                 autoshard_config=None,
                 injector: Optional[FaultInjector] = None,
                 hooks: Optional[Dict[str, Callable]] = None,
                 max_recoveries: int = 3,
                 coincidence_window: int = 1,
                 sharded_restore_io: bool = True):
        from repro import autoshard
        from ..train.loop import TrainLoop

        self.cfg, self.st, self.opt, self.tc = cfg, st, opt, tc
        self.pipeline = pipeline
        self.model_parallel = model_parallel
        # `world` is the full pool devices can rejoin from (regrow ceiling);
        # `devices` is the live subset the current mesh is derived over
        self.world = list(jax.devices())[:n_devices]
        self.devices = list(self.world)
        self.mesh, self.jmesh = derive_mesh(
            devices=self.devices, model_parallel=model_parallel)
        self.ashard_config = autoshard_config or autoshard.AutoshardConfig(
            top_n=4, sa_steps=4)
        self.injector = injector
        self.max_recoveries = max_recoveries
        self.coincidence_window = coincidence_window
        self.sharded_restore_io = sharded_restore_io
        self.recoveries: List[Dict] = []
        # keyed by step: a post-recovery replay of an uncheckpointed step
        # overwrites rather than duplicates, so the returned curve is one
        # loss per step — continuous across recoveries
        self.losses: Dict[int, float] = {}
        self.assignment = None   # last AutoshardResult
        self.degraded = False    # True after a DP-only fallback
        self.dump_path = (os.path.join(tc.ckpt_dir, "assignment.json")
                          if tc.ckpt_dir else None)
        loop_hooks = dict(hooks or {})
        if injector is not None:
            loop_hooks["fault"] = injector.hook
            injector.arm_save_fault()
            if injector.ckpt_dir is None:
                injector.ckpt_dir = tc.ckpt_dir
            spec = injector.numeric_spec()
            if spec is not None:
                # numeric faults live inside the jitted step; arm before the
                # TrainLoop builds/jits its step function
                tc.numeric_fault = spec
        loop_hooks["metrics"] = lambda step, loss: self.losses.__setitem__(
            step, loss)
        loop_hooks.setdefault("ckpt_extra", self._manifest_extra)
        self.loop = TrainLoop(cfg, st, opt, tc, pipeline, hooks=loop_hooks)

    def _manifest_extra(self) -> Dict[str, Any]:
        """Coordinator state merged into every manifest ``extra``: the
        assignment dump path, the live mesh, and — after any recovery — the
        fault/recovery provenance (what was classified, what was restored
        from), so a post-mortem can read the history off the checkpoints."""
        extra: Dict[str, Any] = {
            "mesh": {"shape": list(self.mesh.shape),
                     "axes": list(self.mesh.axis_names)}}
        if self.dump_path:
            extra["assignment_path"] = self.dump_path
        if self.recoveries:
            last = self.recoveries[-1]
            extra["recovery"] = {
                "count": len(self.recoveries),
                "last": {k: last[k] for k in
                         ("classes", "step", "restored_from", "mesh",
                          "fell_back_from", "crash_save") if k in last},
            }
        return extra

    # -- sharding re-solve ---------------------------------------------------
    def _problem(self, mesh: Mesh):
        dc = self.pipeline.cfg
        return sharding_problem(self.cfg, self.st, mesh,
                                self.pipeline.local_batch, dc.seq_len)

    def solve_assignment(self, warm=None, warm_mesh=None):
        """(Re-)solve the sharding assignment on the current mesh.  ``warm``
        is a prior-mesh assignment (e.g. ``autoshard.load(dump)[1]``) with
        ``warm_mesh`` the mesh it was solved on: when the current mesh is
        *larger* (regrow), the warm point is **lifted** via
        ``expand_assignment`` (unused mesh axes re-proposed onto the largest
        dividing dims) instead of merely projected — a shrunk or DP-degraded
        assignment regains model parallelism as the warm start.  When the
        warm/cold solve is infeasible under the budget, degrade to the
        data-parallel-only restriction of the baseline."""
        from repro import autoshard

        closed, baseline = self._problem(self.mesh)
        shapes = [tuple(v.aval.shape) for v in closed.jaxpr.invars]
        ws = None
        if warm is not None:
            grew = (warm_mesh is not None
                    and int(np.prod(self.mesh.shape))
                    > int(np.prod(warm_mesh.shape)))
            project = (autoshard.expand_assignment if grew
                       else autoshard.remap_assignment)
            ws = project(warm, self.mesh, shapes)
        res = autoshard.solve_problem(
            closed, self.mesh, self.ashard_config,
            baseline=baseline, warm_start=ws)
        self.degraded = False
        if not res.evaluation.feasible:
            dp = autoshard.restrict_assignment(baseline, self.mesh, shapes)
            res = autoshard.solve_problem(
                closed, self.mesh,
                dataclasses.replace(self.ashard_config, top_n=0, sa_steps=0),
                baseline=dp, warm_start=dp)
            res.assignment = dp
            self.degraded = True
        self.assignment = res
        if self.dump_path:
            os.makedirs(os.path.dirname(self.dump_path), exist_ok=True)
            res.dump(self.dump_path)
        return res

    # -- recovery ------------------------------------------------------------
    def _classify(self, err) -> Dict[str, Any]:
        """Fault-class set for one escalated fault plus everything armed and
        coincident with it.  Keys: ``device_loss`` (lost count),
        ``device_return`` (gained count), ``numerics`` (the NumericsFault or
        None when folded in pre-escalation).  Coincidence is deliberate, not
        heuristic: an armed numeric window that the post-restore replay would
        re-enter, or a device event due within ``coincidence_window`` steps
        of the fault — both *will* trigger a second recovery pass moments
        after a naive single-fault handler resumes, so they are folded into
        this pass instead."""
        classes: Dict[str, Any] = {}
        if isinstance(err, DeviceLossError):
            classes["device_loss"] = err.lost
        elif isinstance(err, DeviceReturnError):
            classes["device_return"] = err.gained
        elif isinstance(err, NumericsFault):
            classes["numerics"] = err
        step = getattr(err, "step", 0)
        if self.injector is not None:
            floor = (ckpt_lib.latest_step(self.tc.ckpt_dir)
                     if self.tc.ckpt_dir else None)
            if ("numerics" not in classes
                    and self.injector.numeric_coincident(
                        step, self.coincidence_window, floor=floor)):
                classes["numerics"] = None
            if not ({"device_loss", "device_return"} & set(classes)):
                taken = self.injector.take_device_event(
                    step, self.coincidence_window)
                if taken is not None:
                    classes[taken[0]] = taken[1]
        return classes

    def _recover_combined(self, err) -> Tuple[Any, Optional[int]]:
        """One recovery pass for every coincident fault class: adjust the
        device world (shrink *or* regrow), re-derive the mesh, warm re-solve
        (``remap_assignment`` on shrink, ``expand_assignment`` on regrow),
        then exactly **one** ``restore_resharded`` from the last intact step
        onto the *new* mesh — a corrupt newest checkpoint falls back inside
        that same call (``ckpt_fallback``), never a second pass.  Disarms any
        consumed numeric injection, swaps the jitted step, and returns
        ``(state, start_step)`` (``(None, None)`` = no checkpoint: reinit)."""
        from repro import autoshard
        from ..train.loop import (
            init_state, make_train_step, state_partition_specs,
        )

        t0 = time.perf_counter()
        classes = self._classify(err)
        step = getattr(err, "step", None)
        # fault-specific instants keep the single-fault vocabulary...
        if isinstance(err, DeviceLossError):
            control_event("device_loss", step=err.step, lost=err.lost)
            obs_metrics.inc("elastic.device_losses")
        elif isinstance(err, DeviceReturnError):
            control_event("device_return", step=err.step, gained=err.gained)
            obs_metrics.inc("elastic.device_returns")
        if isinstance(err, NumericsFault):
            control_event("rewind", step=err.step,
                          consecutive=err.consecutive)
            obs_metrics.inc("elastic.rewinds")
        # ...and a combined_recovery instant marks the single-pass fold
        if len(classes) > 1:
            control_event("combined_recovery", step=step,
                          classes=sorted(classes))
            obs_metrics.inc("elastic.combined_recoveries")
        event: Dict[str, Any] = {"classes": sorted(classes), "step": step}
        old_shape = self.mesh.shape
        mesh_changed = False
        if "device_loss" in classes:
            survivors = max(len(self.devices) - classes["device_loss"], 1)
            self.devices = self.devices[:survivors]
            event["lost"] = classes["device_loss"]
        if "device_return" in classes:
            back = min(len(self.devices) + classes["device_return"],
                       len(self.world))
            self.devices = list(self.world[:back])
            event["gained"] = classes["device_return"]
        if {"device_loss", "device_return"} & set(classes):
            self.mesh, self.jmesh = derive_mesh(
                devices=self.devices, model_parallel=self.model_parallel)
            mesh_changed = True
            control_event(
                "mesh_grow" if "device_return" in classes else "mesh_shrink",
                mesh_from=list(old_shape), mesh_to=list(self.mesh.shape),
                step=step)
        event["mesh"] = {"from": list(old_shape),
                         "to": list(self.mesh.shape)}
        if isinstance(err, NumericsFault):
            event["numerics"] = True
            event["consecutive"] = err.consecutive
            event["faults"] = [dict(f) for f in err.faults[:8]]
        # re-solve only when the mesh changed; a pure rewind keeps the plan
        if mesh_changed:
            warm, warm_mesh = None, None
            if self.dump_path and os.path.exists(self.dump_path):
                warm_mesh, warm = autoshard.load(self.dump_path)
            res = self.solve_assignment(warm=warm, warm_mesh=warm_mesh)
            event.update({"warm_started": res.warm_started,
                          "degraded": self.degraded, "evals": res.evals})
        # the single restore pass (fallback to older intact steps inside)
        state, start = None, None
        if self.tc.ckpt_dir and ckpt_lib.latest_step(self.tc.ckpt_dir) is not None:
            target = init_state(self.cfg, self.st, self.opt, self.tc,
                                self.loop.rng)
            specs = specs_by_key(
                state_partition_specs(self.cfg, self.st, self.opt, self.tc))
            state, manifest, report = ckpt_lib.restore_resharded(
                self.tc.ckpt_dir, target, self.mesh, self.jmesh,
                target_specs=specs, sharded_io=self.sharded_restore_io)
            start = int(manifest.get("extra", {}).get(
                "data_cursor", manifest["step"]))
            if report.get("fell_back_from"):
                classes["corrupt_checkpoint"] = report["fell_back_from"]
                event["classes"] = sorted(classes)
                event["fell_back_from"] = report["fell_back_from"]
                control_event("ckpt_fallback", step=step,
                              skipped=report["fell_back_from"],
                              restored=report["step"])
                obs_metrics.inc("elastic.ckpt_fallbacks")
            control_event("restore", step=report["step"],
                          leaves=report["leaves"],
                          resharded=report["resharded_leaves"],
                          sharded_io=bool(report.get("sharded_io")))
            obs_metrics.inc("elastic.restores")
            event["restored_from"] = int(report["step"])
            event["reshard"] = {
                k: report[k] for k in
                ("leaves", "resharded_leaves", "wire_bytes", "launches",
                 "reshard_s", "step")
            }
            if report.get("sharded_io"):
                event["io"] = dict(report.get("io", {}))
            if "numerics" in classes:
                event["rewound_to"] = int(report["step"])
        if "numerics" in classes:
            # disarm the consumed injection (replaying the same window would
            # re-fault forever) and arm the next pending one, if any
            if self.injector is not None:
                self.injector.ack_numeric(
                    step if step is not None else 1 << 30)
                self.tc.numeric_fault = self.injector.numeric_spec()
            else:
                self.tc.numeric_fault = None
            self.loop.guard_counters["rewinds"] += 1
            obs_metrics.inc("train.guard.rewinds")
            self.loop._consecutive_faults = 0
        self.loop.swap_plan(
            make_train_step(self.cfg, self.st, self.opt, self.tc))
        reason = ("rewind" if set(classes) == {"numerics"}
                  else "+".join(sorted(classes)))
        control_event("plan_swap", reason=reason, step=step,
                      mesh=list(self.mesh.shape),
                      rewound_to=event.get("rewound_to"))
        event["duration_ms"] = (time.perf_counter() - t0) * 1e3
        obs_metrics.observe("elastic.recovery_ms", event["duration_ms"])
        self.recoveries.append(event)
        return state, start

    def run(self):
        """Train to completion, recovering in-process from injected faults."""
        from repro.core.compat import set_mesh

        if self.assignment is None:
            self.solve_assignment()
        state, start = None, None
        attempts = 0
        while True:
            try:
                with set_mesh(self.jmesh):
                    final, _ = self.loop.run(
                        initial_state=state, start_step=start)
                return final, [self.losses[s] for s in sorted(self.losses)]
            except (DeviceLossError, DeviceReturnError, NumericsFault) as e:
                # one classified pass handles the fault plus everything
                # coincident with it: shrink/regrow + rewind + corrupt-step
                # fallback collapse into a single restore
                attempts += 1
                if attempts > self.max_recoveries:
                    raise
                state, start = self._recover_combined(e)
            except OSError:
                # crash mid-save: the atomic tmp-rename never committed, so
                # the last intact step is still the restore point; disarm the
                # injector and resume from it on the same mesh
                attempts += 1
                if attempts > self.max_recoveries:
                    raise
                if self.injector is not None:
                    self.injector.disarm()
                state, start = None, None
                control_event("crash_save", resumed=True)
                obs_metrics.inc("elastic.crash_saves")
                self.recoveries.append(
                    {"crash_save": True, "classes": ["crash_save"]})
