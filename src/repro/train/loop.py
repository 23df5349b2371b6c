"""Fault-tolerant training loop: step builder, grad accumulation, gradient
compression, checkpoint/restart, straggler watchdog, numerics guards.

``make_train_step`` builds the jittable step:
  loss (bf16 compute) -> grad -> [bf16 reduce + fp32 error-feedback] ->
  optimizer update (sharded state).
Gradient accumulation scans over microbatches (constant memory); remat policy is
the model config's.  ``TrainLoop.run`` checkpoints every N steps, auto-restores on
restart (deterministic data cursor), records per-step wall times and flags
straggler steps (> k × median) through a hook — on a real fleet the hook reports
to the coordinator; here it feeds the test harness and logs.

**Numerics guards** (``TrainConfig.guard``, a
:class:`repro.core.plan.GuardConfig`): the step computes a fused
non-finite/abs-max sentinel over the guarded tensors (loss, grads, optionally
optimizer moments) *inside* the jitted step, plus a scalar fault flag.  On a
fault the update is **skipped in-jit** — a ``where``-select keeps the old
params/opt-state/error-feedback while the step counter still advances, so the
data cursor moves past the poisoned batch and the optimizer never sees the
bad update.  The host side of the loop decodes per-leaf provenance
(:func:`repro.core.plan.guard_faults`), counts consecutive faults, and raises
:class:`repro.core.plan.NumericsFault` once ``guard.rewind_after`` is reached
— the signal for a coordinator to rewind to the last intact checkpoint.
Fault/skip/rewind counters ride in the checkpoint manifest ``extra`` so
recovery history survives restarts.

``TrainConfig.numeric_fault`` (a :class:`NumericFaultSpec`) injects numeric
faults *inside* the jitted step (NaN-poisoned or spiked gradients over a
static step window) — the guard-drill counterpart of
``launch.elastic.FaultInjector``'s mechanical faults.
"""
from __future__ import annotations

import dataclasses
import os
import time
from functools import partial
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig, Strategy
from ..models import api
from ..models.layers import tree_init, tree_shapes, tree_specs
from ..obs import metrics as obs_metrics
from ..obs.trace import control_event
from . import checkpoint as ckpt_lib
from .optimizer import Optimizer, opt_state_specs


@dataclasses.dataclass(frozen=True)
class NumericFaultSpec:
    """Deterministic numeric-fault injection, baked into the jitted step.

    The window is a *traced* comparison on the state's step counter (static
    constants, so the jitted program is reusable): for ``steps`` consecutive
    steps starting at the armed step, gradients (and the loss, for the NaN
    mode) are poisoned after differentiation and before the guard sentinel —
    exactly where a real numerics blowup would surface."""

    nan_at_step: int = -1         # poison grads+loss with NaN at this step
    grad_spike_at_step: int = -1  # multiply grads by spike_factor at this step
    spike_factor: float = 1e12
    steps: int = 1                # window length (consecutive faulted steps)


@dataclasses.dataclass
class TrainConfig:
    steps: int = 100
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    keep_ckpts: int = 3
    grad_accum: int = 1
    compress_grads: bool = False  # bf16 gradient exchange + fp32 error feedback
    log_every: int = 10
    straggler_factor: float = 3.0
    fail_at_step: int = -1  # fault-injection for tests
    guard: Optional[Any] = None  # core.plan.GuardConfig: numerics sentinels
    numeric_fault: Optional[NumericFaultSpec] = None  # guard-drill injection


def make_train_step(cfg: ModelConfig, st: Strategy, opt: Optimizer, tc: TrainConfig):
    """Returns step(state, batch) -> (state, metrics). state = (params, opt_state,
    step, [ef]).  Donation-friendly: pure function of state."""

    def loss_of(params, batch):
        return api.loss_fn(cfg, st, params, batch)

    def grads_of(params, batch):
        if tc.grad_accum <= 1:
            return jax.value_and_grad(loss_of)(params, batch)
        # microbatch scan: split leading batch dim
        def micro(carry, mb):
            loss_sum, g_sum = carry
            l, g = jax.value_and_grad(loss_of)(params, mb)
            g_sum = jax.tree_util.tree_map(jnp.add, g_sum, g)
            return (loss_sum + l, g_sum), None

        mbs = jax.tree_util.tree_map(
            lambda x: x.reshape((tc.grad_accum, x.shape[0] // tc.grad_accum) + x.shape[1:]),
            batch,
        )
        zero = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )
        from ..models.layers import scan_or_loop

        (loss, grads), _ = scan_or_loop(
            micro, (jnp.zeros((), jnp.float32), zero), mbs, cfg
        )
        inv = 1.0 / tc.grad_accum
        return loss * inv, jax.tree_util.tree_map(lambda g: g * inv, grads)

    def _fault_window(step, at, width):
        return (step >= at) & (step < at + width)

    def step_fn(state, batch):
        params, opt_state, step = state["params"], state["opt"], state["step"]
        loss, grads = grads_of(params, batch)
        nf = tc.numeric_fault
        if nf is not None and nf.nan_at_step >= 0:
            poison = jnp.where(_fault_window(step, nf.nan_at_step, nf.steps),
                               jnp.nan, 1.0).astype(jnp.float32)
            loss = loss * poison
            grads = jax.tree_util.tree_map(lambda g: g * poison, grads)
        if nf is not None and nf.grad_spike_at_step >= 0:
            spike = jnp.where(
                _fault_window(step, nf.grad_spike_at_step, nf.steps),
                jnp.float32(nf.spike_factor), 1.0)
            grads = jax.tree_util.tree_map(lambda g: g * spike, grads)
        # the update and the step's norm, clip and guard math: one scope
        # in the program's op names, so a device trace can time it
        with jax.named_scope("optimizer"):
            if tc.compress_grads:
                # half-precision gradient exchange with error feedback: quantize to
                # bf16 (halves ReduceScatter bytes), remember the residual in fp32.
                ef = state["ef"]
                grads = jax.tree_util.tree_map(jnp.add, grads, ef)
                q = jax.tree_util.tree_map(lambda g: g.astype(jnp.bfloat16), grads)
                new_ef = jax.tree_util.tree_map(
                    lambda g, qq: g - qq.astype(jnp.float32), grads, q
                )
                grads = jax.tree_util.tree_map(lambda qq: qq.astype(jnp.float32), q)
            new_params, new_opt = opt.update(grads, opt_state, params, step)
            gnorm = jnp.sqrt(
                sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree_util.tree_leaves(grads))
            )
            new_state = {"params": new_params, "opt": new_opt, "step": step + 1}
            if tc.compress_grads:
                new_state["ef"] = new_ef
            metrics = {"loss": loss, "grad_norm": gnorm}
            gc = tc.guard
            if gc is not None:
                stats = [_guard_stat(x) for _, x in
                         _guard_tensors(gc, loss, grads, new_opt)]
                gvec = jnp.stack(stats)  # (k, 2): [nonfinite_count, absmax]
                fault = jnp.any(gvec[:, 0] > 0) | jnp.any(~jnp.isfinite(gvec[:, 1]))
                if np.isfinite(gc.max_abs):
                    fault = fault | jnp.any(gvec[:, 1] > gc.max_abs)
                if np.isfinite(gc.max_grad_norm):
                    fault = fault | ~jnp.isfinite(gnorm) | (gnorm > gc.max_grad_norm)
                # skip-in-jit: keep old params/opt/ef on fault so the poisoned
                # update never lands; the step counter still advances (the data
                # cursor moves past the bad batch)
                keep = lambda old, new: jnp.where(fault, old, new)
                new_state["params"] = jax.tree_util.tree_map(
                    keep, params, new_state["params"])
                new_state["opt"] = jax.tree_util.tree_map(
                    keep, opt_state, new_state["opt"])
                if tc.compress_grads:
                    new_state["ef"] = jax.tree_util.tree_map(
                        keep, state["ef"], new_state["ef"])
                metrics["guard"] = gvec.reshape(-1)
                metrics["fault"] = fault
        return new_state, metrics

    return step_fn


def _guard_stat(x):
    """Fused sentinel for one tensor: ``[non-finite count, abs-max]`` fp32."""
    x = x.astype(jnp.float32)
    nonfin = jnp.sum(~jnp.isfinite(x)).astype(jnp.float32)
    amax = jnp.max(jnp.abs(x)) if x.size else jnp.float32(0.0)
    return jnp.stack([nonfin, amax])


def _guard_tensors(gc, loss, grads, opt_state):
    """``(name, tensor)`` selection for a GuardConfig — one fixed order shared
    by the traced step and the host-side decoder (`guard_leaf_names`)."""
    out = []
    if gc.loss:
        out.append(("loss", loss))
    if gc.grads:
        out.extend(("grads/" + k, g)
                   for k, g in ckpt_lib._flatten_with_paths(grads)[0])
    if gc.moments:
        out.extend(("opt/" + k, m)
                   for k, m in ckpt_lib._flatten_with_paths(opt_state)[0])
    return out


def guard_leaf_names(gc, state) -> tuple:
    """Leaf provenance for the step's guard vector, decodable on the host
    with :func:`repro.core.plan.guard_faults` — same order as the traced
    selection in ``make_train_step``."""
    names = []
    if gc.loss:
        names.append("loss")
    if gc.grads:
        names.extend("grads/" + k
                     for k, _ in ckpt_lib._flatten_with_paths(state["params"])[0])
    if gc.moments:
        names.extend("opt/" + k
                     for k, _ in ckpt_lib._flatten_with_paths(state["opt"])[0])
    return tuple(names)


def init_state(cfg: ModelConfig, st: Strategy, opt: Optimizer, tc: TrainConfig, rng):
    tree = api.param_tree(cfg, st)
    params = tree_init(tree, rng)
    state = {"params": params, "opt": opt.init(params), "step": jnp.zeros((), jnp.int32)}
    if tc.compress_grads:
        state["ef"] = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )
    return state


def state_partition_specs(cfg, st, opt, tc) -> Dict[str, Any]:
    """PartitionSpec tree shaped like the train-loop state (params, opt
    state sharded like params, replicated step) — the strategy's layout of
    the state, and the restore target specs for a cross-topology checkpoint
    load.  Call under the mesh the specs are for: weight specs drop the axes
    it lacks and the ones that do not divide a dim."""
    from jax.sharding import PartitionSpec as P

    tree = api.param_tree(cfg, st)
    pspecs = tree_specs(tree)
    ospecs = opt_state_specs(opt, pspecs, tree_shapes(tree))
    fill = lambda t: jax.tree_util.tree_map(
        lambda s: s if s is not None else P(),
        t, is_leaf=lambda x: x is None or isinstance(x, P))
    spec_state = {"params": fill(pspecs), "opt": fill(ospecs), "step": P()}
    if tc.compress_grads:
        spec_state["ef"] = fill(pspecs)
    return spec_state


def _ambient_mesh():
    """The ambient concrete jax mesh, or None outside any mesh context."""
    m = jax.sharding.get_mesh()
    return None if m.empty else m


class TrainLoop:
    """Drives training with checkpoint/restart and a straggler watchdog."""

    def __init__(self, cfg, st, opt, tc: TrainConfig, pipeline, rng=None,
                 step_fn=None, hooks=None):
        self.cfg, self.st, self.opt, self.tc = cfg, st, opt, tc
        self.pipeline = pipeline
        self.hooks = hooks or {}
        self.step_fn = jax.jit(step_fn or make_train_step(cfg, st, opt, tc),
                               donate_argnums=(0,))
        self.rng = rng if rng is not None else jax.random.PRNGKey(0)
        self.step_times = []
        # numerics-guard bookkeeping (populated when tc.guard is set);
        # counters ride in the manifest extra and survive restarts
        self.guard_counters = {"faults": 0, "skips": 0, "rewinds": 0}
        self.skipped_steps: list = []
        self.guard_leaves: Optional[tuple] = None
        self._consecutive_faults = 0

    def swap_plan(self, step_fn) -> None:
        """Replace the jitted step without restarting the process — the
        elastic-recovery path after a mesh change (new assignment → new
        partitioned step function)."""
        self.step_fn = jax.jit(step_fn, donate_argnums=(0,))
        self.step_times = []  # old timings are not comparable post-reshard

    def _ckpt_extra(self, step: int) -> Dict[str, Any]:
        """Manifest ``extra``: the data cursor (next batch index) is the
        authoritative resume point — restart replays nothing and skips
        nothing.  A ``ckpt_extra`` hook merges coordinator state (e.g. the
        autoshard assignment dump) into the same manifest."""
        extra = {"data_cursor": step + 1}
        if self.tc.guard is not None:
            extra["guard"] = dict(self.guard_counters)
        if "ckpt_extra" in self.hooks:
            extra.update(self.hooks["ckpt_extra"]() or {})
        return extra

    def _save(self, save_step: int, state, cursor_step: int,
              prune: bool = True) -> None:
        """One checkpoint save + retention pass, with a ``ckpt_save`` control
        instant so an exported trace shows the restore *points* alongside the
        faults and restores that use them (the chaos invariant "data cursor
        monotone across saves" is checked off these events)."""
        ckpt_lib.save(self.tc.ckpt_dir, save_step, state,
                      extra=self._ckpt_extra(cursor_step))
        control_event("ckpt_save", step=save_step,
                      data_cursor=cursor_step + 1)
        if prune:
            ckpt_lib.cleanup(self.tc.ckpt_dir, self.tc.keep_ckpts)

    def _restore_or_init(self):
        """Returns ``(state, start_step)``; start comes from the manifest's
        data cursor (not the state leaf), so the pipeline resumes exactly
        where the checkpoint left off."""
        state = init_state(self.cfg, self.st, self.opt, self.tc, self.rng)
        amesh = _ambient_mesh()
        if amesh is not None:
            # under a mesh, fresh arrays come out replicated on every device;
            # lay the state out by the strategy instead
            from jax.sharding import NamedSharding, PartitionSpec

            specs = state_partition_specs(self.cfg, self.st, self.opt, self.tc)
            state = jax.device_put(state, jax.tree_util.tree_map(
                lambda s: NamedSharding(amesh, s), specs,
                is_leaf=lambda x: isinstance(x, PartitionSpec)))
        start = 0
        if self.tc.ckpt_dir:
            last = ckpt_lib.latest_step(self.tc.ckpt_dir)
            if last is not None:
                # under an ambient mesh, land every leaf replicated on it so
                # the jitted step's constraints can reshard device-side (the
                # restarted-on-a-new-mesh path); otherwise plain device_put
                sharding_for = None
                if amesh is not None:
                    from jax.sharding import NamedSharding, PartitionSpec

                    sharding_for = (
                        lambda key: NamedSharding(amesh, PartitionSpec()))
                state, manifest = ckpt_lib.restore(
                    self.tc.ckpt_dir, state, last, sharding_for=sharding_for)
                start = int(manifest.get("extra", {}).get(
                    "data_cursor", manifest["step"]))
                saved = manifest.get("extra", {}).get("guard")
                if saved:
                    self.guard_counters.update(
                        {k: int(v) for k, v in saved.items()})
                if "log" in self.hooks:
                    self.hooks["log"](
                        f"restored checkpoint step={last} cursor={start}")
        return state, start

    def run(self, initial_state=None, start_step: Optional[int] = None):
        """Train until ``tc.steps``.  ``initial_state``/``start_step`` let a
        coordinator resume mid-process after an elastic reshard (skipping the
        checkpoint-restore path it already performed)."""
        if initial_state is not None:
            state = initial_state
            start = (start_step if start_step is not None
                     else int(jax.device_get(state["step"])))
        else:
            state, start = self._restore_or_init()
            if start_step is not None:
                start = start_step
        losses = []
        for step in range(start, self.tc.steps):
            # one profiler step per training step (a no-op until a profiler
            # session runs): its batch, dispatch, host sync and checkpoint
            with jax.profiler.StepTraceAnnotation("train", step_num=step):
                if step == self.tc.fail_at_step:
                    raise RuntimeError(f"injected failure at step {step}")
                batch = {
                    k: jnp.asarray(v) for k, v in self.pipeline.batch_at(step).items()
                }
                t0 = time.perf_counter()
                if "fault" in self.hooks:
                    # fault-injection point (launch/elastic.FaultInjector): sits
                    # after t0 so an injected straggler stall lands in the
                    # measured dt and trips the watchdog below
                    self.hooks["fault"](step)
                state, metrics = self.step_fn(state, batch)
                loss = float(jax.device_get(metrics["loss"]))
                dt = time.perf_counter() - t0
                obs_metrics.observe("train.step_ms", dt * 1e3)
                gc = self.tc.guard
                if gc is not None and bool(jax.device_get(metrics["fault"])):
                    # the jitted step already skipped the update in-device; the
                    # host side decodes provenance, records the skip, and
                    # escalates to a rewind after K consecutive faults
                    from ..core.plan import NumericsFault, guard_faults

                    if self.guard_leaves is None:
                        self.guard_leaves = guard_leaf_names(gc, state)
                    faults = guard_faults(
                        gc, np.asarray(jax.device_get(metrics["guard"])),
                        self.guard_leaves)
                    if not faults:  # norm-only trip (gnorm > max_grad_norm)
                        faults = ({"leaf": "grad_norm", "kind": "norm",
                                   "value": float(jax.device_get(
                                       metrics["grad_norm"]))},)
                    self.guard_counters["faults"] += 1
                    self._consecutive_faults += 1
                    obs_metrics.inc("train.guard.faults")
                    control_event(
                        "numerics_fault", step=step,
                        consecutive=self._consecutive_faults,
                        leaves=[f["leaf"] for f in faults[:4]])
                    if "numerics_fault" in self.hooks:
                        self.hooks["numerics_fault"](
                            step, faults, self._consecutive_faults)
                    if self._consecutive_faults >= gc.rewind_after:
                        raise NumericsFault(step, faults,
                                            self._consecutive_faults)
                    self.guard_counters["skips"] += 1
                    self.skipped_steps.append(step)
                    obs_metrics.inc("train.guard.skips")
                    control_event("skip_step", step=step)
                    if "log" in self.hooks:
                        self.hooks["log"](
                            f"step {step} numerics fault -> skipped "
                            f"({self._consecutive_faults} consecutive): "
                            + ", ".join(f"{f['leaf']}[{f['kind']}]"
                                        for f in faults[:4]))
                    if self.tc.ckpt_dir and (step + 1) % self.tc.ckpt_every == 0:
                        self._save(step + 1, state, step)
                    continue
                self._consecutive_faults = 0
                self.step_times.append(dt)
                losses.append(loss)
                if "metrics" in self.hooks:
                    self.hooks["metrics"](step, loss)
                # straggler watchdog (real deployment: report to coordinator,
                # trigger backup-worker promotion; here: hook + log)
                if len(self.step_times) >= 8:
                    med = float(np.median(self.step_times[-32:]))
                    if dt > self.tc.straggler_factor * med:
                        control_event("straggler", step=step, dt_ms=dt * 1e3,
                                      median_ms=med * 1e3)
                        if "straggler" in self.hooks:
                            self.hooks["straggler"](step, dt, med)
                if self.tc.ckpt_dir and (step + 1) % self.tc.ckpt_every == 0:
                    self._save(step + 1, state, step)
                if "log" in self.hooks and step % self.tc.log_every == 0:
                    self.hooks["log"](f"step {step} loss {loss:.4f} ({dt*1e3:.0f} ms)")
        if self.tc.ckpt_dir:
            self._save(self.tc.steps, state, self.tc.steps - 1, prune=False)
        return state, losses
