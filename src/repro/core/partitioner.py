"""The reference SPMD partitioner (paper §4).

XLA's SPMD partitioner (production GSPMD) is what ``jax.jit`` invokes; this module
is our own *reference implementation* of the same transformation, executing a
jaxpr as a single program over local shards inside one ``shard_map`` region, with
explicit ``jax.lax`` collectives:

* dot_general  — einsum partitioning with recursive grouping (§4.4) via
                 ``einsum_rules.partitioned_einsum`` (AllReduce / ReduceScatter /
                 AllGather as required);
* elementwise  — operands resharded to the merged sharding, computed locally;
* reduce       — local reduce + psum over mesh axes sharding reduced dims;
* conv         — halo exchange on sharded spatial dims (§4.3);
* formatting   — pad/slice/concatenate fall back to AllGather + op + DynamicSlice
                 (§4.5 resharding; GSPMD's optimized halo versions exist in
                 halo.py and are used by the model layer directly);
* annotate     — explicit resharding to the user's annotation.

It is validated numerically against the unpartitioned program — GSPMD's
"mathematically equivalent" guarantee — in tests/multidev/.

Two execution paths share these semantics:

* the **compiled-plan path** (default): ``spmd_partition`` lowers the
  propagated jaxpr once into a ``plan.PartitionPlan`` (resolved per-equation
  steps, cost-model-chosen reshard programs) and caches it keyed by input
  avals + mesh — steady-state calls skip tracing, propagation, and all
  per-equation Python dispatch;
* the **dynamic reference path** (``SpmdPartitioner``, or
  ``spmd_partition(..., compile_plans=False)``): re-decides everything while
  tracing.  Kept as the executable specification the plan compiler must
  match, and for differential testing.
"""
from __future__ import annotations

import dataclasses
import hashlib
import threading
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import core, lax
from jax.extend import core as excore

from repro.obs.trace import span

from .annotate import annotate_p
from .compat import shard_map, trace_for
from .einsum_rules import partitioned_einsum
from .propagation import Propagation, propagate
from .reshard import reshard_local, shard_shape
from .rules import ELEMENTWISE
from .sharding import Mesh, Sharding, merge_shardings, replicated, to_partition_spec


class SpmdPartitioner:
    """Evaluates a jaxpr on local shards, inserting collectives per §4."""

    def __init__(self, prop: Propagation, mesh: Mesh):
        self.prop = prop
        self.mesh = mesh
        # local values + their current shardings
        self.vals: Dict[excore.Var, object] = {}
        self.shardings: Dict[excore.Var, Sharding] = {}

    # -- var access -------------------------------------------------------------
    def read(self, v):
        if isinstance(v, excore.Literal):
            return v.val, replicated(self.mesh, np.ndim(v.val))
        return self.vals[v], self.shardings[v]

    def write(self, v, val, sh: Sharding):
        if isinstance(v, core.DropVar):
            return
        self.vals[v] = val
        self.shardings[v] = sh

    def _to(self, val, cur: Sharding, tgt: Sharding):
        if cur.dims_mapping == tgt.dims_mapping:
            return val
        return reshard_local(val, cur, tgt)

    # -- the partitioning pass ----------------------------------------------------
    def run(self, jaxpr: excore.Jaxpr, consts, *args):
        for v, c in zip(jaxpr.constvars, consts):
            self.write(v, c, replicated(self.mesh, np.ndim(c)))
        for v, a in zip(jaxpr.invars, args):
            sh = self.prop.get(v) or replicated(self.mesh, np.ndim(a))
            self.write(v, a, sh)
        for eqn in jaxpr.eqns:
            self.eqn(eqn)
        outs = []
        for v in jaxpr.outvars:
            val, sh = self.read(v)
            want = self.prop.get(v) or replicated(self.mesh, np.ndim(val))
            outs.append(self._to(val, sh, want))
        return tuple(outs)

    def eqn(self, eqn):
        prim = eqn.primitive
        name = prim.name
        if prim is annotate_p:
            val, sh = self.read(eqn.invars[0])
            tgt = eqn.params["sharding"]
            self.write(eqn.outvars[0], self._to(val, sh, tgt), tgt)
            return
        if name == "dot_general":
            self._dot(eqn)
            return
        if name in ELEMENTWISE or name in ("select_n", "convert_element_type"):
            self._elementwise(eqn)
            return
        if name.startswith("reduce_") and "window" not in name:
            self._reduce(eqn)
            return
        if name == "transpose":
            self._transpose(eqn)
            return
        if name == "broadcast_in_dim":
            self._broadcast(eqn)
            return
        if name == "reshape":
            self._reshape(eqn)
            return
        if name == "conv_general_dilated":
            self._conv(eqn)
            return
        if name == "jit":
            self._jit(eqn)
            return
        if name == "scan":
            self._scan(eqn)
            return
        if name in ("iota",):
            out = prim.bind(**eqn.params)
            self.write(eqn.outvars[0], out, replicated(self.mesh, out.ndim))
            return
        # fallback: gather everything, run globally, re-slice to inferred sharding
        self._fallback(eqn)

    # -- op handlers ----------------------------------------------------------------
    def _dot(self, eqn):
        (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
        lv, ls = self.read(eqn.invars[0])
        rv, rs = self.read(eqn.invars[1])
        # express the dot as an einsum spec
        import string

        letters = iter(string.ascii_lowercase)
        l_names = [next(letters) for _ in range(lv.ndim if hasattr(lv, "ndim") else 0)]
        r_names = [None] * np.ndim(rv)
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
        out, osh = partitioned_einsum(
            spec, lv, rv, ls, rs, want,
            preferred_element_type=eqn.params.get("preferred_element_type"),
        )
        self.write(eqn.outvars[0], out, osh)

    def _elementwise(self, eqn):
        vals, shs = zip(*(self.read(v) for v in eqn.invars))
        ov0 = eqn.outvars[0]
        rank = ov0.aval.ndim
        out_shape = tuple(ov0.aval.shape)

        def gshape(iv, val):
            aval = getattr(iv, "aval", None)
            return tuple(aval.shape) if aval is not None else tuple(np.shape(val))

        def mask_bcast(shape, s: Sharding) -> Sharding:
            # size-1 broadcast dims must stay replicated on that operand:
            # every shard needs the single value (matches plan.PlanBuilder)
            return Sharding(self.mesh, tuple(
                s.dims_mapping[d] if shape[d] == out_shape[d] else ()
                for d in range(rank)
            ))

        tgt = None
        for iv, s, v in zip(eqn.invars, shs, vals):
            shape = gshape(iv, v)
            if len(shape) == rank:
                m = mask_bcast(shape, s)
                tgt = m if tgt is None else (merge_shardings(tgt, m) or tgt)
        if tgt is None:
            tgt = replicated(self.mesh, rank)
        new_vals = [
            self._to(v, s, mask_bcast(gshape(iv, v), tgt))
            if len(gshape(iv, v)) == rank else v
            for iv, v, s in zip(eqn.invars, vals, shs)
        ]
        subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)
        out = eqn.primitive.bind(*subfuns, *new_vals, **bind_params)
        outs = out if eqn.primitive.multiple_results else [out]
        for v, o in zip(eqn.outvars, outs):
            self.write(v, o, tgt)

    def _reduce(self, eqn):
        val, sh = self.read(eqn.invars[0])
        axes = eqn.params["axes"]
        subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)
        out = eqn.primitive.bind(*subfuns, val, **bind_params)
        psum_axes = tuple(a for d in axes for a in sh.dims_mapping[d])
        if psum_axes:
            if eqn.primitive.name == "reduce_sum":
                out = lax.psum(out, psum_axes)
            elif eqn.primitive.name == "reduce_max":
                out = lax.pmax(out, psum_axes)
            elif eqn.primitive.name == "reduce_min":
                out = lax.pmin(out, psum_axes)
            else:  # prod/and/or: gather first instead
                val = self._to(val, sh, replicated(self.mesh, sh.rank))
                out = eqn.primitive.bind(*subfuns, val, **bind_params)
                # the gathered reduce produced a *global* result — its sharding
                # is replicated, not the kept slice of the input's sharding
                self.write(
                    eqn.outvars[0], out,
                    replicated(self.mesh, sh.rank - len(axes)),
                )
                return
        kept = [i for i in range(sh.rank) if i not in axes]
        osh = Sharding(self.mesh, tuple(sh.dims_mapping[i] for i in kept))
        self.write(eqn.outvars[0], out, osh)

    def _transpose(self, eqn):
        val, sh = self.read(eqn.invars[0])
        perm = eqn.params["permutation"]
        out = lax.transpose(val, perm)
        osh = Sharding(self.mesh, tuple(sh.dims_mapping[i] for i in perm))
        self.write(eqn.outvars[0], out, osh)

    def _broadcast(self, eqn):
        val, sh = self.read(eqn.invars[0])
        bcast = eqn.params["broadcast_dimensions"]
        gshape = eqn.params["shape"]
        out_rank = len(gshape)
        dm = [() for _ in range(out_rank)]
        in_aval = eqn.invars[0].aval
        for i, j in enumerate(bcast):
            if in_aval.shape[i] == gshape[j]:
                dm[j] = sh.dims_mapping[i]
        osh = Sharding(self.mesh, tuple(dm))
        local_shape = shard_shape(tuple(gshape), osh)
        out = lax.broadcast_in_dim(val, local_shape, bcast)
        self.write(eqn.outvars[0], out, osh)

    def _reshape(self, eqn):
        val, sh = self.read(eqn.invars[0])
        want = self.prop.get(eqn.outvars[0])
        gshape = eqn.params["new_sizes"]
        if want is not None:
            # try the local reshape: valid when each sharded output dim's shard
            # count divides its size and the factor layout matches (propagation
            # only proposes such mappings)
            local = shard_shape(tuple(gshape), want)
            try:
                out = lax.reshape(val, local, eqn.params.get("dimensions"))
                self.write(eqn.outvars[0], out, want)
                return
            except TypeError:
                pass
        # fallback: gather, reshape, re-slice
        val = self._to(val, sh, replicated(self.mesh, sh.rank))
        out = lax.reshape(val, gshape, eqn.params.get("dimensions"))
        osh = want or replicated(self.mesh, len(gshape))
        out = self._to(out, replicated(self.mesh, len(gshape)), osh)
        self.write(eqn.outvars[0], out, osh)

    def _conv(self, eqn):
        from .halo import sharded_conv_nd

        lv, ls = self.read(eqn.invars[0])
        rv, rs = self.read(eqn.invars[1])
        # kernel replicated; lhs may be sharded on batch and/or spatial dims
        rv = self._to(rv, rs, replicated(self.mesh, rs.rank))
        dn = eqn.params["dimension_numbers"]
        assert dn.lhs_spec[0] == 0 and dn.lhs_spec[1] == 1, "NC*spatial layout only"
        sharded = [
            (d, ls.dims_mapping[d][0])
            for d in range(2, ls.rank)
            if ls.dims_mapping[d]
        ]
        if ls.dims_mapping[1]:
            # feature-dim sharded: contract locally then psum (Megatron-style)
            ax = ls.dims_mapping[1]
            idx = lax.axis_index(ax[0])
            n = self.mesh.axis_size(ax[0])
            size = rv.shape[1] // n
            rv_local = lax.dynamic_slice_in_dim(rv, idx * size, size, axis=1)
            out = lax.conv_general_dilated(
                lv, rv_local,
                window_strides=eqn.params["window_strides"],
                padding=eqn.params["padding"],
            )
            out = lax.psum(out, ax)
            osh = Sharding(self.mesh, (ls.dims_mapping[0], ()) + ((),) * (ls.rank - 2))
            self.write(eqn.outvars[0], out, osh)
            return
        out = sharded_conv_nd(
            lv, rv,
            sharded=sharded,
            window_strides=eqn.params["window_strides"],
            padding=eqn.params["padding"],
        )
        dm = list(ls.dims_mapping)
        osh = Sharding(self.mesh, tuple(dm))
        self.write(eqn.outvars[0], out, osh)

    def _jit(self, eqn):
        sub = eqn.params["jaxpr"]
        inner_prop = self.prop.sub.get(id(eqn)) or Propagation(sub.jaxpr, self.mesh)
        inner = SpmdPartitioner(inner_prop, self.mesh)
        # seed inner input shardings from our current ones
        vals, shs = zip(*(self.read(v) for v in eqn.invars)) if eqn.invars else ((), ())
        for iv, s in zip(sub.jaxpr.invars, shs):
            if inner_prop.get(iv) is None:
                inner_prop.env[iv] = s
        outs = inner.run(sub.jaxpr, sub.consts, *vals)
        for ov, iv, o in zip(eqn.outvars, sub.jaxpr.outvars, outs):
            osh = inner_prop.get(iv) or replicated(self.mesh, np.ndim(o))
            self.write(ov, o, osh)

    def _scan(self, eqn):
        p = eqn.params
        nc, nk = p["num_consts"], p["num_carry"]
        closed = p["jaxpr"]
        inner_prop = self.prop.sub.get(id(eqn)) or Propagation(closed.jaxpr, self.mesh)
        vals_shs = [self.read(v) for v in eqn.invars]
        consts = [v for v, _ in vals_shs[:nc]]
        init = [v for v, _ in vals_shs[nc : nc + nk]]
        xs = [v for v, _ in vals_shs[nc + nk :]]

        def body(carry, x):
            inner = SpmdPartitioner(inner_prop, self.mesh)
            outs = inner.run(closed.jaxpr, closed.consts, *consts, *carry, *x)
            return tuple(outs[:nk]), tuple(outs[nk:])

        # grad-of-scan is a reverse scan; replaying it forward permutes the
        # per-trip xs/ys (same fix as the compiled-plan path)
        carry, ys = lax.scan(body, tuple(init), tuple(xs),
                             length=p.get("length"),
                             reverse=bool(p.get("reverse", False)))
        outs = list(carry) + list(ys)
        # index-based classification: outputs [0, nk) are carries, the rest are
        # stacked ys that grow a leading (unsharded) scan dim.  (A membership
        # test against eqn.outvars[nk:] is O(n) per output and miscounts when
        # the same var object appears twice.)
        for i, (ov, bodyv, o) in enumerate(
            zip(eqn.outvars, closed.jaxpr.outvars, outs)
        ):
            osh = inner_prop.get(bodyv)
            if osh is None:
                osh = replicated(self.mesh, np.ndim(o))
            elif i >= nk:
                osh = Sharding(self.mesh, ((),) + osh.dims_mapping)
            self.write(ov, o, osh)

    def _fallback(self, eqn):
        """Gather → op → reshard to the propagated sharding (§4.5).

        For formatting ops whose touched dims are known (pad / slice /
        concatenate / rev), only the mesh axes on *modified* dims are
        gathered; unmodified dims keep their sharding and the op runs locally
        (with params rewritten to local extents where needed).  Unknown ops
        still fully replicate.
        """
        from .plan import fallback_keep_sharding

        vals_shs = [self.read(v) for v in eqn.invars]
        keep = fallback_keep_sharding(
            eqn, [sh for _, sh in vals_shs], self.mesh
        )
        if keep is not None:
            kept_sh, params = keep
            rank = kept_sh.rank
            vals = [
                self._to(val, sh, kept_sh)
                if sh.rank == rank
                else self._to(val, sh, replicated(self.mesh, sh.rank))
                for val, sh in vals_shs
            ]
            subfuns, bind_params = eqn.primitive.get_bind_params(params)
            out = eqn.primitive.bind(*subfuns, *vals, **bind_params)
            outs = out if eqn.primitive.multiple_results else [out]
            for v, o in zip(eqn.outvars, outs):
                osh = Sharding(
                    self.mesh,
                    tuple(
                        kept_sh.dims_mapping[d] if d < rank else ()
                        for d in range(np.ndim(o))
                    ),
                )
                want = self.prop.get(v) or osh
                self.write(v, self._to(o, osh, want), want)
            return
        vals = []
        for (val, sh) in vals_shs:
            vals.append(self._to(val, sh, replicated(self.mesh, sh.rank)))
        subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)
        out = eqn.primitive.bind(*subfuns, *vals, **bind_params)
        outs = out if eqn.primitive.multiple_results else [out]
        for v, o in zip(eqn.outvars, outs):
            want = self.prop.get(v) or replicated(self.mesh, np.ndim(o))
            o2 = self._to(o, replicated(self.mesh, np.ndim(o)), want)
            self.write(v, o2, want)


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
    plan: object  # PartitionPlan (for stats/reporting)
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


def spmd_partition(fn, jmesh, mesh: Mesh, compile_plans: bool = True,
                   optimize: bool = True, process_cache: bool = True,
                   autoshard=None, verify=None, guard=None, trace=None,
                   profile=None):
    """Partition ``fn`` with the reference partitioner and return a callable that
    runs the SPMD program over ``jmesh`` via shard_map.

    The user writes ``fn`` against global shapes with ``annotate`` hints; we
    trace, complete shardings (propagation pass), then lower the result into a
    :class:`~repro.core.plan.PartitionPlan` — a flat list of resolved
    per-equation steps with cost-model-chosen reshard programs.  Plans are
    cached keyed by (input avals, mesh): steady-state calls skip
    ``make_jaxpr``, propagation, and all per-equation dispatch, going straight
    to the jitted partitioned program.

    ``compile_plans=False`` selects the dynamic reference path
    (``SpmdPartitioner``), which re-decides everything per trace — kept for
    differential testing and benchmarking against the compiled path.
    ``optimize=False`` skips the whole-program optimizer passes
    (``plan_opt``: jit inlining, scan-invariant reshard hoisting, reshard
    CSE, dead-reshard elimination, collective fusion, overlap-aware
    scheduling) on the compiled plan.  ``process_cache=False`` opts this runner out of the
    process-level plan cache (shared across ``spmd_partition`` call sites,
    keyed by jaxpr digest + mesh + avals).

    ``autoshard`` (an :class:`repro.autoshard.AutoshardConfig`) makes the
    partitioner *annotation-free*: instead of relying on ``annotate`` seeds in
    ``fn``, the traced jaxpr's input shardings are found by the autoshard
    search (cost-only lowering under the roofline model) and fed to
    propagation as seeds.  The searched assignment is cached process-wide by
    jaxpr digest + mesh + config, so repeat call sites pay for the search
    once.

    ``verify`` controls the static plan verifier
    (:func:`repro.core.plan_verify.verify_plan`) on compiled plans: ``None``
    defers to the module default (on unless ``REPRO_PLAN_VERIFY=0``),
    ``True``/``False`` force it.  ``guard`` (a
    :class:`repro.core.plan.GuardConfig`) appends runtime numerics-sentinel
    steps to the plan; the runner host-checks the sentinel vector after each
    call and raises :class:`repro.core.plan.NumericsFault` with per-leaf
    provenance when a guarded output is non-finite or exceeds
    ``guard.max_abs``.  Guards require ``compile_plans=True``.

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
    if guard is not None and not compile_plans:
        raise ValueError("spmd_partition: guard= requires compile_plans=True")
    if trace is not None and not trace.enabled:
        trace = None  # disabled config ≡ no tracing: identical runner
    if trace is not None and not compile_plans:
        raise ValueError("spmd_partition: trace= requires compile_plans=True")
    tracer = None
    if trace is not None:
        from repro.obs.trace import Tracer

        tracer = Tracer(trace)
        process_cache = False  # tracer is runner-local; sharing a traced
        # entry across call sites would cross-wire their spans
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
        if process_cache:
            pkey = (
                _jaxpr_digest(closed), mesh.structural_key(), _jmesh_key(jmesh),
                tuple(_aval_key(a) for a in args), compile_plans, optimize,
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
        plan = None
        if compile_plans:
            from .plan import compile_plan

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

        else:

            def local_fn(*local_args):
                part = SpmdPartitioner(prop, mesh)
                outs = part.run(closed.jaxpr, closed.consts, *local_args)
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
            traced_eager = tracer is not None and tracer.config.measured
            call = shmapped if traced_eager else jax.jit(shmapped)
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
        if guard is not None and entry.plan is not None \
                and entry.plan.guard is not None:
            from .plan import NumericsFault, guard_faults

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
