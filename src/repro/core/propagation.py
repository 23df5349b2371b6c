"""Sharding auto-completion over jaxprs (paper §3.5).

Implements the paper's iterative, priority-based propagation:

* alternating forward (input→output) and backward (output→input) sweeps;
* per-operator, per-direction priorities (elementwise first, dimension-changing
  ops later, Broadcast prefers backward);
* merging of compatible shardings (Figure 3);
* only-refine updates, so a fixed point is guaranteed;
* user annotations (``gspmd_annotate`` equations) are preserved verbatim, except
  on their declared ``unspecified_dims`` (partial specification, §3.5);
* recursion into ``scan`` / ``jit`` / ``remat`` / ``custom_*`` sub-jaxprs, with a
  carry fixed-point for ``scan``.

The result maps every jaxpr variable to a ``Sharding``; ``apply.py`` turns that
into ``with_sharding_constraint``s for XLA (the partitioning pass).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
from jax import core
from jax.extend import core as excore

from .annotate import annotate_p
from .rules import MAX_PRIORITY, PRIORITY, RULES
from .sharding import Mesh, Sharding, is_refinement, merge_shardings

MaybeS = Optional[Sharding]


def _subjaxpr(eqn):
    """Find the sub-jaxpr an equation calls, if any.  A Pallas kernel's
    ``jaxpr`` is a program over blocks of its operands, not a function of
    them: nothing propagates through it."""
    if eqn.primitive.name == "pallas_call":
        return None
    params = eqn.params
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        if key in params:
            j = params[key]
            if isinstance(j, excore.ClosedJaxpr):
                return j
            if isinstance(j, excore.Jaxpr):
                return excore.ClosedJaxpr(j, ())
    return None


class Propagation:
    """One propagation problem over one (closed) jaxpr."""

    def __init__(self, jaxpr: excore.Jaxpr, mesh: Mesh):
        self.jaxpr = jaxpr
        self.mesh = mesh
        self.env: Dict[excore.Var, Sharding] = {}
        self.locked: Dict[excore.Var, frozenset] = {}  # locked dims per var
        self.sub: Dict[int, "Propagation"] = {}  # id(eqn) -> inner propagation
        self.changed = False

    # -- env access ---------------------------------------------------------------
    def get(self, v) -> MaybeS:
        if isinstance(v, excore.Literal):
            return None
        return self.env.get(v)

    def refine(self, v, s: MaybeS) -> None:
        """Merge ``s`` into v's sharding; refuses to alter locked dims.

        Mesh axes that do not divide the dim size are dropped (§4.1 fallback:
        replicate rather than fail) — the reference partitioner's reshard
        planner requires even shards, so propagating a non-dividing axis would
        only produce an unlowerable plan.  Stacked axes are cut at the first
        non-dividing position (shards stack product-wise).
        """
        if s is None or isinstance(v, excore.Literal):
            return
        if getattr(v.aval, "ndim", None) != s.rank:
            return
        shape = getattr(v.aval, "shape", None)
        if shape is not None and len(shape) == s.rank:
            dm, masked = [], False
            for d, axes in enumerate(s.dims_mapping):
                kept, n = [], 1
                for a in axes:
                    n *= s.mesh.axis_size(a)
                    if shape[d] % n:
                        masked = True
                        break
                    kept.append(a)
                dm.append(tuple(kept))
            if masked:
                s = Sharding(s.mesh, tuple(dm))
        cur = self.env.get(v)
        locked = self.locked.get(v)
        if locked:
            # locked dims keep their seeded mapping
            dm = list(s.dims_mapping)
            used = set()
            for d in range(s.rank):
                if d in locked:
                    dm[d] = cur.dims_mapping[d]
                    used.update(dm[d])
            # drop unlocked entries that now collide with a locked axis
            for d in range(s.rank):
                if d not in locked:
                    if any(a in used for a in dm[d]):
                        dm[d] = ()
                    else:
                        used.update(dm[d])
            try:
                s = Sharding(s.mesh, tuple(dm))
            except AssertionError:
                return
        if cur is None:
            self.env[v] = s
            self.changed = True
            return
        m = merge_shardings(cur, s)
        if m is not None and m.dims_mapping != cur.dims_mapping:
            self.env[v] = m
            self.changed = True

    # -- seeding ------------------------------------------------------------------
    def seed_annotations(self) -> None:
        for eqn in self.jaxpr.eqns:
            if eqn.primitive is annotate_p:
                s: Sharding = eqn.params["sharding"]
                unspec = set(eqn.params["unspecified_dims"])
                locked = frozenset(d for d in range(s.rank) if d not in unspec)
                for v in (eqn.invars[0], eqn.outvars[0]):
                    if isinstance(v, excore.Literal):
                        continue
                    self.env[v] = s
                    self.locked[v] = locked

    def seed_io(self, in_sh: List[MaybeS] = None, out_sh: List[MaybeS] = None):
        if in_sh:
            for v, s in zip(self.jaxpr.invars, in_sh):
                self.refine(v, s)
        if out_sh:
            for v, s in zip(self.jaxpr.outvars, out_sh):
                self.refine(v, s)

    # -- one eqn ------------------------------------------------------------------
    def _apply_eqn(self, eqn, direction: str) -> None:
        name = eqn.primitive.name
        if eqn.primitive is annotate_p:
            # identity: merge across the annotation (respecting locks via refine)
            self.refine(eqn.outvars[0], self.get(eqn.invars[0]))
            self.refine(eqn.invars[0], self.get(eqn.outvars[0]))
            return
        sub = _subjaxpr(eqn)
        if sub is not None:
            self._apply_call(eqn, sub)
            return
        rule = RULES.get(name)
        if rule is None:
            return
        in_sh = [self.get(v) for v in eqn.invars]
        out_sh = [self.get(v) for v in eqn.outvars]
        new_in, new_out = rule(eqn, in_sh, out_sh, direction)
        for v, s in zip(eqn.invars, new_in):
            self.refine(v, s)
        for v, s in zip(eqn.outvars, new_out):
            self.refine(v, s)

    # -- calls & scan ---------------------------------------------------------------
    def _inner(self, eqn, closed) -> "Propagation":
        p = self.sub.get(id(eqn))
        if p is None:
            p = Propagation(closed.jaxpr, self.mesh)
            p.seed_annotations()
            self.sub[id(eqn)] = p
        return p

    def _apply_call(self, eqn, closed: excore.ClosedJaxpr) -> None:
        name = eqn.primitive.name
        if name == "scan":
            self._apply_scan(eqn, closed)
            return
        inner = self._inner(eqn, closed)
        # account for jaxprs that close over consts: invars align at the tail
        n_in = len(closed.jaxpr.invars)
        n_out = len(closed.jaxpr.outvars)
        outer_in = list(eqn.invars)[-n_in:] if n_in else []
        outer_out = list(eqn.outvars)[:n_out]
        inner.seed_io(
            [self.get(v) for v in outer_in], [self.get(v) for v in outer_out]
        )
        inner.run(max_rounds=4)
        for ov, iv in zip(outer_in, closed.jaxpr.invars):
            self.refine(ov, inner.get(iv))
        for ov, iv in zip(outer_out, closed.jaxpr.outvars):
            self.refine(ov, inner.get(iv))

    def _apply_scan(self, eqn, closed: excore.ClosedJaxpr) -> None:
        nc = eqn.params["num_consts"]
        nk = eqn.params["num_carry"]
        inner = self._inner(eqn, closed)
        body = closed.jaxpr
        consts = eqn.invars[:nc]
        init = eqn.invars[nc : nc + nk]
        xs = eqn.invars[nc + nk :]
        final = eqn.outvars[:nk]
        ys = eqn.outvars[nk:]

        def drop0(s: MaybeS) -> MaybeS:
            if s is None or s.rank == 0:
                return None
            return Sharding(s.mesh, s.dims_mapping[1:])

        def add0(s: MaybeS) -> MaybeS:
            if s is None:
                return None
            return Sharding(s.mesh, ((),) + s.dims_mapping)

        # carry fixed point (bounded)
        for _ in range(4):
            in_seed = (
                [self.get(v) for v in consts]
                + [self.get(v) for v in init]
                + [drop0(self.get(v)) for v in xs]
            )
            out_seed = [self.get(v) for v in final] + [
                drop0(self.get(v)) for v in ys
            ]
            inner.seed_io(in_seed, out_seed)
            inner.changed = False
            inner.run(max_rounds=4)
            # feed carry-out back to carry-in; converged when the carry-in
            # *mapping* stops changing (refine may rebuild an equal Sharding
            # object, so identity comparison would never converge early)
            moved = False
            for i in range(nk):
                cin, cout = body.invars[nc + i], body.outvars[i]
                before = inner.get(cin)
                inner.refine(cin, inner.get(cout))
                inner.refine(cout, inner.get(cin))
                after = inner.get(cin)
                if (before is None) != (after is None) or (
                    after is not None
                    and before is not None
                    and after.dims_mapping != before.dims_mapping
                ):
                    moved = True
            if not moved and not inner.changed:
                break
        # reflect to outer
        for ov, iv in zip(consts, body.invars[:nc]):
            self.refine(ov, inner.get(iv))
        for ov, iv in zip(init, body.invars[nc : nc + nk]):
            self.refine(ov, inner.get(iv))
        for ov, iv in zip(xs, body.invars[nc + nk :]):
            self.refine(ov, add0(inner.get(iv)))
        for ov, iv in zip(final, body.outvars[:nk]):
            self.refine(ov, inner.get(iv))
        for ov, iv in zip(ys, body.outvars[nk:]):
            self.refine(ov, add0(inner.get(iv)))

    # -- driver ---------------------------------------------------------------------
    def run(self, max_rounds: int = 32) -> Dict[excore.Var, Sharding]:
        for _ in range(max_rounds):
            round_changed = False
            for p in range(MAX_PRIORITY + 1):
                self.changed = False
                for eqn in self.jaxpr.eqns:  # forward sweep
                    if self._prio(eqn) <= p:
                        self._apply_eqn(eqn, "fwd")
                for eqn in reversed(self.jaxpr.eqns):  # backward sweep
                    if self._prio(eqn) <= p:
                        self._apply_eqn(eqn, "bwd")
                if self.changed:
                    round_changed = True
            if not round_changed:
                break
        return self.env

    @staticmethod
    def _prio(eqn) -> int:
        if eqn.primitive is annotate_p:
            return 0
        if _subjaxpr(eqn) is not None:
            return 2
        return PRIORITY.get(eqn.primitive.name, MAX_PRIORITY)

    # -- stable post-run handle -------------------------------------------------
    def result(self) -> "PropagationResult":
        """Freeze this propagation into a :class:`PropagationResult`.

        The live ``Propagation`` keys sub-problems by ``id(eqn)`` — fine while
        the object graph is alive, but useless as a cache artifact.  The result
        re-keys them by equation *index*, which is stable for the lifetime of
        the (retained) jaxpr, so the partition-plan compiler can look up inner
        propagations without holding the mutable pass object.
        """
        sub = {}
        for i, eqn in enumerate(self.jaxpr.eqns):
            p = self.sub.get(id(eqn))
            if p is not None:
                sub[i] = p.result()
        return PropagationResult(self.jaxpr, self.mesh, dict(self.env), sub)


@dataclasses.dataclass(frozen=True)
class PropagationResult:
    """Immutable view of a finished propagation: the plan compiler's input.

    ``sub`` maps *equation index* (not ``id``) to the inner result for
    scan/jit/remat bodies.
    """

    jaxpr: excore.Jaxpr
    mesh: Mesh
    env: Dict[excore.Var, Sharding]
    sub: Dict[int, "PropagationResult"]

    def get(self, v) -> MaybeS:
        if isinstance(v, excore.Literal):
            return None
        return self.env.get(v)


def propagate(
    closed_jaxpr: excore.ClosedJaxpr,
    mesh: Mesh,
    in_shardings: List[MaybeS] = None,
    out_shardings: List[MaybeS] = None,
) -> Propagation:
    """Complete shardings for every var in ``closed_jaxpr`` (paper §3.5)."""
    p = Propagation(closed_jaxpr.jaxpr, mesh)
    p.seed_annotations()
    p.seed_io(in_shardings, out_shardings)
    p.run()
    return p
