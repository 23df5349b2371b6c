"""Apply completed shardings to a computation (the "partitioning" handoff).

After propagation (propagation.py) assigns a ``Sharding`` to every jaxpr var, this
module re-evaluates the jaxpr inserting ``with_sharding_constraint`` on every
annotated/inferred tensor, then hands the constrained program to ``jax.jit`` —
XLA's SPMD partitioner (the production GSPMD implementation, §4) emits the
per-device program and collectives.

``gspmd_jit(fn, jmesh, mesh)`` is the end-user entry point: write ``fn`` as a
single-device program with a few ``annotate`` calls; we complete the shardings and
compile one SPMD program.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import core, lax
from jax.extend import core as excore

from .annotate import annotate_p
from .compat import trace_for
from .propagation import Propagation, propagate
from .sharding import Mesh, Sharding, to_named_sharding


def _wsc(x, s: Optional[Sharding], jmesh):
    if s is None or s.is_fully_replicated():
        return x
    if getattr(x, "ndim", None) != s.rank:
        return x
    return lax.with_sharding_constraint(x, to_named_sharding(s, jmesh))


def eval_with_constraints(jaxpr: excore.Jaxpr, consts, prop: Propagation, jmesh, *args):
    """eval_jaxpr clone that pins every var to its completed sharding."""
    env: Dict[excore.Var, object] = {}

    def read(v):
        return v.val if isinstance(v, excore.Literal) else env[v]

    def write(v, val, constrain=True):
        if constrain:
            val = _wsc(val, prop.get(v), jmesh)
        env[v] = val

    for v, c in zip(jaxpr.constvars, consts):
        write(v, c, constrain=False)
    for v, a in zip(jaxpr.invars, args):
        write(v, a)

    for eqn in jaxpr.eqns:
        invals = [read(v) for v in eqn.invars]
        prim = eqn.primitive
        if prim is annotate_p:
            outvals = [_wsc(invals[0], eqn.params["sharding"], jmesh)]
        elif prim.name == "scan":
            outvals = _eval_scan(eqn, invals, prop, jmesh)
        elif prim.name == "jit":
            inner = prop.sub.get(id(eqn))
            sub = eqn.params["jaxpr"]
            if inner is None:
                inner = Propagation(sub.jaxpr, prop.mesh)
            outs = eval_with_constraints(
                sub.jaxpr, sub.consts, inner, jmesh, *invals
            )
            outvals = list(outs)
        else:
            subfuns, bind_params = prim.get_bind_params(eqn.params)
            ans = prim.bind(*subfuns, *invals, **bind_params)
            outvals = list(ans) if prim.multiple_results else [ans]
        for v, val in zip(eqn.outvars, outvals):
            if isinstance(v, core.DropVar):
                continue
            write(v, val)

    return tuple(read(v) for v in jaxpr.outvars)


def _eval_scan(eqn, invals, prop: Propagation, jmesh):
    p = eqn.params
    nc, nk = p["num_consts"], p["num_carry"]
    closed = p["jaxpr"]
    inner = prop.sub.get(id(eqn))
    if inner is None:
        inner = Propagation(closed.jaxpr, prop.mesh)
    consts = invals[:nc]
    init = invals[nc : nc + nk]
    xs = invals[nc + nk :]

    def body(carry, x):
        outs = eval_with_constraints(
            closed.jaxpr, closed.consts, inner, jmesh, *consts, *carry, *x
        )
        return tuple(outs[:nk]), tuple(outs[nk:])

    carry, ys = lax.scan(
        body,
        tuple(init),
        tuple(xs),
        length=p.get("length"),
        reverse=p.get("reverse", False),
        unroll=p.get("unroll", 1),
    )
    return list(carry) + list(ys)


def gspmd_jit(fn, jmesh, mesh: Mesh, static_argnums=()):
    """Compile ``fn`` with GSPMD auto-completion from its ``annotate`` calls.

    The returned callable traces once per input-shape signature, runs the
    propagation pass, and jit-compiles the constrained program.
    """
    cache = {}

    def wrapped(*args):
        import numpy as np

        flat, treedef = jax.tree_util.tree_flatten(args)
        key = (treedef, tuple((x.shape, str(jnp.result_type(x))) for x in flat))
        if key not in cache:
            closed, out_shape = trace_for(mesh, fn, *args, return_shape=True)
            out_tree = jax.tree_util.tree_structure(out_shape)
            prop = propagate(closed, mesh)

            def constrained(*inner_args):
                inner_flat, _ = jax.tree_util.tree_flatten(inner_args)
                outs = eval_with_constraints(
                    closed.jaxpr, closed.consts, prop, jmesh, *inner_flat
                )
                return jax.tree_util.tree_unflatten(out_tree, list(outs))

            cache[key] = (jax.jit(constrained), prop)
        return cache[key][0](*args)

    wrapped.propagation_for = lambda *args: propagate(
        trace_for(mesh, fn, *args), mesh
    )
    return wrapped
