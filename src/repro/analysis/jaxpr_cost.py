"""Analytic FLOP counting by walking a jaxpr (cross-check for cost_analysis).

Counts matmul/conv FLOPs exactly and elementwise ops at 1 flop/element,
multiplying ``scan`` bodies by their trip count (the correction XLA's
``cost_analysis()`` lacks) and recursing into jit/remat/custom_* calls.
Used in tests to validate the layers-delta roofline accounting.
"""
from __future__ import annotations

import math
from typing import Dict

import numpy as np
from jax import core
from jax.extend import core as excore

ELEMENTWISE_1FLOP = {
    "add", "sub", "mul", "div", "max", "min", "neg", "abs", "exp", "log",
    "tanh", "logistic", "rsqrt", "sqrt", "select_n", "pow", "integer_pow",
    "erf", "sin", "cos", "sign", "floor", "ceil", "round", "square",
}


def _subjaxpr(params):
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        if key in params:
            j = params[key]
            if isinstance(j, excore.ClosedJaxpr):
                return j.jaxpr
            if isinstance(j, excore.Jaxpr):
                return j
    return None


def _nelems(aval) -> int:
    return int(np.prod(aval.shape)) if aval.shape else 1


def count_flops(jaxpr: excore.Jaxpr) -> float:
    """Total FLOPs for one evaluation of ``jaxpr`` (global, unsharded)."""
    total = 0.0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            (lc, _), (lb, _) = eqn.params["dimension_numbers"]
            out = _nelems(eqn.outvars[0].aval)
            k = 1
            for ci in lc:
                k *= eqn.invars[0].aval.shape[ci]
            total += 2.0 * out * k
        elif name == "conv_general_dilated":
            out = _nelems(eqn.outvars[0].aval)
            rhs = eqn.invars[1].aval
            # per output element: 2 * (in_features/groups) * prod(kernel spatial)
            k = _nelems(rhs) // rhs.shape[0]
            total += 2.0 * out * k
        elif name == "scan":
            body = _subjaxpr(eqn.params)
            total += eqn.params["length"] * count_flops(body)
        elif name == "while":
            body = _subjaxpr({"jaxpr": eqn.params.get("body_jaxpr")})
            if body is not None:
                total += count_flops(body)  # unknown trips: count once
        elif _subjaxpr(eqn.params) is not None:
            total += count_flops(_subjaxpr(eqn.params))
        elif name in ELEMENTWISE_1FLOP:
            total += float(_nelems(eqn.outvars[0].aval))
        elif name.startswith("reduce_"):
            total += float(_nelems(eqn.invars[0].aval))
    return total


def count_flops_fn(fn, *args) -> float:
    import jax

    closed = jax.make_jaxpr(fn)(*args)
    return count_flops(closed.jaxpr)
