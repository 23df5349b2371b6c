"""Version bridges for the jax APIs the partitioner depends on — and the
repo-wide numeric tolerance policy.

The partitioner executes local programs under ``shard_map``; everything in
``repro.core`` reaches it and the mesh APIs through this module, so the rest of
the code has one spelling of each.

**Tolerance policy** (:data:`TOLERANCES`, :func:`assert_close`): partitioned
programs are *mathematically* identical to their single-device references but
not *bitwise* — sharded contractions commit to a different reduction order
(psum over per-shard partials), so results drift by a few ULP per reduction
depth.  Instead of each test hand-picking an rtol, tests name the comparison
class:

========== ============== =================================================
kind        rtol / atol    when
========== ============== =================================================
exact       0 / 0          same reduction order — must be bit-identical
                           (e.g. replaying the same plan, reshard restore)
f32         1e-6 / 1e-6    elementwise or unsharded-contraction f32: no
                           reduction reorder, only fusion differences
f32_dot     1e-5 / 1e-5    one sharded contraction (matmul/einsum whose
                           reduction dim is split: psum reorders the sum)
ulp         2e-5 / 1e-8    gradients through sharded einsums — the known
                           ULP-close backward-einsum gap (ROADMAP): reverse
                           AD stacks a second reduction reorder on top
f32_chain   1e-4 / 1e-5    multi-op chains (halo/conv pipelines, MLP
                           towers): reorders compound per layer
coarse      1e-3 / 1e-3    bf16-compute paths or deep mixed chains
loss_curve  5e-2 / 0       training-loss trajectories across recoveries:
                           optimizer noise amplifies per-step drift
========== ============== =================================================

Tightening a class is always safe; loosening one (or adding an ad-hoc rtol
in a test) needs a comment explaining which new reduction reorder justifies
it.
"""
from __future__ import annotations

import contextvars

import jax

# kind -> (rtol, atol); see module docstring for the policy table
TOLERANCES = {
    "exact": (0.0, 0.0),
    "f32": (1e-6, 1e-6),
    "f32_dot": (1e-5, 1e-5),
    "ulp": (2e-5, 1e-8),
    "f32_chain": (1e-4, 1e-5),
    "coarse": (1e-3, 1e-3),
    "loss_curve": (5e-2, 0.0),
}


def assert_close(got, want, kind: str = "f32", **kwargs):
    """``np.testing.assert_allclose`` under the named tolerance class.

    Extra kwargs pass through (``err_msg``, ...); overriding ``rtol``/``atol``
    directly is deliberately not supported — change the class or the policy.
    """
    import numpy as np

    if kind not in TOLERANCES:
        raise KeyError(
            f"unknown tolerance class {kind!r}; one of {sorted(TOLERANCES)}")
    if "rtol" in kwargs or "atol" in kwargs:
        raise TypeError("assert_close takes a tolerance class, not rtol/atol")
    rtol, atol = TOLERANCES[kind]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=rtol, atol=atol, **kwargs)


def shard_map(f, *, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with replication checking disabled by default.

    The reference partitioner inserts its own collectives, which the
    replication checker cannot see through.
    """
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check
    )


def eqn_name_stack(eqn):
    """The name stack (``jax.named_scope`` scopes and ``jvp``/``transpose``
    transforms) that ``eqn`` was traced under; None when it is empty."""
    ns = eqn.source_info.name_stack
    return ns if len(ns) else None


def under_name_stack(ns):
    """Context manager that binds primitives under ``ns``, appended to the
    current name stack, so that the lowered program's op names carry it."""
    from jax._src import source_info_util

    return source_info_util.set_name_stack(
        source_info_util.current_name_stack() + ns)


def cost_analysis_dict(compiled):
    """``compiled.cost_analysis()`` as a flat dict (``{}`` when the backend
    reports nothing)."""
    return compiled.cost_analysis() or {}


def get_abstract_mesh():
    """The ambient mesh; exposes ``empty`` / ``axis_names`` / ``axis_sizes``."""
    return jax.sharding.get_abstract_mesh()


def set_mesh(jmesh):
    """Context manager making ``jmesh`` the ambient mesh."""
    return jax.set_mesh(jmesh)


# devices of the mesh that the program being traced is for (1: none)
_TRACE_DEVICES = contextvars.ContextVar("trace_devices", default=1)


def trace_for(mesh, fn, *args, return_shape: bool = False):
    """``jax.make_jaxpr(fn)(*args)`` for a program that will run on ``mesh``.

    Partitioners and the sharding search trace with global shapes and no
    ambient mesh, so the program's own code cannot see how it will be split;
    while it is traced here, :func:`partition_devices` gives the number of
    devices of ``mesh``.  Every trace of a program for a mesh goes through
    this function, so the program analysed is the one that runs."""
    token = _TRACE_DEVICES.set(int(mesh.devices.size))
    try:
        return jax.make_jaxpr(fn, return_shape=return_shape)(*args)
    finally:
        _TRACE_DEVICES.reset(token)


def partition_devices() -> int:
    """Devices of the mesh the program is traced for by :func:`trace_for`
    (1 outside it)."""
    return _TRACE_DEVICES.get()


def axis_size(name: str) -> int:
    """Static size of a named mesh axis inside a shard_map region."""
    return jax.lax.axis_size(name)


def make_jax_mesh(shape, axis_names):
    """A ``jax.sharding.Mesh`` with Auto axis types."""
    axis_names = tuple(axis_names)
    return jax.make_mesh(
        tuple(shape), axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names),
    )
