"""Per-primitive sharding propagation rules (paper §3.5).

Each rule looks at the current (possibly None) shardings of an equation's inputs
and outputs and proposes refinements for the opposite side.  Rules never *remove*
sharding — the propagation pass only refines (merge of compatible shardings), which
guarantees a fixed point.

Priorities (lower = propagates earlier), following the paper:
  0  elementwise ops and annotations (no comm if consistent; most intuitive)
  0  broadcast backward  /  1 broadcast forward (prefer deciding the small shape)
  1  transpose, reshape, pad/slice/concat and other data-formatting ops
  2  dot_general, conv, reduce, gather, scatter-add (dimension-changing)
  3  everything else (no rule -> no propagation)
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import numpy as np
from jax import lax

from .sharding import Sharding, merge_shardings, replicated

MaybeS = Optional[Sharding]

# ---------------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------------


def _merge_many(shs: Sequence[MaybeS]) -> MaybeS:
    out: MaybeS = None
    for s in shs:
        if s is None:
            continue
        if out is None:
            out = s
        else:
            m = merge_shardings(out, s)
            out = m if m is not None else out
    return out


def _project(s: Sharding, dim_map: Sequence[Optional[int]], out_rank: int) -> Sharding:
    """Build a rank-``out_rank`` sharding where out dim j gets s.dims_mapping[i]
    whenever dim_map[j] == i (None -> unsharded).  Drops duplicate axis uses."""
    dm: List[Tuple[str, ...]] = [() for _ in range(out_rank)]
    used = set()
    for j, i in enumerate(dim_map):
        if i is None:
            continue
        axes = s.dims_mapping[i]
        if axes and not any(a in used for a in axes):
            dm[j] = axes
            used.update(axes)
    return Sharding(s.mesh, tuple(dm))


# ---------------------------------------------------------------------------------
# elementwise
# ---------------------------------------------------------------------------------

ELEMENTWISE = {
    "add", "sub", "mul", "div", "pow", "max", "min", "rem", "atan2",
    "neg", "sign", "floor", "ceil", "round", "abs", "exp", "log", "log1p",
    "expm1", "tanh", "logistic", "sin", "cos", "sqrt", "rsqrt", "cbrt",
    "square", "reciprocal", "erf", "erfc", "erf_inv", "is_finite",
    "integer_pow", "not", "and", "or", "xor", "shift_left",
    "shift_right_logical", "shift_right_arithmetic", "eq", "ne", "ge", "gt",
    "le", "lt", "select_n", "convert_element_type", "stop_gradient",
    "clamp", "nextafter", "copy", "real", "imag", "exp2", "tan", "asin",
    "acos", "atan", "sinh", "cosh", "asinh", "acosh", "atanh",
    "population_count", "clz", "reduce_precision", "gspmd_annotate",
    "optimization_barrier", "add_any",
    "name",  # checkpoint_name: an identity tag for remat policies
}


def rule_elementwise(eqn, in_sh: List[MaybeS], out_sh: List[MaybeS], direction):
    rank = eqn.outvars[0].aval.ndim
    cands = [
        s
        for v, s in zip(list(eqn.invars) + list(eqn.outvars), in_sh + out_sh)
        if s is not None and getattr(v.aval, "ndim", None) == rank
    ]
    m = _merge_many(cands)
    if m is None:
        return in_sh, out_sh
    new_in = [
        m if getattr(v.aval, "ndim", None) == rank else s
        for v, s in zip(eqn.invars, in_sh)
    ]
    new_out = [m for _ in out_sh]
    return new_in, new_out


# ---------------------------------------------------------------------------------
# structural ops
# ---------------------------------------------------------------------------------


def rule_transpose(eqn, in_sh, out_sh, direction):
    perm = eqn.params["permutation"]
    (s_in,), (s_out,) = in_sh, out_sh
    if direction == "fwd" and s_in is not None:
        out_map = [perm.index(j) if j in perm else None for j in range(len(perm))]
        # output dim j comes from input dim perm[j]
        new = _project(s_in, list(perm), len(perm))
        return in_sh, [new]
    if direction == "bwd" and s_out is not None:
        inv = [0] * len(perm)
        for j, i in enumerate(perm):
            inv[i] = j
        new = _project(s_out, inv, len(perm))
        return [new], out_sh
    return in_sh, out_sh


def rule_broadcast_in_dim(eqn, in_sh, out_sh, direction):
    bcast = eqn.params["broadcast_dimensions"]
    in_aval = eqn.invars[0].aval
    out_aval = eqn.outvars[0].aval
    (s_in,), (s_out,) = in_sh, out_sh
    if direction == "fwd" and s_in is not None:
        dim_map = [None] * out_aval.ndim
        for i, j in enumerate(bcast):
            if in_aval.shape[i] == out_aval.shape[j]:
                dim_map[j] = i
        return in_sh, [_project(s_in, dim_map, out_aval.ndim)]
    if direction == "bwd" and s_out is not None:
        dim_map = [None] * in_aval.ndim
        for i, j in enumerate(bcast):
            if in_aval.shape[i] == out_aval.shape[j]:
                dim_map[i] = j
        return [_project(s_out, dim_map, in_aval.ndim)], out_sh
    return in_sh, out_sh


def _reshape_dim_map(in_shape, out_shape):
    """Greedy factor-block matching: returns (in->out) and (out->in) partial maps
    for dims whose size is preserved at the front of a matching block."""
    in_to_out = {}
    out_to_in = {}
    i = j = 0
    while i < len(in_shape) and j < len(out_shape):
        # skip size-1 dims
        if in_shape[i] == 1 and (j >= len(out_shape) or out_shape[j] != 1):
            i += 1
            continue
        if out_shape[j] == 1 and in_shape[i] != 1:
            j += 1
            continue
        pi, pj = in_shape[i], out_shape[j]
        bi, bj = [i], [j]
        ii, jj = i, j
        while pi != pj:
            if pi < pj:
                ii += 1
                pi *= in_shape[ii]
                bi.append(ii)
            else:
                jj += 1
                pj *= out_shape[jj]
                bj.append(jj)
        # block [bi] of input matches block [bj] of output
        if len(bi) == 1 and len(bj) == 1:
            in_to_out[bi[0]] = bj[0]
            out_to_in[bj[0]] = bi[0]
        else:
            # major (first) dims correspond if equal size
            if in_shape[bi[0]] == out_shape[bj[0]]:
                in_to_out[bi[0]] = bj[0]
                out_to_in[bj[0]] = bi[0]
            # merged dim: sharding on the major input dim maps to the merged
            # output dim (and vice versa) when sizes allow clean tiling; we only
            # propagate the major-dim case (GSPMD supports more via resharding).
            elif len(bj) == 1:  # merge
                in_to_out[bi[0]] = bj[0]
            elif len(bi) == 1:  # split
                out_to_in[bj[0]] = bi[0]
        i, j = bi[-1] + 1, bj[-1] + 1
    return in_to_out, out_to_in


def rule_reshape(eqn, in_sh, out_sh, direction):
    in_aval = eqn.invars[0].aval
    out_aval = eqn.outvars[0].aval
    (s_in,), (s_out,) = in_sh, out_sh
    i2o, o2i = _reshape_dim_map(in_aval.shape, out_aval.shape)
    if direction == "fwd" and s_in is not None:
        dim_map = [None] * out_aval.ndim
        for i, j in i2o.items():
            # divisibility check for merge case
            n = s_in.num_shards(i)
            if out_aval.shape[j] % max(n, 1) == 0:
                dim_map[j] = i
        return in_sh, [_project(s_in, dim_map, out_aval.ndim)]
    if direction == "bwd" and s_out is not None:
        dim_map = [None] * in_aval.ndim
        for j, i in o2i.items():
            n = s_out.num_shards(j)
            if in_aval.shape[i] % max(n, 1) == 0:
                dim_map[i] = j
        return [_project(s_out, dim_map, in_aval.ndim)], out_sh
    return in_sh, out_sh


def rule_same_rank_passthrough(eqn, in_sh, out_sh, direction):
    """pad, slice, dynamic-slice/update, rev, concatenate, reduce-window-free
    formatting ops: dims keep identity; partitioner does the data movement
    (halo exchange, §4.3)."""
    rank = eqn.outvars[0].aval.ndim
    cands = [
        s
        for v, s in zip(list(eqn.invars) + list(eqn.outvars), in_sh + out_sh)
        if s is not None and getattr(v.aval, "ndim", None) == rank
    ]
    m = _merge_many(cands)
    if m is None:
        return in_sh, out_sh
    new_in = [
        m if getattr(v.aval, "ndim", None) == rank else s
        for v, s in zip(eqn.invars, in_sh)
    ]
    return new_in, [m for _ in out_sh]


def rule_reduce(eqn, in_sh, out_sh, direction):
    axes = eqn.params.get("axes", ())
    in_aval = eqn.invars[0].aval
    out_rank = eqn.outvars[0].aval.ndim
    kept = [i for i in range(in_aval.ndim) if i not in axes]
    (s_in,) = in_sh[:1]
    (s_out,) = out_sh[:1]
    if direction == "fwd" and s_in is not None:
        return in_sh, [_project(s_in, kept, out_rank)]
    if direction == "bwd" and s_out is not None:
        dim_map = [None] * in_aval.ndim
        for j, i in enumerate(kept):
            dim_map[i] = j
        new_in = list(in_sh)
        new_in[0] = _project(s_out, dim_map, in_aval.ndim)
        return new_in, out_sh
    return in_sh, out_sh


def rule_argminmax(eqn, in_sh, out_sh, direction):
    return rule_reduce(eqn, in_sh, out_sh, direction)


# ---------------------------------------------------------------------------------
# dot_general — the Einsum of §3.2 / Figure 3
# ---------------------------------------------------------------------------------


def rule_dot_general(eqn, in_sh, out_sh, direction):
    ((lc, rc), (lb, rb)) = eqn.params["dimension_numbers"]
    l_aval, r_aval = eqn.invars[0].aval, eqn.invars[1].aval
    out_rank = eqn.outvars[0].aval.ndim
    l_sh, r_sh = in_sh
    (s_out,) = out_sh
    l_nc = [i for i in range(l_aval.ndim) if i not in lc and i not in lb]
    r_nc = [i for i in range(r_aval.ndim) if i not in rc and i not in rb]
    # output layout: batch dims, then lhs non-contracting, then rhs non-contracting
    if direction == "fwd" and (l_sh is not None or r_sh is not None):
        proposals = []
        if l_sh is not None:
            dim_map = [None] * out_rank
            for j, i in enumerate(lb):
                dim_map[j] = i
            for k, i in enumerate(l_nc):
                dim_map[len(lb) + k] = i
            proposals.append(_project(l_sh, dim_map, out_rank))
        if r_sh is not None:
            dim_map = [None] * out_rank
            for j, i in enumerate(rb):
                dim_map[j] = i
            for k, i in enumerate(r_nc):
                dim_map[len(rb) + len(l_nc) + k] = i
            proposals.append(_project(r_sh, dim_map, out_rank))
        m = _merge_many(proposals)  # Figure 3: merged from both inputs
        if m is not None:
            return in_sh, [m]
        return in_sh, out_sh
    if direction == "bwd" and s_out is not None:
        new_l, new_r = l_sh, r_sh
        dim_map = [None] * l_aval.ndim
        for j, i in enumerate(lb):
            dim_map[i] = j
        for k, i in enumerate(l_nc):
            dim_map[i] = len(lb) + k
        cand = _project(s_out, dim_map, l_aval.ndim)
        new_l = cand if new_l is None else (merge_shardings(new_l, cand) or new_l)
        dim_map = [None] * r_aval.ndim
        for j, i in enumerate(rb):
            dim_map[i] = j
        for k, i in enumerate(r_nc):
            dim_map[i] = len(rb) + len(l_nc) + k
        cand = _project(s_out, dim_map, r_aval.ndim)
        new_r = cand if new_r is None else (merge_shardings(new_r, cand) or new_r)
        return [new_l, new_r], out_sh
    return in_sh, out_sh


def rule_conv(eqn, in_sh, out_sh, direction):
    dn = eqn.params["dimension_numbers"]
    lhs_spec, rhs_spec, out_spec = dn.lhs_spec, dn.rhs_spec, dn.out_spec
    # lhs_spec = (batch, feature, *spatial)
    out_rank = eqn.outvars[0].aval.ndim
    (l_sh, r_sh) = in_sh
    (s_out,) = out_sh
    if direction == "fwd" and l_sh is not None:
        dim_map = [None] * out_rank
        dim_map[out_spec[0]] = lhs_spec[0]  # batch
        for k in range(len(lhs_spec) - 2):  # spatial dims pass through (halo)
            dim_map[out_spec[2 + k]] = lhs_spec[2 + k]
        return in_sh, [_project(l_sh, dim_map, out_rank)]
    if direction == "bwd" and s_out is not None:
        l_rank = eqn.invars[0].aval.ndim
        dim_map = [None] * l_rank
        dim_map[lhs_spec[0]] = out_spec[0]
        for k in range(l_rank - 2):
            dim_map[lhs_spec[2 + k]] = out_spec[2 + k]
        cand = _project(s_out, dim_map, l_rank)
        new_l = cand if l_sh is None else (merge_shardings(l_sh, cand) or l_sh)
        return [new_l, r_sh], out_sh
    return in_sh, out_sh


def rule_stage_shift(eqn, in_sh, out_sh, direction):
    """§3.3 shifting buffer: the shift permutes data *along* the stage dim, so
    every dim's sharding passes straight through (the stage dim's included —
    each slot moves globally, landing on the neighbor shard via ppermute at
    partition time).  The injected row ``x`` (rank-1 lower) aligns with the
    state's trailing dims."""
    from .sharding import Sharding

    s_state, s_x = in_sh
    (s_out,) = out_sh
    cands = [s for s in (s_state, s_out) if s is not None]
    if s_x is not None:
        # lift the injected row to state rank with an unsharded stage dim;
        # merge fails (None) when x reuses the stage axis — leave it alone
        cands.append(Sharding(s_x.mesh, ((),) + s_x.dims_mapping))
    m = _merge_many(cands)
    if m is None:
        return in_sh, out_sh
    x_new = Sharding(m.mesh, m.dims_mapping[1:])
    return [m, x_new], [m]


# ---------------------------------------------------------------------------------
# gather / scatter-add — row lookups (the partitioner's rule: core/plan.py)
# ---------------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class IndexDims:
    """The dims of one gather or scatter-add, by role.  ``result`` is the
    gather's output or the scatter's updates; every index dim but the last
    (the index vector) is a batching or a batch dim."""

    indexed: Tuple[int, ...]  # operand dim of each index-vector component
    batching: Tuple[Tuple[int, int, int], ...]  # (operand, indices, result)
    window: Tuple[Tuple[int, int], ...]  # (operand, result): passes through
    batch: Tuple[Tuple[int, int], ...]  # (indices, result)

    @property
    def rows(self) -> Tuple[Tuple[int, int], ...]:
        """(indices, result) of every index dim but the index vector."""
        return self.batch + tuple((i, j) for _, i, j in self.batching)


def index_dims(eqn) -> Optional[IndexDims]:
    """Roles of a gather's or scatter-add's dims, or None where it is not a
    lookup of whole rows (an indexed dim kept in the window, a window
    narrower than its operand dim, ``ONE_HOT`` mode): that keeps the
    fallback."""
    modes = (lax.GatherScatterMode.CLIP, lax.GatherScatterMode.FILL_OR_DROP,
             lax.GatherScatterMode.PROMISE_IN_BOUNDS)
    p = eqn.params
    if p["mode"] not in modes:
        return None
    dn = p["dimension_numbers"]
    operand, indices = eqn.invars[0].aval, eqn.invars[1].aval
    if eqn.primitive.name == "gather":
        result = eqn.outvars[0].aval
        win, collapsed, index_map = (dn.offset_dims, dn.collapsed_slice_dims,
                                     dn.start_index_map)
        op_batch, idx_batch = (dn.operand_batching_dims,
                               dn.start_indices_batching_dims)
    else:
        result = eqn.invars[2].aval
        win, collapsed, index_map = (dn.update_window_dims,
                                     dn.inserted_window_dims,
                                     dn.scatter_dims_to_operand_dims)
        op_batch, idx_batch = (dn.operand_batching_dims,
                               dn.scatter_indices_batching_dims)
    if (sorted(collapsed) != sorted(index_map)
            or indices.shape[-1] != len(index_map)):
        return None
    win_ops = [d for d in range(operand.ndim)
               if d not in collapsed and d not in op_batch]
    rest = [j for j in range(result.ndim) if j not in win]
    if (len(win_ops) != len(win) or len(rest) != indices.ndim - 1
            or any(result.shape[j] != operand.shape[d]
                   for d, j in zip(win_ops, win))):
        return None
    return IndexDims(
        indexed=tuple(index_map),
        batching=tuple((d, i, rest[i]) for d, i in zip(op_batch, idx_batch)),
        window=tuple(zip(win_ops, win)),
        batch=tuple((i, rest[i]) for i in range(indices.ndim - 1)
                    if i not in idx_batch),
    )


def rule_gather(eqn, in_sh, out_sh, direction):
    """Window dims follow the operand's, batching and batch dims the
    indices' (batching dims the operand's too); the indexed dim has no
    output dim.  Nothing flows back into the operand: the lookup adapts to
    its table's layout, which the table's other users decide."""
    dims = index_dims(eqn)
    if dims is None:
        return in_sh, out_sh
    (s_op, s_idx), (s_out,) = in_sh[:2], out_sh
    rank = eqn.outvars[0].aval.ndim
    props = []
    if s_op is not None:
        dm = [None] * rank
        for d, j in dims.window:
            dm[j] = d
        for d, _, j in dims.batching:
            dm[j] = d
        props.append(_project(s_op, dm, rank))
    if s_idx is not None:
        dm = [None] * rank
        for i, j in dims.rows:
            dm[j] = i
        props.append(_project(s_idx, dm, rank))
    new_out = _merge_many([s_out] + props)
    new_idx = s_idx
    if s_out is not None:
        irank = eqn.invars[1].aval.ndim
        dm = [None] * irank
        for i, j in dims.rows:
            dm[i] = j
        new_idx = _merge_many([s_idx, _project(s_out, dm, irank)])
    return [s_op, new_idx] + list(in_sh[2:]), [new_out]


def rule_scatter_add(eqn, in_sh, out_sh, direction):
    """The operand and the output share one layout; the updates' window and
    batching dims take it, and the updates' batch dims and the indices
    share theirs.  Nothing flows from the updates into the operand."""
    dims = index_dims(eqn)
    if dims is None:
        return in_sh, out_sh
    (s_op, s_idx, s_upd), (s_out,) = in_sh[:3], out_sh
    table = _merge_many([s_op, s_out])
    irank = eqn.invars[1].aval.ndim
    urank = eqn.invars[2].aval.ndim
    to_upd = [None] * urank  # update dim -> indices dim
    to_idx = [None] * irank  # indices dim -> update dim
    for i, j in dims.rows:
        to_upd[j], to_idx[i] = i, j
    upd_props, idx_props = [s_upd], [s_idx]
    if table is not None:
        dm = [None] * urank
        for d, j in dims.window:
            dm[j] = d
        for d, _, j in dims.batching:
            dm[j] = d
        upd_props.append(_project(table, dm, urank))
        dm = [None] * irank
        for d, i, _ in dims.batching:
            dm[i] = d
        idx_props.append(_project(table, dm, irank))
    if s_idx is not None:
        upd_props.append(_project(s_idx, to_upd, urank))
    if s_upd is not None:
        idx_props.append(_project(s_upd, to_idx, irank))
    return ([table, _merge_many(idx_props), _merge_many(upd_props)]
            + list(in_sh[3:]), [table])


# ---------------------------------------------------------------------------------
# registry + priorities
# ---------------------------------------------------------------------------------

SAME_RANK = {
    "pad", "rev", "concatenate", "dynamic_slice", "dynamic_update_slice",
    "slice", "sort", "cumsum", "cumprod", "cummax", "cummin", "cumlogsumexp",
}

RULES = {}
PRIORITY = {}

for name in ELEMENTWISE:
    RULES[name] = rule_elementwise
    PRIORITY[name] = 0
for name in SAME_RANK:
    RULES[name] = rule_same_rank_passthrough
    PRIORITY[name] = 1

RULES["transpose"] = rule_transpose
PRIORITY["transpose"] = 1
RULES["broadcast_in_dim"] = rule_broadcast_in_dim
PRIORITY["broadcast_in_dim"] = 0  # paper: backward through Broadcast is high prio
RULES["reshape"] = rule_reshape
PRIORITY["reshape"] = 1
RULES["reduce_sum"] = rule_reduce
RULES["reduce_max"] = rule_reduce
RULES["reduce_min"] = rule_reduce
RULES["reduce_prod"] = rule_reduce
RULES["reduce_and"] = rule_reduce
RULES["reduce_or"] = rule_reduce
RULES["argmax"] = rule_argminmax
RULES["argmin"] = rule_argminmax
for n in ("reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "reduce_and",
          "reduce_or", "argmax", "argmin"):
    PRIORITY[n] = 2
RULES["stage_shift"] = rule_stage_shift
PRIORITY["stage_shift"] = 1
RULES["dot_general"] = rule_dot_general
PRIORITY["dot_general"] = 2
RULES["conv_general_dilated"] = rule_conv
PRIORITY["conv_general_dilated"] = 2
RULES["gather"] = rule_gather
PRIORITY["gather"] = 2
RULES["scatter-add"] = rule_scatter_add
PRIORITY["scatter-add"] = 2

MAX_PRIORITY = 3
