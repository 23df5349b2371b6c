"""Mamba2 SSD (state space duality) Pallas TPU kernel.

Grid (B, H, nc) with the chunk dim innermost/sequential: the inter-chunk SSM
state (head_dim × d_state, f32) is carried in VMEM scratch across grid steps,
while each chunk's quadratic intra-chunk part runs on the MXU:

    G     = C · Bᵀ                        (Q × Q)
    W     = tril(exp(l_t − l_s)) ⊙ G ⊙ dt (Q × Q)
    y     = W · x  +  exp(l) ⊙ (C · Sᵀ)   (Q × hd)
    S_new = exp(l_Q) S + (decay ⊙ dt ⊙ x)ᵀ · B

Layout: the wrapper hands the kernel head-major ``x`` (B,H,S,hd) and ``dt``
(B,H,1,S), so every block's last two dims are (chunk, full dim) — the TPU
tiling rule (divisible by 8 × 128, or equal to the array dim).  ``dt`` arrives
as a (1, Q) row; its column form and both cumulative sums are masked (Q × Q)
reductions, which keeps the kernel free of 1-D vectors and transposes.  ``A``
is read per head as a scalar from SMEM.

Block sizes: chunk Q=128 (lane aligned; a multiple of 128 on the chip),
head_dim 64, d_state 128 — the working set (x,B,C blocks + a few QxQ f32 +
state 64×128 f32) is under 1 MB, well inside VMEM.  The pure-jnp oracle is
models/ssm.ssd_scan_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, y_ref, state_ref, *, Q: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)            # (Q, hd)
    dt = dt_ref[0, 0].astype(jnp.float32)          # (1, Q)
    B = b_ref[0].astype(jnp.float32)               # (Q, ds)
    C = c_ref[0].astype(jnp.float32)               # (Q, ds)
    A = a_ref[pl.program_id(1)]                    # scalar (negative)

    rows = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    causal = rows >= cols
    dt_col = jnp.sum(jnp.where(rows == cols, dt, 0.0), axis=1, keepdims=True)
    loga_row, loga_col = dt * A, dt_col * A        # (1, Q), (Q, 1)
    # inclusive cumsum of log-decay, as a column (l_t) and as a row (l_s)
    l_col = jnp.sum(jnp.where(causal, loga_row, 0.0), axis=1, keepdims=True)
    l_row = jnp.sum(jnp.where(rows <= cols, loga_col, 0.0), axis=0,
                    keepdims=True)
    l_end = jnp.sum(loga_row, axis=1, keepdims=True)  # (1, 1)

    # intra-chunk quadratic part
    G = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (Q,Q)
    W = jnp.where(causal, jnp.exp(l_col - l_row), 0.0) * G * dt
    y_intra = jax.lax.dot(W, x, preferred_element_type=jnp.float32)  # (Q,hd)

    # inter-chunk contribution from the carried state
    s_prev = state_ref[...]                          # (hd, ds)
    y_inter = jax.lax.dot_general(
        C, s_prev, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * jnp.exp(l_col)                               # (Q, hd)

    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: S = exp(l_Q) S + sum_s exp(l_Q - l_s) dt_s x_s (x) B_s
    decay_end = jnp.exp(l_end - l_col) * dt_col      # (Q, 1)
    upd = jax.lax.dot_general(
        x * decay_end, B, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                 # (hd, ds)
    state_ref[...] = jnp.exp(l_end) * s_prev + upd


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, B, C, A, *, chunk: int = 128, interpret: bool = False):
    """x: (Bb,S,H,hd); dt: (Bb,S,H); B,C: (Bb,S,ds); A: (H,) negative.

    Returns y (Bb,S,H,hd).  S % chunk == 0 required (§4.1: callers pad).
    ``interpret=True`` runs the Pallas interpreter (the CPU path).
    """
    Bb, S, H, hd = x.shape
    ds = B.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0
    nc = S // Q
    grid = (Bb, H, nc)
    xh = jnp.transpose(x, (0, 2, 1, 3))                   # (Bb,H,S,hd)
    dth = jnp.transpose(dt, (0, 2, 1))[:, :, None, :]     # (Bb,H,1,S)

    yh = pl.pallas_call(
        functools.partial(_ssd_kernel, Q=Q),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, Q, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, 1, Q), lambda b, h, c: (b, h, 0, c)),
            pl.BlockSpec((1, Q, ds), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, Q, ds), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, Q, hd), lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct(xh.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((hd, ds), jnp.float32)],
        interpret=interpret,
    )(xh, dth, B, C, A.astype(jnp.float32))
    return jnp.transpose(yh, (0, 2, 1, 3))
