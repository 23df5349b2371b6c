"""Flash attention Pallas TPU kernel.

TPU-native adaptation of the memory-bound attention hot spot: blocked online
softmax with the (batch·heads, q_blocks, kv_blocks) grid — the kv dim is the
innermost (sequential) grid dim, so the m/l/acc accumulators live in VMEM
scratch and the output block is revisited.  Causal block skipping avoids the
2× masked-compute waste of the XLA chunked path.  GQA is native: the kv
BlockSpec index_map maps q-head h to kv-head h // group_size, so kv blocks are
never materialized per-q-head.

Block sizes default to (128, 128): MXU-aligned (128 lanes) and small enough
that q,k,v,acc blocks fit VMEM comfortably:
  q (128, D) + k,v (128, D) + scores (128,128) f32 + acc (128, D) f32
  ≈ 0.25 MB for D=128 — far under the ~16 MB VMEM budget, leaving room for
double buffering of the k/v streams.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, scale: float, causal: bool, block_q: int, block_k: int, nk: int,
):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bk)
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    if causal:
        # skip blocks entirely above the diagonal (saves ~2x compute)
        @pl.when(qi * block_q + block_q - 1 >= kj * block_k)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(kj == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0, 0, ...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "group_size", "interpret"),
)
def flash_attention(
    q, k, v, *, causal: bool = True, block_q: int = 128, block_k: int = 128,
    group_size: int = 1, interpret: bool = False,
):
    """q: (B, Hq, S, D); k, v: (B, Hkv, T, D) with Hq = Hkv * group_size.

    Returns (B, Hq, S, D).  S % block_q == 0 and T % block_k == 0 required
    (callers pad per §4.1).  ``interpret=True`` runs the Pallas interpreter
    (the CPU path).
    """
    B, Hq, S, D = q.shape
    _, Hkv, T, _ = k.shape
    assert Hq == Hkv * group_size, (Hq, Hkv, group_size)
    assert S % block_q == 0 and T % block_k == 0, (S, T, block_q, block_k)
    nq, nk = S // block_q, T // block_k
    scale = 1.0 / math.sqrt(D)

    grid = (B * Hq, nq, nk)

    def q_map(bh, i, j):
        return (bh // Hq, bh % Hq, i, 0)

    def kv_map(bh, i, j):
        return (bh // Hq, (bh % Hq) // group_size, j, 0)

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, nk=nk,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda bh, i, j: q_map(bh, i, j)),
            pl.BlockSpec((1, 1, block_k, D), lambda bh, i, j: kv_map(bh, i, j)),
            pl.BlockSpec((1, 1, block_k, D), lambda bh, i, j: kv_map(bh, i, j)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, D), lambda bh, i, j: (bh // Hq, bh % Hq, i, 0)
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pl_scratch((block_q,), jnp.float32),   # m: running max
            pl_scratch((block_q,), jnp.float32),   # l: running denom
            pl_scratch((block_q, D), jnp.float32), # acc: running numerator
        ],
        interpret=interpret,
    )(
        q.reshape(B, Hq, S, D),
        k,
        v,
    )


def pl_scratch(shape, dtype):
    """A VMEM scratch buffer (the interpreter models VMEM too)."""
    return pltpu.VMEM(shape, dtype)
