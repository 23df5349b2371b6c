"""Flash attention on the TPU, forward and backward.

A thin entry over the Pallas kernels JAX ships
(``jax.experimental.pallas.ops.tpu.flash_attention``): a forward kernel and
dK/dV and dQ kernels under one ``custom_vjp``, bf16 operands with f32
accumulation on the MXU, and causal block skipping, so the (S, T) scores
live in VMEM one block at a time and never reach HBM.  What this module adds
is the choice of block sizes, made from the shape alone, and GQA: k and v
are repeated to the q heads before the call, and the transpose of that
repeat sums their gradients over each group in f32.  The kernel has
rounded each head's dk and dv to bf16 by then, one rounding more than an
f32 group sum inside the kernel would make.

Block sizes.  Every sequence block is the largest multiple of 128 that
divides the length and is at most ``SEQ_BLOCK``; 128 is the kernels'
minimum.  ``SEQ_BLOCK`` comes from a sweep of the kernel alone on a TPU v5e
(forward + backward at B 4, H 16, S 1,024, D 64 in bf16; the table is in
PERF.md): the shipped default, 128 on every axis, took 2.9x as long as
512-blocks and was slower than XLA's chunked loop; 1,024-blocks lose the
causal skipping inside the sequence.  More than one batch row per forward
grid step (``block_b``) gained nothing there, so it stays 1.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu import flash_attention as _tpu_flash

MIN_BLOCK = _tpu_flash.MIN_BLOCK_SIZE  # 128
SEQ_BLOCK = 512


def _seq_block(n: int) -> int:
    """Largest multiple of 128 that divides ``n`` and is at most
    ``SEQ_BLOCK``."""
    if n % MIN_BLOCK:
        raise ValueError(f"flash_attention: length {n} is not a multiple "
                         f"of {MIN_BLOCK}")
    return max(b for b in range(MIN_BLOCK, min(n, SEQ_BLOCK) + 1, MIN_BLOCK)
               if n % b == 0)


def block_sizes(q_len: int, kv_len: int) -> _tpu_flash.BlockSizes:
    """The kernels' blocks for this shape (forward, dK/dV and dQ)."""
    bq, bk = _seq_block(q_len), _seq_block(kv_len)
    return _tpu_flash.BlockSizes(
        block_q=bq, block_k_major=bk, block_k=bk, block_b=1,
        block_q_major_dkv=bq, block_k_major_dkv=bk, block_k_dkv=bk,
        block_q_dkv=bq,
        block_k_major_dq=bk, block_k_dq=bk, block_q_dq=bq,
    )


@functools.partial(jax.jit, static_argnames=("causal", "interpret",
                                             "blocks"))
def flash_attention(q, k, v, *, causal: bool = True, interpret: bool = False,
                    blocks: _tpu_flash.BlockSizes | None = None):
    """q: (B, Hq, S, D); k, v: (B, Hkv, T, D) with Hq a multiple of Hkv.

    Returns (B, Hq, S, D) in q's dtype; differentiable.  S and T are
    multiples of 128; D is below 128 or a multiple of it.  ``interpret=True``
    runs the kernels in the Pallas TPU interpreter (the CPU path);
    ``blocks`` overrides :func:`block_sizes` (for sweeps and tests).
    """
    _, Hq, S, D = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    if Hq % Hkv:
        raise ValueError(f"flash_attention: {Hq} q heads over {Hkv} kv heads")
    if Hq != Hkv:
        # repeated through f32, so that the gradient's sum over each
        # group runs in f32
        k, v = (jnp.repeat(x.astype(jnp.float32), Hq // Hkv, axis=1)
                .astype(x.dtype) for x in (k, v))
    call = functools.partial(
        _tpu_flash.flash_attention, causal=causal, sm_scale=1.0 / math.sqrt(D),
        block_sizes=blocks or block_sizes(S, T))
    return (_interpreted(call) if interpret else call)(q, k, v)


def _interpreted(fn):
    """``fn`` run, and differentiated, in the Pallas TPU interpreter: the
    backward kernels are traced when the gradient is, outside the forward's
    call, so the interpreter is switched on in both rules."""

    @jax.custom_vjp
    def f(*args):
        with pltpu.force_tpu_interpret_mode():
            return fn(*args)

    def fwd(*args):
        with pltpu.force_tpu_interpret_mode():
            return jax.vjp(fn, *args)

    def bwd(vjp, g):
        with pltpu.force_tpu_interpret_mode():
            return vjp(g)

    f.defvjp(fwd, bwd)
    return f
