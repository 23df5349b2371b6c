"""Flash attention on the TPU, forward and backward.

A thin entry over the Pallas kernels JAX ships
(``jax.experimental.pallas.ops.tpu.flash_attention``): a forward kernel and
dK/dV and dQ kernels, bf16 operands with f32 accumulation on the MXU, and
causal block skipping, so the (S, T) scores live in VMEM one block at a
time and never reach HBM.  What this module adds is the choice of block
sizes, made from the shape alone; GQA: k and v are repeated to the q heads
before the call, and the transpose of that repeat sums their gradients over
each group in f32 (the kernel has rounded each head's dk and dv to bf16 by
then, one rounding more than an f32 group sum inside the kernel would
make); and its own ``custom_vjp`` around the shipped rules, so that a remat
policy can see the forward's residuals.

Residuals.  The forward keeps its output ``o`` (bf16, B·H·S·D) and each
row's softmax sum ``l`` and max ``m`` (f32, B·H·S) for the backward kernels,
tagged with ``checkpoint_name`` under ``RESIDUAL_NAMES``.  A layer under
``jax.checkpoint`` whose policy saves those names (``models/layers.py``
``remat_policy("dots")``) keeps them, B·H·S·(2·D + 8) bytes per layer, and
its backward runs only the dK/dV and dQ kernels; a policy that does not
runs the forward kernel a second time to rebuild them.  At Qwen1.5-0.5B's
16 heads of 64 that is 17.8 MB per layer at batch 1 by 8,192 (428 MB over
24 layers) and 214 MB over 24 layers at batch 4 by 1,024.

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

import contextlib
import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu import flash_attention as _tpu_flash

MIN_BLOCK = _tpu_flash.MIN_BLOCK_SIZE  # 128
SEQ_BLOCK = 512
# what the forward keeps for the backward, under these names for a remat
# policy: the output, and each row's softmax sum and max
RESIDUAL_NAMES = ("flash_o", "flash_l", "flash_m")


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
    return _flash(q, k, v, causal, 1.0 / math.sqrt(D),
                  blocks or block_sizes(S, T), interpret)


def _mode(interpret: bool):
    """The Pallas TPU interpreter, switched on where the kernels are traced:
    the backward kernels are traced when the gradient is, outside the
    forward's call, so every rule enters it."""
    return (pltpu.force_tpu_interpret_mode() if interpret
            else contextlib.nullcontext())


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, sm_scale, blocks, interpret):
    with _mode(interpret):
        return _tpu_flash._flash_attention(
            q, k, v, None, None, False, causal, sm_scale, blocks, False)


def _flash_fwd(q, k, v, causal, sm_scale, blocks, interpret):
    with _mode(interpret):
        o, (*_, l, m) = _tpu_flash._flash_attention_fwd(
            q, k, v, None, None, False, causal, sm_scale, blocks, False)
    o, l, m = (checkpoint_name(x, n) for x, n in zip((o, l, m),
                                                      RESIDUAL_NAMES))
    return o, (q, k, v, o, l, m)


def _flash_bwd(causal, sm_scale, blocks, interpret, res, do):
    q, k, v, o, l, m = res
    with _mode(interpret):
        dq, dk, dv, _, _ = _tpu_flash._flash_attention_bwd(
            False, causal, sm_scale, blocks, False,
            (q, k, v, None, None, o, l, m), do)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)
