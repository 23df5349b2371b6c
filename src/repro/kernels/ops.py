"""Jit'd dispatch wrappers for the Pallas kernels.

On the CPU backend the kernels run in the Pallas interpreter (validation
only); on every other backend they compile to Mosaic, so a kernel that cannot
compile fails instead of silently interpreting.  ``attention`` also
adapts the model's padded (B,S,KR,Gl,D) layout to the kernel's (B,H,S,D).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention
from .ssd_scan import ssd_scan


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def attention(q, k, v, *, causal: bool = True, block_q: int = 128, block_k: int = 128):
    """q (B,Hq,S,D), k/v (B,Hkv,T,D) -> (B,Hq,S,D), auto GQA group mapping."""
    group = q.shape[1] // k.shape[1]
    return flash_attention(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        group_size=group, interpret=_interpret(),
    )


def attention_model_layout(q, k, v, *, causal: bool = True, block_q=128, block_k=128):
    """Adapter for the model's padded layout: q (B,S,KR,Gl,D), kv (B,T,KR,D)."""
    B, S, KR, Gl, D = q.shape
    T = k.shape[1]
    qk = jnp.transpose(q, (0, 2, 3, 1, 4)).reshape(B, KR * Gl, S, D)
    kk = jnp.transpose(k, (0, 2, 1, 3))
    vk = jnp.transpose(v, (0, 2, 1, 3))
    out = attention(qk, kk, vk, causal=causal, block_q=block_q, block_k=block_k)
    return jnp.transpose(out.reshape(B, KR, Gl, S, D), (0, 3, 1, 2, 4))


def ssd(x, dt, B, C, A, *, chunk: int = 128):
    return ssd_scan(x, dt, B, C, A, chunk=chunk, interpret=_interpret())
