"""Jit'd dispatch wrappers for the Pallas kernels.

``attention_model_layout`` is the flash kernel's entry from the models:
``models/attention.py`` ``self_attention`` calls it for full-sequence
self-attention on the TPU when no mesh axis splits attention's batch,
sequence or heads (the conditions are listed there), and it adapts the
model's padded (B,S,KR,Gl,D) layout to the kernel's (B,H,S,D).  Every other
attention path runs the XLA chunked loop of ``models/attention.py``.

On the CPU backend the kernels run in the Pallas TPU interpreter (validation
only); on every other backend they compile to Mosaic, so a kernel that cannot
compile fails instead of silently interpreting.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention
from .ssd_scan import ssd_scan


def on_tpu() -> bool:
    """Whether the default backend is a TPU (the models route on it)."""
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def attention(q, k, v, *, causal: bool = True, blocks=None):
    """q (B,Hq,S,D), k/v (B,Hkv,T,D) -> (B,Hq,S,D); GQA by head groups."""
    return flash_attention(q, k, v, causal=causal, interpret=_interpret(),
                           blocks=blocks)


def attention_model_layout(q, k, v, *, causal: bool = True):
    """Adapter for the model's padded layout: q (B,S,KR,Gl,D), kv (B,T,KR,D)."""
    B, S, KR, Gl, D = q.shape
    qk = jnp.transpose(q, (0, 2, 3, 1, 4)).reshape(B, KR * Gl, S, D)
    kk = jnp.transpose(k, (0, 2, 1, 3))
    vk = jnp.transpose(v, (0, 2, 1, 3))
    out = attention(qk, kk, vk, causal=causal)
    return jnp.transpose(out.reshape(B, KR, Gl, S, D), (0, 3, 1, 2, 4))


def ssd(x, dt, B, C, A, *, chunk: int = 128):
    return ssd_scan(x, dt, B, C, A, chunk=chunk, interpret=_interpret())
