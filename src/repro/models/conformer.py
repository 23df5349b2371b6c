"""Conformer-style stage for the pipelining case study (paper §5.3, Table 5).

One stage = conv-augmented transformer layer (attention + depthwise conv module +
MLP).  Used with core/pipeline.py under GPipe and circular schedules; data
parallelism outside the backbone, exactly the paper's configuration.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, Strategy
from . import attention as attn
from .layers import Params, mlp_forward, mlp_params, pspec, rms_norm


def layer_tree(cfg: ModelConfig, st: Strategy, conv_k: int = 9):
    return {
        "ln1": pspec((cfg.d_model,), st.w("embed_vec"), init="ones"),
        "attn": attn.attn_params(cfg, st),
        "lnc": pspec((cfg.d_model,), st.w("embed_vec"), init="ones"),
        "conv_w": pspec((conv_k, cfg.d_model), st.w(None, "embed"), fan_in=conv_k),
        "ln2": pspec((cfg.d_model,), st.w("embed_vec"), init="ones"),
        "mlp": mlp_params(cfg, st),
    }


def _depthwise_conv(x, w):
    """Causal depthwise conv over seq: x (B,S,M), w (K,M)."""
    K = w.shape[0]
    out = jnp.zeros_like(x)
    for k in range(K):
        shift = K - 1 - k
        xs = jnp.pad(x, ((0, 0), (shift, 0), (0, 0)))[:, : x.shape[1]]
        out = out + xs * w[k]
    return out


def stage_forward(cfg: ModelConfig, st: Strategy, lp: Params, x):
    """One conformer layer; used as OneStageCompute in the pipeline wrapper."""
    B, S, M = x.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    h = attn.self_attention(cfg, st, lp["attn"], h, positions, causal=False)
    x = x + h
    h = rms_norm(x, lp["lnc"], cfg.norm_eps)
    h = jax.nn.silu(_depthwise_conv(h, lp["conv_w"].astype(h.dtype)))
    x = x + h
    h = rms_norm(x, lp["ln2"], cfg.norm_eps)
    return x + mlp_forward(cfg, st, lp["mlp"], h)
