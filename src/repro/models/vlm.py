"""InternVL2-style VLM: stubbed vision frontend + InternLM2 LM backbone.

Per the assignment the ViT frontend is a STUB — ``input_specs()`` provides
precomputed patch embeddings (B, P, M) which are prepended to the text embedding
sequence.  Training computes loss on text positions only; decode is the plain LM
decode over a cache whose prefix was prefilled with the patch embeddings.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, Strategy
from . import transformer
from .layers import (
    Params, embed_lookup, rms_norm, softmax_xent, stack_layers, unembed_logits,
)


def param_tree(cfg: ModelConfig, st: Strategy):
    return transformer.param_tree(cfg, st)


def forward(cfg: ModelConfig, st: Strategy, params: Params, tokens, patches):
    """tokens (B,S_text), patches (B,P,M) -> logits over text positions."""
    B, S = tokens.shape
    P = patches.shape[1]
    x_txt = embed_lookup(cfg, st, params["embed"], tokens)
    x = jnp.concatenate([patches.astype(x_txt.dtype), x_txt], axis=1)
    x = st.constrain(x, "batch", "seq", "embed")
    positions = jnp.broadcast_to(jnp.arange(P + S), (B, P + S))

    def layer_fn(lp, carry, extra):
        x, aux = carry
        x, a = transformer.decoder_layer(cfg, st, lp, x, extra)
        return x, aux + a

    (x, aux) = stack_layers(
        layer_fn, params["layers"], (x, jnp.zeros((), jnp.float32)), cfg,
        extra=positions,
    )
    x = rms_norm(x, params["final_ln"], cfg.norm_eps)
    logits = unembed_logits(cfg, st, params["embed"], x[:, P:])
    return logits, aux


def loss_fn(cfg: ModelConfig, st: Strategy, params: Params, batch, aux_coef=0.01):
    logits, aux = forward(
        cfg, st, params, batch["tokens"], batch["patches"]
    )
    return softmax_xent(cfg, st, logits, batch["labels"]) + aux_coef * aux


decode_step = transformer.decode_step  # decode is identical to the LM backbone
