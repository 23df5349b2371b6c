"""Pure-SSM LM (mamba2-130m): embed -> L × (norm + SSD block) -> norm -> logits."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, Strategy
from .layers import (
    Params, embed_lookup, embed_params, pspec, rms_norm, scan_or_loop,
    softmax_xent, stack_layers, stacked, unembed_logits,
)
from .ssm import ssm_decode, ssm_forward, ssm_params, ssm_state_shapes


def layer_tree(cfg: ModelConfig, st: Strategy):
    return {
        "ln": pspec((cfg.d_model,), st.w("embed_vec"), init="ones"),
        "mixer": ssm_params(cfg, st),
    }


def param_tree(cfg: ModelConfig, st: Strategy):
    return {
        "embed": embed_params(cfg, st),
        "layers": stacked(layer_tree(cfg, st), cfg.num_layers),
        "final_ln": pspec((cfg.d_model,), st.w("embed_vec"), init="ones"),
    }


def forward(cfg: ModelConfig, st: Strategy, params: Params, tokens):
    x = embed_lookup(cfg, st, params["embed"], tokens)

    def layer_fn(lp, x, _):
        h = rms_norm(x, lp["ln"], cfg.norm_eps)
        return st.constrain(x + ssm_forward(cfg, st, lp["mixer"], h), "batch", "seq", "embed")

    x = stack_layers(layer_fn, params["layers"], x, cfg)
    x = rms_norm(x, params["final_ln"], cfg.norm_eps)
    return unembed_logits(cfg, st, params["embed"], x)


def loss_fn(cfg: ModelConfig, st: Strategy, params: Params, batch):
    logits = forward(cfg, st, params, batch["tokens"])
    return softmax_xent(cfg, st, logits, batch["labels"])


def cache_shapes(cfg: ModelConfig, st: Strategy, batch: int, max_len: int):
    ss = ssm_state_shapes(cfg, st, batch)
    L = cfg.num_layers
    return {"s": (L,) + ss["s"], "conv": (L,) + ss["conv"]}


def decode_step(cfg: ModelConfig, st: Strategy, params: Params, token, cache, pos):
    x = embed_lookup(cfg, st, params["embed"], token)

    def body(x, inp):
        lp, s, conv = inp
        h = rms_norm(x, lp["ln"], cfg.norm_eps)
        h, new = ssm_decode(cfg, st, lp["mixer"], h, {"s": s, "conv": conv})
        return x + h, (new["s"], new["conv"])

    x, (s, conv) = scan_or_loop(
        body, x, (params["layers"], cache["s"], cache["conv"]), cfg
    )
    x = rms_norm(x, params["final_ln"], cfg.norm_eps)
    logits = unembed_logits(cfg, st, params["embed"], x)
    return logits, {"s": s, "conv": conv}
