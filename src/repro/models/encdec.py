"""Whisper-style encoder–decoder backbone (audio frontend stubbed).

Per the assignment, the conv frontend is a STUB: ``input_specs()`` provides
precomputed frame embeddings (B, S_enc, M).  The backbone is the real model:
bidirectional encoder, causal decoder with cross-attention, tied text embedding.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, Strategy
from . import attention as attn
from .layers import (
    Params, embed_lookup, embed_params, mlp_forward, mlp_params, pspec,
    rms_norm, scan_or_loop, softmax_xent, stack_layers, stacked,
    unembed_logits,
)


def enc_layer_tree(cfg, st):
    return {
        "ln1": pspec((cfg.d_model,), st.w("embed_vec"), init="ones"),
        "attn": attn.attn_params(cfg, st),
        "ln2": pspec((cfg.d_model,), st.w("embed_vec"), init="ones"),
        "mlp": mlp_params(cfg, st),
    }


def dec_layer_tree(cfg, st):
    return {
        "ln1": pspec((cfg.d_model,), st.w("embed_vec"), init="ones"),
        "attn": attn.attn_params(cfg, st),
        "lnx": pspec((cfg.d_model,), st.w("embed_vec"), init="ones"),
        "xattn": attn.attn_params(cfg, st),
        "ln2": pspec((cfg.d_model,), st.w("embed_vec"), init="ones"),
        "mlp": mlp_params(cfg, st),
    }


def param_tree(cfg: ModelConfig, st: Strategy):
    enc_layers = cfg.encoder_layers or cfg.num_layers
    return {
        "embed": embed_params(cfg, st),
        "enc_layers": stacked(enc_layer_tree(cfg, st), enc_layers),
        "enc_ln": pspec((cfg.d_model,), st.w("embed_vec"), init="ones"),
        "dec_layers": stacked(dec_layer_tree(cfg, st), cfg.num_layers),
        "final_ln": pspec((cfg.d_model,), st.w("embed_vec"), init="ones"),
    }


def encode(cfg: ModelConfig, st: Strategy, params: Params, frames):
    """frames: precomputed embeddings (B, S_enc, M) — frontend stub output."""
    x = st.constrain(frames.astype(jnp.dtype(cfg.dtype)), "batch", "seq", "embed")

    def layer_fn(lp, x, _):
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        h = attn.self_attention(cfg, st, lp["attn"], h, None, causal=False)
        x = st.constrain(x + h, "batch", "seq", "embed")
        h = rms_norm(x, lp["ln2"], cfg.norm_eps)
        return st.constrain(x + mlp_forward(cfg, st, lp["mlp"], h), "batch", "seq", "embed")

    x = stack_layers(layer_fn, params["enc_layers"], x, cfg)
    return rms_norm(x, params["enc_ln"], cfg.norm_eps)


def decode_train(cfg: ModelConfig, st: Strategy, params: Params, tokens, enc_out):
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    x = embed_lookup(cfg, st, params["embed"], tokens)

    def layer_fn(lp, x, _):
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        h = attn.self_attention(cfg, st, lp["attn"], h, positions, causal=True)
        x = st.constrain(x + h, "batch", "seq", "embed")
        h = rms_norm(x, lp["lnx"], cfg.norm_eps)
        ek, ev = attn.encode_kv(cfg, st, lp["xattn"], enc_out)
        h = attn.cross_attention(cfg, st, lp["xattn"], h, ek, ev)
        x = st.constrain(x + h, "batch", "seq", "embed")
        h = rms_norm(x, lp["ln2"], cfg.norm_eps)
        return st.constrain(x + mlp_forward(cfg, st, lp["mlp"], h), "batch", "seq", "embed")

    x = stack_layers(layer_fn, params["dec_layers"], x, cfg)
    x = rms_norm(x, params["final_ln"], cfg.norm_eps)
    return unembed_logits(cfg, st, params["embed"], x)


def loss_fn(cfg: ModelConfig, st: Strategy, params: Params, batch):
    enc_out = encode(cfg, st, params, batch["frames"])
    logits = decode_train(cfg, st, params, batch["tokens"], enc_out)
    return softmax_xent(cfg, st, logits, batch["labels"])


def cache_shapes(cfg: ModelConfig, st: Strategy, batch: int, max_len: int, enc_len: int):
    K, G, r, Gp, KR = attn.head_layout(cfg, st)
    L = cfg.num_layers
    return {
        "k": (L, batch, max_len, KR, cfg.dh),
        "v": (L, batch, max_len, KR, cfg.dh),
        "ek": (L, batch, enc_len, KR, cfg.dh),
        "ev": (L, batch, enc_len, KR, cfg.dh),
    }


def decode_step(cfg: ModelConfig, st: Strategy, params: Params, token, cache, pos):
    """One decoder token; cross-kv precomputed in the cache (``ek``/``ev``)."""
    x = embed_lookup(cfg, st, params["embed"], token)

    def body(x, inp):
        lp, ck, cv, ek, ev = inp
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        h, ck, cv = attn.decode_attention(cfg, st, lp["attn"], h, ck, cv, pos)
        x = x + h
        h = rms_norm(x, lp["lnx"], cfg.norm_eps)
        h = attn.cross_attention(cfg, st, lp["xattn"], h, ek, ev)
        x = x + h
        h = rms_norm(x, lp["ln2"], cfg.norm_eps)
        x = x + mlp_forward(cfg, st, lp["mlp"], h)
        return x, (ck, cv)

    x, (ck, cv) = scan_or_loop(
        body, x,
        (params["dec_layers"], cache["k"], cache["v"], cache["ek"], cache["ev"]),
        cfg,
    )
    x = rms_norm(x, params["final_ln"], cfg.norm_eps)
    logits = unembed_logits(cfg, st, params["embed"], x)
    return logits, {"k": ck, "v": cv, "ek": cache["ek"], "ev": cache["ev"]}
