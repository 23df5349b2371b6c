"""Dense decoder-only Transformer LM (paper §5.1's subject model).

Pure-functional: ``param_tree`` declares shapes+shardings (Table-1 annotations),
``train_step_fn`` / ``serve_step_fn`` build the jittable steps.  Layers run under
``lax.scan`` with remat so compiled HLO size is depth-independent.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, Strategy
from . import attention as attn
from .layers import (
    Params,
    embed_lookup,
    embed_params,
    mlp_forward,
    mlp_params,
    pspec,
    rms_norm,
    softmax_xent,
    stack_layers,
    stacked,
    unembed_logits,
)


def superblock(cfg: ModelConfig) -> int:
    """Scan unit: MoE-every-k archs scan over k-layer superblocks."""
    return cfg.moe_every if (cfg.moe and cfg.moe_every > 1) else 1


def layer_param_tree(cfg: ModelConfig, st: Strategy, use_moe: bool = None):
    from .moe import moe_params

    if use_moe is None:
        use_moe = cfg.moe and cfg.moe_every == 1
    p = {
        "ln1": pspec((cfg.d_model,), st.w("embed_vec"), init="ones"),
        "attn": attn.attn_params(cfg, st),
        "ln2": pspec((cfg.d_model,), st.w("embed_vec"), init="ones"),
    }
    if use_moe:
        p["moe"] = moe_params(cfg, st)
        if cfg.shared_expert:
            p["mlp"] = mlp_params(cfg, st)
    else:
        p["mlp"] = mlp_params(cfg, st)
    return p


def param_tree(cfg: ModelConfig, st: Strategy):
    sb = superblock(cfg)
    if sb == 1:
        layers = stacked(layer_param_tree(cfg, st), cfg.num_layers)
    else:
        assert cfg.num_layers % sb == 0
        block = {
            str(i): layer_param_tree(cfg, st, use_moe=(i == sb - 1))
            for i in range(sb)
        }
        layers = stacked(block, cfg.num_layers // sb)
    return {
        "embed": embed_params(cfg, st),
        "layers": layers,
        "final_ln": pspec((cfg.d_model,), st.w("embed_vec"), init="ones"),
    }


def decoder_layer(cfg: ModelConfig, st: Strategy, lp: Params, x, positions):
    """Returns (x, aux_loss).  The two sublayers run under the scopes
    ``attention`` and ``mlp``, which the program's op names keep (their
    backward under ``transpose(...)``), so a device trace can time each."""
    from .moe import moe_forward

    with jax.named_scope("attention"):
        if cfg.gather_norm_input:
            # §Perf: gather a bf16 COPY of the residual for the layer (instead
            # of XLA gathering the f32 norm input); the carry stays sharded.
            h_src = st.constrain(x, "batch", "seq", None)
        else:
            h_src = x
        h = rms_norm(h_src, lp["ln1"], cfg.norm_eps)
        h = attn.self_attention(cfg, st, lp["attn"], h, positions,
                                causal=cfg.causal)
    x = st.constrain(x + h, "batch", "seq", "embed")
    with jax.named_scope("mlp"):
        h_src = (st.constrain(x, "batch", "seq", None)
                 if cfg.gather_norm_input else x)
        h = rms_norm(h_src, lp["ln2"], cfg.norm_eps)
        aux = jnp.zeros((), jnp.float32)
        if "moe" in lp:
            y, aux = moe_forward(cfg, st, lp["moe"], h)
            if "mlp" in lp:
                y = y + mlp_forward(cfg, st, lp["mlp"], h)
        else:
            y = mlp_forward(cfg, st, lp["mlp"], h)
    return st.constrain(x + y, "batch", "seq", "embed"), aux


def forward(cfg: ModelConfig, st: Strategy, params: Params, tokens):
    """tokens (B,S) -> (logits (B,S,V), aux_loss).  The embedding, the final
    norm and the LM head run under the scope ``head``."""
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    with jax.named_scope("head"):
        x = embed_lookup(cfg, st, params["embed"], tokens)

    sb = superblock(cfg)

    def layer_fn(lp, carry, extra):
        x, aux = carry
        if sb == 1:
            x, a = decoder_layer(cfg, st, lp, x, extra)
            return x, aux + a
        for i in range(sb):
            x, a = decoder_layer(cfg, st, lp[str(i)], x, extra)
            aux = aux + a
        return x, aux

    x, aux = stack_layers(
        layer_fn, params["layers"], (x, jnp.zeros((), jnp.float32)), cfg,
        extra=positions,
    )
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_ln"], cfg.norm_eps)
        return unembed_logits(cfg, st, params["embed"], x), aux


def backbone(cfg: ModelConfig, st: Strategy, params: Params, tokens):
    """Embedding + layer stack + final norm (pre-logits); the embedding and
    the final norm run under the scope ``head``."""
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    with jax.named_scope("head"):
        x = embed_lookup(cfg, st, params["embed"], tokens)
    sb = superblock(cfg)

    def layer_fn(lp, carry, extra):
        x, aux = carry
        if sb == 1:
            x, a = decoder_layer(cfg, st, lp, x, extra)
            return x, aux + a
        for i in range(sb):
            x, a = decoder_layer(cfg, st, lp[str(i)], x, extra)
            aux = aux + a
        return x, aux

    x, aux = stack_layers(
        layer_fn, params["layers"], (x, jnp.zeros((), jnp.float32)), cfg,
        extra=positions,
    )
    with jax.named_scope("head"):
        return rms_norm(x, params["final_ln"], cfg.norm_eps), aux


def loss_fn(cfg: ModelConfig, st: Strategy, params: Params, batch, aux_coef=0.01):
    """Cross entropy (under the scope ``head``) plus the weighted aux loss."""
    if cfg.xent_chunk:
        from .layers import streamed_xent

        x, aux = backbone(cfg, st, params, batch["tokens"])
        with jax.named_scope("head"):
            xent = streamed_xent(cfg, st, x, params["embed"]["embedding"],
                                 batch["labels"])
        return xent + aux_coef * aux
    logits, aux = forward(cfg, st, params, batch["tokens"])
    with jax.named_scope("head"):
        xent = softmax_xent(cfg, st, logits, batch["labels"])
    return xent + aux_coef * aux


# ---------------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------------


def decode_layer(cfg: ModelConfig, st: Strategy, lp: Params, x, ck, cv, pos):
    from .moe import moe_forward

    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    h, ck, cv = attn.decode_attention(cfg, st, lp["attn"], h, ck, cv, pos)
    x = x + h
    h = rms_norm(x, lp["ln2"], cfg.norm_eps)
    if "moe" in lp:
        y, _ = moe_forward(cfg, st, lp["moe"], h)
        if "mlp" in lp:
            y = y + mlp_forward(cfg, st, lp["mlp"], h)
    else:
        y = mlp_forward(cfg, st, lp["mlp"], h)
    return x + y, ck, cv


def decode_step(cfg: ModelConfig, st: Strategy, params: Params, token, cache, pos):
    """One decode step.  token (B,1) int32; cache {"k","v"}: (L,B,T,KR,D) with
    L = layers (sb=1) or L = superblocks and (sb,...) inner dims."""
    x = embed_lookup(cfg, st, params["embed"], token)
    sb = superblock(cfg)
    seq_ax = "kv_seq" if cfg.shard_kv_seq else None

    def ckv(t):
        # keep stacked caches on their sharding — without this GSPMD reshards
        # the concatenate by full replication (involuntary remat)
        lead = (None,) * (t.ndim - 4)
        return st.constrain(t, *lead, "batch", seq_ax, "kv", None)

    def body(carry, lp_and_cache):
        x = carry
        lp, ck, cv = lp_and_cache
        if sb == 1:
            x, ck, cv = decode_layer(cfg, st, lp, x, ck, cv, pos)
            return x, (ck, cv)
        cks, cvs = [], []
        for i in range(sb):
            x, cki, cvi = decode_layer(cfg, st, lp[str(i)], x, ck[i], cv[i], pos)
            cks.append(cki)
            cvs.append(cvi)
        return x, (ckv(jnp.stack(cks)), ckv(jnp.stack(cvs)))

    if cfg.scan_layers:
        x, (ck, cv) = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"]),
            unroll=cfg.scan_unroll,
        )
    else:
        cks, cvs = [], []
        L = cache["k"].shape[0]
        for i in range(L):
            lp = jax.tree_util.tree_map(lambda p: p[i], params["layers"])
            x, (ck, cv) = body(x, (lp, cache["k"][i], cache["v"][i]))
            cks.append(ck)
            cvs.append(cv)
        ck, cv = ckv(jnp.stack(cks)), ckv(jnp.stack(cvs))
    x = rms_norm(x, params["final_ln"], cfg.norm_eps)
    logits = unembed_logits(cfg, st, params["embed"], x)
    return logits, {"k": ck, "v": cv}
