"""Jamba-style hybrid model: Mamba + attention 1:7 interleave, MoE every 2 layers.

72 layers = 9 identical super-blocks of 8 sub-layers:
  index 0..6 -> Mamba mixer, index 7 -> attention mixer;
  odd indices -> MoE FFN, even -> dense FFN.
The scan runs over super-blocks (stacked params), each super-block unrolled — the
compiled HLO stays depth/9-sized while layer heterogeneity is preserved.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, Strategy
from . import attention as attn
from .layers import (
    Params, embed_lookup, embed_params, mlp_forward, mlp_params, pspec,
    remat_policy, rms_norm, scan_or_loop, softmax_xent, stacked,
    unembed_logits,
)
from .moe import moe_forward, moe_params
from .ssm import ssm_decode, ssm_forward, ssm_params, ssm_state_shapes


def superblock_size(cfg: ModelConfig) -> int:
    return cfg.attn_every or 8


def _sub_param(cfg, st, idx):
    sb = superblock_size(cfg)
    is_attn = (idx % sb) == sb - 1
    is_moe = cfg.moe and (idx % cfg.moe_every) == cfg.moe_every - 1
    p = {"ln1": pspec((cfg.d_model,), st.w("embed_vec"), init="ones"),
         "ln2": pspec((cfg.d_model,), st.w("embed_vec"), init="ones")}
    p["mixer"] = attn.attn_params(cfg, st) if is_attn else ssm_params(cfg, st)
    p["ffn"] = moe_params(cfg, st) if is_moe else mlp_params(cfg, st)
    return p


def param_tree(cfg: ModelConfig, st: Strategy):
    sb = superblock_size(cfg)
    assert cfg.num_layers % sb == 0
    block = {str(i): _sub_param(cfg, st, i) for i in range(sb)}
    return {
        "embed": embed_params(cfg, st),
        "blocks": stacked(block, cfg.num_layers // sb),
        "final_ln": pspec((cfg.d_model,), st.w("embed_vec"), init="ones"),
    }


def _sub_forward(cfg, st, idx, lp, x, positions):
    sb = superblock_size(cfg)
    is_attn = (idx % sb) == sb - 1
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    if is_attn:
        h = attn.self_attention(cfg, st, lp["mixer"], h, positions, causal=cfg.causal)
    else:
        h = ssm_forward(cfg, st, lp["mixer"], h)
    x = st.constrain(x + h, "batch", "seq", "embed")
    h = rms_norm(x, lp["ln2"], cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    if "router" in lp["ffn"]:
        y, aux = moe_forward(cfg, st, lp["ffn"], h)
    else:
        y = mlp_forward(cfg, st, lp["ffn"], h)
    return st.constrain(x + y, "batch", "seq", "embed"), aux


def forward(cfg: ModelConfig, st: Strategy, params: Params, tokens):
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    x = embed_lookup(cfg, st, params["embed"], tokens)
    sb = superblock_size(cfg)

    def block_fn(carry, bp):
        x, aux = carry
        for i in range(sb):
            x, a = _sub_forward(cfg, st, i, bp[str(i)], x, positions)
            aux = aux + a
        return (x, aux), None

    if cfg.remat != "none":
        block_fn = jax.checkpoint(
            block_fn, policy=remat_policy("dots"), prevent_cse=False)
    (x, aux), _ = scan_or_loop(
        block_fn, (x, jnp.zeros((), jnp.float32)), params["blocks"], cfg
    )
    x = rms_norm(x, params["final_ln"], cfg.norm_eps)
    return unembed_logits(cfg, st, params["embed"], x), aux


def loss_fn(cfg: ModelConfig, st: Strategy, params: Params, batch, aux_coef=0.01):
    logits, aux = forward(cfg, st, params, batch["tokens"])
    return softmax_xent(cfg, st, logits, batch["labels"]) + aux_coef * aux


# ---------------------------------------------------------------------------------
# decode: kv cache only for attention sub-layers; ssm state for mamba sub-layers
# ---------------------------------------------------------------------------------


def cache_shapes(cfg: ModelConfig, st: Strategy, batch: int, max_len: int):
    sb = superblock_size(cfg)
    nb = cfg.num_layers // sb
    K, G, r, Gp, KR = attn.head_layout(cfg, st)
    ss = ssm_state_shapes(cfg, st, batch)
    return {
        "k": (nb, batch, max_len, KR, cfg.dh),
        "v": (nb, batch, max_len, KR, cfg.dh),
        "s": (nb, sb - 1) + ss["s"],
        "conv": (nb, sb - 1) + ss["conv"],
    }


def decode_step(cfg: ModelConfig, st: Strategy, params: Params, token, cache, pos):
    x = embed_lookup(cfg, st, params["embed"], token)
    sb = superblock_size(cfg)

    def block_fn(x, inp):
        bp, ck, cv, ss, sconv = inp
        new_s, new_conv = [], []
        for i in range(sb):
            lp = bp[str(i)]
            h = rms_norm(x, lp["ln1"], cfg.norm_eps)
            if i == sb - 1:
                h, ck, cv = attn.decode_attention(cfg, st, lp["mixer"], h, ck, cv, pos)
            else:
                h, st_new = ssm_decode(
                    cfg, st, lp["mixer"], h, {"s": ss[i], "conv": sconv[i]}
                )
                new_s.append(st_new["s"])
                new_conv.append(st_new["conv"])
            x = x + h
            h = rms_norm(x, lp["ln2"], cfg.norm_eps)
            if "router" in lp["ffn"]:
                y, _ = moe_forward(cfg, st, lp["ffn"], h)
            else:
                y = mlp_forward(cfg, st, lp["ffn"], h)
            x = x + y
        s_stack = st.constrain(jnp.stack(new_s), None, "batch", "heads", None, None)
        c_stack = st.constrain(jnp.stack(new_conv), None, "batch", None, "heads", None)
        return x, (ck, cv, s_stack, c_stack)

    x, (ck, cv, s, conv) = scan_or_loop(
        block_fn, x,
        (params["blocks"], cache["k"], cache["v"], cache["s"], cache["conv"]),
        cfg,
    )
    x = rms_norm(x, params["final_ln"], cfg.norm_eps)
    logits = unembed_logits(cfg, st, params["embed"], x)
    return logits, {"k": ck, "v": cv, "s": s, "conv": conv}
