"""Family dispatch: one uniform interface over all model families.

Every family exposes:
  param_tree(cfg, st)            declarative param tree (shapes + specs)
  loss_fn(cfg, st, params, batch)  scalar training loss
  decode_step(cfg, st, params, token, cache, pos) -> (logits, new_cache)
  cache_shapes(cfg, st, batch, max_len) -> dict of cache array shapes

Families with a homogeneous layer stack additionally declare a
**stackable-layer boundary** (:func:`pipeline_boundary`): the prologue /
layer-body / epilogue decomposition the pipeline subsystem
(``repro.pipeline``) may rewrite into GSPMD §3.3 stage-stacked form.  A
config opts out with ``ModelConfig.stackable_layers = False`` (set in the
registry for families whose stack is not homogeneous: MoE-every-k
superblocks, hybrid attn/ssm interleaves, encoder-decoder, VLM prefixes).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, Strategy
from . import attention as attn_mod
from . import encdec, hybrid, ssm_lm, transformer, vlm


def family_module(cfg: ModelConfig):
    return {
        "dense": transformer,
        "moe": transformer,  # MoE FFN handled inside the transformer layer
        "hybrid": hybrid,
        "ssm": ssm_lm,
        "encdec": encdec,
        "vlm": vlm,
    }[cfg.family]


def param_tree(cfg: ModelConfig, st: Strategy):
    return family_module(cfg).param_tree(cfg, st)


def loss_fn(cfg: ModelConfig, st: Strategy, params, batch):
    return family_module(cfg).loss_fn(cfg, st, params, batch)


def decode_step(cfg: ModelConfig, st: Strategy, params, token, cache, pos):
    return family_module(cfg).decode_step(cfg, st, params, token, cache, pos)


class PipelineBoundary(NamedTuple):
    """The stackable-layer region of one family's training loss.

    ``prologue(params, tokens) -> x`` (embedding; full batch),
    ``layer(lp, x, extra) -> x`` (ONE homogeneous layer — same in/out avals,
    no aux carry), ``epilogue(params, x, batch) -> loss`` (final norm +
    logits + xent).  ``layers_key`` names the stacked-params subtree
    (leaves with a leading layer dim) the pipeline stage-stacks.
    """

    prologue: Callable
    layer: Callable
    epilogue: Callable
    layers_key: str


def pipeline_boundary(cfg: ModelConfig, st: Strategy) -> Optional[PipelineBoundary]:
    """The family's stackable-layer boundary, or None when the stack is not
    homogeneous (MoE superblocks, hybrid interleaves, encdec, vlm) or the
    config declares ``stackable_layers=False``."""
    from .layers import (
        embed_lookup, rms_norm, softmax_xent, streamed_xent, unembed_logits,
    )

    if not cfg.stackable_layers:
        return None

    # the embedding and the LM head with its loss, under the scope the
    # unpipelined model gives them (transformer.forward)
    @jax.named_scope("head")
    def prologue(params, tokens):
        return embed_lookup(cfg, st, params["embed"], tokens)

    @jax.named_scope("head")
    def epilogue(params, x, batch):
        x = rms_norm(x, params["final_ln"], cfg.norm_eps)
        if cfg.xent_chunk:
            return streamed_xent(
                cfg, st, x, params["embed"]["embedding"], batch["labels"]
            )
        logits = unembed_logits(cfg, st, params["embed"], x)
        return softmax_xent(cfg, st, logits, batch["labels"])

    if cfg.family == "dense" and not cfg.moe:
        from .transformer import decoder_layer, superblock

        if superblock(cfg) != 1:
            return None

        def layer(lp, x, positions):
            return decoder_layer(cfg, st, lp, x, positions)[0]

        return PipelineBoundary(prologue, layer, epilogue, "layers")
    if cfg.family == "ssm":
        from .ssm import ssm_forward

        def layer(lp, x, _extra):
            h = rms_norm(x, lp["ln"], cfg.norm_eps)
            return st.constrain(
                x + ssm_forward(cfg, st, lp["mixer"], h),
                "batch", "seq", "embed",
            )

        return PipelineBoundary(prologue, layer, epilogue, "layers")
    return None


def cache_shapes(cfg: ModelConfig, st: Strategy, batch: int, max_len: int) -> Dict[str, tuple]:
    mod = family_module(cfg)
    if hasattr(mod, "cache_shapes"):
        if cfg.family == "encdec":
            return mod.cache_shapes(cfg, st, batch, max_len, enc_len=1500)
        return mod.cache_shapes(cfg, st, batch, max_len)
    # dense/moe/vlm transformers: plain kv cache (superblocked when moe_every>1)
    from .transformer import superblock

    K, G, r, Gp, KR = attn_mod.head_layout(cfg, st)
    sb = superblock(cfg)
    if sb == 1:
        return {
            "k": (cfg.num_layers, batch, max_len, KR, cfg.dh),
            "v": (cfg.num_layers, batch, max_len, KR, cfg.dh),
        }
    nb = cfg.num_layers // sb
    return {
        "k": (nb, sb, batch, max_len, KR, cfg.dh),
        "v": (nb, sb, batch, max_len, KR, cfg.dh),
    }


def cache_specs(cfg: ModelConfig, st: Strategy) -> Dict[str, Any]:
    """PartitionSpec per cache entry (leading layer dim unsharded)."""
    from jax.sharding import PartitionSpec as P

    def with_lead(spec):
        return P(*((None,) + tuple(spec)))

    seq_ax = "kv_seq" if cfg.shard_kv_seq else None

    def padded(spec_logical, shape):
        """NB: build at full rank — PartitionSpec trims trailing Nones, so lead
        padding must come from the SHAPE rank, never len(spec)."""
        lead = (None,) * (len(shape) - len(spec_logical))
        return st.a(*(lead + spec_logical))

    out = {}
    for name, shape in cache_shapes(cfg, st, 1, 2).items():
        if name in ("k", "v", "ek", "ev"):
            out[name] = padded(("batch", seq_ax, "kv", None), shape)
        elif name == "s":
            out[name] = padded(("batch", "heads", None, None), shape)
        elif name == "conv":
            out[name] = padded(("batch", None, "heads", None), shape)
    return out


def abstract_cache(cfg: ModelConfig, st: Strategy, batch: int, max_len: int, sharding_for=None):
    from .base_filter import filter_for_shape

    shapes = cache_shapes(cfg, st, batch, max_len)
    specs = cache_specs(cfg, st)
    dt = jnp.bfloat16

    def mk(name, shape):
        dtype = jnp.float32 if name in ("s",) else dt
        if sharding_for is None:
            return jax.ShapeDtypeStruct(shape, dtype)
        spec = filter_for_shape(specs[name], shape)
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding_for(spec))

    return {name: mk(name, shape) for name, shape in shapes.items()}
