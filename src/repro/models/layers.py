"""Shared model layers: norms, MLPs, embeddings, RoPE, scan-over-layers utils.

All layers are pure functions over explicit param pytrees (dicts), with
``ShapeDtypeStruct`` shape builders so the dry-run can lower without allocating.
Sharding annotations go through the Strategy (configs/base.py) — the GSPMD
user-annotation layer.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig, Strategy
from ..core.sharding import pad_to_multiple
from ..kernels import flash_attention

Params = Dict[str, Any]


# ---------------------------------------------------------------------------------
# param declaration helpers
# ---------------------------------------------------------------------------------


def pspec(shape, spec, dtype=jnp.float32, init="normal", fan_in=None):
    """Declarative param: shape + PartitionSpec + init kind.  The spec is
    filtered against the active mesh for divisibility (§4.1 fallback)."""
    if spec is not None:
        from .base_filter import filter_for_shape

        spec = filter_for_shape(spec, tuple(shape))
    return {
        "__param__": True,
        "shape": tuple(shape),
        "spec": spec,
        "dtype": dtype,
        "init": init,
        "fan_in": fan_in,
    }


def is_param(x) -> bool:
    return isinstance(x, dict) and x.get("__param__") is True


def tree_specs(tree):
    """Extract the PartitionSpec pytree from a param-declaration tree."""
    return jax.tree_util.tree_map(
        lambda p: p["spec"], tree, is_leaf=is_param
    )


def tree_shapes(tree, sharding_for=None):
    """ShapeDtypeStruct pytree (optionally with NamedSharding attached)."""

    def mk(p):
        if sharding_for is None:
            return jax.ShapeDtypeStruct(p["shape"], p["dtype"])
        return jax.ShapeDtypeStruct(
            p["shape"], p["dtype"], sharding=sharding_for(p["spec"])
        )

    return jax.tree_util.tree_map(mk, tree, is_leaf=is_param)


def tree_init(tree, rng):
    """Materialize params (for real training / smoke tests)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree, is_leaf=is_param)
    rngs = jax.random.split(rng, len(leaves))

    def mk(p, r):
        shape, dtype = p["shape"], p["dtype"]
        if p["init"] == "zeros":
            return jnp.zeros(shape, dtype)
        if p["init"] == "ones":
            return jnp.ones(shape, dtype)
        fan_in = p["fan_in"] or (shape[0] if shape else 1)
        std = 1.0 / math.sqrt(max(fan_in, 1))
        return (jax.random.normal(r, shape, jnp.float32) * std).astype(dtype)

    return jax.tree_util.tree_unflatten(
        treedef, [mk(p, r) for p, r in zip(leaves, rngs)]
    )


# ---------------------------------------------------------------------------------
# numerics
# ---------------------------------------------------------------------------------


def rms_norm(x, scale, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def rope(q, positions, dh, base=10000.0, rotary_dims=None):
    """Rotary embedding on the first ``rotary_dims`` (default all ``dh``) of
    the last dim, half-split over those dims with the frequencies taken
    over them; the other dims pass through (HF Phi-3's partial rotary).
    positions (B, S)."""
    rot = dh if rotary_dims is None else rotary_dims
    half = rot // 2
    freqs = jnp.exp(
        -math.log(base) * jnp.arange(0, half, dtype=jnp.float32) / half
    )
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B,S,half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    while cos.ndim < q.ndim:
        cos, sin = cos[..., None, :], sin[..., None, :]
    q1, q2 = q[..., :half], q[..., half:rot]
    parts = [q1 * cos - q2 * sin, q2 * cos + q1 * sin]
    if rot < dh:
        parts.append(q[..., rot:])
    out = jnp.concatenate(parts, axis=-1)
    return out.astype(q.dtype)


def mlp_params(cfg: ModelConfig, st: Strategy, d_ff: int = 0, expert_dims=()):
    """MLP weights; ``expert_dims=(E,)`` prepends a sharded expert dim (§5.5)."""
    d_ff = d_ff or cfg.d_ff
    M = cfg.d_model
    pre = tuple(expert_dims)
    e = ("expert",) if expert_dims else ()
    mlp_ax = "expert_mlp" if expert_dims else "mlp"
    # per-expert weights (§5.5): E on X, per-expert M *unsharded* (E already
    # consumes the X axis), H on Y
    m_ax = "expert_embed" if expert_dims else "embed"
    if cfg.mlp == "swiglu":
        return {
            "wi_gate": pspec(pre + (M, d_ff), st.w(*e, m_ax, mlp_ax), fan_in=M),
            "wi_up": pspec(pre + (M, d_ff), st.w(*e, m_ax, mlp_ax), fan_in=M),
            "wo": pspec(pre + (d_ff, M), st.w(*e, mlp_ax, m_ax), fan_in=d_ff),
        }
    return {
        "wi": pspec(pre + (M, d_ff), st.w(*e, m_ax, mlp_ax), fan_in=M),
        "wo": pspec(pre + (d_ff, M), st.w(*e, mlp_ax, m_ax), fan_in=d_ff),
    }


def mlp_forward(cfg: ModelConfig, st: Strategy, p: Params, x, einsum_pre="", out_label="embed"):
    """x: (..., M) activations in compute dtype."""
    dt = jnp.dtype(cfg.dtype)
    act = {
        "swiglu": lambda g, u: jax.nn.silu(g) * u,
        "gelu": lambda g, _: jax.nn.gelu(g),
        "relu2": lambda g, _: jnp.square(jax.nn.relu(g)),
    }
    pre = einsum_pre  # e.g. "e" for per-expert batched mlp
    if "wi_gate" in p:
        g = jnp.einsum(f"{pre}...m,{pre}mh->{pre}...h", x, p["wi_gate"].astype(dt))
        u = jnp.einsum(f"{pre}...m,{pre}mh->{pre}...h", x, p["wi_up"].astype(dt))
        h = act["swiglu"](g, u)
    else:
        g = jnp.einsum(f"{pre}...m,{pre}mh->{pre}...h", x, p["wi"].astype(dt))
        h = act[cfg.mlp](g, None)
    return jnp.einsum(f"{pre}...h,{pre}hm->{pre}...m", h, p["wo"].astype(dt))


# ---------------------------------------------------------------------------------
# embedding / unembedding with padded vocab (paper §4.1 pad-and-mask)
# ---------------------------------------------------------------------------------


def padded_vocab(cfg: ModelConfig, st: Strategy) -> int:
    tp = st.axis_size("vocab", "weight")
    return pad_to_multiple(cfg.vocab_size, max(tp, 1))


def embed_params(cfg: ModelConfig, st: Strategy):
    V = padded_vocab(cfg, st)
    return {
        "embedding": pspec((V, cfg.d_model), st.w("vocab", "embed"), fan_in=cfg.d_model),
    }


def embed_lookup(cfg: ModelConfig, st: Strategy, p: Params, tokens):
    dt = jnp.dtype(cfg.dtype)
    emb = p["embedding"]
    out = jnp.take(emb, tokens, axis=0).astype(dt)
    return st.constrain(out, "batch", "seq", "embed")


def unembed_logits(cfg: ModelConfig, st: Strategy, p: Params, x):
    dt = jnp.dtype(cfg.dtype)
    logits = jnp.einsum("bsm,vm->bsv", x, p["embedding"].astype(dt))
    return st.constrain(logits, "batch", "seq", "vocab")


def softmax_xent(cfg: ModelConfig, st: Strategy, logits, labels):
    """Cross entropy with padded-vocab masking (§4.1: mask with identity value)."""
    V = logits.shape[-1]
    logits = logits.astype(jnp.float32)
    if V > cfg.vocab_size:
        mask = jnp.arange(V) < cfg.vocab_size
        logits = jnp.where(mask, logits, -1e9)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return (lse - picked).mean()


def streamed_xent(cfg: ModelConfig, st: Strategy, x, embedding, labels):
    """§Perf: loss per seq-chunk with bf16 logits — the (B,S,V) f32 logits
    tensor never materializes (peak ~ B·chunk·V bf16; the f32 math happens on
    per-chunk reductions only)."""
    B, S, M = x.shape
    Q = cfg.xent_chunk
    nc = S // Q
    assert S % Q == 0, (S, Q)
    V = embedding.shape[0]
    mask = jnp.arange(V) < cfg.vocab_size if V > cfg.vocab_size else None

    def body(acc, inp):
        xc, lc = inp  # (B,Q,M), (B,Q)
        logits = jnp.einsum("bqm,vm->bqv", xc, embedding.astype(xc.dtype))
        logits = st.constrain(logits, "batch", "seq", "vocab")
        if mask is not None:
            logits = jnp.where(mask, logits, jnp.asarray(-1e4, logits.dtype))
        # max-subtracted lse in f32 over the bf16 logits (stable, half traffic)
        mx = jax.lax.stop_gradient(logits.max(axis=-1, keepdims=True))
        z = (logits - mx).astype(jnp.float32)
        lse = jnp.log(jnp.sum(jnp.exp(z), axis=-1)) + mx[..., 0].astype(jnp.float32)
        picked = jnp.take_along_axis(
            logits.astype(jnp.float32), lc[..., None], axis=-1
        )[..., 0]
        return acc + (lse - picked).sum(), None

    from .layers import scan_or_loop  # self-import ok at call time

    # recompute each chunk's logits in the backward: saved, the scan's
    # residuals would be the whole (B,S,V) logits the chunking avoids
    body = jax.checkpoint(body, prevent_cse=False)
    xc = jnp.moveaxis(x.reshape(B, nc, Q, M), 1, 0)
    lc = jnp.moveaxis(labels.reshape(B, nc, Q), 1, 0)
    total, _ = scan_or_loop(body, jnp.zeros((), jnp.float32), (xc, lc), cfg)
    return total / (B * S)


# ---------------------------------------------------------------------------------
# layer-stack scan
# ---------------------------------------------------------------------------------


# one object, so that every trace names the same policy
_DOTS_POLICY = jax.checkpoint_policies.save_from_both_policies(
    jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
    jax.checkpoint_policies.save_only_these_names(
        *flash_attention.RESIDUAL_NAMES))


def remat_policy(remat: str):
    """The ``jax.checkpoint`` policy of ``ModelConfig.remat``: "full" saves
    nothing (None), "dots" saves the matmuls without batch dims and the
    flash kernel's residuals, so the backward runs only its backward
    kernels.  The names exist only where ``self_attention`` took the
    kernel; elsewhere "dots" decides every equation as the matmul policy
    alone would."""
    return None if remat == "full" else _DOTS_POLICY


def stack_layers(layer_fn, params_stacked, x, cfg: ModelConfig, extra=None):
    """Run a stack of identical layers: scan when cfg.scan_layers (small HLO;
    production) else a Python loop (used with scan_unroll for exact roofline
    accounting).  ``params_stacked`` leaves have leading dim L."""

    def body(carry, lp):
        out = layer_fn(lp, carry, extra)
        return out, None

    if cfg.remat in ("full", "dots"):
        body = jax.checkpoint(body, policy=remat_policy(cfg.remat),
                              prevent_cse=False)
    if cfg.scan_layers:
        x, _ = jax.lax.scan(body, x, params_stacked, unroll=cfg.scan_unroll)
        return x
    L = jax.tree_util.tree_leaves(params_stacked)[0].shape[0]
    for i in range(L):
        lp = jax.tree_util.tree_map(lambda p: p[i], params_stacked)
        x, _ = body(x, lp)
    return x


def scan_or_loop(body, carry, xs, cfg: ModelConfig):
    """lax.scan when cfg.scan_layers else an unrolled python loop (used by the
    layers-delta roofline accounting; identical math)."""
    if cfg.scan_layers:
        return jax.lax.scan(body, carry, xs, unroll=cfg.scan_unroll)
    L = jax.tree_util.tree_leaves(xs)[0].shape[0]
    ys = []
    for i in range(L):
        xi = jax.tree_util.tree_map(lambda t: t[i], xs)
        carry, y = body(carry, xi)
        ys.append(y)
    if all(y is None for y in ys):
        return carry, None
    stacked_ys = jax.tree_util.tree_map(lambda *t: jnp.stack(t), *ys)
    return carry, stacked_ys


def stacked(tree, n: int, extra_leading_spec=None):
    """Stack a param-declaration tree n times along a new leading dim."""

    def mk(p):
        spec = p["spec"]
        entries = (None,) + tuple(spec) if spec is not None else (None,)
        from jax.sharding import PartitionSpec as P

        return {
            **p,
            "shape": (n,) + p["shape"],
            "spec": P(*entries),
        }

    return jax.tree_util.tree_map(mk, tree, is_leaf=is_param)
