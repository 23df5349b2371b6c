"""GQA attention with GSPMD-friendly padded-head layout.

The assigned archs have kv-head counts (2..16) that rarely divide the model-axis
size (16).  GSPMD's answer to non-divisible dims is pad-and-mask (§4.1); the
production-friendly layout here:

* if  K >= tp  (kv heads divide the axis): shard kv heads directly;
* if  K <  tp: each kv head is *replicated* r = tp/K times (the standard
  TP>kv_heads duplication, e.g. vLLM), expressed as an in-graph broadcast so
  gradients stay exact; q heads are grouped by kv head and padded G -> G' so each
  replica owns G'/r query heads.  Padded q heads have zero Q activations and zero
  W_O columns, so their contribution is exactly zero — the §4.1 masking argument.
  The waste shows up honestly in the roofline MODEL_FLOPS/HLO_FLOPS ratio.

Full-sequence self-attention (``self_attention``, which every model's
training step calls) runs the Pallas flash kernel with its backward pass
(``kernels/ops.py`` ``attention_model_layout``) when the code can see that it
may: the backend is a TPU, the length is a multiple of 128, the head size is
64 or a multiple of 128, each q head has its own kv head, and no mesh axis
splits attention's batch, sequence or heads (an ambient mesh, by the
strategy's rules; under a partitioner, a mesh of one device).  Each trace
counts its path in the metrics registry: ``attention.flash_kernel`` or
``attention.xla_chunked``.

Every other path runs ``chunked_attention``: kv-chunked with an online
softmax ("flash-in-XLA") so the dry-run never materializes (S, T) score
tensors.  That is decode, prefill, cross-attention, GQA, the CPU backend and
meshes that shard attention; a sharded mesh would lower the kernel through
the partitioner's gather-everything fallback.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig, Strategy
from ..core.compat import get_abstract_mesh, partition_devices
from ..kernels import ops as kernel_ops
from ..obs import metrics
from .layers import Params, pspec, rope

NEG_INF = -1e9


def head_layout(cfg: ModelConfig, st: Strategy):
    """(K, G, r, Gp, KR): kv heads, q-per-kv, replicas, padded group, layout heads."""
    N, K = cfg.num_heads, cfg.num_kv_heads
    tp = st.axis_size("kv")
    G = N // K
    if K >= tp:
        assert K % tp == 0, f"kv heads {K} not divisible by axis {tp}"
        return K, G, 1, G, K
    assert tp % K == 0, f"axis {tp} not divisible by kv heads {K}"
    r = tp // K
    Gp = -(-G // r) * r
    return K, G, r, Gp, K * r


def attn_params(cfg: ModelConfig, st: Strategy, cross: bool = False):
    M, N, K, Dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.dh
    # true (unpadded) param shapes: shard the head dim only when divisible,
    # otherwise shard head_dim (Dh is always a multiple of the axis here)
    h = st.w_div("heads", N)
    hd = "mlp" if h is None else None  # head_dim rides the Y axis as fallback
    p = {
        "wq": pspec((M, N, Dh), st.w("embed", h, hd), fan_in=M),
        "wk": pspec((M, K, Dh), st.w("embed", st.w_div("heads", K), None if st.w_div("heads", K) else "mlp"), fan_in=M),
        "wv": pspec((M, K, Dh), st.w("embed", st.w_div("heads", K), None if st.w_div("heads", K) else "mlp"), fan_in=M),
        "wo": pspec((N, Dh, M), st.w(h, hd, "embed"), fan_in=N * Dh),
    }
    if cfg.qkv_bias:
        p["bq"] = pspec((N, Dh), st.w(h, hd), init="zeros")
        p["bk"] = pspec((K, Dh), st.w(st.w_div("heads", K)), init="zeros")
        p["bv"] = pspec((K, Dh), st.w(st.w_div("heads", K)), init="zeros")
    return p


def _pad_group(x, G, Gp, axis):
    if Gp == G:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, Gp - G)
    return jnp.pad(x, pads)


def project_qkv(cfg: ModelConfig, st: Strategy, p: Params, xq, xkv, positions):
    """Returns q (B,S,KR,Gl,D), k,v (B,T,KR,D) in the padded layout."""
    dt = jnp.dtype(cfg.dtype)
    K, G, r, Gp, KR = head_layout(cfg, st)
    Gl = Gp // r
    q = jnp.einsum("bsm,mnd->bsnd", xq, p["wq"].astype(dt))
    k = jnp.einsum("btm,mkd->btkd", xkv, p["wk"].astype(dt))
    v = jnp.einsum("btm,mkd->btkd", xkv, p["wv"].astype(dt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    if cfg.rope and positions is not None:
        q = rope(q, positions, cfg.dh, cfg.rope_base, cfg.rotary_dims)
        k = rope(k, positions, cfg.dh, cfg.rope_base, cfg.rotary_dims)
    B, S = q.shape[:2]
    T = k.shape[1]
    # q: (B,S,N=K*G,D) -> (B,S,K,G,D) -> pad G->Gp -> (B,S,KR,Gl,D)
    q = q.reshape(B, S, K, G, cfg.dh)
    if Gp != G:
        # §4.1: the (K, G) split is not divisible by the kv axis until padded;
        # pin the head dims unsharded here or sharding propagates backward
        # through the uneven reshape (an expensive reshard everywhere, and
        # numerically miscompiled by older jaxlib CPU SPMD)
        q = st.constrain(q, "batch", "seq", None, None, None)
        q = _pad_group(q, G, Gp, axis=3)
        q = st.constrain(q, "batch", "seq", None, None, None)
    q = q.reshape(B, S, KR, Gl, cfg.dh)
    q = st.constrain(q, "batch", "seq", "kv", None, None)
    # k,v: (B,T,K,D) -> replicate r times -> (B,T,KR,D)
    if r > 1:
        k = jnp.broadcast_to(k[:, :, :, None, :], (B, T, K, r, cfg.dh)).reshape(
            B, T, KR, cfg.dh
        )
        v = jnp.broadcast_to(v[:, :, :, None, :], (B, T, K, r, cfg.dh)).reshape(
            B, T, KR, cfg.dh
        )
    k = st.constrain(k, "batch", "seq", "kv", None)
    v = st.constrain(v, "batch", "seq", "kv", None)
    return q, k, v


def out_projection(cfg: ModelConfig, st: Strategy, p: Params, attn):
    """attn: (B,S,KR,Gl,D) padded layout -> (B,S,M) via padded W_O."""
    dt = jnp.dtype(cfg.dtype)
    K, G, r, Gp, KR = head_layout(cfg, st)
    B, S = attn.shape[:2]
    attn = attn.reshape(B, S, K * Gp, cfg.dh)
    wo = p["wo"].astype(dt)
    if Gp != G:
        wo = wo.reshape(K, G, cfg.dh, cfg.d_model)
        wo = _pad_group(wo, G, Gp, axis=1)  # zero columns: masks padded heads
        wo = wo.reshape(K * Gp, cfg.dh, cfg.d_model)
    out = jnp.einsum("bsnd,ndm->bsm", attn, wo)
    return st.constrain(out, "batch", "seq", "embed")


def chunked_attention(
    q, k, v, *, causal: bool, chunk: int, q_offset=0, kv_len: Optional[jnp.ndarray] = None
):
    """Online-softmax attention, scanned over kv chunks.

    q: (B,S,KR,Gl,D); k,v: (B,T,KR,D).  ``q_offset`` is the absolute position of
    q[0] (for decode/prefill continuation); ``kv_len`` masks the valid cache
    prefix when decoding into a longer preallocated cache.
    """
    B, S, KR, Gl, D = q.shape
    T = k.shape[1]
    scale = 1.0 / math.sqrt(D)
    chunk = min(chunk, T)
    if T % chunk:  # pad kv to a chunk multiple; §4.1 pad-and-mask
        padded = -(-T // chunk) * chunk
        pads = ((0, 0), (0, padded - T), (0, 0), (0, 0))
        k = jnp.pad(k, pads)
        v = jnp.pad(v, pads)
        kv_len = jnp.minimum(kv_len, T) if kv_len is not None else T
        T = padded
    nt = T // chunk
    qf = (q * scale).astype(q.dtype)
    q_pos = q_offset + jnp.arange(S)

    kc = jnp.moveaxis(k.reshape(B, nt, chunk, KR, D), 1, 0)
    vc = jnp.moveaxis(v.reshape(B, nt, chunk, KR, D), 1, 0)

    acc0 = jnp.zeros((B, S, KR, Gl, D), jnp.float32)
    m0 = jnp.full((B, S, KR, Gl), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, S, KR, Gl), jnp.float32)

    def body(carry, inp):
        acc, m, l, idx = carry
        kb, vb = inp
        s = jnp.einsum(
            "bsngd,btnd->bsngt", qf, kb, preferred_element_type=jnp.float32
        )
        k_pos = idx * chunk + jnp.arange(chunk)
        mask = jnp.ones((S, chunk), bool)
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]
        if kv_len is not None:
            mask = jnp.logical_and(mask, (k_pos < kv_len)[None, :])
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bsngt,btnd->bsngd", p.astype(kb.dtype), vb,
            preferred_element_type=jnp.float32,
        )
        return (acc_new, m_new, l_new, idx + 1), None

    (acc, m, l, _), _ = jax.lax.scan(body, (acc0, m0, l0, 0), (kc, vc))
    out = acc / jnp.maximum(l[..., None], 1e-20)
    return out.astype(q.dtype)


def self_attention(
    cfg: ModelConfig,
    st: Strategy,
    p: Params,
    x,
    positions,
    *,
    causal=True,
):
    """Full-sequence self-attention (training / prefill): the flash kernel
    where :func:`uses_flash_kernel`, else the chunked loop."""
    q, k, v = project_qkv(cfg, st, p, x, x, positions)
    if uses_flash_kernel(st, q):
        metrics.inc("attention.flash_kernel")
        attn = kernel_ops.attention_model_layout(q, k, v, causal=causal)
    else:
        metrics.inc("attention.xla_chunked")
        attn = chunked_attention(q, k, v, causal=causal, chunk=cfg.attn_chunk)
    return out_projection(cfg, st, p, attn)


def uses_flash_kernel(st: Strategy, q) -> bool:
    """Whether self-attention over q (B,S,KR,Gl,D) runs the flash kernel:
    on a TPU, S a multiple of 128, D 64 or a multiple of 128, one q head per
    kv head, and no mesh axis splitting attention's batch, sequence or heads.

    GQA stays on the loop: the kernel sees k and v repeated to the q heads
    and rounds each head's dk and dv to bf16 before the group is summed,
    which leaves dv further from f32 than the loop's (tests/test_kernels.py).
    """
    _, S, _, Gl, D = q.shape
    if (not kernel_ops.on_tpu() or S % 128 or Gl != 1
            or not (D == 64 or D % 128 == 0)):
        return False
    mesh = get_abstract_mesh()
    if mesh is not None and not mesh.empty:
        return all(st.axis_size(n) == 1 for n in ("batch", "seq", "kv"))
    return partition_devices() == 1


# ---------------------------------------------------------------------------------
# decode with KV cache
# ---------------------------------------------------------------------------------


def cache_spec(cfg: ModelConfig, st: Strategy):
    return st.a("batch", None, "kv", None)


def init_cache_shapes(cfg: ModelConfig, st: Strategy, batch, max_len, layers=None):
    K, G, r, Gp, KR = head_layout(cfg, st)
    L = layers if layers is not None else cfg.num_layers
    shape = (L, batch, max_len, KR, cfg.dh)
    return shape


def decode_attention(cfg: ModelConfig, st: Strategy, p: Params, x, ck, cv, pos):
    """One-token decode.  x: (B,1,M); ck/cv: (B,T,KR,D) layer cache; pos: scalar
    absolute position.  Returns (out, new_ck, new_cv)."""
    B = x.shape[0]
    positions = jnp.full((B, 1), pos, jnp.int32)
    q, k, v = project_qkv(cfg, st, p, x, x, positions)
    # write new kv at pos
    seq_ax = "kv_seq" if cfg.shard_kv_seq else None
    ck = jax.lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype), pos, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype), pos, axis=1)
    ck = st.constrain(ck, "batch", seq_ax, "kv", None)
    cv = st.constrain(cv, "batch", seq_ax, "kv", None)
    # decode always uses ONE kv chunk: per-device score tensors are tiny
    # (B_loc × heads_loc × T × 4B ≈ MBs), and the chunked scan's
    # reshape+moveaxis would force full-cache layout copies.  With a
    # seq-sharded cache this is flash-decode: GSPMD partitions the softmax
    # stats + weighted sum with small AllReduces.
    attn = chunked_attention(
        q,
        ck,
        cv,
        causal=False,
        chunk=ck.shape[1],
        q_offset=pos,
        kv_len=pos + 1,
    )
    out = out_projection(cfg, st, p, attn)
    return out, ck, cv


def prefill_attention(cfg: ModelConfig, st: Strategy, p: Params, x, positions):
    """Prefill: full self-attention AND return the kv to seed a cache."""
    q, k, v = project_qkv(cfg, st, p, x, x, positions)
    attn = chunked_attention(q, k, v, causal=cfg.causal, chunk=cfg.attn_chunk)
    return out_projection(cfg, st, p, attn), k, v


def cross_attention(cfg: ModelConfig, st: Strategy, p: Params, x, enc_k, enc_v):
    """Decoder cross-attention over precomputed encoder kv."""
    B, S = x.shape[:2]
    dt = jnp.dtype(cfg.dtype)
    K, G, r, Gp, KR = head_layout(cfg, st)
    Gl = Gp // r
    q = jnp.einsum("bsm,mnd->bsnd", x, p["wq"].astype(dt))
    q = q.reshape(B, S, K, G, cfg.dh)
    if Gp != G:  # §4.1: see project_qkv — no sharding across the uneven pad
        q = st.constrain(q, "batch", "seq", None, None, None)
        q = _pad_group(q, G, Gp, axis=3)
        q = st.constrain(q, "batch", "seq", None, None, None)
    else:
        q = _pad_group(q, G, Gp, axis=3)
    q = q.reshape(B, S, KR, Gl, cfg.dh)
    attn = chunked_attention(
        q, enc_k, enc_v, causal=False, chunk=min(1024, enc_k.shape[1])
    )
    return out_projection(cfg, st, p, attn)


def encode_kv(cfg: ModelConfig, st: Strategy, p: Params, x_enc):
    """Project encoder states to cross-attention kv in padded layout."""
    dt = jnp.dtype(cfg.dtype)
    K, G, r, Gp, KR = head_layout(cfg, st)
    B, T = x_enc.shape[:2]
    k = jnp.einsum("btm,mkd->btkd", x_enc, p["wk"].astype(dt))
    v = jnp.einsum("btm,mkd->btkd", x_enc, p["wv"].astype(dt))
    if r > 1:
        k = jnp.broadcast_to(k[:, :, :, None, :], (B, T, K, r, cfg.dh)).reshape(B, T, KR, cfg.dh)
        v = jnp.broadcast_to(v[:, :, :, None, :], (B, T, K, r, cfg.dh)).reshape(B, T, KR, cfg.dh)
    return k, v
