"""Pallas kernels vs pure-jnp oracles (TPU interpret mode), shape/dtype
sweeps, and the flash kernel's gradients."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes

from repro.configs.base import get_strategy
from repro.kernels import flash_attention, ops
from repro.kernels.flash_attention import block_sizes
from repro.kernels.ops import attention, attention_model_layout
from repro.kernels.ref import attention_ref, ssd_scan_ref
from repro.kernels.ssd_scan import ssd_scan
from repro.models.attention import chunked_attention, uses_flash_kernel

rng = np.random.default_rng(0)


def _blocks(bq, bk):
    """The same q and kv blocks in the forward, dK/dV and dQ kernels."""
    return BlockSizes(
        block_q=bq, block_k_major=bk, block_k=bk, block_b=1,
        block_q_major_dkv=bq, block_k_major_dkv=bk, block_k_dkv=bk,
        block_q_dkv=bq, block_k_major_dq=bk, block_k_dq=bk, block_q_dq=bq)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Hq,Hkv,S,D,bq,bk",
    [
        (1, 2, 2, 256, 64, 128, 128),
        (2, 4, 2, 256, 64, 128, 128),
        (1, 8, 2, 256, 128, 256, 128),
        (2, 2, 1, 256, 32, 128, 256),
    ],
)
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, Hq, Hkv, S, D, bq, bk, causal, dtype):
    tol = 2e-3 if dtype == jnp.float32 else 2e-2
    q = jnp.asarray(rng.standard_normal((B, Hq, S, D)), dtype)
    k = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), dtype)
    v = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), dtype)
    out = jax.block_until_ready(
        attention(q, k, v, causal=causal, blocks=_blocks(bq, bk)))
    ref = attention_ref(q, k, v, causal=causal, group_size=Hq // Hkv)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=tol, atol=tol * 5,
    )


@pytest.mark.parametrize(
    "S,T,block,want",
    [
        (1024, 1024, 512, (512, 512)),
        (1024, 1024, 1024, (1024, 1024)),
        (4096, 4096, 512, (512, 512)),
        (640, 640, 512, (128, 128)),
        (256, 384, 512, (256, 384)),
        (128, 128, 512, (128, 128)),
    ],
)
def test_block_sizes_follow_the_shape(monkeypatch, S, T, block, want):
    """The rule under a cap of ``block`` (the module's ``SEQ_BLOCK``)."""
    monkeypatch.setattr(flash_attention, "SEQ_BLOCK", block)
    bs = block_sizes(S, T)
    assert (bs.block_q, bs.block_k_major, bs.block_b) == (*want, 1)
    assert bs.has_backward_blocks
    assert {bs.block_q_major_dkv, bs.block_q_dkv, bs.block_q_dq} == {bs.block_q}
    assert {bs.block_k_major_dkv, bs.block_k_dkv, bs.block_k_major_dq,
            bs.block_k_dq, bs.block_k} == {bs.block_k_major}


def test_block_sizes_refuse_a_length_off_the_128_grid():
    with pytest.raises(ValueError, match="multiple of 128"):
        block_sizes(192, 192)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("S", [256, 640])
@pytest.mark.parametrize("D", [64, 128])
@pytest.mark.parametrize("G", [1, 3])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_gradients(causal, G, D, S):
    """dq, dk, dv through the kernel (model layout, bf16) against the f32
    oracle, beside the chunked XLA loop's: no more than 1.25x its error.

    With G q heads per kv head the kernel sees k and v repeated, so it
    rounds each q head's dv to bf16 before the group is summed: one
    rounding more than the loop, which sums the group in f32.  Causal at
    D 64 that puts dv over the bar (1.25-1.28x), so a gradient over the bar
    may only be dv with G > 1, and ``self_attention`` must then keep the
    shape on the loop: routing GQA to the kernel needs a kernel that meets
    the bar first."""
    B, KR = 1, 2
    key = jax.random.PRNGKey(S + D + G + causal)
    kq, kk, kv, kw = jax.random.split(key, 4)
    q = jax.random.normal(kq, (B, S, KR, G, D), jnp.bfloat16)
    k = jax.random.normal(kk, (B, S, KR, D), jnp.bfloat16)
    v = jax.random.normal(kv, (B, S, KR, D), jnp.bfloat16)
    w = jax.random.normal(kw, (B, S, KR, G, D), jnp.float32)

    def ref(q, k, v):  # the oracle in f32, in the kernel's (B,H,S,D)
        out = attention_ref(
            jnp.transpose(q, (0, 2, 3, 1, 4)).reshape(B, KR * G, S, D),
            jnp.transpose(k, (0, 2, 1, 3)), jnp.transpose(v, (0, 2, 1, 3)),
            causal=causal, group_size=G)
        return jnp.transpose(out.reshape(B, KR, G, S, D), (0, 3, 1, 2, 4))

    def grads(attn, *xs):
        def loss(*xs):
            return jnp.sum(attn(*xs).astype(jnp.float32) * w)

        # ready before the next dispatch: the interpreter's callbacks
        # must not run beside another computation
        return jax.block_until_ready(
            jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(*xs))

    want = grads(ref, *(x.astype(jnp.float32) for x in (q, k, v)))
    got = grads(lambda *a: attention_model_layout(*a, causal=causal), q, k, v)
    xla = grads(lambda *a: chunked_attention(*a, causal=causal, chunk=S),
                q, k, v)
    for name, g, x, r in zip(("dq", "dk", "dv"), got, xla, want):
        assert g.shape == r.shape and g.dtype == jnp.bfloat16, name
        if _rel(g, r) > 1.25 * _rel(x, r):
            assert name == "dv" and G > 1, (name, _rel(g, r), _rel(x, r))
            with pytest.MonkeyPatch.context() as m:
                m.setattr(ops, "on_tpu", lambda: True)
                assert not uses_flash_kernel(get_strategy("2d_finalized"), q)


@pytest.mark.parametrize("dtype", [jnp.float32])
@pytest.mark.parametrize(
    "B,S,H,hd,ds,chunk",
    [
        (1, 128, 2, 64, 64, 64),
        (2, 256, 3, 64, 128, 128),
        (1, 256, 1, 32, 16, 128),
    ],
)
def test_ssd_sweep(B, S, H, hd, ds, chunk, dtype):
    x = jnp.asarray(rng.standard_normal((B, S, H, hd)), dtype)
    dt = jnp.asarray(np.abs(rng.standard_normal((B, S, H))) * 0.5, dtype)
    Bm = jnp.asarray(rng.standard_normal((B, S, ds)) * 0.2, dtype)
    Cm = jnp.asarray(rng.standard_normal((B, S, ds)) * 0.2, dtype)
    A = jnp.asarray(-np.abs(rng.standard_normal((H,))), jnp.float32)
    got = ssd_scan(x, dt, Bm, Cm, A, chunk=chunk, interpret=True)
    ref = ssd_scan_ref(x, dt, Bm, Cm, A, chunk=chunk)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=3e-3, atol=3e-3
    )


def test_ssd_kernel_matches_sequential_recurrence():
    """End-to-end: kernel == chunked ref == exact sequential recurrence."""
    B, S, H, hd, ds = 1, 64, 2, 16, 8
    x = rng.standard_normal((B, S, H, hd)).astype(np.float32)
    dt = np.abs(rng.standard_normal((B, S, H))).astype(np.float32) * 0.5
    Bm = rng.standard_normal((B, S, ds)).astype(np.float32)
    Cm = rng.standard_normal((B, S, ds)).astype(np.float32)
    A = -np.abs(rng.standard_normal((H,))).astype(np.float32)
    y_seq = np.zeros_like(x)
    for b in range(B):
        state = np.zeros((H, hd, ds))
        for t in range(S):
            a = np.exp(dt[b, t] * A)
            state = a[:, None, None] * state + dt[b, t][:, None, None] * np.einsum(
                "hp,d->hpd", x[b, t], Bm[b, t]
            )
            y_seq[b, t] = np.einsum("hpd,d->hp", state, Cm[b, t])
    got = ssd_scan(jnp.asarray(x), jnp.asarray(dt), jnp.asarray(Bm),
                   jnp.asarray(Cm), jnp.asarray(A), chunk=32,
                   interpret=True)
    np.testing.assert_allclose(np.asarray(got), y_seq, rtol=2e-4, atol=2e-4)
