"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ops import attention
from repro.kernels.ref import attention_ref, ssd_scan_ref
from repro.kernels.ssd_scan import ssd_scan

rng = np.random.default_rng(0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Hq,Hkv,S,D,bq,bk",
    [
        (1, 2, 2, 128, 64, 64, 64),
        (2, 4, 2, 256, 64, 128, 128),
        (1, 8, 2, 128, 128, 64, 32),
        (2, 2, 1, 256, 32, 128, 64),
    ],
)
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, Hq, Hkv, S, D, bq, bk, causal, dtype):
    tol = 2e-3 if dtype == jnp.float32 else 2e-2
    q = jnp.asarray(rng.standard_normal((B, Hq, S, D)), dtype)
    k = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), dtype)
    v = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), dtype)
    out = attention(q, k, v, causal=causal, block_q=bq, block_k=bk)
    ref = attention_ref(q, k, v, causal=causal, group_size=Hq // Hkv)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=tol, atol=tol * 5,
    )


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
