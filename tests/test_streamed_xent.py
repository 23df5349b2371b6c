"""The chunked loss (``layers.streamed_xent``): the same loss and gradients
as the whole-logits loss, and a backward that keeps no chunk's logits."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, get_strategy
from repro.core.compat import assert_close
from repro.models import layers

ST = get_strategy("2d_finalized")
B, S, Q, M, V = 2, 64, 16, 8, 512


def _cfg(scan):
    return ModelConfig(name="t", family="dense", num_layers=1, d_model=M,
                       num_heads=1, num_kv_heads=1, d_ff=16, vocab_size=V,
                       xent_chunk=Q, scan_layers=scan)


def _inputs():
    kx, ke, kl = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(kx, (B, S, M), jnp.float32)
    emb = jax.random.normal(ke, (V, M), jnp.float32)
    labels = jax.random.randint(kl, (B, S), 0, V)
    return x, emb, labels


@pytest.mark.parametrize("scan", [True, False])
def test_streamed_xent_matches_the_whole_logits_loss(scan):
    cfg = _cfg(scan)
    x, emb, labels = _inputs()

    def streamed(x, emb):
        return layers.streamed_xent(cfg, ST, x, emb, labels)

    def whole(x, emb):
        logits = jnp.einsum("bsm,vm->bsv", x, emb)
        return layers.softmax_xent(cfg, ST, logits, labels)

    got, got_g = jax.value_and_grad(streamed, (0, 1))(x, emb)
    want, want_g = jax.value_and_grad(whole, (0, 1))(x, emb)
    assert_close(got, want, "f32")
    for g, w in zip(got_g, want_g):
        assert_close(g, w, "f32")


@pytest.mark.parametrize("scan", [True, False])
def test_streamed_xent_backward_keeps_no_chunk_logits(scan):
    """Each chunk's logits are recomputed in the backward: what the vjp
    keeps is the inputs, far below the (B, S, V) logits."""
    cfg = _cfg(scan)
    x, emb, labels = _inputs()
    _, vjp = jax.vjp(
        lambda x, emb: layers.streamed_xent(cfg, ST, x, emb, labels), x, emb)
    kept = sum(np.size(r) for r in jax.tree_util.tree_leaves(vjp))
    assert kept < B * S * V // 4, kept
    assert np.isfinite(vjp(jnp.ones((), jnp.float32))[0]).all()
