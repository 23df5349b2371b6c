"""Rotary on part of the head, the rotary base and the RMSNorm eps as
``ModelConfig`` fields: Phi-3's layout where the config asks for it, and
the program every other config ran before, unchanged."""
import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, get_strategy
from repro.configs.registry import get_config
from repro.core.compat import assert_close
from repro.models import attention, layers, transformer

ST = get_strategy("2d_finalized")


def _parent_rope(q, positions, dh, base=10000.0):
    """``layers.rope`` as it was before the config chose the rotary dims
    and base: the whole head."""
    half = dh // 2
    freqs = jnp.exp(
        -math.log(base) * jnp.arange(0, half, dtype=jnp.float32) / half
    )
    ang = positions[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    while cos.ndim < q.ndim:
        cos, sin = cos[..., None, :], sin[..., None, :]
    q1, q2 = q[..., :half], q[..., half:]
    out = jnp.concatenate(
        [q1 * cos - q2 * sin, q2 * cos + q1 * sin], axis=-1
    )
    return out.astype(q.dtype)


def _cfg(**kw):
    base = dict(name="t", family="dense", num_layers=2, d_model=64,
                num_heads=6, num_kv_heads=2, d_ff=128, vocab_size=256,
                head_dim=16, dtype="float32", attn_chunk=32)
    return ModelConfig(**{**base, **kw})


def test_phi4_mini_registry_entry_is_phi3s_layout():
    cfg = get_config("phi4-mini-3.8b")
    assert (cfg.rotary_dims, cfg.norm_eps, cfg.rope_base) == (96, 1e-5, 1e4)
    qwen = get_config("qwen1.5-0.5b")
    assert (qwen.rotary_dims, qwen.norm_eps, qwen.rope_base) == (64, 1e-6,
                                                                 1e6)


def test_rope_base_reaches_the_rotary_frequencies():
    """Qwen's registry entry runs its published 1e6: its q and k differ from
    the 1e4 default's."""
    cfg = _cfg(rope_base=get_config("qwen1.5-0.5b").rope_base)
    p = layers.tree_init(attention.attn_params(cfg, ST),
                         jax.random.PRNGKey(1))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 8, 64))
    pos = jnp.broadcast_to(jnp.arange(8), (2, 8))
    k = attention.project_qkv(cfg, ST, p, x, x, pos)[1]
    want = layers.rope(attention.project_qkv(cfg.with_(rope=False), ST, p, x,
                                             x, pos)[1], pos, 16, 1e6)
    assert_close(k, want, "exact")
    k4 = attention.project_qkv(cfg.with_(rope_base=1e4), ST, p, x, x, pos)[1]
    assert not np.allclose(k, k4)


def test_rope_rotates_the_leading_dims_over_their_own_frequencies():
    q = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 3, 16))
    pos = jnp.broadcast_to(jnp.arange(8), (2, 8))
    out = layers.rope(q, pos, 16, 500.0, rotary_dims=12)
    np.testing.assert_array_equal(out[..., 12:], q[..., 12:])
    assert_close(out[..., :12], layers.rope(q[..., :12], pos, 12, 500.0),
                 "exact")


def test_the_program_does_not_rotate_the_whole_head():
    """With Phi's 0.75 the projected q and k keep their last quarter as
    projected; a program that rotated the whole head fails here."""
    cfg = _cfg(rope_fraction=0.75)
    p = layers.tree_init(attention.attn_params(cfg, ST),
                         jax.random.PRNGKey(1))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 8, 64))
    pos = jnp.broadcast_to(jnp.arange(8), (2, 8))
    q, k, _ = attention.project_qkv(cfg, ST, p, x, x, pos)
    _, k0, _ = attention.project_qkv(cfg.with_(rope=False), ST, p, x, x, pos)
    np.testing.assert_array_equal(k[..., 12:], k0[..., 12:])
    assert not np.allclose(k[..., :12], k0[..., :12])
    whole = attention.project_qkv(cfg.with_(rope_fraction=1.0), ST, p, x, x,
                                  pos)[1]
    assert not np.allclose(whole[..., 12:], k0[..., 12:])


def test_rms_norm_takes_the_configs_eps():
    x = jnp.full((1, 4), 1e-3, jnp.float32)
    scale = jnp.ones((4,))
    # mean(x^2) = 1e-6, so eps 1e-6 halves the variance term's weight
    assert_close(layers.rms_norm(x, scale, 1e-6), x / math.sqrt(2e-6),
                 "f32")
    assert_close(layers.rms_norm(x, scale, 1e-5), x / math.sqrt(1.1e-5),
                 "f32")


@pytest.mark.parametrize("arch", ["mamba2-130m", "jamba-1.5-large-398b",
                                  "whisper-base", "internvl2-1b"])
def test_every_family_takes_the_configs_eps(monkeypatch, arch):
    """Every RMSNorm of the loss, and of the pipeline's layer and epilogue
    where the family has them, takes ``cfg.norm_eps``."""
    from repro.launch.train import reduced_config
    from repro.models import api

    cfg = reduced_config(get_config(arch), 16).with_(
        attn_chunk=16, remat="none", norm_eps=0.0123)
    seen, norm = [], layers.rms_norm

    def record(x, scale, eps=1e-6):
        seen.append(eps)
        return norm(x, scale, eps)

    for mod in (api, layers, transformer, *(importlib.import_module(
            f"repro.models.{m}") for m in ("encdec", "vlm", "ssm_lm",
                                           "hybrid", "conformer"))):
        if hasattr(mod, "rms_norm"):
            monkeypatch.setattr(mod, "rms_norm", record)
    params = jax.eval_shape(lambda: layers.tree_init(
        api.param_tree(cfg, ST), jax.random.PRNGKey(0)))
    B, S = 2, 32
    batch = {k: jax.ShapeDtypeStruct((B, S), jnp.int32)
             for k in ("tokens", "labels")}
    if cfg.family == "vlm":
        batch["patches"] = jax.ShapeDtypeStruct(
            (B, cfg.num_prefix_tokens, cfg.d_model), jnp.bfloat16)
    if cfg.family == "encdec":
        batch["frames"] = jax.ShapeDtypeStruct((B, 16, cfg.d_model),
                                               jnp.bfloat16)
    jax.eval_shape(lambda p, b: api.loss_fn(cfg, ST, p, b), params, batch)
    boundary = api.pipeline_boundary(cfg, ST)
    if boundary is not None:
        x = jax.ShapeDtypeStruct((B, S, cfg.d_model), jnp.bfloat16)
        lp = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
            params[boundary.layers_key])
        jax.eval_shape(lambda lp, x: boundary.layer(lp, x, None), lp, x)
        jax.eval_shape(lambda p, x, b: boundary.epilogue(p, x, b), params,
                       x, batch)
    assert seen and set(seen) == {0.0123}


def test_qwens_program_is_unchanged(monkeypatch):
    """The defaults (whole head, base 1e4, eps 1e-6) trace the program the
    dense decoder traced before these fields: the same jaxpr as with the
    earlier rope and the norm's default eps.  The benchmark's Qwen file runs
    these defaults."""
    cfg = get_config("qwen1.5-0.5b").with_(num_layers=2, vocab_size=512,
                                           attn_chunk=32, rope_base=1e4)
    params = jax.eval_shape(lambda: layers.tree_init(
        transformer.param_tree(cfg, ST), jax.random.PRNGKey(0)))
    batch = {k: jax.ShapeDtypeStruct((2, 64), jnp.int32)
             for k in ("tokens", "labels")}

    def jaxpr():
        return str(jax.make_jaxpr(jax.value_and_grad(
            lambda p, b: transformer.loss_fn(cfg, ST, p, b)))(params, batch))

    now = jaxpr()
    monkeypatch.setattr(attention, "rope",
                        lambda q, pos, dh, base, rot: _parent_rope(q, pos,
                                                                   dh))
    monkeypatch.setattr(transformer, "rms_norm",
                        lambda x, scale, eps: layers.rms_norm(x, scale))
    assert now == jaxpr()
