"""Phi-3 decoder-only LM (Phi-4-mini): the plain float32 reference.

Written from the configuration file alone; it imports nothing of the program
under test.  The weights, their names and the FLOP count are those of
``dense_lm.py`` (the same parameter tree: no biases, tied embedding), and so
are Adafactor, the fp8 control's rounding and the blocked loss.  What differs
is the layer, as HF ``Phi3`` computes it:

* RMSNorm ``x * rsqrt(mean(x^2) + eps) * scale`` at the file's
  ``rms_norm_eps``;
* rotary embedding on the first ``round(partial_rotary_factor * head_dim)``
  dims of each head, half-split over those dims, with the frequencies
  ``rope_theta^(-2i/rotary_dims)`` taken over them; the other dims pass
  through unrotated;
* grouped-query attention (query head ``n`` reads kv head
  ``n // (heads / kv_heads)``), causal softmax over ``q.k / sqrt(head_dim)``;
  SwiGLU MLP; final RMSNorm; logits against the tied embedding; mean token
  cross entropy.

The file lists what it does not follow under ``departures``.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

from benchmarks.chip.families import dense_lm

make_weights = dense_lm.make_weights
step_flops = dense_lm.step_flops


def dims(c):
    d = dense_lm.dims(c)
    d["R"] = int(round(float(c.get("partial_rotary_factor", 1.0)) * d["D"]))
    return d


def program_config(c, traffic):
    """Keyword arguments of the program's ``ModelConfig`` for this file."""
    d = dims(c)
    if not c.get("tie_word_embeddings", False):
        raise ValueError("phi3_lm: the program ties the output head to the "
                         "embedding; an untied configuration does not run")
    if d["bias"]:
        raise ValueError("phi3_lm: Phi-3 attention has no biases")
    kw = dict(
        name=c["name"], family="dense", num_layers=d["L"], d_model=d["M"],
        num_heads=d["N"], num_kv_heads=d["K"], d_ff=d["F"],
        vocab_size=d["V"], head_dim=d["D"], qkv_bias=False, mlp="swiglu",
        rope=True, rope_fraction=d["R"] / d["D"], rope_base=d["theta"],
        norm_eps=d["eps"], dtype=c["compute_dtype"],
        param_dtype=c["param_dtype"],
    )
    kw.update(traffic.get("model", {}))
    return kw


def _rope(x, theta, rot):
    """x: (B, S, H, D); rotary on dims [0, rot), halves [x1, x2] of them."""
    S = x.shape[1]
    half = rot // 2
    freqs = theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def _layer(d, matmul, x, lp):
    B, S, _ = x.shape
    G = d["N"] // d["K"]
    mm = functools.partial(dense_lm._mm, matmul=matmul)
    h = dense_lm._rms_norm(x, lp["ln1"], d["eps"])
    q = mm("bsm,mnd->bsnd", h, lp["attn/wq"])
    k = mm("bsm,mkd->bskd", h, lp["attn/wk"])
    v = mm("bsm,mkd->bskd", h, lp["attn/wv"])
    q, k = _rope(q, d["theta"], d["R"]), _rope(k, d["theta"], d["R"])
    q = q.reshape(B, S, d["K"], G, d["D"])
    s = mm("bskgd,btkd->bkgst", q, k) / math.sqrt(d["D"])
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = mm("bkgst,btkd->bskgd", p, v).reshape(B, S, d["N"], d["D"])
    x = x + mm("bsnd,ndm->bsm", o, lp["attn/wo"])
    h = dense_lm._rms_norm(x, lp["ln2"], d["eps"])
    g = mm("bsm,mf->bsf", h, lp["mlp/wi_gate"])
    u = mm("bsm,mf->bsf", h, lp["mlp/wi_up"])
    return x + mm("bsf,fm->bsm", jax.nn.silu(g) * u, lp["mlp/wo"])


def reference_loss(c, w, tokens, labels, matmul="f32", rows=256):
    """Mean next-token cross entropy, layer by layer (rematerialised) and
    the logits in blocks of ``rows`` positions, so that it fits."""
    d = dims(c)
    layers = {n[len("layers/"):]: a for n, a in w.items()
              if n.startswith("layers/")}
    x = w["embed/embedding"][tokens]
    body = jax.checkpoint(lambda x, lp: (_layer(d, matmul, x, lp), None))
    x, _ = jax.lax.scan(body, x, layers)
    x = dense_lm._rms_norm(x, w["final_ln"], d["eps"])
    B, S, M = x.shape
    rows = min(rows, S)
    nb = S // rows
    xb = jnp.moveaxis(x.reshape(B, nb, rows, M), 1, 0)
    lb = jnp.moveaxis(labels.reshape(B, nb, rows), 1, 0)

    @jax.checkpoint
    def block(total, xl):
        xc, lc = xl
        logits = dense_lm._mm("brm,vm->brv", xc, w["embed/embedding"], matmul)
        lse = jax.nn.logsumexp(logits, -1)
        picked = jnp.take_along_axis(logits, lc[..., None], -1)[..., 0]
        return total + jnp.sum(lse - picked), None

    total, _ = jax.lax.scan(block, jnp.zeros((), jnp.float32), (xb, lb))
    return total / (B * S)


@functools.lru_cache(maxsize=None)
def _reference_step(config_json: str, optimizer_json: str, matmul: str):
    c, optimizer = json.loads(config_json), json.loads(optimizer_json)
    if optimizer.get("name") != "adafactor":
        raise ValueError(f"reference: no optimizer {optimizer.get('name')!r}")
    init, update = dense_lm._adafactor(optimizer)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(w, s, t, tokens, labels):
        loss, g = jax.value_and_grad(
            lambda w: reference_loss(c, w, tokens, labels, matmul))(w)
        w, s = update(g, s, w, t)
        gn = {n: jnp.sqrt(jnp.sum(x * x)) for n, x in g.items()}
        return w, s, loss, gn

    change = jax.jit(lambda a, b: {n: jnp.sqrt(jnp.sum((a[n] - b[n]) ** 2))
                                   for n in a})
    return jax.jit(init), step, change


def reference_steps(c, optimizer, w0, batches, matmul="f32"):
    """Train ``len(batches)`` steps from ``w0``; returns the losses, the
    per-leaf norms of the first gradient, and the per-leaf norms of the
    change of the weights over all the steps (``dense_lm.reference_steps``
    with this family's loss)."""
    init, step, change = _reference_step(
        json.dumps(c, sort_keys=True), json.dumps(optimizer, sort_keys=True),
        matmul)
    w = jax.tree_util.tree_map(jnp.copy, w0)
    s = init(w)
    losses, grad_norms = [], None
    for t, (tokens, labels) in enumerate(batches):
        w, s, loss, gn = step(w, s, jnp.float32(t), tokens, labels)
        losses.append(loss)
        if grad_norms is None:
            grad_norms = gn
    moved = change(w, w0)
    return {
        "losses": [float(x) for x in jax.device_get(losses)],
        "grad_norms": {n: float(x) for n, x in
                       jax.device_get(grad_norms).items()},
        "change_norms": {n: float(x) for n, x in
                         jax.device_get(moved).items()},
    }
