"""Dense decoder-only LM (Qwen1.5, Phi-4-mini): the plain float32 reference.

Everything here is written from the configuration file alone and imports
nothing of the program under test:

* ``weight_specs`` / ``make_weights``: the weights, made from ``--seed`` on the
  device in one jitted call, named by their path in the program's parameter
  tree (``layers/attn/wq`` ...), stacked over layers;
* ``program_config``: the keyword arguments of the program's ``ModelConfig``
  that run this configuration;
* ``step_flops``: model FLOPs of one training step (forward + backward,
  causal attention counted once, recomputation not counted);
* ``reference_steps``: the training steps in float32 at ``highest`` matmul
  precision, with Adafactor as the program states it; ``matmul="fp8"`` is
  the control, the same steps with every matmul operand rounded to fp8
  (e4m3 forward, e5m2 backward, per-tensor scaling).

Semantics followed (and the departures from the published models that the
program makes, which each configuration file lists under ``departures``):
pre-norm blocks with RMSNorm ``x * rsqrt(mean(x^2) + eps) * scale``; q/k/v
projections with optional bias, then rotary embedding of the whole head
(half-split, not interleaved) at ``rope_theta``; grouped-query attention with
query head ``n`` reading kv head ``n // (heads / kv_heads)``; causal softmax
over ``q.k / sqrt(head_dim)``; SwiGLU MLP ``(silu(x Wg) * x Wu) Wo``; final
RMSNorm; logits against the tied embedding; mean token cross entropy.
"""
from __future__ import annotations

import functools
import json
import math
import zlib

import jax
import jax.numpy as jnp

def dims(c):
    M, N = c["hidden_size"], c["num_attention_heads"]
    return dict(
        L=c["num_hidden_layers"], M=M, N=N, K=c["num_key_value_heads"],
        D=c.get("head_dim") or M // N, F=c["intermediate_size"],
        V=c["vocab_size"], bias=bool(c.get("qkv_bias", False)),
        eps=float(c["rms_norm_eps"]), theta=float(c["rope_theta"]),
    )


def program_config(c, traffic):
    """Keyword arguments of the program's ``ModelConfig`` for this file."""
    d = dims(c)
    if not c.get("tie_word_embeddings", False):
        raise ValueError("dense_lm: the program ties the output head to the "
                         "embedding; an untied configuration does not run")
    if (d["theta"], d["eps"]) != (1e4, 1e-6):
        raise ValueError("dense_lm: the program's rotary base (1e4) and "
                         "RMSNorm eps (1e-6) are fixed; the file states "
                         f"{d['theta']} and {d['eps']}")
    kw = dict(
        name=c["name"], family="dense", num_layers=d["L"], d_model=d["M"],
        num_heads=d["N"], num_kv_heads=d["K"], d_ff=d["F"],
        vocab_size=d["V"], head_dim=d["D"], qkv_bias=d["bias"],
        mlp="swiglu", rope=True, dtype=c["compute_dtype"],
        param_dtype=c["param_dtype"],
    )
    kw.update(traffic.get("model", {}))
    return kw


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def weight_specs(c):
    """``{path: (shape, init, scale)}``; init is ``normal`` (scale = std) or
    ``around_one`` (1 + scale * normal, for norm scales)."""
    d = dims(c)
    L, M, N, K, D, F, V = (d[k] for k in "LMNKDFV")
    s = {
        "embed/embedding": ((V, M), "normal", 1 / math.sqrt(M)),
        "final_ln": ((M,), "around_one", 0.1),
        "layers/ln1": ((L, M), "around_one", 0.1),
        "layers/ln2": ((L, M), "around_one", 0.1),
        "layers/attn/wq": ((L, M, N, D), "normal", 1 / math.sqrt(M)),
        "layers/attn/wk": ((L, M, K, D), "normal", 1 / math.sqrt(M)),
        "layers/attn/wv": ((L, M, K, D), "normal", 1 / math.sqrt(M)),
        "layers/attn/wo": ((L, N, D, M), "normal", 1 / math.sqrt(N * D)),
        "layers/mlp/wi_gate": ((L, M, F), "normal", 1 / math.sqrt(M)),
        "layers/mlp/wi_up": ((L, M, F), "normal", 1 / math.sqrt(M)),
        "layers/mlp/wo": ((L, F, M), "normal", 1 / math.sqrt(F)),
    }
    if d["bias"]:
        s["layers/attn/bq"] = ((L, N, D), "normal", 0.1)
        s["layers/attn/bk"] = ((L, K, D), "normal", 0.1)
        s["layers/attn/bv"] = ((L, K, D), "normal", 0.1)
    return s


def _leaf(key, name, shape, init, scale):
    k = jax.random.fold_in(key, zlib.crc32(name.encode()))
    z = jax.random.normal(k, shape, jnp.float32)
    return 1.0 + scale * z if init == "around_one" else scale * z


def make_weights(c, key):
    """All weights, float32, as a flat ``{path: array}``, from ``key =
    jax.random.PRNGKey(seed)``.  Traceable: callers make them in one jitted
    call, with the key as an argument so that one program serves every
    seed."""
    specs = weight_specs(c)
    return {n: _leaf(key, n, *specs[n]) for n in specs}


# ---------------------------------------------------------------------------
# FLOPs
# ---------------------------------------------------------------------------


def step_flops(c, batch: int, seq: int) -> float:
    """Model FLOPs of one training step: 3 x forward (backward = 2 x
    forward); matmuls at 2 FLOPs per multiply-add; causal attention counts
    the S(S+1)/2 ~ S^2/2 score and value products it needs, once."""
    d = dims(c)
    L, M, N, K, D, F, V = (d[k] for k in "LMNKDFV")
    per_token = 2 * (L * (M * N * D + 2 * M * K * D + N * D * M + 3 * M * F)
                     + V * M)
    attn_per_seq = L * 2 * (2 * seq * seq * N * D) / 2
    return 3.0 * (per_token * batch * seq + attn_per_seq * batch)


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------


def _scaled_cast(x, dtype):
    """Round to an fp8 type with per-tensor scaling to its largest finite."""
    big = float(jnp.finfo(dtype).max)
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, big / amax, 1.0)
    return (x * s).astype(dtype).astype(jnp.float32) / s


@jax.custom_vjp
def _fp8(x):
    return _scaled_cast(x, jnp.float8_e4m3fn)


def _fp8_fwd(x):
    return _fp8(x), None


def _fp8_bwd(_, ct):
    return (_scaled_cast(ct, jnp.float8_e5m2),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def _mm(spec, a, b, matmul):
    if matmul == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (B, S, H, D); rotary on the whole head, halves [x1, x2]."""
    S, D = x.shape[1], x.shape[-1]
    half = D // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(d, matmul, x, lp):
    B, S, _ = x.shape
    G = d["N"] // d["K"]
    h = _rms_norm(x, lp["ln1"], d["eps"])
    q = _mm("bsm,mnd->bsnd", h, lp["attn/wq"], matmul)
    k = _mm("bsm,mkd->bskd", h, lp["attn/wk"], matmul)
    v = _mm("bsm,mkd->bskd", h, lp["attn/wv"], matmul)
    if d["bias"]:
        q, k, v = q + lp["attn/bq"], k + lp["attn/bk"], v + lp["attn/bv"]
    q, k = _rope(q, d["theta"]), _rope(k, d["theta"])
    q = q.reshape(B, S, d["K"], G, d["D"])
    s = _mm("bskgd,btkd->bkgst", q, k, matmul) / math.sqrt(d["D"])
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = _mm("bkgst,btkd->bskgd", p, v, matmul).reshape(B, S, d["N"], d["D"])
    x = x + _mm("bsnd,ndm->bsm", o, lp["attn/wo"], matmul)
    h = _rms_norm(x, lp["ln2"], d["eps"])
    g = _mm("bsm,mf->bsf", h, lp["mlp/wi_gate"], matmul)
    u = _mm("bsm,mf->bsf", h, lp["mlp/wi_up"], matmul)
    return x + _mm("bsf,fm->bsm", jax.nn.silu(g) * u, lp["mlp/wo"], matmul)


def reference_loss(c, w, tokens, labels, matmul="f32", rows=256):
    """Mean next-token cross entropy, layer by layer (rematerialised) and
    the logits in blocks of ``rows`` positions, so that it fits."""
    d = dims(c)
    layers = {n[len("layers/"):]: a for n, a in w.items()
              if n.startswith("layers/")}
    x = w["embed/embedding"][tokens]
    body = jax.checkpoint(lambda x, lp: (_layer(d, matmul, x, lp), None))
    x, _ = jax.lax.scan(body, x, layers)
    x = _rms_norm(x, w["final_ln"], d["eps"])
    B, S, M = x.shape
    rows = min(rows, S)
    nb = S // rows
    xb = jnp.moveaxis(x.reshape(B, nb, rows, M), 1, 0)
    lb = jnp.moveaxis(labels.reshape(B, nb, rows), 1, 0)

    @jax.checkpoint
    def block(total, xl):
        xc, lc = xl
        logits = _mm("brm,vm->brv", xc, w["embed/embedding"], matmul)
        lse = jax.nn.logsumexp(logits, -1)
        picked = jnp.take_along_axis(logits, lc[..., None], -1)[..., 0]
        return total + jnp.sum(lse - picked), None

    total, _ = jax.lax.scan(block, jnp.zeros((), jnp.float32), (xb, lb))
    return total / (B * S)


def _adafactor(opt, lr=1e-2, decay_pow=0.8, clip=1.0, eps=1e-30):
    """Adafactor as the program states it: second moments factored over the
    last two dims of every leaf with both > 1, beta2 = 1 - t^-0.8, update
    clipped to rms 1, step lr * max(rms(param), 1e-3), no momentum."""
    lr = float(opt.get("lr", lr))

    def factored(shape):
        return len(shape) >= 2 and shape[-1] > 1 and shape[-2] > 1

    def init(w):
        return {n: ({"vr": jnp.zeros(p.shape[:-1]),
                     "vc": jnp.zeros(p.shape[:-2] + p.shape[-1:])}
                    if factored(p.shape) else {"v": jnp.zeros(p.shape)})
                for n, p in w.items()}

    def rms(x):
        return jnp.sqrt(jnp.mean(x * x) + 1e-30)

    def update(g, s, w, t):
        beta2 = 1.0 - (t + 1.0) ** (-decay_pow)
        new_w, new_s = {}, {}
        for n in w:
            g2 = g[n] * g[n] + eps
            if factored(w[n].shape):
                vr = beta2 * s[n]["vr"] + (1 - beta2) * g2.mean(-1)
                vc = beta2 * s[n]["vc"] + (1 - beta2) * g2.mean(-2)
                row = vr / jnp.maximum(vr.mean(-1, keepdims=True), eps)
                u = g[n] * jax.lax.rsqrt(row[..., None] * vc[..., None, :]
                                         + eps)
                new_s[n] = {"vr": vr, "vc": vc}
            else:
                v = beta2 * s[n]["v"] + (1 - beta2) * g2
                u = g[n] * jax.lax.rsqrt(v + eps)
                new_s[n] = {"v": v}
            u = u / jnp.maximum(1.0, rms(u) / clip)
            new_w[n] = w[n] - lr * jnp.maximum(rms(w[n]), 1e-3) * u
        return new_w, new_s

    return init, update


@functools.lru_cache(maxsize=None)
def _reference_step(config_json: str, optimizer_json: str, matmul: str):
    c, optimizer = json.loads(config_json), json.loads(optimizer_json)
    if optimizer.get("name") != "adafactor":
        raise ValueError(f"reference: no optimizer {optimizer.get('name')!r}")
    init, update = _adafactor(optimizer)

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
    change of the weights over all the steps.  Jitted per step, so that
    the caller's placement of ``w0`` and the batches decides the devices."""
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
