"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
        --reduce 8 --steps 100 --ckpt-dir /tmp/ckpt

``--reduce k`` divides layers/width/vocab by ~k for CPU-runnable examples;
``--reduce 1`` trains the published config (qwen1.5-0.5b trains that way on
one TPU chip).  JAX's persistent compilation cache
is on for every run (:func:`enable_compile_cache`).
"""
from __future__ import annotations

import argparse
import os
import pathlib
import time

import jax
import jax.numpy as jnp

from repro.configs.base import get_strategy
from repro.configs.registry import default_strategy, get_config
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.train.loop import TrainConfig, TrainLoop
from repro.train.optimizer import get_optimizer


# fixed, inside the checkout: a cache directory that moves never hits
DEFAULT_COMPILE_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets nothing; otherwise the cache goes to :data:`DEFAULT_COMPILE_CACHE`.
    Entry points call this from ``main()``, never at import.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_COMPILE_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def reduced_config(cfg, k: int):
    if k <= 1:
        return cfg
    def div(x, lo=1):
        return max(x // k, lo)
    kw = dict(
        num_layers=max(cfg.num_layers // k, 2),
        d_model=max(cfg.d_model // k, 64),
        d_ff=max(cfg.d_ff // k, 128) if cfg.d_ff else 0,
        vocab_size=max(cfg.vocab_size // k, 512),
        num_heads=max(cfg.num_heads // max(k // 2, 1), 2) if cfg.num_heads else 0,
        attn_chunk=256,
    )
    if cfg.num_kv_heads:
        kw["num_kv_heads"] = min(max(cfg.num_kv_heads // max(k // 2, 1), 1), kw["num_heads"])
        while kw["num_heads"] % kw["num_kv_heads"]:
            kw["num_kv_heads"] -= 1
    if cfg.moe:
        kw["num_experts"] = max(cfg.num_experts // k, 4)
        kw["top_k"] = min(cfg.top_k, kw["num_experts"])
        if cfg.moe_every > 1:  # keep superblock divisibility
            sb = cfg.moe_every
            kw["num_layers"] = max(kw["num_layers"] // sb * sb, sb)
    if cfg.encoder_layers:
        kw["encoder_layers"] = max(cfg.encoder_layers // k, 2)
    if cfg.num_prefix_tokens:
        kw["num_prefix_tokens"] = max(cfg.num_prefix_tokens // k, 4)
    if cfg.family == "hybrid":
        kw["num_layers"] = max((cfg.num_layers // k) // 8 * 8, 8)
    return cfg.with_(**kw)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--strategy", default=None)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--reduce", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--optimizer", default="adafactor")
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--fail-at-step", type=int, default=-1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--data-pattern", default="uniform",
                    choices=["uniform", "arithmetic"])
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = reduced_config(get_config(args.arch), args.reduce)
    st = get_strategy(args.strategy or default_strategy(args.arch))
    opt = get_optimizer(args.optimizer, lr=args.lr)
    tc = TrainConfig(
        steps=args.steps, ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        grad_accum=args.grad_accum, compress_grads=args.compress_grads,
        fail_at_step=args.fail_at_step,
    )
    pipe = TokenPipeline(
        DataConfig(cfg.vocab_size, args.seq, args.batch, seed=args.seed,
                   pattern=args.data_pattern)
    )
    loop = TrainLoop(
        cfg, st, opt, tc, pipe, rng=jax.random.PRNGKey(args.seed),
        hooks={"log": print, "straggler": lambda s, dt, med: print(
            f"[straggler] step {s}: {dt:.2f}s vs median {med:.2f}s")},
    )
    t0 = time.time()
    state, losses = loop.run()
    dt = time.time() - t0
    dev = jax.devices()[0]
    print(f"done on {dev.platform} ({dev.device_kind}): {len(losses)} steps "
          f"in {dt:.1f}s; loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    return losses, loop.step_times


if __name__ == "__main__":
    main()
