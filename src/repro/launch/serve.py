"""Serving launcher: autoregressive decode of the trained global model,
a batch of prompts at a time (what a deployed FL system does with the
aggregated weights).

Smoke mode runs a reduced config on CPU: prefill via decode loop over the
prompt, then N generation steps, reporting tokens/s.

Example:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --smoke \
      --batch 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import smoke_variant
from repro.configs.registry import get_arch
from repro.launch.mesh import make_host_mesh
from repro.models.model import build_model


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()

    arch = get_arch(args.arch)
    cfg = smoke_variant(arch.model) if args.smoke else arch.model
    model = build_model(cfg)
    mesh = make_host_mesh()
    rng = np.random.default_rng(0)
    # split BEFORE init: the sampling stream must never reuse the key the
    # parameter init consumed
    key, init_key = jax.random.split(jax.random.PRNGKey(0))

    params = model.init(init_key)
    cache_len = args.prompt_len + args.gen
    cache = model.init_cache(args.batch, cache_len)
    if cfg.is_encdec:
        frames = jnp.asarray(rng.normal(
            size=(args.batch, cfg.encoder_seq_len, cfg.d_model)), jnp.float32)
        cache = model.prefill_cross(params, cache, frames)

    step = jax.jit(model.decode_step, donate_argnums=1)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                      (args.batch, args.prompt_len)), jnp.int32)

    with mesh:
        # prefill by stepping the prompt through the cache
        t0 = time.time()
        logits = None
        for i in range(args.prompt_len):
            logits, cache = step(params, cache, prompt[:, i:i + 1], jnp.int32(i))
        t_prefill = time.time() - t0

        # autoregressive generation
        tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        out = [tok]
        t0 = time.time()
        for g in range(args.gen):
            pos = jnp.int32(args.prompt_len + g)
            logits, cache = step(params, cache, tok, pos)
            if args.temperature > 0:
                key, sub = jax.random.split(key)
                tok = jax.random.categorical(
                    sub, logits[:, -1] / args.temperature)[:, None].astype(jnp.int32)
            else:
                tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
            out.append(tok)
        jax.block_until_ready(tok)
        t_gen = time.time() - t0

    toks = np.concatenate([np.asarray(t) for t in out], axis=1)
    print(f"prefill: {args.prompt_len} steps in {t_prefill:.2f}s; "
          f"decode: {args.batch * args.gen / t_gen:.1f} tok/s")
    print("generated token ids (first row):", toks[0].tolist())


if __name__ == "__main__":
    main()
