"""Top-level model: embedding -> (leading dense stack) -> scanned decoder
stack -> LM head.

One class covers all assigned families (dense / moe / hybrid / ssm / vlm /
audio). Params are plain dict pytrees; every method is a pure function of
(params, inputs) so the FL core and pjit treat models uniformly.

Batch dicts:
  LM     : {"tokens": (B,S) int32, "labels": (B,S) int32}
  VLM    : + {"patches": (B,P,d_model)}   (stub frontend output; loss on text)
  audio  : {"frames": (B,S_enc,d_model)}  (stub conv/mel output) + tokens/labels
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import blocks
from repro.models.layers import (
    apply_norm,
    dense_init,
    embed_init,
    make_norm_params,
    matmul,
    sinusoidal_positions,
)


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ------------------------------------------------------------------ init
    def init(self, key) -> Dict[str, Any]:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.param_dtype)
        ks = jax.random.split(key, 8)
        p: Dict[str, Any] = {
            "embed": embed_init(ks[0], cfg.vocab_size, cfg.d_model, dtype),
            "final_norm": make_norm_params(cfg, cfg.d_model),
            "layers": blocks.init_stack(ks[1], cfg,
                                        cfg.num_layers - cfg.first_k_dense,
                                        cross=cfg.is_encdec),
        }
        if cfg.first_k_dense:
            p["dense_layers"] = blocks.init_stack(ks[5], cfg,
                                                  cfg.first_k_dense,
                                                  dense=True)
        if not cfg.tie_embeddings:
            p["lm_head"] = dense_init(ks[2], cfg.d_model, cfg.vocab_size, dtype)
        if cfg.num_patches:
            p["projector"] = dense_init(ks[3], cfg.d_model, cfg.d_model, dtype)
        if cfg.is_encdec:
            p["encoder"] = {
                "layers": blocks.init_stack(ks[4], cfg, cfg.encoder_layers),
                "final_norm": make_norm_params(cfg, cfg.d_model),
            }
        return p

    # ------------------------------------------------------------- embedding
    def _embed(self, params, tokens):
        cfg = self.cfg
        x = params["embed"][tokens]
        if cfg.family == "dense" and cfg.tie_embeddings:
            # gemma-style input scaling
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
        if not cfg.rope_theta:
            pos = jnp.arange(tokens.shape[1])
            x = x + sinusoidal_positions(pos, cfg.d_model)[None].astype(x.dtype)
        return x.astype(jnp.dtype(cfg.compute_dtype))

    @jax.named_scope("lm_head")
    def _logits(self, params, x):
        cfg = self.cfg
        x = apply_norm(cfg, params["final_norm"], x)
        if cfg.tie_embeddings:
            return x @ params["embed"].T
        return matmul(cfg, x, params["lm_head"])

    def _encode(self, params, frames):
        cfg = self.cfg
        pos = jnp.arange(frames.shape[1])
        x = frames.astype(jnp.dtype(cfg.compute_dtype))
        x = x + sinusoidal_positions(pos, cfg.d_model)[None].astype(x.dtype)
        x = blocks.run_encoder_stack(cfg, params["encoder"]["layers"], x)
        return apply_norm(cfg, params["encoder"]["final_norm"], x)

    # --------------------------------------------------------- full sequence
    def hidden(self, params, batch: Dict[str, jnp.ndarray],
               window: Optional[int] = None):
        """Full-sequence forward up to (and incl.) trimming non-text
        positions; returns (hidden (B,S,d), aux_loss) — no LM head."""
        x, aux, _ = self._hidden(params, batch, window)
        return x, aux

    def _hidden(self, params, batch, window):
        """``hidden`` and the MoE stack's per-layer stats."""
        cfg = self.cfg
        x = self._embed(params, batch["tokens"])
        enc_out = None
        if cfg.num_patches:
            patches = batch["patches"].astype(x.dtype) @ params["projector"]
            x = jnp.concatenate([patches, x], axis=1)
        if cfg.is_encdec:
            enc_out = self._encode(params, batch["frames"])
        aux0 = jnp.zeros((), jnp.float32)
        if cfg.first_k_dense:
            x, aux0, _ = blocks.run_stack_train(cfg, params["dense_layers"], x,
                                                window=window)
        x, aux, stats = blocks.run_stack_train(cfg, params["layers"], x,
                                               enc_out=enc_out, window=window)
        if cfg.num_patches:
            x = x[:, batch["patches"].shape[1]:]  # loss on text positions only
        return x, aux0 + aux, stats

    def apply(self, params, batch: Dict[str, jnp.ndarray],
              window: Optional[int] = None):
        """Full-sequence forward. Returns (logits, aux_loss)."""
        x, aux = self.hidden(params, batch, window=window)
        return self._logits(params, x), aux

    def prefill_logits(self, params, batch: Dict[str, jnp.ndarray],
                       window: Optional[int] = None):
        """Serving prefill: last-token logits only — the (B, S, V) logits
        tensor is never materialised (the LM head sees one position)."""
        x, _ = self.hidden(params, batch, window=window)
        return self._logits(params, x[:, -1:])

    def loss(self, params, batch, window: Optional[int] = None):
        """Mean next-token cross-entropy (+ MoE aux). Returns (loss, metrics).

        With ``cfg.ce_chunk > 0`` the LM-head matmul and the CE reduction are
        fused per token-chunk (lax.scan + remat), so the (T, V) logits tensor
        never exists in HBM — see EXPERIMENTS.md §Perf iteration 2.
        """
        labels = batch["labels"]
        x, aux, stats = self._hidden(params, batch, window)
        if not self.cfg.ce_chunk:
            logits = self._logits(params, x)
            lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
            gold = jnp.take_along_axis(logits.astype(jnp.float32),
                                       labels[..., None], axis=-1)[..., 0]
            ce = jnp.mean(lse - gold)
            return ce + aux, {"ce": ce, "aux": aux, **stats}

        cfg = self.cfg
        x = apply_norm(cfg, params["final_norm"], x)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        b, s, d = x.shape
        t = b * s
        chunk = min(cfg.ce_chunk, t)
        nc = -(-t // chunk)
        pad = nc * chunk - t
        xf = x.reshape(t, d)
        lf = labels.reshape(t)
        valid = jnp.ones((t,), jnp.float32)
        if pad:
            xf = jnp.pad(xf, ((0, pad), (0, 0)))
            lf = jnp.pad(lf, (0, pad))
            valid = jnp.pad(valid, (0, pad))
        xc = xf.reshape(nc, chunk, d)
        lc = lf.reshape(nc, chunk)
        vc = valid.reshape(nc, chunk)

        def body(acc, inp):
            xi, li, vi = inp
            lg = (xi @ head).astype(jnp.float32)  # (chunk, V) — chunk-local
            lse = jax.nn.logsumexp(lg, axis=-1)
            gold = jnp.take_along_axis(lg, li[:, None], axis=-1)[:, 0]
            return acc + jnp.sum((lse - gold) * vi), None

        total, _ = jax.lax.scan(jax.checkpoint(body), jnp.zeros((), jnp.float32),
                                (xc, lc, vc))
        ce = total / t
        return ce + aux, {"ce": ce, "aux": aux, **stats}

    # ------------------------------------------------------------------ serve
    def init_cache(self, batch: int, cache_len: int):
        """The stack's layer-stacked cache; with leading dense layers, theirs
        under ``dense_layers``."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.compute_dtype)
        cache = blocks.init_stack_cache(cfg, cfg.num_layers - cfg.first_k_dense,
                                        batch, cache_len, dtype,
                                        cross=cfg.is_encdec)
        if cfg.first_k_dense:
            cache["dense_layers"] = blocks.init_stack_cache(
                cfg, cfg.first_k_dense, batch, cache_len, dtype)
        return cache

    def prefill_cross(self, params, cache, frames):
        """Enc-dec only: run the encoder, fill per-layer cross K/V caches."""
        from repro.models.attention import precompute_cross_kv

        enc_out = self._encode(params, frames)

        def per_layer(layer_p):
            return precompute_cross_kv(self.cfg, layer_p["xattn"], enc_out)

        cross = jax.vmap(per_layer)(params["layers"])
        new = dict(cache)
        new["cross"] = cross
        return new

    def decode_step(self, params, cache, tokens, pos):
        """tokens: (B,1) int32; pos: scalar int32. Returns (logits, cache)."""
        cfg = self.cfg
        x = params["embed"][tokens]
        if cfg.family == "dense" and cfg.tie_embeddings:
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
        if not cfg.rope_theta:
            x = x + sinusoidal_positions(jnp.full((1,), pos), cfg.d_model)[None].astype(x.dtype)
        x = x.astype(jnp.dtype(cfg.compute_dtype))
        cache = dict(cache)
        dense = cache.pop("dense_layers", None)
        if dense is not None:
            x, dense = blocks.run_stack_decode(cfg, params["dense_layers"], x,
                                               dense, pos)
        x, cache = blocks.run_stack_decode(cfg, params["layers"], x, cache, pos)
        if dense is not None:
            cache["dense_layers"] = dense
        return self._logits(params, x), cache

    # ------------------------------------------------------------- utilities
    def param_count(self, params=None) -> int:
        if params is None:
            params = jax.eval_shape(self.init, jax.random.PRNGKey(0))
        return sum(int(x.size) for x in jax.tree.leaves(params))

    def active_param_count(self, params=None) -> int:
        """Params touched per token (MoE: shared + top-k routed only)."""
        cfg = self.cfg
        if params is None:
            params = jax.eval_shape(self.init, jax.random.PRNGKey(0))
        total = self.param_count(params)
        if not cfg.is_moe:
            return total
        expert_leaves = ["w_gate", "w_up", "w_down"]
        moe = params["layers"]["moe"]
        routed = sum(int(moe[k].size) for k in expert_leaves)
        active = routed * cfg.experts_per_token // cfg.held_experts[1]
        return total - routed + active


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
