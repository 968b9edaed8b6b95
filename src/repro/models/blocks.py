"""Decoder/encoder blocks and the scanned layer stack.

Each architecture family maps to one homogeneous block type so the whole
stack is a single ``lax.scan`` over layer-stacked parameters — compact HLO
at any depth (80-layer qwen1.5-110b lowers as one loop), remat-friendly.
Leading dense layers of an MoE model (``cfg.first_k_dense``) are a second,
dense stack scanned in front of it (``models/model.py``); a layer's FFN
kind follows its parameters (``"moe"`` or ``"mlp"``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import apply_mlp, apply_norm, init_mlp, make_norm_params


# ---------------------------------------------------------------------------
# per-layer init
# ---------------------------------------------------------------------------


def init_layer(key, cfg, cross: bool = False, dense: bool = False):
    """One decoder layer's params for the cfg's family; ``dense`` gives an
    MoE model's leading dense layer (an MLP of width ``d_ff``)."""
    ks = jax.random.split(key, 8)
    p = {}
    if cfg.is_ssm_only:
        p["norm1"] = make_norm_params(cfg, cfg.d_model)
        p["ssm"] = ssm_mod.init_ssm(ks[0], cfg)
        return p
    p["norm1"] = make_norm_params(cfg, cfg.d_model)
    p["attn"] = attn.init_attention(ks[0], cfg)
    if cfg.is_hybrid:
        p["ssm"] = ssm_mod.init_ssm(ks[1], cfg)
        p["fuse_norm_a"] = make_norm_params(cfg, cfg.d_model)
        p["fuse_norm_s"] = make_norm_params(cfg, cfg.d_model)
    if cross:
        p["norm_x"] = make_norm_params(cfg, cfg.d_model)
        p["xattn"] = attn.init_attention(ks[2], cfg, cross=True)
    p["norm2"] = make_norm_params(cfg, cfg.d_model)
    if cfg.is_moe and not dense:
        p["moe"] = moe_mod.init_moe(ks[3], cfg)
        if cfg.dense_residual_ff:
            p["mlp"] = init_mlp(ks[4], cfg)
    else:
        p["mlp"] = init_mlp(ks[4], cfg)
    return p


def init_stack(key, cfg, num_layers: int, cross: bool = False,
               dense: bool = False):
    keys = jax.random.split(key, num_layers)
    return jax.vmap(lambda k: init_layer(k, cfg, cross=cross,
                                         dense=dense))(keys)


# ---------------------------------------------------------------------------
# full-sequence (train / prefill) block application
# ---------------------------------------------------------------------------


def _mixer_train(cfg, p, x, window):
    """Token mixer (attn / ssm / hybrid) with pre-norm + residual."""
    h = apply_norm(cfg, p["norm1"], x)
    if cfg.is_ssm_only:
        return x + ssm_mod.ssm_train(cfg, p["ssm"], h)
    if cfg.is_hybrid:
        a = attn.attention_train(cfg, p["attn"], h, window=window)
        s = ssm_mod.ssm_train(cfg, p["ssm"], h)
        fused = 0.5 * (apply_norm(cfg, p["fuse_norm_a"], a) +
                       apply_norm(cfg, p["fuse_norm_s"], s))
        return x + fused
    return x + attn.attention_train(cfg, p["attn"], h, window=window)


def _ffn_train(cfg, p, x):
    """Returns (x, aux_loss, stats): ``stats`` holds the held experts'
    routed-pair counts (``expert_counts``) where the layer has them."""
    zero = jnp.zeros((), jnp.float32)
    if cfg.is_ssm_only:
        return x, zero, {}  # mamba block subsumes the MLP
    h = apply_norm(cfg, p["norm2"], x)
    if "moe" in p and cfg.experts_held:
        y, aux, counts = moe_mod.moe_held_ffn(cfg, p["moe"], h)
        return x + y, aux, {"expert_counts": counts}
    if "moe" in p:
        y, aux = moe_mod.moe_ffn(cfg, p["moe"], h)
        if cfg.dense_residual_ff:
            y = y + apply_mlp(cfg, p["mlp"], h)
        return x + y, aux, {}
    with jax.named_scope("dense_ffn"):
        return x + apply_mlp(cfg, p["mlp"], x=h), zero, {}


def decoder_layer_train(cfg, p, x, enc_out=None, causal: bool = True,
                        window: Optional[int] = None):
    """Returns (x, aux_loss, stats). enc_out enables cross-attention."""
    x = _mixer_train(cfg, p, x, window)
    if enc_out is not None and "xattn" in p:
        h = apply_norm(cfg, p["norm_x"], x)
        x = x + attn.attention_train(cfg, p["xattn"], h, kv_x=enc_out, causal=False)
    return _ffn_train(cfg, p, x)


def encoder_layer_train(cfg, p, x):
    h = apply_norm(cfg, p["norm1"], x)
    x = x + attn.attention_train(cfg, p["attn"], h, causal=False)
    x, _, _ = _ffn_train(cfg, p, x)
    return x


def run_stack_train(cfg, stacked, x, enc_out=None, causal: bool = True,
                    window: Optional[int] = None, remat: bool = True):
    """Scan the layer stack. Returns (x, total_aux, stats), ``stats``
    each layer's stats stacked on a leading layer axis.

    ``cfg.remat_block = G`` enables sqrt-remat: an outer (checkpointed) scan
    over L/G layer groups and an inner scan over the G layers of a group —
    only L/G boundary activations are saved for the backward pass; the G
    within-group carries are rematerialised transiently (EXPERIMENTS.md
    §Perf). G=0 checkpoints every layer (the baseline).
    """

    def body(carry, layer_p):
        h, aux = carry
        h, a, stats = decoder_layer_train(cfg, layer_p, h, enc_out=enc_out,
                                          causal=causal, window=window)
        return (h, aux + a), stats

    init = (x, jnp.zeros((), jnp.float32))
    g = getattr(cfg, "remat_block", 0)
    nl = jax.tree.leaves(stacked)[0].shape[0]
    if remat and g and g > 1 and nl % g == 0:
        grouped = jax.tree.map(
            lambda a: a.reshape(nl // g, g, *a.shape[1:]), stacked)

        def group_body(carry, gp):
            # inner body checkpointed too: during the group's backward only
            # the G carry boundaries go live, never full layer residuals
            return jax.lax.scan(jax.checkpoint(body), carry, gp)

        (x, aux), stats = jax.lax.scan(jax.checkpoint(group_body), init,
                                       grouped)
        return x, aux, jax.tree.map(
            lambda a: a.reshape(nl, *a.shape[2:]), stats)
    fn = jax.checkpoint(body) if remat else body
    (x, aux), stats = jax.lax.scan(fn, init, stacked)
    return x, aux, stats


def run_encoder_stack(cfg, stacked, x, remat: bool = True):
    def body(h, layer_p):
        return encoder_layer_train(cfg, layer_p, h), None

    fn = jax.checkpoint(body) if remat else body
    x, _ = jax.lax.scan(fn, x, stacked)
    return x


# ---------------------------------------------------------------------------
# decode (single token) block application
# ---------------------------------------------------------------------------


def init_layer_cache(cfg, batch: int, cache_len: int, dtype, cross: bool = False):
    c = {}
    if not cfg.is_ssm_only:
        c["kv"] = attn.init_kv_cache(cfg, batch, cache_len, dtype)
    if cfg.is_ssm_only or cfg.is_hybrid:
        c["ssm"] = ssm_mod.init_ssm_cache(cfg, batch, dtype)
    if cross:
        hd = cfg.resolved_head_dim
        c["cross"] = {
            "k": jnp.zeros((batch, cfg.encoder_seq_len, cfg.num_kv_heads, hd), dtype),
            "v": jnp.zeros((batch, cfg.encoder_seq_len, cfg.num_kv_heads, hd), dtype),
        }
    return c


def init_stack_cache(cfg, num_layers: int, batch: int, cache_len: int, dtype,
                     cross: bool = False):
    """Layer-stacked cache pytree (leading axis L) for lax.scan decode."""
    one = init_layer_cache(cfg, batch, cache_len, dtype, cross=cross)
    return jax.tree.map(lambda a: jnp.broadcast_to(a, (num_layers,) + a.shape), one)


def decoder_layer_decode(cfg, p, x, cache, pos):
    """x: (B,1,d). Returns (x, cache)."""
    h = apply_norm(cfg, p["norm1"], x)
    new_cache = dict(cache)
    if cfg.is_ssm_only:
        y, new_cache["ssm"] = ssm_mod.ssm_decode(cfg, p["ssm"], h, cache["ssm"])
        return x + y, new_cache
    if cfg.is_hybrid:
        a, new_cache["kv"] = attn.attention_decode(cfg, p["attn"], h, cache["kv"], pos)
        s, new_cache["ssm"] = ssm_mod.ssm_decode(cfg, p["ssm"], h, cache["ssm"])
        x = x + 0.5 * (apply_norm(cfg, p["fuse_norm_a"], a) +
                       apply_norm(cfg, p["fuse_norm_s"], s))
    else:
        a, new_cache["kv"] = attn.attention_decode(cfg, p["attn"], h, cache["kv"], pos)
        x = x + a
    if "xattn" in p and "cross" in cache:
        h = apply_norm(cfg, p["norm_x"], x)
        x = x + attn.cross_attention_decode(cfg, p["xattn"], h, cache["cross"])
    h = apply_norm(cfg, p["norm2"], x)
    if "moe" in p and cfg.experts_held:
        y, _, _ = moe_mod.moe_held_ffn(cfg, p["moe"], h)
        x = x + y
    elif "moe" in p:
        y, _ = moe_mod.moe_ffn(cfg, p["moe"], h)
        if cfg.dense_residual_ff:
            y = y + apply_mlp(cfg, p["mlp"], h)
        x = x + y
    else:
        x = x + apply_mlp(cfg, p["mlp"], h)
    return x, new_cache


def run_stack_decode(cfg, stacked, x, caches, pos):
    """Scan layers carrying x, threading per-layer caches. Returns (x, caches)."""

    def body(h, inp):
        layer_p, layer_c = inp
        h, new_c = decoder_layer_decode(cfg, layer_p, h, layer_c, pos)
        return h, new_c

    x, new_caches = jax.lax.scan(body, x, (stacked, caches))
    return x, new_caches
