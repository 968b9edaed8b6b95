"""Attention: GQA/MQA, qk-norm, optional bias, RoPE, causal / bidirectional /
sliding-window, chunked (flash-style, O(S) memory) training path, and
single-token decode against full or ring KV caches. Latent attention
(MLA, DeepSeek-V2: low-rank KV, a rope key shared across heads, YaRN)
when ``cfg.kv_lora_rank`` is set.

Long causal sequences (``causal_long``) run a block-skipping Pallas kernel
with its own backward on the TPU (``repro.kernels.flash_attn.ops.
causal_attention``). Elsewhere, and for lengths its block does not divide,
they run the chunked path: pure JAX (lax.scan + online softmax), so it
lowers on any backend, and the tests' reference for the kernel.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attn import ops as flash_ops
from repro.models.layers import (
    apply_rope,
    dense_init,
    matmul,
    rms_norm,
    rope_frequencies,
    yarn_frequencies,
    yarn_mscale,
)

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


def init_attention(key, cfg, cross: bool = False):
    if cfg.kv_lora_rank:
        return init_mla(key, cfg)
    dtype = jnp.dtype(cfg.param_dtype)
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "wq": dense_init(kq, d, cfg.num_heads * hd, dtype),
        "wk": dense_init(kk, d, cfg.num_kv_heads * hd, dtype),
        "wv": dense_init(kv, d, cfg.num_kv_heads * hd, dtype),
        "wo": dense_init(ko, cfg.num_heads * hd, d, dtype, scale=(cfg.num_heads * hd) ** -0.5),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.num_heads * hd,), dtype)
        p["bk"] = jnp.zeros((cfg.num_kv_heads * hd,), dtype)
        p["bv"] = jnp.zeros((cfg.num_kv_heads * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), jnp.float32)
        p["k_norm"] = jnp.zeros((hd,), jnp.float32)
    return p


def _project_q(cfg, p, x):
    hd = cfg.resolved_head_dim
    q = x @ p["wq"]
    if cfg.qkv_bias:
        q = q + p["bq"]
    q = q.reshape(x.shape[:-1] + (cfg.num_heads, hd))
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
    return q


def _project_kv(cfg, p, x):
    hd = cfg.resolved_head_dim
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        k = k + p["bk"]
        v = v + p["bv"]
    k = k.reshape(x.shape[:-1] + (cfg.num_kv_heads, hd))
    v = v.reshape(x.shape[:-1] + (cfg.num_kv_heads, hd))
    if cfg.qk_norm:
        k = rms_norm(k, p["k_norm"])
    return k, v


def _repeat_kv(x, n_rep: int):
    if n_rep == 1:
        return x
    b, s, h, d = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(b, s, h * n_rep, d)


# ---------------------------------------------------------------------------
# core attention maths
# ---------------------------------------------------------------------------


def _full_attention(q, k, v, causal: bool, q_offset: int = 0,
                    window: int = 0, scale: Optional[float] = None):
    """Materialised-scores attention. q,k:(B,S,H,D) v:(B,Sk,H,Dv)."""
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale,
                        k.astype(jnp.float32))
    if causal:
        qpos = jnp.arange(q.shape[1]) + q_offset
        kpos = jnp.arange(k.shape[1])
        mask = qpos[:, None] >= kpos[None, :]
        if window:
            mask &= kpos[None, :] > qpos[:, None] - window
        scores = jnp.where(mask[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out


def _chunked_causal_attention(q, k, v, q_chunk: int,
                              scale: Optional[float] = None):
    """Flash-style online-softmax over q chunks; kv masked per chunk.

    O(S * q_chunk) live memory. Scans q chunks; each chunk attends to the
    full (masked) key range — the upper-triangle overcount is accepted and
    accounted for in the roofline notes. The chunk body is checkpointed:
    the backward pass recomputes each chunk's scores instead of keeping
    them.
    """
    b, s, h, d = q.shape
    dv = v.shape[-1]
    scale = d ** -0.5 if scale is None else scale
    nq = s // q_chunk
    qs = q.reshape(b, nq, q_chunk, h, d).transpose(1, 0, 2, 3, 4)  # (nq,B,c,H,D)
    kpos = jnp.arange(k.shape[1])

    def body(carry, inp):
        qc, i = inp
        qpos = i * q_chunk + jnp.arange(q_chunk)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qc.astype(jnp.float32) * scale,
                            k.astype(jnp.float32))
        mask = qpos[:, None] >= kpos[None, :]
        scores = jnp.where(mask[None, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
        return carry, out

    _, outs = jax.lax.scan(jax.checkpoint(body), 0, (qs, jnp.arange(nq)))
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, s, h, dv)


def _sliding_window_attention(q, k, v, window: int, q_chunk: int):
    """Causal SWA with exact banded compute: each q chunk slices the
    (window + chunk)-length kv band it can see — no full-S scores."""
    b, s, h, d = q.shape
    band = window + q_chunk
    # left-pad kv by `window` so band slicing is always in range
    kp = jnp.pad(k, ((0, 0), (window, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (window, 0), (0, 0), (0, 0)))
    nq = s // q_chunk
    qs = q.reshape(b, nq, q_chunk, h, d).transpose(1, 0, 2, 3, 4)

    def body(carry, inp):
        qc, i = inp
        start = i * q_chunk  # band start in padded coords
        kb = jax.lax.dynamic_slice_in_dim(kp, start, band, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(vp, start, band, axis=1)
        scale = d ** -0.5
        scores = jnp.einsum("bqhd,bkhd->bhqk", qc.astype(jnp.float32) * scale,
                            kb.astype(jnp.float32))
        qpos = start + window + jnp.arange(q_chunk)  # padded absolute pos
        kpos = start + jnp.arange(band)
        mask = (qpos[:, None] >= kpos[None, :]) & \
               (kpos[None, :] > qpos[:, None] - window) & \
               (kpos[None, :] >= window)  # drop the padding region
        scores = jnp.where(mask[None, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(vb.dtype), vb)
        return carry, out

    _, outs = jax.lax.scan(jax.checkpoint(body), 0, (qs, jnp.arange(nq)))
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, s, h, d)


FULL_ATTN_MAX_SEQ = 4096  # above this, causal_long is used
Q_CHUNK = 512


def causal_long(q, k, v, scale: Optional[float] = None):
    """Causal attention over a sequence too long for materialised scores:
    the Pallas kernel on the TPU when its block divides S, else the
    chunked XLA scan. Returns v's dtype, as the chunked path does."""
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    if jax.default_backend() == "tpu" and flash_ops.causal_fits(q.shape[1]):
        return flash_ops.causal_attention(q, k, v, scale).astype(v.dtype)
    return _chunked_causal_attention(q, k, v, Q_CHUNK, scale=scale)


def attention_train(cfg, p, x, kv_x=None, causal: bool = True,
                    positions: Optional[jnp.ndarray] = None,
                    window: Optional[int] = None):
    """Self (or cross, via kv_x) attention over a full sequence."""
    if cfg.kv_lora_rank:
        return mla_train(cfg, p, x, positions)
    b, s, _ = x.shape
    q = _project_q(cfg, p, x)
    k, v = _project_kv(cfg, p, kv_x if kv_x is not None else x)
    if positions is None:
        positions = jnp.arange(s)[None, :]
    if cfg.rope_theta and kv_x is None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    n_rep = cfg.num_heads // cfg.num_kv_heads
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    window = window if window is not None else cfg.attn_window
    if window and causal:
        qc = min(Q_CHUNK, s)
        out = _sliding_window_attention(q, k, v, window, qc) if s > qc \
            else _full_attention(q, k, v, causal=True, window=window)
    elif causal and (s > FULL_ATTN_MAX_SEQ or
                     (getattr(cfg, "force_chunked_attn", False) and s > Q_CHUNK)):
        out = causal_long(q, k, v)
    else:
        out = _full_attention(q, k, v, causal=causal)
    return out.reshape(b, s, -1) @ p["wo"]


# ---------------------------------------------------------------------------
# latent attention (MLA, DeepSeek-V2)
# ---------------------------------------------------------------------------
# q = x Wq, per head [q_nope | q_pe]; [c_kv | k_pe] = x Wkv_a with c_kv
# RMS-normed; [k_nope | v] = c_kv Wkv_b per head; rope on q_pe and on the
# one k_pe that every head shares; k = [k_nope | k_pe]; causal softmax;
# out Wo. There is no q_lora path (DeepSeek-V2-Lite has none).


def init_mla(key, cfg):
    dtype = jnp.dtype(cfg.param_dtype)
    d, h, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    kq, ka, kb, ko = jax.random.split(key, 4)
    return {
        "wq": dense_init(kq, d, h * (dn + dr), dtype),
        "wkv_a": dense_init(ka, d, r + dr, dtype),
        "kv_norm": jnp.zeros((r,), jnp.float32),
        "wkv_b": dense_init(kb, r, h * (dn + dv), dtype),
        "wo": dense_init(ko, h * dv, d, dtype, scale=(h * dv) ** -0.5),
    }


def mla_rope(cfg):
    """MLA's rope: (inverse frequencies, cos/sin factor), YaRN when
    ``cfg.rope_yarn`` is set."""
    dr, y = cfg.qk_rope_head_dim, cfg.rope_yarn
    if y is None:
        return rope_frequencies(dr, cfg.rope_theta), 1.0
    return (jnp.asarray(yarn_frequencies(dr, cfg.rope_theta, y)),
            yarn_mscale(y.factor, y.mscale)
            / yarn_mscale(y.factor, y.mscale_all_dim))


def mla_softmax_scale(cfg) -> float:
    """(q/k head dim)^-1/2, times YaRN's mscale(factor, mscale_all_dim)^2."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    y = cfg.rope_yarn
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def _deinterleave(x):
    """Rope pairs (x0, x1), (x2, x3), ... to (x0, x2, ..., x1, x3, ...):
    DeepSeek-V2 permutes its rope dims so before the rotate-half rope."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


def _mla_qkv(cfg, p, x, positions):
    """q, k: (B, S, H, dn + dr); v: (B, S, H, dv)."""
    b, s, _ = x.shape
    h, r = cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    q = matmul(cfg, x, p["wq"]).reshape(b, s, h, dn + dr)
    kv_a = matmul(cfg, x, p["wkv_a"])
    c_kv = rms_norm(kv_a[..., :r], p["kv_norm"])
    kv = matmul(cfg, c_kv, p["wkv_b"]).reshape(b, s, h, dn + dv)
    freqs, cs = mla_rope(cfg)
    q_pe = apply_rope(_deinterleave(q[..., dn:]), positions, cfg.rope_theta,
                      freqs, cs)
    k_pe = apply_rope(_deinterleave(kv_a[:, :, None, r:]), positions,
                      cfg.rope_theta, freqs, cs)  # (B, S, 1, dr)
    q = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_pe, (b, s, h, dr))], axis=-1)
    return q, k, kv[..., dn:]


@jax.named_scope("mla")
def mla_train(cfg, p, x, positions=None):
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)[None, :]
    q, k, v = _mla_qkv(cfg, p, x, positions)
    scale = mla_softmax_scale(cfg)
    if s > FULL_ATTN_MAX_SEQ or (cfg.force_chunked_attn and s > Q_CHUNK):
        out = causal_long(q, k, v, scale)
    else:
        out = _full_attention(q, k, v, causal=True, scale=scale)
    return matmul(cfg, out.reshape(b, s, -1), p["wo"])


@jax.named_scope("mla")
def mla_decode(cfg, p, x, cache, pos):
    """One token against a cache of the expanded per-head k and v (no
    latent cache is kept)."""
    b = x.shape[0]
    q, k_new, v_new = _mla_qkv(cfg, p, x, jnp.full((b, 1), pos))
    k = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], k_new.astype(cache["k"].dtype), pos, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v_new.astype(cache["v"].dtype), pos, axis=1)
    scores = jnp.einsum("bqhd,bkhd->bhqk",
                        q.astype(jnp.float32) * mla_softmax_scale(cfg),
                        k.astype(jnp.float32))
    valid = jnp.arange(k.shape[1]) <= pos
    scores = jnp.where(valid[None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return matmul(cfg, out.reshape(b, 1, -1), p["wo"]), {"k": k, "v": v}


# ---------------------------------------------------------------------------
# decode (single new token against a KV cache)
# ---------------------------------------------------------------------------


def init_kv_cache(cfg, batch: int, cache_len: int, dtype):
    """Full cache, or ring cache of size ``attn_window`` when SWA."""
    if cfg.kv_lora_rank:
        h = cfg.num_heads
        dk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        return {"k": jnp.zeros((batch, cache_len, h, dk), dtype),
                "v": jnp.zeros((batch, cache_len, h, cfg.v_head_dim), dtype)}
    hd = cfg.resolved_head_dim
    length = min(cache_len, cfg.attn_window) if cfg.attn_window else cache_len
    shape = (batch, length, cfg.num_kv_heads, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def attention_decode(cfg, p, x, cache, pos):
    """x: (B, 1, d); pos: scalar int32 current position. Returns (y, cache).

    Cache semantics: full cache writes at index ``pos``; ring (SWA) cache
    writes at ``pos % window`` and masks by recency.
    """
    if cfg.kv_lora_rank:
        return mla_decode(cfg, p, x, cache, pos)
    b = x.shape[0]
    q = _project_q(cfg, p, x)  # (B,1,H,Dh)
    k_new, v_new = _project_kv(cfg, p, x)  # (B,1,Hkv,Dh)
    if cfg.rope_theta:
        pp = jnp.full((b, 1), pos)
        q = apply_rope(q, pp, cfg.rope_theta)
        k_new = apply_rope(k_new, pp, cfg.rope_theta)
    length = cache["k"].shape[1]
    write_idx = (pos % length) if cfg.attn_window else pos
    k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new.astype(cache["k"].dtype), write_idx, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new.astype(cache["v"].dtype), write_idx, axis=1)
    n_rep = cfg.num_heads // cfg.num_kv_heads
    kk = _repeat_kv(k, n_rep)
    vv = _repeat_kv(v, n_rep)
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale,
                        kk.astype(jnp.float32))  # (B,H,1,L)
    slot = jnp.arange(length)
    if cfg.attn_window:
        valid = slot <= pos if length > 0 else slot < 0  # ring: all slots <= pos written
        # slots hold positions pos-window+1..pos (mod window) once warm
        valid = jnp.minimum(pos + 1, length) > ((write_idx - slot) % length)
    else:
        valid = slot <= pos
    scores = jnp.where(valid[None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(vv.dtype), vv)
    y = out.reshape(b, 1, -1) @ p["wo"]
    return y, {"k": k, "v": v}


def cross_attention_decode(cfg, p, x, enc_kv):
    """Decoder cross-attention against precomputed encoder K/V."""
    b = x.shape[0]
    q = _project_q(cfg, p, x)
    k, v = enc_kv["k"], enc_kv["v"]
    n_rep = cfg.num_heads // cfg.num_kv_heads
    kk = _repeat_kv(k, n_rep)
    vv = _repeat_kv(v, n_rep)
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale,
                        kk.astype(jnp.float32))
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(vv.dtype), vv)
    return out.reshape(b, x.shape[1], -1) @ p["wo"]


def precompute_cross_kv(cfg, p, enc_out):
    k, v = _project_kv(cfg, p, enc_out)
    return {"k": k, "v": v}
