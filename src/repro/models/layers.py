"""Shared neural-net layers (pure JAX, params are plain dict pytrees)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def dense_init(key, in_dim, out_dim, dtype, scale=None):
    scale = scale if scale is not None else in_dim ** -0.5
    return (jax.random.normal(key, (in_dim, out_dim), jnp.float32) * scale).astype(dtype)


def embed_init(key, vocab, dim, dtype):
    return (jax.random.normal(key, (vocab, dim), jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms (computed in f32, cast back)
# ---------------------------------------------------------------------------


def rms_norm(x, weight, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + weight.astype(jnp.float32))).astype(x.dtype)


def layer_norm(x, weight, bias, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def make_norm_params(cfg, dim, key=None):
    if cfg.norm == "rmsnorm":
        return {"w": jnp.zeros((dim,), jnp.float32)}
    return {"w": jnp.ones((dim,), jnp.float32), "b": jnp.zeros((dim,), jnp.float32)}


def apply_norm(cfg, p, x):
    if cfg.norm == "rmsnorm":
        return rms_norm(x, p["w"])
    return layer_norm(x, p["w"], p["b"])


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float):
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature factor, 0.1 m ln(f) + 1 (f > 1)."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_frequencies(dim: int, theta: float, yarn) -> np.ndarray:
    """YaRN inverse frequencies of a ``dim``-wide rope (``yarn`` a
    ``configs.base.YarnRope``): the high frequencies kept
    (extrapolated), the low ones divided by ``factor`` (interpolated),
    and a linear ramp between the correction dims of ``beta_fast`` and
    ``beta_slow`` rotations over the original context."""
    i = np.arange(dim // 2, dtype=np.float64)
    extra = theta ** (-2.0 * i / dim)
    inter = extra / yarn.factor

    def corr(rotations):
        return (dim * math.log(yarn.original_max_position_embeddings
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr(yarn.beta_fast)), 0)
    high = min(math.ceil(corr(yarn.beta_slow)), dim - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    mask = 1.0 - ramp  # 1 where the frequency is kept
    return (inter * (1.0 - mask) + extra * mask).astype(np.float32)


def apply_rope(x, positions, theta: float, freqs=None, scale: float = 1.0):
    """x: (..., S, H, Dh); positions: broadcastable to (..., S).

    ``freqs`` (Dh/2,) replaces the plain rope frequencies (YaRN), and
    ``scale`` multiplies cos and sin (YaRN's mscale ratio)."""
    head_dim = x.shape[-1]
    if freqs is None:
        freqs = rope_frequencies(head_dim, theta)  # (Dh/2,)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # (..., S, Dh/2)
    cos = scale * jnp.cos(angles)[..., None, :]  # (..., S, 1, Dh/2)
    sin = scale * jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(positions, dim: int):
    """Classic transformer sinusoid table, computed on the fly.

    positions: (...,) int -> (..., dim) float32.
    """
    half = dim // 2
    freqs = jnp.exp(-jnp.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# gated MLP
# ---------------------------------------------------------------------------


def init_mlp(key, cfg, d_model=None, d_ff=None):
    d_model = d_model or cfg.d_model
    d_ff = d_ff or cfg.d_ff
    dtype = jnp.dtype(cfg.param_dtype)
    k1, k2, k3 = jax.random.split(key, 3)
    if cfg.activation in ("swiglu", "geglu"):
        return {
            "w_gate": dense_init(k1, d_model, d_ff, dtype),
            "w_up": dense_init(k2, d_model, d_ff, dtype),
            "w_down": dense_init(k3, d_ff, d_model, dtype, scale=d_ff ** -0.5),
        }
    return {
        "w_up": dense_init(k1, d_model, d_ff, dtype),
        "b_up": jnp.zeros((d_ff,), dtype),
        "w_down": dense_init(k2, d_ff, d_model, dtype, scale=d_ff ** -0.5),
        "b_down": jnp.zeros((d_model,), dtype),
    }


def matmul(cfg, x, w):
    """x @ w; with ``cfg.matmul_dtype`` both operands are cast to it and
    the product is accumulated and returned in float32 (bf16 operands
    under float32 master weights)."""
    if not cfg.matmul_dtype:
        return x @ w
    dt = jnp.dtype(cfg.matmul_dtype)
    return jnp.dot(x.astype(dt), w.astype(dt),
                   preferred_element_type=jnp.float32)


def apply_mlp(cfg, p, x):
    if cfg.activation in ("swiglu", "geglu"):
        gate = matmul(cfg, x, p["w_gate"])
        act = jax.nn.silu(gate) if cfg.activation == "swiglu" else jax.nn.gelu(gate)
        return matmul(cfg, act * matmul(cfg, x, p["w_up"]), p["w_down"])
    h = jax.nn.gelu(x @ p["w_up"] + p["b_up"])
    return h @ p["w_down"] + p["b_down"]
