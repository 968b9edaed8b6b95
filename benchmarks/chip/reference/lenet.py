"""Plain LeNet-5 on 28x28x1 images, with its loss and local SGD.

Conv(6, 5x5) -> tanh -> avgpool 2 -> Conv(16, 5x5) -> tanh -> avgpool 2
-> FC 120 -> tanh -> FC 84 -> tanh -> FC 10, mean cross-entropy.
Written from that description, importing nothing of the program.
Every convolution and product runs at ``Precision.HIGHEST`` in float32;
``dtype=bfloat16`` rounds the forward operands of each to bf16 first
(products of bf16 values are exact in f32, so this is one bf16 pass with
f32 accumulation, as a TPU's default precision runs it); the backward
products stay f32. That is the control.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
LAYERS = (("c1", (5, 5, 1, 6)), ("c2", (5, 5, 6, 16)),
          ("f1", (256, 120)), ("f2", (120, 84)), ("f3", (84, 10)))


def init(key):
    """Seeded weights: normal * fan_in ** -0.5, zero biases."""
    keys = jax.random.split(key, len(LAYERS))
    out = {}
    for k, (name, shape) in zip(keys, LAYERS):
        fan_in = 1
        for s in shape[:-1]:
            fan_in *= s
        out[name] = {"w": jax.random.normal(k, shape, jnp.float32)
                     * fan_in ** -0.5,
                     "b": jnp.zeros((shape[-1],), jnp.float32)}
    return out


def _cast(a, dtype):
    return a if dtype == jnp.float32 else a.astype(dtype).astype(jnp.float32)


def forward(p, x, dtype=jnp.float32):
    def conv(h, layer):
        y = jax.lax.conv_general_dilated(
            _cast(h, dtype), _cast(layer["w"], dtype), (1, 1), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI)
        return y + layer["b"]

    def pool(h):
        b, hh, ww, c = h.shape
        return h.reshape(b, hh // 2, 2, ww // 2, 2, c).mean(axis=(2, 4))

    def dense(h, layer):
        return jnp.dot(_cast(h, dtype), _cast(layer["w"], dtype),
                       precision=HI) + layer["b"]

    h = pool(jnp.tanh(conv(x, p["c1"])))
    h = pool(jnp.tanh(conv(h, p["c2"])))
    h = h.reshape(h.shape[0], -1)
    h = jnp.tanh(dense(h, p["f1"]))
    h = jnp.tanh(dense(h, p["f2"]))
    return dense(h, p["f3"])


def loss(p, x, y, dtype=jnp.float32):
    logits = forward(p, x, dtype)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - gold)


def local_sgd(p, xs, ys, lr, dtype=jnp.float32):
    """M plain SGD steps over (M, B, ...) batches; returns base - end."""
    grad = jax.grad(loss)
    q = p
    for m in range(xs.shape[0]):
        g = grad(q, xs[m], ys[m], dtype)
        q = jax.tree.map(lambda a, b: a - lr * b, q, g)
    return jax.tree.map(lambda a, b: a - b, p, q)
