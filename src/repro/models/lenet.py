"""LeNet-5 (the paper's Fashion-MNIST backbone), pure JAX.

Conv(6,5x5) -> avgpool -> Conv(16,5x5) -> avgpool -> FC120 -> FC84 -> FC10.

The first convolution is computed as patches times a matrix. It reads the
input images, which need no gradient, so its weight gradient is one dot
over the patches; vmapped over clients' weights that dot gains a batch
dimension. As a convolution, the same vmap folds the clients into a
feature-grouped convolution whose weight gradient has one input channel
per group, which a TPU runs as an elementwise multiply-add. The second
convolution stays one: as patches, its input gradient would need the
patches' transpose, a scatter-add of kh*kw slices.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def init_lenet(key, num_classes: int = 10, in_channels: int = 1):
    ks = jax.random.split(key, 5)

    def conv_w(k, kh, kw, cin, cout):
        fan_in = kh * kw * cin
        return jax.random.normal(k, (kh, kw, cin, cout), jnp.float32) * fan_in ** -0.5

    def fc(k, din, dout):
        return jax.random.normal(k, (din, dout), jnp.float32) * din ** -0.5

    return {
        "c1": {"w": conv_w(ks[0], 5, 5, in_channels, 6), "b": jnp.zeros((6,))},
        "c2": {"w": conv_w(ks[1], 5, 5, 6, 16), "b": jnp.zeros((16,))},
        "f1": {"w": fc(ks[2], 16 * 4 * 4, 120), "b": jnp.zeros((120,))},
        "f2": {"w": fc(ks[3], 120, 84), "b": jnp.zeros((84,))},
        "f3": {"w": fc(ks[4], 84, num_classes), "b": jnp.zeros((num_classes,))},
    }


def _conv(x, w, b):
    y = jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + b


def _patch_conv(x, w, b):
    """VALID, stride-1 convolution (NHWC x HWIO) as im2col patches @ w.

    The patches are laid out (kh*kw, h, wd*B*cin): each is a slice of the
    images transposed to (H, W*B*cin), so its minor axis stays dense on a
    TPU. With (kh*kw*cin) or the batch as the minor axis, the patches pad
    to 128 lanes, and LeNet's round runs slower than with the convolution.
    """
    kh, kw, cin, cout = w.shape
    n, hi, wi, _ = x.shape
    h, wd = hi - kh + 1, wi - kw + 1
    c = n * cin
    rows = jnp.transpose(x, (1, 2, 0, 3)).reshape(hi, wi * c)
    patches = jnp.stack([rows[i:i + h, j * c:(j + wd) * c]
                         for i in range(kh) for j in range(kw)])
    patches = patches.reshape(kh * kw, h, wd * n, cin)
    y = jnp.einsum("kpqi,kio->pqo", patches, w.reshape(kh * kw, cin, cout))
    # The bias goes after the transpose: added before it, XLA lays out the
    # first pool's gradient with the images minor and splits it into two
    # one-axis reduce-windows as slow as the grouped convolution.
    return jnp.transpose(y.reshape(h, wd, n, cout), (2, 0, 1, 3)) + b


def _avgpool2(x):
    return jax.lax.reduce_window(x, 0.0, jax.lax.add, (1, 2, 2, 1),
                                 (1, 2, 2, 1), "VALID") / 4.0


def apply_lenet(params, x):
    """x: (B, 28, 28, 1) -> logits (B, 10)."""
    h = jnp.tanh(_patch_conv(x, params["c1"]["w"], params["c1"]["b"]))  # (B,24,24,6)
    h = _avgpool2(h)  # (B,12,12,6)
    h = jnp.tanh(_conv(h, params["c2"]["w"], params["c2"]["b"]))  # (B,8,8,16)
    h = _avgpool2(h)  # (B,4,4,16)
    h = h.reshape(h.shape[0], -1)
    h = jnp.tanh(h @ params["f1"]["w"] + params["f1"]["b"])
    h = jnp.tanh(h @ params["f2"]["w"] + params["f2"]["b"])
    return h @ params["f3"]["w"] + params["f3"]["b"]


def lenet_loss(params, batch):
    """batch: (x (B,28,28,1), y (B,)) -> (mean CE, metrics)."""
    x, y = batch
    logits = apply_lenet(params, x)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, y[:, None].astype(jnp.int32), axis=-1)[:, 0]
    ce = jnp.mean(lse - gold)
    acc = jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))
    return ce, {"ce": ce, "acc": acc}
