"""LeNet-5's first convolution, computed as patches times a matrix."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.client import make_local_update_fn
from repro.models.lenet import _conv, _patch_conv, init_lenet, lenet_loss


def _conv1_case(in_channels, batch, k):
    """c1's weights and bias (stacked over k clients when k), images, and
    a random cotangent for the output."""
    ks = jax.random.split(jax.random.PRNGKey(in_channels * 100 + batch), 5)
    lead = (k,) if k else ()
    c1 = init_lenet(ks[0], in_channels=in_channels)["c1"]
    w = jax.random.normal(ks[1], lead + c1["w"].shape) * 0.3
    b = jax.random.normal(ks[2], lead + c1["b"].shape)
    x = jax.random.normal(ks[3], lead + (batch, 28, 28, in_channels))
    r = jax.random.normal(ks[4], lead + (batch, 24, 24, 6))
    return w, b, x, r


@pytest.mark.parametrize("in_channels,batch,k", [
    (1, 32, 0),    # LeNet's conv1: 28x28x1 -> 24x24x6
    (3, 8, 0),     # init_lenet(in_channels=3)
    (1, 8, 10),    # weights vmapped over K clients
], ids=["conv1", "in_channels3", "vmap_k10"])
def test_patch_conv_matches_lax_conv(in_channels, batch, k):
    w, b, x, r = _conv1_case(in_channels, batch, k)

    def value_and_grads(conv):
        def obj(w, b, x, r):
            return jnp.sum(jnp.tanh(conv(x, w, b)) * r)

        def out(w, b, x, r):
            return conv(x, w, b), jax.grad(obj, argnums=(0, 1))(w, b, x, r)

        if k:
            out = jax.vmap(out)
        with jax.default_matmul_precision("highest"):
            y, (gw, gb) = jax.jit(out)(w, b, x, r)
        return y, gw, gb

    for got, want in zip(value_and_grads(_patch_conv), value_and_grads(_conv)):
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, rtol=1e-4,
                                   atol=1e-4 * float(jnp.max(jnp.abs(want))))


def test_vmapped_local_update_convolves_only_conv2():
    """The K clients' local update at the paper's K=10, M=4, B=32 reads
    the images through dots alone: the only convolutions left are conv2's
    forward pass, weight gradient and input gradient."""
    k, m, b = 10, 4, 32
    params = init_lenet(jax.random.PRNGKey(0))
    params = jax.tree.map(lambda a: jnp.stack([a] * k), params)
    batches = (jnp.zeros((k, m, b, 28, 28, 1)), jnp.zeros((k, m, b), jnp.int32))
    update = jax.vmap(make_local_update_fn(lenet_loss, m, 0.05))
    text = jax.jit(update).lower(params, batches).as_text()
    convs = [line for line in text.splitlines()
             if "stablehlo.convolution" in line]
    operands = [re.search(r":\s*\((tensor<[^>]*>), (tensor<[^>]*>)\)", line)
                .groups() for line in convs]
    assert len(convs) == 3, convs
    assert not any("28x28" in t for pair in operands for t in pair), operands
