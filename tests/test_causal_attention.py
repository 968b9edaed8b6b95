"""The causal training attention's Pallas kernel (``kernels/flash_attn/ops.py::
causal_attention``) in interpret mode against materialised-scores attention,
and the choice between it and the chunked XLA path (``models/attention.py::
causal_long``)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_arch
from repro.kernels.flash_attn import ops as flash_ops
from repro.models import attention as attn

BLOCK = flash_ops.CAUSAL_BLOCK
LIMIT = 2e-2  # bf16 operands against float32; readings 3e-3 to 5e-3


def rel(a, b):
    """Largest gap over the largest value."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def _inputs(b, s, h, dqk, dv, kv_heads, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, s, h, dqk))
    k = jax.random.normal(ks[1], (b, s, kv_heads, dqk))
    v = jax.random.normal(ks[2], (b, s, kv_heads, dv))
    rep = h // kv_heads
    k, v = attn._repeat_kv(k, rep), attn._repeat_kv(v, rep)
    return q, k, v, jax.random.normal(ks[3], (b, s, h, dv))


@pytest.mark.parametrize("b,s,h,dqk,dv,kv_heads", [
    (1, 512, 2, 192, 128, 2),    # MLA's head split
    (2, 256, 2, 192, 128, 2),
    (2, 512, 2, 64, 64, 1),      # GQA, one kv head expanded to two
    (1, 2 * BLOCK, 2, 192, 128, 2),  # blocks above the diagonal skipped
])
def test_kernel_matches_full_attention(b, s, h, dqk, dv, kv_heads):
    """The value and the gradients in q, k and v (through a random
    cotangent) against float32 materialised scores."""
    q, k, v, ct = _inputs(b, s, h, dqk, dv, kv_heads, seed=s + dqk + b)
    scale = 0.1147214

    def kernel(q, k, v):
        return flash_ops.causal_attention(q, k, v, scale, interpret=True)

    def full(q, k, v):
        return attn._full_attention(q, k, v, causal=True, scale=scale)

    got, got_g = jax.value_and_grad(
        lambda *a: jnp.sum(kernel(*a) * ct), argnums=(0, 1, 2))(q, k, v)
    want, want_g = jax.value_and_grad(
        lambda *a: jnp.sum(full(*a) * ct), argnums=(0, 1, 2))(q, k, v)
    out = kernel(q, k, v)
    assert out.dtype == jnp.float32 and out.shape == (b, s, h, dv)
    assert rel(out, full(q, k, v)) < LIMIT
    for name, g, w in zip("qkv", got_g, want_g):
        assert g.shape == w.shape
        assert rel(g, w) < LIMIT, name


def _spy_paths(monkeypatch, backend):
    """Replace both paths by recorders and set the backend the choice
    sees; returns the list the chosen path's name is appended to."""
    calls = []

    def record(name):
        def fn(q, k, v, *a, **kw):
            calls.append(name)
            return jnp.zeros(q.shape[:-1] + v.shape[-1:], jnp.float32)
        return fn

    monkeypatch.setattr(attn, "_chunked_causal_attention", record("chunked"))
    monkeypatch.setattr(flash_ops, "causal_attention", record("kernel"))
    monkeypatch.setattr(attn.jax, "default_backend", lambda: backend)
    return calls


@pytest.mark.parametrize("backend,s,path", [
    ("cpu", 2 * BLOCK, "chunked"),     # off the TPU
    ("tpu", 2 * BLOCK, "kernel"),
    ("tpu", BLOCK + 128, "chunked"),   # a length the block does not divide
    ("tpu", 2 * BLOCK + 64, "chunked"),  # not a lane multiple
])
def test_causal_long_picks_the_path(monkeypatch, backend, s, path):
    calls = _spy_paths(monkeypatch, backend)
    q = jnp.zeros((1, s, 2, 32))
    out = attn.causal_long(q, q, jnp.zeros((1, s, 2, 16)))
    assert calls == [path]
    assert out.shape == (1, s, 2, 16)


def test_causal_long_off_the_tpu_is_the_chunked_path():
    q, k, v, _ = _inputs(1, 2 * BLOCK, 2, 32, 16, 2, seed=7)
    assert jax.default_backend() != "tpu"
    np.testing.assert_array_equal(
        attn.causal_long(q, k, v, 0.2),
        attn._chunked_causal_attention(q, k, v, attn.Q_CHUNK, scale=0.2))


@pytest.mark.parametrize("arch", ["deepseek-v2-lite", "dense"])
def test_callers_run_the_kernel_on_the_tpu(monkeypatch, arch):
    """MLA and the dense causal path, with the backend read as a TPU and
    the kernel in interpret mode, against the chunked path on the same
    weights."""
    if arch == "dense":
        from repro.configs.base import ModelConfig
        cfg = ModelConfig(name="t", family="dense", num_layers=1, d_model=64,
                          num_heads=4, num_kv_heads=2, d_ff=128,
                          vocab_size=32, force_chunked_attn=True)
    else:
        cfg = get_arch(arch).model.replace(
            d_model=64, num_heads=2, kv_lora_rank=32, qk_nope_head_dim=32,
            qk_rope_head_dim=16, v_head_dim=32, force_chunked_attn=True)
    p = attn.init_attention(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 2 * BLOCK, cfg.d_model))
    want = attn.attention_train(cfg, p, x)
    monkeypatch.setattr(attn.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        flash_ops, "causal_attention",
        functools.partial(flash_ops.causal_attention, interpret=True))
    got = attn.attention_train(cfg, p, x)
    assert got.dtype == want.dtype
    assert rel(got, want) < LIMIT
