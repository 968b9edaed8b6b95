"""The main path's Pallas kernels compile for a TPU v5e, without the chip.

The TPU compiler ships with libtpu, and it compiles for a described
``v5e:2x2`` topology that is not attached. It refuses what interpret mode
accepts: blocks not aligned to the (8, 128) tiling, and kernels that need
more than the 16 MiB of scoped VMEM. Nothing runs here, so these tests
check that the kernels lower and fit, not what they compute.

The topology is described inside a module fixture, never at import: only
one process may load libtpu at a time, and every test worker imports
this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import FLConfig
from repro.core.server_pass import apply_server_round
from repro.core.weighting import POLICIES
from repro.kernels.ring_codec.kernel import int8_sq_dists_pallas
from repro.kernels.weighted_agg import kernel as wk
from repro.kernels.weighted_agg.ops import pad_to

V5E_HBM_BYTES = 16 * 1024**3
SMALL_N = 1 << 22  # the K=64 cases: 64 x 4M f32 per stream


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a TPU compile is written to the persistent cache but cannot be read
    # back without a chip, so keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # the compiler writes its logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def whisper_np():
    """whisper-tiny's parameter count (unpadded)."""
    from repro.configs.registry import get_arch
    from repro.models.model import build_model

    shapes = jax.eval_shape(build_model(get_arch("whisper-tiny").model).init,
                            jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert n == 56_379_648
    return n


def _kernel_calls(text, kernel):
    """The custom-call instructions named after ``kernel`` (``<kernel>.<n>``):
    each main-path kernel's scope names its call, whatever jitted
    function is around it."""
    return re.findall(rf"%{kernel}\.\d+ = [^\n]*custom-call\(", text)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _n(case, whisper_np, shards=1):
    """N padded as ``make_flat_spec`` pads it for ``shards`` model shards."""
    if case == "small":
        return SMALL_N
    return pad_to(whisper_np, wk.DEFAULT_BLOCK_N * shards)


@pytest.mark.parametrize("k,case", [(16, "whisper"), (64, "small")])
def test_fused_server_kernel_compiles(one_chip, whisper_np, k, case):
    n = _n(case, whisper_np)
    f32 = jnp.float32
    compiled = _compile(
        lambda x, b, d, p, t, m: wk.fused_server_pallas(x, b, d, p, t, m),
        one_chip, ((n,), f32), ((k, n), f32), ((k, n), f32), ((k,), f32),
        ((k,), f32), ((k,), f32))
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert len(_kernel_calls(text, "fused_server_pallas")) == 1


@pytest.mark.parametrize("policy", POLICIES)
def test_fused_server_kernel_lowers_every_policy(one_chip, policy):
    """The kernel's weighting step is core/weighting's own code, traced
    on (K, 1) columns inside the kernel: each policy must lower."""
    k, n, f32 = 16, 1 << 18, jnp.float32
    compiled = _compile(
        lambda x, b, d, p, t, m: wk.fused_server_pallas(
            x, b, d, p, t, m, policy=policy),
        one_chip, ((n,), f32), ((k, n), f32), ((k, n), f32), ((k,), f32),
        ((k,), f32), ((k,), f32))
    assert len(_kernel_calls(compiled.as_text(), "fused_server_pallas")) == 1


@pytest.mark.parametrize("kernel", ["sq_dists", "weighted_sum"])
@pytest.mark.parametrize("k,case", [(16, "whisper"), (64, "small")])
def test_two_phase_kernels_compile(one_chip, whisper_np, kernel, k, case):
    n = _n(case, whisper_np)
    f32 = jnp.float32
    if kernel == "sq_dists":
        compiled = _compile(wk.sq_dists_pallas, one_chip, ((n,), f32),
                            ((k, n), f32))
    else:
        compiled = _compile(wk.weighted_sum_pallas, one_chip, ((k, n), f32),
                            ((k,), f32))
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert len(_kernel_calls(text, f"{kernel}_pallas")) == 1


@pytest.mark.parametrize("k,case", [(16, "whisper"), (64, "small")])
def test_int8_distance_kernel_compiles(one_chip, whisper_np, k, case):
    n, qblock = _n(case, whisper_np), 256
    f32 = jnp.float32
    compiled = _compile(
        lambda x, c, s, z: int8_sq_dists_pallas(x, c, s, z, qblock=qblock),
        one_chip, ((n,), f32), ((k, n), jnp.int8), ((k, n // qblock), f32),
        ((k, n // qblock), f32))
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert len(_kernel_calls(text, "int8_sq_dists_pallas")) == 1


def test_fused_server_pass_fits_one_chip(one_chip, whisper_np):
    """The whole fused round at whisper-tiny width, K=16, fits 16 GB."""
    k, n, f32 = 16, _n("whisper", whisper_np), jnp.float32
    fl = FLConfig()
    compiled = _compile(
        lambda x, b, d, l, s, t: apply_server_round(x, b, d, l, s, t, fl,
                                                    mode="fused"),
        one_chip, ((n,), f32), ((k, n), f32), ((k, n), f32), ((k,), f32),
        ((k,), f32), ((k,), f32))
    # inside the round's own server_pass scope the kernel keeps its name
    assert len(_kernel_calls(compiled.as_text(), "fused_server_pallas")) == 1
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < V5E_HBM_BYTES, total


def test_sharded_server_pass_compiles_on_four_chips(topo, whisper_np):
    """The model-sharded pass (two-phase kernels + one eq. 3 psum) at
    whisper-tiny width, K=16, over a (data=1, model=4) mesh."""
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4), ("data", "model"))
    k, n, f32 = 16, _n("whisper", whisper_np, shards=4), jnp.float32
    flat = NamedSharding(mesh, P("model"))
    rows = NamedSharding(mesh, P(None, "model"))
    rep = NamedSharding(mesh, P())
    fl = FLConfig()
    args = [jax.ShapeDtypeStruct((n,), f32, sharding=flat),
            jax.ShapeDtypeStruct((k, n), f32, sharding=rows),
            jax.ShapeDtypeStruct((k, n), f32, sharding=rows)] + [
        jax.ShapeDtypeStruct((k,), f32, sharding=rep)] * 3
    compiled = jax.jit(
        lambda x, b, d, l, s, t: apply_server_round(
            x, b, d, l, s, t, fl, mode="fused", mesh=mesh)).lower(
        *args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-reduce" in text
    assert _kernel_calls(text, "sq_dists_pallas")
    assert _kernel_calls(text, "weighted_sum_pallas")
    mem = compiled.memory_analysis()
    per_device = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                  + mem.temp_size_in_bytes)
    assert per_device < V5E_HBM_BYTES // 2, per_device


def test_causal_attention_kernel_compiles_at_the_mla_shape(one_chip):
    """The causal kernel's value and gradients at DeepSeek-V2-Lite's MLA
    shape (B=1, S=8,192, 16 heads, q/k 192, v 128) lower to its three
    passes, with no float32 (..., 512, 8192) chunk scores, and need fewer
    temporaries than the chunked XLA path's (0.168 GB against 0.823 GB
    when written)."""
    from repro.kernels.flash_attn.ops import causal_attention
    from repro.models.attention import Q_CHUNK, _chunked_causal_attention

    b, s, h, dqk, dv, f32 = 1, 8192, 16, 192, 128, jnp.float32
    shapes = (((b, s, h, dqk), f32), ((b, s, h, dqk), f32),
              ((b, s, h, dv), f32))

    def value_and_grad(attention):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(attention(q, k, v, 0.1147214)),
            argnums=(0, 1, 2))

    kernel = _compile(value_and_grad(causal_attention), one_chip, *shapes)
    chunked = _compile(value_and_grad(
        lambda q, k, v, scale: _chunked_causal_attention(
            q, k, v, Q_CHUNK, scale=scale)), one_chip, *shapes)
    text = kernel.as_text()
    for phase in ("fwd", "dq", "dkv"):
        calls = re.findall(
            rf"%splash_mha_{phase}_\w+(?:\.\d+)? = [^\n]*custom-call\(", text)
        assert len(calls) == 1, phase
    chunk_scores = re.compile(r"f32\[[\d,]*512,8192\]")
    assert chunk_scores.search(chunked.as_text())
    assert not chunk_scores.search(text)
    temp = kernel.memory_analysis().temp_size_in_bytes
    assert temp < chunked.memory_analysis().temp_size_in_bytes, temp
