"""Jit'd wrappers in the (B, S, H, D) layout.

``flash_attention``: this package's forward-only kernel, with the
kernel-vs-oracle switch and padding. ``causal_attention``: the model's
causal training attention on the TPU, JAX's block-sparse ("splash")
kernel with its own backward.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_kernel as _splash,
    splash_attention_mask as _splash_mask,
)

from repro.kernels.flash_attn import kernel as _k
from repro.kernels.flash_attn import ref as _ref


@functools.partial(jax.jit, static_argnames=("causal", "window", "use_kernel",
                                             "interpret", "block_q", "block_k"))
def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    use_kernel: bool = True, interpret: bool = False,
                    block_q: int = 128, block_k: int = 128):
    """q, k, v: (B, S, H, D) (H already GQA-expanded). Returns (B, S, H, D)."""
    b, s, h, d = q.shape

    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)

    qf, kf, vf = to_bh(q), to_bh(k), to_bh(v)
    if not use_kernel:
        out = _ref.attention_ref(qf, kf, vf, causal=causal, window=window)
    else:
        bq = min(block_q, s)
        bk = min(block_k, kf.shape[1])
        pad_q = (-s) % bq
        pad_k = (-kf.shape[1]) % bk
        if pad_k:
            assert causal, "kv padding requires a causal mask to stay exact"
        if pad_q or pad_k:
            # pad kv with fully-masked positions (kpos >= original length is
            # never attended because q rows are causal and padded q dropped)
            qf = jnp.pad(qf, ((0, 0), (0, pad_q), (0, 0)))
            kf = jnp.pad(kf, ((0, 0), (0, pad_k), (0, 0)))
            vf = jnp.pad(vf, ((0, 0), (0, pad_k), (0, 0)))
        out = _k.flash_attention_pallas(qf, kf, vf, causal=causal,
                                        window=window, block_q=bq, block_k=bk,
                                        interpret=interpret)
        out = out[:, :s]
    return out.reshape(b, h, s, d).transpose(0, 2, 1, 3)


# The causal kernel's block, the same in its forward, dq and dkv passes:
# at DeepSeek-V2-Lite's MLA shape (S 8,192, 16 heads, q/k 192, v 128) on
# a TPU v5e, 512 throughout ran forward plus backward within 5% of
# 1,024-wide blocks and 1.5x faster than 256; a fused backward was 9%
# faster but held 0.94 GB more temporaries (PERF.md §6). A sequence runs
# the kernel when the block divides it, or when it is one block of a
# lane multiple.
CAUSAL_BLOCK = 512


def causal_fits(s: int) -> bool:
    """Whether ``causal_attention`` takes a sequence of length ``s``."""
    return s % 128 == 0 and (s <= CAUSAL_BLOCK or s % CAUSAL_BLOCK == 0)


@functools.lru_cache(maxsize=None)
def _causal_kernel(h: int, s: int, interpret: bool):
    """The kernel for ``h`` heads of length ``s``: its mask's block
    tables are built once, on the host, and not on every trace."""
    b = min(CAUSAL_BLOCK, s)
    sizes = _splash.BlockSizes(block_q=b, block_kv=b, block_kv_compute=b,
                               block_q_dkv=b, block_kv_dkv=b,
                               block_kv_dkv_compute=b, block_q_dq=b,
                               block_kv_dq=b)
    mask = _splash_mask.MultiHeadMask([_splash_mask.CausalMask((s, s))] * h)
    with jax.ensure_compile_time_eval():
        return _splash.make_splash_mha(mask, block_sizes=sizes, head_shards=1,
                                       q_seq_shards=1, interpret=interpret)


def causal_attention(q, k, v, scale: float, interpret: bool = False):
    """Causal softmax attention; q, k: (B, S, H, Dqk), v: (B, S, H, Dv).
    Returns (B, S, H, Dv) float32.

    q is scaled in float32; the kernel takes q, k and v in bf16, keeps
    the softmax's running max and sum and every accumulator in float32,
    and never writes a score to HBM. Blocks above the diagonal are
    skipped, in the forward and in both backward passes.
    """
    _, s, h, _ = q.shape
    kernel = _causal_kernel(h, s, interpret)

    def heads_major(x):
        return x.transpose(0, 2, 1, 3).astype(jnp.bfloat16)

    out = jax.vmap(kernel)(heads_major(q.astype(jnp.float32) * scale),
                           heads_major(k), heads_major(v))
    return out.transpose(0, 2, 1, 3).astype(jnp.float32)
