"""Pallas TPU kernel: fused int8 dequantize + eq. 3 distance.

The compressed ring keeps rows as int8 codewords with per-block affine
(scale, zero) pairs (``core/version_store.py``). A naive distance path
would decode the K rows to (K, N) f32 in HBM — 4x the bytes the codec
just saved — and only then stream them through ``sq_dists_pallas``. This
kernel fuses the decode into the distance accumulation: each grid step
loads one int8 tile (plus its scale/zero columns), dequantizes it
in-register, and folds ``||x - deq||^2`` into the resident (K, 1)
accumulator. The decoded f32 rows never exist anywhere — per tile HBM
traffic is ``K * bn`` int8 bytes + ``2 * K * bn / qblock`` f32 scales
instead of ``4 * K * bn`` f32 bytes, so the distance pass inherits the
codec's ~4x bandwidth win on a bandwidth-bound loop.

Tile layout: the (K, N // qblock) scale and zero columns are regrouped
per tile as (tiles, K, block_n // qblock), so each grid step's block
spans the last two dimensions whole. A (K, block_n // qblock) block of
the flat layout would not lower on TPU: at the 16k tile and
qblock=256 its last dimension is 64, not a multiple of 128. The
regrouping copies only the scales (2 / qblock of the f32 row bytes).
The tile follows ``weighted_agg.kernel.fit_block`` (one (K, tile) f32
slab <= 2 MiB: the dequantized tile and its difference are f32).

Same sequential-grid accumulation idiom as
``weighted_agg.kernel.sq_dists_pallas`` (the single (K, 1) output block
is carried across grid steps and initialised at step 0). Under a model
mesh the caller runs this per shard and psums the partials — identical
communication shape to the f32 path (DESIGN.md §5).

TARGET: TPU (Mosaic). VALIDATION: Pallas interpret mode on CPU
(tests/test_version_store.py sweeps shapes against ref.py).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.weighted_agg.kernel import DEFAULT_BLOCK_N, LANE, fit_block


def _int8_sq_dist_kernel(x_ref, c_ref, s_ref, z_ref, o_ref, *, qblock: int):
    """x:(1,bn) c:(K,bn) int8, s/z:(K,bn//qblock) (this tile's columns),
    o:(K,1) accumulator."""
    i = pl.program_id(0)
    k, bn = c_ref.shape
    q = c_ref[...].astype(jnp.float32).reshape(k, bn // qblock, qblock)
    deq = (q * s_ref[...][..., None] + z_ref[...][..., None]).reshape(k, bn)
    diff = deq - x_ref[...]  # broadcast over the K clients
    part = jnp.sum(diff * diff, axis=1, keepdims=True)  # (K, 1)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += part


@jax.named_scope("int8_sq_dists_pallas")
def int8_sq_dists_pallas(x: jnp.ndarray, codes: jnp.ndarray,
                         scales: jnp.ndarray, zeros: jnp.ndarray, *,
                         qblock: int, block_n: int = DEFAULT_BLOCK_N,
                         interpret: bool = False) -> jnp.ndarray:
    """x: (N,) f32, codes: (K, N) int8, scales/zeros: (K, N // qblock) f32
    -> (K,) ``||x - dequant(row_k)||^2``. Requires ``N % block_n == 0``
    and ``block_n % qblock == 0`` (the ops wrapper and
    ``version_store.resolve_qblock`` guarantee both).
    """
    k, n = codes.shape
    assert x.shape == (n,)
    assert n % block_n == 0, (n, block_n)
    assert block_n % qblock == 0, (block_n, qblock)
    block_n = fit_block(block_n, k, math.lcm(LANE, qblock))
    sb = block_n // qblock  # scale/zero columns per tile
    tiles = n // block_n

    def per_tile(a):  # (K, N // qblock) -> (tiles, K, sb)
        return a.reshape(k, tiles, sb).transpose(1, 0, 2)

    out = pl.pallas_call(
        functools.partial(_int8_sq_dist_kernel, qblock=qblock),
        grid=(tiles,),
        in_specs=[
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
            pl.BlockSpec((k, block_n), lambda i: (0, i)),
            pl.BlockSpec((None, k, sb), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, k, sb), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((k, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((k, 1), jnp.float32),
        interpret=interpret,
    )(x.reshape(1, n), codes, per_tile(scales), per_tile(zeros))
    return out[:, 0]
