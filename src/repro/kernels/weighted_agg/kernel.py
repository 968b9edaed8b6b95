"""Pallas TPU kernels for the CA-AFL server hot loop.

The server pass over a model of N params with K buffered updates is
memory-bound streaming: for each parameter tile it must
  (a) reduce K client deltas with contribution weights (eq. 5), and
  (b) accumulate per-client squared distances ||x - base_i||^2 (eq. 3).

``fused_server_pallas`` does (a) and (b) plus the weighting policy in a
single two-phase launch (see its docstring); the two single-purpose
kernels below are the two-phase path the server pass takes where the
weighting needs distances from outside the launch (a model mesh's psum,
or a ring codec's own eq. 3).

All kernels tile the flattened parameter axis into VMEM-resident blocks
(lane-aligned multiples of 128; K rides the sublane dimension), so one HBM
pass per tile feeds the VPU — on TPU the arithmetic intensity is K flops
per 4*K bytes loaded, i.e. firmly bandwidth-bound, and fusing the weighting
into the reduction avoids materialising weighted deltas in HBM (which is
what a naive jnp einsum would do between two kernels).

VMEM budget rule (``fit_block``): the caller's ``block_n`` is the
padding granularity of N; each kernel runs the largest tile that divides
it (halving, staying a multiple of 128) such that one (K, tile) f32 slab,
with K padded to the 8 sublanes, is at most ``SLAB_BYTES`` = 2 MiB. The
fused kernel holds about six such slabs at once (bases and deltas, each
double-buffered, plus two f32 temporaries), i.e. <= 12 MiB of the 16 MiB
scoped VMEM a TPU v5e kernel gets by default; the single-purpose kernels
hold fewer. So K=16 keeps the 16k tile, K=64 runs 8k, K=128 runs 4k.

TARGET: TPU (Mosaic). VALIDATION: Pallas interpret mode on CPU (tests sweep
shapes/dtypes against ref.py); tests/test_tpu_compile.py compiles every
kernel for a described TPU v5e.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128
SUBLANE = 8
DEFAULT_BLOCK_N = 16 * 1024  # padding granularity of N; see fit_block
SLAB_BYTES = 2 * 1024 * 1024  # one (K, tile) f32 slab; see the module doc


def fit_block(block_n: int, k: int, multiple: int = LANE) -> int:
    """The tile a kernel runs for K rows on an N padded to ``block_n``.

    Halves ``block_n`` while one (K, tile) f32 slab exceeds ``SLAB_BYTES``
    and the half is still a multiple of ``multiple`` (a multiple of
    LANE), so the tile always divides ``block_n`` and therefore N.
    """
    rows = -(-k // SUBLANE) * SUBLANE
    while rows * block_n * 4 > SLAB_BYTES and (block_n // 2) % multiple == 0:
        block_n //= 2
    return block_n


def _weighted_sum_kernel(d_ref, w_ref, o_ref):
    """o[n] = sum_k w[k] * d[k, n] for one N-tile. d:(K,bn) w:(K,1) o:(bn,)."""
    d = d_ref[...]  # (K, bn)
    w = w_ref[...]  # (K, 1)
    o_ref[...] = jnp.sum(d * w, axis=0)


@jax.named_scope("weighted_sum_pallas")
def weighted_sum_pallas(deltas: jnp.ndarray, weights: jnp.ndarray,
                        block_n: int = DEFAULT_BLOCK_N,
                        interpret: bool = False) -> jnp.ndarray:
    """deltas: (K, N) f32, weights: (K,) f32 -> (N,) f32. N % block_n == 0."""
    k, n = deltas.shape
    assert n % block_n == 0, (n, block_n)
    block_n = fit_block(block_n, k)
    grid = (n // block_n,)
    return pl.pallas_call(
        _weighted_sum_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((k, block_n), lambda i: (0, i)),
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_n,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.float32),
        interpret=interpret,
    )(deltas, weights.reshape(k, 1))


def _fused_server_kernel(x_ref, b_ref, d_ref, p_ref, tau_ref, m_ref,
                         upd_ref, dist_ref, w_ref, *, weigh, eta_g: float):
    """Whole eq. 3 + weighting + eq. 5 server reduction in ONE kernel.

    Two-phase sequential grid (ph, i) with ph in {0, 1}, i over N-tiles:
      phase 0  accumulates per-client ||x - base_k||^2 into the resident
               (K, 1) dist block (bases stream through VMEM once);
      boundary (ph=1, i=0) applies ``weigh`` — eq. 3's staleness degrees,
               the weighting policy and its normalisation — to the
               resident (K, 1) columns in-VMEM (no host round-trip, no
               second kernel launch);
      phase 1  streams the deltas once, reducing sum_k w_k * d[k, tile]
               scaled by eta_g / k_eff straight into the output tiles.

    Index maps park the inactive operand on block 0 during the other
    phase, so bases and deltas are each read from HBM exactly once.
    x:(1,bn) b:(K,bn) d:(K,bn) p/tau/m:(K,1) -> upd:(bn,) dist/w:(K,1).
    """
    ph = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(jnp.logical_and(ph == 0, i == 0))
    def _init():
        dist_ref[...] = jnp.zeros_like(dist_ref)
        w_ref[...] = jnp.zeros_like(w_ref)

    @pl.when(ph == 0)
    def _accum_dists():
        diff = b_ref[...] - x_ref[...]  # (K, bn), broadcast over clients
        dist_ref[...] += jnp.sum(diff * diff, axis=1, keepdims=True)
        # phase-0 out index is parked on tile 0; keep it defined (it is
        # overwritten by the real reduction at (ph=1, i=0) before flush).
        upd_ref[...] = jnp.zeros_like(upd_ref)

    @pl.when(jnp.logical_and(ph == 1, i == 0))
    def _weights():
        w_ref[...] = weigh(dist_ref[...], p_ref[...], tau_ref[...],
                           m_ref[...])

    @pl.when(ph == 1)
    def _reduce():
        k_eff = jnp.maximum(jnp.sum(m_ref[...]), 1.0)
        scale = eta_g / k_eff
        upd_ref[...] = jnp.sum(d_ref[...] * (w_ref[...] * scale), axis=0)


# each kernel's scope names its custom call in the compiled program,
# whatever jitted function the caller puts around it
@jax.named_scope("fused_server_pallas")
def fused_server_pallas(x: jnp.ndarray, bases: jnp.ndarray,
                        deltas: jnp.ndarray, p_stat: jnp.ndarray,
                        taus: jnp.ndarray, arrival_mask: jnp.ndarray,
                        *, policy: str = "paper", eta_g: float = 1.0,
                        s_min: float = 1e-3, poly_a: float = 0.5,
                        hinge_a: float = 10.0, hinge_b: float = 6.0,
                        normalize: str = "mean", eps: float = 1e-12,
                        block_n: int = DEFAULT_BLOCK_N,
                        interpret: bool = False):
    """One-launch server pass. x:(N,), bases/deltas:(K,N) f32; the rest (K,).

    Returns (upd (N,), sq_dists (K,), weights (K,)) where
    upd = (eta_g / k_eff) * sum_k w_k * deltas[k] already carries eq. 5's
    scale. N % block_n == 0 (use the ops wrapper for padding); the
    kernel runs ``fit_block(block_n, K)``.
    """
    # imported here: repro.core's package imports the server pass, which
    # imports this module
    from repro.core import weighting

    if policy not in weighting.POLICIES:
        raise ValueError(f"unknown policy {policy!r}")

    def weigh(dists, p, tau, mask):
        s = weighting.staleness_degree(dists, eps, arrival_mask=mask)
        return weighting.contribution_weights(
            policy, p, s, tau, s_min=s_min, poly_a=poly_a, hinge_a=hinge_a,
            hinge_b=hinge_b, normalize=normalize, arrival_mask=mask)

    k, n = bases.shape
    assert deltas.shape == (k, n) and x.shape == (n,)
    assert n % block_n == 0, (n, block_n)
    block_n = fit_block(block_n, k)
    tiles = n // block_n
    grid = (2, tiles)
    col2 = lambda a: a.astype(jnp.float32).reshape(k, 1)
    kernel = functools.partial(_fused_server_kernel, weigh=weigh,
                               eta_g=eta_g)
    upd, dists, w = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            # park the phase-inactive operand on tile 0 (single fetch)
            pl.BlockSpec((1, block_n), lambda ph, i: (0, i * (1 - ph))),
            pl.BlockSpec((k, block_n), lambda ph, i: (0, i * (1 - ph))),
            pl.BlockSpec((k, block_n), lambda ph, i: (0, i * ph)),
            pl.BlockSpec((k, 1), lambda ph, i: (0, 0)),
            pl.BlockSpec((k, 1), lambda ph, i: (0, 0)),
            pl.BlockSpec((k, 1), lambda ph, i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n,), lambda ph, i: (i * ph,)),
            pl.BlockSpec((k, 1), lambda ph, i: (0, 0)),
            pl.BlockSpec((k, 1), lambda ph, i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n,), jnp.float32),
            jax.ShapeDtypeStruct((k, 1), jnp.float32),
            jax.ShapeDtypeStruct((k, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x.reshape(1, n), bases, deltas, col2(p_stat), col2(taus),
      col2(arrival_mask))
    return upd, dists[:, 0], w[:, 0]


def _sq_dist_kernel(x_ref, b_ref, o_ref):
    """Accumulate per-client ||x - base_k||^2 over N-tiles.

    Sequential-grid accumulation: the single (K,1) output block is carried
    across grid steps (TPU grid is sequential), initialised at step 0.
    x:(1,bn) b:(K,bn) o:(K,1).
    """
    i = pl.program_id(0)
    diff = b_ref[...] - x_ref[...]  # (K, bn) broadcast over clients
    part = jnp.sum(diff * diff, axis=1, keepdims=True)  # (K, 1)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += part


@jax.named_scope("sq_dists_pallas")
def sq_dists_pallas(x: jnp.ndarray, bases: jnp.ndarray,
                    block_n: int = DEFAULT_BLOCK_N,
                    interpret: bool = False) -> jnp.ndarray:
    """x: (N,) f32, bases: (K, N) f32 -> (K,) per-client squared distance."""
    k, n = bases.shape
    assert x.shape == (n,)
    assert n % block_n == 0, (n, block_n)
    block_n = fit_block(block_n, k)
    grid = (n // block_n,)
    out = pl.pallas_call(
        _sq_dist_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
            pl.BlockSpec((k, block_n), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((k, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((k, 1), jnp.float32),
        interpret=interpret,
    )(x.reshape(1, n), bases)
    return out[:, 0]
