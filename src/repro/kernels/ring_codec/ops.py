"""Public dispatcher: fused-kernel vs jnp-reference int8 distance."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.ring_codec import kernel as _k
from repro.kernels.ring_codec import ref as _ref


def int8_sq_dists(x: jnp.ndarray, codes: jnp.ndarray, scales: jnp.ndarray,
                  zeros: jnp.ndarray, *, qblock: int, block_n: int = 0,
                  use_kernel: bool = False,
                  interpret: bool = False) -> jnp.ndarray:
    """eq. 3 squared distances against K int8-quantized ring rows.

    x: (N,) f32, codes: (K, N) int8, scales/zeros: (K, N // qblock) f32
    -> (K,). Inputs arrive already padded on the flat-spec layout (N a
    ``block_n`` multiple, ``qblock`` dividing ``block_n`` — see
    ``version_store.resolve_qblock``), so unlike ``weighted_agg.ops``
    there is no pad/slice here. ``use_kernel`` picks the fused Mosaic
    kernel (TPU, or interpret-mode validation); otherwise the jnp
    reference runs — same dispatch convention as the server pass's
    fused vs reference modes.
    """
    x = x.astype(jnp.float32)
    if not use_kernel:
        return _ref.int8_sq_dists_ref(x, codes, scales, zeros, qblock)
    n = x.shape[0]
    block = block_n or _k.DEFAULT_BLOCK_N
    if n % block:  # single lane-padded tile (small models)
        block = n
    return _k.int8_sq_dists_pallas(x, codes, scales, zeros, qblock=qblock,
                                   block_n=block, interpret=interpret)
