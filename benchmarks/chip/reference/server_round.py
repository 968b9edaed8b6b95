"""Plain reference of one server round: the paper's eq. 3, 4 and 5.

Written from the equations, importing nothing of the program:

    eq. 4  P_i = N_i * L_i            (L_i: fresh loss of x on a probe)
    eq. 3  S_i = min_j d_j / d_i,     d_i = ||x - base_i||^2
    eq. 5  x' = x - eta_g / K * sum_i w_i * Delta_i,
           w_i = P_i / max(S_i, s_min), rescaled to mean 1

``weigh`` works on the host in float64. ``flat_round`` runs the two
passes over the (K, N) rows on the device in float32, each one fused
reduction, so no (K, N) temporary is made. ``dtype`` is the precision
the rows are held in before the arithmetic: ``bfloat16`` makes the
control that a later change to bf16 update rows would be.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-12  # keeps eq. 3 finite when every distance is 0


def weigh(sq_dists, losses, sizes, *, s_min: float, normalize: str = "mean"):
    """(P, S, w) of eq. 4, eq. 3 and the paper's eq. 5 weights."""
    d = np.maximum(np.asarray(sq_dists, np.float64), 0.0)
    p = np.asarray(sizes, np.float64) * np.asarray(losses, np.float64)
    s = np.clip((d.min() + EPS) / (d + EPS), 0.0, 1.0)
    w = p / np.maximum(s, s_min)
    if normalize == "mean":
        w = w * len(w) / max(w.sum(), 1e-12)
    elif normalize != "none":
        raise ValueError(f"unknown normalize {normalize!r}")
    return p, s, w


@jax.jit
def _sq_dists(x, bases):
    diff = bases.astype(jnp.float32) - x.astype(jnp.float32)[None]
    return jnp.sum(diff * diff, axis=1)


@jax.jit
def _weighted_sum(deltas, coef):
    return jnp.sum(deltas.astype(jnp.float32) * coef[:, None], axis=0)


def flat_round(x, bases, deltas, losses, sizes, *, s_min: float,
               eta_g: float = 1.0, normalize: str = "mean",
               dtype=jnp.float32):
    """Reference round on flat rows: returns (update, info).

    ``update`` is the device vector ``x - x'``; ``info`` holds the host
    (K,) arrays ``sq_dists``, ``staleness``, ``stat_effect``,
    ``weights``.
    """
    if dtype != jnp.float32:
        x, bases, deltas = (a.astype(dtype) for a in (x, bases, deltas))
        losses = np.asarray(jnp.asarray(losses, dtype).astype(jnp.float32))
        sizes = np.asarray(jnp.asarray(sizes, dtype).astype(jnp.float32))
    d = np.asarray(_sq_dists(x, bases), np.float64)
    p, s, w = weigh(d, losses, sizes, s_min=s_min, normalize=normalize)
    coef = jnp.asarray(w * eta_g / len(w), jnp.float32)
    upd = _weighted_sum(deltas, coef)
    return upd, {"sq_dists": d, "staleness": s, "stat_effect": p,
                 "weights": w}
