"""Plain replay of the simulated FL run: K local updates, eq. 3-5.

Given the seeded start weights, each client's samples, and the run's
event stream (which clients fold in which round, from which base, on
which sample indices), replay every round from the equations:

    Delta_i = base_i - SGD^M(base_i)          (local update, plain SGD)
    L_i     = loss(x, probe_i)                (eq. 4 probe)
    d_i     = ||x - base_i||^2                (eq. 3)
    x'      = x - eta_g / K * sum_i w_i Delta_i  (eq. 5, reference/server_round.weigh)

Runs on the host's CPU device when JAX has one, so that it shares no
chip arithmetic with the program; the weights and eq. 5 in float64.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from reference import lenet
from reference.server_round import weigh


def device():
    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        return jax.devices()[0]


@functools.partial(jax.jit, static_argnames=("lr", "dtype"))
def _clients(x, bases, xs, ys, px, py, lr, dtype):
    deltas = jax.vmap(lambda b, a, c: lenet.local_sgd(b, a, c, lr, dtype))(
        bases, xs, ys)
    losses = jax.vmap(lambda a, c: lenet.loss(x, a, c, dtype))(px, py)
    d = sum(jnp.sum(jnp.square(leaf_b - leaf_x[None]),
                    axis=tuple(range(1, leaf_b.ndim)))
            for leaf_x, leaf_b in zip(jax.tree.leaves(x),
                                      jax.tree.leaves(bases)))
    return deltas, losses, d


def replay(params0, rounds: Sequence[Dict], data: Sequence, *, lr: float,
           sizes: np.ndarray, s_min: float, eta_g: float = 1.0,
           normalize: str = "mean", dtype=jnp.float32,
           keep: Sequence[int] = ()):
    """Replay ``rounds`` from ``params0``.

    Each round is a dict with ``clients`` (K,), ``tau`` (K,),
    ``train`` (K, M, B) and ``probe`` (K, B) sample indices into each
    client's ``data[cid] = (x, y)``. Returns the per-round logs
    (``sq_dists``, ``staleness``, ``stat_effect``, ``weights``, each
    (K,)) and the host weights after each round index in ``keep``.
    """
    dev = device()
    to_dev = functools.partial(jax.device_put, device=dev)
    leaves0, treedef = jax.tree.flatten(params0)
    versions: List = [[np.asarray(a, np.float32) for a in leaves0]]
    logs, kept = [], {}
    for r, row in enumerate(rounds):
        x = versions[r]
        bases = [versions[r - int(t)] for t in row["tau"]]
        stacked = [np.stack(col) for col in zip(*bases)]
        cids = row["clients"]
        xs = np.stack([data[c][0][i] for c, i in zip(cids, row["train"])])
        ys = np.stack([data[c][1][i] for c, i in zip(cids, row["train"])])
        px = np.stack([data[c][0][i] for c, i in zip(cids, row["probe"])])
        py = np.stack([data[c][1][i] for c, i in zip(cids, row["probe"])])
        deltas, losses, d = _clients(
            to_dev(jax.tree.unflatten(treedef, x)),
            to_dev(jax.tree.unflatten(treedef, stacked)),
            to_dev(xs), to_dev(ys), to_dev(px), to_dev(py), lr=lr,
            dtype=dtype)
        d = np.asarray(d, np.float64)
        p, s, w = weigh(d, np.asarray(losses), sizes[list(cids)],
                        s_min=s_min, normalize=normalize)
        coef = w * eta_g / len(w)
        new = [(np.asarray(a, np.float64)
                - np.tensordot(coef, np.asarray(dl, np.float64), axes=1)
                ).astype(np.float32)
               for a, dl in zip(x, jax.tree.leaves(deltas))]
        versions.append(new)
        logs.append({"sq_dists": d, "staleness": s, "stat_effect": p,
                     "weights": w})
        if r in keep:
            kept[r] = new
    return logs, kept
