"""Plain reference of the streaming (distributed-client) round, in float64.

One client's uploads fold one at a time into a running accumulator, and
the K-th upload of a buffer applies it. Written from the equations,
importing nothing of the program. For each upload, against the current
model x:

    eq. 4  P = N * L,          L: loss of x on the upload's probe batch
    eq. 3  d = sum of the last min(tau, R - 1) squared update norms of
               the ring (newest first, R entries), the cross terms left
               out; S = clip((0 + eps) / (d + eps), 0, 1), the reference
               distance pinned at x itself
    paper  v = P / max(S, s_min)
    fold   A = A + v * Delta,  Delta = base - end of the local update

and at the K-th upload, with "mean" normalization:

    eq. 5  x' = x - eta_g / sum(v) * A
    ring   = [||x - x'||^2, ring[:-1]];  A = 0

The local update and the loss are the caller's, in float64: the
reference knows only the fold.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Sequence

import numpy as np

EPS = 1e-12  # keeps eq. 3 finite when the distance is 0


def run(params: Sequence[np.ndarray], uploads: Sequence[Dict], *, k: int,
        local_update: Callable, loss: Callable, s_min: float,
        max_staleness: int, eta_g: float = 1.0):
    """Fold ``uploads`` into ``params`` (a list of leaves).

    Each upload is a dict with ``local`` and ``probe`` (what
    ``local_update(x, local) -> delta leaves`` and ``loss(x, probe) ->
    float`` take), ``data_size`` and ``tau``. Returns ``(x, per_upload)``:
    the leaves after the last apply, and for each upload its ``v`` and
    ``fresh`` loss.
    """
    x = [np.asarray(p, np.float64) for p in params]
    acc = [np.zeros_like(a) for a in x]
    ring = np.zeros(max_staleness)
    vs: List[float] = []
    per_upload = []
    for up in uploads:
        delta = local_update(x, up["local"])
        fresh = float(loss(x, up["probe"]))
        p = float(up["data_size"]) * fresh
        tau = min(int(up["tau"]), max_staleness - 1)
        d = float(np.sum(ring[:tau]))
        s = min(max(EPS / (d + EPS), 0.0), 1.0)
        v = p / max(s, s_min)
        acc = [a + v * np.asarray(dl, np.float64) for a, dl in zip(acc, delta)]
        vs.append(v)
        per_upload.append({"v": v, "fresh": fresh})
        if len(vs) == k:
            scale = eta_g / max(sum(vs), 1e-12)
            upd = [scale * a for a in acc]
            x = [xi - u for xi, u in zip(x, upd)]
            ring = np.concatenate([[sum(float(np.sum(u * u)) for u in upd)],
                                   ring[:-1]])
            acc = [np.zeros_like(a) for a in x]
            vs = []
    return x, per_upload
