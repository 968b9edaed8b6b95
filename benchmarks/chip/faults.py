"""Faults planted in the timed path, to show that ``correct`` catches them.

Each fault wraps the program where a cell's timed path reaches it: its
``apply_server_round``, under the driver's own name for it and in the
round body that both simulator engines trace, and the streaming round
body (``make_streaming_round_body``) that the distributed-client step
and the serving controller build. Nothing here runs in a benchmark run:
the tests under ``tests/`` and ``calibrate.py`` plant them.

* ``unchanged``: the round returns the model as it was;
* ``half_batch``: half of the K updates left out, the mean taken over
  the rest (in ``apply_server_round`` the arrival mask of the program's
  own masked path; in the streaming fold the first K/2 uploads of each
  buffer folded with weight 0, so the apply's mean runs over the rest);
* ``altered``: one answer altered where it is produced: the element of
  the new model that moved most moves 5% further.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import io_callback

FAULTS = ("unchanged", "half_batch", "altered")


def move_most_moved(old, new):
    """``new`` with the element that moved most from ``old``, over every
    leaf, moved 5% further."""
    olds, treedef = jax.tree.flatten(old)
    news = jax.tree.leaves(new)
    moved = [(o - n).astype(jnp.float32).ravel() for o, n in zip(olds, news)]
    peaks = [jnp.max(jnp.abs(m)) for m in moved]
    top = jnp.max(jnp.stack(peaks))
    taken = jnp.bool_(False)
    out = []
    for n, m, peak in zip(news, moved, peaks):
        mine = (peak == top) & ~taken
        taken = taken | mine
        i = jnp.argmax(jnp.abs(m))
        step = jnp.where(mine, -0.05 * m[i], 0.0).astype(n.dtype)
        out.append(n.ravel().at[i].add(step).reshape(n.shape))
    return jax.tree.unflatten(treedef, out)


def faulty(real, fault: str):
    def round_(x, bases, deltas, losses, sizes, taus, fl, **kw):
        if fault == "half_batch":
            k = bases.shape[0]
            half = (jnp.arange(k) < k // 2).astype(jnp.float32)
            mask = kw.pop("arrival_mask", None)
            kw["arrival_mask"] = half if mask is None else half * mask
        new_x, info = real(x, bases, deltas, losses, sizes, taus, fl, **kw)
        if fault == "unchanged":
            new_x = x
        elif fault == "altered":
            new_x = move_most_moved(x, new_x)
        return new_x, info

    return round_


class _Slots:
    """The buffer slot of each upload a compiled step folds, counted on
    the host: the streaming fold is told no slot index."""

    def __init__(self, k: int):
        self.k, self.seen = k, 0

    def _take(self):
        slot = self.seen % self.k
        self.seen += 1
        return np.int32(slot)

    def next(self):
        return io_callback(self._take, jax.ShapeDtypeStruct((), jnp.int32),
                           ordered=True)


def faulty_streaming(real_make, fault: str):
    def make(loss_fn, fl):
        body = real_make(loss_fn, fl)
        contribute, apply = body.contribute, body.apply
        if fault == "half_batch":
            slots = _Slots(fl.buffer_size)

            def contribute(params, accum, ring, *rest):
                new_accum, v, fresh = body.contribute(params, accum, ring,
                                                      *rest)
                out = slots.next() < fl.buffer_size // 2
                kept = jax.tree.map(lambda a, b: jnp.where(out, a, b),
                                    accum, new_accum)
                return kept, jnp.where(out, 0.0, v), fresh
        elif fault == "unchanged":
            def apply(params, *rest):
                _, ring = body.apply(params, *rest)
                return params, ring
        elif fault == "altered":
            def apply(params, *rest):
                new_params, ring = body.apply(params, *rest)
                return move_most_moved(params, new_params), ring
        return body._replace(contribute=contribute, apply=apply)

    return make


@contextlib.contextmanager
def planted(fault: str, driver):
    """Plant ``fault`` for the programs that ``driver`` traces from now on."""
    from repro.core import cohort, round_body, serving
    from repro.sim import engine, population

    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    patches = [(round_body, "apply_server_round", faulty)]
    if hasattr(driver, "apply_server_round"):
        patches.append((driver, "apply_server_round", faulty))
    patches += [(mod, "make_streaming_round_body", faulty_streaming)
                for mod in (round_body, cohort, serving)]
    saved = [(mod, name, getattr(mod, name)) for mod, name, _ in patches]
    caches = (engine._make_chunk_step, population._make_pop_chunk)
    try:
        for (mod, name, wrap), (_, _, real) in zip(patches, saved):
            setattr(mod, name, wrap(real, fault))
        for c in caches:
            c.cache_clear()
        yield
    finally:
        for mod, name, real in saved:
            setattr(mod, name, real)
        for c in caches:
            c.cache_clear()
