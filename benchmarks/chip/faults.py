"""Faults planted in the timed path, to show that ``correct`` catches them.

Each fault wraps the program's ``apply_server_round`` where the cell's
driver reaches it: the server-round driver's own name for it, or the
round body that both simulator engines trace. Nothing here runs in a
benchmark run: the tests under ``tests/`` and ``calibrate.py`` plant
them.

* ``unchanged``: the round returns x as it was;
* ``half_batch``: half of the K updates left out, the mean taken over
  the rest (the arrival mask of the program's own masked path);
* ``altered``: one answer altered where it is produced: the element of
  the new x that moved most moves 5% further.
"""
from __future__ import annotations

import contextlib

import jax.numpy as jnp

FAULTS = ("unchanged", "half_batch", "altered")


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
            moved = x - new_x
            i = jnp.argmax(jnp.abs(moved))
            new_x = new_x.at[i].add(-0.05 * moved[i])
        return new_x, info

    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    return round_


@contextlib.contextmanager
def planted(fault: str, driver):
    """Plant ``fault`` for the programs that ``driver`` traces from now on."""
    from repro.core import round_body
    from repro.sim import engine, population

    targets = [(round_body, "apply_server_round")]
    if hasattr(driver, "apply_server_round"):
        targets.append((driver, "apply_server_round"))
    saved = [(mod, name, getattr(mod, name)) for mod, name in targets]
    caches = (engine._make_chunk_step, population._make_pop_chunk)
    try:
        for mod, name, real in saved:
            setattr(mod, name, faulty(real, fault))
        for c in caches:
            c.cache_clear()
        yield
    finally:
        for mod, name, real in saved:
            setattr(mod, name, real)
        for c in caches:
            c.cache_clear()
