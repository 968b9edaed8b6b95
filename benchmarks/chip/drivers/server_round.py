"""Driver: the aggregator's server round, ``apply_server_round``.

One round folds K buffered client updates into the model: eq. 3
distances of the K bases from x, the eq. 4 effects from the clients'
losses and sizes, the paper's weights, and the eq. 5 reduction, on the
flat f32 layout that the version ring holds (``make_flat_spec``), in
the mode that ``resolve_mode("auto")`` picks on the platform.

Set-up makes x, the K bases and the K deltas on the device in one
jitted call from the seed, draws the per-client scalars, and compiles
the round once. The window repeats the round on those inputs, each round
ended by ``block_until_ready``, as the new version must be ready to pull
before it is served. The check compares the window's last round with
``reference/server_round.py``.

Traffic keys: ``k`` (updates per round), ``weighting``, ``spread``
(the range of base distances from x, per element), ``loss`` and
``size`` (ranges of the per-client scalars), ``max_tau``.
"""
from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

import common
from reference import server_round as ref
from repro.configs.base import FLConfig
from repro.core.server_pass import apply_server_round, make_flat_spec, resolve_mode


@dataclasses.dataclass
class State:
    fl: FLConfig
    n: int
    n_padded: int
    mode: str
    fn: object
    args: tuple
    out: object = None

    def describe(self) -> str:
        k = self.args[1].shape[0]
        return (f"server_round: K={k} Np={self.n} padded={self.n_padded} "
                f"mode={self.mode} weighting={self.fl.weighting}")


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def make_rows(k: int, n: int, n_padded: int, key, spread):
    """x (n_padded,), bases and deltas (K, n_padded), zero past n.

    Base k lies ``spread[k]`` per element from x, so eq. 3 weighs the
    clients differently; the small x keeps ``x - x'`` an exact-enough
    read of the update.
    """
    kx, kb, kd = jax.random.split(key, 3)
    live = (jnp.arange(n_padded) < n).astype(jnp.float32)
    x = 0.1 * jax.random.normal(kx, (n_padded,), jnp.float32) * live
    bases = x[None] + spread[:, None] * jax.random.normal(
        kb, (k, n_padded), jnp.float32) * live
    deltas = jax.random.normal(kd, (k, n_padded), jnp.float32) * live
    return x, bases, deltas


def setup(config: dict, traffic: dict, seed: int, work) -> State:
    k = int(traffic["k"])
    n = work.n_params(config)
    fl = FLConfig(buffer_size=k, weighting=traffic["weighting"])
    spec = make_flat_spec(jax.ShapeDtypeStruct((n,), jnp.float32))
    mode, interpret = resolve_mode("auto")
    g = common.rng(seed, 2)
    spread = g.uniform(*traffic["spread"], size=k).astype(np.float32)
    losses = g.uniform(*traffic["loss"], size=k).astype(np.float32)
    sizes = g.integers(*traffic["size"], size=k).astype(np.float32)
    taus = g.integers(0, traffic["max_tau"] + 1, size=k).astype(np.float32)
    with common.span("inputs"):
        key = jax.random.PRNGKey(common.sub_seed(seed, 1))
        rows = make_rows(k, n, spec.n_padded, key, jnp.asarray(spread))
        args = rows + tuple(jnp.asarray(a) for a in (losses, sizes, taus))
        jax.block_until_ready(args)

    def server_round(x, bases, deltas, losses, sizes, taus):
        return apply_server_round(x, bases, deltas, losses, sizes, taus, fl,
                                  mode=mode, block_n=spec.block_n,
                                  interpret=interpret)

    fn = jax.jit(server_round)
    state = State(fl=fl, n=n, n_padded=spec.n_padded, mode=mode, fn=fn,
                  args=args)
    state.out = jax.block_until_ready(fn(*args))  # compiles
    return state


def window(state: State, seconds: float) -> dict:
    fn, args = state.fn, state.args
    rounds = 0
    t0 = time.perf_counter()
    while True:
        with common.span("pass_dispatch"):
            out = fn(*args)
        with common.span("sync"):
            jax.block_until_ready(out)
        rounds += 1
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            break
    state.out = out
    k = args[1].shape[0]
    return {"rounds": rounds, "updates": rounds * k, "elapsed_s": elapsed}


def check(state: State, control: bool = False) -> dict:
    """Readings of the window's last round against the reference.

    ``control=True`` puts the reference, with its rows held in bfloat16,
    in the program's place.
    """
    x, bases, deltas, losses, sizes, _ = state.args
    fl = state.fl
    new_x, info = state.out
    state.out = state.fn = None
    upd_p = x - new_x
    info_p = {key: np.asarray(info[key], np.float64)
              for key in ("sq_dists", "staleness", "stat_effect", "weights")}
    del new_x, info
    upd_r, info_r = ref.flat_round(
        x, bases, deltas, np.asarray(losses), np.asarray(sizes),
        s_min=fl.s_min, eta_g=fl.global_lr, normalize=fl.normalize)
    if control:
        upd_p, info_p = ref.flat_round(
            x, bases, deltas, np.asarray(losses), np.asarray(sizes),
            s_min=fl.s_min, eta_g=fl.global_lr, normalize=fl.normalize,
            dtype=jnp.bfloat16)
    gap = float(jnp.max(jnp.abs(upd_p - upd_r)) / jnp.max(jnp.abs(upd_r)))
    out = {"update": gap}
    for key in ("sq_dists", "staleness", "stat_effect", "weights"):
        out[key] = common.rel_each(info_p[key], info_r[key])
    return out
