"""Pieces shared by the simulator drivers: the FL configuration, the
seeded start weights, the resumable call, and the check that replays
the set-up calls' rounds with ``reference/fl_sim.py``."""
from __future__ import annotations

import hashlib
import time
from typing import Callable, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import common
from reference import fl_sim, lenet
from repro.configs.base import FLConfig

LOG_KEYS = (("sq_dists", "sq_dists"), ("staleness", "staleness_deg"),
            ("stat_effect", "stat_effect"), ("weights", "weights"))


def fl_config(config: dict, num_clients: int) -> FLConfig:
    return FLConfig(num_clients=num_clients, buffer_size=config["k"],
                    local_steps=config["local_steps"],
                    local_lr=config["local_lr"],
                    batch_size=config["batch_size"],
                    weighting=config["weighting"],
                    max_staleness=config["max_staleness"],
                    ring_codec=config["ring_codec"])


@jax.jit
def _init(key):
    return lenet.init(key)


def init_params(seed: int):
    """LeNet start weights, made on the device in one call."""
    return jax.block_until_ready(
        _init(jax.random.PRNGKey(common.sub_seed(seed, 6))))


class Resumable:
    """One simulation driven in calls of ``rounds`` rounds, each resuming
    the state the previous call captured. ``run(init_state, total)``
    calls the engine and returns its ``SimResult``."""

    def __init__(self, run: Callable, precision: str):
        self._run = run
        self.precision = precision
        self.resume = None
        self.version = 0

    def call(self, rounds: int):
        with jax.default_matmul_precision(self.precision):
            res = self._run(self.resume, self.version + rounds)
        self.version = res.server_rounds
        # the next call resumes here; the round log and history this
        # call carried stay with its caller
        self.resume = res.final_state._replace(round_log=[], history=[])
        return res


def setup_calls(sim: Resumable, rounds: int):
    """The two set-up calls: the first compiles the fresh run, the second
    the resume path. Returns their round-log rows and the weights after
    each."""
    rows: List[dict] = []
    params: List[list] = []
    for _ in range(2):
        with common.span("engine_call"):
            res = sim.call(rounds)
        rows.extend(res.round_log)
        params.append(jax.tree.leaves(res.final_state.params))
    return rows, params


def window(sim: Resumable, rounds: int, k: int, seconds: float) -> dict:
    done = launches = 0
    t0 = time.perf_counter()
    while True:
        with common.span("engine_call"):
            res = sim.call(rounds)
        done += len(res.round_log)
        launches += res.num_launches
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            break
    return {"rounds": done, "updates": done * k, "launches": launches,
            "elapsed_s": elapsed}


def event_stream_line(rows: Sequence[dict]) -> str:
    h = hashlib.sha256()
    for row in rows:
        h.update(np.asarray(row["clients"], np.int64).tobytes())
        h.update(np.asarray(row["tau"], np.int64).tobytes())
    taus = [t for row in rows for t in row["tau"]]
    return (f"event stream of the {len(rows)} set-up rounds: "
            f"sha256={h.hexdigest()} mean_tau={float(np.mean(taus))!r} "
            f"max_tau={max(taus)}")


def check(params0, rows: Sequence[dict], ends: Sequence[int],
          prog_params: Sequence[list], replay_rounds: Sequence[dict], data,
          fl: FLConfig, sizes: np.ndarray, control: bool) -> dict:
    """Replay ``replay_rounds`` and compare the program's round-log
    ``rows`` and its weights after the rounds ``ends`` with it.
    ``control=True`` puts the replay with bf16 operands in the program's
    place."""
    kw = dict(lr=fl.local_lr, sizes=sizes, s_min=fl.s_min,
              eta_g=fl.global_lr, normalize=fl.normalize, keep=ends)
    logs, kept = fl_sim.replay(params0, replay_rounds, data, **kw)
    if control:
        c_logs, c_kept = fl_sim.replay(params0, replay_rounds, data,
                                       dtype=jnp.bfloat16, **kw)
        rows = [{pk: lg[rk] for rk, pk in LOG_KEYS} for lg in c_logs]
        prog_params = [c_kept[r] for r in ends]
    out = {}
    for rk, pk in LOG_KEYS:
        prog = np.asarray([row[pk] for row in rows])
        ref = np.asarray([lg[rk] for lg in logs])
        out[rk] = common.rel_each(prog, ref)
    start = [np.asarray(a) for a in jax.tree.leaves(params0)]
    out["params_change"] = max(
        common.leaf_change_gap(start, p, kept[r])
        for p, r in zip(prog_params, ends))
    return out
