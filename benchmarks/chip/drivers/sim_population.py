"""Driver: the population simulator, ``run_async(engine="population")``.

A cross-device population of ``num_clients`` clients whose whole
scenario state machine lives on the chip: each window's K uploads are
picked by a vectorised peek and ``top_k`` over every client, the
batches are gathered from one shared sample pool on the device, and
``rounds_per_launch`` rounds of K local updates plus eq. 3-5 run per
launch, with no host walk. The benchmark drives it in calls of
``rounds_per_call`` rounds, each resuming the previous call's captured
state, so the run is one simulation that is never set up again.

Client ``cid`` owns the pool rows ``[offset, offset + samples)`` with
``offset = cid * 7919 mod (pool - samples + 1)``, and draws its batches
from the program's counter streams: train batch ``j`` of an upload is
``randint(fold_in(fold_in(fold_in(key(seed), 303), cid), bk0 + j))``
and its probe ``fold_in(fold_in(fold_in(key(seed), 304), cid),
bk0 // M)``, where ``bk0`` is M times the client's earlier accepted
uploads. The check recomputes those indices from the round log's event
stream with the benchmark's own copy of that rule and replays the
set-up calls' rounds (``reference/fl_sim.py``).

The engine's own seed (``engine_seed``: arrivals, durations, the
counter streams) is fixed by the traffic, and ``--seed`` makes the pool
and the start weights. The engine bakes its seed into the programs it
compiles, so a new engine seed compiles them anew (about a minute on the
chip); with it fixed, every run after a checkout's first finds them in
the compile cache, and every seed runs the same arrivals.

Traffic keys: ``scenario``, ``engine_seed``, ``num_clients``,
``pool_samples``, ``samples_per_client``, ``rounds_per_call``,
``rounds_per_launch``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

import common
import simlib
import synthetic_fmnist
from repro.configs.base import FLConfig
from repro.core import run_async
from repro.models.lenet import lenet_loss
from repro.sim.population import DevicePool
from repro.sim.scenarios import registry

TAG_TRAIN, TAG_PROBE = 303, 304
OFFSET_STRIDE = 7919


def offsets(num_clients: int, pool: int, samples: int) -> np.ndarray:
    return ((np.arange(num_clients, dtype=np.int64) * OFFSET_STRIDE)
            % (pool - samples + 1)).astype(np.int32)


@dataclasses.dataclass
class State:
    traffic: dict
    fl: FLConfig
    pool: tuple              # host (x, y)
    offsets: np.ndarray
    params0: dict
    sim_seed: int
    sim: simlib.Resumable
    rows: list = dataclasses.field(default_factory=list)
    params: list = dataclasses.field(default_factory=list)

    def describe(self) -> str:
        t = self.traffic
        return (f"sim_population: clients={t['num_clients']} over a pool of "
                f"{t['pool_samples']} ({t['samples_per_client']} each) "
                f"K={self.fl.buffer_size} M={self.fl.local_steps} "
                f"B={self.fl.batch_size} scenario={t['scenario']} "
                f"rounds_per_call={t['rounds_per_call']} rounds_per_launch="
                f"{t['rounds_per_launch']} precision={self.sim.precision}")

    def traffic_lines(self) -> list:
        return [simlib.event_stream_line(self.rows)]


def setup(config: dict, traffic: dict, seed: int, work) -> State:
    n, spc = traffic["num_clients"], traffic["samples_per_client"]
    with common.span("inputs"):
        x, y = synthetic_fmnist.pool(common.sub_seed(seed, 4),
                                     traffic["pool_samples"])
        offs = offsets(n, len(x), spc)
        pool = DevicePool(x=jnp.asarray(x), y=jnp.asarray(y),
                          offsets=jnp.asarray(offs),
                          sizes=jnp.full((n,), spc, jnp.int32))
        params0 = simlib.init_params(seed)
    fl = simlib.fl_config(config, n)
    scenario = registry()[traffic["scenario"]]
    sim_seed = int(traffic["engine_seed"])

    def run(init_state, total):
        return run_async(lenet_loss, params0, pool, fl, total_rounds=total,
                         engine="population", seed=sim_seed,
                         scenario=scenario,
                         rounds_per_launch=traffic["rounds_per_launch"],
                         init_state=init_state, capture_state=True)

    state = State(traffic=traffic, fl=fl, pool=(x, y), offsets=offs,
                  params0=params0, sim_seed=sim_seed,
                  sim=simlib.Resumable(run, config["matmul_precision"]))
    state.rows, state.params = simlib.setup_calls(
        state.sim, traffic["rounds_per_call"])
    return state


def window(state: State, seconds: float) -> dict:
    return simlib.window(state.sim, state.traffic["rounds_per_call"],
                         state.fl.buffer_size, seconds)


@jax.jit
def _draws(seed, cid, bk0, offset, size, steps_batch):
    """(M, B) train and (B,) probe pool rows of one accepted upload."""
    m, b = steps_batch.shape
    root = jax.random.PRNGKey(seed)
    kc = jax.random.fold_in(jax.random.fold_in(root, TAG_TRAIN), cid)
    train = jax.vmap(lambda j: offset + jax.random.randint(
        jax.random.fold_in(kc, bk0 + j), (b,), 0, size))(
            jnp.arange(m, dtype=jnp.int32))
    kp = jax.random.fold_in(jax.random.fold_in(
        jax.random.fold_in(root, TAG_PROBE), cid), bk0 // m)
    return train, offset + jax.random.randint(kp, (b,), 0, size)


def replay_rounds(state: State) -> list:
    m, b = state.fl.local_steps, state.fl.batch_size
    spc = state.traffic["samples_per_client"]
    shape = jnp.zeros((m, b), jnp.int8)
    accepted: dict = {}
    out = []
    with jax.default_device(simlib.fl_sim.device()):
        for log in state.rows:
            train, probe = [], []
            for cid in log["clients"]:
                bk0 = m * accepted.get(cid, 0)
                accepted[cid] = accepted.get(cid, 0) + 1
                t, p = _draws(state.sim_seed, cid, bk0,
                              int(state.offsets[cid]), spc, shape)
                train.append(np.asarray(t))
                probe.append(np.asarray(p))
            out.append({"clients": [0] * len(train), "tau": log["tau"],
                        "train": np.stack(train), "probe": np.stack(probe)})
    return out


def check(state: State, control: bool = False) -> dict:
    """Replay the set-up calls' rounds; compare their round logs and the
    weights after each call. Indices are absolute pool rows, so the
    replay sees one 'client' holding the whole pool."""
    state.sim = None
    per_call = state.traffic["rounds_per_call"]
    sizes = np.full(1, state.traffic["samples_per_client"], np.float64)
    return simlib.check(state.params0, state.rows,
                        [per_call - 1, 2 * per_call - 1], state.params,
                        replay_rounds(state), [state.pool], state.fl, sizes,
                        control)
