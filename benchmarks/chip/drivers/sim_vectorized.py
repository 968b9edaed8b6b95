"""Driver: the paper's simulation, ``repro.core.run_async`` (default engine).

One continuous simulation of buffered async FL over the configuration's
clients: the engine walks the scenario's upload events on the host,
gathers each window's batches, and runs ``rounds_per_launch`` rounds of
K local updates plus eq. 3-5 per compiled launch. The benchmark drives
it in calls of ``rounds_per_call`` rounds, each resuming the previous
call's captured state (``capture_state`` / ``init_state``), so the run
is one simulation that is never set up again.

Set-up makes the clients' samples (``synthetic_fmnist.py``) and the
start weights from the seed, then makes the first two calls: the first
compiles the round program, the second the resume path. Those two calls
are what the check replays (``reference/fl_sim.py``): their round logs
and the weights after each. The window then makes calls until its time
is up.

The clients are the program's ``ClientDataset``, which draws each batch
with numpy ``choice`` and gathers it; the benchmark only records the
indices it draws while set-up runs, which is what lets the reference
replay them.

Traffic keys: ``scenario``, ``rounds_per_call``, ``rounds_per_launch``.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

import common
import simlib
import synthetic_fmnist
from repro.configs.base import FLConfig
from repro.core import run_async
from repro.data.synthetic import ClientDataset
from repro.models.lenet import lenet_loss
from repro.sim.scenarios import registry


class Recorder:
    """Sample indices that the clients draw while ``on`` is set: one
    ``(cid, indices)`` row per batch."""

    def __init__(self):
        self.on = True
        self.rows: List = []


@dataclasses.dataclass
class RecordingClient(ClientDataset):
    """The program's ``ClientDataset``, which also records each batch's
    indices while set-up runs. Its gathers and its RNG capture and
    resume are the program's own."""

    cid: int = 0
    rec: Optional[Recorder] = None

    def batch_indices(self, batch_size: int) -> np.ndarray:
        idx = super().batch_indices(batch_size)
        if self.rec.on:
            self.rec.rows.append((self.cid, idx))
        return idx


@dataclasses.dataclass
class State:
    traffic: dict
    fl: FLConfig
    clients: list
    data: list
    params0: dict
    sim: simlib.Resumable
    rec: Recorder
    rows: list = dataclasses.field(default_factory=list)
    params: list = dataclasses.field(default_factory=list)

    def describe(self) -> str:
        t = self.traffic
        return (f"sim_vectorized: clients={len(self.clients)}x"
                f"{self.clients[0].size} K={self.fl.buffer_size} "
                f"M={self.fl.local_steps} B={self.fl.batch_size} "
                f"scenario={t['scenario']} rounds_per_call="
                f"{t['rounds_per_call']} rounds_per_launch="
                f"{t['rounds_per_launch']} precision={self.sim.precision}")

    def traffic_lines(self) -> list:
        return [simlib.event_stream_line(self.rows)]


def setup(config: dict, traffic: dict, seed: int, work) -> State:
    with common.span("inputs"):
        data = synthetic_fmnist.federated(
            common.sub_seed(seed, 4), config["num_clients"],
            config["samples_per_client"], config["dirichlet_alpha"])
        params0 = simlib.init_params(seed)
    rec = Recorder()
    clients = [RecordingClient(x, y, common.sub_seed(seed, 5) + i, cid=i,
                               rec=rec)
               for i, (x, y) in enumerate(data)]
    fl = simlib.fl_config(config, len(clients))
    scenario = registry()[traffic["scenario"]]
    sim_seed = common.sub_seed(seed, 3)

    def run(init_state, total):
        return run_async(lenet_loss, params0, clients, fl,
                         total_rounds=total, seed=sim_seed,
                         scenario=scenario,
                         rounds_per_launch=traffic["rounds_per_launch"],
                         init_state=init_state, capture_state=True)

    state = State(traffic=traffic, fl=fl, clients=clients,
                  data=data, params0=params0,
                  sim=simlib.Resumable(run, config["matmul_precision"]),
                  rec=rec)
    state.rows, state.params = simlib.setup_calls(
        state.sim, traffic["rounds_per_call"])
    rec.on = False
    return state


def window(state: State, seconds: float) -> dict:
    return simlib.window(state.sim, state.traffic["rounds_per_call"],
                         state.fl.buffer_size, seconds)


def replay_rounds(state: State) -> list:
    """The set-up calls' rounds with the sample indices each client drew:
    per round, the K clients' M train batches each, then their K probe
    batches, in window order."""
    k, m, b = state.fl.buffer_size, state.fl.local_steps, state.fl.batch_size
    draws = state.rec.rows
    per_round = k * (m + 1)
    if len(draws) != per_round * len(state.rows):
        raise RuntimeError(f"recorded {len(draws)} batches for "
                           f"{len(state.rows)} rounds of K={k}, M={m}")
    out = []
    for r, log in enumerate(state.rows):
        rnd = draws[per_round * r:per_round * (r + 1)]
        train, probe = rnd[:k * m], rnd[k * m:]
        cids = [c for c, _ in train[::m]]
        if (cids != list(log["clients"]) or [c for c, _ in probe] != cids
                or [c for c, _ in train] != [c for c in cids
                                             for _ in range(m)]):
            raise RuntimeError(f"round {r}: recorded draws do not follow "
                               "the round log's clients")
        out.append({"clients": cids, "tau": log["tau"],
                    "train": np.stack([i for _, i in train]).reshape(k, m, b),
                    "probe": np.stack([i for _, i in probe])})
    return out


def check(state: State, control: bool = False) -> dict:
    """Replay the set-up calls' rounds; compare their round logs and the
    weights after each call."""
    state.sim = None
    per_call = state.traffic["rounds_per_call"]
    sizes = np.asarray([c.size for c in state.clients], np.float64)
    return simlib.check(state.params0, state.rows,
                        [per_call - 1, 2 * per_call - 1], state.params,
                        replay_rounds(state), state.data, state.fl, sizes,
                        control)
