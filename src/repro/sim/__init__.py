"""Vectorized scenario-simulation engine (DESIGN.md §4).

``engine``     — device-resident windowed round engine (one XLA launch per
                 server round); the default behind ``repro.core.run_async``.
``population`` — fully device-resident client STATE machine (counter-based
                 RNG, vmapped behavior kernel, device top-k windows) for
                 million-client scenarios (DESIGN.md §10).
``pool``       — every client's samples as one device-resident pool, which
                 both engines gather their batches from.
``scenarios``  — registry of named, composable client-behavior models.
``traces``     — record/replay of client timelines for exact reproducibility.
``metrics``    — staleness / participation / weight-entropy telemetry.
``legacy``     — the original per-event heapq loop (parity reference).
"""
from repro.sim import metrics  # noqa: F401
from repro.sim.arrivals import TrafficGenerator  # noqa: F401
from repro.sim.base import (  # noqa: F401
    SimResult,
    make_batches,
    resolve_behavior,
)
from repro.sim.engine import run_vectorized  # noqa: F401
from repro.sim.legacy import run_async_legacy, run_sync  # noqa: F401
from repro.sim.population import (  # noqa: F401
    CounterBehavior,
    CounterDataset,
    DevicePool,
    PopulationEngineState,
    collect_windows,
    make_counter_clients,
    population_state_from_tree,
    population_state_to_tree,
    run_population,
)
from repro.sim.scenarios import (  # noqa: F401
    ClientBehavior,
    LatencyModel,
    Scenario,
    get_scenario,
    register,
    registry,
)
from repro.sim.traces import EventTrace  # noqa: F401
