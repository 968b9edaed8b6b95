"""Vectorized, device-resident asynchronous-FL simulation engine.

The legacy simulator (now ``sim/legacy.py``) walks the event heap one
upload at a time and dispatches one jitted ``local_update`` per client
event — O(K) XLA launches plus O(K) host round-trips per server round.
This engine exploits the FedBuff structure instead: the buffer drains
completely at every aggregation, so **every server round is exactly one
window of K uploads**, and within a window no aggregation happens until
the K-th upload. All K clients' local training therefore depends only on
state known at the window start, and the whole round compiles to ONE
program (``_make_chunk_step``, scanning the shared
``core/round_body.py`` implementation — the same body the compiled
cohort step runs, optionally mesh-sharded over (data, model) per
DESIGN.md §5):

    ring   (R, Np)   device-resident version ring (R = max_staleness + 1)
                     of padded FLAT parameter rows — sharded P(None,
                     "model") on a mesh, R * Np / model_shards per device
    bases  = ring[base_slots]                      # flat gather
    deltas = vmap(local_update)(unflatten(bases))  # K clients, one launch
    losses = vmap(loss(params, probe_k))           # eq. 4 probes
    x', info = apply_server_round(...)             # eq. 3 + 4 + 5
    ring'  = ring.at[slot(t+1)].set(x')            # flat write, no round-trip

Because a client's upload timeline never depends on server state (it
trains, uploads after a sampled duration, immediately re-pulls), the
host can walk the event heap **ahead of the device**: it pre-computes up
to ``rounds_per_launch`` windows of (batch rows, base slots, staleness,
probe rows) as stacked ``(S, K, ...)`` arrays and drives all S rounds
through one ``jax.lax.scan`` launch, the version ring advancing
on-device between rounds. The clients' samples sit on the device as one
pool (``sim/pool.py``), placed once per set of client arrays; the host
draws each batch's indices from the client's own stream and stages only
those, and the round gathers ``pool_x[rows]`` inside the scan. The round
log is fetched once at the end of
the run — ``jax.device_get`` on one host, process-local addressable
shards on a process-spanning mesh (DESIGN.md §7) — so a T-round
simulation costs O(T / rounds_per_launch) launches and O(1) log syncs
instead of the legacy O(T*K) launches and O(T) syncs. Launch chunks are
clipped to eval boundaries, so the eval cadence is identical to the
legacy loop.

Event semantics match the legacy loop event-for-event on the scenarios
both can express (tested in tests/test_sim_engine.py): uploads are
processed in (time, client) heap order; a client that uploads without
triggering aggregation immediately re-pulls the *current* version; the
K-th client pulls the new version; bases older than the ring resync to
the current model with staleness 0. On top of that the engine supports
the behaviors the legacy loop cannot: availability gating, dropped
uploads (the client re-pulls and retrains; no delta is computed for the
lost upload), and trace replay (see sim/traces.py).
"""
from __future__ import annotations

import functools
import heapq
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FLConfig
from repro.obs.metrics import MetricsRegistry, default_registry
from repro.obs.trace import (
    SPAN_APPLY,
    SPAN_CHECKPOINT,
    SPAN_COLLECT,
    SPAN_GATHER,
    SPAN_HOST_SYNC,
    SPAN_PREPARE,
    SPAN_STAGE,
    Tracer,
    default_tracer,
)
from repro.core.round_body import make_ring_round
from repro.core.server_pass import flatten_tree, make_flat_spec
from repro.core.version_store import build_ring, ring_state_to_host
from repro.launch.multihost import (
    fetch_replicated,
    mesh_spans_processes,
    put_replicated,
)
from repro.sim.base import (  # noqa: F401  (re-exported for callers)
    SimResult,
    history_from_arrays,
    history_to_arrays,
    host_nbytes,
    make_batches,
    record_eval,
    resolve_behavior,
    round_log_from_arrays,
    round_log_rows,
    round_log_to_arrays,
)
from repro.sim.pool import place_client_pool
from repro.sim.scenarios import ClientBehavior, LatencyModel, Scenario
from repro.sim.traces import EventTrace


def init_version_ring(init_params: Any, fl: FLConfig, *,
                      mesh: Optional[Any] = None, shard_ring: bool = True,
                      rows: Optional[Any] = None):
    """Build the device-resident version store (``core/version_store.py``).

    Each of the R = max_staleness + 1 retained versions is one padded
    flat parameter vector on the ``make_flat_spec`` layout (DESIGN.md
    §6), stored by the ``fl.ring_codec`` codec (DESIGN.md §11): the
    default ``f32`` keeps the raw (R, n_padded) f32 matrix — bit-
    compatible with every pre-codec caller of this function — while
    ``int8`` / ``delta`` keep a compressed NamedTuple state. With a mesh
    whose ``model`` axis has size m > 1 the state is placed on the
    codec's pspecs (f32/int8 rows ``P(None, "model")`` — per device
    ``R * n_padded / m`` (bytes-per-param-scaled) instead of R full
    replicas; on a process-spanning mesh (DESIGN.md §7) each PROCESS
    holds only its model slice of every row). ``shard_ring=False`` keeps
    the same layout but replicates (the bit-parity reference the
    multi-device tests pin against). ``rows`` restores from the
    checkpointed host representation (``version_store.ring_state_to_host``)
    instead of encoding the initial params; a codec or layout mismatch
    raises naming the codec and its expected layout. Returns
    ``(spec, ring)``.
    """
    return build_ring(init_params, fl, mesh=mesh, shard_ring=shard_ring,
                      rows=rows)


class EngineState(NamedTuple):
    """Host-side snapshot of a ``run_vectorized`` run at a round boundary.

    Everything a resumed run needs to be BIT-identical to the
    uninterrupted one: the version ring + params, the host event heap,
    the per-client behavior RNG streams (``ClientBehavior.get_state``),
    and the round log / eval history accumulated so far. Serialise with
    ``engine_state_to_tree`` (arrays only — ``checkpoint/ckpt.py``
    npz-safe) and restore with ``engine_state_from_tree``.
    """

    version: int
    now: float
    num_events: int
    base_version: np.ndarray  # (n,) int64
    events: Tuple[Tuple[float, int], ...]  # pending (t, cid) uploads
    params: Any  # host pytree
    ring: Any  # codec host state: (R, n_padded) f32 matrix for the f32
    # codec (pre-codec byte layout), dict of arrays for int8/delta
    # (version_store.ring_state_to_host)
    behavior: Dict[str, np.ndarray]
    dataset_rng: np.ndarray  # (n, 6) uint64 ClientDataset batch streams
    history: List[Dict]
    round_log: List[Dict]


def engine_state_to_tree(state: EngineState) -> Dict[str, Any]:
    """EngineState -> pytree of plain arrays (``save_checkpoint``-able)."""
    ev = np.asarray(sorted(state.events), np.float64).reshape(-1, 2)
    return {
        "meta": {"version": np.int64(state.version),
                 "now": np.float64(state.now),
                 "num_events": np.int64(state.num_events)},
        "base_version": np.asarray(state.base_version, np.int64),
        "events": ev,
        "params": state.params,
        # f32 codec: the bare (R, Np) matrix (existing checkpoints stay
        # byte-compatible); compressed codecs: a stamped dict of arrays
        # (ckpt.py keypath-flattens nested dicts)
        "ring": (dict(state.ring) if isinstance(state.ring, dict)
                 else np.asarray(state.ring, np.float32)),
        "behavior": dict(state.behavior),
        "dataset_rng": np.asarray(state.dataset_rng, np.uint64),
        "round_log": round_log_to_arrays(state.round_log),
        "history": history_to_arrays(state.history),
    }


def engine_state_from_tree(tree: Dict[str, Any]) -> EngineState:
    """Inverse of ``engine_state_to_tree``."""
    ev = np.asarray(tree["events"], np.float64).reshape(-1, 2)
    return EngineState(
        version=int(tree["meta"]["version"]),
        now=float(tree["meta"]["now"]),
        num_events=int(tree["meta"]["num_events"]),
        base_version=np.asarray(tree["base_version"], np.int64),
        events=tuple((float(t), int(c)) for t, c in ev),
        params=tree["params"],
        ring=(dict(tree["ring"]) if isinstance(tree["ring"], dict)
              else np.asarray(tree["ring"], np.float32)),
        behavior=dict(tree["behavior"]),
        dataset_rng=np.asarray(tree["dataset_rng"], np.uint64),
        history=history_from_arrays(tree["history"]),
        round_log=round_log_from_arrays(tree["round_log"]),
    )


@functools.lru_cache(maxsize=64)
def _make_chunk_step(loss_fn: Callable, fl: FLConfig,
                     mesh: Optional[Any] = None) -> Callable:
    """Compile S whole server rounds (K local trainings + eq. 3/4/5 each)
    into one ``lax.scan`` program; the version ring advances on-device.
    The round maths is the shared ``core/round_body.py`` implementation —
    the same body the compiled cohort step runs — wrapped in the ring
    gather/write; ``mesh`` shards it over (data, model) (DESIGN.md §5).
    Memoized on (loss_fn, fl, mesh) so repeated runs — benchmark sweeps,
    protocol comparisons — reuse the compiled program."""
    ring_round = make_ring_round(loss_fn, fl, mesh=mesh)

    @jax.jit
    def chunk_step(params, ring, pool_x, pool_y, base_slots, train_rows,
                   probe_rows, sizes, taus, new_slots):
        def round_body(carry, xs):
            params, ring = carry
            slots, rows, prows, size, tau, new_slot = xs
            batch = (pool_x[rows], pool_y[rows])     # (K, M, B, ...)
            probe = (pool_x[prows], pool_y[prows])   # (K, B, ...)
            params, ring, info = ring_round(params, ring, slots, batch,
                                            probe, size, tau, new_slot)
            return (params, ring), info

        (params, ring), infos = jax.lax.scan(
            round_body, (params, ring),
            (base_slots, train_rows, probe_rows, sizes, taus, new_slots))
        return params, ring, infos

    return chunk_step


def run_vectorized(loss_fn: Callable, init_params: Any, clients: Sequence,
                   fl: FLConfig, total_rounds: int,
                   eval_fn: Optional[Callable[[Any], Dict]] = None,
                   eval_every: int = 5,
                   latency: Optional[LatencyModel] = None,
                   seed: int = 0,
                   behavior: Optional[ClientBehavior] = None,
                   scenario: Optional[Scenario] = None,
                   trace: Optional[EventTrace] = None,
                   record_trace: bool = False,
                   rounds_per_launch: int = 8,
                   mesh: Optional[Any] = None,
                   shard_ring: bool = True,
                   init_state: Optional[EngineState] = None,
                   capture_state: bool = False,
                   registry: Optional[MetricsRegistry] = None,
                   tracer: Optional[Tracer] = None) -> SimResult:
    """Simulate buffered-async FL, many server rounds per XLA launch.

    Same contract as the legacy ``run_async`` plus scenario/trace hooks;
    behavior precedence: ``trace`` (replay) > ``behavior`` > ``scenario``
    > ``latency`` (plain lognormal population). ``rounds_per_launch``
    bounds how far ahead of the device the host event loop runs (launch
    chunks are additionally clipped to eval boundaries). ``mesh`` runs
    every round through the sharded substrate (DESIGN.md §5): the
    K-client vmap over the ``data`` axis, the flat-vector server pass
    over ``model``, with the params device-resident on the mesh and the
    version ring stored as flat-sharded rows (``init_version_ring``:
    R * n_padded / model_shards floats per device; ``shard_ring=False``
    replicates the rows instead — same program, parity-test reference);
    no mesh is the single-device path, bit-for-bit unchanged.

    A mesh spanning PROCESSES (``launch/multihost.py``, DESIGN.md §7)
    runs the same program multi-controller: every process executes this
    host loop on identical seeds (so per-round metadata agrees without
    communication), chunk inputs are placed replicated across processes,
    and the round log is read back from process-local addressable shards
    — ``jax.device_get`` is never issued on a non-addressable array.

    ``capture_state=True`` attaches an ``EngineState`` snapshot to
    ``SimResult.final_state``; passing it back as ``init_state`` (same
    loss/clients/config/seed) resumes the run BIT-identically to the
    uninterrupted one. ``total_rounds`` always counts from round 0, so a
    resume runs ``total_rounds - init_state.version`` more rounds.

    Every client's samples are placed on the device as one pool, once per
    set of client ``x``/``y`` arrays (``sim/pool.py``): a resumed or
    repeated run with the same clients reuses the placed pool. A client's
    arrays are read, not copied, between runs, so changing them in place
    between runs is not seen. Samples that do not fit on the device raise.
    The host draws every batch's indices from the client's own stream
    (``ClientDataset.batches_rows`` / ``batch_rows``) and stages only
    those.
    """
    n = len(clients)
    k = fl.buffer_size
    ring_depth = fl.max_staleness + 1
    spans = mesh_spans_processes(mesh)

    # ---- observability plane (DESIGN.md §9) ----------------------------
    # EVERY XLA dispatch of the round program goes through this one
    # wrapper, so the registry counter — the number the launch-count
    # tests read via SimResult.num_launches — cannot miss a
    # dispatch site the way a hand-maintained `num_launches += 1` could
    # (e.g. a future final-eval or warmup path calling chunk_step
    # directly). Each call records its phases as spans (``prepare``,
    # then per launch ``collect_window``, ``gather``, ``stage``,
    # ``apply``, then ``host_sync`` and ``checkpoint``)
    # into the process's bounded default recorder unless a tracer is
    # passed.
    reg = registry if registry is not None else default_registry()
    tr = tracer if tracer is not None else default_tracer()
    _dispatches = reg.counter("engine_dispatches_total")
    _syncs = reg.counter("engine_host_syncs_total")
    _pool_puts = reg.counter("engine_pool_puts_total")
    _pool_hits = reg.counter("engine_pool_hits_total")
    _dispatches_start = _dispatches.value

    with tr.span(SPAN_PREPARE, version=0 if init_state is None
                 else init_state.version) as prep:
        _raw_chunk_step = _make_chunk_step(loss_fn, fl, mesh)
        beh = resolve_behavior(n, seed, behavior, scenario, latency, trace)
        pool, pool_bytes = place_client_pool(clients, mesh)
        (_pool_puts if pool_bytes else _pool_hits).inc()
        if init_state is None:
            params = init_params
            _, ring = init_version_ring(init_params, fl, mesh=mesh,
                                        shard_ring=shard_ring)
            version = 0
            base_version = np.zeros(n, np.int64)
            now = 0.0
            history: List[Dict] = []
            round_log_prefix: List[Dict] = []
            num_events = 0
            # every client starts training at t=0 (availability-gated)
            # from v0
            events = []
            for cid in range(n):
                start = beh.next_start(cid, 0.0)
                events.append((start + beh.duration(cid, start), cid))
            heapq.heapify(events)
            put_bytes = host_nbytes(params)
        else:
            if record_trace:
                raise ValueError(
                    "record_trace cannot resume from a checkpoint: the "
                    "duration draws before the snapshot are not in the "
                    "restored state")
            if len(init_state.base_version) != n:
                raise ValueError(
                    f"checkpoint has {len(init_state.base_version)} "
                    f"clients, this run has {n}")
            beh.set_state(init_state.behavior)
            for c, row in zip(clients, init_state.dataset_rng):
                c.set_rng_state(row)
            params = init_state.params
            _, ring = init_version_ring(init_params, fl, mesh=mesh,
                                        shard_ring=shard_ring,
                                        rows=init_state.ring)
            version = init_state.version
            base_version = np.asarray(init_state.base_version,
                                      np.int64).copy()
            now = init_state.now
            history = [dict(h) for h in init_state.history]
            if eval_fn and history and history[-1]["round"] == version \
                    and version % eval_every:
                # the snapshot run's trailing FORCED eval: off the
                # cadence, the uninterrupted run never evaluates here —
                # drop it so the resumed history matches bit-for-bit
                history.pop()
            round_log_prefix = [dict(r) for r in init_state.round_log]
            num_events = init_state.num_events
            events = [(float(t), int(c)) for t, c in init_state.events]
            heapq.heapify(events)
            put_bytes = host_nbytes((params, init_state.ring))
        if mesh is not None:
            # params live replicated on the mesh (the flat vector and the
            # K-client axis are re-partitioned inside the round's
            # shard_maps)
            params = (put_replicated(params, mesh) if spans
                      else jax.device_put(params, jax.sharding.NamedSharding(
                          mesh, jax.sharding.PartitionSpec())))
        # bytes of the ring rows and the pool placed here and of host
        # params, which the first launch copies
        prep.set(bytes=put_bytes + pool_bytes)

    def chunk_step(*args):
        _dispatches.inc()
        return _raw_chunk_step(*args)

    pending: List[Dict] = []  # per-round host metadata + device info handles
    event_log: List = []

    def maybe_eval(force=False):
        if eval_fn is None or not (force or version % eval_every == 0):
            return
        with tr.span(SPAN_HOST_SYNC, what="eval", version=version):
            record_eval(history, eval_fn, version, now, params, eval_every,
                        force)

    def reschedule(cid, t):
        start = beh.next_start(cid, t)
        heapq.heappush(events, (start + beh.duration(cid, start), cid))

    def walk_window():
        """Pop exactly K accepted uploads; the host event loop runs ahead
        of the device, which is legal because upload times never depend
        on server state. Returns the window as (t, cid, base_version,
        tau) rows."""
        nonlocal num_events, now
        window: List = []  # (t, cid, base_version, tau)
        while len(window) < k:
            t, cid = heapq.heappop(events)
            num_events += 1
            # one atomic consume: the attempt's index AND its drop verdict
            upload_idx, lost = beh.next_upload(cid)
            if lost:
                # upload lost: client re-pulls the current model, retrains
                base_version[cid] = version
                reschedule(cid, t)
                continue
            bv = int(base_version[cid])
            if bv < version - fl.max_staleness:  # fell out of the ring
                bv = version  # resync: train from the current model, tau 0
                base_version[cid] = version
            window.append((t, cid, bv, version - bv))
            event_log.append((t, cid, upload_idx, version))
            if len(window) < k:
                # no aggregation yet: re-pull the CURRENT version and go
                base_version[cid] = version
                reschedule(cid, t)
        now = window[-1][0]  # the K-th upload triggers the aggregation
        return window

    def gather(window):
        """The window's batch indices, each client's own draws, stacked
        (K, M, B) train and (K, B) probe. Each client draws its M
        local-step batches from its own stream
        (``ClientDataset.batches_rows``, the same stream as M sequential
        ``.batch()`` calls); probes draw AFTER all train draws — the
        aggregation-time order AsyncServer uses — so legacy parity holds.
        Gathering a launch's windows after walking them keeps each
        client's draws in order: the walk draws nothing from the clients'
        batch streams."""
        cids = [cid for _, cid, _, _ in window]
        train = [clients[c].batches_rows(fl.batch_size, fl.local_steps)
                 for c in cids]
        probes = [clients[c].batch_rows(fl.batch_size)
                  for c in cids]  # eq.-4 probes, FIFO order
        return np.stack(train), np.stack(probes)

    if init_state is None:
        # a resumed run's round-0 (and any snapshot-round) eval is
        # already in the restored history
        maybe_eval(force=True)
    while version < total_rounds:
        # ---- clip the launch chunk to the next eval boundary ------------
        horizon = total_rounds - version
        if eval_fn:
            horizon = min(horizon, eval_every - version % eval_every)
        s = min(rounds_per_launch, horizon)

        # ---- host: walk S windows of events, then draw their batches ----
        windows = []
        with tr.span(SPAN_COLLECT, rounds=s, version=version):
            for _ in range(s):
                w = walk_window()
                version += 1
                # window clients re-pull: the K-th gets the NEW version
                t_trigger, cid_trigger = w[-1][0], w[-1][1]
                base_version[cid_trigger] = version
                reschedule(cid_trigger, t_trigger)
                windows.append(w)
        with tr.span(SPAN_GATHER, rounds=s):
            drawn = [gather(w) for w in windows]

        # ---- device: all S rounds in one scanned launch -----------------
        with tr.span(SPAN_STAGE, rounds=s) as stage:
            cids = np.asarray([[cid for _, cid, _, _ in w] for w in windows])
            train = np.stack([d[0] for d in drawn])   # (S, K, M, B)
            probes = np.stack([d[1] for d in drawn])  # (S, K, B)
            size = pool.sizes[cids]
            if (min(train.min(), probes.min()) < 0
                    or (train >= size[..., None, None]).any()
                    or (probes >= size[..., None]).any()):
                # the pool would silently read another client's rows
                raise IndexError("a client drew a batch index outside "
                                 "its own samples")
            off = pool.offsets[cids]
            chunk_args = (
                np.asarray([[bv % ring_depth for _, _, bv, _ in w]
                            for w in windows], np.int32),
                (train + off[..., None, None]).astype(np.int32),
                (probes + off[..., None]).astype(np.int32),
                size.astype(np.float32),
                np.asarray([[tau for _, _, _, tau in w] for w in windows],
                           np.float32),
                np.asarray([(version - s + j + 1) % ring_depth
                            for j in range(s)], np.int32))
            stage.set(bytes=host_nbytes(chunk_args))
            if spans:
                # multi-controller: every process computed the SAME host
                # arrays (same seeds drive the event loop), so placing
                # them replicated across the process-spanning mesh needs
                # no communication — each process fills its shards
                # locally
                chunk_args = put_replicated(chunk_args, mesh)
        with tr.span(SPAN_APPLY, rounds=s, version=version):
            params, ring, infos = chunk_step(params, ring, pool.x, pool.y,
                                             *chunk_args)
        # keep only the round-log metadata; the batch rows would
        # otherwise pin O(total_rounds * K * batch) host memory
        pending.append({"windows": [
            {"clients": [cid for _, cid, _, _ in w],
             "tau": [tau for _, _, _, tau in w]} for w in windows],
            "v_end": version, "infos": infos})
        maybe_eval()
    maybe_eval(force=True)

    # ---- single device->host sync for the whole run's round log --------
    # On one host this is the classic ``jax.device_get``. On a
    # process-spanning mesh the info arrays are pinned fully replicated
    # (sharding/specs.info_pspec), so every process assembles the full
    # log from its own ADDRESSABLE shards — no ``device_get`` of a
    # non-addressable array, no cross-process collective (DESIGN.md §7).
    infos_list = [p.pop("infos") for p in pending]
    with tr.span(SPAN_HOST_SYNC, what="round_log", launches=len(infos_list)):
        _syncs.inc()
        if any(isinstance(leaf, jax.Array) and not leaf.is_fully_addressable
               for info in infos_list for leaf in jax.tree.leaves(info)):
            fetched = fetch_replicated(infos_list)
        else:
            fetched = jax.device_get(infos_list)
        round_log = list(round_log_prefix)
        for meta, logs in zip(pending, fetched):
            windows = meta["windows"]
            round_log.extend(round_log_rows(
                meta["v_end"] - len(windows), k,
                [w["clients"] for w in windows],
                [w["tau"] for w in windows], logs))
        trace_out = (EventTrace.from_behavior(beh, event_log)
                     if record_trace else None)
    final_state = None
    if capture_state:
        with tr.span(SPAN_CHECKPOINT, version=version):
            _syncs.inc()
            final_state = EngineState(
                version=version, now=now, num_events=num_events,
                base_version=base_version.copy(),
                events=tuple(sorted(events)),
                params=fetch_replicated(params),
                ring=ring_state_to_host(fl, fetch_replicated(ring)),
                behavior=beh.get_state(),
                dataset_rng=np.stack([c.rng_state() for c in clients]),
                history=[dict(h) for h in history],
                round_log=[dict(r) for r in round_log])
    return SimResult(history=history, server_rounds=version, sim_time=now,
                     round_log=round_log, num_events=num_events,
                     num_launches=int(_dispatches.value - _dispatches_start),
                     trace=trace_out, final_state=final_state)
