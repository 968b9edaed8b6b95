"""Compiled production FL round — the paper's protocol as one SPMD program.

Two deployment mappings (DESIGN.md §2.1):

* **replicated-client** (archs whose replica fits one model-axis group):
  the ``data`` mesh axis carries C concurrent cohort slots (clients). State
  holds, per slot, the client's *current* local params and the *base*
  snapshot it last pulled — so eq. (3) staleness is computed EXACTLY
  (per-slot ``||x^t - base_i||^2`` full-model reductions), the fresh-loss
  probe (eq. 4) evaluates x^t on each client's probe batch, and the
  weighted delta reduction (eq. 5) is one masked psum over ``data``.
  Stragglers (arrival_mask=0) carry their local progress into the next
  round instead of contributing — identical semantics to the event-driven
  simulator, but fully compiled. The round maths itself lives in
  ``core/round_body.py`` — the SAME implementation the vectorized engine
  scans (DESIGN.md §5) — so engine==cohort agreement holds by
  construction; this module only adds the slot state machine (resync of
  arrivals, straggler carry-over, version bookkeeping).

* **distributed-client** (arctic-480b, qwen1.5-110b): one client spans the
  whole mesh (FSDP x TP). The K-buffer fills across sequential step calls
  with a *running weighted accumulator* — the O(1)-memory streaming form
  of eq. (5), now implemented by ``core/round_body.py::
  make_streaming_round_body`` so all three deployment mappings share one
  round implementation. Per-upload weights run the SAME ``weighting.py``
  policy code as the exact paths (``s_min`` cap included) with the eq. 3
  reference pinned to the current model; staleness distances use the
  scalar update-norm ring (cross terms dropped; exact variant =
  simulator; parity tested on small models in tests/test_round_body.py).
  This module only keeps the buffer state machine, and the host loop that
  folds one upload after another through it (``fold_upload``).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional

import heapq

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FLConfig
from repro.core.round_body import make_round_body, make_streaming_round_body
from repro.obs.metrics import MetricsRegistry, default_registry
from repro.obs.trace import (
    SPAN_CONTRIBUTE,
    SPAN_HOST_SYNC,
    SPAN_STAGE,
    Tracer,
    default_tracer,
)
from repro.utils.pytree import tree_sq_dist


# ---------------------------------------------------------------------------
# replicated-client cohort
# ---------------------------------------------------------------------------


class CohortState(NamedTuple):
    global_params: Any  # x^t (replicated over data, TP over model)
    client_params: Any  # (C, ...) current local state per slot
    client_base: Any  # (C, ...) base snapshot each slot pulled (eq. 3)
    client_version: jnp.ndarray  # (C,) int32 — version of that base
    version: jnp.ndarray  # scalar int32, t


def init_cohort_state(params: Any, cohort: int) -> CohortState:
    def stack():
        # distinct buffers per field: donation must never see aliased args
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (cohort,) + x.shape) * 1,
            params)

    return CohortState(
        global_params=params,
        client_params=stack(),
        client_base=stack(),
        client_version=jnp.zeros((cohort,), jnp.int32),
        version=jnp.zeros((), jnp.int32),
    )


def make_cohort_step(loss_fn: Callable, fl: FLConfig, *,
                     mesh: Any = None) -> Callable:
    """Build the compiled replicated-client FL round.

    loss_fn(params, batch_dict) -> (scalar, metrics).
    Batch layout (C = cohort slots on the data axis):
      batch["local"] : leaves (C, M, b, ...) — M local steps per slot
      batch["probe"] : leaves (C, bp, ...)   — fresh-loss probe (eq. 4)
      batch["arrival"]: (C,) f32 {0,1}       — slots buffered this round
      batch["data_sizes"]: (C,) f32          — N_i

    ``mesh`` shards the C-slot vmap over ``data`` and the flat-vector
    server pass over ``model`` (core/round_body.py, DESIGN.md §5); with
    no mesh the step is the single-device program it always was.
    """
    round_body = make_round_body(loss_fn, fl, mesh=mesh)

    def step(state: CohortState, batch: Dict[str, Any]):
        arrival = batch["arrival"].astype(jnp.float32)
        tau = (state.version - state.client_version).astype(jnp.float32)

        # --- the paper's round: local training + eq. 3/4/5 (shared body) -
        new_global, end_params, info = round_body(
            state.global_params, state.client_base, batch["local"],
            batch["probe"], batch["data_sizes"], tau,
            client_params=state.client_params, arrival_mask=arrival)
        fresh, s, w = info["fresh_loss"], info["staleness"], info["weights"]

        # --- arrivals re-sync; stragglers keep their local progress ------
        def resync(stacked_new_src, stacked_old):
            def leaf(g, old):
                m = arrival.reshape((-1,) + (1,) * (old.ndim - 1))
                return jnp.where(m > 0, g[None].astype(old.dtype), old)
            return jax.tree.map(leaf, stacked_new_src, stacked_old)

        new_client_params = resync(new_global, end_params)
        new_client_base = resync(new_global, state.client_base)
        new_version = state.version + 1
        new_client_version = jnp.where(arrival > 0, new_version,
                                       state.client_version).astype(jnp.int32)

        # round telemetry over ARRIVED slots only: zero-weight non-arrival
        # slots (stragglers) must not pollute the mins/means (jit-safe
        # where-reductions; an empty round reports neutral 0.0s)
        n_arr = jnp.sum(arrival)
        any_arr = n_arr > 0
        metrics = {
            "fresh_loss_mean": jnp.where(
                any_arr, jnp.sum(fresh * arrival) / jnp.maximum(n_arr, 1.0),
                0.0),
            "staleness_min": jnp.where(
                any_arr, jnp.min(jnp.where(arrival > 0, s, jnp.inf)), 0.0),
            "weights_max": jnp.where(
                any_arr, jnp.max(jnp.where(arrival > 0, w, -jnp.inf)), 0.0),
            "update_sq_norm": tree_sq_dist(state.global_params, new_global),
        }
        return CohortState(new_global, new_client_params, new_client_base,
                           new_client_version, new_version), metrics

    return step


# ---------------------------------------------------------------------------
# distributed-client (sequential buffer, streaming weighted accumulator)
# ---------------------------------------------------------------------------


class DistFLState(NamedTuple):
    global_params: Any  # x^t, FSDP x TP sharded
    accum: Any  # running sum v_i * Delta_i (params-shaped, f32)
    v_buf: jnp.ndarray  # (buffer_size,) per-slot scalar weights v_i
    count: jnp.ndarray  # updates buffered so far (int32)
    version: jnp.ndarray  # t (int32)
    update_norm_ring: jnp.ndarray  # (max_staleness,) ||u_s||^2 scalars


def init_dist_state(params: Any, fl: FLConfig) -> DistFLState:
    acc_dtype = jnp.dtype(fl.accum_dtype)
    return DistFLState(
        global_params=params,
        accum=jax.tree.map(lambda x: jnp.zeros(x.shape, acc_dtype), params),
        v_buf=jnp.zeros((fl.buffer_size,), jnp.float32),
        count=jnp.zeros((), jnp.int32),
        version=jnp.zeros((), jnp.int32),
        update_norm_ring=jnp.zeros((fl.max_staleness,), jnp.float32),
    )


def make_dist_step(loss_fn: Callable, fl: FLConfig) -> Callable:
    """One sequential buffer contribution + conditional server apply.

    A thin state machine over the shared streaming round body
    (``core/round_body.py::make_streaming_round_body``) — ALL weighting
    and eq. 5 arithmetic lives there; this wrapper only manages the
    buffer fill (v-slot write, count), the ``lax.cond`` apply/hold, and
    version bookkeeping.

    Batch layout (single distributed client):
      batch["local"] : leaves (M, b, ...)
      batch["probe"] : leaves (bp, ...)
      batch["tau"]   : scalar int32 — simulated staleness in rounds
      batch["data_size"]: scalar f32

    Metrics: ``buffered`` is the PRE-apply fill count (so the round that
    triggers the apply reports K, not 0) and ``applied`` is a {0,1} flag
    for whether this step flushed the buffer; ``local`` holds the loss's
    metrics of the M local steps, each stacked on a leading (M,) axis.
    """
    streaming = make_streaming_round_body(loss_fn, fl)

    def step(state: DistFLState, batch: Dict[str, Any]):
        accum, v, info = streaming.contribute(
            state.global_params, state.accum, state.update_norm_ring,
            batch["local"], batch["probe"],
            batch["data_size"].astype(jnp.float32), batch["tau"])
        v_buf = state.v_buf.at[state.count].set(v)
        count = state.count + 1

        def apply_fn(st):
            accum_, v_buf_, count_ = st
            new_params, ring = streaming.apply(
                state.global_params, accum_, v_buf_, count_,
                state.update_norm_ring)
            return (new_params, jax.tree.map(jnp.zeros_like, accum_),
                    jnp.zeros_like(v_buf_), jnp.zeros((), jnp.int32),
                    state.version + 1, ring)

        def hold_fn(st):
            accum_, v_buf_, count_ = st
            return (state.global_params, accum_, v_buf_, count_,
                    state.version, state.update_norm_ring)

        applied = count >= fl.buffer_size
        new_params, accum, v_buf, count, version, ring = jax.lax.cond(
            applied, apply_fn, hold_fn, (accum, v_buf, count))

        metrics = {"fresh_loss": info["fresh_loss"], "v_weight": v,
                   "buffered": state.count + 1,
                   "applied": applied.astype(jnp.int32),
                   "local": info["local"]}
        return DistFLState(new_params, accum, v_buf, count, version,
                           ring), metrics

    return step


def fold_upload(step: Callable, state: DistFLState, batch: Dict[str, Any], *,
                tracer: Optional[Tracer] = None,
                registry: Optional[MetricsRegistry] = None):
    """Fold one client upload through the jitted ``make_dist_step``.

    The upload loop's one step, on the host: stage the upload's batch on
    the device (span ``stage``, arg ``bytes``), dispatch the step (span
    ``contribute``), then fetch its weight and fresh loss as the server's
    acknowledgement (span ``host_sync``, which waits for the device). The
    fetch also carries the loss's own metrics of the M local steps, each
    recorded as an arg of that span under its name. Counters
    ``dist_uploads_total`` and ``dist_applies_total``. Returns the new
    state and the acknowledgement (host values).
    """
    tr = tracer if tracer is not None else default_tracer()
    reg = registry if registry is not None else default_registry()
    with tr.span(SPAN_STAGE) as sp:
        staged = jax.device_put(batch)
        sp.set(bytes=int(sum(np.asarray(a).nbytes
                             for a in jax.tree.leaves(batch))))
    with tr.span(SPAN_CONTRIBUTE):
        state, mets = step(state, staged)
    reg.counter("dist_uploads_total").inc()
    with tr.span(SPAN_HOST_SYNC, what="ack") as sp:
        ack = jax.device_get(mets)
        sp.set(**{name: np.asarray(value).tolist()
                  for name, value in ack["local"].items()})
    if ack["applied"]:
        reg.counter("dist_applies_total").inc()
    return state, ack


def staleness_stream(behavior: Any, k: int, n: int):
    """Client ids and staleness tau of the first ``n`` uploads that buffered
    async FL with buffer K sees over a client population.

    ``behavior`` is a ``sim.scenarios.ClientBehavior``: each client trains
    from the version it pulled, for the durations it draws; an upload's
    tau is the server's version when it lands minus that; every K landed
    uploads advance the version; the uploading client then pulls the
    current version and trains again. Lost uploads are skipped. What the
    distributed-client mapping, which trains every upload from the
    current model, takes as each upload's simulated tau.
    """
    heap = []
    for c in range(behavior.num_clients):
        start = behavior.next_start(c, 0.0)
        heapq.heappush(heap, (start + behavior.duration(c, start), c))
    base = [0] * behavior.num_clients
    version, buffered = 0, 0
    cids, taus = [], []
    while len(taus) < n:
        t, c = heapq.heappop(heap)
        _, lost = behavior.next_upload(c)
        if not lost:
            cids.append(c)
            taus.append(version - base[c])
            buffered += 1
            if buffered == k:
                version, buffered = version + 1, 0
        base[c] = version
        start = behavior.next_start(c, t)
        heapq.heappush(heap, (start + behavior.duration(c, start), c))
    return np.asarray(cids, np.int64), np.asarray(taus, np.int32)
