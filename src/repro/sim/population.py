"""Device-resident population engine: million-client scenarios (§10).

``run_vectorized`` keeps every client's state on the host — one NumPy
PCG64 generator pair per client, a Python heapq event loop, and (on a
process-spanning mesh) every process replaying the same host event walk.
That caps N at thousands: building 1e6 generators costs seconds and
gigabytes before the first round runs, and each K-upload window costs
O(K) heap pops plus per-event RNG calls on the host.

This module moves the whole scenario state machine onto the device:

* **Counter-based RNG.** Every stochastic draw of client ``cid`` is a
  pure function of ``(seed, stream, cid, k)`` via
  ``jax.random.fold_in(fold_in(stream_key, cid), k)`` — no mutable
  generator state, so draws are random-access and the *order* the engine
  consumes them in is irrelevant. The per-client state that remains is
  just the draw counters, packed as plain ``(N,)`` int32 arrays
  (retiring the ``utils/rngstate.py`` PCG64 pack on this path).

* **Vmapped behavior kernel.** Availability gating, duration draws,
  Bernoulli/trace dropouts and straggler-burst multipliers evaluate as
  one vmapped kernel over the ``(N,)``-leading ``PopState`` array pytree
  (FLGo-style state machine — start/complete/drop/reschedule — preserved
  as arrays), sharded over the mesh's ``data`` axis
  (``sharding/specs.client_state_pspec``). On a process-spanning mesh
  the state init runs under ``out_shardings``, so each process only
  materializes its addressable shard — no host event walk to replay.

* **Device top-k window selection.** A window is the K lexicographically
  smallest ``(t, cid)`` *accepted* uploads. Each client's next accepted
  upload time is computed by a vmapped drop-chain walk (``_peek``), then
  ``jax.lax.top_k`` picks the window (XLA top-k is stable, so time ties
  resolve to the lower cid exactly like the host heap). A re-entry check
  (can a selected client's *next* accept land back inside this window?)
  guards the top-k fast path; when it trips — only plausible at small
  N/K ratios — a ``lax.while_loop`` replica of the host event loop runs
  the window exactly. Either way the window feeds straight into the
  shared ``core/round_body.py`` ring round, and a whole
  ``rounds_per_launch`` chunk of windows + training rounds compiles to
  ONE fused ``lax.scan`` — **zero host syncs per window**, O(1) syncs
  per eval/run regardless of K (the engine's host walk costs O(K) heap
  pops + RNG calls per window).

Event-for-event parity with the host walk is the contract, pinned at
small N by tests/test_population.py: ``CounterBehavior`` /
``CounterDataset`` are host twins that consume the SAME counter streams
through the same jitted scalar kernels, so ``run_vectorized`` driven by
them reproduces this engine's event sequence (and round log) exactly.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FLConfig
from repro.core.round_body import make_ring_round
from repro.core.version_store import ring_state_to_host
from repro.data.synthetic import ClientDataset
from repro.launch.multihost import (
    fetch_replicated,
    mesh_spans_processes,
    put_replicated,
    put_with_sharding,
)
from repro.obs.metrics import MetricsRegistry, default_registry
from repro.obs.trace import (
    SPAN_APPLY,
    SPAN_CHECKPOINT,
    SPAN_HOST_SYNC,
    SPAN_PREPARE,
    Tracer,
    default_tracer,
)
from repro.sharding.specs import DATA_AXIS, client_state_pspec, mesh_axis_size
from repro.sim.base import (
    SimResult,
    history_from_arrays,
    history_to_arrays,
    host_nbytes,
    record_eval,
    round_log_from_arrays,
    round_log_rows,
    round_log_to_arrays,
)
from repro.sim.engine import init_version_ring
from repro.sim.pool import DevicePool
from repro.sim.scenarios import ClientBehavior, Scenario

P = jax.sharding.PartitionSpec

# stream tags: every draw is fold_in(fold_in(PRNGKey(seed) ^ tag, cid), k)
_TAG_DUR = 101     # lognormal duration draws      (mirrors SeedSequence 101)
_TAG_DROP = 202    # Bernoulli dropout draws       (mirrors SeedSequence 202)
_TAG_TRAIN = 303   # local-step batch index draws
_TAG_PROBE = 304   # eq.-4 probe batch index draws
_TAG_TIER = 401    # static: compute tier assignment
_TAG_SPREAD = 402  # static: log-uniform in-tier spread
_TAG_COMM = 403    # static: comm tier assignment
_TAG_PHASE = 404   # static: diurnal phase offset


@functools.lru_cache(maxsize=None)
def _stream_key(seed: int, tag: int):
    return jax.random.fold_in(jax.random.PRNGKey(seed), tag)


class PopStatics(NamedTuple):
    """Immutable per-client population statics, ``(N,)`` f32 each."""

    speed: Any  # multiplicative slowness (sorted, like ClientBehavior)
    comm: Any   # additive upload latency
    phase: Any  # diurnal phase offset


class PopState(NamedTuple):
    """The whole mutable scenario state machine, as arrays.

    ``(N,)`` leading per-client fields plus three scalars — this IS the
    checkpoint payload (plain arrays; no PCG64 state to pack):

    * ``t_next``  f32: completion time of the client's pending attempt
    * ``k_next``  i32: upload-attempt index of that pending attempt
                  (doubles as the duration-draw counter: attempt k's
                  duration is draw k of the ``_TAG_DUR`` stream, and the
                  drop verdict is draw k of ``_TAG_DROP``)
    * ``batch_k`` i32: train-batch draw counter (advances ``local_steps``
                  per accepted upload; probe draws live on their own
                  stream indexed by the accept count ``batch_k // M``)
    * ``base_version`` i32: version of the model the client trains from
    """

    t_next: Any
    k_next: Any
    batch_k: Any
    base_version: Any
    version: Any     # () i32 server version
    now: Any         # () f32 sim time of the last aggregation
    num_events: Any  # () i32 uploads processed (incl. dropped)


class _BehaviorFns(NamedTuple):
    """Pure counter-based scalar draw kernels (vmappable)."""

    gate: Callable      # (phase, t) -> earliest start >= t
    duration: Callable  # (cid, k, t, speed, comm) -> f32 train+upload time
    dropped: Callable   # (cid, k) -> bool upload-k lost
    has_drops: bool


@functools.lru_cache(maxsize=64)
def make_behavior_fns(sc: Scenario, seed: int) -> _BehaviorFns:
    """The scenario's stochastic pieces as pure functions of counters.

    Same semantics as ``ClientBehavior`` (diurnal gate, lognormal
    durations with burst multipliers, trace-then-Bernoulli drops), with
    the PCG64 streams replaced by threefry counter draws.
    """
    log_mean = float(math.log(sc.base_mean))
    k_dur = _stream_key(seed, _TAG_DUR)
    k_drop = _stream_key(seed, _TAG_DROP)
    period = np.float32(sc.diurnal_period)
    on = np.float32(sc.diurnal_duty * sc.diurnal_period)
    has_drops = sc.dropout_p > 0.0 or bool(sc.dropout_trace)
    trace_c = jnp.asarray([c for c, _ in sc.dropout_trace], jnp.int32)
    trace_k = jnp.asarray([k for _, k in sc.dropout_trace], jnp.int32)

    def gate(phase, t):
        if not sc.diurnal:
            return t
        local = jnp.mod(t - phase, period)
        return jnp.where(local < on, t, t + (period - local))

    def _burst_mult(cid, t):
        if sc.burst_every <= 0.0:
            return jnp.float32(1.0)
        be = np.float32(sc.burst_every)
        j = jnp.floor(t / be).astype(jnp.int32)  # burst index
        in_burst = jnp.mod(t, be) < np.float32(sc.burst_len)
        stride = max(1, int(round(1.0 / max(sc.burst_frac, 1e-9))))
        hit = jnp.mod(cid + j, stride) == 0
        return jnp.where(in_burst & hit, np.float32(sc.burst_factor),
                         jnp.float32(1.0))

    def duration(cid, k, t, speed, comm):
        key = jax.random.fold_in(jax.random.fold_in(k_dur, cid), k)
        z = jax.random.normal(key, (), jnp.float32)
        draw = jnp.exp(np.float32(log_mean) + np.float32(sc.sigma) * z)
        return (speed * draw * _burst_mult(cid, t) + comm).astype(jnp.float32)

    def dropped(cid, k):
        if not has_drops:
            return jnp.bool_(False)
        hit = jnp.bool_(False)
        if sc.dropout_trace:
            hit = jnp.any((trace_c == cid) & (trace_k == k))
        if sc.dropout_p > 0.0:
            key = jax.random.fold_in(jax.random.fold_in(k_drop, cid), k)
            u = jax.random.uniform(key, (), jnp.float32)
            hit = hit | (u < np.float32(sc.dropout_p))
        return hit

    return _BehaviorFns(gate=gate, duration=duration, dropped=dropped,
                        has_drops=has_drops)


def _n_pspec(mesh, n: int):
    """Spec for ``(N,)`` client arrays: ``P(data)`` when it divides."""
    if mesh is None:
        return P()
    d = mesh_axis_size(mesh, DATA_AXIS)
    return client_state_pspec() if d > 1 and n % d == 0 else P()


def _shardings(mesh, pspec_tree):
    return jax.tree.map(
        lambda p: jax.sharding.NamedSharding(mesh, p), pspec_tree,
        is_leaf=lambda x: isinstance(x, P))


@functools.lru_cache(maxsize=16)
def _make_statics_fn(sc: Scenario, n: int, seed: int,
                     mesh: Optional[Any]) -> Callable:
    """Jitted statics init; per-client draws are counter-based, so with a
    mesh the ``out_shardings`` partitioning makes every process compute
    only its addressable ``data``-axis shard (no replayed host init)."""
    tiers = jnp.asarray(sc.compute_tiers, jnp.float32)
    comms = jnp.asarray(sc.comm_tiers, jnp.float32)
    log_slow = np.float32(math.log(max(sc.max_slowdown, 1.0 + 1e-9)))
    k_tier = _stream_key(seed, _TAG_TIER)
    k_spread = _stream_key(seed, _TAG_SPREAD)
    k_comm = _stream_key(seed, _TAG_COMM)
    k_phase = _stream_key(seed, _TAG_PHASE)

    def init() -> PopStatics:
        def per_client(cid):
            tier = jax.random.randint(jax.random.fold_in(k_tier, cid), (),
                                      0, tiers.shape[0])
            spread = jnp.exp(jax.random.uniform(
                jax.random.fold_in(k_spread, cid), (), jnp.float32,
                0.0, log_slow))
            comm = comms[jax.random.randint(jax.random.fold_in(k_comm, cid),
                                            (), 0, comms.shape[0])]
            phase = jax.random.uniform(
                jax.random.fold_in(k_phase, cid), (), jnp.float32,
                0.0, np.float32(sc.diurnal_period))
            return tiers[tier] * spread, comm, phase
        speed, comm, phase = jax.vmap(per_client)(
            jnp.arange(n, dtype=jnp.int32))
        # sorted like ClientBehavior: speed rank decorrelated from cid
        return PopStatics(speed=jnp.sort(speed), comm=comm, phase=phase)

    if mesh is None:
        return jax.jit(init)
    pspec = _n_pspec(mesh, n)
    out = _shardings(mesh, PopStatics(speed=pspec, comm=pspec, phase=pspec))
    return jax.jit(init, out_shardings=out)


@functools.lru_cache(maxsize=16)
def _make_init_state_fn(sc: Scenario, n: int, seed: int,
                        mesh: Optional[Any]) -> Callable:
    """Jitted initial PopState: every client starts training at t=0
    (availability-gated) from version 0, duration draw 0."""
    fns = make_behavior_fns(sc, seed)

    def init(statics: PopStatics) -> PopState:
        cids = jnp.arange(n, dtype=jnp.int32)
        start = jax.vmap(fns.gate)(statics.phase, jnp.zeros(n, jnp.float32))
        dur = jax.vmap(fns.duration)(cids, jnp.zeros(n, jnp.int32), start,
                                     statics.speed, statics.comm)
        zi = jnp.zeros(n, jnp.int32)
        return PopState(t_next=start + dur, k_next=zi, batch_k=zi,
                        base_version=zi, version=jnp.int32(0),
                        now=jnp.float32(0.0), num_events=jnp.int32(0))

    if mesh is None:
        return jax.jit(init)
    pspec = _n_pspec(mesh, n)
    out = PopState(t_next=pspec, k_next=pspec, batch_k=pspec,
                   base_version=pspec, version=P(), now=P(), num_events=P())
    return jax.jit(init, out_shardings=_shardings(mesh, out))


@functools.lru_cache(maxsize=32)
def make_window_step(sc: Scenario, fl: FLConfig, n: int, seed: int,
                     mesh: Optional[Any] = None) -> Callable:
    """One K-upload window as a pure device function.

    ``window_step(statics, state) -> (new_state, win)`` where ``win``
    holds the (K,) window arrays in host-heap order — ``cids``, ``taus``
    f32, ``slots`` (ring rows), ``bk0`` (pre-advance train-batch
    counters) and ``t`` (upload times). Semantics match
    ``run_vectorized``'s ``collect_window`` + trigger bookkeeping
    event-for-event (see module docstring for the fast/exact split).
    """
    fns = make_behavior_fns(sc, seed)
    k = fl.buffer_size
    ring_depth = fl.max_staleness + 1
    max_stal = fl.max_staleness
    m = fl.local_steps
    cids_all = jnp.arange(n, dtype=jnp.int32)
    force_exact = k > n

    def _peek(cid, t, ki, speed, comm, phase):
        """Follow the pending drop chain to the next ACCEPTED upload:
        (t_accept, k_accept, drops consumed on the way). Pure — counter
        draws are random-access, so peeking never perturbs state."""
        if not fns.has_drops:
            return t, ki, jnp.int32(0)

        def cond(c):
            return fns.dropped(cid, c[1])

        def body(c):
            t_, k_, nd = c
            s = fns.gate(phase, t_)
            return (s + fns.duration(cid, k_ + 1, s, speed, comm),
                    k_ + 1, nd + 1)

        return jax.lax.while_loop(cond, body, (t, ki, jnp.int32(0)))

    def _resched(cid, t, k_new, speed, comm, phase):
        s = fns.gate(phase, t)
        return s + fns.duration(cid, k_new, s, speed, comm)

    def _exact(st: PopState, statics: PopStatics):
        """The host event loop, verbatim, as a lax.while_loop: pop the
        lexicographically smallest (t, cid) pending event until K
        uploads are accepted. O(K + drops) iterations with an O(N)
        argmin each — the correctness fallback for re-entry windows."""
        v = st.version
        zf = jnp.zeros(k, jnp.float32)
        zi = jnp.zeros(k, jnp.int32)

        def cond(c):
            return c[5] < k

        def body(c):
            (t_next, k_next, batch_k, bv, nev, count,
             w_c, w_tau, w_slot, w_bk, w_t) = c
            i = jnp.argmin(t_next).astype(jnp.int32)  # first min: lowest cid
            t = t_next[i]
            ki = k_next[i]
            drop = fns.dropped(i, ki)
            t_new = _resched(i, t, ki + 1, statics.speed[i], statics.comm[i],
                             statics.phase[i])
            t_next = t_next.at[i].set(t_new)
            k_next = k_next.at[i].set(ki + 1)
            bvi = bv[i]
            bvi = jnp.where(bvi < v - max_stal, v, bvi)  # ring resync
            acc = ~drop
            idx = count  # the window slot this accept (if any) fills
            w_c = w_c.at[idx].set(jnp.where(acc, i, w_c[idx]))
            w_tau = w_tau.at[idx].set(
                jnp.where(acc, (v - bvi).astype(jnp.float32), w_tau[idx]))
            w_slot = w_slot.at[idx].set(
                jnp.where(acc, jnp.mod(bvi, ring_depth), w_slot[idx]))
            w_bk = w_bk.at[idx].set(jnp.where(acc, batch_k[i], w_bk[idx]))
            w_t = w_t.at[idx].set(jnp.where(acc, t, w_t[idx]))
            batch_k = batch_k.at[i].add(jnp.where(acc, m, 0))
            bv = bv.at[i].set(v)  # drop AND non-trigger accept re-pull v
            return (t_next, k_next, batch_k, bv, nev + 1,
                    count + acc.astype(jnp.int32),
                    w_c, w_tau, w_slot, w_bk, w_t)

        (t_next, k_next, batch_k, bv, nev, _,
         w_c, w_tau, w_slot, w_bk, w_t) = jax.lax.while_loop(
            cond, body,
            (st.t_next, st.k_next, st.batch_k, st.base_version,
             st.num_events, jnp.int32(0), zi, zf, zi, zi, zf))
        trig = w_c[k - 1]
        bv = bv.at[trig].set(v + 1)  # the K-th upload pulls the NEW version
        new_st = PopState(t_next=t_next, k_next=k_next, batch_k=batch_k,
                          base_version=bv, version=v + 1, now=w_t[k - 1],
                          num_events=nev)
        return new_st, {"cids": w_c, "taus": w_tau, "slots": w_slot,
                        "bk0": w_bk, "t": w_t}

    @jax.named_scope("window_step")
    def window_step(statics: PopStatics, st: PopState):
        if force_exact:
            return _exact(st, statics)
        v = st.version
        t_acc, k_acc, nd_pre = jax.vmap(_peek)(
            cids_all, st.t_next, st.k_next, statics.speed, statics.comm,
            statics.phase)
        # K smallest accepted times; XLA top-k is stable, so equal times
        # select ascending cid — the host heap's (t, cid) order
        neg, sel = jax.lax.top_k(-t_acc, k)
        # the barrier keeps TopK a custom call: fusing the t_w/trig
        # scalar slices below into it makes XLA CPU re-lower the whole
        # thing as a full O(N log N) sort per window (~30 ms at N=1e5)
        t_sel, sel = jax.lax.optimization_barrier((-neg, sel))
        t_w = t_sel[k - 1]
        trig = sel[k - 1]
        # staleness bookkeeping, host order: an in-window drop re-pulled
        # v first; then the ring resync check
        bv = st.base_version[sel]
        bv = jnp.where(nd_pre[sel] > 0, v, bv)
        bv = jnp.where(bv < v - max_stal, v, bv)
        taus = (v - bv).astype(jnp.float32)
        slots = jnp.mod(bv, ring_depth).astype(jnp.int32)
        bk0 = st.batch_k[sel]
        sp_s = statics.speed[sel]
        cm_s = statics.comm[sel]
        ph_s = statics.phase[sel]
        # post-accept reschedule, then the re-entry check: if any selected
        # client's NEXT accepted upload lands lexicographically before the
        # trigger event, the host walk would have put it IN this window —
        # the top-k of first-accepts is wrong, take the exact path
        t_re = jax.vmap(_resched)(sel, t_sel, k_acc[sel] + 1, sp_s, cm_s,
                                  ph_s)
        t_acc2, _, _ = jax.vmap(_peek)(sel, t_re, k_acc[sel] + 1, sp_s,
                                       cm_s, ph_s)
        reenter = jnp.any((t_acc2 < t_w) | ((t_acc2 == t_w) & (sel < trig)))

        def fast(_):
            t_next = st.t_next.at[sel].set(t_re)
            k_next = st.k_next.at[sel].set(k_acc[sel] + 1)
            batch_k = st.batch_k.at[sel].add(m)
            base_version = st.base_version.at[sel].set(v)
            nev = st.num_events + k + jnp.sum(nd_pre[sel])
            if fns.has_drops:
                # consume every remaining drop with event order <= the
                # trigger (the host walk popped those this window)
                def consume(cid, t, ki, speed, comm, phase):
                    def cond(c):
                        t_, k_, nd = c
                        before = (t_ < t_w) | ((t_ == t_w) & (cid < trig))
                        return before & fns.dropped(cid, k_)

                    def body(c):
                        t_, k_, nd = c
                        s = fns.gate(phase, t_)
                        return (s + fns.duration(cid, k_ + 1, s, speed,
                                                 comm), k_ + 1, nd + 1)

                    return jax.lax.while_loop(cond, body,
                                              (t, ki, jnp.int32(0)))

                t_next, k_next, nd_post = jax.vmap(consume)(
                    cids_all, t_next, k_next, statics.speed, statics.comm,
                    statics.phase)
                base_version = jnp.where(nd_post > 0, v, base_version)
                nev = nev + jnp.sum(nd_post)
            base_version = base_version.at[trig].set(v + 1)
            new_st = PopState(t_next=t_next, k_next=k_next, batch_k=batch_k,
                              base_version=base_version, version=v + 1,
                              now=t_w, num_events=nev)
            return new_st, {"cids": sel, "taus": taus, "slots": slots,
                            "bk0": bk0, "t": t_sel}

        return jax.lax.cond(reenter, lambda _: _exact(st, statics), fast,
                            None)

    return window_step


# ---------------------------------------------------------------------------
# fused chunk: S x (window kernel -> pool gather -> ring round)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=32)
def _make_pop_chunk(loss_fn: Callable, fl: FLConfig, sc: Scenario, n: int,
                    s: int, seed: int, mesh: Optional[Any]) -> Callable:
    """Compile S whole server rounds — window selection, batch gather,
    K local trainings, eq. 3/4/5 — into ONE jitted ``lax.scan``. Unlike
    the host-walk engine there is no per-window host work at all: the
    event machine advances on device inside the scan carry."""
    window_step = make_window_step(sc, fl, n, seed, mesh)
    ring_round = make_ring_round(loss_fn, fl, mesh=mesh)
    b = fl.batch_size
    m = fl.local_steps
    ring_depth = fl.max_staleness + 1
    k_train = _stream_key(seed, _TAG_TRAIN)
    k_probe = _stream_key(seed, _TAG_PROBE)
    rep = (jax.sharding.NamedSharding(mesh, P())
           if mesh is not None else None)

    def draw_indices(cid, bk0, offset, size):
        """(M, B) train + (B,) probe pool rows for one accepted upload.

        Train draws are counters ``bk0 .. bk0+M-1`` of the train stream;
        the probe is draw ``bk0 // M`` (== the accept count) of its own
        stream, so the interleaving order host twins consume draws in
        cannot shift either stream."""
        kc_t = jax.random.fold_in(k_train, cid)

        def one_train(j):
            return offset + jax.random.randint(
                jax.random.fold_in(kc_t, bk0 + j), (b,), 0, size)

        idx_t = jax.vmap(one_train)(jnp.arange(m, dtype=jnp.int32))
        kp = jax.random.fold_in(jax.random.fold_in(k_probe, cid),
                                bk0 // m)
        idx_p = offset + jax.random.randint(kp, (b,), 0, size)
        return idx_t, idx_p

    @jax.jit
    def chunk(params, ring, state, statics, pool_x, pool_y, offsets, sizes):
        def one_round(carry, _):
            params, ring, st = carry
            st, win = window_step(statics, st)
            cids = win["cids"]
            idx_t, idx_p = jax.vmap(draw_indices)(
                cids, win["bk0"], offsets[cids], sizes[cids])
            batch = (pool_x[idx_t], pool_y[idx_t])
            probe = (pool_x[idx_p], pool_y[idx_p])
            dsz = sizes[cids].astype(jnp.float32)
            new_slot = jnp.mod(st.version, ring_depth).astype(jnp.int32)
            params, ring, info = ring_round(params, ring, win["slots"],
                                            batch, probe, dsz, win["taus"],
                                            new_slot)
            out = {**info, "clients": cids, "tau": win["taus"]}
            if rep is not None:
                # multi-host contract (DESIGN.md §7): round-log outputs
                # are fully replicated so every process reads them from
                # its own addressable shards
                out = jax.lax.with_sharding_constraint(out, rep)
            return (params, ring, st), out

        (params, ring, state), outs = jax.lax.scan(
            one_round, (params, ring, state), None, length=s)
        return params, ring, state, outs

    return chunk


@functools.lru_cache(maxsize=32)
def _make_collect_scan(sc: Scenario, fl: FLConfig, n: int, num_windows: int,
                       seed: int, mesh: Optional[Any]) -> Callable:
    """Events-only: scan the window kernel alone (no training). The
    device counterpart of ``host_walk_windows`` for parity tests and the
    population-scale benchmark."""
    window_step = make_window_step(sc, fl, n, seed, mesh)

    @jax.jit
    def run(statics, state):
        def body(st, _):
            st, win = window_step(statics, st)
            return st, win

        state, wins = jax.lax.scan(body, state, None, length=num_windows)
        return state, wins

    return run


def init_population(scenario: Scenario, n: int, fl: FLConfig, seed: int = 0,
                    mesh: Optional[Any] = None
                    ) -> Tuple[PopStatics, PopState]:
    """Fresh device-resident statics + state for an N-client population."""
    statics = _make_statics_fn(scenario, n, seed, mesh)()
    state = _make_init_state_fn(scenario, n, seed, mesh)(statics)
    return statics, state


def collect_windows(scenario: Scenario, n: int, fl: FLConfig,
                    num_windows: int, seed: int = 0,
                    mesh: Optional[Any] = None,
                    statics: Optional[PopStatics] = None,
                    state: Optional[PopState] = None) -> Dict[str, Any]:
    """Run ``num_windows`` windows of the device event machine (no
    training): host-order (T, K) arrays + the final state. One dispatch,
    one sync — the O(1)-host-syncs-per-window contract in its purest
    form."""
    if statics is None or state is None:
        statics, state = init_population(scenario, n, fl, seed, mesh)
    state, wins = _make_collect_scan(scenario, fl, n, num_windows, seed,
                                     mesh)(statics, state)
    host = fetch_replicated((state, wins)) if any(
        isinstance(l, jax.Array) and not l.is_fully_addressable
        for l in jax.tree.leaves((state, wins))) \
        else jax.device_get((state, wins))
    state_h, wins_h = host
    return {"clients": np.asarray(wins_h["cids"], np.int64),
            "tau": np.asarray(wins_h["taus"], np.int64),
            "slots": np.asarray(wins_h["slots"], np.int64),
            "t": np.asarray(wins_h["t"], np.float64),
            "num_events": int(state_h.num_events),
            "now": float(state_h.now),
            "state": state}


def host_walk_windows(behavior: ClientBehavior, fl: FLConfig,
                      num_windows: int) -> Dict[str, Any]:
    """The engine's host event walk, events only (no data plane): the
    reference the device path is pinned against, and the baseline the
    population-scale benchmark measures speedup over."""
    import heapq

    n = behavior.num_clients
    k = fl.buffer_size
    ring_depth = fl.max_staleness + 1
    base_version = np.zeros(n, np.int64)
    version = 0
    num_events = 0
    events = []
    for cid in range(n):
        start = behavior.next_start(cid, 0.0)
        events.append((start + behavior.duration(cid, start), cid))
    heapq.heapify(events)

    def reschedule(cid, t):
        start = behavior.next_start(cid, t)
        heapq.heappush(events, (start + behavior.duration(cid, start), cid))

    out_c = np.zeros((num_windows, k), np.int64)
    out_tau = np.zeros((num_windows, k), np.int64)
    out_slot = np.zeros((num_windows, k), np.int64)
    out_t = np.zeros((num_windows, k), np.float64)
    now = 0.0
    for w in range(num_windows):
        filled = 0
        while filled < k:
            t, cid = heapq.heappop(events)
            num_events += 1
            _, lost = behavior.next_upload(cid)
            if lost:
                base_version[cid] = version
                reschedule(cid, t)
                continue
            bv = int(base_version[cid])
            if bv < version - fl.max_staleness:
                bv = version
                base_version[cid] = version
            out_c[w, filled] = cid
            out_tau[w, filled] = version - bv
            out_slot[w, filled] = bv % ring_depth
            out_t[w, filled] = t
            filled += 1
            if filled < k:
                base_version[cid] = version
                reschedule(cid, t)
        version += 1
        now = out_t[w, k - 1]
        trig = int(out_c[w, k - 1])
        base_version[trig] = version
        reschedule(trig, now)
    return {"clients": out_c, "tau": out_tau, "slots": out_slot, "t": out_t,
            "num_events": num_events, "now": float(now)}


# ---------------------------------------------------------------------------
# host twins: the SAME counter streams, consumed by the host engine
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=64)
def _scalar_fns(sc: Scenario, n: int, seed: int):
    """Jitted scalar kernels over the cached device statics — what
    ``CounterBehavior`` calls per event so host and device runs share
    every draw bit-for-bit."""
    fns = make_behavior_fns(sc, seed)
    statics = _make_statics_fn(sc, n, seed, None)()

    @jax.jit
    def dur(cid, k, t):
        return fns.duration(cid, k, t, statics.speed[cid], statics.comm[cid])

    @jax.jit
    def drop(cid, k):
        return fns.dropped(cid, k)

    @jax.jit
    def gate(cid, t):
        return fns.gate(statics.phase[cid], t)

    return statics, dur, drop, gate


class CounterBehavior(ClientBehavior):
    """Host ``ClientBehavior`` drawing from the population engine's
    counter streams (threefry ``fold_in`` by ``(cid, k)``) instead of
    per-client PCG64 generators.

    Drives ``run_vectorized``'s host event walk with the exact draws the
    device kernel uses — the bridge the small-N parity tests cross. Its
    checkpoint state is counters only (``get_state`` packs no PCG64
    rows): with this behavior the vectorized path no longer needs
    ``utils/rngstate.py``.
    """

    def __init__(self, scenario: Scenario, num_clients: int, seed: int = 0):
        super().__init__(scenario, num_clients, seed)
        statics, dur, drop, gate = _scalar_fns(scenario, int(num_clients),
                                               int(seed))
        # replace the PCG64-drawn statics with the device population's
        self.speed = np.asarray(statics.speed, np.float64)
        self.comm = np.asarray(statics.comm, np.float64)
        self.phase = np.asarray(statics.phase, np.float64)
        self._dur_fn, self._drop_fn, self._gate_fn = dur, drop, gate
        self._dur_rng = self._drop_rng = None  # PCG64 streams retired

    def next_start(self, cid: int, t: float) -> float:
        if not self.scenario.diurnal:
            return t
        # f32 gate, like the device: the host's running time is the f64
        # image of the same f32 value, so casting loses nothing
        return float(self._gate_fn(np.int32(cid), np.float32(t)))

    def duration(self, cid: int, t: float = 0.0) -> float:
        if self._replay_dur is not None:
            return super().duration(cid, t)
        k = len(self._durations[cid])
        dur = float(self._dur_fn(np.int32(cid), np.int32(k), np.float32(t)))
        self._durations[cid].append(dur)
        return dur

    def next_upload(self, cid: int) -> Tuple[int, bool]:
        k = int(self._upload_idx[cid])
        self._upload_idx[cid] += 1
        if self._replay_drops is not None:
            hit = (cid, k) in self._replay_drops
        else:
            hit = bool(self._drop_fn(np.int32(cid), np.int32(k)))
        if hit:
            self._drops.append((cid, k))
        return k, hit

    # -- checkpointing: counters ARE the whole stream state -------------
    def get_state(self) -> Dict[str, np.ndarray]:
        return {"upload_idx": self._upload_idx.copy(),
                "draw_counts": np.asarray([len(d) for d in self._durations],
                                          np.int64)}

    def set_state(self, state: Dict[str, np.ndarray]) -> None:
        upload_idx = np.asarray(state["upload_idx"], np.int64)
        if len(upload_idx) != self.num_clients:
            raise ValueError(f"state has {len(upload_idx)} clients, "
                             f"behavior has {self.num_clients}")
        self._upload_idx = upload_idx.copy()
        counts = np.asarray(state["draw_counts"], np.int64)
        self._durations = [[float("nan")] * int(c) for c in counts]
        self._drops = []


@functools.lru_cache(maxsize=None)
def _host_index_fns(seed: int, batch_size: int):
    k_train = _stream_key(seed, _TAG_TRAIN)
    k_probe = _stream_key(seed, _TAG_PROBE)

    @jax.jit
    def train_idx(cid, k, size):
        key = jax.random.fold_in(jax.random.fold_in(k_train, cid), k)
        return jax.random.randint(key, (batch_size,), 0, size)

    @jax.jit
    def probe_idx(cid, k, size):
        key = jax.random.fold_in(jax.random.fold_in(k_probe, cid), k)
        return jax.random.randint(key, (batch_size,), 0, size)

    return train_idx, probe_idx


@dataclasses.dataclass
class CounterDataset(ClientDataset):
    """Host twin of the device pool's batch sampling.

    Train batches (``batches_rows``/``batches``) and probe batches
    (``batch_rows``/``batch``) consume separate counter streams —
    order-independent, so the engine's probes-after-all-train-draws
    convention and the device's per-accept
    draws index identically even when a client appears twice in one
    window. Checkpoint state is the two counters (no PCG64).
    """

    cid: int = 0
    stream_seed: int = 0

    def __post_init__(self):
        super().__post_init__()
        self._k_train = 0
        self._k_probe = 0

    def batch_indices(self, batch_size: int) -> np.ndarray:
        raise NotImplementedError(
            "CounterDataset draws are stream-specific: use batch_rows() "
            "(probe stream) or batches_rows() (train stream)")

    def batches_rows(self, batch_size: int, count: int) -> np.ndarray:
        fn, _ = _host_index_fns(self.stream_seed, batch_size)
        idx = np.stack([
            np.asarray(fn(np.int32(self.cid), np.int32(self._k_train + j),
                          np.int32(self.size))) for j in range(count)])
        self._k_train += count
        return idx

    def batch_rows(self, batch_size: int) -> np.ndarray:
        _, fn = _host_index_fns(self.stream_seed, batch_size)
        idx = np.asarray(fn(np.int32(self.cid), np.int32(self._k_probe),
                            np.int32(self.size)))
        self._k_probe += 1
        return idx

    def rng_state(self) -> np.ndarray:
        return np.asarray([self._k_train, self._k_probe, 0, 0, 0, 0],
                          np.uint64)

    def set_rng_state(self, row: np.ndarray) -> None:
        row = np.asarray(row).reshape(-1)
        self._k_train = int(row[0])
        self._k_probe = int(row[1])


def make_counter_clients(clients: Sequence[ClientDataset],
                         seed: int = 0) -> List[CounterDataset]:
    """Wrap existing per-client datasets as counter-stream twins of the
    ``DevicePool.from_clients`` sampling (shares the x/y arrays)."""
    return [CounterDataset(x=c.x, y=c.y, seed=c.seed, cid=i,
                           stream_seed=seed)
            for i, c in enumerate(clients)]


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------


class PopulationEngineState(NamedTuple):
    """Host snapshot of a ``run_population`` run at a round boundary.

    The client state machine is four plain ``(N,)`` arrays + three
    scalars (PopState) — counter-based RNG means there is NO generator
    state to pack, unlike ``EngineState``'s PCG64 rows. Statics are not
    stored: they are a pure function of (scenario, n, seed)."""

    version: int
    now: float
    num_events: int
    t_next: np.ndarray        # (N,) f32
    k_next: np.ndarray        # (N,) i32
    batch_k: np.ndarray       # (N,) i32
    base_version: np.ndarray  # (N,) i32
    params: Any               # host pytree
    ring: Any                 # codec host state: (R, n_padded) f32 for the
    # f32 codec, dict of arrays for int8/delta (version_store)
    history: List[Dict]
    round_log: List[Dict]


def population_state_to_tree(state: PopulationEngineState) -> Dict[str, Any]:
    """PopulationEngineState -> pytree of plain arrays (npz-safe)."""
    return {
        "meta": {"version": np.int64(state.version),
                 "now": np.float64(state.now),
                 "num_events": np.int64(state.num_events)},
        "t_next": np.asarray(state.t_next, np.float32),
        "k_next": np.asarray(state.k_next, np.int32),
        "batch_k": np.asarray(state.batch_k, np.int32),
        "base_version": np.asarray(state.base_version, np.int32),
        "params": state.params,
        "ring": (dict(state.ring) if isinstance(state.ring, dict)
                 else np.asarray(state.ring, np.float32)),
        "round_log": round_log_to_arrays(state.round_log),
        "history": history_to_arrays(state.history),
    }


def population_state_from_tree(tree: Dict[str, Any]) -> PopulationEngineState:
    """Inverse of ``population_state_to_tree``."""
    return PopulationEngineState(
        version=int(tree["meta"]["version"]),
        now=float(tree["meta"]["now"]),
        num_events=int(tree["meta"]["num_events"]),
        t_next=np.asarray(tree["t_next"], np.float32),
        k_next=np.asarray(tree["k_next"], np.int32),
        batch_k=np.asarray(tree["batch_k"], np.int32),
        base_version=np.asarray(tree["base_version"], np.int32),
        params=tree["params"],
        ring=(dict(tree["ring"]) if isinstance(tree["ring"], dict)
              else np.asarray(tree["ring"], np.float32)),
        history=history_from_arrays(tree["history"]),
        round_log=round_log_from_arrays(tree["round_log"]),
    )


# ---------------------------------------------------------------------------
# the runner
# ---------------------------------------------------------------------------


def run_population(loss_fn: Callable, init_params: Any,
                   data: Any, fl: FLConfig, total_rounds: int,
                   eval_fn: Optional[Callable[[Any], Dict]] = None,
                   eval_every: int = 5,
                   scenario: Optional[Scenario] = None,
                   seed: int = 0,
                   latency: Optional[Any] = None,
                   rounds_per_launch: int = 8,
                   mesh: Optional[Any] = None,
                   shard_ring: bool = True,
                   init_state: Optional[PopulationEngineState] = None,
                   capture_state: bool = False,
                   registry: Optional[MetricsRegistry] = None,
                   tracer: Optional[Tracer] = None) -> SimResult:
    """Simulate buffered-async FL with the client state machine resident
    on device (see module docstring).

    ``data`` is a ``DevicePool`` or a sequence of ``ClientDataset`` (then
    pooled via ``DevicePool.from_clients`` — that path samples batches on
    the counter streams, matching ``CounterDataset`` twins rather than
    the PCG64 ``ClientDataset`` draws). Scenario-driven only: behaviors,
    traces, and ``LatencyModel`` stay on the host engine. Host syncs per
    run: one per eval (params + sim time) plus one final round-log fetch
    — independent of K, N and ``total_rounds / rounds_per_launch``.

    ``capture_state=True`` attaches a ``PopulationEngineState`` to
    ``SimResult.final_state``; passing it back as ``init_state`` (same
    loss/pool/config/seed) resumes BIT-identically to the uninterrupted
    run. ``total_rounds`` counts from round 0, as in ``run_vectorized``.
    """
    if latency is not None:
        raise ValueError("run_population is scenario-driven; LatencyModel "
                         "populations need the host engine "
                         "(engine='vectorized')")
    reg = registry if registry is not None else default_registry()
    tr = tracer if tracer is not None else default_tracer()
    _dispatches = reg.counter("engine_dispatches_total")
    _syncs = reg.counter("engine_host_syncs_total")
    _dispatches_start = _dispatches.value

    with tr.span(SPAN_PREPARE, version=0 if init_state is None
                 else init_state.version) as prep:
        sc = scenario if scenario is not None else Scenario(
            name="population-default",
            description="heterogeneous lognormal population")
        pool = data if isinstance(data, DevicePool) else \
            DevicePool.from_clients(data)
        n = pool.num_clients
        k = fl.buffer_size
        spans = mesh_spans_processes(mesh)
        pspec_n = _n_pspec(mesh, n)

        # ---- place the pool ----------------------------------------------
        if mesh is not None:
            # every pool array goes through the host
            host = (np.asarray(pool.x), np.asarray(pool.y),
                    np.asarray(pool.offsets, np.int32),
                    np.asarray(pool.sizes, np.int32))
            put_bytes = host_nbytes(host)
            pool_x = put_with_sharding(host[0], mesh, P())
            pool_y = put_with_sharding(host[1], mesh, P())
            offsets = put_with_sharding(host[2], mesh, pspec_n)
            sizes = put_with_sharding(host[3], mesh, pspec_n)
        else:
            put_bytes = host_nbytes(tuple(pool))
            pool_x = jnp.asarray(pool.x)
            pool_y = jnp.asarray(pool.y)
            offsets = jnp.asarray(pool.offsets, jnp.int32)
            sizes = jnp.asarray(pool.sizes, jnp.int32)

        statics = _make_statics_fn(sc, n, seed, mesh)()

        # ---- init / resume -----------------------------------------------
        if init_state is None:
            params = init_params
            _, ring = init_version_ring(init_params, fl, mesh=mesh,
                                        shard_ring=shard_ring)
            state = _make_init_state_fn(sc, n, seed, mesh)(statics)
            version = 0
            history: List[Dict] = []
            round_log_prefix: List[Dict] = []
            put_bytes += host_nbytes(params)
        else:
            if len(init_state.base_version) != n:
                raise ValueError(
                    f"checkpoint has {len(init_state.base_version)} "
                    f"clients, this run has {n}")
            params = init_state.params
            _, ring = init_version_ring(init_params, fl, mesh=mesh,
                                        shard_ring=shard_ring,
                                        rows=init_state.ring)
            version = init_state.version

            def _place(arr, dtype):
                a = np.asarray(arr, dtype)
                return put_with_sharding(a, mesh, pspec_n) \
                    if mesh is not None else jnp.asarray(a)

            state = PopState(
                t_next=_place(init_state.t_next, np.float32),
                k_next=_place(init_state.k_next, np.int32),
                batch_k=_place(init_state.batch_k, np.int32),
                base_version=_place(init_state.base_version, np.int32),
                version=jnp.int32(version),
                now=jnp.float32(init_state.now),
                num_events=jnp.int32(init_state.num_events))
            history = [dict(h) for h in init_state.history]
            if eval_fn and history and history[-1]["round"] == version \
                    and version % eval_every:
                # drop the snapshot run's trailing forced eval
                # (off-cadence) so the resumed history matches the
                # uninterrupted run
                history.pop()
            round_log_prefix = [dict(r) for r in init_state.round_log]
            put_bytes += host_nbytes((
                params, init_state.ring, init_state.t_next,
                init_state.k_next, init_state.batch_k,
                init_state.base_version))
        if mesh is not None:
            params = (put_replicated(params, mesh) if spans
                      else jax.device_put(params, jax.sharding.NamedSharding(
                          mesh, P())))
        # bytes of the pool, ring rows and (N,) state placed here and of
        # host params, which the first launch copies
        prep.set(bytes=put_bytes)

    def _fetch(tree):
        if any(isinstance(l, jax.Array) and not l.is_fully_addressable
               for l in jax.tree.leaves(tree)):
            return fetch_replicated(tree)
        return jax.device_get(tree)

    def maybe_eval(force=False):
        if eval_fn is None or not (force or version % eval_every == 0):
            return
        with tr.span(SPAN_HOST_SYNC, what="eval", version=version):
            _syncs.inc()
            now = float(_fetch(state.now))
        record_eval(history, eval_fn, version, now, params, eval_every,
                    force)

    pending: List[Dict] = []
    if init_state is None:
        maybe_eval(force=True)
    while version < total_rounds:
        horizon = total_rounds - version
        if eval_fn:
            horizon = min(horizon, eval_every - version % eval_every)
        s = min(rounds_per_launch, horizon)
        chunk = _make_pop_chunk(loss_fn, fl, sc, n, s, seed, mesh)
        with tr.span(SPAN_APPLY, rounds=s, version=version):
            _dispatches.inc()
            params, ring, state, outs = chunk(params, ring, state, statics,
                                              pool_x, pool_y, offsets, sizes)
        # the host mirrors `version` deterministically — no sync needed
        # for loop control
        version += s
        pending.append({"v_end": version, "outs": outs})
        maybe_eval()
    maybe_eval(force=True)

    # ---- single device->host sync for the whole run's round log ---------
    outs_list = [p.pop("outs") for p in pending]
    with tr.span(SPAN_HOST_SYNC, what="round_log", launches=len(outs_list)):
        _syncs.inc()
        fetched = _fetch(outs_list)
        state_h = _fetch(state)
        round_log = list(round_log_prefix)
        for meta, logs in zip(pending, fetched):
            s_chunk = len(logs["clients"])
            round_log.extend(round_log_rows(
                meta["v_end"] - s_chunk, k, logs["clients"], logs["tau"],
                logs))
    now = float(state_h.now)
    num_events = int(state_h.num_events)

    final_state = None
    if capture_state:
        with tr.span(SPAN_CHECKPOINT, version=version):
            _syncs.inc()
            final_state = PopulationEngineState(
                version=version, now=now, num_events=num_events,
                t_next=np.asarray(state_h.t_next, np.float32),
                k_next=np.asarray(state_h.k_next, np.int32),
                batch_k=np.asarray(state_h.batch_k, np.int32),
                base_version=np.asarray(state_h.base_version, np.int32),
                params=_fetch(params),
                ring=ring_state_to_host(fl, _fetch(ring)),
                history=[dict(h) for h in history],
                round_log=[dict(r) for r in round_log])
    return SimResult(history=history, server_rounds=version, sim_time=now,
                     round_log=round_log, num_events=num_events,
                     num_launches=int(_dispatches.value - _dispatches_start),
                     final_state=final_state)
