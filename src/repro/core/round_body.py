"""The paper's round body — ONE implementation for engine and cohort.

Before this module the eq. 3/4/5 round existed twice: once inside
``sim/engine.py::_make_chunk_step`` (gather stale bases from the version
ring, vmap K local updates, probe, server round, write the ring) and once
in ``core/cohort.py`` (the replicated-client SPMD mapping, with its own
copy of the local-update / probe / flatten plumbing). ``make_round_body``
is now the single source of both: the engine wraps it in the version-ring
gather/write (``make_ring_round``), the cohort step wraps it in its
slot-resync state machine, and agreement between the two is pinned by
construction (tests/test_round_body.py).

    bases   (K, ...) pytree   stale base snapshots the clients pulled
    batch   (K, M, b, ...)    M local-step batches per client
    probe   (K, bp, ...)      eq. 4 fresh-loss probe batches
    ------------------------------------------------------------------
    deltas = vmap(local_update)(start, batch)          K clients, 1 launch
    losses = vmap(loss(params, probe_k))               eq. 4
    x', info = apply_server_round(flat(params), ...)   eq. 3 + 5

Three entry shapes — one per deployment mapping (DESIGN.md §2.1/§6):

* ``client_params=None`` (the engine): every client trains from the base
  it pulled, so the upload delta IS the local-update delta — bitwise
  identical to the pre-refactor engine. ``flat_bases``/``return_flat``
  let the engine's flat-sharded version ring feed bases in and take the
  new params out as (n_padded,) flat vectors.
* ``client_params`` given (the cohort): slots carry local progress across
  rounds (stragglers), so training starts from ``client_params`` and the
  upload delta is measured from the pulled base,
  ``Delta_i = base_i - end_i``; ``end_params`` is returned for the
  cohort's resync.
* ``make_streaming_round_body`` (the distributed client): one client
  spans the mesh, the K-buffer fills across sequential calls, and only
  O(1) state is carried — a params-shaped running accumulator, (K,)
  scalar weight buffers, and the update-norm ring for eq. 3 distances.
  The per-upload weight runs the SAME ``weighting.py`` policy code as
  the exact paths (``s_min`` cap included), with the eq. 3 reference
  pinned to the current model.

Mesh scale-out (DESIGN.md §5): with ``mesh``, the K-client vmap is
sharded over the ``data`` axis via ``shard_map`` (local training and
probes are embarrassingly parallel over K — no collectives), and the
flat-vector server pass is sharded over ``model`` inside
``apply_server_round``. Both shardings degrade gracefully: no mesh, a
size-1 axis, or a K not divisible by the data-axis size fall back to the
single-device path, so existing callers are untouched.
"""
from __future__ import annotations

import warnings
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from typing import NamedTuple

from repro.configs.base import FLConfig
from repro.core.client import make_local_update_fn
from repro.core.version_store import resolve_codec
from repro.core.server_pass import (
    apply_server_round,
    flatten_stacked,
    flatten_tree,
    make_flat_spec,
    resolve_mode,
    unflatten_like,
    unflatten_stacked,
)
from repro.core.weighting import (
    contribution_weights,
    staleness_degree,
    statistical_effect,
)
from repro.sharding.specs import (
    DATA_AXIS,
    MODEL_AXIS,
    flat_param_pspec,
    kclient_pspec,
    mesh_axis_size,
)
from repro.utils.pytree import tree_sub


def make_round_body(loss_fn: Callable, fl: FLConfig, *,
                    mesh: Any = None) -> Callable:
    """Build the shared round body.

    Returns ``body(params, bases, batch, probe, data_sizes, taus, *,
    client_params=None, arrival_mask=None) -> (new_params, end_params,
    info)`` — jit-safe, scan-safe. ``end_params`` is None on the engine
    path (``client_params=None``).
    """
    local_update = make_local_update_fn(loss_fn, fl.local_steps, fl.local_lr,
                                        fl.local_momentum)
    mode, interpret = resolve_mode(fl.server_pass_mode)
    data_shards = mesh_axis_size(mesh, DATA_AXIS)

    @jax.named_scope("probe")
    def probe_losses(params, probe):
        """eq. 4: the current model's loss on each client's probe batch."""
        losses = jax.vmap(lambda pb: loss_fn(params, pb)[0])(probe)
        return losses.astype(jnp.float32)

    def engine_phase(params, bases, batch, probe):
        deltas, _ = jax.vmap(local_update)(bases, batch)
        return deltas, probe_losses(params, probe)

    def cohort_phase(params, client_params, bases, batch, probe):
        # in-flight slots advance M steps from their CURRENT local state
        deltas_cur, _ = jax.vmap(local_update)(client_params, batch)
        end_params = jax.vmap(tree_sub)(client_params, deltas_cur)
        end_params = jax.tree.map(lambda e, c: e.astype(c.dtype), end_params,
                                  client_params)
        # cumulative upload delta measured from the pulled base (Delta_i)
        up_delta = jax.vmap(tree_sub)(bases, end_params)
        return up_delta, end_params, probe_losses(params, probe)

    def sharded_over_clients(phase, params, *stacked):
        """Run ``phase`` with its K-stacked args/results over ``data``."""
        k = jax.tree.leaves(stacked[0])[0].shape[0]
        if data_shards > 1 and k % data_shards:
            warnings.warn(
                f"K={k} clients do not divide the data axis "
                f"({data_shards} shards): the K-client local-update vmap "
                "runs unsharded (replicated over data). Pick K a multiple "
                "of the data-axis size to shard it.",
                RuntimeWarning, stacklevel=2)
        if data_shards <= 1 or k % data_shards:
            return phase(params, *stacked)
        return jax.shard_map(
            phase, mesh=mesh,
            in_specs=(P(),) + (kclient_pspec(),) * len(stacked),
            out_specs=kclient_pspec(),  # every result is K-leading
            check_vma=False)(params, *stacked)

    def body(params, bases, batch, probe, data_sizes, taus, *,
             client_params: Optional[Any] = None,
             arrival_mask: Optional[jnp.ndarray] = None,
             flat_bases: bool = False, return_flat: bool = False,
             sq_dists: Optional[jnp.ndarray] = None):
        """``flat_bases=True`` takes ``bases`` as the (K, n_padded) flat
        rows the sharded version ring stores (DESIGN.md §6) instead of a
        stacked pytree; ``return_flat=True`` replaces the ``end_params``
        return slot with the (n_padded,) flat new-params vector so the
        engine's ring write never leaves flat space (engine path only —
        ``client_params`` must be None). ``sq_dists`` carries precomputed
        eq. 3 distances from a compressed version store
        (``core/version_store.py``) into ``apply_server_round`` — the
        codec computed them against its compressed rows directly, so the
        server pass must not recompute them from the decoded bases."""
        spec = make_flat_spec(params, mesh=mesh)
        if flat_bases:
            bases_flat = bases
            bases = unflatten_stacked(spec, bases_flat, params)
        else:
            bases_flat = flatten_stacked(spec, bases)
        if client_params is None:
            deltas, losses = sharded_over_clients(
                engine_phase, params, bases, batch, probe)
            up_delta, end_params = deltas, None
        else:
            assert not return_flat, "return_flat is engine-path only"
            up_delta, end_params, losses = sharded_over_clients(
                cohort_phase, params, client_params, bases, batch, probe)
        new_x, info = apply_server_round(
            flatten_tree(spec, params),
            bases_flat,
            flatten_stacked(spec, up_delta),
            losses, data_sizes, taus, fl, arrival_mask=arrival_mask,
            mode=mode, block_n=spec.block_n, interpret=interpret, mesh=mesh,
            sq_dists=sq_dists)
        new_params = unflatten_like(spec, new_x, params)
        if not return_flat:
            return new_params, end_params, info
        # the flat vector the ring stores must hold the values clients
        # actually receive: for all-f32 templates new_x already does
        # (skip the round-trip); lower-precision params re-flatten the
        # dtype-cast tree so a fresh (tau=0) client's eq. 3 distance
        # stays exactly 0
        if all(jnp.dtype(dt) == jnp.float32 for dt in spec.dtypes):
            flat_new = new_x
        else:
            flat_new = flatten_tree(spec, new_params)
        if mesh is not None and mesh_axis_size(mesh, MODEL_AXIS) > 1:
            # the ring row must stay on the ring's P(None, "model") layout
            # so the engine's slot write is shard-local — on a
            # process-spanning mesh an unconstrained re-flatten would let
            # the partitioner replicate the row (a cross-process
            # broadcast per round) before the write re-shards it
            flat_new = jax.lax.with_sharding_constraint(
                flat_new, jax.sharding.NamedSharding(mesh,
                                                     flat_param_pspec()))
        return new_params, flat_new, info

    return body


def make_ring_round(loss_fn: Callable, fl: FLConfig, *,
                    mesh: Any = None) -> Callable:
    """The engine flavour: version-store gather -> round body -> store write.

    Returns ``ring_round(params, ring, slots, batch, probe, sizes, taus,
    new_slot) -> (new_params, new_ring, info)``. ``ring`` is whatever
    state the ``FLConfig.ring_codec`` codec keeps
    (``core/version_store.py``, DESIGN.md §11): for the default ``f32``
    codec the raw (R, n_padded) f32 matrix on the ``ShardedFlatSpec``
    layout (DESIGN.md §6) — gather ``ring[slots]``, write
    ``.at[new_slot].set(new_x)``, the bitwise pre-codec program — and a
    codec NamedTuple (int8 codewords + scales, or sparse deltas + base)
    otherwise. Gather/decode and the new-slot encode both happen in flat
    space (the round body hands back the flat new-params vector), and
    the state advances in place so a ``lax.scan`` over rounds never
    leaves the device. Compressed codecs also hand ``apply_server_round``
    their own eq. 3 distances (fused dequantize-distance kernel /
    sparse expansion), so the K decoded f32 rows feed ONLY the K-client
    local-update vmap — never a second full-width distance pass.
    """
    body = make_round_body(loss_fn, fl, mesh=mesh)
    codec = resolve_codec(fl)
    mode, interpret = resolve_mode(fl.server_pass_mode)
    use_kernel = mode == "fused"

    def ring_round(params, ring, slots, batch, probe, sizes, taus, new_slot):
        spec = make_flat_spec(params, mesh=mesh)
        with jax.named_scope("ring_decode"):
            bases = codec.decode(spec, ring, slots)  # (K, n_padded) rows
        dists = None
        if codec.precomputes_distance:  # f32 leaves eq. 3 to the server
            # pass (the exact pre-codec program — nothing extra traced)
            dists = codec.distance_sq(
                spec, ring, slots, flatten_tree(spec, params), mesh=mesh,
                use_kernel=use_kernel, interpret=interpret)
        new_params, new_x, info = body(params, bases, batch, probe, sizes,
                                       taus, flat_bases=True,
                                       return_flat=True, sq_dists=dists)
        with jax.named_scope("ring_encode"):
            new_ring = codec.encode(spec, ring, new_slot, new_x)
        return new_params, new_ring, info

    return ring_round


# ---------------------------------------------------------------------------
# streaming entry shape (distributed-client mapping, DESIGN.md §6)
# ---------------------------------------------------------------------------


class StreamingRoundBody(NamedTuple):
    """The O(1)-memory running-accumulator form of the round (third entry
    shape). ``contribute`` folds one buffered upload into the running
    state; ``apply`` completes eq. 5 once the buffer is full. The caller
    (``core/cohort.py::make_dist_step``) owns only the state machine —
    ALL weighting arithmetic lives here and in ``core/weighting.py``.
    """

    contribute: Callable
    apply: Callable


def make_streaming_round_body(loss_fn: Callable,
                              fl: FLConfig) -> StreamingRoundBody:
    """Build the streaming (distributed-client) form of the round.

    One client spans the whole mesh (FSDP x TP), so the K-buffer fills
    across sequential calls and only O(1) state is carried: a
    params-shaped accumulator ``sum_i v_i * Delta_i``, the (K,) scalar
    weight buffer ``v_i``, and the (max_staleness,) update-norm ring that
    estimates eq. 3 squared distances (cross terms dropped; ring[0] is
    the newest update).

    The per-upload weight ``v_i`` is the SAME ``weighting.py`` policy the
    exact paths run — ``contribution_weights(..., normalize="none")`` on
    the (1,)-slot vectors, including the ``s_min`` cap — with one
    convention: the eq. 3 reference distance is pinned to 0.0 (the
    current model, ``staleness_degree(..., ref_sq_dist=0.0)``) because
    the buffer-wide ``min_j`` is unknown until the buffer is full, after
    the earlier deltas have already been folded away. Whenever the buffer
    holds a fresh (tau=0) update the pinned reference equals the true
    min and the streaming weights match the exact path EXACTLY, cap
    included; with every update stale, staleness is measured against the
    current model instead of the freshest buffered update, which engages
    the ``s_min`` cap earlier. Under ``normalize="mean"`` (the default)
    only weight RATIOS matter, so that shift is conservative — the
    relative up-weighting of staler updates can only saturate at the
    cap. Under ``normalize="none"`` the absolute magnitude matters too
    and an all-stale buffer diverges from the exact reference: ``paper``
    saturates every weight at P/s_min (step inflated by up to 1/s_min),
    ``multiplicative`` shrinks weights toward eps*P/d (step nearly
    vanishes) — prefer mean normalization for this mapping. See
    DESIGN.md §6 for the full coverage statement. ``apply`` finishes
    with ``contribution_weights``'s normalization semantics: ``mean``
    divides by ``sum v_i`` (the K/K factors cancel), ``none`` by
    ``k_eff`` alone.

    ``contribute(params, accum, update_norm_ring, batch, probe,
    data_size, tau) -> (new_accum, v, info)``, ``info`` the upload's
    ``fresh_loss`` (eq. 4) and under ``local`` the loss metrics of its M
    local steps (``make_local_update_fn``), and
    ``apply(params, accum, v_buf, count, update_norm_ring) ->
    (new_params, new_ring)``.
    """
    if fl.normalize not in ("mean", "none"):  # match contribution_weights
        raise ValueError(f"unknown normalize {fl.normalize!r}")
    local_update = make_local_update_fn(loss_fn, fl.local_steps, fl.local_lr,
                                        fl.local_momentum)

    def contribute(params, accum, update_norm_ring, batch, probe, data_size,
                   tau):
        delta, local = local_update(params, batch)

        # eq. 4 probe of the CURRENT model
        with jax.named_scope("probe"):
            fresh = loss_fn(params, probe)[0].astype(jnp.float32)
        p = statistical_effect(fresh[None], data_size[None])

        # eq. 3 distance via the scalar update-norm ring (cross terms
        # dropped): ||x^t - x^{t-tau}||^2 ~= sum of the last tau ||u||^2
        tau = jnp.minimum(tau, fl.max_staleness - 1)
        recent = jnp.arange(fl.max_staleness) < tau  # ring[0] = newest
        d = jnp.sum(update_norm_ring * recent)

        # the exact policy code on this one slot (cap, poly, ...) with the
        # reference pinned to the current model; normalization is deferred
        # to apply, where the full v-buffer exists
        s = staleness_degree(d[None], ref_sq_dist=0.0)
        v = contribution_weights(fl.weighting, p, s,
                                 tau[None].astype(jnp.float32),
                                 s_min=fl.s_min, poly_a=fl.poly_a,
                                 hinge_a=fl.hinge_a, hinge_b=fl.hinge_b,
                                 normalize="none")[0]
        with jax.named_scope("fold"):
            new_accum = jax.tree.map(
                lambda a, dl: a + (v * dl.astype(jnp.float32)).astype(a.dtype),
                accum, delta)
        return new_accum, v, {"fresh_loss": fresh, "local": local}

    @jax.named_scope("fold")
    def apply(params, accum, v_buf, count, update_norm_ring):
        # eq. 5 on the running accumulator: x - eta_g/k_eff * sum w_i D_i
        # with w_i = v_i * k_eff / sum v_j ("mean") or w_i = v_i ("none")
        # — identical semantics to contribution_weights + apply_server_round
        k_eff = jnp.maximum(count.astype(jnp.float32), 1.0)
        if fl.normalize == "mean":
            scale = fl.global_lr / jnp.maximum(jnp.sum(v_buf), 1e-12)
        else:
            scale = fl.global_lr / k_eff
        upd = jax.tree.map(lambda a: scale * a.astype(jnp.float32), accum)
        new_params = jax.tree.map(lambda x, u: (x - u.astype(x.dtype)),
                                  params, upd)
        unorm = jnp.sum(jnp.stack([jnp.sum(jnp.square(u))
                                   for u in jax.tree.leaves(upd)]))
        new_ring = jnp.concatenate([unorm[None], update_norm_ring[:-1]])
        return new_params, new_ring

    return StreamingRoundBody(contribute=contribute, apply=apply)
