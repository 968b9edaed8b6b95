"""Device-resident contribution-aware server pass (DESIGN.md §3).

One compiled program computes the paper's whole per-round server maths —
eq. 3 staleness distances, the eq. 4 fresh-loss probe, the weighting
policy, and the eq. 5 reduction — over the stacked K buffered updates.
The seed implementation looped on the host with a ``float()`` sync per
buffered entry (O(K) device<->host round-trips per round); this module is
the single jitted entry point that replaces it for ``AsyncServer``, the
compiled cohort step, and ``aggregate_fused``.

Dataflow (all inside one ``jax.jit``):

    params pytree ──flatten/pad──> x (Np,)          f32
    deltas  (K, ...) ──flatten──>  d (K, Np)         f32
    bases   (K, ...) ──flatten──>  b (K, Np)         f32
    probes  (K, ...) ──vmap loss─> losses (K,)          (eq. 4)
    dists_k = ||x - b_k||^2                             (eq. 3)
    w = contribution_weights(policy, N_i * losses, S(dists), tau)
    x' = x - eta_g / k_eff * sum_k w_k d_k              (eq. 5)
    x' ──unflatten──> new params pytree (original dtypes)

The flatten/pad adapter zero-pads the concatenated parameter vector to a
lane-aligned tile multiple, which is distance- and sum-neutral, so the
Pallas kernels' ``N % block_n == 0`` contract holds for arbitrary models.

Modes (``FLConfig.server_pass_mode``):
  reference : pure-jnp body — one XLA program, runs everywhere;
  fused     : the Pallas kernels — ``fused_server_pallas`` (eq. 3 +
              weighting + eq. 5 in one two-phase launch, bases and deltas
              each read from HBM once) where it can run; under a model
              mesh, or with distances a ring codec computed, the
              two-phase ``sq_dists_pallas`` / ``weighted_sum_pallas``
              tiles, since the weighting needs the whole eq. 3 distances;
  auto      : fused on TPU, reference elsewhere (Mosaic kernels need a
              TPU; interpret mode is validation-only).

Host-sync contract: callers receive the new params and a dict of (K,)
info arrays, all device-resident. ``AsyncServer`` reads the info back
with ONE ``jax.device_get`` for its round log — at most 2 host syncs per
aggregation round, tested in tests/test_server_pass.py.

Mesh scale-out (DESIGN.md §5): ``make_flat_spec(..., mesh=...)`` returns a
``ShardedFlatSpec`` whose padded length is a multiple of
``block_n * model_shards``, and ``apply_server_round(..., mesh=...)`` runs
the round as a ``shard_map`` over the ``model`` axis — per-shard eq. 3
partial distances meet in ONE ``psum``, the (K,) weighting stays
replicated, and the eq. 5 reduction (over K, not N) completes per-shard
with no further collective.
"""
from __future__ import annotations

import functools
import logging

from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import FLConfig
from repro.core.weighting import (
    contribution_weights,
    staleness_degree,
    statistical_effect,
)
from repro.kernels.weighted_agg import kernel as _k
from repro.kernels.weighted_agg import ops as _ops
from repro.sharding.specs import MODEL_AXIS, info_pspec, mesh_axis_size

logger = logging.getLogger(__name__)

MODES = ("auto", "reference", "fused")


def resolve_mode(mode: str, interpret: Optional[bool] = None) -> Tuple[str, bool]:
    """Map ``auto`` to a backend-appropriate concrete mode.

    Mosaic kernels compile only for TPU; everywhere else interpret mode
    would run them tile-by-tile in Python (validation-only), so ``auto``
    falls back to the pure-jnp reference body — still one compiled,
    device-resident program. An explicit ``fused`` request off
    TPU is honoured in interpret mode but warns, so the silent-slowdown
    failure mode is visible.
    """
    if mode not in MODES:
        raise ValueError(f"unknown server_pass_mode {mode!r}; valid: {MODES}")
    backend = jax.default_backend()
    on_tpu = backend == "tpu"
    if interpret is None:
        interpret = not on_tpu
    if mode == "auto":
        mode = "fused" if on_tpu else "reference"
        if not on_tpu:
            logger.info(
                "server_pass_mode='auto' resolved to 'reference' on backend "
                "%r: the Pallas kernels are Mosaic programs "
                "and compile only for TPU", backend)
    elif mode == "fused" and not on_tpu and interpret:
        # standardized logging (obs.configure_logging, DESIGN.md §9):
        # launchers set the level once; this used to be a warnings.warn
        logger.warning(
            "server_pass_mode=%r requested on backend %r: Mosaic/Pallas "
            "kernels compile only for TPU, so the kernel will run in "
            "interpret mode (tile-by-tile Python, validation-only — orders "
            "of magnitude slower). Use server_pass_mode='reference' or "
            "'auto' for a compiled %s path.", mode, backend, backend)
    return mode, interpret


# ---------------------------------------------------------------------------
# pytree flatten / pad / unflatten adapter
# ---------------------------------------------------------------------------


class FlatSpec(NamedTuple):
    """Static layout of a pytree flattened to one padded f32 vector."""

    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]
    dtypes: Tuple[Any, ...]
    sizes: Tuple[int, ...]
    n: int  # true parameter count
    n_padded: int  # lane-aligned, block-divisible length
    block_n: int  # padding granularity; kernels run fit_block of it


class ShardedFlatSpec(NamedTuple):
    """FlatSpec plus the mesh layout of the flat vector (DESIGN.md §5).

    Same leading fields as ``FlatSpec`` (the flatten/unflatten helpers
    accept either), but ``n_padded`` is a multiple of
    ``block_n * model_shards`` so every ``model``-axis shard holds a whole
    number of kernel tiles. Zero padding is distance- and sum-neutral, so
    shards holding only padding contribute 0 to the eq. 3 psum.
    """

    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]
    dtypes: Tuple[Any, ...]
    sizes: Tuple[int, ...]
    n: int
    n_padded: int
    block_n: int
    mesh: Any  # jax.sharding.Mesh carrying the ``model`` axis
    model_shards: int  # size of the model axis (> 1)


def make_flat_spec(template: Any, block_n: int = 0, mesh: Any = None):
    """Build the flatten layout for ``template`` (works under tracing).

    With ``mesh`` carrying a ``model`` axis of size m > 1, returns a
    ``ShardedFlatSpec`` padded to a ``block_n * m`` multiple so the padded
    vector partitions evenly into per-shard whole-tile slices.
    """
    leaves, treedef = jax.tree.flatten(template)
    shapes = tuple(tuple(x.shape) for x in leaves)
    dtypes = tuple(x.dtype for x in leaves)
    sizes = tuple(int(np.prod(s)) if s else 1 for s in shapes)
    n = sum(sizes)
    block = block_n or _ops.pick_block(n)
    shards = mesh_axis_size(mesh, MODEL_AXIS) if mesh is not None else 1
    if shards > 1:
        return ShardedFlatSpec(treedef, shapes, dtypes, sizes, n,
                               _ops.pad_to(n, block * shards), block,
                               mesh, shards)
    return FlatSpec(treedef, shapes, dtypes, sizes, n,
                    _ops.pad_to(n, block), block)


def flatten_tree(spec: FlatSpec, tree: Any) -> jnp.ndarray:
    """pytree -> (n_padded,) f32, zero-padded (distance/sum neutral)."""
    leaves = jax.tree.leaves(tree)
    flat = jnp.concatenate([x.astype(jnp.float32).reshape(-1) for x in leaves])
    if spec.n_padded != spec.n:
        flat = jnp.pad(flat, (0, spec.n_padded - spec.n))
    return flat


def flatten_stacked(spec: FlatSpec, stacked: Any) -> jnp.ndarray:
    """pytree with (K, ...) leaves -> (K, n_padded) f32."""
    leaves = jax.tree.leaves(stacked)
    k = leaves[0].shape[0]
    flat = jnp.concatenate(
        [x.astype(jnp.float32).reshape(k, -1) for x in leaves], axis=1)
    if spec.n_padded != spec.n:
        flat = jnp.pad(flat, ((0, 0), (0, spec.n_padded - spec.n)))
    return flat


def unflatten_stacked(spec: FlatSpec, mat: jnp.ndarray, template: Any) -> Any:
    """(K, n_padded) f32 -> pytree with (K, ...) leaves (template dtypes).

    Inverse of ``flatten_stacked``; the engine's flat-sharded version ring
    (DESIGN.md §6) gathers bases as (K, Np) rows and unflattens them only
    for the K-client local-update vmap."""
    k = mat.shape[0]
    leaves = []
    off = 0
    for shape, dtype, size in zip(spec.shapes, spec.dtypes, spec.sizes):
        leaves.append(mat[:, off:off + size].reshape((k,) + shape)
                      .astype(dtype))
        off += size
    return jax.tree.unflatten(spec.treedef, leaves)


def unflatten_like(spec: FlatSpec, vec: jnp.ndarray, template: Any) -> Any:
    """(n_padded,) or (n,) f32 -> pytree with the template's dtypes."""
    leaves = []
    off = 0
    for shape, dtype, size in zip(spec.shapes, spec.dtypes, spec.sizes):
        leaves.append(vec[off:off + size].reshape(shape).astype(dtype))
        off += size
    return jax.tree.unflatten(spec.treedef, leaves)


# ---------------------------------------------------------------------------
# the round core (shared by AsyncServer, cohort step, and the benchmarks)
# ---------------------------------------------------------------------------


@jax.named_scope("server_pass")
def apply_server_round(x: jnp.ndarray, bases: jnp.ndarray,
                       deltas: jnp.ndarray, losses: jnp.ndarray,
                       data_sizes: jnp.ndarray, taus: jnp.ndarray,
                       fl: FLConfig, *,
                       arrival_mask: Optional[jnp.ndarray] = None,
                       mode: str = "reference", block_n: int = 0,
                       interpret: bool = False, mesh: Any = None,
                       sq_dists: Optional[jnp.ndarray] = None):
    """eq. 3 + 4 + 5 on flat arrays. Returns (new_x, info dict of (K,)).

    x: (Np,), bases/deltas: (K, Np) — already padded to a ``block_n``
    multiple (zeros), e.g. by the FlatSpec adapter. losses/data_sizes/
    taus: (K,). ``arrival_mask`` zeroes absent cohort slots (weights AND
    the k_eff divisor), matching ``contribution_weights``.

    ``sq_dists`` short-circuits eq. 3 with precomputed (K,) squared
    distances — the compressed version store's escape hatch
    (``core/version_store.py``: the int8 codec's fused
    dequantize-distance kernel and the delta codec's sparse expansion
    both produce distances WITHOUT materializing the decoded rows, so
    recomputing them here from the decoded ``bases`` would waste the
    codec's bandwidth win). With it set, the fused single-launch kernel
    is skipped in favour of the two-phase weighted-sum path (its phase 0
    IS the distance computation), and the sharded path drops its psum
    (the codec already reduced across shards).

    With ``mesh`` carrying a ``model`` axis of size m > 1, the pass runs
    as a ``shard_map`` over that axis (``Np`` must be a
    ``block_n * m`` multiple — use ``make_flat_spec(..., mesh=mesh)``):
    per-shard partial eq. 3 distances complete with one psum, the (K,)
    weighting is computed replicated, and the eq. 5 reduction (over K)
    finishes per-shard with no further collective.
    """
    if mode not in ("reference", "fused"):
        raise ValueError(f"unknown concrete mode {mode!r}")
    p = statistical_effect(losses, data_sizes)
    k = bases.shape[0]
    mask = (jnp.ones((k,), jnp.float32) if arrival_mask is None
            else arrival_mask.astype(jnp.float32))
    taus = taus.astype(jnp.float32)
    shards = mesh_axis_size(mesh, MODEL_AXIS) if mesh is not None else 1
    # default tile from the PER-SHARD slice length, so the kernels'
    # N % block_n == 0 contract holds inside the shard_map body too
    block = block_n or _ops.pick_block(x.shape[0] // shards)
    if shards > 1:
        return _apply_server_round_sharded(
            x, bases, deltas, losses, p, taus, mask, fl, mode=mode,
            block=block, interpret=interpret, mesh=mesh, sq_dists=sq_dists)

    if mode == "fused" and sq_dists is None:
        upd, dists, w = _ops.server_update(
            x, bases, deltas, p, taus, mask, policy=fl.weighting,
            eta_g=fl.global_lr, s_min=fl.s_min, poly_a=fl.poly_a,
            hinge_a=fl.hinge_a, hinge_b=fl.hinge_b,
            normalize=fl.normalize, block_n=block, interpret=interpret)
        s = staleness_degree(dists, arrival_mask=mask)
    else:
        # the reference body, or ``fused`` with a codec's distances (the
        # one-launch kernel's phase 0 would only recompute them)
        dists = (_sq_dists(x, bases, use_kernel=False, block=block,
                           interpret=interpret)
                 if sq_dists is None else sq_dists.astype(jnp.float32))
        upd, s, w = _weight_and_reduce(
            dists, deltas, p, taus, mask, fl,
            use_kernel=(mode == "fused"), block=block, interpret=interpret)
    new_x = x - upd

    info = {"sq_dists": dists, "staleness": s, "stat_effect": p,
            "weights": w, "fresh_loss": losses}
    return new_x, info


def _sq_dists(x, bases, *, use_kernel, block, interpret):
    """eq. 3 squared distances over the (local slice of the) flat vector."""
    if use_kernel:
        return _k.sq_dists_pallas(x, bases, block_n=block,
                                  interpret=interpret)
    diff = bases - x[None]
    return jnp.sum(diff * diff, axis=1)


def _weight_and_reduce(dists, deltas, p, taus, mask, fl: FLConfig, *,
                       use_kernel, block, interpret):
    """Everything after eq. 3: staleness ratio -> policy weights -> the
    eq. 5 weighted-delta reduction. The ONE copy both the single-device
    pass and the per-shard shard_map body run, so sharded-vs-single
    parity cannot drift when the weighting logic evolves. The eq. 3 min
    reference is taken over ARRIVED slots only (mask>0) — an absent
    straggler's base must not distort the applied weights (and the
    cohort's arrival-masked telemetry stays consistent with them).
    """
    s = staleness_degree(dists, arrival_mask=mask)
    w = contribution_weights(fl.weighting, p, s, taus, s_min=fl.s_min,
                             poly_a=fl.poly_a, hinge_a=fl.hinge_a,
                             hinge_b=fl.hinge_b, normalize=fl.normalize,
                             arrival_mask=mask)
    k_eff = jnp.maximum(jnp.sum(mask), 1.0)
    w_scaled = w * (fl.global_lr / k_eff)
    if use_kernel:
        upd = _k.weighted_sum_pallas(deltas, w_scaled, block_n=block,
                                     interpret=interpret)
    else:
        # an f32 multiply-and-reduce over K, not a dot: on TPU an f32 dot
        # at default precision runs in bf16 passes, and a (K, Np) dot at
        # HIGHEST precision may split the deltas into bf16 copies in HBM
        upd = jnp.sum(deltas * w_scaled[:, None], axis=0)
    return upd, s, w


def _apply_server_round_sharded(x, bases, deltas, losses, p, taus, mask,
                                fl: FLConfig, *, mode, block, interpret,
                                mesh, sq_dists=None):
    """shard_map body of the round over the ``model`` axis (DESIGN.md §5).

    Inputs are the preprocessed arrays from ``apply_server_round`` (mask
    built, taus cast, block picked per-shard). The fused single-launch
    kernel folds the weighting into the kernel, but the weighting needs
    the GLOBAL eq. 3 distances — which only exist after the cross-shard
    psum — so under sharding ``fused`` runs the two-phase tiles
    (``sq_dists_pallas`` + ``weighted_sum_pallas``) per shard.
    Precomputed ``sq_dists`` (the compressed-ring codecs) arrive already
    globally reduced, so that path carries them in replicated and skips
    the psum entirely — the round then has NO collective beyond the
    final output layout.
    """
    use_kernel = mode == "fused"

    if sq_dists is not None:
        def shard_body_pre(x_s, d_s, p_, taus_, mask_, dists):
            upd, s, w = _weight_and_reduce(
                dists, d_s, p_, taus_, mask_, fl, use_kernel=use_kernel,
                block=block, interpret=interpret)
            return x_s - upd, dists, s, w

        new_x, dists, s, w = jax.shard_map(
            shard_body_pre, mesh=mesh,
            in_specs=(P(MODEL_AXIS), P(None, MODEL_AXIS),
                      P(), P(), P(), P()),
            out_specs=(P(MODEL_AXIS), P(), P(), P()),
            check_vma=False)(x, deltas, p, taus, mask,
                             sq_dists.astype(jnp.float32))
    else:
        def shard_body(x_s, b_s, d_s, p_, taus_, mask_):
            # eq. 3: per-shard partial squared distances -> ONE psum, then
            # the shared post-distance pipeline (weighting replicated,
            # eq. 5 reducing over K) completes per-shard with no further
            # collective
            part = _sq_dists(x_s, b_s, use_kernel=use_kernel, block=block,
                             interpret=interpret)
            dists = jax.lax.psum(part, MODEL_AXIS)
            upd, s, w = _weight_and_reduce(
                dists, d_s, p_, taus_, mask_, fl, use_kernel=use_kernel,
                block=block, interpret=interpret)
            return x_s - upd, dists, s, w

        new_x, dists, s, w = jax.shard_map(
            shard_body, mesh=mesh,
            in_specs=(P(MODEL_AXIS), P(None, MODEL_AXIS),
                      P(None, MODEL_AXIS), P(), P(), P()),
            out_specs=(P(MODEL_AXIS), P(), P(), P()),
            check_vma=False)(x, bases, deltas, p, taus, mask)
    info = {"sq_dists": dists, "staleness": s, "stat_effect": p,
            "weights": w, "fresh_loss": losses}
    # multi-host contract (DESIGN.md §7): info stays FULLY REPLICATED so
    # every process can read the round log from its own addressable
    # shards — pin it so the partitioner can never reshard it over a
    # process-spanning axis downstream (e.g. under the engine's scan)
    rep = jax.sharding.NamedSharding(mesh, info_pspec())
    info = jax.tree.map(
        lambda a: jax.lax.with_sharding_constraint(a, rep), info)
    return new_x, info


@functools.lru_cache(maxsize=64)
def make_server_pass(fl: FLConfig,
                     fresh_loss_fn: Optional[Callable[[Any, Any], jnp.ndarray]],
                     *, mode: Optional[str] = None,
                     interpret: Optional[bool] = None,
                     mesh: Any = None) -> Callable:
    """Build the jitted server pass (memoized: one compiled program per
    (fl, fresh_loss_fn, mode) across repeated server constructions).

    Returns ``pass_fn(params, deltas_st, bases_st, probes, probe_mask,
    data_sizes, taus, losses=None) -> (new_params, info)`` where
    ``deltas_st`` / ``bases_st`` are pytrees with (K, ...) leaves,
    ``probes`` is a pytree of stacked probe batches (leading K) or None,
    and ``probe_mask`` is (K,) {0,1} marking entries that actually
    supplied a probe (the rest fall back to loss 1.0, i.e. pure size
    weighting). ``losses`` short-circuits the probe with precomputed
    (K,) fresh losses — the escape hatch for probe batches whose shapes
    don't stack (AsyncServer._gather_probes). Everything stays on
    device; the caller decides what (if anything) to read back.

    ``mesh`` shards the flat-vector round over the mesh's ``model`` axis
    (DESIGN.md §5); with no mesh the pass is the single-device program.
    """
    mode_, interpret_ = resolve_mode(fl.server_pass_mode if mode is None
                                     else mode, interpret)

    @jax.jit
    def pass_fn(params, deltas_st, bases_st, probes, probe_mask,
                data_sizes, taus, precomputed_losses=None):
        spec = make_flat_spec(params, mesh=mesh)
        x = flatten_tree(spec, params)
        d = flatten_stacked(spec, deltas_st)
        b = flatten_stacked(spec, bases_st)
        data_sizes_ = data_sizes.astype(jnp.float32)
        if precomputed_losses is not None:
            losses = precomputed_losses.astype(jnp.float32)
        elif probes is None or fresh_loss_fn is None:
            losses = jnp.ones_like(data_sizes_)
        else:
            losses = jax.vmap(lambda pb: fresh_loss_fn(params, pb))(probes)
            losses = losses.astype(jnp.float32)
            if probe_mask is not None:
                losses = jnp.where(probe_mask > 0, losses, 1.0)
        new_x, info = apply_server_round(
            x, b, d, losses, data_sizes_, taus, fl, mode=mode_,
            block_n=spec.block_n, interpret=interpret_, mesh=mesh)
        return unflatten_like(spec, new_x, params), info

    return pass_fn
