"""Codec-pluggable compressed version store (DESIGN.md §11).

The paper's eq. 3 staleness weighting needs the server to retain
R = max_staleness + 1 historical model versions. The engine stored them
as R full f32 flat rows — linear in model size, so R=16 of a 7B-param
model is ~450 GB even sharded. But eq. 3 only consumes *distances* to
ring entries (and clients pull bases that are immediately perturbed by M
local SGD steps), so the rows can be stored compressed. This module owns
that storage behind one interface; ``core/round_body.py::make_ring_round``
is codec-agnostic, ``sim/engine.py`` / ``sim/population.py`` carry the
codec state through scan/checkpoint, and every layer selects the codec
from ``FLConfig.ring_codec``.

Codecs (all on the ``make_flat_spec`` padded flat layout, DESIGN.md §6):

``f32``   identity — the pre-refactor (R, Np) f32 matrix, BIT-compatible:
          gather is ``ring[slots]``, write is ``ring.at[slot].set(row)``,
          and ``distance_sq`` defers to the server pass (returns None),
          so the engine compiles to the identical XLA program and every
          existing sharded/multihost/population parity pin holds.
``int8``  per-block affine quantization: int8 codewords + per-block f32
          (scale, zero) pairs, ``~(1 + 8/qblock) / 4`` of the f32 bytes
          (3.8x smaller at qblock=256). eq. 3 distances run through the
          fused dequantize-distance kernel (``kernels/ring_codec``) so
          the K decoded rows are never materialized.
``delta`` sparse residual against a periodically-refreshed f32 base
          snapshot: per row the top-m |residual| entries (m = density *
          Np) as (int32 idx, f32 val) pairs. Distances are EXACT via the
          expansion ||x - (base + s)||^2 = ||x - base||^2
          - 2<x - base, s> + ||s||^2 — one dense base pass plus O(m)
          gathers per row. Every ``ring_base_refresh`` writes the base
          snaps to the incoming row and retained rows re-encode against
          it (scanned row-at-a-time so no (R, Np) dense intermediate
          ever exists).

Checkpointing: a codec's device state round-trips through
``state_to_host`` / ``state_from_host`` as plain numpy (f32: the bare
(R, Np) matrix, unchanged on disk; compressed codecs: a dict of arrays
with a ``codec`` name stamp). Restore is codec-aware — a layout or
codec mismatch raises with the codec NAME and the expected layout, not
a bare shape pair.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.kernels.ring_codec import ops as _cops
from repro.kernels.ring_codec import ref as _cref
from repro.sharding.specs import (
    MODEL_AXIS,
    flat_param_pspec,
    ring_codes_pspec,
    ring_pspec,
    ring_scales_pspec,
)

CODECS = ("f32", "int8", "delta")


def resolve_qblock(spec, requested: int) -> int:
    """Largest power-of-two-ish divisor of the kernel tile <= requested.

    The quantization block must divide ``spec.block_n`` (so the fused
    kernel's scale columns align with its N-tiles) and therefore also
    ``n_padded`` and the per-shard slice. ``block_n`` is always a
    multiple of LANE=128, so halving from the requested size always
    terminates at a valid block.
    """
    qb = max(1, int(requested))
    while spec.block_n % qb:
        qb //= 2
    return max(qb, 1)


# ---------------------------------------------------------------------------
# codec states (NamedTuples: scan-carry and checkpoint friendly)
# ---------------------------------------------------------------------------


class Int8RingState(NamedTuple):
    """int8 codec device state: codewords + per-block affine params."""

    codes: jnp.ndarray  # (R, Np) int8
    scale: jnp.ndarray  # (R, Np // qblock) f32
    zero: jnp.ndarray  # (R, Np // qblock) f32


class DeltaRingState(NamedTuple):
    """delta codec device state: base snapshot + per-row sparse residual."""

    base: jnp.ndarray  # (Np,) f32 snapshot the residuals are against
    idx: jnp.ndarray  # (R, m) int32 residual positions
    val: jnp.ndarray  # (R, m) f32 residual values
    writes: jnp.ndarray  # () int32 ring-write counter (refresh schedule)


# ---------------------------------------------------------------------------
# codecs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class F32Codec:
    """Identity codec — the pre-refactor ring, bit-for-bit."""

    name = "f32"
    precomputes_distance = False  # eq. 3 stays in the server pass

    def init_state(self, spec, flat0: jnp.ndarray, depth: int):
        # broadcast_to + * 1 materializes a writable copy (as before)
        return jnp.broadcast_to(flat0[None], (depth, spec.n_padded)) * 1

    def decode(self, spec, state, slots: jnp.ndarray) -> jnp.ndarray:
        return state[slots]

    def encode(self, spec, state, slot, row: jnp.ndarray):
        return state.at[slot].set(row)

    def distance_sq(self, spec, state, slots, x, *, mesh=None,
                    use_kernel=False, interpret=False):
        """None: the server pass computes eq. 3 from the decoded rows —
        the exact program that ran before the refactor (bit parity)."""
        return None

    def pspecs(self, spec) -> List[P]:
        return [ring_pspec()]

    def expected_layout(self, spec, depth: int) -> Dict[str, Tuple]:
        return {"ring": ((depth, spec.n_padded), "float32")}

    def device_bytes(self, spec, depth: int, model_shards: int = 1) -> int:
        per_shard_np = -(-spec.n_padded // model_shards)
        return depth * per_shard_np * 4

    def state_to_host(self, state) -> np.ndarray:
        return np.asarray(state, np.float32)

    def state_from_host(self, spec, depth: int, host):
        if isinstance(host, dict):
            raise ValueError(_codec_mismatch_msg(self, spec, depth, host))
        rows = np.asarray(host)
        if tuple(rows.shape) != (depth, spec.n_padded):
            raise ValueError(
                f"checkpointed f32 ring shape {tuple(rows.shape)} does not "
                f"match this run's layout "
                f"{_layout_str(self.expected_layout(spec, depth))} — same "
                "model/fl config (incl. ring_codec) required to resume")
        return jnp.asarray(rows, jnp.float32)


@dataclasses.dataclass(frozen=True)
class Int8Codec:
    """Per-block affine int8 quantization (codewords + scale/zero)."""

    qblock: int = 256

    name = "int8"
    precomputes_distance = True

    def _qb(self, spec) -> int:
        return resolve_qblock(spec, self.qblock)

    def _nblocks(self, spec) -> int:
        return spec.n_padded // self._qb(spec)

    def _quant_row(self, spec, row: jnp.ndarray):
        """(Np,) f32 -> (codes (Np,) int8, scale (Nb,), zero (Nb,))."""
        qb = self._qb(spec)
        v = row.reshape(-1, qb)
        hi = jnp.max(v, axis=1)
        lo = jnp.min(v, axis=1)
        zero = 0.5 * (hi + lo)
        scale = (hi - lo) / 254.0  # symmetric range [-127, 127]
        safe = jnp.where(scale > 0, scale, 1.0)
        q = jnp.round((v - zero[:, None]) / safe[:, None])
        codes = jnp.clip(q, -127, 127).astype(jnp.int8)
        return codes.reshape(-1), scale, zero

    def init_state(self, spec, flat0: jnp.ndarray, depth: int):
        codes, scale, zero = self._quant_row(spec, flat0)
        rep = lambda a: jnp.broadcast_to(a[None], (depth,) + a.shape) * 1
        return Int8RingState(codes=rep(codes), scale=rep(scale),
                             zero=rep(zero))

    def decode(self, spec, state: Int8RingState, slots) -> jnp.ndarray:
        qb = self._qb(spec)
        return _cref.dequant_ref(state.codes[slots], state.scale[slots],
                                 state.zero[slots], qb)

    def encode(self, spec, state: Int8RingState, slot, row):
        codes, scale, zero = self._quant_row(spec, row)
        return Int8RingState(codes=state.codes.at[slot].set(codes),
                             scale=state.scale.at[slot].set(scale),
                             zero=state.zero.at[slot].set(zero))

    def distance_sq(self, spec, state: Int8RingState, slots, x, *,
                    mesh=None, use_kernel=False, interpret=False):
        """Fused dequantize-distance: the K decoded f32 rows are never
        materialized on the kernel path; under a model mesh each shard
        computes its partial and they meet in ONE psum (the same
        communication shape as the f32 server pass)."""
        qb = self._qb(spec)
        codes = state.codes[slots]
        scale = state.scale[slots]
        zero = state.zero[slots]
        shards = getattr(spec, "model_shards", 1)
        if mesh is None or shards <= 1:
            return _cops.int8_sq_dists(
                x, codes, scale, zero, qblock=qb, block_n=spec.block_n,
                use_kernel=use_kernel, interpret=interpret)

        def shard_body(x_s, c_s, s_s, z_s):
            part = _cops.int8_sq_dists(
                x_s, c_s, s_s, z_s, qblock=qb, block_n=spec.block_n,
                use_kernel=use_kernel, interpret=interpret)
            return jax.lax.psum(part, MODEL_AXIS)

        return jax.shard_map(
            shard_body, mesh=mesh,
            in_specs=(flat_param_pspec(), P(None, MODEL_AXIS),
                      P(None, MODEL_AXIS), P(None, MODEL_AXIS)),
            out_specs=P(), check_vma=False)(x, codes, scale, zero)

    def pspecs(self, spec) -> List[P]:
        return [ring_codes_pspec(), ring_scales_pspec(),
                ring_scales_pspec()]

    def expected_layout(self, spec, depth: int) -> Dict[str, Tuple]:
        nb = self._nblocks(spec)
        return {"codes": ((depth, spec.n_padded), "int8"),
                "scale": ((depth, nb), "float32"),
                "zero": ((depth, nb), "float32")}

    def device_bytes(self, spec, depth: int, model_shards: int = 1) -> int:
        per_shard_np = -(-spec.n_padded // model_shards)
        per_shard_nb = -(-self._nblocks(spec) // model_shards)
        return depth * (per_shard_np + 2 * per_shard_nb * 4)

    def state_to_host(self, state: Int8RingState) -> Dict[str, np.ndarray]:
        return {"codec": np.asarray(self.name),
                "codes": np.asarray(state.codes, np.int8),
                "scale": np.asarray(state.scale, np.float32),
                "zero": np.asarray(state.zero, np.float32)}

    def state_from_host(self, spec, depth: int, host):
        arrays = _checked_host_dict(self, spec, depth, host)
        return Int8RingState(codes=jnp.asarray(arrays["codes"], jnp.int8),
                             scale=jnp.asarray(arrays["scale"], jnp.float32),
                             zero=jnp.asarray(arrays["zero"], jnp.float32))


@dataclasses.dataclass(frozen=True)
class DeltaCodec:
    """Sparse residual vs a periodically-refreshed f32 base snapshot."""

    density: float = 0.05
    base_refresh: int = 0  # 0 -> ring depth (one full lap)

    name = "delta"
    precomputes_distance = True

    def _m(self, spec) -> int:
        return max(1, min(spec.n_padded,
                          int(round(self.density * spec.n_padded))))

    def _refresh_every(self, depth: int) -> int:
        return self.base_refresh if self.base_refresh > 0 else depth

    def init_state(self, spec, flat0: jnp.ndarray, depth: int):
        m = self._m(spec)
        return DeltaRingState(
            base=flat0 * 1,
            idx=jnp.zeros((depth, m), jnp.int32),
            val=jnp.zeros((depth, m), jnp.float32),
            writes=jnp.zeros((), jnp.int32))

    def _scatter(self, n: int, idx: jnp.ndarray, val: jnp.ndarray):
        # duplicate indices only occur for the all-zero init rows, where
        # add keeps the scatter exact
        return jnp.zeros((n,), jnp.float32).at[idx].add(val)

    def decode(self, spec, state: DeltaRingState, slots) -> jnp.ndarray:
        idx = state.idx[slots]
        val = state.val[slots]
        sp = jax.vmap(lambda i, v: self._scatter(spec.n_padded, i, v))(idx,
                                                                       val)
        return state.base[None] + sp

    def encode(self, spec, state: DeltaRingState, slot, row):
        m = self._m(spec)

        def top_m(dense):
            mag, idx = jax.lax.top_k(jnp.abs(dense), m)
            idx = idx.astype(jnp.int32)
            return idx, dense[idx]

        def normal(st):
            idx, val = top_m(row - st.base)
            return DeltaRingState(base=st.base,
                                  idx=st.idx.at[slot].set(idx),
                                  val=st.val.at[slot].set(val),
                                  writes=st.writes)

        def refresh(st):
            # new base := the incoming row; every retained row re-encodes
            # against it. Row r's dense residual vs the new base is
            # (base_old - base_new) + scatter(idx_r, val_r) — rebuilt one
            # row at a time under lax.scan so the (R, Np) dense ring is
            # never materialized (the whole point of this codec).
            base_diff = st.base - row

            def per_row(carry, iv):
                idx_r, val_r = iv
                dense = base_diff.at[idx_r].add(val_r)
                return carry, top_m(dense)

            _, (idx, val) = jax.lax.scan(per_row, 0, (st.idx, st.val))
            # the slot being written IS the new base: residual exactly 0
            idx = idx.at[slot].set(jnp.zeros((m,), jnp.int32))
            val = val.at[slot].set(jnp.zeros((m,), jnp.float32))
            return DeltaRingState(base=row, idx=idx, val=val,
                                  writes=st.writes)

        every = self._refresh_every(state.idx.shape[0])
        do_refresh = jnp.mod(state.writes + 1, every) == 0
        new = jax.lax.cond(do_refresh, refresh, normal, state)
        return new._replace(writes=state.writes + 1)

    def distance_sq(self, spec, state: DeltaRingState, slots, x, *,
                    mesh=None, use_kernel=False, interpret=False):
        """EXACT eq. 3 distances without densifying the rows:
        ||x - (base + s_r)||^2 = ||x - base||^2 - 2<x - base, s_r>
        + ||s_r||^2 — one dense pass over the base plus O(m) gathers per
        row (the sparse rows never become (K, Np))."""
        xb = x - state.base
        idx = state.idx[slots]
        val = state.val[slots]
        d = (jnp.sum(xb * xb)
             - 2.0 * jnp.sum(xb[idx] * val, axis=1)
             + jnp.sum(val * val, axis=1))
        return jnp.maximum(d, 0.0)

    def pspecs(self, spec) -> List[P]:
        # base rides the flat-param layout; the sparse (idx, val) pairs
        # index the GLOBAL flat vector so they stay replicated (m is tiny
        # — density * Np entries vs Np per dense row), as do the scalars
        return [flat_param_pspec(), P(), P(), P()]

    def expected_layout(self, spec, depth: int) -> Dict[str, Tuple]:
        m = self._m(spec)
        return {"base": ((spec.n_padded,), "float32"),
                "idx": ((depth, m), "int32"),
                "val": ((depth, m), "float32"),
                "writes": ((), "int32")}

    def device_bytes(self, spec, depth: int, model_shards: int = 1) -> int:
        per_shard_np = -(-spec.n_padded // model_shards)
        return per_shard_np * 4 + depth * self._m(spec) * 8 + 4

    def state_to_host(self, state: DeltaRingState) -> Dict[str, np.ndarray]:
        return {"codec": np.asarray(self.name),
                "base": np.asarray(state.base, np.float32),
                "idx": np.asarray(state.idx, np.int32),
                "val": np.asarray(state.val, np.float32),
                "writes": np.asarray(state.writes, np.int32)}

    def state_from_host(self, spec, depth: int, host):
        arrays = _checked_host_dict(self, spec, depth, host)
        return DeltaRingState(
            base=jnp.asarray(arrays["base"], jnp.float32),
            idx=jnp.asarray(arrays["idx"], jnp.int32),
            val=jnp.asarray(arrays["val"], jnp.float32),
            writes=jnp.asarray(arrays["writes"], jnp.int32))


def resolve_codec(fl) -> Any:
    """The codec instance ``FLConfig.ring_codec`` selects."""
    if fl.ring_codec == "f32":
        return F32Codec()
    if fl.ring_codec == "int8":
        return Int8Codec(qblock=fl.ring_qblock)
    if fl.ring_codec == "delta":
        return DeltaCodec(density=fl.ring_delta_density,
                          base_refresh=fl.ring_base_refresh)
    raise ValueError(
        f"unknown ring_codec {fl.ring_codec!r}; valid: {CODECS}")


# ---------------------------------------------------------------------------
# codec-aware restore errors (the f32-only shape message predates codecs)
# ---------------------------------------------------------------------------


def _layout_str(layout: Dict[str, Tuple]) -> str:
    return "{" + ", ".join(f"{k}: {shape} {dtype}"
                           for k, (shape, dtype) in layout.items()) + "}"


def _codec_mismatch_msg(codec, spec, depth: int, host) -> str:
    if isinstance(host, dict):
        found = str(host.get("codec", "<unstamped dict>"))
    else:
        found = f"f32 matrix of shape {tuple(np.shape(host))}"
    return (f"checkpointed ring was written by codec {found!r} but this "
            f"run uses ring_codec={codec.name!r} expecting layout "
            f"{_layout_str(codec.expected_layout(spec, depth))} — resume "
            "with the SAME ring_codec (and model/fl config) it was "
            "checkpointed with")


def _checked_host_dict(codec, spec, depth: int, host) -> Dict[str, Any]:
    """Validate a compressed codec's host dict: codec stamp + exact layout."""
    if not isinstance(host, dict):
        raise ValueError(_codec_mismatch_msg(codec, spec, depth, host))
    stamp = host.get("codec")
    if stamp is not None and str(np.asarray(stamp)) != codec.name:
        raise ValueError(_codec_mismatch_msg(codec, spec, depth, host))
    layout = codec.expected_layout(spec, depth)
    for key, (shape, _) in layout.items():
        if key not in host:
            raise ValueError(
                f"checkpointed {codec.name!r} ring is missing field "
                f"{key!r}; expected layout {_layout_str(layout)}")
        got = tuple(np.shape(host[key]))
        if got != shape:
            raise ValueError(
                f"checkpointed {codec.name!r} ring field {key!r} has shape "
                f"{got}, expected {shape} (full layout "
                f"{_layout_str(layout)}) — same model/fl config (incl. "
                "ring codec parameters) required to resume")
    return host


# ---------------------------------------------------------------------------
# store construction + host round-trip (the engine/population entry points)
# ---------------------------------------------------------------------------


def build_ring(init_params: Any, fl, *, mesh: Optional[Any] = None,
               shard_ring: bool = True, rows: Optional[Any] = None):
    """Build (or restore) the version store. Returns ``(spec, state)``.

    The codec-generalised ``sim/engine.py::init_version_ring`` (which now
    delegates here): ``state`` is the raw (R, Np) f32 matrix for the
    ``f32`` codec — bit-compatible with every pre-codec caller — and a
    codec NamedTuple otherwise. ``rows`` restores from the host
    representation ``ring_state_to_host`` produced; mismatches raise
    codec-aware errors naming the codec and its expected layout.
    """
    from repro.core.server_pass import flatten_tree, make_flat_spec
    from repro.launch.multihost import put_with_sharding

    spec = make_flat_spec(init_params, mesh=mesh)
    depth = fl.max_staleness + 1
    codec = resolve_codec(fl)
    if rows is None:
        state = codec.init_state(spec, flatten_tree(spec, init_params),
                                 depth)
    else:
        state = codec.state_from_host(spec, depth, rows)
    shards = getattr(spec, "model_shards", 1)
    if mesh is not None:
        pspecs = (codec.pspecs(spec) if shard_ring and shards > 1
                  else [P()] * len(jax.tree.leaves(state)))
        leaves, treedef = jax.tree.flatten(state)
        placed = [put_with_sharding(leaf, mesh, ps)
                  for leaf, ps in zip(leaves, pspecs)]
        state = jax.tree.unflatten(treedef, placed)
    return spec, state


def ring_state_to_host(fl, state) -> Any:
    """Device (already-fetched) ring state -> checkpointable host arrays.

    f32 keeps the bare (R, Np) f32 matrix (existing checkpoints and the
    ``EngineState.ring`` pins stay byte-compatible); compressed codecs
    produce a dict of arrays stamped with the codec name.
    """
    return resolve_codec(fl).state_to_host(state)


def ring_device_bytes(fl, spec, model_shards: int = 1) -> int:
    """Per-device bytes the ring costs under ``fl`` on ``spec``'s layout."""
    return resolve_codec(fl).device_bytes(spec, fl.max_staleness + 1,
                                          model_shards)
