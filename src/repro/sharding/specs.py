"""PartitionSpec rules for every parameter / state / batch pytree.

Sharding scheme (mesh axes: optional "pod", "data", "model"):

* Tensor parallelism over ``model``: attention QKV/O, MLP up/down, SSM
  in/out projections, MoE experts (expert-parallel on the E axis), and the
  embedding/LM head (vocab-sharded when divisible, else d-sharded).
* ``data`` carries FL cohort slots (replicated-client mode) or FSDP
  (distributed-client mode: the largest not-yet-sharded dim of each large
  weight is sharded over ``data``).
* ``pod`` is a second data-parallel tier (more cohort slots / batch).

Rules are name+shape driven over pytree key-paths; specs are padded on the
left with None for stacking axes (layer stack L, cohort stack C, expert E),
and every sharded dim is checked for divisibility — falling back to
replication rather than producing an invalid spec (the fallback is logged
via ``collect_fallbacks``).
"""
from __future__ import annotations

from typing import Any, List, Optional, Tuple

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

MODEL_AXIS = "model"
DATA_AXIS = "data"


def _axis_size(mesh, name) -> int:
    if name is None:
        return 1
    if isinstance(name, tuple):
        return int(np.prod([_axis_size(mesh, n) for n in name]))
    return int(dict(zip(mesh.axis_names, mesh.devices.shape)).get(name, 1))


def mesh_axis_size(mesh, name) -> int:
    """Size of mesh axis ``name`` (1 when absent or ``mesh`` is None)."""
    if mesh is None:
        return 1
    return _axis_size(mesh, name)


# ---------------------------------------------------------------------------
# sharded round substrate (DESIGN.md §5)
# ---------------------------------------------------------------------------
# The mesh-sharded round (core/round_body.py + core/server_pass.py) works on
# two layouts: the padded flat f32 parameter vector, partitioned over the
# ``model`` axis, and K-client stacked pytrees, partitioned over ``data``.


def flat_param_pspec() -> P:
    """(Np,) padded flat parameter vector: partitioned over ``model``."""
    return P(MODEL_AXIS)


def flat_stacked_pspec() -> P:
    """(K, Np) stacked flat bases/deltas: K replicated, Np over ``model``."""
    return P(None, MODEL_AXIS)


def ring_pspec() -> P:
    """(R, Np) flat version ring: versions replicated, Np over ``model``.

    The engine's version ring stores each of the R retained versions as a
    ``ShardedFlatSpec`` padded flat row (DESIGN.md §6), so per device the
    ring costs ``R * n_padded / model_shards`` floats instead of R full
    replicas — the layout that makes a deep ring pod-viable. Same layout
    as ``flat_stacked_pspec`` (leading axis replicated, flat dim over
    ``model``) — delegate so the two can never drift.
    """
    return flat_stacked_pspec()


def ring_codes_pspec() -> P:
    """(R, Np) int8 codewords of the compressed version ring.

    The ``int8`` codec (core/version_store.py, DESIGN.md §11) stores each
    ring row as Np codewords on the SAME flat layout as the f32 ring, so
    the codeword matrix shards exactly like it: versions replicated, the
    flat dim over ``model``. Delegates to ``flat_stacked_pspec`` so the
    compressed and identity layouts can never drift.
    """
    return flat_stacked_pspec()


def ring_scales_pspec() -> P:
    """(R, Np // qblock) per-block scale/zero arrays: blocks over ``model``.

    ``resolve_qblock`` guarantees the quantization block divides the
    per-shard tile, so the block axis partitions evenly over ``model``
    and every device holds exactly the (scale, zero) columns its codeword
    slice needs — the fused dequantize-distance kernel never reads a
    remote scale.
    """
    return flat_stacked_pspec()


def kclient_pspec() -> P:
    """(K, ...) client-stacked leaves: K over ``data``, rest replicated.

    Used as a pytree-prefix spec: trailing (unmentioned) dims replicate.
    """
    return P(DATA_AXIS)


def client_state_pspec() -> P:
    """(N,) per-client population state/statics: N over ``data``.

    The device-resident scenario engine (``sim/population.py``) keeps the
    whole client state machine as ``(N,)``-leading arrays; sharding them
    over ``data`` is what lets a process-spanning mesh materialize only
    its addressable shard of a million-client population (no host event
    walk to replay). Callers fall back to replication when N does not
    divide the data-axis size.
    """
    return P(DATA_AXIS)


def info_pspec() -> P:
    """(K,) per-round info arrays (weights, sq_dists, ...): replicated.

    This is a multi-host CONTRACT, not just a layout: the round's info
    outputs are pinned fully replicated so every process can read the
    round log from its own addressable shards (DESIGN.md §7) — the
    engine never issues a ``jax.device_get`` on a non-addressable array.
    ``core/server_pass.py`` enforces it with a sharding constraint on the
    mesh path.
    """
    return P()


def _div(dim: int, size: int) -> bool:
    return size > 0 and dim % size == 0


def _keystr(path) -> str:
    return jax.tree_util.keystr(path)


def _base_rule(name: str, path: str, shape: Tuple[int, ...], mesh,
               fsdp: bool) -> List[Optional[Any]]:
    """Spec for the TRAILING dims of a leaf (left-padding added later).

    Returns a list of axis assignments for the last ``len(spec)`` dims.
    """
    msize = _axis_size(mesh, MODEL_AXIS)
    dsize = _axis_size(mesh, DATA_AXIS)
    in_moe = ("'moe'" in path or ".moe" in path) and "'shared'" not in path
    is_expert = in_moe and name in ("w_gate", "w_up", "w_down")

    if name == "embed":
        v, d = shape[-2], shape[-1]
        if _div(v, msize * (dsize if fsdp else 1)) and fsdp:
            return [(MODEL_AXIS, DATA_AXIS), None]
        if _div(v, msize):
            return [MODEL_AXIS, DATA_AXIS if (fsdp and _div(d, dsize)) else None]
        return [None, MODEL_AXIS if _div(d, msize) else None]
    if name == "lm_head":
        d, v = shape[-2], shape[-1]
        if _div(v, msize):
            return [DATA_AXIS if (fsdp and _div(d, dsize)) else None, MODEL_AXIS]
        return [MODEL_AXIS if _div(d, msize) else None, None]
    if name == "projector":
        return [None, MODEL_AXIS if _div(shape[-1], msize) else None]
    if is_expert:
        # (E, d, ff) or (E, ff, d): expert-parallel over model
        e = shape[-3]
        return [MODEL_AXIS if _div(e, msize) else None,
                DATA_AXIS if (fsdp and _div(shape[-2], dsize)) else None,
                None]
    if name == "router":
        return [None, None]
    # MLA: wkv_a (d, kv_lora_rank + rope) and wkv_b (kv_lora_rank,
    # H * (nope + v)) are column-parallel like wq; kv_norm replicates
    if name in ("wq", "wk", "wv", "wkv_a", "wkv_b", "w_gate", "w_up",
                "in_proj", "dt_proj"):
        out_ok = _div(shape[-1], msize)
        in_ok = fsdp and _div(shape[-2], dsize)
        return [DATA_AXIS if in_ok else None, MODEL_AXIS if out_ok else None]
    if name in ("wo", "w_down", "out_proj", "x_proj"):
        in_ok = _div(shape[-2], msize)
        out_ok = fsdp and _div(shape[-1], dsize)
        return [MODEL_AXIS if in_ok else None, DATA_AXIS if out_ok else None]
    if name in ("bq", "bk", "bv", "b_up"):
        return [MODEL_AXIS if _div(shape[-1], msize) else None]
    if name == "conv_w":
        return [None, MODEL_AXIS if _div(shape[-1], msize) else None]
    if name in ("conv_b", "dt_bias", "D"):
        return [MODEL_AXIS if _div(shape[-1], msize) else None]
    if name == "A_log":
        return [MODEL_AXIS if _div(shape[-2], msize) else None, None]
    # norms, scalar-ish leaves: replicated
    return [None] * min(len(shape), 1)


def _leaf_spec(path, leaf, mesh, fsdp: bool, extra_leading: int = 0) -> P:
    """Build the full PartitionSpec for one leaf."""
    shape = tuple(leaf.shape)
    keys = [k.key for k in path if hasattr(k, "key")]
    name = keys[-1] if keys else ""
    pathstr = _keystr(path)
    trailing = _base_rule(name, pathstr, shape, mesh, fsdp)
    trailing = trailing[-len(shape):] if len(trailing) > len(shape) else trailing
    pad = len(shape) - len(trailing)
    spec = [None] * pad + list(trailing)
    # cohort stacking axis (client dim) handled by cohort_state_pspecs
    for _ in range(extra_leading):
        spec = [None] + spec
    return P(*spec)


def param_pspecs(params_shape: Any, mesh, fsdp: bool = False) -> Any:
    """PartitionSpec pytree matching ``params_shape`` (arrays or SDS)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_shape)
    specs = [_leaf_spec(p, l, mesh, fsdp) for p, l in flat]
    return jax.tree_util.tree_unflatten(treedef, specs)


def cohort_state_pspecs(state_shape: Any, mesh, fsdp: bool = False,
                        client_axes=(DATA_AXIS,)) -> Any:
    """Specs for CohortState: client-stacked pytrees get the client dim
    sharded over the data(+pod) axes; global params are TP-only."""
    from repro.core.cohort import CohortState

    client_axis = client_axes if len(client_axes) > 1 else client_axes[0]

    def stacked_spec(tree):
        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        specs = []
        for p, l in flat:
            inner = _leaf_spec(p, jax.ShapeDtypeStruct(l.shape[1:], l.dtype),
                               mesh, False)
            specs.append(P(client_axis, *inner))
        return jax.tree_util.tree_unflatten(treedef, specs)

    return CohortState(
        global_params=param_pspecs(state_shape.global_params, mesh, fsdp),
        client_params=stacked_spec(state_shape.client_params),
        client_base=stacked_spec(state_shape.client_base),
        client_version=P(client_axis),
        version=P(),
    )


def dist_state_pspecs(state_shape: Any, mesh) -> Any:
    """Specs for DistFLState (FSDP x TP params + same-sharded accumulator)."""
    from repro.core.cohort import DistFLState

    pspec = param_pspecs(state_shape.global_params, mesh, fsdp=True)
    return DistFLState(
        global_params=pspec,
        accum=pspec,
        v_buf=P(),
        count=P(),
        version=P(),
        update_norm_ring=P(),
    )


def batch_pspecs(batch_shape: Any, batch_axes=(DATA_AXIS,)) -> Any:
    """Shard the leading (batch/cohort) dim of every batch leaf."""
    ax = batch_axes if len(batch_axes) > 1 else batch_axes[0]

    def leaf(l):
        if l.ndim == 0:
            return P()
        return P(ax, *([None] * (l.ndim - 1)))

    return jax.tree.map(leaf, batch_shape)


def cache_pspecs(cache_shape: Any, mesh, batch_axes=(DATA_AXIS,),
                 batch_size: int = 0) -> Any:
    """KV/SSM cache specs: batch dim over data(+pod) when divisible, the
    head_dim / d_inner dim over model when divisible.

    Cache leaves (from init_stack_cache): leading L, then
      kv k/v : (L, B, len, Hkv, hd)   -> (None, B_ax, None, None, model)
      ssm conv: (L, B, K-1, di)       -> (None, B_ax, None, model)
      ssm h  : (L, B, di, N)          -> (None, B_ax, model, None)
      cross k/v: (L, B, S_enc, Hkv, hd) same as kv
    """
    msize = _axis_size(mesh, MODEL_AXIS)
    bsize = int(np.prod([_axis_size(mesh, a) for a in batch_axes]))
    b_ax = batch_axes if len(batch_axes) > 1 else batch_axes[0]

    def leaf_spec(path, l):
        keys = [k.key for k in path if hasattr(k, "key")]
        shape = l.shape
        b_ok = len(shape) >= 2 and shape[1] % bsize == 0
        bspec = b_ax if b_ok else None
        if "kv" in keys or "cross" in keys:
            hd_ok = shape[-1] % msize == 0
            return P(None, bspec, None, None, MODEL_AXIS if hd_ok else None)
        if keys[-1] == "conv":
            return P(None, bspec, None, MODEL_AXIS if shape[-1] % msize == 0 else None)
        if keys[-1] == "h":
            return P(None, bspec, MODEL_AXIS if shape[-2] % msize == 0 else None, None)
        return P(*([None] * len(shape)))

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_shape)
    return jax.tree_util.tree_unflatten(treedef,
                                        [leaf_spec(p, l) for p, l in flat])
