"""Client samples as one device-resident pool, shared by both engines.

``DevicePool`` is the layout: every client's samples concatenated, client
``cid`` owning rows ``[offsets[cid], offsets[cid] + sizes[cid])``. The
engines stage only batch row numbers and gather ``pool_x[rows]`` inside
their scanned round, so no sample crosses from the host once the pool is
placed.

``place_client_pool`` places the pool of a ``run_vectorized`` call's
clients. A resumed run calls the engine again with the same clients, so
the placement is cached on the identity of the clients' ``x``/``y``
arrays (weakly: a dropped dataset frees its device copy). The arrays are
read when the pool is placed and not again: changing them in place
between runs is not seen.
"""
from __future__ import annotations

import collections
import weakref
from typing import Any, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.synthetic import ClientDataset
from repro.launch.multihost import put_with_sharding

P = jax.sharding.PartitionSpec


class DevicePool(NamedTuple):
    """All clients' samples as one device-resident pool.

    ``x``/``y`` are the concatenated sample arrays; client ``cid`` owns
    rows ``[offsets[cid], offsets[cid] + sizes[cid])``. The pool gather
    for a whole window is one fused op inside the round scan. ``shared``
    overlaps client slices on a small pool — the layout that keeps a
    1e6-client sweep in flat host memory.
    """

    x: Any        # (P, ...) features
    y: Any        # (P,) labels
    offsets: Any  # (N,) i32 first row per client
    sizes: Any    # (N,) i32 rows per client

    @property
    def num_clients(self) -> int:
        return int(self.offsets.shape[0])

    @staticmethod
    def from_clients(clients: Sequence[ClientDataset]) -> "DevicePool":
        """Concatenate per-client datasets (the small-N parity path)."""
        sizes = np.asarray([c.size for c in clients], np.int32)
        offsets = np.concatenate([[0], np.cumsum(sizes)[:-1]]).astype(np.int32)
        return DevicePool(
            x=np.concatenate([np.asarray(c.x) for c in clients]),
            y=np.concatenate([np.asarray(c.y) for c in clients]),
            offsets=offsets, sizes=sizes)

    @staticmethod
    def shared(x: np.ndarray, y: np.ndarray, num_clients: int,
               samples_per_client: int) -> "DevicePool":
        """N overlapping client slices over one fixed pool: O(pool) memory
        independent of N (a prime-stride walk decorrelates neighbors)."""
        total = int(np.asarray(x).shape[0])
        if samples_per_client > total:
            raise ValueError(f"samples_per_client {samples_per_client} "
                             f"exceeds pool size {total}")
        span = total - samples_per_client + 1
        offsets = (np.arange(num_clients, dtype=np.int64) * 7919) % span
        return DevicePool(x=x, y=y, offsets=offsets.astype(np.int32),
                          sizes=np.full(num_clients, samples_per_client,
                                        np.int32))


_PLACED: "collections.OrderedDict[tuple, tuple]" = collections.OrderedDict()
_PLACED_MAX = 4  # sets of clients kept placed at once


def _check_fits(nbytes: int, devices) -> None:
    """Raise if the pool cannot fit in a device's free memory (where the
    backend reports it): the engine has no host path to fall back on."""
    for dev in devices:
        stats = dev.memory_stats()
        if not stats or "bytes_limit" not in stats:
            continue
        free = stats["bytes_limit"] - stats.get("bytes_in_use", 0)
        if nbytes > free:
            raise ValueError(
                f"the clients' samples take {nbytes / 1e9:.3f} GB and "
                f"{dev} has {free / 1e9:.3f} GB free: the engine keeps "
                "every client's samples on the device")


def _place(clients: Sequence[ClientDataset],
           mesh: Optional[Any]) -> Tuple[DevicePool, int]:
    """The pool with ``x``/``y`` on the device (replicated on a mesh,
    process-spanning ones included) and ``offsets``/``sizes`` as host
    int64, which turn a client's batch indices into pool rows; and the
    bytes placed."""
    pool = DevicePool.from_clients(clients)
    x, y = np.asarray(pool.x), np.asarray(pool.y)
    if x.shape[0] >= 2 ** 31:
        raise ValueError(f"{x.shape[0]} samples exceed int32 row numbers")
    nbytes = x.nbytes + y.nbytes
    _check_fits(nbytes, jax.devices()[:1] if mesh is None
                else [d for d in mesh.devices.flat
                      if d.process_index == jax.process_index()])
    if mesh is None:
        dx, dy = jnp.asarray(x), jnp.asarray(y)
    else:
        dx, dy = (put_with_sharding(x, mesh, P()),
                  put_with_sharding(y, mesh, P()))
    return DevicePool(dx, dy, np.asarray(pool.offsets, np.int64),
                      np.asarray(pool.sizes, np.int64)), nbytes


def place_client_pool(clients: Sequence[ClientDataset],
                      mesh: Optional[Any] = None) -> Tuple[DevicePool, int]:
    """The clients' samples as a device pool, and the bytes this call
    placed: 0 where an earlier call placed the same arrays on the same
    mesh. Up to ``_PLACED_MAX`` sets stay placed, each only while every
    one of its arrays is alive."""
    arrays = [a for c in clients for a in (c.x, c.y)]
    key = (mesh, tuple(id(a) for a in arrays))
    entry = _PLACED.get(key)
    if entry is not None and all(r() is a for r, a in zip(entry[0], arrays)):
        _PLACED.move_to_end(key)
        return entry[1], 0
    pool, nbytes = _place(clients, mesh)
    try:
        refs = [weakref.ref(a, lambda _, key=key: _PLACED.pop(key, None))
                for a in arrays]
    except TypeError:  # arrays that take no weak reference stay uncached
        return pool, nbytes
    _PLACED[key] = (refs, pool)
    while len(_PLACED) > _PLACED_MAX:
        _PLACED.popitem(last=False)
    return pool, nbytes
