"""Production mesh builders.

Target hardware: TPU v5e pods — 256 chips/pod, (data=16, model=16) within a
pod; the multi-pod mesh adds a leading DCN-mapped "pod" axis (2 pods = 512
chips). Defined as FUNCTIONS so importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before any jax import).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Small mesh over whatever devices exist (tests / CPU smoke)."""
    n = len(jax.devices())
    assert n % model_parallel == 0
    return jax.make_mesh((n // model_parallel, model_parallel),
                         ("data", "model"))


def make_round_mesh(data: int = 1, model: int = 0):
    """(data, model) mesh for the sharded round substrate (DESIGN.md §5).

    ``data`` carries the K-client cohort slots, ``model`` the padded flat
    parameter vector AND the engine's (R, Np) flat version ring
    (``sharding/specs.ring_pspec``: R * Np / model per-device floats,
    DESIGN.md §6). ``model=0`` spreads all remaining devices on the
    model axis. Unlike ``make_host_mesh`` this does not require using
    every device — a sweep over device counts can pin subsets of the
    forced-host-device pool.

    In a multi-process session (``jax.distributed`` initialized via
    ``launch/multihost.initialize``) this delegates to
    ``multihost.make_round_mesh``, which lays the data axis across
    processes so the model-axis collectives stay intra-host (DESIGN.md
    §7 — the bit-parity layout).
    """
    import numpy as np
    from jax.sharding import Mesh

    if jax.process_count() > 1:
        from repro.launch.multihost import make_round_mesh as _mh_mesh
        # the single-host default data=1 means "no data parallelism";
        # multi-host needs data % process_count == 0, so map it to
        # multihost's own default (one data row per process)
        return _mh_mesh(data=0 if data <= 1 else data, model=model)
    devices = jax.devices()
    if model == 0:
        model = max(1, len(devices) // data)
    need = data * model
    if need > len(devices):
        raise ValueError(f"mesh ({data}, {model}) needs {need} devices, "
                         f"have {len(devices)}")
    devs = np.asarray(devices[:need]).reshape(data, model)
    return Mesh(devs, ("data", "model"))


def batch_axes_for(mesh) -> tuple:
    """The data-parallel axes of a mesh (cohort/batch sharding)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
