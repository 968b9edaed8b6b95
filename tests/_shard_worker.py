"""Multi-device parity checks for the sharded round substrate.

Importable check functions (used in-process by tests/test_round_body.py
when the session already has >= 8 devices, e.g. the CI multi-device job)
plus a __main__ that runs them all and prints a JSON error report — the
subprocess entry point the single-device test suite uses with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so the shard_map
paths are exercised everywhere.

Every check compares the mesh-sharded pass against the single-device
pass on identical inputs; differences come only from the eq. 3 psum
summation order, so errors must sit at f32 rounding level.
"""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if __name__ == "__main__":
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import FLConfig  # noqa: E402
from repro.core.cohort import init_cohort_state, make_cohort_step  # noqa: E402
from repro.core.server_pass import (  # noqa: E402
    apply_server_round,
    flatten_stacked,
    flatten_tree,
    make_flat_spec,
)
from repro.launch.mesh import make_round_mesh  # noqa: E402
from repro.models.lenet import init_lenet  # noqa: E402
from repro.sim.engine import init_version_ring, run_vectorized  # noqa: E402


def _quad_loss(params, batch):
    x, y = batch
    return jnp.mean((x @ params["w"] - y) ** 2), {}


def _quad_clients(n=6, size=64, d=4, seed=0):
    from repro.data.synthetic import ClientDataset
    rng = np.random.default_rng(seed)
    w_true = np.arange(1.0, d + 1.0)
    out = []
    for i in range(n):
        x = rng.normal(size=(size, d)).astype(np.float32)
        y = (x @ w_true + 0.05 * rng.normal(size=size)).astype(np.float32)
        out.append(ClientDataset(x=x, y=y, seed=seed + 10 + i))
    return out


def _stack_noisy(params, k, key, scale):
    leaves, treedef = jax.tree.flatten(params)
    out = []
    for i, leaf in enumerate(leaves):
        sub = jax.random.fold_in(key, i)
        noise = scale * jax.random.normal(sub, (k,) + leaf.shape, jnp.float32)
        out.append(leaf[None].astype(jnp.float32) + noise)
    return jax.tree.unflatten(treedef, out)


def server_pass_errors(params, mesh, fl, mode, k=8, seed=0,
                       default_block=False):
    """Max |sharded - single| over new params / eq. 3 dists / weights.

    ``default_block=True`` passes block_n=0 to apply_server_round — the
    documented public default, which must derive a PER-SHARD-valid tile
    (regression: it used to pick from the global padded length).
    """
    key = jax.random.PRNGKey(seed)
    bases = _stack_noisy(params, k, jax.random.fold_in(key, 1), 0.1)
    deltas = _stack_noisy(params, k, jax.random.fold_in(key, 2), 0.01)
    losses = jnp.linspace(0.5, 2.0, k)
    sizes = jnp.linspace(10.0, 50.0, k)
    taus = jnp.arange(k, dtype=jnp.float32)

    def run(mesh_):
        spec = make_flat_spec(params, mesh=mesh_)
        new_x, info = apply_server_round(
            flatten_tree(spec, params), flatten_stacked(spec, bases),
            flatten_stacked(spec, deltas), losses, sizes, taus, fl,
            mode=mode, block_n=0 if default_block else spec.block_n,
            interpret=True, mesh=mesh_)
        return new_x[:spec.n], info

    ref_x, ref_info = run(None)
    got_x, got_info = run(mesh)
    return {
        "new_x": float(jnp.max(jnp.abs(got_x - ref_x))),
        "sq_dists": float(jnp.max(jnp.abs(
            got_info["sq_dists"] - ref_info["sq_dists"]))),
        "weights": float(jnp.max(jnp.abs(
            got_info["weights"] - ref_info["weights"]))),
    }


def engine_errors(mesh, rounds=6):
    """Sharded run_vectorized vs single-device: same windows, same maths."""
    fl = FLConfig(num_clients=6, buffer_size=2, local_steps=2, local_lr=0.05,
                  batch_size=8, max_staleness=4)
    eval_fn = lambda p: {"wnorm": float(jnp.sum(p["w"] ** 2))}  # noqa: E731
    runs = {}
    for name, m in (("single", None), ("sharded", mesh)):
        runs[name] = run_vectorized(
            _quad_loss, {"w": jnp.zeros(4)}, _quad_clients(), fl,
            total_rounds=rounds, eval_fn=eval_fn, eval_every=2, seed=0,
            mesh=m)
    ref, got = runs["single"], runs["sharded"]
    assert [l["clients"] for l in ref.round_log] == \
           [l["clients"] for l in got.round_log]
    assert [h["round"] for h in ref.history] == \
           [h["round"] for h in got.history]
    werr = max(float(np.max(np.abs(np.asarray(a["weights"])
                                   - np.asarray(b["weights"]))))
               for a, b in zip(ref.round_log, got.round_log))
    herr = max(abs(a["wnorm"] - b["wnorm"])
               for a, b in zip(ref.history, got.history))
    return {"weights": werr, "history_wnorm": herr,
            "num_launches": got.num_launches}


def ring_errors(mesh, rounds=6):
    """Flat-SHARDED version ring vs flat replicated ring on the SAME mesh.

    Only the ring's device placement differs (P(None, "model") slices vs
    replicated rows); the compiled round is identical, so the engine
    results must be BIT-identical — plus the per-device footprint
    contract: each device holds R * ceil(Np_pad / model_shards) * 4
    bytes of ring (the layout that makes a deep ring pod-viable)."""
    fl = FLConfig(num_clients=6, buffer_size=2, local_steps=2, local_lr=0.05,
                  batch_size=8, max_staleness=4)
    eval_fn = lambda p: {"wnorm": float(jnp.sum(p["w"] ** 2))}  # noqa: E731
    runs = {}
    for name, shard in (("replicated", False), ("sharded", True)):
        runs[name] = run_vectorized(
            _quad_loss, {"w": jnp.zeros(4)}, _quad_clients(), fl,
            total_rounds=rounds, eval_fn=eval_fn, eval_every=2, seed=0,
            mesh=mesh, shard_ring=shard)
    ref, got = runs["replicated"], runs["sharded"]
    w_bits = max(float(np.max(np.abs(np.asarray(a["weights"])
                                     - np.asarray(b["weights"]))))
                 for a, b in zip(ref.round_log, got.round_log))
    h_bits = max(abs(a["wnorm"] - b["wnorm"])
                 for a, b in zip(ref.history, got.history))

    # footprint: lenet-sized ring, every addressable shard one model slice
    lenet = init_lenet(jax.random.PRNGKey(0))
    spec, ring = init_version_ring(lenet, fl, mesh=mesh)
    depth = fl.max_staleness + 1
    expect = depth * (-(-spec.n_padded // spec.model_shards)) * 4
    byte_err = max(abs(sh.data.nbytes - expect)
                   for sh in ring.addressable_shards)
    return {"ring_weights_bits": w_bits, "ring_history_bits": h_bits,
            "ring_bytes_err": float(byte_err),
            "per_device_ring_bytes": expect}


def population_errors(mesh, rounds=8):
    """Sharded population engine vs single-device on identical streams.

    Counter draws are keyed by (cid, attempt), not by device placement,
    so sharding the (N,) client-state arrays over the data axis must not
    change a single window: cids/taus/slots are compared EXACTLY, upload
    times and round maths at f32 rounding level."""
    from repro.sim import get_scenario
    from repro.sim.population import collect_windows, run_population

    sc = get_scenario("dropout-bernoulli")
    n, k, t = 8, 4, 10
    fl = FLConfig(num_clients=n, buffer_size=k, local_steps=2,
                  local_lr=0.05, batch_size=8, max_staleness=4)
    ref = collect_windows(sc, n, fl, t, seed=3)
    got = collect_windows(sc, n, fl, t, seed=3, mesh=mesh)
    meta_err = 0.0 if (np.array_equal(ref["clients"], got["clients"])
                       and np.array_equal(ref["tau"], got["tau"])
                       and np.array_equal(ref["slots"], got["slots"])
                       and ref["num_events"] == got["num_events"]) else 1.0
    t_err = float(np.max(np.abs(ref["t"] - got["t"])))

    fl6 = FLConfig(num_clients=6, buffer_size=2, local_steps=2,
                   local_lr=0.05, batch_size=8, max_staleness=4)
    eval_fn = lambda p: {"wnorm": float(jnp.sum(p["w"] ** 2))}  # noqa: E731
    runs = {}
    for name, m in (("single", None), ("sharded", mesh)):
        runs[name] = run_population(
            _quad_loss, {"w": jnp.zeros(4)}, _quad_clients(), fl6,
            total_rounds=rounds, eval_fn=eval_fn, eval_every=2,
            scenario=sc, seed=0, mesh=m)
    ref_r, got_r = runs["single"], runs["sharded"]
    assert [l["clients"] for l in ref_r.round_log] == \
           [l["clients"] for l in got_r.round_log]
    assert [l["tau"] for l in ref_r.round_log] == \
           [l["tau"] for l in got_r.round_log]
    werr = max(float(np.max(np.abs(np.asarray(a["weights"])
                                   - np.asarray(b["weights"]))))
               for a, b in zip(ref_r.round_log, got_r.round_log))
    herr = max(abs(a["wnorm"] - b["wnorm"])
               for a, b in zip(ref_r.history, got_r.history))
    return {"win_meta": meta_err, "win_t": t_err,
            "pop_weights": werr, "pop_wnorm": herr}


def cohort_errors(mesh, cohort=4, seed=0):
    """Sharded make_cohort_step vs single-device on one quad round."""
    fl = FLConfig(buffer_size=cohort, local_steps=2, local_lr=0.1,
                  weighting="paper")
    params = {"w": jnp.array([1.0, -1.0, 0.5, 2.0])}
    key = jax.random.PRNGKey(seed)

    def quad_batch(k_):
        k1, k2 = jax.random.split(k_)
        x = jax.random.normal(k1, (8, 4))
        y = x @ jnp.arange(1.0, 5.0) + 0.01 * jax.random.normal(k2, (8,))
        return x, y

    batch = {
        "local": jax.tree.map(
            lambda *xs: jnp.stack(xs).reshape(cohort, 2, 4, *xs[0].shape[1:]),
            *[quad_batch(jax.random.fold_in(key, i)) for i in range(cohort)]),
        "probe": jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[quad_batch(jax.random.fold_in(key, 100 + i))
              for i in range(cohort)]),
        "arrival": jnp.array([1.0] * (cohort - 1) + [0.0]),  # one straggler
        "data_sizes": jnp.linspace(10.0, 40.0, cohort),
    }
    outs = {}
    for name, m in (("single", None), ("sharded", mesh)):
        step = make_cohort_step(_quad_loss, fl, mesh=m)
        state = init_cohort_state(params, cohort)
        new_state, mets = step(state, batch)
        outs[name] = (new_state, mets)
    ref_s, ref_m = outs["single"]
    got_s, got_m = outs["sharded"]
    return {
        "global": float(jnp.max(jnp.abs(ref_s.global_params["w"]
                                        - got_s.global_params["w"]))),
        "client_params": float(max(
            jnp.max(jnp.abs(a - b)) for a, b in
            zip(jax.tree.leaves(ref_s.client_params),
                jax.tree.leaves(got_s.client_params)))),
        "metrics": float(max(abs(float(ref_m[k_]) - float(got_m[k_]))
                             for k_ in ref_m)),
    }


def run_all():
    assert len(jax.devices()) >= 8, len(jax.devices())
    mesh_m8 = make_round_mesh(data=1, model=8)
    mesh_d2m4 = make_round_mesh(data=2, model=4)
    fl = FLConfig(weighting="paper")
    report = {"devices": len(jax.devices())}
    # acceptance gate: lenet_fmnist flat pass, 8-way model sharding
    lenet = init_lenet(jax.random.PRNGKey(0))
    for mode in ("reference", "fused"):
        report[f"lenet_pass_{mode}"] = server_pass_errors(
            lenet, mesh_m8, fl, mode)
    report["lenet_pass_d2m4"] = server_pass_errors(lenet, mesh_d2m4, fl,
                                                   "reference")
    # block_n=0 default on a tiny tree: per-shard tile must stay valid
    report["small_pass_default_block"] = server_pass_errors(
        {"w": jnp.linspace(-1.0, 1.0, 100)}, mesh_m8, fl, "fused", k=4,
        default_block=True)
    report["engine"] = engine_errors(mesh_d2m4)
    report["cohort"] = cohort_errors(mesh_d2m4)
    # population engine: data-axis-sharded client state, exact windows
    report["population"] = population_errors(mesh_d2m4)
    # sharded-ring vs replicated-ring: bit parity + per-device footprint
    report["ring"] = ring_errors(mesh_d2m4)
    report["ring_m8"] = ring_errors(mesh_m8)
    return report


if __name__ == "__main__":
    import json
    print(json.dumps(run_all()))
