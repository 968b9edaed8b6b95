"""The shared round body (core/round_body.py) and the mesh-sharded round
substrate (DESIGN.md §5): engine==cohort agreement through the single
implementation, ShardedFlatSpec padding, and multi-device parity of the
sharded pass against the single-device path (in-process when the session
has >= 8 devices — the CI multi-device job — else via a subprocess with
8 forced host devices)."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FLConfig
from repro.core.client import make_local_update_fn
from repro.core.cohort import (
    init_cohort_state,
    init_dist_state,
    make_cohort_step,
    make_dist_step,
)
from repro.core.round_body import make_ring_round, make_round_body
from repro.core.server_pass import (
    FlatSpec,
    ShardedFlatSpec,
    apply_server_round,
    flatten_stacked,
    flatten_tree,
    make_flat_spec,
    unflatten_like,
    unflatten_stacked,
)
from repro.core.weighting import POLICIES

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _quad_loss(params, batch):
    x, y = batch
    return jnp.mean((x @ params["w"] - y) ** 2), {}


def _quad_batch(key, n=8, d=4):
    k1, k2 = jax.random.split(key)
    x = jax.random.normal(k1, (n, d))
    y = x @ jnp.arange(1.0, d + 1.0) + 0.01 * jax.random.normal(k2, (n,))
    return x, y


def _round_inputs(k=3, steps=2, seed=0):
    key = jax.random.PRNGKey(seed)
    params = {"w": jnp.array([1.0, -1.0, 0.5, 2.0])}
    local = jax.tree.map(
        lambda *xs: jnp.stack(xs).reshape(k, steps, -1, *xs[0].shape[1:])
        if xs[0].ndim > 1 else jnp.stack(xs).reshape(k, steps, -1),
        *[_quad_batch(jax.random.fold_in(key, i), n=steps * 4)
          for i in range(k)])
    probe = jax.tree.map(
        lambda *xs: jnp.stack(xs),
        *[_quad_batch(jax.random.fold_in(key, 100 + i)) for i in range(k)])
    sizes = jnp.linspace(10.0, 30.0, k)
    taus = jnp.arange(k, dtype=jnp.float32)
    return params, local, probe, sizes, taus


FL = FLConfig(buffer_size=3, local_steps=2, local_lr=0.05, weighting="paper")


class TestSharedRoundBody:
    """engine path == cohort path through the ONE round implementation."""

    def test_engine_and_cohort_paths_agree(self):
        """With fresh slots (client_params == pulled base) the cohort path
        must reproduce the engine path: identical new_params and info."""
        params, local, probe, sizes, taus = _round_inputs()
        body = make_round_body(_quad_loss, FL)
        bases = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (3,) + x.shape),
                             params)
        new_e, end_e, info_e = body(params, bases, local, probe, sizes, taus)
        new_c, end_c, info_c = body(params, bases, local, probe, sizes, taus,
                                    client_params=bases,
                                    arrival_mask=jnp.ones(3))
        assert end_e is None and end_c is not None
        np.testing.assert_allclose(np.asarray(new_e["w"]),
                                   np.asarray(new_c["w"]),
                                   rtol=1e-5, atol=1e-6)
        assert set(info_e) == set(info_c)
        for k_ in info_e:
            np.testing.assert_allclose(np.asarray(info_e[k_]),
                                       np.asarray(info_c[k_]),
                                       rtol=1e-5, atol=1e-6, err_msg=k_)

    def test_cohort_step_matches_ring_round(self):
        """The shared-round fixture: one make_cohort_step round (all slots
        arrive, fresh bases) == one engine ring round on the same inputs —
        same new global params AND same info/round-log quantities."""
        params, local, probe, sizes, taus = _round_inputs()
        k = 3

        # engine side: depth-1 FLAT ring holding x^t, everyone pulls slot 0
        ring_round = make_ring_round(_quad_loss, FL)
        spec = make_flat_spec(params)
        ring = flatten_tree(spec, params)[None] * 1
        new_p, new_ring, info = ring_round(
            params, ring, jnp.zeros(k, jnp.int32), local, probe, sizes,
            jnp.zeros(k, jnp.float32), jnp.int32(0))

        # cohort side: same batches through the compiled cohort state machine
        step = make_cohort_step(_quad_loss, FL)
        state = init_cohort_state(params, k)
        batch = {"local": local, "probe": probe, "arrival": jnp.ones(k),
                 "data_sizes": sizes}
        new_state, mets = step(state, batch)

        np.testing.assert_allclose(np.asarray(new_p["w"]),
                                   np.asarray(new_state.global_params["w"]),
                                   rtol=1e-5, atol=1e-6)
        # the flat ring write holds the same new params (row 0 = new x')
        np.testing.assert_allclose(np.asarray(new_ring[0][:spec.n]),
                                   np.asarray(new_p["w"]), rtol=1e-6)
        np.testing.assert_allclose(float(jnp.mean(info["fresh_loss"])),
                                   float(mets["fresh_loss_mean"]), rtol=1e-5)
        np.testing.assert_allclose(float(jnp.min(info["staleness"])),
                                   float(mets["staleness_min"]), rtol=1e-5)
        np.testing.assert_allclose(float(jnp.max(info["weights"])),
                                   float(mets["weights_max"]), rtol=1e-5)

    def test_flat_ring_write_is_dtype_faithful(self):
        """Non-f32 params: the ring row must hold exactly the values
        clients receive, so a fresh (tau=0) client's eq. 3 distance is
        exactly 0 (the write re-flattens the dtype-cast tree)."""
        params = {"w": jnp.array([1.0, -1.0, 0.5, 2.0], jnp.bfloat16)}
        _, local, probe, sizes, _ = _round_inputs()
        ring_round = make_ring_round(_quad_loss, FL)
        spec = make_flat_spec(params)
        ring = flatten_tree(spec, params)[None] * 1
        zeros = jnp.zeros(3, jnp.float32)
        p1, ring, _ = ring_round(params, ring, jnp.zeros(3, jnp.int32),
                                 local, probe, sizes, zeros, jnp.int32(0))
        assert jax.tree.leaves(p1)[0].dtype == jnp.bfloat16
        _, _, info = ring_round(p1, ring, jnp.zeros(3, jnp.int32), local,
                                probe, sizes, zeros, jnp.int32(0))
        np.testing.assert_array_equal(np.asarray(info["sq_dists"]), 0.0)

    def test_non_dividing_k_warns_and_falls_back(self):
        """K not divisible by the data axis degrades to the plain vmap —
        but loudly, naming K and the shard count."""
        params, local, probe, sizes, taus = _round_inputs()  # K = 3
        bases = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (3,) + x.shape), params)
        body = make_round_body(_quad_loss, FL, mesh=_FakeMesh(data=2, model=1))
        with pytest.warns(RuntimeWarning, match="do not divide the data"):
            got, _, _ = body(params, bases, local, probe, sizes, taus)
        ref, _, _ = make_round_body(_quad_loss, FL)(
            params, bases, local, probe, sizes, taus)
        np.testing.assert_allclose(np.asarray(got["w"]), np.asarray(ref["w"]),
                                   rtol=1e-6)

    def test_straggler_semantics_preserved(self):
        """The refactored cohort still carries straggler progress (the
        behaviour its old in-module round implemented)."""
        fl = FLConfig(buffer_size=1, local_steps=1, local_lr=0.1,
                      weighting="paper")
        params = {"w": jnp.zeros(4)}
        state = init_cohort_state(params, 2)
        step = jax.jit(make_cohort_step(_quad_loss, fl))
        key = jax.random.PRNGKey(0)
        batch = {
            "local": jax.tree.map(
                lambda x: jnp.broadcast_to(x, (2, 1) + x.shape),
                _quad_batch(key)),
            "probe": jax.tree.map(
                lambda x: jnp.broadcast_to(x, (2,) + x.shape),
                _quad_batch(jax.random.fold_in(key, 9))),
            "arrival": jnp.array([1.0, 0.0]),
            "data_sizes": jnp.ones(2),
        }
        s1, _ = step(state, batch)
        w_stale = np.asarray(jax.tree.leaves(s1.client_params)[0][1])
        w_base = np.asarray(jax.tree.leaves(s1.client_base)[0][1])
        assert not np.allclose(w_stale, w_base)  # progress carried


class TestStreamingRoundBody:
    """The streaming (distributed-client) entry shape vs the exact
    flat-vector path on identical inputs. Before the fix, the dist step
    carried its own weighting (``paper``: v = p * d, NO ``s_min`` cap;
    ``normalize`` ignored), so these fail on the pre-fix code."""

    @pytest.mark.parametrize("policy", list(POLICIES))
    @pytest.mark.parametrize("normalize", ["mean", "none"])
    def test_dist_step_matches_exact_path(self, policy, normalize):
        """K sequential dist steps (staleness 0..max against a seeded
        update-norm ring) == one exact ``apply_server_round`` fed bases
        whose eq. 3 distances equal the ring distances. The fill holds a
        fresh (tau=0) upload, so the streaming form's pinned reference
        equals the buffer min and parity is exact, cap included."""
        k = 4
        fl = FLConfig(buffer_size=k, local_steps=1, local_lr=0.1,
                      weighting=policy, normalize=normalize, global_lr=1.0,
                      max_staleness=k)
        params = {"w": jnp.array([1.0, -1.0, 0.5, 2.0])}
        norm_ring = jnp.array([0.3, 0.2, 0.1, 0.05])
        state = init_dist_state(params, fl)._replace(
            update_norm_ring=norm_ring)
        step = jax.jit(make_dist_step(_quad_loss, fl))
        local_update = make_local_update_fn(_quad_loss, fl.local_steps,
                                            fl.local_lr, fl.local_momentum)
        taus = [0, 1, 2, 3]
        sizes = [10.0, 20.0, 30.0, 40.0]
        key = jax.random.PRNGKey(0)
        deltas, losses = [], []
        for i in range(k):
            b = _quad_batch(jax.random.fold_in(key, i))
            pb = _quad_batch(jax.random.fold_in(key, 100 + i))
            stacked = jax.tree.map(lambda x: x[None], b)
            batch = {"local": stacked, "probe": pb,
                     "tau": jnp.int32(taus[i]),
                     "data_size": jnp.float32(sizes[i])}
            deltas.append(local_update(params, stacked)[0])
            losses.append(_quad_loss(params, pb)[0])
            state, mets = step(state, batch)
        assert int(mets["applied"]) == 1
        assert int(mets["buffered"]) == k  # pre-apply fill count

        # exact path: bases crafted so ||x - b_i||^2 == the ring distance
        dists = np.array([float(jnp.sum(norm_ring[:t])) for t in taus])
        spec = make_flat_spec(params)
        x = flatten_tree(spec, params)
        onehot = jnp.eye(spec.n_padded)[:k]
        bases = x[None] - jnp.sqrt(jnp.asarray(dists, jnp.float32))[:, None] \
            * onehot
        deltas_flat = flatten_stacked(
            spec, jax.tree.map(lambda *xs: jnp.stack(xs), *deltas))
        new_x, info = apply_server_round(
            x, bases, deltas_flat, jnp.asarray(losses, jnp.float32),
            jnp.asarray(sizes, jnp.float32),
            jnp.asarray(taus, jnp.float32), fl,
            mode="reference", block_n=spec.block_n)
        expect = unflatten_like(spec, new_x, params)
        np.testing.assert_allclose(np.asarray(state.global_params["w"]),
                                   np.asarray(expect["w"]),
                                   rtol=2e-5, atol=1e-6)

    def test_s_min_caps_stale_weight(self):
        """The bugfix itself: a hugely stale upload's streaming weight is
        bounded by P / s_min — it can no longer dominate unboundedly."""
        fl = FLConfig(buffer_size=2, local_steps=1, local_lr=0.1,
                      weighting="paper", max_staleness=4)
        params = {"w": jnp.array([1.0, -1.0, 0.5, 2.0])}
        state = init_dist_state(params, fl)._replace(
            update_norm_ring=jnp.array([1e6, 0.0, 0.0, 0.0]))
        step = jax.jit(make_dist_step(_quad_loss, fl))
        key = jax.random.PRNGKey(1)
        b = _quad_batch(key)
        vs = []
        for tau in (0, 1):  # same data, same probe: only staleness differs
            batch = {"local": jax.tree.map(lambda x: x[None], b),
                     "probe": _quad_batch(jax.random.fold_in(key, 9)),
                     "tau": jnp.int32(tau), "data_size": jnp.float32(10.0)}
            state, mets = step(state, batch)
            vs.append(float(mets["v_weight"]))
        assert vs[1] / vs[0] <= 1.0 / fl.s_min * 1.01  # capped at P/s_min
        assert vs[1] > vs[0]  # the paper's literal read still up-weights

    def test_unknown_normalize_raises_at_build(self):
        """The streaming path must reject bad normalize strings exactly
        like contribution_weights does on the exact paths — not silently
        fall through to 'none' semantics."""
        with pytest.raises(ValueError, match="normalize"):
            make_dist_step(_quad_loss, FLConfig(normalize="typo"))

    def test_flat_ring_roundtrip(self):
        """unflatten_stacked inverts flatten_stacked on the ring layout."""
        tree = {"a": jnp.arange(7.0), "b": jnp.ones((3, 5), jnp.bfloat16)}
        spec = make_flat_spec(tree)
        stacked = jax.tree.map(
            lambda x: jnp.stack([x, 2 * x.astype(x.dtype)]), tree)
        back = unflatten_stacked(spec, flatten_stacked(spec, stacked), tree)
        for a, b in zip(jax.tree.leaves(stacked), jax.tree.leaves(back)):
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_allclose(np.asarray(a, jnp.float32),
                                       np.asarray(b, jnp.float32))


class _FakeMesh:
    """Duck-typed mesh (axis names/sizes only) for spec-layout tests."""

    def __init__(self, data=2, model=4):
        self.axis_names = ("data", "model")
        self.devices = np.empty((data, model))


class TestShardedFlatSpec:
    def test_padding_is_per_shard_whole_tiles(self):
        tree = {"a": jnp.arange(7.0), "b": jnp.ones((3, 5))}
        for model in (2, 4, 8):
            spec = make_flat_spec(tree, mesh=_FakeMesh(model=model))
            assert isinstance(spec, ShardedFlatSpec)
            assert spec.model_shards == model
            assert spec.n_padded % (spec.block_n * model) == 0
            assert spec.n == 22

    def test_model_axis_of_one_falls_back_to_flat_spec(self):
        spec = make_flat_spec({"a": jnp.arange(7.0)},
                              mesh=_FakeMesh(data=8, model=1))
        assert isinstance(spec, FlatSpec)
        assert not isinstance(spec, ShardedFlatSpec)

    def test_roundtrip_with_extra_padding(self):
        tree = {"a": jnp.arange(7.0),
                "b": {"c": jnp.ones((3, 5), jnp.bfloat16)}}
        spec = make_flat_spec(tree, mesh=_FakeMesh(model=8))
        vec = flatten_tree(spec, tree)
        assert vec.shape == (spec.n_padded,)
        back = unflatten_like(spec, vec, tree)
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_allclose(np.asarray(a, jnp.float32),
                                       np.asarray(b, jnp.float32))


TOL = {"new_x": 1e-5, "sq_dists": 1e-3, "weights": 1e-5,
       "global": 1e-5, "client_params": 1e-5, "metrics": 1e-5,
       "history_wnorm": 1e-5,
       # population engine: window metadata is EXACT under sharding
       "win_meta": 0.0, "win_t": 1e-5,
       "pop_weights": 1e-5, "pop_wnorm": 1e-5,
       # sharded ring vs replicated ring: same program, BIT-identical
       "ring_weights_bits": 0.0, "ring_history_bits": 0.0,
       "ring_bytes_err": 0.0}


def _assert_report(report):
    assert report["devices"] >= 8
    for check, errs in report.items():
        if not isinstance(errs, dict):
            continue
        for key, err in errs.items():
            if key in TOL:
                assert err <= TOL[key], (check, key, err)
    assert report["engine"]["num_launches"] >= 1


class TestMultiDeviceParity:
    """Sharded pass == single-device pass, 8 forced host devices."""

    def test_sharded_matches_single_device(self):
        if len(jax.devices()) >= 8:
            # already multi-device (CI multi-device job): run in-process
            from _shard_worker import run_all
            _assert_report(run_all())
            return
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
            "PYTHONPATH": os.pathsep.join(
                [os.path.join(ROOT, "src"),
                 env.get("PYTHONPATH", "")]).rstrip(os.pathsep),
        })
        proc = subprocess.run(
            [sys.executable, os.path.join(ROOT, "tests", "_shard_worker.py")],
            capture_output=True, text=True, env=env, timeout=900)
        assert proc.returncode == 0, proc.stderr[-4000:]
        report = json.loads(proc.stdout.strip().splitlines()[-1])
        _assert_report(report)
