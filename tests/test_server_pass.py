"""Device-resident server pass: mode parity, padding, and the host-sync
contract (DESIGN.md §3).

The reference mode is itself checked against a hand-computed pure-jnp
oracle built directly from core/weighting, then the Pallas kernels
(``fused``) are swept against reference in interpret mode across K,
non-lane-multiple N, dtypes, and policies.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FLConfig
from repro.core.server import AsyncServer
from repro.core.server_pass import (
    apply_server_round,
    flatten_stacked,
    flatten_tree,
    make_flat_spec,
    make_server_pass,
    resolve_mode,
    unflatten_like,
)
from repro.core.weighting import (
    POLICIES,
    contribution_weights,
    staleness_degree,
    statistical_effect,
)


def _flat_case(key, k, n, dtype=jnp.float32):
    kx, kb, kd = jax.random.split(key, 3)
    x = jax.random.normal(kx, (n,), jnp.float32)
    bases = x[None] + 0.1 * jax.random.normal(kb, (k, n), jnp.float32)
    deltas = jax.random.normal(kd, (k, n), jnp.float32).astype(dtype)
    losses = jnp.linspace(0.5, 2.0, k)
    sizes = jnp.linspace(10.0, 50.0, k)
    taus = jnp.arange(k, dtype=jnp.float32)
    return x, bases, deltas, losses, sizes, taus


def _pad(a, npad):
    widths = ((0, npad - a.shape[-1]),)
    if a.ndim == 2:
        widths = ((0, 0),) + widths
    return jnp.pad(a.astype(jnp.float32), widths)


def _oracle(x, bases, deltas, losses, sizes, taus, fl, mask=None):
    """Unpadded pure-jnp eq. 3+4+5 straight from core/weighting."""
    dists = jnp.sum((bases - x[None]) ** 2, axis=1)
    s = staleness_degree(dists, arrival_mask=mask)
    p = statistical_effect(losses, sizes)
    w = contribution_weights(fl.weighting, p, s, taus, s_min=fl.s_min,
                             poly_a=fl.poly_a, hinge_a=fl.hinge_a,
                             hinge_b=fl.hinge_b, normalize=fl.normalize,
                             arrival_mask=mask)
    k_eff = bases.shape[0] if mask is None else float(jnp.sum(mask))
    upd = jnp.einsum("kn,k->n", deltas.astype(jnp.float32),
                     w * (fl.global_lr / max(k_eff, 1.0)))
    return x - upd, dists, w


def _run_mode(mode, x, bases, deltas, losses, sizes, taus, fl, mask=None):
    spec_n = x.shape[0]
    block = 0
    from repro.kernels.weighted_agg.ops import pad_to, pick_block
    block = pick_block(spec_n)
    npad = pad_to(spec_n, block)
    new_x, info = apply_server_round(
        _pad(x, npad), _pad(bases, npad), _pad(deltas, npad), losses,
        sizes, taus, fl, arrival_mask=mask, mode=mode, block_n=block,
        interpret=True)
    return new_x[:spec_n], info


class TestModeParity:
    @pytest.mark.parametrize("k", [1, 3, 8, 32])
    def test_k_sweep(self, k):
        fl = FLConfig(weighting="paper")
        case = _flat_case(jax.random.PRNGKey(k), k, 1000)
        ref, dists, w = _oracle(*case, fl)
        for mode in ("reference", "fused"):
            got, info = _run_mode(mode, *case, fl)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=1e-4, atol=1e-5, err_msg=mode)
            np.testing.assert_allclose(np.asarray(info["sq_dists"]),
                                       np.asarray(dists), rtol=1e-4,
                                       err_msg=mode)
            np.testing.assert_allclose(np.asarray(info["weights"]),
                                       np.asarray(w), rtol=1e-4,
                                       err_msg=mode)

    @pytest.mark.parametrize("n", [1000, 130 * 1000 + 7])
    def test_non_lane_multiple_n(self, n):
        """Padding must be distance- and sum-neutral at awkward N."""
        fl = FLConfig(weighting="paper")
        case = _flat_case(jax.random.PRNGKey(0), 3, n)
        ref, dists, _ = _oracle(*case, fl)
        got, info = _run_mode("fused", *case, fl)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(info["sq_dists"]),
                                   np.asarray(dists), rtol=1e-3)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_delta_dtypes(self, dtype):
        fl = FLConfig(weighting="paper")
        case = _flat_case(jax.random.PRNGKey(1), 4, 1000, dtype=dtype)
        ref, _, _ = _oracle(*case, fl)
        tol = 1e-4 if dtype == jnp.float32 else 5e-2
        for mode in ("reference", "fused"):
            got, _ = _run_mode(mode, *case, fl)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=tol, atol=tol, err_msg=mode)

    @pytest.mark.parametrize("policy", POLICIES)
    def test_policies_and_mask(self, policy):
        # hinge_b=1.0 puts taus 2..3 past the hinge knee, so the fused
        # kernel's in-kernel reciprocal branch is actually exercised
        fl = FLConfig(weighting=policy, hinge_b=1.0)
        case = _flat_case(jax.random.PRNGKey(2), 4, 520)
        mask = jnp.array([1.0, 0.0, 1.0, 1.0])
        ref, _, w_ref = _oracle(*case, fl, mask=mask)
        for mode in ("reference", "fused"):
            got, info = _run_mode(mode, *case, fl, mask=mask)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=1e-4, atol=1e-5, err_msg=mode)
            np.testing.assert_allclose(np.asarray(info["weights"]),
                                       np.asarray(w_ref), rtol=1e-4,
                                       atol=1e-6, err_msg=mode)
        assert float(info["weights"][1]) == 0.0


class TestFlatSpecAdapter:
    def test_roundtrip_mixed_shapes_and_dtypes(self):
        tree = {"a": jnp.arange(7.0), "b": {"c": jnp.ones((3, 5), jnp.bfloat16),
                                            "d": jnp.float32(2.0).reshape(())}}
        spec = make_flat_spec(tree)
        vec = flatten_tree(spec, tree)
        assert vec.shape == (spec.n_padded,) and spec.n == 7 + 15 + 1
        assert spec.n_padded % spec.block_n == 0
        back = unflatten_like(spec, vec, tree)
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_allclose(np.asarray(a, jnp.float32),
                                       np.asarray(b, jnp.float32))

    def test_flatten_stacked_matches_per_item(self):
        trees = [{"w": jnp.full((2, 3), float(i)), "b": jnp.full((4,), -float(i))}
                 for i in range(3)]
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *trees)
        spec = make_flat_spec(trees[0])
        flat = flatten_stacked(spec, stacked)
        for i, t in enumerate(trees):
            np.testing.assert_allclose(np.asarray(flat[i]),
                                       np.asarray(flatten_tree(spec, t)))

    def test_resolve_mode(self):
        mode, interpret = resolve_mode("auto")
        assert mode in ("reference", "fused")
        if jax.default_backend() != "tpu":
            assert mode == "reference" and interpret
        with pytest.raises(ValueError):
            resolve_mode("nope")

    def test_explicit_kernel_mode_off_tpu_warns(self, caplog):
        """Satellite: a non-TPU user asking for the Mosaic kernels gets an
        actionable warning naming the backend, not a silent slowdown.
        Emitted through the standardized logging plane (obs, DESIGN.md
        §9) rather than warnings.warn."""
        if jax.default_backend() == "tpu":
            pytest.skip("kernel modes are native on TPU")
        import logging
        with caplog.at_level(logging.WARNING,
                             logger="repro.core.server_pass"):
            got, interpret = resolve_mode("fused")
        assert any("compile only for TPU" in r.getMessage()
                   and r.levelno == logging.WARNING
                   for r in caplog.records), caplog.records
        assert got == "fused" and interpret

    def test_auto_fallback_is_silent(self, caplog):
        import logging
        import warnings as _w
        with _w.catch_warnings():
            _w.simplefilter("error")
            with caplog.at_level(logging.WARNING,
                                 logger="repro.core.server_pass"):
                resolve_mode("auto")
        assert not [r for r in caplog.records
                    if r.levelno >= logging.WARNING]


def _quad_loss(params, batch):
    x, y = batch
    return jnp.mean((x @ params["w"] - y) ** 2), {}


def _quad_batch(key, n=16, d=4):
    k1, k2 = jax.random.split(key)
    x = jax.random.normal(k1, (n, d))
    y = x @ jnp.arange(1.0, d + 1.0) + 0.01 * jax.random.normal(k2, (n,))
    return x, y


class TestHostSyncContract:
    """AsyncServer._do_aggregate: at most 2 device->host syncs per round
    (the single round-log readback), with exactly one jitted-pass call."""

    def test_at_most_two_host_syncs(self, monkeypatch):
        fl = FLConfig(buffer_size=3, weighting="paper")
        server = AsyncServer({"w": jnp.zeros(4)}, fl,
                             lambda p, b: _quad_loss(p, b)[0])
        batch = _quad_batch(jax.random.PRNGKey(0))

        sync_calls = []
        orig_get = jax.device_get
        monkeypatch.setattr(
            jax, "device_get",
            lambda tree: (sync_calls.append(1), orig_get(tree))[1])
        pass_calls = []
        orig_pass = server._pass
        server._pass = lambda *a, **kw: (pass_calls.append(1),
                                         orig_pass(*a, **kw))[1]

        d = {"w": jnp.ones(4)}
        assert not server.receive(0, d, 0, 10, lambda: batch)
        assert not server.receive(1, d, 0, 20, lambda: batch)
        assert server.receive(2, d, 0, 30, lambda: batch)

        assert len(pass_calls) == 1  # one jitted pass per round
        assert len(sync_calls) <= 2  # round-log readback only
        assert server.version == 1 and len(server.round_log) == 1

    def test_pass_output_stays_on_device(self):
        fl = FLConfig(buffer_size=2)
        server = AsyncServer({"w": jnp.zeros(4)}, fl,
                             lambda p, b: _quad_loss(p, b)[0])
        batch = _quad_batch(jax.random.PRNGKey(1))
        server.receive(0, {"w": jnp.ones(4)}, 0, 10, lambda: batch)
        server.receive(1, {"w": jnp.ones(4)}, 0, 10, lambda: batch)
        assert isinstance(server.params["w"], jax.Array)

    def test_heterogeneous_probe_shapes(self, monkeypatch):
        """Clients with different probe batch sizes must not crash the
        round (seed behaviour) and must keep the host-sync budget: the
        fallback evaluates K separate jitted losses, all device-side."""
        fl = FLConfig(buffer_size=2, weighting="paper")
        server = AsyncServer({"w": jnp.zeros(4)}, fl,
                             lambda p, b: _quad_loss(p, b)[0])
        big = _quad_batch(jax.random.PRNGKey(0), n=16)
        small = _quad_batch(jax.random.PRNGKey(1), n=8)

        sync_calls = []
        orig_get = jax.device_get
        monkeypatch.setattr(
            jax, "device_get",
            lambda tree: (sync_calls.append(1), orig_get(tree))[1])

        server.receive(0, {"w": jnp.ones(4)}, 0, 10, lambda: big)
        assert server.receive(1, {"w": jnp.ones(4)}, 0, 30, lambda: small)
        assert server.version == 1
        assert len(sync_calls) <= 2
        log = server.round_log[0]
        # probes ran: P_i = N_i * loss_i, not the size-only fallback
        assert log["stat_effect"][0] != 10.0 or log["stat_effect"][1] != 30.0

    def test_missing_probe_falls_back_to_size_weighting(self):
        fl = FLConfig(buffer_size=2, weighting="paper")
        server = AsyncServer({"w": jnp.zeros(4)}, fl,
                             lambda p, b: _quad_loss(p, b)[0])
        server.receive(0, {"w": jnp.ones(4)}, 0, 10)
        server.receive(1, {"w": jnp.ones(4)}, 0, 30)
        log = server.round_log[0]
        # no probes anywhere: losses default to 1 => P_i = N_i
        np.testing.assert_allclose(log["stat_effect"], [10.0, 30.0],
                                   rtol=1e-6)


class TestServerPassJit:
    def test_make_server_pass_end_to_end(self):
        fl = FLConfig(buffer_size=2, weighting="paper", global_lr=1.0)
        params = {"w": jnp.array([1.0, -1.0, 0.5, 2.0])}
        pass_fn = make_server_pass(fl, lambda p, b: _quad_loss(p, b)[0])
        key = jax.random.PRNGKey(0)
        deltas = [{"w": 0.1 * jnp.arange(4.0)}, {"w": -0.1 * jnp.ones(4)}]
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *deltas)
        bases = jax.tree.map(lambda x: jnp.stack([x, x]), params)
        probes = jax.tree.map(lambda *xs: jnp.stack(xs),
                              _quad_batch(key), _quad_batch(key))
        new_params, info = pass_fn(params, stacked, bases, probes,
                                   jnp.ones(2), jnp.array([10.0, 30.0]),
                                   jnp.zeros(2))
        # both fresh => S = 1; paper weights proportional to N_i * loss
        assert float(info["weights"][1]) > float(info["weights"][0])
        ref, _, _ = _oracle(
            jnp.asarray(params["w"]),
            jnp.stack([params["w"], params["w"]]),
            jnp.stack([d["w"] for d in deltas]),
            info["fresh_loss"], jnp.array([10.0, 30.0]), jnp.zeros(2), fl)
        np.testing.assert_allclose(np.asarray(new_params["w"]),
                                   np.asarray(ref), rtol=1e-5)
