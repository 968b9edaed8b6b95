"""Always-on serving loop (core/serving.py + sim/arrivals.py, DESIGN.md §8):
admission-control semantics under bursts, adaptive-K settling, FedAsync
staleness-discount parity, and the acceptance gate — the serving loop's
aggregate pinned against the exact ``apply_server_round`` path on the
same seeded upload stream for EVERY weighting policy."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FLConfig
from repro.core.client import make_local_update_fn
from repro.core.serving import (
    ADMITTED,
    DROP_MAX_STALENESS,
    REJECT_QUEUE_FULL,
    ServeConfig,
    ServingController,
    Upload,
    serve_stream,
)
from repro.core.server_pass import (
    apply_server_round,
    flatten_stacked,
    flatten_tree,
    make_flat_spec,
    unflatten_like,
)
from repro.core.weighting import (
    FEDASYNC_POLICIES,
    POLICIES,
    contribution_weights,
    fedasync_discount,
)
from repro.sim.arrivals import TrafficGenerator
from repro.sim.scenarios import get_scenario


def _quad_loss(params, batch):
    x, y = batch
    return jnp.mean((x @ params["w"] - y) ** 2), {}


def _quad_batch(key, n=8, d=4):
    k1, k2 = jax.random.split(key)
    x = jax.random.normal(k1, (n, d))
    y = x @ jnp.arange(1.0, d + 1.0) + 0.01 * jax.random.normal(k2, (n,))
    return x, y


PARAMS = {"w": jnp.array([1.0, -1.0, 0.5, 2.0])}


def _upload(ctrl, i, tau=0, t=0.0, size=10.0):
    """One deterministic quad-problem upload, staleness ``tau`` rounds."""
    key = jax.random.PRNGKey(0)
    b = _quad_batch(jax.random.fold_in(key, i))
    return Upload(client_id=i, base_version=ctrl.version - tau,
                  data_size=size,
                  batch=jax.tree.map(lambda x: x[None], b),
                  probe=_quad_batch(jax.random.fold_in(key, 100 + i)),
                  sent_at=t)


class TestAdmissionControl:
    def _ctrl(self, **kw):
        fl = FLConfig(buffer_size=4, local_steps=1, local_lr=0.1,
                      max_staleness=4)
        return ServingController(_quad_loss, PARAMS, fl,
                                 ServeConfig(**kw)), fl

    def test_backpressure_rejects_under_burst(self):
        """A burst beyond queue capacity at a busy endpoint: capacity
        admitted, the rest rejected with a positive retry-after hint —
        and every outcome accounted for in a counter."""
        ctrl, _ = self._ctrl(queue_capacity=4, service_time=0.25,
                             adapt_every=0, retry_after_min=0.1)
        outcomes = []
        for i in range(12):  # simultaneous burst: service can't drain
            outcomes.append(ctrl.offer(_upload(ctrl, i, t=0.0), now=0.0))
            ctrl.pump(0.0)
        rejected = [a for a in outcomes if not a.accepted]
        assert ctrl.counters["admitted"] == 4
        assert ctrl.counters["rejected_queue_full"] == len(rejected) == 8
        assert all(a.reason == REJECT_QUEUE_FULL for a in rejected)
        assert all(a.retry_after >= 0.1 for a in rejected)
        # the hint scales with the modeled drain time of the full queue
        assert rejected[0].retry_after == pytest.approx(4 * 0.25)
        # once the service catches up, the queued uploads fold
        ctrl.pump(4 * 0.25)
        assert ctrl.counters["folded"] == 4
        assert ctrl.counters["rounds"] == 1

    def test_stale_uploads_dropped_with_counters(self):
        """Ingress drop when staleness > max_staleness; queued entries
        that out-age while waiting are evicted oldest-first."""
        ctrl, fl = self._ctrl(queue_capacity=8, service_time=0.0,
                              adapt_every=0)
        ctrl.version = 10
        adm = ctrl.offer(_upload(ctrl, 0, tau=fl.max_staleness + 1), now=0.0)
        assert not adm.accepted and adm.reason == DROP_MAX_STALENESS
        assert adm.retry_after == 0.0
        assert ctrl.counters["dropped_stale_ingress"] == 1
        # a queued upload at the staleness edge out-ages when the version
        # advances before it is serviced
        assert ctrl.offer(_upload(ctrl, 1, tau=fl.max_staleness),
                          now=0.0).accepted
        ctrl.version += 1  # round applied elsewhere; queue head now too old
        ctrl.offer(_upload(ctrl, 2, tau=0), now=0.1)
        assert ctrl.counters["dropped_stale_queue"] == 1
        assert len(ctrl.queue) == 1

    def test_queue_capacity_validated(self):
        with pytest.raises(ValueError, match="queue_capacity"):
            self._ctrl(queue_capacity=0)
        with pytest.raises(ValueError, match="k_min"):
            self._ctrl(k_min=4, k_max=2)

    def test_retry_after_clamped_to_max(self):
        """A deep queue with slow service would hint hours of backoff;
        the Admission contract clamps it to retry_after_max so clients
        re-probe on a bounded cadence."""
        ctrl, _ = self._ctrl(queue_capacity=8, service_time=10.0,
                             adapt_every=0, retry_after_max=5.0)
        for i in range(8):
            assert ctrl.offer(_upload(ctrl, i, t=0.0), now=0.0).accepted
        adm = ctrl.offer(_upload(ctrl, 9, t=0.0), now=0.0)
        assert not adm.accepted and adm.reason == REJECT_QUEUE_FULL
        # unclamped hint would be 8 * 10.0 = 80s of modeled drain
        assert adm.retry_after == 5.0


class TestAdaptiveK:
    def test_k_settles_to_arrival_rate_times_target(self):
        """Uniform arrivals at rate lambda: K converges to the fixed point
        lambda * target_round_latency and round cadence lands on target."""
        fl = FLConfig(buffer_size=4, local_steps=1, local_lr=0.1,
                      max_staleness=4)
        cfg = ServeConfig(queue_capacity=8, service_time=0.0,
                          target_round_latency=2.0, k_min=2, k_max=64,
                          adapt_every=2, adapt_gain=0.5, arrival_ewma=0.5)
        ctrl = ServingController(_quad_loss, PARAMS, fl, cfg)
        assert ctrl.k == 4
        gap = 0.125  # lambda = 8/s  ->  K* = 16
        for i in range(200):
            t = i * gap
            ctrl.offer(_upload(ctrl, i % 8, t=t), now=t)
            ctrl.pump(t)
        assert ctrl.k == 16
        assert ctrl.arrival_rate() == pytest.approx(8.0, rel=1e-3)
        # once settled, cadence == K / lambda == the latency target
        cadence = np.diff(ctrl.round_times[-4:])
        np.testing.assert_allclose(cadence, 2.0, atol=0.15)
        # the trajectory is recorded for telemetry
        assert ctrl.k_history[0] == (0, 4)
        assert ctrl.k_history[-1][1] == 16

    def test_fixed_k_when_adaptation_disabled(self):
        fl = FLConfig(buffer_size=4, local_steps=1, local_lr=0.1,
                      max_staleness=4)
        ctrl = ServingController(_quad_loss, PARAMS, fl,
                                 ServeConfig(adapt_every=0))
        for i in range(64):
            t = i * 0.01  # fast arrivals would push K up if enabled
            ctrl.offer(_upload(ctrl, i % 8, t=t), now=t)
            ctrl.pump(t)
        assert ctrl.k == 4 and ctrl.k_history == [(0, 4)]


class TestFedAsyncPolicies:
    def test_discount_family_shapes(self):
        tau = jnp.arange(0.0, 12.0)
        const = fedasync_discount("constant", tau)
        hinge = fedasync_discount("hinge", tau, hinge_a=10.0, hinge_b=6.0)
        poly = fedasync_discount("poly", tau, poly_a=0.5)
        np.testing.assert_allclose(const, 1.0)
        np.testing.assert_allclose(hinge[:7], 1.0)  # flat through tau == b
        np.testing.assert_allclose(hinge[7], 1.0 / 10.0)  # 1/(a*(tau-b))
        assert np.all(np.diff(poly) < 0)  # strictly decreasing
        np.testing.assert_allclose(poly, (1.0 + np.arange(12.0)) ** -0.5,
                                   rtol=1e-6)

    def test_all_discounts_are_one_at_tau_zero(self):
        """At tau=0 every FedAsync policy reduces to FedBuff's uniform
        weight — pinned through contribution_weights itself."""
        p = jnp.array([1.0, 2.0, 3.0])
        s = jnp.ones(3)
        tau = jnp.zeros(3)
        fb = contribution_weights("fedbuff", p, s, tau, normalize="none")
        for policy in FEDASYNC_POLICIES:
            w = contribution_weights(policy, p, s, tau, normalize="none")
            np.testing.assert_allclose(np.asarray(w), np.asarray(fb),
                                       rtol=1e-6, err_msg=policy)

    @pytest.mark.parametrize("policy", list(FEDASYNC_POLICIES))
    def test_serving_loop_parity_with_fedbuff_at_tau_zero(self, policy):
        """All-fresh traffic: the served model under each FedAsync
        discount is bit-comparable to FedBuff on the same stream."""
        def run(weighting):
            fl = FLConfig(buffer_size=3, local_steps=1, local_lr=0.1,
                          max_staleness=4, weighting=weighting,
                          normalize="none")
            ctrl = ServingController(_quad_loss, PARAMS, fl,
                                     ServeConfig(adapt_every=0))
            for i in range(9):
                ctrl.offer(_upload(ctrl, i, t=float(i)), now=float(i))
                ctrl.pump(float(i))
            assert ctrl.counters["rounds"] == 3
            return np.asarray(ctrl.params["w"])

        np.testing.assert_allclose(run(policy), run("fedbuff"),
                                   rtol=1e-6, atol=1e-7)


class TestServingParity:
    """The acceptance gate: serving-loop aggregate == apply_server_round
    on the same seeded upload stream, every weighting policy, f32 tol."""

    @pytest.mark.parametrize("policy", list(POLICIES))
    def test_serving_matches_apply_server_round(self, policy):
        k = 4
        fl = FLConfig(buffer_size=k, local_steps=1, local_lr=0.1,
                      weighting=policy, normalize="mean", global_lr=1.0,
                      max_staleness=k)
        ctrl = ServingController(_quad_loss, PARAMS, fl,
                                 ServeConfig(adapt_every=0, k_max=8))
        # seed the eq.-3 ring so staleness distances are non-trivial from
        # round one (afterwards it evolves with the real update norms)
        ctrl.update_norm_ring = jnp.array([0.3, 0.2, 0.1, 0.05])
        local_update = make_local_update_fn(_quad_loss, fl.local_steps,
                                            fl.local_lr, fl.local_momentum)
        taus = [0, 1, 2, 3]  # tau=0 present: pinned reference is exact
        sizes = [10.0, 20.0, 30.0, 40.0]
        key = jax.random.PRNGKey(0)
        t = 0.0
        for rnd in range(3):
            x_tree = ctrl.params
            ring = ctrl.update_norm_ring
            deltas, losses, batches = [], [], []
            for i in range(k):
                b = _quad_batch(jax.random.fold_in(key, 10 * rnd + i))
                pb = _quad_batch(jax.random.fold_in(key, 900 + 10 * rnd + i))
                stacked = jax.tree.map(lambda x: x[None], b)
                deltas.append(local_update(x_tree, stacked)[0])
                losses.append(_quad_loss(x_tree, pb)[0])
                batches.append((stacked, pb))
            for i, (stacked, pb) in enumerate(batches):
                t += 0.1
                up = Upload(client_id=i, base_version=ctrl.version - taus[i],
                            data_size=sizes[i], batch=stacked, probe=pb,
                            sent_at=t)
                assert ctrl.offer(up, now=t).reason == ADMITTED
                ctrl.pump(t)
            assert ctrl.version == rnd + 1  # the K-th fold applied eq. 5

            # exact path on the SAME stream: bases whose eq. 3 distances
            # equal the pre-round ring distances the streaming form used
            dists = np.array([float(jnp.sum(ring[:tt])) for tt in taus])
            spec = make_flat_spec(x_tree)
            x = flatten_tree(spec, x_tree)
            onehot = jnp.eye(spec.n_padded)[:k]
            bases = x[None] - jnp.sqrt(
                jnp.asarray(dists, jnp.float32))[:, None] * onehot
            deltas_flat = flatten_stacked(
                spec, jax.tree.map(lambda *xs: jnp.stack(xs), *deltas))
            new_x, _ = apply_server_round(
                x, bases, deltas_flat, jnp.asarray(losses, jnp.float32),
                jnp.asarray(sizes, jnp.float32),
                jnp.asarray(taus, jnp.float32), fl,
                mode="reference", block_n=spec.block_n)
            expect = unflatten_like(spec, new_x, x_tree)
            np.testing.assert_allclose(
                np.asarray(ctrl.params["w"]), np.asarray(expect["w"]),
                rtol=2e-5, atol=1e-6,
                err_msg=f"policy={policy} round={rnd}")


class TestServeStream:
    """End-to-end: scenario traffic through serve_stream, deterministic
    under a seed, with loss/retry accounting surfaced in the metrics."""

    def _run(self, scenario="dropout-bernoulli", seed=0, rounds=3):
        sc = get_scenario(scenario)
        n = 6
        clients, _ = sc.make_dataset(n, samples_per_client=16, seed=seed)
        fl = FLConfig(num_clients=n, buffer_size=3, max_staleness=6,
                      local_steps=1, batch_size=4)

        def loss(params, batch):
            x, y = batch
            x = x.reshape(x.shape[0], -1)
            return jnp.mean((x @ params["w"] - y) ** 2), {}

        params = {"w": jnp.zeros(784) }
        ctrl = ServingController(loss, params, fl,
                                 ServeConfig(queue_capacity=8))
        gen = TrafficGenerator(clients, sc.behavior(n, seed=seed), fl)
        out = serve_stream(ctrl, gen, max_rounds=rounds)
        return out, np.asarray(ctrl.params["w"])

    def test_deterministic_under_seed(self):
        out1, w1 = self._run(seed=0)
        out2, w2 = self._run(seed=0)
        assert out1["folded"] == out2["folded"]
        assert out1["rounds"] == out2["rounds"]
        assert out1["lost_in_transit"] == out2["lost_in_transit"]
        np.testing.assert_array_equal(w1, w2)

    def test_dropouts_are_counted_not_folded(self):
        out, _ = self._run(scenario="dropout-bernoulli", rounds=4)
        assert out["lost_in_transit"] > 0
        assert out["folded"] + out["lost_in_transit"] <= out["events"]
        assert out["rounds"] == 4

    def test_requires_a_bound(self):
        fl = FLConfig(buffer_size=2, max_staleness=4)
        ctrl = ServingController(_quad_loss, PARAMS, fl, ServeConfig())
        with pytest.raises(ValueError, match="max_rounds"):
            serve_stream(ctrl, object())


class TestOverloadRetry:
    """Backpressure end to end through TrafficGenerator: every
    queue_full rejection is re-offered after EXACTLY the hinted delay
    with the SAME (now staler) payload, and every offer lands in exactly
    one admission counter."""

    def test_rejections_reoffered_at_hint_with_same_payload(self):
        sc = get_scenario("paper-fig1")
        n = 4
        clients, _ = sc.make_dataset(n, samples_per_client=16, seed=0)
        fl = FLConfig(num_clients=n, buffer_size=2, max_staleness=100,
                      local_steps=1, batch_size=4)

        def loss(params, batch):
            x, y = batch
            x = x.reshape(x.shape[0], -1)
            return jnp.mean((x @ params["w"] - y) ** 2), {}

        # one queue slot + slow modeled service: arrivals outpace the
        # fold drain, so the generator's retry path gets exercised hard
        ctrl = ServingController(
            loss, {"w": jnp.zeros(784)}, fl,
            ServeConfig(queue_capacity=1, service_time=0.9,
                        adapt_every=0, retry_after_min=0.05))
        gen = TrafficGenerator(clients, sc.behavior(n, seed=0), fl)
        horizon = 40.0
        log = []  # (t, cid, upload, admission) for every real offer
        while not gen.empty():
            t, cid = gen.pop()
            if t > horizon:
                break
            up = gen.realize(cid, t, ctrl.version)
            if up is None:
                continue
            adm = ctrl.offer(up, t)
            ctrl.pump(t)
            log.append((t, cid, up, adm))
            gen.settle(cid, t, adm, ctrl.version, up)

        rejections = [(i, e) for i, e in enumerate(log)
                      if e[3].reason == REJECT_QUEUE_FULL]
        assert len(rejections) >= 3, "config failed to provoke overload"
        for i, (t, cid, up, adm) in rejections:
            if t + adm.retry_after > horizon:
                continue  # retry scheduled past the cut
            later = [e for e in log[i + 1:] if e[1] == cid]
            assert later, f"rejection at t={t} never re-offered"
            rt, _, rup, _ = later[0]
            assert rup is up  # SAME payload object, held in gen.pending
            assert rt == t + adm.retry_after  # exact heap arithmetic
            assert rup.base_version == up.base_version  # staler, not redrawn
        # reconciliation: offered == admitted + rejected + dropped
        c = ctrl.counters
        assert len(log) == (c["admitted"] + c["rejected_queue_full"]
                            + c["dropped_stale_ingress"])
        assert gen.retries == len(rejections)
