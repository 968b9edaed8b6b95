"""Vectorized simulation engine: parity with the legacy loop, scenario
registry coverage, trace record/replay, fair-RNG and partial-participation
fixes, telemetry."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FLConfig
from repro.core import run_async, run_async_legacy, run_sync, run_vectorized
from repro.data.synthetic import ClientDataset
from repro.sim import (
    ClientBehavior,
    EventTrace,
    LatencyModel,
    Scenario,
    get_scenario,
    metrics,
    registry,
)


def _quad_loss(params, batch):
    x, y = batch
    pred = x @ params["w"]
    return jnp.mean((pred - y) ** 2), {}


def _quad_clients(n=6, size=64, d=4, seed=0):
    rng = np.random.default_rng(seed)
    w_true = np.arange(1.0, d + 1.0)
    out = []
    for i in range(n):
        x = rng.normal(size=(size, d)).astype(np.float32)
        y = (x @ w_true + 0.05 * rng.normal(size=size)).astype(np.float32)
        out.append(ClientDataset(x=x, y=y, seed=seed + 10 + i))
    return out


def _params(d=4):
    return {"w": jnp.zeros(d)}


FL = FLConfig(num_clients=6, buffer_size=3, local_steps=2, local_lr=0.05,
              batch_size=8, max_staleness=4)


def _eval_fn(params):
    return {"wnorm": float(jnp.sum(params["w"] ** 2))}


class TestEngineParity:
    """The vectorized engine must reproduce the legacy heapq loop's round
    log event-for-event on a fixed seed (the ISSUE-2 acceptance gate)."""

    @pytest.mark.parametrize("weighting", ["paper", "fedbuff"])
    def test_round_log_event_for_event(self, weighting):
        fl = dataclasses.replace(FL, weighting=weighting)
        res_v = run_vectorized(_quad_loss, _params(), _quad_clients(), fl,
                               total_rounds=10, eval_fn=_eval_fn, seed=0)
        res_l = run_async_legacy(_quad_loss, _params(), _quad_clients(), fl,
                                 total_rounds=10, eval_fn=_eval_fn, seed=0)
        assert res_v.server_rounds == res_l.server_rounds == 10
        assert res_v.num_events == res_l.num_events
        assert res_v.sim_time == res_l.sim_time
        for lv, ll in zip(res_v.round_log, res_l.round_log):
            assert lv["version"] == ll["version"]
            assert lv["clients"] == ll["clients"]  # same uploads, same order
            assert lv["tau"] == ll["tau"]
            np.testing.assert_allclose(lv["weights"], ll["weights"],
                                       rtol=1e-4, atol=1e-6)
            np.testing.assert_allclose(lv["sq_dists"], ll["sq_dists"],
                                       rtol=1e-4, atol=1e-6)
        # eval cadence and timestamps identical too
        assert [(h["round"], h["time"]) for h in res_v.history] == \
               [(h["round"], h["time"]) for h in res_l.history]
        for hv, hl in zip(res_v.history, res_l.history):
            np.testing.assert_allclose(hv["wnorm"], hl["wnorm"], rtol=1e-4)

    def test_parity_exercises_stale_ring_fallback(self):
        """max_staleness=1 forces base versions out of the ring, hitting
        the resync path on both sides — they must still agree."""
        fl = dataclasses.replace(FL, max_staleness=1, buffer_size=2)
        res_v = run_vectorized(_quad_loss, _params(), _quad_clients(), fl,
                               total_rounds=8, seed=1)
        res_l = run_async_legacy(_quad_loss, _params(), _quad_clients(), fl,
                                 total_rounds=8, seed=1)
        for lv, ll in zip(res_v.round_log, res_l.round_log):
            assert lv["clients"] == ll["clients"]
            assert lv["tau"] == ll["tau"]

    def test_run_async_dispatches_engines(self):
        r = run_async(_quad_loss, _params(), _quad_clients(), FL,
                      total_rounds=2, seed=0, engine="vectorized")
        assert r.server_rounds == 2
        with pytest.raises(ValueError):
            run_async(_quad_loss, _params(), _quad_clients(), FL,
                      total_rounds=1, engine="nope")


class TestEvalCadence:
    """Satellite fix: the trailing ``maybe_eval(force=True)`` must not
    duplicate the final history row when total_rounds % eval_every == 0."""

    def test_no_duplicate_final_eval(self):
        for runner in (run_vectorized, run_async_legacy):
            res = runner(_quad_loss, _params(), _quad_clients(), FL,
                         total_rounds=4, eval_fn=_eval_fn, eval_every=2,
                         seed=0)
            assert [h["round"] for h in res.history] == [0, 2, 4]

    def test_final_eval_still_forced_on_odd_horizon(self):
        res = run_vectorized(_quad_loss, _params(), _quad_clients(), FL,
                             total_rounds=5, eval_fn=_eval_fn, eval_every=2,
                             seed=0)
        assert [h["round"] for h in res.history] == [0, 2, 4, 5]

    def test_num_launches_counted(self):
        res = run_vectorized(_quad_loss, _params(), _quad_clients(), FL,
                             total_rounds=10, seed=0, rounds_per_launch=4)
        assert res.num_launches == 3  # ceil(10 / 4), no eval clipping


class TestScenarioRegistry:
    def test_registry_has_at_least_six(self):
        reg = registry()
        assert len(reg) >= 6
        for name, sc in reg.items():
            assert sc.name == name and sc.description

    @pytest.mark.parametrize("name", sorted(registry()))
    def test_every_scenario_runs(self, name):
        """Each named scenario drives the engine for a couple of rounds."""
        sc = get_scenario(name)
        res = run_vectorized(_quad_loss, _params(), _quad_clients(), FL,
                             total_rounds=2, scenario=sc, seed=0)
        assert res.server_rounds == 2
        assert len(res.round_log) == 2
        assert np.isfinite(res.sim_time)

    def test_alpha_wiring_to_partition(self):
        """Scenario alpha reaches the Dirichlet partitioner: extreme skew
        concentrates labels, the IID scenario does not."""
        skew, _ = get_scenario("dirichlet-extreme").make_dataset(
            6, samples_per_client=200, seed=0)
        iid, _ = get_scenario("iid-uniform").make_dataset(
            6, samples_per_client=200, seed=0)
        seen_skew = np.median([np.unique(c.y).size for c in skew])
        seen_iid = np.median([np.unique(c.y).size for c in iid])
        assert seen_skew < seen_iid

    def test_diurnal_gating(self):
        sc = get_scenario("diurnal-phones")
        beh = sc.behavior(4, seed=0)
        period, on = sc.diurnal_period, sc.diurnal_duty * sc.diurnal_period
        for cid in range(4):
            for t in (0.0, 5.0, 13.7, 23.9, 42.0):
                start = beh.next_start(cid, t)
                assert start >= t
                local = (start - beh.phase[cid]) % period
                assert local < on or np.isclose(local % period, 0.0)

    def test_bernoulli_dropout_loses_uploads(self):
        sc = get_scenario("dropout-bernoulli")
        res = run_vectorized(_quad_loss, _params(), _quad_clients(), FL,
                             total_rounds=4, scenario=sc, seed=0,
                             record_trace=True)
        # dropped uploads consumed events beyond the 4*K accepted ones
        assert res.num_events > 4 * FL.buffer_size or res.trace.drops == []
        assert len(res.trace.drops) == res.num_events - 4 * FL.buffer_size

    def test_upload_index_api_and_stream(self):
        """The public ClientBehavior upload API: ``upload_index`` peeks,
        ``next_upload`` consumes atomically, and dropped uploads consume
        an index too (the stream identifies every ATTEMPT)."""
        sc = dataclasses.replace(
            get_scenario("iid-uniform"), name="drop-k1",
            dropout_trace=((0, 1),))  # client 0 loses its second upload
        beh = sc.behavior(2, seed=0)
        assert beh.upload_index(0) == 0
        assert beh.next_upload(0) == (0, False)
        assert beh.upload_index(0) == 1  # peek does not consume
        assert beh.upload_index(0) == 1
        assert beh.next_upload(0) == (1, True)  # the traced drop
        assert beh.next_upload(0) == (2, False)
        assert beh.next_upload(1) == (0, False)  # streams are per-client

    def test_replay_pins_index_stream_across_drops(self):
        """Trace replay re-issues the SAME (cid, k) upload stream: the
        recorded event log's indices skip the dropped ks identically on
        record and replay (the regression the public API guards — the
        engine used to sample the index separately from the drop check)."""
        sc = dataclasses.replace(  # drops that land inside a short run
            get_scenario("dropout-trace"), name="drop-early",
            dropout_trace=((0, 0), (1, 1), (3, 0), (3, 2)))
        rec = run_vectorized(_quad_loss, _params(), _quad_clients(), FL,
                             total_rounds=4, scenario=sc, seed=0,
                             record_trace=True)
        assert len(rec.trace.drops) > 0  # the scenario actually drops
        rep = run_vectorized(_quad_loss, _params(), _quad_clients(), FL,
                             total_rounds=4, trace=rec.trace, seed=99,
                             record_trace=True)
        # byte-identical (t, cid, k, round) streams, drops included
        assert rep.trace.events == rec.trace.events
        assert rep.trace.drops == rec.trace.drops
        # accepted events never reuse a dropped (cid, k); every dropped k
        # is still consumed (absent from events, present in the k-stream)
        dropped = set(map(tuple, rec.trace.drops))
        seen = {}
        for _, cid, k, _ in rec.trace.events:
            assert (cid, k) not in dropped
            ks = seen.setdefault(cid, [])
            ks.append(k)
        for cid, ks in seen.items():
            assert ks == sorted(ks)  # per-client indices strictly advance
            skipped = set(range(ks[-1] + 1)) - set(ks)
            assert skipped <= {k for c, k in dropped if c == cid}

    def test_trace_dropout_is_deterministic(self):
        sc = get_scenario("dropout-trace")
        r1 = run_vectorized(_quad_loss, _params(), _quad_clients(), FL,
                            total_rounds=3, scenario=sc, seed=0,
                            record_trace=True)
        r2 = run_vectorized(_quad_loss, _params(), _quad_clients(), FL,
                            total_rounds=3, scenario=sc, seed=0,
                            record_trace=True)
        assert r1.trace.drops == r2.trace.drops
        assert r1.sim_time == r2.sim_time

    def test_straggler_burst_slows_hit_clients(self):
        sc = get_scenario("straggler-burst")
        beh = sc.behavior(8, seed=0)
        # inside a burst window the hit client's multiplier applies
        assert beh._burst_mult(0, 0.5) == sc.burst_factor
        assert beh._burst_mult(1, 0.5) == 1.0
        assert beh._burst_mult(0, sc.burst_len + 0.5) == 1.0  # burst over
        # bursts rotate: next burst index shifts the hit set
        assert beh._burst_mult(3, sc.burst_every + 0.5) == sc.burst_factor

    def test_bandwidth_tiers_assign_comm(self):
        sc = get_scenario("bandwidth-tiers")
        beh = sc.behavior(32, seed=0)
        assert set(np.unique(beh.comm)) <= set(sc.comm_tiers)
        assert np.unique(beh.comm).size > 1  # population actually spans tiers


class TestFairRNG:
    """Satellite: one seeded duration stream per client, shared by
    sync/async/engine — draw k of client i never depends on the protocol."""

    def test_sync_and_async_see_identical_durations(self):
        lat = LatencyModel.heterogeneous(4, seed=0)
        a = ClientBehavior.from_latency(lat, 4, seed=5)
        b = ClientBehavior.from_latency(lat, 4, seed=5)
        # async consumption order (interleaved) vs sync order (per round)
        async_draws = [a.duration(0, 0), a.duration(1, 0), a.duration(0, 0)]
        sync_first = [b.duration(0, 0), b.duration(1, 0)]
        assert async_draws[0] == sync_first[0]
        assert async_draws[1] == sync_first[1]
        assert async_draws[2] == b.duration(0, 0)  # draw 1 of client 0

    def test_protocols_share_timeline(self):
        """K=1 and K=3 runs see the same upload times (timeline is
        protocol-independent), so wall-clock comparisons are fair."""
        fl1 = dataclasses.replace(FL, buffer_size=1)
        r3 = run_vectorized(_quad_loss, _params(), _quad_clients(), FL,
                            total_rounds=4, seed=3, record_trace=True)
        r1 = run_vectorized(_quad_loss, _params(), _quad_clients(), fl1,
                            total_rounds=12, seed=3, record_trace=True)
        t3 = [(t, c) for t, c, _, _ in r3.trace.events]
        t1 = [(t, c) for t, c, _, _ in r1.trace.events]
        assert t3 == t1[:len(t3)]


class TestTraces:
    def test_save_load_roundtrip(self, tmp_path):
        res = run_vectorized(_quad_loss, _params(), _quad_clients(), FL,
                             total_rounds=3, seed=0, record_trace=True)
        p = str(tmp_path / "trace.json")
        res.trace.save(p)
        tr = EventTrace.load(p)
        assert tr.num_clients == res.trace.num_clients
        assert tr.durations == res.trace.durations
        assert tr.drops == res.trace.drops
        assert tr.events == res.trace.events

    def test_replay_reproduces_run_exactly(self):
        res = run_vectorized(_quad_loss, _params(), _quad_clients(), FL,
                             total_rounds=3, seed=0, record_trace=True)
        replay = run_vectorized(_quad_loss, _params(), _quad_clients(), FL,
                                total_rounds=3, trace=res.trace, seed=99)
        assert replay.sim_time == res.sim_time
        assert [l["clients"] for l in replay.round_log] == \
               [l["clients"] for l in res.round_log]

    def test_replay_works_across_engines(self):
        res = run_vectorized(_quad_loss, _params(), _quad_clients(), FL,
                             total_rounds=3, seed=0, record_trace=True)
        replay = run_async_legacy(_quad_loss, _params(), _quad_clients(), FL,
                                  total_rounds=3, trace=res.trace)
        assert replay.sim_time == res.sim_time

    def test_replay_recovers_registered_scenario_gating(self):
        """A trace recorded under a registry scenario replays its
        deterministic parts (diurnal gating) without re-passing it."""
        sc = get_scenario("diurnal-phones")
        res = run_vectorized(_quad_loss, _params(), _quad_clients(), FL,
                             total_rounds=3, scenario=sc, seed=0,
                             record_trace=True)
        replay = run_vectorized(_quad_loss, _params(), _quad_clients(), FL,
                                total_rounds=3, trace=res.trace)
        assert replay.sim_time == res.sim_time
        assert [l["clients"] for l in replay.round_log] == \
               [l["clients"] for l in res.round_log]

    def test_exhausted_trace_raises_clearly(self):
        res = run_vectorized(_quad_loss, _params(), _quad_clients(), FL,
                             total_rounds=2, seed=0, record_trace=True)
        with pytest.raises(RuntimeError, match="trace exhausted"):
            run_vectorized(_quad_loss, _params(), _quad_clients(), FL,
                           total_rounds=10, trace=res.trace)


class TestSyncPartialParticipation:
    def test_partial_participation_counts(self):
        fl = dataclasses.replace(FL, clients_per_round=2)
        res = run_sync(_quad_loss, _params(), _quad_clients(), fl,
                       total_rounds=3, eval_fn=_eval_fn, eval_every=1)
        assert res.server_rounds == 3
        assert res.num_events == 6  # 2 clients x 3 rounds

    def test_partial_faster_than_full(self):
        """Waiting on a uniform subset is never slower than on all N."""
        full = run_sync(_quad_loss, _params(), _quad_clients(), FL,
                        total_rounds=3, seed=0)
        part = run_sync(_quad_loss, _params(),
                        _quad_clients(),
                        dataclasses.replace(FL, clients_per_round=2),
                        total_rounds=3, seed=0)
        assert part.sim_time <= full.sim_time

    def test_zero_means_all(self):
        res = run_sync(_quad_loss, _params(), _quad_clients(), FL,
                       total_rounds=2)
        assert res.num_events == 2 * len(_quad_clients())


class TestTelemetry:
    def test_summarize_fields(self):
        res = run_vectorized(_quad_loss, _params(), _quad_clients(), FL,
                             total_rounds=5, seed=0)
        s = metrics.summarize(res.round_log, 6)
        assert s["rounds"] == 5
        assert 0.0 <= s["participation_gini"] < 1.0
        assert s["tau_max"] >= 0
        assert 0.0 < s["staleness_deg_mean"] <= 1.0
        assert s["weight_entropy_mean"] <= s["weight_entropy_uniform"] + 1e-9

    def test_uniform_weights_hit_max_entropy(self):
        fl = dataclasses.replace(FL, weighting="fedbuff")
        res = run_vectorized(_quad_loss, _params(), _quad_clients(), fl,
                             total_rounds=3, seed=0)
        s = metrics.summarize(res.round_log, 6)
        np.testing.assert_allclose(s["weight_entropy_mean"],
                                   np.log2(FL.buffer_size), rtol=1e-5)

    def test_empty_round_log(self):
        assert metrics.summarize([], 4) == {"rounds": 0}


class TestImportOrder:
    def test_repro_sim_imports_standalone(self):
        """``import repro.sim`` before any repro.core import must not
        trip the core.simulator <-> sim.engine cycle."""
        import subprocess
        import sys
        r = subprocess.run(
            [sys.executable, "-c", "import repro.sim; import repro.core; "
             "print(repro.sim.SimResult is repro.core.SimResult)"],
            capture_output=True, text=True)
        assert r.returncode == 0, r.stderr
        assert r.stdout.strip() == "True"


class TestScenarioComposability:
    def test_replace_composes_new_scenario(self):
        base = get_scenario("compute-tiers")
        composed = dataclasses.replace(base, name="tiers+dropout",
                                       dropout_p=0.3)
        res = run_vectorized(_quad_loss, _params(), _quad_clients(), FL,
                             total_rounds=2, scenario=composed, seed=0,
                             record_trace=True)
        assert res.server_rounds == 2


@dataclasses.dataclass
class _RecordingClient(ClientDataset):
    """Records every ``batch_indices`` draw as ``(cid, indices)``."""

    cid: int = 0
    rows: list = dataclasses.field(default_factory=list)

    def batch_indices(self, batch_size):
        idx = super().batch_indices(batch_size)
        self.rows.append((self.cid, idx))
        return idx


class TestDevicePool:
    """The engine keeps the clients' samples on the device and stages
    only the indices each client draws from its own stream."""

    def test_subclass_sees_every_draw_in_window_order(self):
        log = []
        clients = [_RecordingClient(c.x, c.y, c.seed, cid=i, rows=log)
                   for i, c in enumerate(_quad_clients())]
        k, m = FL.buffer_size, FL.local_steps
        res = run_vectorized(_quad_loss, _params(), clients, FL,
                             total_rounds=6, seed=0, rounds_per_launch=4)
        per_round = k * (m + 1)
        assert len(log) == per_round * 6
        for r, row in enumerate(res.round_log):
            drawn = [c for c, _ in log[per_round * r:per_round * (r + 1)]]
            # train batches client by client, then the probes
            assert drawn == [c for c in row["clients"] for _ in range(m)] \
                + list(row["clients"])

    def test_engine_batches_equal_host_gathers(self):
        """What the round trains and probes on is exactly
        ``ClientDataset.batches``/``batch`` gathered on the host from the
        same RNG state."""
        seen = []

        def record(x, y):
            seen.append((np.asarray(x).copy(), np.asarray(y).copy()))

        def loss(params, batch):
            jax.debug.callback(record, *batch)
            return _quad_loss(params, batch)

        res = run_vectorized(loss, _params(), _quad_clients(), FL,
                             total_rounds=5, seed=0, rounds_per_launch=4)
        host = _quad_clients()
        want = []
        for row in res.round_log:
            for c in row["clients"]:
                xs, ys = host[c].batches(FL.batch_size, FL.local_steps)
                want.extend(zip(xs, ys))
            want.extend(host[c].batch(FL.batch_size) for c in row["clients"])

        def key(b):
            return b[0].tobytes() + b[1].tobytes()

        assert len(seen) == len(want)
        assert sorted(map(key, seen)) == sorted(map(key, want))

    def test_dropped_clients_free_the_pool(self):
        import gc
        import weakref

        from repro.sim.pool import place_client_pool

        clients = _quad_clients()
        placed, put = place_client_pool(clients)
        again, put_again = place_client_pool(clients)
        assert put > 0 and put_again == 0 and again is placed
        ref = weakref.ref(placed.x)
        del placed, again, clients
        gc.collect()
        assert ref() is None

    def test_out_of_range_draw_raises(self):
        """An index past a client's samples would read its neighbour's
        rows in the pool: the engine refuses it."""

        @dataclasses.dataclass
        class PastTheEnd(ClientDataset):
            def batch_indices(self, batch_size):
                return np.full(batch_size, self.size)

        clients = [PastTheEnd(c.x, c.y, c.seed) for c in _quad_clients()]
        with pytest.raises(IndexError):
            run_vectorized(_quad_loss, _params(), clients, FL,
                           total_rounds=2, seed=0)
