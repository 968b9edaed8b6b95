"""Observability plane (src/repro/obs, DESIGN.md §9): registry semantics,
histogram-quantile accuracy vs numpy, Chrome-trace schema + coverage,
sink gating, and the migration contracts — ServingController.metrics()
parity with the registry, the engine's dispatch counter backing
SimResult.num_launches, and bench provenance compatibility checks."""
import dataclasses
import functools
import json
import logging
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FLConfig
from repro.core.serving import ServeConfig, ServingController, serve_stream
from repro.obs import (
    DEFAULT_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    InMemorySink,
    JsonlSink,
    MetricsRegistry,
    Tracer,
    configure_logging,
    emit_snapshot,
    merge_snapshots,
)
from repro.obs.trace import (
    ANNOTATION_PREFIX,
    DEFAULT_CAPACITY,
    NULL_TRACER,
    SPAN_APPLY,
    SPAN_CHECKPOINT,
    SPAN_COLLECT,
    SPAN_GATHER,
    SPAN_HOST_SYNC,
    SPAN_NAMES,
    SPAN_PREPARE,
    SPAN_STAGE,
    _NULL_SPAN,
    default_tracer,
    span_coverage,
    validate_trace,
)
from repro.sim.arrivals import TrafficGenerator
from repro.sim.engine import run_vectorized
from repro.sim.population import run_population
from repro.sim.scenarios import get_scenario



# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_get_or_create_returns_same_instrument(self):
        reg = MetricsRegistry()
        a = reg.counter("x", route="fold")
        b = reg.counter("x", route="fold")
        assert a is b
        a.inc(2)
        assert b.value == 2.0

    def test_label_order_is_canonical(self):
        reg = MetricsRegistry()
        assert reg.counter("x", b=1, a=2) is reg.counter("x", a=2, b=1)
        assert reg.counter("x", a=2, b=1).key == "x{a=2,b=1}"

    def test_type_conflict_rejected(self):
        reg = MetricsRegistry()
        reg.counter("x")
        with pytest.raises(TypeError, match="already registered"):
            reg.gauge("x")

    def test_counter_is_monotonic(self):
        c = Counter("c")
        with pytest.raises(ValueError, match="cannot decrease"):
            c.inc(-1)

    def test_gauge_last_write_wins(self):
        g = Gauge("g")
        g.set(3)
        g.inc(2)
        assert g.value == 5.0

    def test_snapshot_is_flat_and_sorted(self):
        reg = MetricsRegistry()
        reg.counter("b_total").inc(3)
        reg.gauge("a_depth").set(7)
        h = reg.histogram("lat", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        h.observe(5.0)  # +inf overflow
        snap = reg.snapshot()
        assert list(snap) == sorted(snap)
        assert snap["b_total"] == 3.0 and snap["a_depth"] == 7.0
        # cumulative le buckets + overflow into +Inf
        assert snap["lat_bucket{le=0.1}"] == 1.0
        assert snap["lat_bucket{le=1.0}"] == 2.0
        assert snap["lat_bucket{le=+Inf}"] == 3.0
        assert snap["lat_count"] == 3.0
        assert snap["lat_sum"] == pytest.approx(5.55)
        assert all(isinstance(v, float) for v in snap.values())

    def test_merge_sums_counters_and_keeps_last_gauge(self):
        regs = []
        for pid in range(3):
            reg = MetricsRegistry()
            reg.counter("folds_total").inc(10 * (pid + 1))
            reg.gauge("queue_depth").set(pid)
            regs.append(reg)
        merged = merge_snapshots([r.snapshot() for r in regs],
                                 gauge_keys=regs[0].gauge_keys())
        assert merged["folds_total"] == 60.0
        assert merged["queue_depth"] == 2.0  # last process's read, not sum

    def test_histogram_rejects_bad_edges(self):
        with pytest.raises(ValueError, match="strictly increasing"):
            Histogram("h", buckets=(1.0, 1.0, 2.0))
        with pytest.raises(ValueError, match="strictly increasing"):
            Histogram("h", buckets=())


class TestHistogramQuantiles:
    def test_quantiles_track_numpy_within_bucket_width(self):
        """Linear interpolation inside the winning bucket: the error
        bound is that bucket's width, checked against exact numpy
        percentiles on a seeded latency-like sample."""
        rng = np.random.default_rng(0)
        samples = rng.lognormal(mean=-3.0, sigma=1.2, size=4000)
        samples = samples[samples < DEFAULT_BUCKETS[-1]]
        h = Histogram("lat")
        for x in samples:
            h.observe(float(x))
        edges = (0.0,) + DEFAULT_BUCKETS
        for q in (0.1, 0.5, 0.9, 0.99):
            exact = float(np.percentile(samples, 100 * q))
            approx = h.quantile(q)
            i = int(np.searchsorted(DEFAULT_BUCKETS, exact))
            width = edges[i + 1] - edges[i]
            assert abs(approx - exact) <= width, (q, exact, approx)

    def test_edge_cases(self):
        h = Histogram("h", buckets=(1.0, 2.0))
        assert math.isnan(h.quantile(0.5))
        h.observe(10.0)  # only the overflow bucket populated
        assert h.quantile(0.5) == 2.0  # top finite edge: no upper bound
        with pytest.raises(ValueError, match="outside"):
            h.quantile(1.5)


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


class TestTracer:
    def test_span_records_valid_chrome_trace(self):
        tr = Tracer(annotate=False)
        with tr.span(SPAN_APPLY, version=3):
            pass
        doc = tr.to_json()
        assert validate_trace(doc) == 1
        ev = doc["traceEvents"][0]
        assert ev["name"] == SPAN_APPLY and ev["ph"] == "X"
        assert ev["dur"] >= 0 and ev["args"] == {"version": 3}
        assert ev["pid"] == os.getpid()

    def test_bounded_tracer_keeps_the_newest_events(self):
        tr = Tracer(annotate=False, capacity=4)
        for i in range(10):
            with tr.span(SPAN_APPLY, i=i):
                pass
        assert [ev["args"]["i"] for ev in tr.events] == [6, 7, 8, 9]
        doc = tr.to_json()  # the export holds what the ring holds
        assert validate_trace(doc) == 4
        assert doc["traceEvents"][0]["args"]["i"] == 6

    def test_default_tracer_is_one_bounded_recorder(self):
        tr = default_tracer()
        assert tr is default_tracer()
        assert tr.enabled and tr.annotate
        assert tr.events.maxlen == DEFAULT_CAPACITY

    def test_span_args_set_inside(self):
        tr = Tracer(annotate=False)
        with tr.span(SPAN_STAGE, rounds=2) as sp:
            sp.set(bytes=64)
        assert tr.events[0]["args"] == {"rounds": 2, "bytes": 64}
        with NULL_TRACER.span(SPAN_STAGE) as sp:
            sp.set(bytes=64)  # a no-op on the disabled tracer

    def test_annotations_reach_the_profiler_host_plane(self, tmp_path):
        """Each span opens ``repro.<span>`` on the profiler's timeline,
        apart from the benchmark's and JAX's own events."""
        from jax.profiler import ProfileData

        tr = Tracer()
        jax.profiler.start_trace(str(tmp_path))
        try:
            with tr.span(SPAN_APPLY):
                jnp.ones(3).block_until_ready()
        finally:
            jax.profiler.stop_trace()
        (path,) = tmp_path.glob("**/*.xplane.pb")
        pd = ProfileData.from_file(str(path))
        names = {ev.name for plane in pd.planes
                 if plane.name.startswith("/host:")
                 for line in plane.lines for ev in line.events}
        assert ANNOTATION_PREFIX + SPAN_APPLY in names

    def test_disabled_tracer_is_free(self):
        tr = Tracer(enabled=False)
        assert tr.span("x") is _NULL_SPAN  # the ONE shared no-op context
        assert NULL_TRACER.span("y") is _NULL_SPAN
        with tr.span("x"):
            pass
        tr.complete("x", 0.0, 1.0)
        assert not tr.events

    def test_retroactive_complete(self):
        tr = Tracer(annotate=False)
        t0 = tr.now()
        tr.complete(SPAN_COLLECT, t0, 0.25)
        (ev,) = tr.events
        assert ev["dur"] == pytest.approx(0.25e6)

    def test_write_and_validate_roundtrip(self, tmp_path):
        tr = Tracer(annotate=False)
        with tr.span(SPAN_APPLY):
            pass
        path = tr.write(str(tmp_path / "t.json"))
        with open(path) as f:
            doc = json.load(f)
        assert validate_trace(doc) == 1
        assert doc["displayTimeUnit"] == "ms"

    def test_validate_rejects_malformed(self):
        with pytest.raises(ValueError, match="traceEvents"):
            validate_trace({"events": []})
        with pytest.raises(ValueError, match="missing"):
            validate_trace({"traceEvents": [{"ph": "X"}]})
        bad = {"traceEvents": [{"name": "a", "ph": "X", "ts": 0,
                                "pid": 1, "tid": 0, "dur": -1}]}
        with pytest.raises(ValueError, match="non-negative dur"):
            validate_trace(bad)

    def test_span_coverage_union(self):
        def ev(name, ts, dur):
            return {"name": name, "cat": "round", "ph": "X", "ts": ts,
                    "dur": dur, "pid": 1, "tid": 0}

        # [0, 40) covered out of [0, 50): overlap must not double-count
        doc = {"traceEvents": [ev(SPAN_COLLECT, 0, 30),
                               ev(SPAN_APPLY, 20, 20),
                               ev(SPAN_APPLY, 45, 5),
                               ev("other", 0, 50)]}
        assert span_coverage(doc) == pytest.approx(0.9)
        assert span_coverage({"traceEvents": []}) == 0.0


# ---------------------------------------------------------------------------
# sinks + logging
# ---------------------------------------------------------------------------


class TestSinks:
    def test_jsonl_sink_writes_lines(self, tmp_path):
        path = str(tmp_path / "m.jsonl")
        sink = JsonlSink(path, gate=lambda: True)
        sink.emit({"event": "a", "n": 1})
        sink.emit({"event": "b"})
        sink.close()
        lines = [json.loads(ln) for ln in open(path)]
        assert [ln["event"] for ln in lines] == ["a", "b"]
        assert all("t" in ln for ln in lines)  # wall-clock stamp

    def test_gated_out_sink_never_creates_the_file(self, tmp_path):
        path = str(tmp_path / "m.jsonl")
        sink = JsonlSink(path, gate=lambda: False)
        sink.emit({"event": "a"})
        sink.flush()
        sink.close()
        assert not os.path.exists(path)  # lazy open: no create, no truncate

    def test_emit_snapshot_shape(self):
        reg = MetricsRegistry()
        reg.counter("x_total").inc(4)
        sink = InMemorySink()
        emit_snapshot(sink, reg, version=7)
        (ev,) = sink.events
        assert ev["event"] == "metrics_snapshot" and ev["version"] == 7
        assert ev["metrics"] == {"x_total": 4.0}

    def test_configure_logging_idempotent(self):
        root = logging.getLogger()
        configure_logging("info")
        n = len(root.handlers)
        configure_logging("debug")
        assert len(root.handlers) == n  # later calls only move the level
        assert root.level == logging.DEBUG
        configure_logging("warning")
        with pytest.raises(ValueError, match="unknown log level"):
            configure_logging("loud")


# ---------------------------------------------------------------------------
# serving migration: metrics() parity + trace coverage
# ---------------------------------------------------------------------------


def _quad_loss(params, batch):
    x, y = batch
    return jnp.mean((x @ params["w"] - y) ** 2), {}


def _quad_batch(key, n=8, d=4):
    k1, k2 = jax.random.split(key)
    x = jax.random.normal(k1, (n, d))
    y = x @ jnp.arange(1.0, d + 1.0) + 0.01 * jax.random.normal(k2, (n,))
    return x, y


PARAMS = {"w": jnp.array([1.0, -1.0, 0.5, 2.0])}


def _upload(ctrl, i, tau=0, t=0.0):
    from repro.core.serving import Upload

    key = jax.random.PRNGKey(0)
    b = _quad_batch(jax.random.fold_in(key, i))
    return Upload(client_id=i, base_version=ctrl.version - tau,
                  data_size=10.0,
                  batch=jax.tree.map(lambda x: x[None], b),
                  probe=_quad_batch(jax.random.fold_in(key, 100 + i)),
                  sent_at=t)


class TestServingRegistryParity:
    """The counters moved onto an obs registry; the historical
    ``metrics()`` dict shape must be unchanged, and the registry
    snapshot must mirror every counter."""

    SERIES = {
        "admitted": "serve_admitted_total",
        "rejected_queue_full": "serve_rejected_total{reason=queue_full}",
        "dropped_stale_ingress": "serve_dropped_total{reason=stale_ingress}",
        "dropped_stale_queue": "serve_dropped_total{reason=stale_queue}",
        "folded": "serve_folded_total",
        "rounds": "serve_rounds_total",
    }

    def _exercised_controller(self):
        """A seeded stream that hits EVERY admission outcome: admit,
        queue-full reject, stale-at-ingress drop, stale-in-queue drop."""
        fl = FLConfig(buffer_size=4, local_steps=1, local_lr=0.1,
                      max_staleness=4)
        reg = MetricsRegistry()
        ctrl = ServingController(
            _quad_loss, PARAMS, fl,
            ServeConfig(queue_capacity=4, service_time=0.25, adapt_every=0,
                        retry_after_min=0.1),
            registry=reg)
        expect = dict.fromkeys(self.SERIES, 0)
        for i in range(8):  # burst past capacity: 4 admitted, 4 rejected
            adm = ctrl.offer(_upload(ctrl, i, t=0.0), now=0.0)
            expect["admitted" if adm.accepted
                   else "rejected_queue_full"] += 1
            ctrl.pump(0.0)
        ctrl.pump(4 * 0.25)  # drain: one full round folds + applies
        expect["folded"] += 4
        expect["rounds"] += 1
        adm = ctrl.offer(_upload(ctrl, 0, tau=fl.max_staleness + 1), now=2.0)
        assert not adm.accepted
        expect["dropped_stale_ingress"] += 1
        assert ctrl.offer(_upload(ctrl, 1, tau=fl.max_staleness),
                          now=2.0).accepted
        expect["admitted"] += 1
        ctrl.version += 1  # queue head out-ages before service
        ctrl.offer(_upload(ctrl, 2, tau=0), now=2.1)
        expect["admitted"] += 1
        expect["dropped_stale_queue"] += 1
        assert all(v > 0 for v in expect.values()), expect
        return ctrl, reg, expect

    def test_counters_match_independent_accounting(self):
        ctrl, reg, expect = self._exercised_controller()
        assert ctrl.counters == expect
        snap = reg.snapshot()
        for dict_key, series in self.SERIES.items():
            assert snap[series] == float(expect[dict_key]), series

    def test_metrics_dict_shape_unchanged(self):
        ctrl, _, expect = self._exercised_controller()
        m = ctrl.metrics()
        for key in (*expect, "k", "k_history", "version", "arrival_rate",
                    "round_latency_p50", "round_latency_p99",
                    "round_cadence_mean", "queue_depth_now",
                    "queue_depth_max"):
            assert key in m, key
        assert m["admitted"] == expect["admitted"]
        assert isinstance(m["admitted"], int)  # not a float counter leak

    def test_gauges_and_latency_histogram_populated(self):
        ctrl, reg, _ = self._exercised_controller()
        snap = reg.snapshot()
        assert snap["serve_k"] == float(ctrl.k)
        assert snap["serve_queue_depth"] == float(len(ctrl.queue))
        assert snap["serve_round_latency_seconds_count"] == float(
            len(ctrl.round_latencies))

    def test_private_registries_do_not_alias(self):
        fl = FLConfig(buffer_size=4, local_steps=1, local_lr=0.1,
                      max_staleness=4)
        a = ServingController(_quad_loss, PARAMS, fl, ServeConfig())
        b = ServingController(_quad_loss, PARAMS, fl, ServeConfig())
        a.offer(_upload(a, 0), now=0.0)
        assert a.counters["admitted"] == 1
        assert b.counters["admitted"] == 0


class TestServeTraceCoverage:
    def test_round_spans_cover_measured_walltime(self):
        """The acceptance gate for serve_fl --trace-out, in-process:
        collect_window/apply spans tile >= 95% of the round window."""
        sc = get_scenario("paper-fig1")
        n = 8
        clients, _ = sc.make_dataset(n, samples_per_client=16, seed=0)
        fl = FLConfig(num_clients=n, buffer_size=3, max_staleness=6,
                      local_steps=1, batch_size=4)

        def loss(params, batch):
            x, y = batch
            x = x.reshape(x.shape[0], -1)
            return jnp.mean((x @ params["w"] - y) ** 2), {}

        tracer = Tracer(annotate=False)
        ctrl = ServingController(loss, {"w": jnp.zeros(784)}, fl,
                                 ServeConfig(queue_capacity=8),
                                 tracer=tracer)
        gen = TrafficGenerator(clients, sc.behavior(n, seed=0), fl)
        hook_versions = []
        serve_stream(ctrl, gen, max_rounds=4,
                     round_hook=hook_versions.append)
        assert hook_versions == [1, 2, 3, 4]  # once per applied round
        doc = tracer.to_json()
        validate_trace(doc)
        names = {ev["name"] for ev in doc["traceEvents"]}
        assert names <= set(SPAN_NAMES)
        assert {"collect_window", "contribute", "apply"} <= names
        assert span_coverage(doc) >= 0.95


# ---------------------------------------------------------------------------
# engine migration: dispatch counter backs num_launches
# ---------------------------------------------------------------------------


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


class TestEngineDispatchCounter:
    FL = FLConfig(num_clients=6, buffer_size=3, local_steps=2,
                  local_lr=0.05, batch_size=8, max_staleness=4)

    def test_num_launches_is_a_registry_view(self):
        reg = MetricsRegistry()
        tracer = Tracer(annotate=False)
        res = run_vectorized(_quad_loss, {"w": jnp.zeros(4)},
                             _quad_clients(), self.FL, total_rounds=10,
                             seed=0, rounds_per_launch=4, registry=reg,
                             tracer=tracer)
        snap = reg.snapshot()
        assert res.num_launches == 3  # ceil(10 / 4)
        assert snap["engine_dispatches_total"] == 3.0
        # one apply span per dispatch
        assert [ev["name"] for ev in tracer.events].count(SPAN_APPLY) == 3
        assert snap["engine_host_syncs_total"] >= 1.0  # the round-log fetch

    def test_counter_accumulates_but_result_delta_does_not(self):
        """Two runs on one registry: the counter keeps global totals,
        each SimResult reports only its own dispatches."""
        reg = MetricsRegistry()
        for _ in range(2):
            res = run_vectorized(_quad_loss, {"w": jnp.zeros(4)},
                                 _quad_clients(), self.FL, total_rounds=8,
                                 seed=0, rounds_per_launch=4, registry=reg)
            assert res.num_launches == 2
        assert reg.snapshot()["engine_dispatches_total"] == 4.0

    def test_engine_emits_round_spans(self):
        tracer = Tracer(annotate=False)
        run_vectorized(_quad_loss, {"w": jnp.zeros(4)}, _quad_clients(),
                       self.FL, total_rounds=8, seed=0, rounds_per_launch=4,
                       registry=MetricsRegistry(), tracer=tracer)
        doc = tracer.to_json()
        validate_trace(doc)
        by_name = {}
        for ev in doc["traceEvents"]:
            by_name.setdefault(ev["name"], []).append(ev)
        assert len(by_name[SPAN_APPLY]) == 2  # one per dispatch
        assert SPAN_COLLECT in by_name and "host_sync" in by_name


# ---------------------------------------------------------------------------
# engine spans: the default recorder, counts as args, coverage
# ---------------------------------------------------------------------------


# the spans a simulator engine call is made of, entry to return
ENGINE_SPANS = (SPAN_PREPARE, SPAN_COLLECT, SPAN_GATHER, SPAN_STAGE,
                SPAN_APPLY, SPAN_HOST_SYNC, SPAN_CHECKPOINT)


def _by_name(events):
    out = {}
    for ev in events:
        out.setdefault(ev["name"], []).append(ev)
    return out


def _call_coverage(events, t0_us, t1_us):
    """Union of the engine spans over one call's wall time [t0, t1)."""
    ivs = sorted((ev["ts"], ev["ts"] + ev["dur"]) for ev in events
                 if ev["name"] in ENGINE_SPANS)
    covered, hi = 0.0, t0_us
    for lo, end in ivs:
        lo = max(lo, hi)
        if end > lo:
            covered += end - lo
            hi = end
    return covered / (t1_us - t0_us)


def _timed_call(tracer, fn, **kw):
    """Run one engine call; returns (result, its events, coverage)."""
    start = len(tracer.events)
    t0 = (tracer.now() - tracer._t0) * 1e6
    res = fn(tracer=tracer, **kw)
    t1 = (tracer.now() - tracer._t0) * 1e6
    events = list(tracer.events)[start:]
    return res, events, _call_coverage(events, t0, t1)


class TestEngineSpans:
    FL = FLConfig(num_clients=6, buffer_size=3, local_steps=2,
                  local_lr=0.05, batch_size=8, max_staleness=4)
    D = 4

    def _vec(self, fl=None, clients=None, **kw):
        kw.setdefault("rounds_per_launch", 4)
        kw.setdefault("registry", MetricsRegistry())
        return functools.partial(
            run_vectorized, _quad_loss, {"w": jnp.zeros(self.D)},
            clients or _quad_clients(d=self.D), fl or self.FL, seed=0,
            capture_state=True, **kw)

    def _pop(self, **kw):
        return functools.partial(
            run_population, _quad_loss, {"w": jnp.zeros(self.D)},
            _quad_clients(d=self.D), self.FL, seed=0,
            scenario=get_scenario("paper-fig1"), rounds_per_launch=4,
            capture_state=True, registry=MetricsRegistry(), **kw)

    def test_vectorized_records_every_engine_span(self):
        tr = Tracer(annotate=False)
        _, events, cover = _timed_call(tr, self._vec(), total_rounds=16)
        by = _by_name(events)
        assert set(by) == set(ENGINE_SPANS)
        assert [len(by[n]) for n in (SPAN_PREPARE, SPAN_HOST_SYNC,
                                     SPAN_CHECKPOINT)] == [1, 1, 1]
        for name in (SPAN_COLLECT, SPAN_GATHER, SPAN_STAGE, SPAN_APPLY):
            assert [ev["args"]["rounds"] for ev in by[name]] == [4] * 4
        assert cover >= 0.9, cover

    def test_population_records_its_spans(self):
        tr = Tracer(annotate=False)
        _, events, cover = _timed_call(tr, self._pop(), total_rounds=16)
        by = _by_name(events)
        # no host walk: the windows are chosen on the device
        assert set(by) == {SPAN_PREPARE, SPAN_APPLY, SPAN_HOST_SYNC,
                           SPAN_CHECKPOINT}
        assert len(by[SPAN_APPLY]) == 4 and len(by[SPAN_PREPARE]) == 1
        assert cover >= 0.9, cover

    def test_engines_record_into_the_default_tracer(self):
        tr = default_tracer()
        for fn in (self._vec(), self._pop()):
            start = len(tr.events)
            fn(total_rounds=4)
            names = {ev["name"] for ev in list(tr.events)[start:]}
            assert {SPAN_PREPARE, SPAN_APPLY, SPAN_HOST_SYNC} <= names

    @pytest.mark.parametrize("k", [2, 5])
    def test_span_count_follows_launches_not_uploads(self, k):
        """K (uploads a window) does not move the count; the number of
        launches does."""
        fl = FLConfig(num_clients=6, buffer_size=k, local_steps=2,
                      local_lr=0.05, batch_size=8, max_staleness=4)
        counts = []
        for rounds in (8, 16):
            tr = Tracer(annotate=False)
            self._vec(fl)(total_rounds=rounds, tracer=tr)
            counts.append(len(tr.events))
        launches = (2, 4)
        # four spans a launch, three a call
        assert counts == [4 * n + 3 for n in launches]

    def test_stage_bytes_are_the_chunk_arguments(self):
        """A launch stages int32 batch rows into the device pool, never
        the samples themselves."""
        tr = Tracer(annotate=False)
        self._vec()(total_rounds=8, tracer=tr)
        fl, s = self.FL, 4
        k, m, b = fl.buffer_size, fl.local_steps, fl.batch_size
        per_round = 4 * (k                    # base slots, int32
                         + k * m * b          # train batch rows, int32
                         + k * b              # probe rows, int32
                         + k + k              # sizes, taus
                         + 1)                 # the new ring slot
        stages = [ev for ev in tr.events if ev["name"] == SPAN_STAGE]
        assert [ev["args"]["bytes"] for ev in stages] == [s * per_round] * 2

    def test_prepare_bytes_count_the_restored_state(self):
        tr = Tracer(annotate=False)
        clients = _quad_clients(d=self.D)
        half = self._vec(clients=clients)(total_rounds=4, tracer=tr)
        self._vec(clients=clients)(total_rounds=8, tracer=tr,
                                   init_state=half.final_state)
        prep = [ev for ev in tr.events if ev["name"] == SPAN_PREPARE]
        st = half.final_state
        ring = np.asarray(st.ring).nbytes
        pool = sum(c.x.nbytes + c.y.nbytes for c in clients)
        # fresh: the start params are already on the device, the pool is
        # placed; resumed: the pool is the one placed before
        assert [ev["args"]["bytes"] for ev in prep] == [
            pool, ring + np.asarray(st.params["w"]).nbytes]

    def test_resume_places_the_pool_once(self):
        reg = MetricsRegistry()
        clients = _quad_clients(d=self.D)
        half = self._vec(clients=clients)(total_rounds=4, registry=reg)
        self._vec(clients=clients)(total_rounds=8, registry=reg,
                                   init_state=half.final_state)
        snap = reg.snapshot()
        assert snap["engine_pool_puts_total"] == 1.0
        assert snap["engine_pool_hits_total"] == 1.0

    def test_new_client_arrays_place_a_new_pool(self):
        reg = MetricsRegistry()
        clients = _quad_clients(d=self.D)
        self._vec(clients=clients)(total_rounds=4, registry=reg)
        # same samples, new arrays: the pool is placed again
        fresh = [dataclasses.replace(c, x=c.x.copy(), y=c.y.copy())
                 for c in clients]
        self._vec(clients=fresh)(total_rounds=4, registry=reg)
        snap = reg.snapshot()
        assert snap["engine_pool_puts_total"] == 2.0
        assert snap.get("engine_pool_hits_total", 0.0) == 0.0

    @pytest.mark.parametrize("engine", ["vectorized", "population"])
    def test_resume_stays_bit_identical(self, engine):
        """Recording into the default tracer leaves a resumed run
        bit-identical to the uninterrupted one."""
        make = self._vec if engine == "vectorized" else self._pop
        full = make()(total_rounds=8)
        half = make()(total_rounds=4)
        resumed = make()(total_rounds=8, init_state=half.final_state)
        assert [r["clients"] for r in resumed.round_log] == \
            [r["clients"] for r in full.round_log]
        for a, b in zip(resumed.round_log, full.round_log):
            np.testing.assert_array_equal(np.asarray(a["weights"]),
                                          np.asarray(b["weights"]))
        np.testing.assert_array_equal(
            np.asarray(resumed.final_state.params["w"]),
            np.asarray(full.final_state.params["w"]))
