"""Round-lifecycle span tracing: Chrome-trace JSON + device-profile hooks.

``Tracer.span(name)`` times a host-side phase of the round lifecycle and
records it as a Chrome trace event (``ph: "X"`` complete event, micro-
second timestamps) loadable in perfetto / ``chrome://tracing``. The span
taxonomy is fixed (DESIGN.md §9) so traces from every runner line up:

    prepare          engine call entry -> first launch: behaviour, pool,
                     statics, ring and state placed or restored
    collect_window   host event walk (engine) / serving wait
    gather           the engine's client batch draws and gathers
    stage            stacking the launch's chunk arguments (and placing
                     them on a process-spanning mesh)
    contribute       one streaming fold (serving path)
    apply            the jitted round dispatch (engine chunk / eq. 5)
    host_sync        device -> host fetches (round log, eval metrics)
    checkpoint       state capture + write
    transport_decode wire-frame decode on a transport worker (§12)
    transport_offer  admission call on a transport worker (decode->offer)

Spans carry counts as args (``rounds``, ``bytes`` put on the device).
Each span also opens a ``jax.profiler.TraceAnnotation`` named
``repro.<span>`` (a no-op unless a profiler session is active), so a
device profile shows the program's host spans on the same timeline as
the XLA ops they dispatched, apart from the profiler's own events.

``default_tracer()`` is the engines' process-wide flight recorder: it
keeps the newest ``DEFAULT_CAPACITY`` events in a ring, so it can stay
on in every run with bounded memory; ``Tracer()`` keeps every event
(``serve_fl --trace-out``).

Overhead contract: a disabled tracer (``Tracer(enabled=False)``, or the
module ``NULL_TRACER``) returns one shared no-op context manager from
``span`` — no allocation, no clock read. A recording span with no
profiler session costs 3.4-4.6 us on the host, its annotation about
1.5 us of that (enter + exit of a span with two args, five runs of
200,000 on a shared x86 CPU, Python 3.12, jax 0.9.0). The engines open
at most four per launch, three per call and one per eval, against
milliseconds of host work a launch.
"""
from __future__ import annotations

import collections
import json
import os
import time
from typing import Any, Dict, Optional

SPAN_PREPARE = "prepare"
SPAN_COLLECT = "collect_window"
SPAN_GATHER = "gather"
SPAN_STAGE = "stage"
SPAN_CONTRIBUTE = "contribute"
SPAN_APPLY = "apply"
SPAN_HOST_SYNC = "host_sync"
SPAN_CHECKPOINT = "checkpoint"
SPAN_TRANSPORT_DECODE = "transport_decode"
SPAN_TRANSPORT_OFFER = "transport_offer"
SPAN_NAMES = (SPAN_PREPARE, SPAN_COLLECT, SPAN_GATHER, SPAN_STAGE,
              SPAN_CONTRIBUTE, SPAN_APPLY, SPAN_HOST_SYNC, SPAN_CHECKPOINT,
              SPAN_TRANSPORT_DECODE, SPAN_TRANSPORT_OFFER)
# the profiler annotation of span ``x`` is ``ANNOTATION_PREFIX + x``
ANNOTATION_PREFIX = "repro."
# the default recorder's ring: a 30 s window of the host-walk engine
# records about 2,000 events
DEFAULT_CAPACITY = 1 << 15


def _annotation(name: str):
    """A jax.profiler.TraceAnnotation for one span.

    Imported lazily so ``repro.obs`` stays importable without jax for a
    tracer built with ``annotate=False``; annotations are cheap no-ops
    when no profiler session is active.
    """
    import jax

    return jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + name)


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args) -> None:
        pass


_NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("tracer", "name", "cat", "args", "t0", "ann")

    def __init__(self, tracer: "Tracer", name: str, cat: str,
                 args: Dict[str, Any]):
        self.tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args

    def __enter__(self):
        self.ann = _annotation(self.name) if self.tracer.annotate else None
        if self.ann is not None:
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self.ann is not None:
            self.ann.__exit__(*exc)
        self.tracer.complete(self.name, self.t0, t1 - self.t0,
                             cat=self.cat, **self.args)
        return False

    def set(self, **args) -> None:
        """Add args known only inside the span (a byte count)."""
        self.args.update(args)


class Tracer:
    """Collects Chrome trace events; host wall-clock, microsecond units.

    ``t0`` (the first construction instant) anchors the timeline so
    ``ts`` values stay small; every event carries ``pid`` (the OS pid —
    jax process index when available would alias on one host) and a
    caller-chosen ``tid`` lane (default 0 — the runners are
    single-threaded host loops, so lanes separate *subsystems*, not
    threads).

    ``capacity`` bounds the events kept to the newest ``capacity`` (a
    ring); ``None`` keeps them all.
    """

    def __init__(self, enabled: bool = True, annotate: bool = True,
                 tid: int = 0, capacity: Optional[int] = None):
        self.enabled = enabled
        self.annotate = annotate and enabled
        self.tid = tid
        self.events = collections.deque(maxlen=capacity)
        self.pid = os.getpid()
        self._t0 = time.perf_counter()

    # -- recording ------------------------------------------------------
    def span(self, name: str, cat: str = "round", **args):
        """Context manager timing one phase; no-op when disabled."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, cat, args)

    def now(self) -> float:
        """The tracer clock (perf_counter seconds) for retroactive events."""
        return time.perf_counter()

    def complete(self, name: str, t_start: float, duration: float,
                 cat: str = "round", **args) -> None:
        """Record a span retroactively from explicit clock readings —
        how the serving loop emits ``collect_window`` (its extent is only
        known once the K-th fold lands)."""
        if not self.enabled:
            return
        self.events.append({
            "name": name, "cat": cat, "ph": "X",
            "ts": (t_start - self._t0) * 1e6, "dur": duration * 1e6,
            "pid": self.pid, "tid": self.tid,
            **({"args": args} if args else {})})

    # -- export ---------------------------------------------------------
    def to_json(self) -> Dict[str, Any]:
        return {"traceEvents": list(self.events), "displayTimeUnit": "ms"}

    def write(self, path: str) -> str:
        doc = self.to_json()
        validate_trace(doc)
        with open(path, "w") as f:
            json.dump(doc, f)
        return path


NULL_TRACER = Tracer(enabled=False)
_DEFAULT_TRACER: Optional[Tracer] = None


def default_tracer() -> Tracer:
    """The process-wide flight recorder the simulator engines record into
    when no tracer is passed: a ``Tracer`` holding its newest
    ``DEFAULT_CAPACITY`` events."""
    global _DEFAULT_TRACER
    if _DEFAULT_TRACER is None:
        _DEFAULT_TRACER = Tracer(capacity=DEFAULT_CAPACITY)
    return _DEFAULT_TRACER


def validate_trace(doc: Dict[str, Any]) -> int:
    """Assert ``doc`` is loadable Chrome-trace-event JSON; returns the
    event count. The schema the CI smoke lane gates serve_fl's
    ``--trace-out`` against: the JSON-object form with a ``traceEvents``
    list where every complete event carries name/ph/ts/pid/tid and a
    non-negative ``dur``."""
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        raise ValueError("trace must be a JSON object with 'traceEvents'")
    events = doc["traceEvents"]
    if not isinstance(events, list):
        raise ValueError("'traceEvents' must be a list")
    for i, ev in enumerate(events):
        for field in ("name", "ph", "ts", "pid", "tid"):
            if field not in ev:
                raise ValueError(f"event {i} missing {field!r}: {ev}")
        if not isinstance(ev["ts"], (int, float)):
            raise ValueError(f"event {i}: non-numeric ts {ev['ts']!r}")
        if ev["ph"] == "X":
            if not isinstance(ev.get("dur"), (int, float)) or ev["dur"] < 0:
                raise ValueError(f"event {i}: complete event needs a "
                                 f"non-negative dur, got {ev.get('dur')!r}")
    return len(events)


def span_coverage(doc: Dict[str, Any], names=(SPAN_COLLECT, SPAN_APPLY),
                  cat: Optional[str] = "round") -> float:
    """Fraction of the round-lifecycle wall-span covered by the union of
    the named spans — the acceptance metric for serve_fl --trace-out
    (>= 0.95). The denominator runs from the first to the last named
    event, i.e. the measured round window, not process startup."""
    ivs = sorted(
        (ev["ts"], ev["ts"] + ev["dur"]) for ev in doc["traceEvents"]
        if ev.get("ph") == "X" and ev["name"] in names
        and (cat is None or ev.get("cat") == cat))
    if not ivs:
        return 0.0
    total = max(hi for _, hi in ivs) - ivs[0][0]
    if total <= 0:
        return 1.0
    covered, cur_lo, cur_hi = 0.0, ivs[0][0], ivs[0][0]
    for lo, hi in ivs:
        if lo > cur_hi:
            covered += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        else:
            cur_hi = max(cur_hi, hi)
    covered += cur_hi - cur_lo
    return covered / total


class WindowedProfiler:
    """Windowed ``jax.profiler`` capture: a full device profile every
    ``every`` rounds, ``window`` rounds long, written under
    ``profile_dir/round_<n>``. Combined with the per-span
    ``TraceAnnotation`` this lines device timelines up with the host
    spans; windowing keeps always-on services from growing unbounded
    profiles. ``every=0`` disables (the default)."""

    def __init__(self, profile_dir: Optional[str], every: int = 0,
                 window: int = 1):
        if every and window < 1:
            raise ValueError("profiler window must be >= 1 round")
        self.profile_dir = profile_dir
        self.every = every if profile_dir else 0
        self.window = window
        self._active_until: Optional[int] = None

    def on_round(self, round_idx: int) -> None:
        """Call once per completed round with its index."""
        if not self.every:
            return
        import jax

        if self._active_until is None and round_idx % self.every == 0:
            path = os.path.join(self.profile_dir, f"round_{round_idx}")
            os.makedirs(path, exist_ok=True)
            jax.profiler.start_trace(path)
            self._active_until = round_idx + self.window
        elif self._active_until is not None \
                and round_idx >= self._active_until:
            jax.profiler.stop_trace()
            self._active_until = None

    def close(self) -> None:
        if self._active_until is not None:
            import jax

            jax.profiler.stop_trace()
            self._active_until = None
