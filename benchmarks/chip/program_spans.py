"""The program's own spans, for the per-layer metrics that read them.

The simulator engines record each call's phases into the program's
process-wide flight recorder (``repro.obs.trace.default_tracer``):
``prepare``, then per launch ``collect_window`` (the host event walk),
``gather`` (client batch draws), ``stage`` (stacking the launch's
arguments, arg ``bytes``) and ``apply`` (the dispatch), then
``host_sync`` (the round-log fetch, which waits for the launches the
host ran ahead with) and ``checkpoint``; ``prepare`` carries ``bytes``
too.
Events are Chrome trace events: ``ts`` and ``dur`` in microseconds.

A run's window is the last the program records: the check after it
replays only the reference. So the window's events are those that start
within the last ``run["seconds"]`` of the recorder, measured back from
the end of its last span; the set-up calls' spans end before the window
opens. A program without the recorder, or a run that recorded nothing,
gives ``None``, and so does every reader built on this.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence


def recorder_events() -> Optional[List[dict]]:
    """Every event the program's recorder holds, or None without one."""
    try:
        from repro.obs import trace
    except ImportError:
        return None
    get = getattr(trace, "default_tracer", None)
    return None if get is None else list(get().events)


def window_events(events: Iterable[dict], seconds: float) -> List[dict]:
    """The complete events that start within the last ``seconds``,
    measured back from the end of the last one."""
    done = [ev for ev in events if ev.get("ph") == "X"]
    if not done:
        return []
    lo = max(ev["ts"] + ev["dur"] for ev in done) - seconds * 1e6
    return [ev for ev in done if ev["ts"] >= lo]


def self_times(events: Iterable[dict]) -> Dict[str, float]:
    """Microseconds by span name, each span less the spans nested inside
    it on the same thread lane."""
    out: Dict[str, float] = {}
    lanes: Dict[tuple, List[dict]] = {}
    for ev in events:
        lanes.setdefault((ev.get("pid"), ev.get("tid")), []).append(ev)
    for lane in lanes.values():
        stack: List[list] = []  # open spans: [name, end, nested us, dur]

        def close():
            name, _, nested, dur = stack.pop()
            out[name] = out.get(name, 0.0) + dur - nested

        for ev in sorted(lane, key=lambda e: (e["ts"], -e["dur"])):
            start, end = ev["ts"], ev["ts"] + ev["dur"]
            while stack and stack[-1][1] <= start:
                close()
            if stack:
                stack[-1][2] += ev["dur"]
            stack.append([ev["name"], end, 0.0, ev["dur"]])
        while stack:
            close()
    return out


def arg_sum(events: Iterable[dict], names: Sequence[str], arg: str) -> float:
    """The sum of ``arg`` over the spans named in ``names``."""
    return float(sum(ev.get("args", {}).get(arg, 0) for ev in events
                     if ev["name"] in names))


def window(run: dict) -> Optional[List[dict]]:
    """The run's window of program events, or None if there is none."""
    events = recorder_events()
    if not events or not run["counts"].get("rounds"):
        return None
    return window_events(events, run["seconds"]) or None


def ms_per_round(run: dict, names: Sequence[str]) -> Optional[float]:
    """The self time of the spans ``names`` over the window, in ms per
    round completed; None where the window holds none of them."""
    events = window(run)
    if events is None:
        return None
    st = self_times(events)
    if not any(n in st for n in names):
        return None
    return sum(st.get(n, 0.0) for n in names) / 1e3 / run["counts"]["rounds"]
