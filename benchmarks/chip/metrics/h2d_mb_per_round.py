"""h2d_mb_per_round.<cell kind>: host-to-device bytes the engine hands
over, the ``bytes`` args of the ``stage`` (each launch's arguments) and
``prepare`` (each call's restored state) spans in the window, in 10^6
bytes per round. An exact count of array sizes."""
import program_spans

SPANS = ("stage", "prepare")


def read(run):
    events = program_spans.window(run)
    if events is None or not any(ev["name"] in SPANS for ev in events):
        return None
    return (program_spans.arg_sum(events, SPANS, "bytes") / 1e6
            / run["counts"]["rounds"])
