"""The readers of the program's own spans, and the kernel roofline.

On hand-built events: the window is what starts within the run's last
``seconds`` of the recorder, and a span's self time leaves out the
spans nested in it. On the CPU, each simulator cell at the sizes its
files give for a test run: the readers see the window's calls and none
of the set-up calls. On a hand-built reduction: the fused kernel's
roofline share reads its ops by the name its scope gives them.
"""
import copy

import pytest

import common
import program_spans
from trace_reduce import Reduced

SEED = 2 ** 31 + 977


def ev(name, ts, dur, tid=0, **args):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "pid": 1,
            "tid": tid, **({"args": args} if args else {})}


def test_window_is_measured_back_from_the_last_span():
    events = [ev("prepare", 0, 10), ev("apply", 10, 5),
              ev("prepare", 2_000_000, 10), ev("apply", 2_000_010, 30),
              {"name": "marker", "ph": "i", "ts": 2_000_050, "pid": 1,
               "tid": 0}]
    got = program_spans.window_events(events, 1.0)
    assert [e["ts"] for e in got] == [2_000_000, 2_000_010]
    assert program_spans.window_events([], 1.0) == []


def test_self_time_leaves_out_nested_spans():
    events = [ev("collect_window", 0, 100),
              ev("gather", 20, 30),          # nested in collect_window
              ev("gather", 60, 10),          # nested too
              ev("apply", 100, 50),          # starts at the end: a sibling
              ev("apply", 40, 20, tid=1)]    # another lane: not nested
    st = program_spans.self_times(events)
    assert st == {"collect_window": 60.0, "gather": 40.0, "apply": 70.0}


def test_arg_sum():
    events = [ev("stage", 0, 1, bytes=100), ev("prepare", 1, 1, bytes=7),
              ev("apply", 2, 1, bytes=1000), ev("host_sync", 3, 1)]
    assert program_spans.arg_sum(events, ("stage", "prepare"),
                                 "bytes") == 107.0


def small(cell: str) -> dict:
    spec = copy.deepcopy(common.load_cell(cell))
    for part in ("config", "traffic"):
        spec[part].update(spec[part].pop("test_sizes", {}))
    return spec


SPAN_READERS = {
    "sim.lenet.paper": ["host_walk_ms.sim", "batch_gather_ms.sim",
                        "stage_ms.sim", "dispatch_ms.sim",
                        "engine_call_ms.sim", "h2d_mb_per_round.sim"],
    "sim.lenet.pop1m": ["engine_call_ms.pop", "h2d_mb_per_round.pop"],
}


@pytest.mark.parametrize("cell", sorted(SPAN_READERS))
def test_readers_see_the_window_and_not_the_setup(cell):
    from repro.obs.trace import default_tracer

    spec = small(cell)
    driver = common.load_module("drivers", spec["traffic"]["driver"])
    work = common.load_module("work", spec["cell"]["config"])
    state = driver.setup(spec["config"], spec["traffic"], SEED, work)
    rec = default_tracer().events
    before = list(rec)
    counts = driver.window(state, 0.2)
    run = {"seconds": counts["elapsed_s"], "counts": counts}
    window = program_spans.window(run)
    # exactly the spans the window's calls recorded: every set-up call's
    # spans are left out
    assert window == list(rec)[len(before):]
    setup_prepares = [e for e in before if e["name"] == "prepare"]
    assert len(setup_prepares) >= 2
    calls = counts["rounds"] // spec["traffic"]["rounds_per_call"]
    names = [e["name"] for e in window]
    assert names.count("prepare") == calls
    assert names.count("apply") == counts["launches"]
    for name in SPAN_READERS[cell]:
        value = common.load_module("metrics", name).read(run)
        assert value is not None and value > 0, name
    # the cell reports each of them
    listed = {m["name"] for m in common.load_cell(cell)["per_layer"]}
    assert set(SPAN_READERS[cell]) <= listed


def test_readers_give_nothing_without_a_window():
    run = {"seconds": 1.0, "counts": {"rounds": 0}}
    for name in SPAN_READERS["sim.lenet.paper"]:
        assert common.load_module("metrics", name).read(run) is None


def reduced(ops):
    return Reduced(window_s=1.0, busy_s=sum(ops.values()), chips=1,
                   top_ops=[], idle_gaps=[], op_seconds=ops)


def test_kernel_roofline_reads_the_named_kernel():
    read = common.load_module("metrics", "fused_server_pallas_roofline").read
    work = {"bytes": 819e9 * 0.006, "flops": 0}  # 6 ms least a round
    peaks = {"hbm_bytes_per_s": 819e9}
    ops = {"fused_server_pallas.1 custom-call": 0.6,
           "fused_server_pallas.3 custom-call": 0.066,
           "sub.1 subtract": 0.075}
    run = {"trace": reduced(ops), "counts": {"rounds": 100}, "work": work,
           "peaks": peaks}
    # 6 ms least over 6.66 ms of kernel a round; the subtract is not in it
    assert read(run) == pytest.approx(100 * 6.0 / 6.66)
    # a program whose kernel bears its caller's name reads nothing
    run["trace"] = reduced({"server_round.1 custom-call": 0.666})
    assert read(run) is None
    run["trace"] = None
    assert read(run) is None
