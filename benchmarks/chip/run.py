"""Chip benchmark of the contribution-aware async FL system: one cell a run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with a TPU. A cell of
``BENCHMARK.json`` names a configuration (``configs/<config>.json``)
and a traffic mix (``traffic/<traffic>.json``); the traffic file names
the driver (``drivers/<driver>.py``) that builds the inputs from the
seed, warms up, runs the window and compares its output with the plain
reference. Each metric is read by ``metrics/<metric>.py``; the work of
a round, from shapes, by ``work/<config>.py``; the chip's peaks come
from ``peaks.json`` by ``device_kind``. A new cell, configuration or
metric is new files and new entries, never an edit.

A run prints the platform, device kind and chip count first, then sets
up (counted in ``setup_s``), measures for ``--seconds`` with nothing
compiled inside the window, and checks the window's output. With
``--trace 1`` the window runs under the profiler and the metrics are
the cell's per-layer ones, reduced from the trace
(``trace_reduce.py``); with ``--trace 0`` they are its end-to-end ones.
The compared numbers and their limits are the last lines of standard
error; the result is the last line of standard output. With no TPU, a
device kind missing from ``peaks.json`` or fewer chips than the cell
asks for, the run exits 1 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Optional  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import common  # noqa: E402


class NoChip(RuntimeError):
    """The machine cannot run the cell: no result is printed."""


def device_info(chips: int, peaks: dict) -> dict:
    import jax

    devices = jax.devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    print(f"device: platform={info['platform']} kind={info['kind']!r} "
          f"count={info['count']}", flush=True)
    if info["platform"] != "tpu":
        raise NoChip(f"no TPU: JAX runs on {info['platform']!r}")
    if info["kind"] not in peaks:
        raise NoChip(f"device kind {info['kind']!r} is not in peaks.json")
    if info["count"] < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds "
                     f"{info['count']}")
    return info


def memory_peak(chips: int) -> int:
    import jax

    stats = [d.memory_stats() or {} for d in jax.devices()[:chips]]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def run_cell(spec: dict, seed: int, seconds: float, trace: bool, *,
             peaks: dict, device: dict, control: bool = False,
             trace_dir: Optional[str] = None, log=print) -> dict:
    """Set up, measure, check and read the metrics of one cell.

    ``spec`` is ``common.load_cell``'s dict; ``device`` the JSON device
    entry, completed here. Returns the result object of the run.
    """
    import jax

    cell, config, traffic = spec["cell"], spec["config"], spec["traffic"]
    driver = common.load_module("drivers", traffic["driver"])
    work = common.load_module("work", cell["config"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = common.CompileCounter()

    with common.span("setup"):
        state = driver.setup(config, traffic, seed, work)
    log(state.describe())
    for line in getattr(state, "traffic_lines", lambda: [])():
        log(line)
    setup_s = time.perf_counter() - T_START

    keep_trace = trace_dir is not None
    if trace:
        trace_dir = trace_dir or tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(trace_dir)
    with compiles.window(), common.span("window"):
        counts = driver.window(state, seconds)
    if trace:
        jax.profiler.stop_trace()
    device = dict(device)
    if device.get("platform") == "tpu":
        device["memory_peak_bytes"] = memory_peak(spec["cell"]["chips"])
    else:
        device["memory_peak_bytes"] = 0
    log(f"window: {json.dumps(counts)} compiles_in_window={compiles.count}")

    t_check = time.perf_counter()
    readings = driver.check(state, control=control)
    del state
    check_s = time.perf_counter() - t_check
    # nothing may compile inside the window: an exact count, limit 0
    checks = common.judge({**readings, "compiles_in_window": compiles.count},
                          {**traffic["limits"], "compiles_in_window": 0})
    correct = all(c["ok"] for c in checks)

    run = {"seconds": counts["elapsed_s"], "setup_s": setup_s,
           "counts": counts, "work": work.per_round(config, traffic),
           "peaks": peaks.get(device.get("kind"), {}), "trace": None}
    if trace:
        import trace_reduce

        red = trace_reduce.reduce_dir(trace_dir)
        if not keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
        run["trace"] = red
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
    metrics = {}
    for m in spec["per_layer" if trace else "end_to_end"]:
        value = common.load_module("metrics", m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    # attempted: rounds run in the window; failed: compared numbers over
    # their limits
    result = {"correct": correct, "attempted": counts["rounds"],
              "failed": sum(not c["ok"] for c in checks),
              "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in run["trace"].top_ops],
            "idle_gaps": [[n, s] for n, s in run["trace"].idle_gaps]}
    result["check_s"] = check_s
    result["not_compared"] = {k: v for k, v in readings.items()
                              if k not in traffic["limits"]}
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="where the profiler writes (default: a new "
                         "temporary directory)")
    args = ap.parse_args(argv)
    # JAX's persistent compilation cache, at the fixed path inside the
    # checkout that the program's enable_compile_cache() also uses; set
    # before jax is imported so that jax and the program both take it
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")

    spec = common.load_cell(args.workload)
    peaks = common.load_json(HERE / "peaks.json")
    try:
        device = device_info(spec["cell"]["chips"], peaks)
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 1
    result = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                      peaks=peaks, device=device,
                      trace_dir=args.trace_dir, log=lambda s: print(s, flush=True))
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
