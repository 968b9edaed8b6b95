"""Helpers shared by the harness, its drivers and its metric readers.

Nothing here imports the program under test: seeds, host spans, the
compile counter, the agreement arithmetic and the loader that finds a
cell's files by the names ``BENCHMARK.json`` gives.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import pathlib
import statistics
import sys
from typing import Dict, List, Optional, Sequence

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]  # the checkout: BENCHMARK.json and src/ live here

# The benchmark's own host spans. Every span the harness or a driver opens
# starts with SPAN_PREFIX, so the trace reduction can tell them from the
# program's spans; SPAN_WINDOW brackets the measured window.
SPAN_PREFIX = "bench."
SPAN_WINDOW = SPAN_PREFIX + "window"


def span(name: str, **kw):
    """A host span on the profiler's timeline (a no-op when not tracing)."""
    import jax

    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **kw)


def rng(seed: int, *tags: int) -> np.random.Generator:
    """A numpy generator for ``seed`` (any size) and a stream tag."""
    return np.random.default_rng([int(seed), *map(int, tags)])


def sub_seed(seed: int, tag: int) -> int:
    """A 31-bit seed drawn from ``seed``: what the program's own seeded
    constructors (``jax.random.PRNGKey``, numpy) are handed."""
    return int(rng(seed, tag).integers(0, 2 ** 31 - 1))


# -- files found by name ------------------------------------------------


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def module_path(kind: str, name: str) -> pathlib.Path:
    """``<kind>/<name>.py``, or, where there is none, the file of the
    name with its last dotted part cut off, and so on: ``mfu_pct.pop``
    is read by ``metrics/mfu_pct.py`` unless ``metrics/mfu_pct.pop.py``
    exists. So a quantity split by the cells that report it keeps one
    reader."""
    parts = name.split(".")
    for n in range(len(parts), 0, -1):
        path = HERE / kind / (".".join(parts[:n]) + ".py")
        if path.is_file():
            return path
    raise FileNotFoundError(f"no {kind} module for {name!r} under "
                            f"{HERE / kind}")


def load_module(kind: str, name: str):
    """Import the module ``module_path`` finds, once a process (names may
    hold dots, so the file is loaded by path, not by package import)."""
    path = module_path(kind, name)
    modname = f"chipbench_{kind}_{path.stem.replace('.', '_')}"
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[modname]
        raise
    return mod


def load_cell(name: str, bench: Optional[dict] = None) -> dict:
    """The cell ``name`` with its configuration, traffic and metrics.

    Returns ``{"cell", "config", "traffic", "end_to_end", "per_layer"}``:
    the metric lists hold the entries of ``BENCHMARK.json`` that this
    cell reports (those without ``workloads`` or listing it).
    """
    bench = bench or load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    cell = cells[name]
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(ROOT / conf["file"])
    traffic = load_json(HERE / "traffic" / f"{cell['traffic']}.json")

    def mine(m):
        return name in m.get("workloads", [name])

    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)]}


# -- agreement arithmetic -------------------------------------------------


def rel_each(a, b) -> float:
    """max over elements of |a - b| / |b|; an element whose reference is
    exactly 0 must be exactly 0 in ``a`` too (else it reads inf)."""
    a = np.asarray(a, np.float64).ravel()
    b = np.asarray(b, np.float64).ravel()
    err = np.abs(a - b)
    zero = b == 0
    if np.any(err[zero] > 0):
        return float("inf")
    if not np.any(~zero):
        return 0.0
    return float(np.max(err[~zero] / np.abs(b[~zero])))


def leaf_change_gap(start: Sequence, prog: Sequence, ref: Sequence) -> float:
    """Worst leaf of the parameters' change from ``start``: the norm of
    (program change - reference change) over the larger of the
    reference's change norm of that leaf and of the median leaf."""
    chg_p = [np.asarray(p, np.float64) - np.asarray(s, np.float64)
             for p, s in zip(prog, start)]
    chg_r = [np.asarray(r, np.float64) - np.asarray(s, np.float64)
             for r, s in zip(ref, start)]
    norms = [float(np.linalg.norm(c)) for c in chg_r]
    med = statistics.median(norms)
    gaps = [float(np.linalg.norm(cp - cr)) / max(nr, med, 1e-30)
            for cp, cr, nr in zip(chg_p, chg_r, norms)]
    return max(gaps)


def judge(readings: Dict[str, float], limits: Dict[str, float]) -> List[dict]:
    """Each compared number beside its limit; a NaN reading fails. A
    reading with no limit in the traffic file is not compared (one that
    no control or fault separates from sound runs)."""
    missing = set(limits) - set(readings)
    if missing:
        raise KeyError(f"limits for numbers the driver does not read: "
                       f"{sorted(missing)}")
    out = []
    for name, limit in limits.items():
        value = float(readings[name])
        ok = bool(np.isfinite(value) and value <= float(limit))
        out.append({"name": name, "value": value, "limit": float(limit),
                    "ok": ok})
    return out


# -- compile counting -------------------------------------------------------


class CompileCounter:
    """Counts XLA backend compiles while ``counting`` is set, so a run can
    say whether anything compiled inside its measured window."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.counting = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event, duration, **kw):
        if self.counting and event == self.EVENT:
            self.count += 1

    @contextlib.contextmanager
    def window(self):
        self.counting = True
        try:
            yield self
        finally:
            self.counting = False
