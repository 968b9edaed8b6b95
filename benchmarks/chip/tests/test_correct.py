"""``correct`` at sizes a test run holds, on the CPU.

Each cell's run is driven past the harness's look for a chip
(``run.run_cell`` with a CPU device entry) at the small size its files
give (``test_sizes``), with the cell's own limits: the program comes out
correct; the control (the reference in the precision below the
configuration's, in the program's place) and each fault that the cell
can have, planted in the timed path, come out not correct. One chip: no exchange between chips to leave out.
"""
import copy

import pytest

import common
import faults
import run as harness

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
PEAKS = common.load_json(common.HERE / "peaks.json")
SEED = 2 ** 31 + 977  # seeds run past 32 bits


def small(cell: str) -> dict:
    """The cell at the sizes its own files give for a test run: the
    ``test_sizes`` of its configuration and of its traffic, each laid
    over the rest of that file."""
    spec = copy.deepcopy(common.load_cell(cell))
    given = [part for part in ("config", "traffic")
             if "test_sizes" in spec[part]]
    assert given, (f"{cell}: neither its configuration nor its traffic "
                   "gives test_sizes, so a test would run it at full size")
    for part in given:
        spec[part].update(spec[part].pop("test_sizes"))
    return spec


def run(cell, **kw):
    return harness.run_cell(small(cell), SEED, 0.2, False, peaks=PEAKS,
                            device=CPU, log=lambda s: None, **kw)


CELLS = [w["name"] for w in common.load_json(
    common.ROOT / "BENCHMARK.json")["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_program_is_correct(cell):
    res = run(cell)
    assert res["correct"], res["checks"]
    assert res["checks"]["compiles_in_window"]["value"] == 0
    assert set(res["metrics"]) == {m["name"] for m in
                                   common.load_cell(cell)["end_to_end"]}


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    res = run(cell, control=True)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("fault", faults.FAULTS)
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(cell, fault):
    driver = common.load_module("drivers",
                                small(cell)["traffic"]["driver"])
    with faults.planted(fault, driver):
        res = run(cell)
    assert not res["correct"], res["checks"]
