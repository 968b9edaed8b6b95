"""Faults in what an upload carries, to show that the dist cell's
``correct`` catches them.

    python3 benchmarks/chip/update_faults.py --workload <cell> \\
        --seeds 1,2,3 --fault <name> [--seconds 1]

``faults.py`` plants its faults in the server's round: the apply and the
buffer. These are planted in the client's part of the distributed-client
step (``core/round_body.py::make_streaming_round_body``), which the dist
cell's check compares against the plain reference:

* ``lr_x1.5``: every local SGD step at 1.5 times the configured rate;
* ``one_step_short``: M - 1 local steps instead of M (the last batch
  left out);
* ``tau_ignored``: eq. 3 weighs every upload as fresh (its tau taken as
  0), so a stale upload is not discounted.

Each wraps the program where the step is built, so the step that a
driver builds from then on carries it. For each seed the CLI runs the
cell as ``calibrate.py`` does and prints one JSON line with the compared
numbers. Not part of a benchmark run; the tests under ``tests/`` and a
calibration plant them.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

import jax
import jax.numpy as jnp

FAULTS = ("lr_x1.5", "one_step_short", "tau_ignored")


def _local_update(real_make, fault: str):
    def make(loss_fn, local_steps, local_lr, momentum=0.0, *a, **kw):
        if fault == "lr_x1.5":
            return real_make(loss_fn, local_steps, 1.5 * local_lr, momentum,
                             *a, **kw)
        if fault == "one_step_short":
            short = real_make(loss_fn, local_steps - 1, local_lr, momentum,
                              *a, **kw)

            def local_update(base, batches, key=None):
                return short(base, jax.tree.map(lambda b: b[:-1], batches),
                             key)
            return local_update
        return real_make(loss_fn, local_steps, local_lr, momentum, *a, **kw)

    return make


def _streaming(real_make, fault: str):
    def make(loss_fn, fl):
        body = real_make(loss_fn, fl)
        if fault != "tau_ignored":
            return body

        def contribute(params, accum, ring, batch, probe, data_size, tau):
            return body.contribute(params, accum, ring, batch, probe,
                                   data_size, jnp.zeros_like(tau))
        return body._replace(contribute=contribute)

    return make


@contextlib.contextmanager
def planted(fault: str):
    """Plant ``fault`` in the distributed-client steps built from now on."""
    from repro.core import cohort, round_body, serving

    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    patches = [(round_body, "make_local_update_fn", _local_update)]
    patches += [(mod, "make_streaming_round_body", _streaming)
                for mod in (round_body, cohort, serving)]
    saved = [(mod, name, getattr(mod, name)) for mod, name, _ in patches]
    try:
        for (mod, name, wrap), (_, _, real) in zip(patches, saved):
            setattr(mod, name, wrap(real, fault))
        yield
    finally:
        for mod, name, real in saved:
            setattr(mod, name, real)


def main(argv=None) -> int:
    import common
    import run as harness

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--fault", required=True, choices=FAULTS)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(harness.ROOT / ".jax_cache")
    spec = common.load_cell(args.workload)
    peaks = common.load_json(harness.HERE / "peaks.json")
    try:
        device = harness.device_info(spec["cell"]["chips"], peaks)
    except harness.NoChip as e:
        print(f"update_faults.py: {e}", file=sys.stderr)
        return 1
    for seed in (int(s) for s in args.seeds.split(",")):
        with planted(args.fault):
            res = harness.run_cell(spec, seed, args.seconds, False,
                                   peaks=peaks, device=device,
                                   log=lambda s: None)
        print(json.dumps({"workload": args.workload,
                          "mode": f"fault:{args.fault}", "seed": seed,
                          "correct": res["correct"],
                          "readings": {**res["not_compared"], **{
                              k: v["value"] for k, v in
                              res["checks"].items()}}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
