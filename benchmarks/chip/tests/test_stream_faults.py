"""The streaming fold against its plain reference, and the faults in it.

``make_dist_step`` (the distributed-client mapping, which runs the
program's ``make_streaming_round_body``) folds K=4 uploads a buffer of a
small linear model with a squared loss, on the CPU, over three buffers;
``reference/fl_stream.py`` folds the same uploads in float64, with the
local update and the loss written out in numpy here. Compared: each
upload's weight ``v`` and fresh loss (the largest relative gap), and the
parameters' change from the start (by the worst leaf,
``common.leaf_change_gap``). Sound runs come out under the limits; each
fault of ``faults.py``, planted in the streaming body, comes out over
them.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import common
import faults
from reference import fl_stream
from repro.configs.base import FLConfig
from repro.core.cohort import init_dist_state, make_dist_step

K, BUFFERS, M, ROWS, D_IN, D_OUT = 4, 3, 2, 8, 16, 4
FL = FLConfig(buffer_size=K, local_steps=M, local_lr=0.05, weighting="paper",
              normalize="mean", max_staleness=4)
# float32 against float64 over 12 uploads and 3 applies: sound runs read
# 5e-8 to 3e-7 on each number over 12 seeds (a float32 rounding is
# 6e-8); a half batch reads 1 on v, a state left unchanged 1 on
# params_change, the altered element 1.0e-2 to 1.3e-2 on params_change
# and 1.5e-3 or more on v and fresh (3 seeds each)
LIMITS = {"v": 1e-5, "fresh": 1e-5, "params_change": 1e-5}


def loss(params, batch):
    x, y = batch
    r = x @ params["w"] + params["b"] - y
    return jnp.mean(r * r), {}


def np_loss(leaves, batch):
    b, w = leaves  # the tree's leaves in key order
    x, y = (np.asarray(a, np.float64) for a in batch)
    r = x @ w + b - y
    return float(np.mean(r * r))


def np_local_update(leaves, local):
    b, w = (a.copy() for a in leaves)
    xs, ys = (np.asarray(a, np.float64) for a in local)
    for x, y in zip(xs, ys):
        r = x @ w + b - y
        scale = 2.0 / r.size
        gb, gw = scale * r.sum(axis=0), scale * x.T @ r
        b, w = b - FL.local_lr * gb, w - FL.local_lr * gw
    return [leaves[0] - b, leaves[1] - w]


def uploads(seed: int):
    g = common.rng(seed, 7)
    truth = g.normal(size=(D_IN, D_OUT))
    out = []
    for i in range(K * BUFFERS):
        x = g.normal(size=(M + 1, ROWS, D_IN)).astype(np.float32)
        y = (x @ truth + 0.1 * g.normal(size=(M + 1, ROWS, D_OUT))
             ).astype(np.float32)
        # each buffer holds a fresh upload, the rest up to 3 rounds stale
        tau = 0 if i % K == 0 else int(g.integers(0, 4))
        out.append({"local": (x[:M], y[:M]), "probe": (x[M], y[M]),
                    "data_size": float(g.integers(500, 3000)), "tau": tau})
    return out


def readings(seed: int) -> dict:
    g = common.rng(seed, 8)
    params = {"b": jnp.asarray(g.normal(size=D_OUT), jnp.float32),
              "w": jnp.asarray(0.1 * g.normal(size=(D_IN, D_OUT)),
                               jnp.float32)}
    start = [np.asarray(a, np.float64) for a in jax.tree.leaves(params)]
    ups = uploads(seed)
    step = jax.jit(make_dist_step(loss, FL))
    state = init_dist_state(params, FL)
    prog = []
    for up in ups:
        state, mets = step(state, {
            "local": jax.tree.map(jnp.asarray, up["local"]),
            "probe": jax.tree.map(jnp.asarray, up["probe"]),
            "tau": jnp.int32(up["tau"]),
            "data_size": jnp.float32(up["data_size"])})
        prog.append({"v": float(mets["v_weight"]),
                     "fresh": float(mets["fresh_loss"])})
    end = [np.asarray(a) for a in jax.tree.leaves(state.global_params)]
    ref_end, ref = fl_stream.run(
        start, ups, k=K, local_update=np_local_update, loss=np_loss,
        s_min=FL.s_min, max_staleness=FL.max_staleness, eta_g=FL.global_lr)
    out = {key: common.rel_each([p[key] for p in prog],
                                [r[key] for r in ref])
           for key in ("v", "fresh")}
    out["params_change"] = common.leaf_change_gap(start, end, ref_end)
    return out


def over(read: dict) -> list:
    return [k for k, limit in LIMITS.items() if not read[k] <= limit]


SEED = 2 ** 31 + 977


@pytest.mark.parametrize("seed", [SEED, SEED + 1, 3])
def test_stream_is_correct(seed):
    read = readings(seed)
    assert not over(read), read


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_stream_fault_is_not_correct(fault):
    with faults.planted(fault, driver=None):
        read = readings(SEED)
    assert over(read), read
