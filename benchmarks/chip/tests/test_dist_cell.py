"""The DeepSeek-V2-Lite cell's work counts, its two new readers, the
split of its accumulator check and the faults in what an upload carries,
on the CPU."""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import common
import program_spans
import update_faults
from repro.models.model import build_model
from test_correct import run

work = common.load_module("work", "deepseek_v2_lite_ep8")
driver = common.load_module("drivers", "dist_stream")
CONFIG = common.load_json(common.HERE / "configs" / "deepseek_v2_lite_ep8.json")


def shapes(cfg: dict):
    model = build_model(driver.model_config(cfg).replace(matmul_dtype=None))
    return model, jax.eval_shape(model.init, jax.random.PRNGKey(0))


def test_count_is_the_programs_tree():
    """At the cell's sizes: 1 dense + 4 MoE layers, 8 of 64 experts held,
    a 12,800-row vocabulary slice."""
    _, params = shapes(CONFIG)
    n = sum(x.size for x in jax.tree.leaves(params))
    assert work.n_params(CONFIG) == n == CONFIG["n_params"] == 535_060_992


def forward_flops(cfg: dict, seq: int) -> float:
    model, params = shapes(cfg)
    batch = {"tokens": jax.ShapeDtypeStruct((1, seq), jnp.int32)}
    c = jax.jit(lambda p, b: model.apply(p, b)[0]).lower(
        params, batch).compile().cost_analysis()
    return (c[0] if isinstance(c, list) else c)["flops"]


def test_forward_flops_against_xla():
    """Model FLOPs of one sequence's forward against XLA's count of the
    program's, at the published widths with one dense and one MoE layer
    and S = 256 (the materialized-scores path). The CPU lowers each held
    expert's grouped product as a product over all T * k routed rows, so
    one held expert more adds what the model counts for E experts' routed
    pairs (within 1%: the masking is elementwise); with the one held
    expert's product taken out, XLA's count lies at or above the model's and
    within 3% above the model's plus the causal mask's upper triangle
    (XLA scores every key; the rest is softmax, norms and other
    elementwise work)."""
    s = 256
    cfg = dict(copy.deepcopy(CONFIG), num_hidden_layers=2)
    w = work._w(cfg)
    one = forward_flops(dict(cfg, experts_held=1), s)
    two = forward_flops(dict(cfg, experts_held=2), s)
    per_group = two - one
    routed = s * w["k"] / w["e"] * 2 * 3 * w["d"] * w["f"]  # one expert
    assert abs(per_group / (w["e"] * routed) - 1) < 0.01, (per_group, routed)
    model = s * work.forward_flops_per_token(dict(cfg, experts_held=0), s)
    xla = one - per_group
    masked = 2 * s * (s - 1) / 2 * w["h"] * (w["dn"] + w["dr"] + w["dv"]) * 2
    assert model <= xla <= 1.03 * (model + masked), (model, xla, masked)


RUN = {"seconds": 10.0, "counts": {"rounds": 3}}


def events(*spans):
    """Complete events of one lane: (name, start us, duration us, args)."""
    return [{"name": n, "ph": "X", "ts": t, "dur": d, "pid": 1, "tid": 0,
             **({"args": a} if a else {})} for n, t, d, a in spans]


def test_upload_host_ms_reads_self_time_per_upload(monkeypatch):
    """Two uploads in the window (the first one's stage fell before it):
    their stage, contribute and host_sync self times over 2 uploads."""
    evs = events(("stage", 0, 100, None), ("contribute", 9e6, 200, None),
                 ("host_sync", 9.5e6, 1000, None),
                 ("stage", 1.0e7, 100, None), ("contribute", 1.0e7 + 100,
                                                200, None),
                 ("host_sync", 1.0e7 + 300, 1000, None))
    monkeypatch.setattr(program_spans, "recorder_events", lambda: evs)
    reader = common.load_module("metrics", "upload_host_ms.dist")
    assert reader.read(RUN) == pytest.approx((200 + 1000 + 100 + 200
                                              + 1000) / 1e3 / 2)


def test_expert_load_max_averages_max_over_mean(monkeypatch):
    """Per step and layer the busiest held expert over the mean, averaged:
    here (4 / 2.5 + 2 / 2) / 2 and (3 / 3 + 3 / 3) / 2."""
    a = {"expert_counts": [[[4, 1, 3, 2], [2, 2, 2, 2]]]}
    b = {"expert_counts": [[[3, 3, 3, 3], [3, 3, 3, 3]]]}
    evs = events(("host_sync", 5e6, 10, a), ("host_sync", 8e6, 10, b),
                 ("stage", 9e6, 10, None))
    monkeypatch.setattr(program_spans, "recorder_events", lambda: evs)
    reader = common.load_module("metrics", "expert_load_max.dist")
    assert reader.read(RUN) == pytest.approx((1.6 + 1 + 1 + 1) / 4)


@pytest.mark.parametrize("name", ["upload_host_ms.dist",
                                  "expert_load_max.dist"])
def test_readers_read_nothing_without_the_spans(monkeypatch, name):
    """A program without the upload loop's spans (the parent's) gives no
    value and does not raise."""
    monkeypatch.setattr(program_spans, "recorder_events",
                        lambda: events(("apply", 9e6, 10, None)))
    assert common.load_module("metrics", name).read(RUN) is None
    monkeypatch.setattr(program_spans, "recorder_events", lambda: None)
    assert common.load_module("metrics", name).read(RUN) is None


def test_accum_gaps_split_experts_from_the_rest():
    """A gap in one held expert's matrix of one layer reads in
    ``accum_experts`` alone, at that slice's own relative size; a gap in
    a non-expert leaf reads in ``accum_dense`` alone, over the leaf."""
    ref = {"attn": {"wq": np.ones((2, 4, 4))},
           "experts": {"w_up": np.ones((2, 3, 4, 4))}}
    prog = copy.deepcopy(ref)
    prog["experts"]["w_up"][1, 2] *= 1.5  # layer 1, held expert 2
    assert driver.accum_gaps(prog, ref) == pytest.approx(
        {"accum_dense": 0.0, "accum_experts": 0.5})
    prog = copy.deepcopy(ref)
    prog["attn"]["wq"][0] *= 1.5  # one layer of two
    assert driver.accum_gaps(prog, ref) == pytest.approx(
        {"accum_dense": 0.5 / 2 ** 0.5, "accum_experts": 0.0})
    # an expert that no token reached: 0 in both reads 0, moved reads inf
    ref["experts"]["w_up"][0, 1] = 0.0
    prog = copy.deepcopy(ref)
    assert driver.accum_gaps(prog, ref)["accum_experts"] == 0.0
    prog["experts"]["w_up"][0, 1, 0, 0] = 1e-3
    assert driver.accum_gaps(prog, ref)["accum_experts"] == float("inf")


# which compared number each fault in an upload has to move over its limit
SEEN_BY = {"lr_x1.5": ("accum_dense", "accum_experts"),
           "one_step_short": ("accum_dense", "accum_experts"),
           "tau_ignored": ("v_stale",)}


@pytest.mark.parametrize("fault", update_faults.FAULTS)
def test_update_fault_is_not_correct(fault):
    """At the test sizes, with the cell's limits: a local update at 1.5
    times the rate or one step short moves both parts of the accumulator
    over their limits; eq. 3 with tau taken as 0 moves the weight of the
    stale upload after the apply over its limit."""
    with update_faults.planted(fault):
        res = run("dist.dsv2_lite.k10")
    assert not res["correct"], res["checks"]
    for name in SEEN_BY[fault]:
        check = res["checks"][name]
        assert check["value"] > check["limit"], (name, res["checks"])
