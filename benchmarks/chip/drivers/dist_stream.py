"""Driver: the distributed-client (streaming) round at DeepSeek-V2-Lite size.

One client spans the chip and folds its uploads one at a time through the
program's ``make_dist_step`` (``core/cohort.py``, over the streaming round
body): each upload trains M local SGD steps from the current model, is
weighed by eq. 3 (the update-norm ring, at the upload's simulated tau)
and eq. 4 (its probe loss times its data size), and folds into the
running accumulator; every K-th upload applies eq. 5. The program's own
upload loop (``core/cohort.py::fold_upload``) stages each upload, dispatches
the step and fetches (v, fresh) as the acknowledgement before the next
upload: a closed loop.

Set-up builds the registry's ``deepseek-v2-lite`` at the configuration's
cut (depth, held experts, vocabulary slice), seeded weights on the
device, a pool of token sequences from the program's synthetic stream,
each upload's tau from the traffic's scenario (``staleness_stream``) and
data size, then folds uploads 1 and 2 of the first buffer through the
timed step (the first compiles it), keeping their acknowledgements and
the accumulator after each on the host with the start weights. The
window folds uploads until its time is up.

The check, after the window, with nothing of it recorded in the
program's spans:
(b) the next apply: uploads are folded until the buffer holds K - 1;
    the program's x, accumulator, v-buffer and ring are fetched; the
    K-th upload's own term v_K Delta_K is read from the same step on an
    empty buffer (which does not apply); the K-th upload is then folded
    into the fetched buffer, which applies. Eq. 5 and the ring write are
    recomputed in float64 on the host from those: ``params_change``
    (the new parameters' largest gap from the float64 result rounded to
    float32, in units of float32 rounding there, ``ulp_gap``: a sound
    apply reads 1, its last rounding) and ``ring`` (relative, element by
    element).
(c) the next upload with a tau of 1 or more, folded after that apply:
    its weight against eq. 3 and the paper weighting in float64 from
    the ring the apply wrote and its own probe loss (``v_stale``,
    relative). Set-up uploads see an empty ring, where eq. 3 gives 1
    whatever tau is; this one sees a staleness distance.
(a) set-up uploads 1 and 2 against ``reference/deepseek_v2.py`` (float32,
    HIGHEST) on the chip from the same start weights and batches: their
    weight ``v`` (relative) and the accumulator after each, in two parts
    (``accum_gaps``): ``accum_dense``, the worst non-expert leaf's
    ||gap|| / ||reference||, and ``accum_experts``, the worst held
    expert's (one layer, one expert, one matrix). bf16 operands route
    some tokens to other experts than the reference does, and a token
    routed differently moves a held expert's gradient by a whole row,
    so the experts part reads higher, and higher still with fewer pairs
    an expert (the test sizes). ``fresh``, the probe loss, is reported
    and not compared.
The program's state is fetched and freed before the reference runs.
``control=True`` puts the reference with its parameters, SGD updates and
accumulator in bfloat16 in the program's place. ``check_apply_s`` and
``check_reference_s`` (not compared) time (b) + (c) and (a).

Traffic keys: ``k``, ``local_steps``, ``batch``, ``probe_batch``,
``seq_len``, ``weighting``, ``normalize``, ``max_staleness``,
``scenario``, ``num_clients``, ``size`` (data-size range),
``pool_uploads`` (distinct uploads' sequences made at set-up, cycled),
``stream_uploads`` (taus drawn from the scenario, cycled).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

import common
from reference import deepseek_v2 as ref
from repro.configs.base import FLConfig, YarnRope
from repro.configs.registry import get_arch
from repro.core.cohort import (
    DistFLState,
    fold_upload,
    init_dist_state,
    make_dist_step,
    staleness_stream,
)
from repro.data.synthetic import make_lm_token_stream
from repro.models.model import build_model
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import NULL_TRACER
from repro.sim.scenarios import ClientBehavior, registry

EPS = 1e-12  # eq. 3's guard, as reference/fl_stream.py
# the configuration's keys -> the program's ModelConfig fields
KEYS = {"num_hidden_layers": "num_layers",
        "first_k_dense_replace": "first_k_dense", "hidden_size": "d_model",
        "num_attention_heads": "num_heads",
        "num_key_value_heads": "num_kv_heads",
        "intermediate_size": "d_ff", "moe_intermediate_size": "moe_d_ff",
        "vocab_size": "vocab_size", "kv_lora_rank": "kv_lora_rank",
        "qk_nope_head_dim": "qk_nope_head_dim",
        "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
        "n_routed_experts": "num_experts",
        "num_experts_per_tok": "experts_per_token",
        "n_shared_experts": "num_shared_experts", "rope_theta": "rope_theta",
        "aux_alpha": "router_aux_coef", "param_dtype": "param_dtype",
        "matmul_dtype": "matmul_dtype"}


def model_config(config: dict):
    """The registry's architecture at the configuration's sizes and cut."""
    rs = config["rope_scaling"]
    kw = {field: config[key] for key, field in KEYS.items()}
    kw["experts_held"] = (config["experts_held_first"],
                          config["experts_held"])
    kw["rope_yarn"] = YarnRope(
        factor=rs["factor"],
        original_max_position_embeddings=rs["original_max_position_embeddings"],
        beta_fast=rs["beta_fast"], beta_slow=rs["beta_slow"],
        mscale=rs["mscale"], mscale_all_dim=rs["mscale_all_dim"])
    return get_arch(config["arch"]).model.replace(**kw)


def ref_dims(config: dict, seq_len: int):
    return ref.dims({**config, "experts_held_range": (
        config["experts_held_first"], config["experts_held"])},
        q_block=min(512, seq_len))


@dataclasses.dataclass
class State:
    config: dict
    traffic: dict
    fl: FLConfig
    model: Any
    step: Any
    dist: Optional[DistFLState]
    pool: np.ndarray
    cids: np.ndarray
    taus: np.ndarray
    sizes: np.ndarray
    x0: Any = None
    setup: List = dataclasses.field(default_factory=list)
    n: int = 0  # uploads folded

    def describe(self) -> str:
        c, t = self.model.cfg, self.traffic
        first, n = c.held_experts
        np_ = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(self.x0))
        return (f"dist_stream: Np={np_} layers={c.num_layers} "
                f"(dense {c.first_k_dense}) experts {first}-{first + n - 1} "
                f"of {c.num_experts} vocab={c.vocab_size} K={self.fl.buffer_size} "
                f"M={self.fl.local_steps} S={t['seq_len']} B={t['batch']} "
                f"P={t['probe_batch']} weighting={self.fl.weighting} "
                f"scenario={t['scenario']}")

    def upload(self, i: int) -> dict:
        """Upload ``i``: its M x B local sequences and P probe sequences
        from the pool, its tau and its client's data size."""
        t = self.traffic
        m, b, p = self.fl.local_steps, t["batch"], t["probe_batch"]
        per = m * b + p
        j = i % t["pool_uploads"]
        seqs = self.pool[j * per:(j + 1) * per]
        loc = seqs[:m * b].reshape(m, b, -1)
        prb = seqs[m * b:]
        s = i % len(self.taus)
        return {"local": {"tokens": loc[..., :-1], "labels": loc[..., 1:]},
                "probe": {"tokens": prb[:, :-1], "labels": prb[:, 1:]},
                "tau": np.int32(self.taus[s]),
                "data_size": np.float32(self.sizes[self.cids[s]])}


def setup(config: dict, traffic: dict, seed: int, work) -> State:
    cfg = model_config(config)
    model = build_model(cfg)
    fl = FLConfig(buffer_size=traffic["k"],
                  local_steps=traffic["local_steps"],
                  local_lr=config["local_lr"], weighting=traffic["weighting"],
                  normalize=traffic["normalize"],
                  max_staleness=traffic["max_staleness"])
    with common.span("inputs"):
        per = fl.local_steps * traffic["batch"] + traffic["probe_batch"]
        pool = make_lm_token_stream(cfg.vocab_size, traffic["seq_len"],
                                    traffic["pool_uploads"] * per,
                                    seed=common.sub_seed(seed, 1))
        behavior = ClientBehavior(registry()[traffic["scenario"]],
                                  traffic["num_clients"],
                                  seed=common.sub_seed(seed, 2))
        cids, taus = staleness_stream(behavior, fl.buffer_size,
                                      traffic["stream_uploads"])
        sizes = common.rng(seed, 3).integers(
            *traffic["size"], size=traffic["num_clients"]).astype(np.float32)
    with common.span("weights"):
        params = jax.jit(model.init)(jax.random.PRNGKey(
            common.sub_seed(seed, 4)))
        x0 = jax.device_get(params)
        dist = init_dist_state(params, fl)
        del params
    step = jax.jit(make_dist_step(model.loss, fl), donate_argnums=0)
    state = State(config=config, traffic=traffic, fl=fl, model=model,
                  step=step, dist=dist, pool=pool, cids=cids, taus=taus,
                  sizes=sizes, x0=x0)
    # uploads 1 and 2 of the first buffer, through the timed step (the
    # first call compiles it); what the check compares
    for _ in range(2):
        batch = state.upload(state.n)
        with common.span("setup_upload"):
            state.dist, ack = fold_upload(step, state.dist, batch)
        state.n += 1
        state.setup.append((batch, ack, jax.device_get(state.dist.accum)))
    return state


def window(state: State, seconds: float) -> dict:
    n0 = state.n
    t0 = time.perf_counter()
    ends = []
    while True:
        batch = state.upload(state.n)
        with common.span("upload"):
            state.dist, _ = fold_upload(state.step, state.dist, batch)
        state.n += 1
        ends.append(time.perf_counter() - t0)
        if ends[-1] >= seconds:
            break
    u = state.n - n0
    # each upload's wall time, for the log line
    each = np.diff([0.0] + ends).round(4).tolist()
    return {"rounds": u, "updates": u, "elapsed_s": ends[-1],
            "upload_s": each}


# -- the check ------------------------------------------------------------


def to_ref(tree, shift: float) -> dict:
    """The program's parameter tree (or a delta of it) in the reference's
    layout: norms named as published and, for parameters (``shift`` 1),
    scaling by w = 1 + the program's w."""
    def stack(p):
        a = p["attn"]
        out = {"input_norm": p["norm1"]["w"] + shift,
               "post_norm": p["norm2"]["w"] + shift,
               "attn": {"wq": a["wq"], "wkv_a": a["wkv_a"],
                        "kv_norm": a["kv_norm"] + shift,
                        "wkv_b": a["wkv_b"], "wo": a["wo"]}}
        if "moe" in p:
            m = p["moe"]
            out["moe"] = {
                "router": m["router"],
                "experts": {k: m[k] for k in ("w_gate", "w_up", "w_down")},
                "shared": dict(m["shared"])}
        else:
            out["mlp"] = dict(p["mlp"])
        return out

    return {"embed": tree["embed"], "lm_head": tree["lm_head"],
            "final_norm": tree["final_norm"]["w"] + shift,
            "dense": stack(tree["dense_layers"]), "moe": stack(tree["layers"])}


def ulp_gap(prog, ref64, size64) -> float:
    """max |prog - round32(ref64)| in units of float32 rounding: the
    spacing of float32 at the rounded reference, or, where the update's
    own float32 arithmetic errs more (the new value near 0), 2^-22 of
    ``size64``, the magnitude of the terms the update was formed from. A
    sound float32 apply reads a few units."""
    r32 = ref64.astype(np.float32)
    err = np.abs(np.asarray(prog, np.float64) - r32.astype(np.float64))
    unit = np.maximum(np.spacing(np.abs(r32)).astype(np.float64),
                      2.0 ** -22 * size64)
    return float(np.max(err / unit))


def bf16(a):
    return np.asarray(a, np.float64).astype(ml_dtypes.bfloat16)


def eq3_weight(ring, tau: int, p: float, fl: FLConfig) -> float:
    """Eq. 3 and the paper weighting in float64, as
    ``reference/fl_stream.py``: v = P / max(S, s_min), S = clip(eps /
    (d + eps), 0, 1), d the last min(tau, R - 1) entries of the ring."""
    ring = np.asarray(ring, np.float64)
    d = float(np.sum(ring[:min(tau, fl.max_staleness - 1)]))
    s = min(max(EPS / (d + EPS), 0.0), 1.0)
    return p / max(s, fl.s_min)


def check_apply(state: State, control: bool) -> dict:
    """(b): the first apply after the window against eq. 5 and the ring
    write in float64 from the program's own pre-apply state; (c): the
    upload after it, at a tau of 1 or more, against eq. 3 from the ring
    the apply wrote."""
    fl, k = state.fl, state.fl.buffer_size
    quiet = {"tracer": NULL_TRACER, "registry": MetricsRegistry()}
    while int(state.dist.count) != k - 1:
        state.dist, _ = fold_upload(state.step, state.dist,
                                    state.upload(state.n), **quiet)
        state.n += 1
    batch = state.upload(state.n)
    state.n += 1
    pre = jax.device_get(state.dist)
    x_dev, ring_dev, version = (state.dist.global_params,
                                state.dist.update_norm_ring,
                                state.dist.version)
    state.dist = None  # frees the accumulator on the device
    empty = DistFLState(x_dev, jax.tree.map(jnp.zeros_like, x_dev),
                        jnp.zeros_like(jnp.asarray(pre.v_buf)),
                        jnp.zeros((), jnp.int32), version, ring_dev)
    out, ack_k = fold_upload(state.step, empty, batch, **quiet)
    term = jax.device_get(out.accum)  # v_K * Delta_K, this program's own
    full = DistFLState(out.global_params, jax.device_put(pre.accum),
                       jax.device_put(pre.v_buf), jnp.int32(k - 1),
                       out.version, out.update_norm_ring)
    del out
    post, ack = fold_upload(state.step, full, batch, **quiet)
    if not ack["applied"]:
        raise RuntimeError("the K-th upload did not apply")
    x_post, ring_post = jax.device_get((post.global_params,
                                        post.update_norm_ring))
    # (c) the next upload with a tau of 1 or more, folded after the apply
    while int(state.taus[state.n % len(state.taus)]) < 1:
        state.n += 1
    stale = state.upload(state.n)
    state.n += 1
    post, ack_c = fold_upload(state.step, post, stale, **quiet)
    del post
    p_c = float(stale["data_size"]) * float(ack_c["fresh_loss"])
    v_c = eq3_weight(ring_post, int(stale["tau"]), p_c, fl)
    got_c = float(ack_c["v_weight"])

    v = np.asarray(pre.v_buf, np.float64)
    v[k - 1] = float(ack_k["v_weight"])
    scale = fl.global_lr / max(float(np.sum(v)), 1e-12)
    worst, unorm, unorm_ctrl = 0.0, 0.0, 0.0
    for x, a, c, xp in zip(*(jax.tree.leaves(t) for t in
                             (pre.global_params, pre.accum, term, x_post))):
        x = np.asarray(x, np.float64)
        a, c = np.asarray(a, np.float64), np.asarray(c, np.float64)
        upd = scale * (a + c)
        unorm += float(np.sum(upd * upd))
        if control:  # parameters, updates and accumulator in bf16
            acc = bf16(bf16(a).astype(np.float64) + bf16(c).astype(np.float64))
            u = bf16(np.float64(scale) * acc.astype(np.float64))
            unorm_ctrl += float(np.sum(u.astype(np.float64) ** 2))
            xp = bf16(bf16(x).astype(np.float64) - u.astype(np.float64))
        worst = max(worst, ulp_gap(xp, x - upd,
                                   scale * (np.abs(a) + np.abs(c))))
    ring_ref = np.concatenate([[unorm], np.asarray(pre.update_norm_ring,
                                                   np.float64)[:-1]])
    if control:
        ring_post = np.concatenate([[unorm_ctrl], ring_ref[1:]])
        got_c = eq3_weight(bf16(ring_post).astype(np.float64),
                           int(stale["tau"]),
                           float(bf16(p_c).astype(np.float64)), fl)
    return {"params_change": worst,
            "ring": common.rel_each(ring_post, ring_ref),
            "v_stale": common.rel_each(got_c, v_c)}


def is_expert(path) -> bool:
    return any(getattr(key, "key", None) == "experts" for key in path)


def accum_gaps(prog, refd) -> dict:
    """The accumulator against the reference's, by part: ``accum_dense``
    the worst non-expert leaf's ||gap|| / ||reference|| (each leaf over
    its stack of layers), ``accum_experts`` the worst held expert's
    (one layer, one expert, one matrix)."""
    def rel(gap, ref_):  # an expert no token reached must read 0 too
        with np.errstate(divide="ignore", invalid="ignore"):
            r = np.where(ref_ > 0, gap / ref_, np.where(gap > 0, np.inf, 0))
        return float(np.max(r))

    dense, experts = 0.0, 0.0
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(prog)[0],
                            jax.tree.leaves(refd)):
        a = np.asarray(a, np.float64)
        b = np.asarray(b, np.float64)
        if is_expert(path):  # (layers, held experts, ...)
            g = (a - b).reshape(a.shape[0] * a.shape[1], -1)
            experts = max(experts, rel(np.linalg.norm(g, axis=1),
                                       np.linalg.norm(b.reshape(g.shape),
                                                      axis=1)))
        else:
            dense = max(dense, rel(np.linalg.norm(a - b), np.linalg.norm(b)))
    return {"accum_dense": dense, "accum_experts": experts}


def check_setup_uploads(state: State, control: bool) -> dict:
    """(a): set-up uploads 1 and 2 against the reference. Each upload's
    reference term v Delta is fetched to the host as it is made, so the
    chip holds only the reference's own local update."""
    fl = state.fl
    dims = ref_dims(state.config, state.traffic["seq_len"])
    _, loss = ref.compiled(dims)
    x = jax.device_put(to_ref(state.x0, 1.0))
    if control:
        x_c = jax.tree.map(lambda a: a.astype(jnp.bfloat16), x)
    acc_ref = acc_ctrl = None
    got, want = {"v": [], "fresh": []}, {"v": [], "fresh": []}
    gaps = {"accum_dense": 0.0, "accum_experts": 0.0}
    ring = np.zeros(fl.max_staleness)  # no apply before these uploads
    for batch, ack, acc_prog in state.setup:
        tau = int(batch["tau"])
        size = float(batch["data_size"])
        local = jax.device_put(batch["local"])
        fresh = float(loss(x, batch["probe"]))
        v = eq3_weight(ring, tau, size * fresh, fl)
        term = jax.device_get(jax.tree.map(
            lambda a: v * a, ref.local_sgd(x, local, dims, fl.local_lr)))
        acc_ref = term if acc_ref is None else jax.tree.map(
            np.add, acc_ref, term)
        del term
        if control:
            fresh_p = float(loss(jax.tree.map(
                lambda a: a.astype(jnp.float32), x_c), batch["probe"]))
            v_p = eq3_weight(ring, tau, size * fresh_p, fl)
            term = jax.device_get(jax.tree.map(
                lambda a: (v_p * a.astype(jnp.float32)).astype(jnp.bfloat16),
                ref.local_sgd(x, local, dims, fl.local_lr, jnp.bfloat16)))
            acc_ctrl = term if acc_ctrl is None else jax.tree.map(
                lambda a, b: (a + b).astype(ml_dtypes.bfloat16), acc_ctrl,
                term)
            prog = acc_ctrl
        else:
            fresh_p, v_p = float(ack["fresh_loss"]), float(ack["v_weight"])
            prog = to_ref(acc_prog, 0.0)
        for name, gap in accum_gaps(prog, acc_ref).items():
            gaps[name] = max(gaps[name], gap)
        got["v"].append(v_p)
        got["fresh"].append(fresh_p)
        want["v"].append(v)
        want["fresh"].append(fresh)
    return {"v": common.rel_each(got["v"], want["v"]),
            "fresh": common.rel_each(got["fresh"], want["fresh"]), **gaps}


def check(state: State, control: bool = False) -> dict:
    t0 = time.perf_counter()
    readings = check_apply(state, control)
    state.step = None
    t1 = time.perf_counter()
    readings.update(check_setup_uploads(state, control))
    return {**readings, "check_apply_s": t1 - t0,
            "check_reference_s": time.perf_counter() - t1}
