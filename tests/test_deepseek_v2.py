"""DeepSeek-V2-Lite's mechanisms against the plain reference, on the CPU.

The reference (``benchmarks/chip/reference/deepseek_v2.py``) is written
from the published description in float32 at HIGHEST precision and
imports nothing of the program; the streaming fold's reference is
``benchmarks/chip/reference/fl_stream.py`` (float64). The program runs
here in float32 (no bf16 matmul operands), at small widths with the
published YaRN settings, on seeded random weights; the benchmark's
chip check compares the two at the published widths. Tolerances are
float32 summation-order bounds, each written beside its test.
"""
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FLConfig
from repro.configs.registry import get_arch
from repro.core.cohort import init_dist_state, make_dist_step
from repro.models import attention as attn
from repro.models import layers, moe
from repro.models.model import build_model
from repro.obs.trace import default_tracer

CHIP = pathlib.Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
if str(CHIP) not in sys.path:
    sys.path.insert(0, str(CHIP))

import common  # noqa: E402
from reference import deepseek_v2 as ref  # noqa: E402
from reference import fl_stream  # noqa: E402

to_ref = common.load_module("drivers", "dist_stream").to_ref

E, K_TOP, D = 16, 6, 64
CONFIG = {  # the reference's keys, at small widths
    "hidden_size": D, "num_attention_heads": 4, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "n_routed_experts": E, "num_experts_per_tok": K_TOP,
    "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096},
    "rms_norm_eps": 1e-6, "aux_alpha": 0.001}


def program_cfg(held=(0, E)):
    """The registry's deepseek-v2-lite at the same small widths, f32."""
    return get_arch("deepseek-v2-lite").model.replace(
        num_layers=3, d_model=D, num_heads=4, num_kv_heads=4, d_ff=96,
        moe_d_ff=32, vocab_size=128, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, num_experts=E, experts_held=held,
        matmul_dtype=None)


def dims(held=(0, E), q_block=512):
    return ref.dims({**CONFIG, "experts_held_range": held}, q_block=q_block)


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def tokens(seed, shape, vocab=128):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0, vocab)


def test_yarn_as_published():
    """At the published rope (64 dims, theta 1e4, factor 40, original
    4,096 positions, betas 32 and 1) the ramp runs from dim 10 to 23:
    below it the frequencies are kept, above it divided by 40; the
    softmax scale is 192^-1/2 mscale(40, 0.707)^2 = 0.1147. The program's
    float32 frequencies agree with the reference's float64 to 1e-6."""
    cfg = get_arch("deepseek-v2-lite").model
    prog = layers.yarn_frequencies(64, 10000.0, cfg.rope_yarn)
    c = ref.dims({**CONFIG, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                  "experts_held_range": (0, E)})
    want = ref.yarn_inv_freq(c)
    np.testing.assert_allclose(prog, want, rtol=1e-6)
    extra = 10000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(want[:10], extra[:10], rtol=1e-12)
    np.testing.assert_allclose(want[23:], extra[23:] / 40, rtol=1e-12)
    assert abs(attn.mla_softmax_scale(cfg) - 0.1147214) < 1e-6
    assert abs(attn.mla_softmax_scale(cfg) - ref.softmax_scale(c)) < 1e-12
    assert attn.mla_rope(cfg)[1] == 1.0  # mscale == mscale_all_dim


@pytest.mark.parametrize("seq,chunked", [(64, False), (1024, True)])
def test_mla_matches_reference(seq, chunked):
    """MLA with YaRN, causal, on both training paths (materialized scores
    and the checkpointed q-chunk scan) against the reference's per-head
    blocks: float32 against float32-HIGHEST, relative 2e-5 (sums over
    up to 1,024 keys in a different order)."""
    cfg = program_cfg().replace(force_chunked_attn=chunked)
    p = attn.init_attention(jax.random.PRNGKey(1), cfg)
    p["kv_norm"] = 0.1 * jax.random.normal(jax.random.PRNGKey(2), (32,))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, seq, D))
    got = attn.attention_train(cfg, p, x)
    rp = dict(p, kv_norm=p["kv_norm"] + 1.0)
    for b in range(2):
        want = ref.attention(rp, x[b], dims(q_block=256))
        assert rel(got[b], want) < 2e-5


def test_stack_matches_reference():
    """The leading dense layer and the scanned MoE stack: the loss and
    every gradient leaf against the reference's. Relative 1e-5 on the
    loss and 2e-4 on each leaf's largest element (float32 backward
    through three layers, summed in another order)."""
    model = build_model(program_cfg())
    params = model.init(jax.random.PRNGKey(4))
    assert params["dense_layers"]["mlp"]["w_gate"].shape == (1, D, 96)
    assert params["layers"]["moe"]["w_gate"].shape == (2, E, D, 32)
    batch = {"tokens": tokens(5, (2, 32)), "labels": tokens(6, (2, 32))}
    loss, g = jax.value_and_grad(lambda q: model.loss(q, batch)[0])(params)
    rp = to_ref(params, 1.0)
    c = dims()
    want_loss, want_g = jax.value_and_grad(ref.loss)(rp, batch, c)
    assert rel(loss, want_loss) < 1e-5
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(
            to_ref(g, 0.0))[0], jax.tree.leaves(want_g)):
        assert rel(a, b) < 2e-4, jax.tree_util.keystr(path)


def test_held_shares_sum_to_uncut_layer():
    """Eight chips' shares of one MoE layer (2 of the 16 experts each):
    every share routes over all 16 and computes only its own experts'
    part, so the shares' outputs, with the shared experts (which every
    chip computes alike) counted once, add up to the uncut reference
    layer. Each share's counts are its own experts' routed pairs, the
    eight together every (token, expert) pair; each share's balance loss
    is the whole layer's. Relative 1e-5 (float32, summed in another
    order)."""
    whole = program_cfg()
    p = moe.init_moe(jax.random.PRNGKey(7), whole)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 32, D))
    outs, counts = [], []
    for first in range(0, E, 2):
        share = whole.replace(experts_held=(first, 2))
        sp = dict(p, **{k: p[k][first:first + 2]
                        for k in ("w_gate", "w_up", "w_down")})
        y, aux, n = moe.moe_held_ffn(share, sp, x)
        outs.append(y)
        counts.append(n)
    shared = moe._shared_ffn(whole, p["shared"], x.reshape(-1, D))
    total = sum(outs) - (len(outs) - 1) * shared.reshape(x.shape)
    rp = {"router": p["router"], "shared": p["shared"],
          "experts": {k: p[k] for k in ("w_gate", "w_up", "w_down")}}
    for b in range(2):
        want, want_aux = ref.moe(rp, x[b], dims())
        assert rel(total[b], want) < 1e-5
    assert int(sum(jnp.sum(n) for n in counts)) == 2 * 32 * K_TOP
    _, aux_whole, _ = moe.moe_held_ffn(whole, p, x)
    assert abs(float(aux) - float(aux_whole)) < 1e-7


def np_leaves(tree):
    return [np.asarray(a, np.float64) for a in jax.tree.leaves(tree)]


def test_dist_step_matches_fl_stream():
    """``make_dist_step`` with the model (held experts 0-7 of 16) over two
    buffers of K=3 uploads, against ``reference/fl_stream.py`` folding the
    same uploads in float64 with the reference model's local SGD and
    probe loss. Compared: each upload's v and fresh loss (float32 model
    against float32-HIGHEST reference: 1e-5 relative) and the parameters'
    change by the worst leaf (``common.leaf_change_gap``, limit 1e-3: the
    norm scales sit near 1, where float32 spaces values by 1.2e-7,
    against changes of about 1e-4 over two applies; readings 1.5e-4)."""
    held = (0, 8)
    model = build_model(program_cfg(held))
    fl = FLConfig(buffer_size=3, local_steps=2, local_lr=5e-3,
                  weighting="paper", normalize="mean", max_staleness=4)
    params = model.init(jax.random.PRNGKey(9))
    g = np.random.default_rng(10)
    ups = []
    for i in range(6):
        seqs = np.asarray(tokens(100 + i, (fl.local_steps + 1, 1, 33)))
        ups.append({"local": {"tokens": seqs[:-1, :, :-1],
                              "labels": seqs[:-1, :, 1:]},
                    "probe": {"tokens": seqs[-1, :, :-1],
                              "labels": seqs[-1, :, 1:]},
                    "tau": 0 if i % 3 == 0 else int(g.integers(0, 3)),
                    "data_size": float(g.integers(500, 3000))})
    step = jax.jit(make_dist_step(model.loss, fl))
    state = init_dist_state(params, fl)
    prog = []
    for up in ups:
        state, mets = step(state, {
            "local": up["local"], "probe": up["probe"],
            "tau": jnp.int32(up["tau"]),
            "data_size": jnp.float32(up["data_size"])})
        prog.append({"v": float(mets["v_weight"]),
                     "fresh": float(mets["fresh_loss"])})
        assert mets["local"]["expert_counts"].shape == (
            fl.local_steps, 2, held[1])
    c = dims(held)
    treedef = jax.tree.structure(to_ref(params, 1.0))

    def unflat(leaves):
        return jax.tree.unflatten(
            treedef, [jnp.asarray(a, jnp.float32) for a in leaves])

    def local_update(x, local):
        return np_leaves(ref.local_sgd(unflat(x), local, c, fl.local_lr))

    def loss(x, probe):
        return float(ref.loss(unflat(x), probe, c))

    start = np_leaves(to_ref(params, 1.0))
    ref_end, want = fl_stream.run(
        start, ups, k=fl.buffer_size, local_update=local_update, loss=loss,
        s_min=fl.s_min, max_staleness=fl.max_staleness)
    for key in ("v", "fresh"):
        assert common.rel_each([p[key] for p in prog],
                               [w[key] for w in want]) < 1e-5, key
    end = np_leaves(to_ref(state.global_params, 1.0))
    assert common.leaf_change_gap(start, end, ref_end) < 1e-3


def test_train_runs_the_streaming_loop(monkeypatch):
    """``launch/train.py`` runs a distributed arch through ``fold_upload``:
    one stage, contribute and host_sync span per upload in the default
    recorder, the held experts' counts on each acknowledgement, and the
    upload and apply counters."""
    from repro.launch import train

    stacks = []
    real = train.ObsStack.from_args

    def keep(args):
        stacks.append(real(args))
        return stacks[-1]

    monkeypatch.setattr(train.ObsStack, "from_args", keep)
    monkeypatch.setattr(sys, "argv", [
        "train", "--arch", "deepseek-v2-lite", "--smoke", "--rounds", "2",
        "--buffer-k", "2", "--seq", "32", "--batch", "1", "--cohort", "4"])
    tracer = default_tracer()
    before = len(tracer.events)
    train.main()
    events = list(tracer.events)[before:]
    names = [ev["name"] for ev in events]
    assert names.count("stage") == names.count("contribute") == 4
    syncs = [ev for ev in events if ev["name"] == "host_sync"]
    assert len(syncs) == 4
    counts = np.asarray(syncs[0]["args"]["expert_counts"])
    assert counts.shape == (2, 1, 4)  # local steps, MoE layers, held
    assert counts.sum() == 2 * 32 * 2  # every pair: held = all 4 experts
    assert all(ev["args"]["bytes"] > 0 for ev in events
               if ev["name"] == "stage")
    snap = stacks[0].registry.snapshot()
    assert snap["dist_uploads_total"] == 4
    assert snap["dist_applies_total"] == 2
