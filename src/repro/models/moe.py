"""Mixture-of-Experts FFN with sort-based capacity dispatch.

TPU adaptation: instead of per-token dynamic routing (GPU-style gather
kernels), tokens are dispatched into a static (E, C, d) buffer via an
argsort over expert assignments — a dense, collective-friendly layout.
Experts are sharded over the ``model`` mesh axis (expert parallelism); XLA
inserts the all-to-all when activations move from token-sharded to
expert-sharded layout. Over-capacity tokens are dropped (standard
capacity-factor semantics); the router aux loss balances load.

``moe_held_ffn`` is the dropless layer of DeepSeek-V2 under expert
parallelism: it is told which experts its parameters hold
(``cfg.experts_held``), routes every token over all ``num_experts``, and
computes only its held experts' part of the result, with no capacity and
no drop. Selected by ``cfg.experts_held``; the capacity layer above
serves every other MoE architecture.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init, matmul


def init_moe(key, cfg):
    """Router over all ``num_experts``; expert weights of the held ones."""
    dtype = jnp.dtype(cfg.param_dtype)
    d, dff, e = cfg.d_model, cfg.resolved_moe_d_ff, cfg.num_experts
    held = cfg.held_experts[1]
    keys = jax.random.split(key, 8)

    def stack_init(k, shape_in, shape_out, n):
        ks = jax.random.split(k, n)
        return jnp.stack([dense_init(ki, shape_in, shape_out, dtype) for ki in ks])

    p = {
        "router": dense_init(keys[0], d, e, jnp.float32, scale=0.02),
        "w_gate": stack_init(keys[1], d, dff, held),  # (E_held, d, dff)
        "w_up": stack_init(keys[2], d, dff, held),
        "w_down": stack_init(keys[3], dff, d, held),
    }
    if cfg.num_shared_experts:
        sd = dff * cfg.num_shared_experts
        p["shared"] = {
            "w_gate": dense_init(keys[4], d, sd, dtype),
            "w_up": dense_init(keys[5], d, sd, dtype),
            "w_down": dense_init(keys[6], sd, d, dtype, scale=sd ** -0.5),
        }
    return p


def _expert_ffn(p, x):
    """x: (E, C, d) -> (E, C, d) with per-expert SwiGLU weights."""
    gate = jnp.einsum("ecd,edf->ecf", x, p["w_gate"])
    up = jnp.einsum("ecd,edf->ecf", x, p["w_up"])
    h = jax.nn.silu(gate) * up
    return jnp.einsum("ecf,efd->ecd", h, p["w_down"])


def _dispatch_group(cfg, p, xf, cap):
    """Sort-based dispatch + expert FFN + combine for ONE token group.

    xf: (Tg, d). All gathers/scatters here index only group-local tensors,
    so under vmap-over-groups (group dim sharded on ``data``) SPMD keeps
    every intermediate sharded — no involuntary replication.
    """
    e, k = cfg.num_experts, cfg.experts_per_token
    tg, d = xf.shape
    logits = (xf.astype(jnp.float32)) @ p["router"]  # (Tg, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)  # (Tg, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # load-balance aux (Switch): E * sum_e f_e * p_e
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(expert_ids[:, 0], e), axis=0)
    aux = e * jnp.sum(me * ce) * cfg.router_aux_coef

    flat_e = expert_ids.reshape(-1)  # (Tg*k,)
    sort_idx = jnp.argsort(flat_e)
    sorted_e = flat_e[sort_idx]
    token_idx = sort_idx // k
    counts = jax.ops.segment_sum(jnp.ones_like(sorted_e), sorted_e,
                                 num_segments=e)
    offsets = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                               jnp.cumsum(counts)[:-1]])
    pos_in_e = jnp.arange(tg * k) - offsets[sorted_e]
    keep = pos_in_e < cap
    slot = sorted_e * cap + jnp.where(keep, pos_in_e, 0)

    gathered = xf[token_idx] * keep[:, None].astype(xf.dtype)
    buf = jnp.zeros((e * cap, d), xf.dtype).at[slot].add(
        jnp.where(keep[:, None], gathered, 0))
    buf = buf.reshape(e, cap, d)

    out_buf = _expert_ffn(p, buf).reshape(e * cap, d)

    y_tok = out_buf[slot] * keep[:, None].astype(out_buf.dtype)
    w = gate_vals.reshape(-1)[sort_idx].astype(y_tok.dtype)
    y = jnp.zeros((tg, d), y_tok.dtype).at[token_idx].add(y_tok * w[:, None])
    return y, aux


def moe_ffn(cfg, p, x, capacity_factor: float = None):
    """x: (B, S, d). Returns (y, aux_loss).

    Dispatch runs independently in ``cfg.moe_groups`` token groups (grouped
    a2a layout: group dim rides the data axis, experts ride the model axis),
    falling back to one global group when tokens don't split evenly.
    """
    if capacity_factor is None:
        capacity_factor = cfg.moe_capacity_factor
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    t = b * s
    # decode (s == 1): guarantee dropless dispatch — serving must not lose
    # tokens to capacity; the buffer is tiny at one token per sequence.
    if s == 1:
        capacity_factor = max(capacity_factor, float(e) / max(k, 1))
    g = cfg.moe_groups if (cfg.moe_groups > 1 and t % cfg.moe_groups == 0) else 1
    tg = t // g
    cap = int(max(1, (k * tg / e) * capacity_factor))
    xg = x.reshape(g, tg, d)
    y, aux = jax.vmap(lambda xf: _dispatch_group(cfg, p, xf, cap))(xg)
    y = y.reshape(t, d)
    aux = jnp.mean(aux)

    # --- shared experts (always active) ----------------------------------
    if cfg.num_shared_experts:
        xf = x.reshape(t, d)
        sp = p["shared"]
        h = jax.nn.silu(xf @ sp["w_gate"]) * (xf @ sp["w_up"])
        y = y + h @ sp["w_down"]

    return y.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# the dropless layer that holds a range of the experts (DeepSeek-V2)
# ---------------------------------------------------------------------------


def _shared_ffn(cfg, sp, xf):
    h = jax.nn.silu(matmul(cfg, xf, sp["w_gate"])) * matmul(cfg, xf, sp["w_up"])
    return matmul(cfg, h, sp["w_down"])


def _grouped(cfg, x, w, sizes):
    """Rows of ``x`` sorted by held expert times that expert's weight.

    Accumulated in float32 inside the product but returned in
    ``cfg.matmul_dtype``, unlike ``layers.matmul``: the (T * k, .) rows
    and their gradients are the layer's largest buffers, and in float32
    DeepSeek-V2-Lite's step no longer fits one chip's share on a v5e
    (16.26 GB of arguments and temporaries against 15.40). So the gate
    and up outputs are rounded to bf16 before the SwiGLU, and the down
    output before the top-k weighting."""
    dt = jnp.dtype(cfg.matmul_dtype) if cfg.matmul_dtype else x.dtype
    return jax.lax.ragged_dot(x.astype(dt), w.astype(dt), sizes,
                              preferred_element_type=dt)


@jax.named_scope("moe_held")
def moe_held_ffn(cfg, p, x):
    """x: (B, S, d). Returns (y, aux_loss, held_counts).

    DeepSeek-V2's MoE as published: softmax scores over all E experts in
    float32, greedy top-k, the top-k scores used as weights without
    renormalization (``norm_topk_prob`` false, ``routed_scaling_factor``
    1), and the shared experts applied to every token. Every (token,
    held expert) pair is computed: the pairs are sorted by expert and run
    through the held experts' SwiGLU as grouped products. ``aux_loss`` is
    the sequence-wise balance loss over all E scores times
    ``router_aux_coef``; ``held_counts`` (E_held,) int32 the routed pairs
    of each held expert.
    """
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    first, n = cfg.held_experts
    t = b * s
    xf = x.reshape(t, d)
    logits = jnp.dot(xf.astype(jnp.float32), p["router"],
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.softmax(logits, axis=-1)  # (T, E)
    top_w, top_e = jax.lax.top_k(scores, k)

    # sequence-wise balance loss: alpha * sum_i f_i P_i per sequence
    chosen = jnp.sum(jax.nn.one_hot(top_e, e, dtype=jnp.float32), axis=1)
    f = jnp.sum(chosen.reshape(b, s, e), axis=1) * (e / (s * k))
    pm = jnp.mean(scores.reshape(b, s, e), axis=1)
    aux = cfg.router_aux_coef * jnp.mean(jnp.sum(f * pm, axis=-1))

    # the held pairs first, sorted by held expert; the rest past them
    local = top_e.reshape(-1) - first
    held = (local >= 0) & (local < n)
    key = jnp.where(held, local, n)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.bincount(key, length=n + 1)[:n].astype(jnp.int32)
    mine = held[order][:, None]
    # rows past the held groups are masked in and out (where, not a
    # product: the grouped product leaves them unspecified)
    xs = jnp.where(mine, xf[order // k], 0)
    h = (jax.nn.silu(_grouped(cfg, xs, p["w_gate"], sizes))
         * _grouped(cfg, xs, p["w_up"], sizes))
    ys = jnp.where(mine, _grouped(cfg, h, p["w_down"], sizes), 0)
    # back to (token, k) order: the inverse permutation is a gather
    ys = ys[jnp.argsort(order)].reshape(t, k, d).astype(jnp.float32)
    y = jnp.sum(ys * top_w[..., None], axis=1)
    y = y + _shared_ffn(cfg, p["shared"], xf).astype(y.dtype)
    return y.reshape(b, s, d).astype(x.dtype), aux, sizes
