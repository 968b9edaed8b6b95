"""Plain DeepSeek-V2 (-Lite) language model: forward, loss and local SGD.

Written from the published description (DeepSeek-V2, arXiv:2405.04434,
and the model's ``config.json`` / ``modeling_deepseek.py``), importing
nothing of the program. Every product runs in float32 at
``Precision.HIGHEST``. One sequence at a time:

    h = embed[tokens]
    layer:  h += MLA(rmsnorm(h));  h += FFN(rmsnorm(h))
            the first ``first_k_dense_replace`` FFNs dense SwiGLU, the
            rest MoE
    logits = rmsnorm(h) @ lm_head;  loss = mean CE + the layers' aux

MLA (no q_lora): q = h Wq, per head [q_nope 128 | q_pe 64];
[c_kv 512 | k_pe 64] = h Wkv_a; c_kv = rmsnorm(c_kv);
[k_nope 128 | v 128] = c_kv Wkv_b per head; q_pe and the one k_pe all
heads share get YaRN rope (the published de-interleave of the pairs,
then rotate-half); k = [k_nope | k_pe]; causal softmax at
(192)^-1/2 * mscale(40, 0.707)^2; out Wo. Queries in blocks, so no
(S, S) score matrix of all heads is ever held.

MoE: softmax scores over all E experts, greedy top-k, the top-k scores
as weights unrenormalized; the held experts ``held = (first, count)``
each applied to every token and weighted by its score where chosen (0
elsewhere); the shared experts as one SwiGLU over every token; aux the
sequence-wise balance loss alpha * sum_i f_i P_i over all E.

Parameters, the layers of ``dense`` and of ``moe`` each stacked on a
leading layer axis and scanned in that order::

    embed (V, d); lm_head (d, V); final_norm (d,)
    layer: input_norm, post_norm (d,);
           attn: wq (d, H*192), wkv_a (d, 576), kv_norm (512,),
                 wkv_b (512, H*256), wo (H*128, d)
           mlp: w_gate, w_up (d, F), w_down (F, d)       dense layers
           moe: router (d, E); experts: w_gate, w_up (n, d, f),
                w_down (n, f, d); shared: w_gate, w_up (d, s*f),
                w_down (s*f, d)                            MoE layers

Departures from the published model, each also the program's:
- norms scale by ``w`` as published; the program stores ``w - 1``
  (it scales by ``1 + w``), so a caller adds 1 to its norm weights;
- the vocabulary, depth and held experts are whatever the parameters
  hold: a chip's share of the deployment (the benchmark's config);
- the loss adds the balance loss to the cross-entropy, where the
  published code adds only its gradient.

``dtype=bfloat16`` in ``local_sgd`` keeps the parameters and each SGD
update in bf16 (the control); the products stay float32. The gradient
of one step is one compiled program; the M steps are a host loop.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


class Dims(NamedTuple):
    d: int
    heads: int
    rank: int
    nope: int
    rope: int
    v: int
    experts: int
    top_k: int
    held_first: int
    held_count: int
    theta: float
    factor: float
    orig_max_pos: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float
    eps: float
    alpha: float
    q_block: int


def dims(config: Dict, q_block: int = 512) -> Dims:
    """The widths a benchmark configuration states (``config.json`` keys)."""
    rs = config["rope_scaling"]
    first, count = config["experts_held_range"]
    return Dims(
        d=config["hidden_size"], heads=config["num_attention_heads"],
        rank=config["kv_lora_rank"], nope=config["qk_nope_head_dim"],
        rope=config["qk_rope_head_dim"], v=config["v_head_dim"],
        experts=config["n_routed_experts"],
        top_k=config["num_experts_per_tok"], held_first=first,
        held_count=count, theta=float(config["rope_theta"]),
        factor=float(rs["factor"]),
        orig_max_pos=int(rs["original_max_position_embeddings"]),
        beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
        mscale=float(rs["mscale"]), mscale_all_dim=float(rs["mscale_all_dim"]),
        eps=float(config["rms_norm_eps"]), alpha=float(config["aux_alpha"]),
        q_block=q_block)


# -- YaRN -----------------------------------------------------------------


def _mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(c: Dims) -> np.ndarray:
    """inv_freq_i = inter (1 - mask) + extra mask, i < rope/2, with
    extra = theta^(-2i/rope), inter = extra / factor and mask = 1 -
    clip((i - low) / (high - low), 0, 1) between the correction dims
    of beta_fast and beta_slow rotations over the original context."""
    dim = c.rope
    extra = 1.0 / c.theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / c.factor

    def corr(rot):
        return dim * math.log(c.orig_max_pos / (rot * 2 * math.pi)) / (
            2 * math.log(c.theta))

    low = max(math.floor(corr(c.beta_fast)), 0)
    high = min(math.ceil(corr(c.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    return inter * (1.0 - mask) + extra * mask


def softmax_scale(c: Dims) -> float:
    m = _mscale(c.factor, c.mscale_all_dim)
    return (c.nope + c.rope) ** -0.5 * m * m


def rope(x, positions, c: Dims):
    """x (S, ..., rope): de-interleave the pairs, then rotate-half."""
    s = x.shape
    x = x.reshape(s[:-1] + (s[-1] // 2, 2))
    x = jnp.swapaxes(x, -1, -2).reshape(s)
    freqs = positions[:, None].astype(jnp.float32) * jnp.asarray(
        yarn_inv_freq(c), jnp.float32)[None]
    emb = jnp.concatenate([freqs, freqs], axis=-1)  # (S, rope)
    ms = _mscale(c.factor, c.mscale) / _mscale(c.factor, c.mscale_all_dim)
    shape = (s[0],) + (1,) * (len(s) - 2) + (s[-1],)
    cos = (jnp.cos(emb) * ms).reshape(shape)
    sin = (jnp.sin(emb) * ms).reshape(shape)
    half = s[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rot * sin


# -- layers -----------------------------------------------------------------


def mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def swiglu(p, x):
    return mm(jax.nn.silu(mm(x, p["w_gate"])) * mm(x, p["w_up"]), p["w_down"])


def attention(p, x, c: Dims):
    """x (S, d) -> (S, d), causal."""
    s = x.shape[0]
    pos = jnp.arange(s)
    q = mm(x, p["wq"]).reshape(s, c.heads, c.nope + c.rope)
    kv_a = mm(x, p["wkv_a"])
    c_kv = rmsnorm(kv_a[:, :c.rank], p["kv_norm"], c.eps)
    kv = mm(c_kv, p["wkv_b"]).reshape(s, c.heads, c.nope + c.v)
    q = jnp.concatenate([q[..., :c.nope], rope(q[..., c.nope:], pos, c)], -1)
    k_pe = rope(kv_a[:, c.rank:], pos, c)  # (S, rope): one for all heads
    k = jnp.concatenate(
        [kv[..., :c.nope], jnp.broadcast_to(k_pe[:, None], (s, c.heads, c.rope))],
        -1)
    v = kv[..., c.nope:]
    qb = min(c.q_block, s)
    scale = softmax_scale(c)

    @jax.checkpoint
    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=0)
        scores = jnp.einsum("qhd,khd->hqk", qi, k, precision=HI) * scale
        causal = (i * qb + jnp.arange(qb))[:, None] >= jnp.arange(s)[None]
        scores = jnp.where(causal[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v, precision=HI)

    out = jax.lax.map(block, jnp.arange(s // qb)).reshape(s, c.heads * c.v)
    return mm(out, p["wo"])


def moe(p, x, c: Dims):
    """x (S, d) -> (the held experts' part + the shared experts, aux)."""
    s = x.shape[0]
    scores = jax.nn.softmax(mm(x, p["router"]), axis=-1)  # (S, E)
    top_w, top_e = jax.lax.top_k(scores, c.top_k)

    def expert(y, e):  # one held expert over every token
        j, w = e
        gate = jnp.sum(jnp.where(top_e == c.held_first + j, top_w, 0.0), -1)
        return y + gate[:, None] * swiglu(w, x), None

    y, _ = jax.lax.scan(expert, swiglu(p["shared"], x),
                        (jnp.arange(c.held_count), p["experts"]))
    counts = jnp.sum(jax.nn.one_hot(top_e, c.experts), axis=(0, 1))
    f = counts * c.experts / (s * c.top_k)
    aux = c.alpha * jnp.sum(f * jnp.mean(scores, axis=0))
    return y, aux


def layer(p, x, c: Dims):
    x = x + attention(p["attn"], rmsnorm(x, p["input_norm"], c.eps), c)
    h = rmsnorm(x, p["post_norm"], c.eps)
    if "moe" in p:
        y, aux = moe(p["moe"], h, c)
        return x + y, aux
    return x + swiglu(p["mlp"], h), jnp.zeros((), jnp.float32)


def sequence_loss(params, tokens, labels, c: Dims):
    """Mean next-token CE of one sequence, plus the layers' aux."""
    x = params["embed"][tokens]
    aux = jnp.zeros((), jnp.float32)

    @jax.checkpoint
    def body(carry, p):
        x, aux = carry
        x, a = layer(p, x, c)
        return (x, aux + a), None

    for stack in ("dense", "moe"):  # each a scan over its layers
        (x, aux), _ = jax.lax.scan(body, (x, aux), params[stack])
    logits = mm(rmsnorm(x, params["final_norm"], c.eps), params["lm_head"])
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - gold) + aux


def loss(params, batch, c: Dims):
    """``batch`` tokens and labels (B, S): the mean over sequences."""
    per = [sequence_loss(params, t, l, c)
           for t, l in zip(batch["tokens"], batch["labels"])]
    return sum(per) / len(per)


@functools.lru_cache(maxsize=None)
def compiled(c: Dims):
    """(gradient, loss) of ``loss`` jitted for the dims ``c``."""
    f = functools.partial(loss, c=c)
    return jax.jit(jax.grad(f)), jax.jit(f)


@functools.partial(jax.jit, static_argnames=("lr", "dtype"), donate_argnums=0)
def _sgd_step(q, g, lr, dtype):
    return jax.tree.map(lambda a, b: (a.astype(jnp.float32) - lr * b
                                      ).astype(dtype), q, g)


def local_sgd(params, batches, c: Dims, lr: float, dtype=jnp.float32):
    """M plain SGD steps over (M, B, S) batches; returns base - end.

    With ``dtype`` bf16 the parameters and every update are rounded to
    it (the control)."""
    grad, _ = compiled(c)
    base = jax.tree.map(lambda a: a.astype(dtype), params)
    q = jax.tree.map(jnp.copy, base)
    for m in range(batches["tokens"].shape[0]):
        step = {k: v[m] for k, v in batches.items()}
        g = grad(jax.tree.map(lambda a: a.astype(jnp.float32), q), step)
        q = _sgd_step(q, g, lr, dtype)
        del g
    return jax.tree.map(jnp.subtract, base, q)
