"""Work of one upload of the DeepSeek-V2-Lite cell, from shapes.

``n_params`` counts the tree one chip holds: embedding and head over the
vocabulary slice, the final norm, the leading dense layers and the MoE
layers with their held experts (router, shared experts and MLA whole).

Model FLOPs count 2 per multiply-add of the products, per token:
MLA's projections and its causal scores over (S + 1) / 2 keys on
average (q.k over q/k head dims, p.v over v head dims); the dense
SwiGLU; per MoE layer the router, the shared SwiGLU and the routed
SwiGLU at top_k * held / E held experts a token (the expected pairs:
768 a held expert a step at S = 8,192); the head. Training is 3 times
the forward (the backward's input and weight gradients); recomputation
does not count. An upload trains M steps of B sequences and probes P
sequences forward (eq. 4). Softmax, norms and other elementwise work
are left out.
"""
from __future__ import annotations


def _w(cfg: dict) -> dict:
    return {"d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
            "r": cfg["kv_lora_rank"], "dn": cfg["qk_nope_head_dim"],
            "dr": cfg["qk_rope_head_dim"], "dv": cfg["v_head_dim"],
            "f": cfg["moe_intermediate_size"], "F": cfg["intermediate_size"],
            "e": cfg["n_routed_experts"], "k": cfg["num_experts_per_tok"],
            "n": cfg["experts_held"], "s": cfg["n_shared_experts"],
            "v": cfg["vocab_size"], "dense": cfg["first_k_dense_replace"],
            "moe": cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]}


def mla_params(w: dict) -> int:
    return (w["d"] * w["h"] * (w["dn"] + w["dr"])
            + w["d"] * (w["r"] + w["dr"]) + w["r"]
            + w["r"] * w["h"] * (w["dn"] + w["dv"])
            + w["h"] * w["dv"] * w["d"])


def n_params(cfg: dict) -> int:
    w = _w(cfg)
    norms = 2 * w["d"]
    dense = mla_params(w) + norms + 3 * w["d"] * w["F"]
    moe = (mla_params(w) + norms + w["d"] * w["e"]
           + w["n"] * 3 * w["d"] * w["f"] + 3 * w["d"] * w["s"] * w["f"])
    return (w["dense"] * dense + w["moe"] * moe + 2 * w["v"] * w["d"]
            + w["d"])


def forward_flops_per_token(cfg: dict, seq_len: int) -> float:
    w = _w(cfg)
    keys = (seq_len + 1) / 2  # causal: position i attends to i + 1 keys
    attn = 2 * mla_params(w) - 2 * w["r"] + 2 * keys * w["h"] * (
        w["dn"] + w["dr"] + w["dv"])
    dense = 2 * 3 * w["d"] * w["F"]
    routed = w["k"] * w["n"] / w["e"]
    moe = (2 * w["d"] * w["e"] + 2 * 3 * w["d"] * w["s"] * w["f"]
           + routed * 2 * 3 * w["d"] * w["f"])
    head = 2 * w["d"] * w["v"]
    return ((w["dense"] + w["moe"]) * attn + w["dense"] * dense
            + w["moe"] * moe + head)


def per_round(cfg: dict, traffic: dict) -> dict:
    """One upload: M steps of B sequences trained, P probed."""
    s = traffic["seq_len"]
    fwd = forward_flops_per_token(cfg, s)
    train = traffic["local_steps"] * traffic["batch"] * s * 3 * fwd
    probe = traffic["probe_batch"] * s * fwd
    return {"flops": train + probe, "bytes": 0}
