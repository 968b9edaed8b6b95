"""Work of one server round at whisper-tiny's update size, from shapes.

``n_params`` counts the parameter tree the configuration states: token
embedding tied to the output head, encoder and decoder blocks of
attention (no biases), MLP (with biases) and layer norms. ``pass_bytes``
is the least HBM traffic of one round of eq. 3 + 5 on the flat f32
layout: read x, the K bases and the K deltas, write the new x, on the
unpadded length, so that no change of tile or padding moves it.
"""
from __future__ import annotations

F32 = 4


def n_params(cfg: dict) -> int:
    d = cfg["d_model"]
    attn = 4 * d * d                      # q, k, v, o
    mlp_enc = 2 * d * cfg["encoder_ffn_dim"] + cfg["encoder_ffn_dim"] + d
    mlp_dec = 2 * d * cfg["decoder_ffn_dim"] + cfg["decoder_ffn_dim"] + d
    norm = 2 * d                          # scale and bias
    enc = cfg["encoder_layers"] * (attn + mlp_enc + 2 * norm) + norm
    dec = cfg["decoder_layers"] * (2 * attn + mlp_dec + 3 * norm) + norm
    embed = cfg["vocab_size"] * d
    head = 0 if cfg["tie_word_embeddings"] else embed
    return embed + enc + dec + head


def pass_bytes(n: int, k: int) -> int:
    """(2K + 2) * N * 4: x, K bases, K deltas read; new x written."""
    return (2 * k + 2) * n * F32


def per_round(cfg: dict, traffic: dict) -> dict:
    return {"bytes": pass_bytes(n_params(cfg), traffic["k"]), "flops": 0}
