"""Useful work of the dense decoder, from its sizes and true lengths.

Counts what the model needs, never what the program dispatches: padded
rows, padded buckets and frozen decode lanes count nothing. A multiply-add
is two operations. The embedding is one table, gathered for the input and
read whole as the output head (the program ties the two).
"""

from __future__ import annotations

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def layer_params(cfg: dict) -> int:
    d, h, hk, hd, f = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                       cfg["head_dim"], cfg["d_ff"])
    attn = d * h * hd + 2 * d * hk * hd + h * hd * d
    return attn + 3 * d * f + 2 * d  # projections, SwiGLU, two norms


def embed_params(cfg: dict) -> int:
    return cfg["vocab_size"] * cfg["d_model"]


def param_count(cfg: dict) -> int:
    """Parameters the model holds: the layers, one table, the final norm."""
    return (cfg["n_layers"] * layer_params(cfg) + embed_params(cfg)
            + cfg["d_model"])


def _matmul_params(cfg: dict) -> int:
    return layer_params(cfg) - 2 * cfg["d_model"]


def attn_flops(cfg: dict, queries: int, context_first: int) -> float:
    """Scores and weighted values for ``queries`` causal positions, the
    first of which sees ``context_first`` keys, over all layers."""
    keys = queries * context_first + queries * (queries - 1) / 2
    return 4.0 * cfg["n_layers"] * cfg["n_heads"] * cfg["head_dim"] * keys


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """One prompt's prefill: every layer over every prompt token, causal
    attention, and the head at the one position that is sampled."""
    n = int(prompt_len)
    dense = 2.0 * n * cfg["n_layers"] * _matmul_params(cfg)
    return dense + attn_flops(cfg, n, 1) + 2.0 * embed_params(cfg)


def decode_flops(cfg: dict, context: int) -> float:
    """One decode token of one row whose cache holds ``context`` tokens
    (the new one included)."""
    dense = 2.0 * (cfg["n_layers"] * _matmul_params(cfg) + embed_params(cfg))
    return dense + attn_flops(cfg, 1, int(context))


def weight_bytes(cfg: dict) -> int:
    return param_count(cfg) * BYTES[cfg["dtype"]]


def kv_bytes(cfg: dict, context: int) -> int:
    """Keys and values of ``context`` tokens over all layers."""
    return (2 * cfg["n_layers"] * cfg["n_kv_heads"] * cfg["head_dim"]
            * int(context) * BYTES[cfg["dtype"]])


def decode_work(cfg: dict, steps: int, contexts) -> tuple:
    """(FLOPs, bytes) of ``steps`` decode steps that together produced one
    token for each entry of ``contexts``: each step reads the weights once,
    each row its own true cache."""
    flops = sum(decode_flops(cfg, c) for c in contexts)
    nbytes = steps * weight_bytes(cfg) + sum(kv_bytes(cfg, c) for c in contexts)
    return flops, nbytes

