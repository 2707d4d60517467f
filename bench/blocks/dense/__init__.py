"""The repo's dense decoder block, for configuration files of ``family``
``dense``: pre-RMSNorm, grouped-query attention with rotary positions, a
SwiGLU feed-forward and an output head tied to the embedding table.

``weights`` makes the served tree from the seed, ``reference`` is the
plain float32 forward the correctness check compares with, and ``work``
counts the useful operations and bytes the readers divide by device
time.
"""

from __future__ import annotations

import dataclasses

from . import reference, weights, work  # noqa: F401  (the block's parts)

# the configuration file's keys that set the program's sizes
SHAPE_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
              "d_ff", "vocab_size", "rope_theta", "norm_eps")


def configure(base, cfg: dict):
    """The program's ``base`` config at the sizes of configuration ``cfg``."""
    return dataclasses.replace(base, name=cfg["name"],
                               **{k: cfg[k] for k in SHAPE_KEYS})


def accepts(mc) -> bool:
    """Whether ``reference`` covers the program's config ``mc``: the plain
    dense block, with no QK norm, window, experts, latent attention or
    state-space layers."""
    return not (mc.family != "dense" or mc.qk_norm or mc.sliding_window
                or mc.moe or mc.mla or mc.ssm)
