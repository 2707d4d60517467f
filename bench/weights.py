"""Weights of a dense decoder made from the run's seed, on the device.

One generator serves both sides of the correctness check. The harness
calls :func:`make_params` once, in one jitted call, and hands the tree to
the system under test in the type it serves (bfloat16). The reference
calls :func:`layer_weights` for one layer at a time after the window, so
it never holds the whole model, and rounds each leaf through the served
type exactly as :func:`make_params` does. Every leaf of every layer has
its own key, folded from the seed, the leaf's index and the layer, so the
two calls give the same numbers.

The tree follows the program's parameter layout for a dense stack (one
scanned group, leaves stacked over layers); the harness checks it
against the program's own abstract parameters before serving.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# leaf name -> (shape from dims, kind); order fixes each leaf's key
LAYER_LEAVES = ("ln1", "attn.wq", "attn.wk", "attn.wv", "attn.wo", "ln2",
                "ffn.w_gate", "ffn.w_up", "ffn.w_down")
TOP_LEAVES = ("embed", "final_norm")


def dims(cfg: dict) -> tuple:
    """Static sizes the generator and the reference need, as a tuple."""
    vp = ((cfg["vocab_size"] + 255) // 256) * 256  # the program pads to 256
    return (cfg["n_layers"], cfg["d_model"], cfg["n_heads"],
            cfg["n_kv_heads"], cfg["head_dim"], cfg["d_ff"], vp)


def _layer_shape(name: str, dm: tuple) -> tuple:
    _, d, h, hk, hd, f, _ = dm
    return {
        "ln1": (d,), "ln2": (d,),
        "attn.wq": (d, h * hd), "attn.wk": (d, hk * hd),
        "attn.wv": (d, hk * hd), "attn.wo": (h * hd, d),
        "ffn.w_gate": (d, f), "ffn.w_up": (d, f), "ffn.w_down": (f, d),
    }[name]


def seed_words(seed: int) -> np.ndarray:
    """A seed of up to 64 bits as two uint32 words (traced, so one
    compiled generator serves every seed)."""
    seed = int(seed) % (1 << 64)
    return np.asarray([seed & 0xFFFFFFFF, seed >> 32], np.uint32)


def _base_key(words):
    return jax.random.fold_in(jax.random.key(words[0]), words[1])


def _sample(key, shape, kind: str):
    """Uniform draws with the variance of the program's own init: norms
    around 1, the embedding at std 0.02, projections at 1/sqrt(fan_in)."""
    if kind == "norm":
        return 1.0 + jax.random.uniform(key, shape, jnp.float32, -0.25, 0.25)
    std = 0.02 if kind == "embed" else 1.0 / math.sqrt(shape[0])
    a = std * math.sqrt(3.0)
    return jax.random.uniform(key, shape, jnp.float32, -a, a)


def _kind(name: str) -> str:
    if name in ("ln1", "ln2", "final_norm"):
        return "norm"
    return "embed" if name == "embed" else "matrix"


def _top_leaf(words, name: str, dm: tuple):
    _, d, *_rest, vp = dm
    shape = (vp, d) if name == "embed" else (d,)
    key = jax.random.fold_in(_base_key(words), 1000 + TOP_LEAVES.index(name))
    return _sample(key, shape, _kind(name))


def _layer_leaf(words, name: str, layer, dm: tuple):
    key = jax.random.fold_in(
        jax.random.fold_in(_base_key(words), LAYER_LEAVES.index(name)), layer)
    return _sample(key, _layer_shape(name, dm), _kind(name))


def _nest(flat: dict) -> dict:
    out: dict = {}
    for k, v in flat.items():
        node = out
        *path, leaf = k.split(".")
        for p in path:
            node = node.setdefault(p, {})
        node[leaf] = v
    return out


def _make(words, dm: tuple, dtype):
    n_layers = dm[0]
    layer = {}
    for name in LAYER_LEAVES:
        per = [_layer_leaf(words, name, i, dm).astype(dtype)
               for i in range(n_layers)]
        layer[name] = jnp.stack(per) if n_layers > 1 else per[0]
    return {
        "embed": _top_leaf(words, "embed", dm).astype(dtype),
        "final_norm": _top_leaf(words, "final_norm", dm).astype(dtype),
        "decoder": {"g0": {"l0": _nest(layer)}},
    }


_make_jit = jax.jit(_make, static_argnums=(1, 2))


def make_params(cfg: dict, seed: int, dtype=jnp.bfloat16):
    """The whole served tree, made on the default device in one jitted
    call."""
    return _make_jit(jnp.asarray(seed_words(seed)), dims(cfg), dtype)


def _layer(words, layer, dm: tuple, dtype):
    return {name: _layer_leaf(words, name, layer, dm).astype(dtype)
            .astype(jnp.float32) for name in LAYER_LEAVES}


def _top(words, dm: tuple, dtype):
    return {name: _top_leaf(words, name, dm).astype(dtype).astype(jnp.float32)
            for name in TOP_LEAVES}


_layer_jit = jax.jit(_layer, static_argnums=(2, 3))
_top_jit = jax.jit(_top, static_argnums=(1, 2))


def layer_weights(cfg: dict, seed: int, layer: int, dtype=jnp.bfloat16):
    """Layer ``layer``'s leaves in float32, as served values (rounded
    through ``dtype``), keyed by dotted name."""
    words = jnp.asarray(seed_words(seed))
    return _layer_jit(words, jnp.int32(layer), dims(cfg), dtype)


def top_weights(cfg: dict, seed: int, dtype=jnp.bfloat16):
    """The embedding table and the final norm, in float32 as served."""
    return _top_jit(jnp.asarray(seed_words(seed)), dims(cfg), dtype)


def abstract_tree(cfg: dict, dtype=jnp.bfloat16):
    """Shapes and types of :func:`make_params`' tree, without making it."""
    return jax.eval_shape(lambda w: _make(w, dims(cfg), dtype),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))
