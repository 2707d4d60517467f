"""Plain float32 forward of the dense decoder, one layer at a time.

This is the yardstick of the correctness check. It imports nothing of
the program and takes nothing the program made: it rebuilds each layer's
weights from the seed (``weights.layer_weights``), runs every sequence
through that layer in float32 at the highest matmul precision, and drops
the weights before the next layer, so it never holds the whole model.

The block is the repo's dense block, which departs from the published
StarCoder2 and Granite-code blocks in the same ways for both (see the
configuration files): pre-RMSNorm, rotary positions with the two halves
of each head rotated together, grouped-query attention with causal
masking and a 1/sqrt(head_dim) scale, a SwiGLU feed-forward, a final
RMSNorm and an output head tied to the embedding table.

``precision="fp8"`` is the control: the same forward with both operands
of every projection, feed-forward and head matmul rounded to float8
(e4m3, weights scaled per output column and activations per token to
the format's range), the precision below the served bfloat16 that a
later change could be tempted to serve in.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W

HIGHEST = jax.lax.Precision.HIGHEST


def _f8(x, axis):
    """x rounded to float8 e4m3 after scaling its maximum along ``axis``
    to the format's largest finite value."""
    top = float(jnp.finfo(jnp.float8_e4m3fn).max)
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b, precision: str):
    """a [..., k] @ b [k, n] in float32, or from float8 operands for the
    control."""
    if precision == "fp8":
        a, b = _f8(a, -1), _f8(b, 0)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [S, H, hd] at positions 0..S-1."""
    s, _, hd = x.shape
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _row(x, w, dm, theta, eps, precision):
    """One sequence [S, d] through one layer."""
    _, _, h, hk, hd, _, _ = dm
    s = x.shape[0]
    y = _rmsnorm(x, w["ln1"], eps)
    q = _rope(_mm(y, w["attn.wq"], precision).reshape(s, h, hd), theta)
    k = _rope(_mm(y, w["attn.wk"], precision).reshape(s, hk, hd), theta)
    v = _mm(y, w["attn.wv"], precision).reshape(s, hk, hd)
    g = h // hk
    q = q.reshape(s, hk, g, hd)
    sc = jnp.einsum("qkgd,tkd->kgqt", q, k, precision=HIGHEST) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(causal, sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("kgqt,tkd->qkgd", p, v, precision=HIGHEST).reshape(s, h * hd)
    x = x + _mm(o, w["attn.wo"], precision)
    y = _rmsnorm(x, w["ln2"], eps)
    ff = jax.nn.silu(_mm(y, w["ffn.w_gate"], precision)) * _mm(
        y, w["ffn.w_up"], precision)
    return x + _mm(ff, w["ffn.w_down"], precision)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _layer(x, w, dm, theta, eps, precision):
    return jax.lax.map(lambda r: _row(r, w, dm, theta, eps, precision), x)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _head(x, pos, norm, table, eps, precision):
    """Logits [N, P, vocab] at positions ``pos`` [N, P] of x [N, S, d]."""
    h = jnp.take_along_axis(x, pos[:, :, None], axis=1)
    h = _rmsnorm(h, norm, eps)
    return _mm(h, table.T, precision)


def logits(cfg: dict, seed: int, seqs: list, positions: list,
           precisions=("f32",), dtype=jnp.bfloat16) -> dict:
    """Reference logits of each sequence at each of its positions.

    ``seqs`` are token arrays; ``positions[i]`` the indices of ``seqs[i]``
    whose next-token logits are wanted. Returns, per precision, a list of
    float32 arrays [len(positions[i]), vocab]. Sequences are right-padded
    to one length; causal masking keeps the pad out of every position read.
    The weights are the served ones, rounded through ``dtype``.
    """
    dm = W.dims(cfg)
    vocab = cfg["vocab_size"]
    s_max = max(len(s) for s in seqs)
    s_pad = -(-s_max // 128) * 128
    toks = np.zeros((len(seqs), s_pad), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    p_max = max(len(p) for p in positions)
    pos = np.zeros((len(seqs), p_max), np.int32)
    for i, p in enumerate(positions):
        pos[i, :len(p)] = p
    top = W.top_weights(cfg, seed, dtype)
    x0 = jnp.take(top["embed"], jnp.asarray(toks), axis=0)
    xs = {p: x0 for p in precisions}
    del x0
    theta, eps = float(cfg["rope_theta"]), float(cfg["norm_eps"])
    for layer in range(cfg["n_layers"]):
        w = W.layer_weights(cfg, seed, layer, dtype)
        for p in precisions:
            xs[p] = _layer(xs[p], w, dm, theta, eps, p)
        del w
    out = {}
    for p in precisions:
        lg = np.asarray(_head(xs[p], jnp.asarray(pos), top["final_norm"],
                              top["embed"], eps, p))[..., :vocab]
        out[p] = [lg[i, :len(q)] for i, q in enumerate(positions)]
    return out
