"""jit'd public wrappers around the Pallas kernels.

Each op accepts model-layout tensors and handles padding/transposes. The
kernels target the TPU and compile with Mosaic there; on the CPU backend
(the tests) they run in interpret mode, which executes the kernel body for
validation. Any other backend raises instead of interpreting in silence.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import preprocess as _pp
from repro.kernels import rmsnorm as _rn
from repro.kernels import ssd_scan as _ssd


def _interpret_default() -> bool:
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels compile for 'tpu' and interpret on 'cpu'; the "
        f"{backend!r} backend is neither"
    )


def _pad_to(x, axis, mult):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# --------------------------------------------------------------------------- #
@functools.partial(
    jax.jit, static_argnames=("causal", "window", "block_q", "block_k", "interpret")
)
def flash_attention(q, k, v, *, causal=True, window=0, scale=None,
                    block_q=128, block_k=128, interpret=None,
                    segment_ids=None):
    """q: [B,Sq,H,hd]; k,v: [B,Skv,Hkv,hd] -> [B,Sq,H,hd].

    segment_ids: optional [B, Sq] int32 packed-prefill ids (requires
    Sq == Skv; pad tokens -1) — forbids cross-segment attention.
    """
    interpret = _interpret_default() if interpret is None else interpret
    B, Sq, H, hd = q.shape
    qt = _pad_to(jnp.moveaxis(q, 1, 2), 2, block_q)
    kt = _pad_to(jnp.moveaxis(k, 1, 2), 2, block_k)
    vt = _pad_to(jnp.moveaxis(v, 1, 2), 2, block_k)
    q_seg = k_seg = None
    if segment_ids is not None:
        seg = segment_ids.astype(jnp.int32)
        q_seg = _pad_to(seg, 1, block_q)
        k_seg = _pad_to(seg, 1, block_k)
    # real (unpadded) lengths are baked into the kernel's masks
    o = _fa.flash_attention_bhsd(
        qt, kt, vt, causal=causal, window=window, scale=scale,
        block_q=min(block_q, qt.shape[2]), block_k=min(block_k, kt.shape[2]),
        interpret=interpret, sq_real=Sq, skv_real=k.shape[1],
        q_segment_ids=q_seg, k_segment_ids=k_seg,
    )
    return jnp.moveaxis(o[:, :, :Sq], 2, 1)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret", "length_aware"))
def decode_attention(q, k, v, lengths, *, scale=None, block_k=512,
                     interpret=None, length_aware=True):
    """q: [B,1,H,hd]; k,v: [B,W,Hkv,hd]; lengths: [B] -> [B,1,H,hd].

    length_aware: short sequences in a ragged batch only stream their valid
    KV prefix from HBM (dead tail blocks re-reference a resident block).
    """
    interpret = _interpret_default() if interpret is None else interpret
    B, _, H, hd = q.shape
    W, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    qg = q.reshape(B, 1, Hkv, G, hd)[:, 0]  # [B,Hkv,G,hd]
    kt = _pad_to(jnp.moveaxis(k, 1, 2), 2, block_k)  # [B,Hkv,W,hd]
    vt = _pad_to(jnp.moveaxis(v, 1, 2), 2, block_k)
    o = _dec.decode_attention_bhgd(
        qg, kt, vt, lengths.astype(jnp.int32),
        scale=scale, block_k=min(block_k, kt.shape[2]), interpret=interpret,
        w_real=W, length_aware=length_aware,
    )
    return o.reshape(B, 1, H, hd)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, k_blocks, v_blocks, page_table, lengths, *,
                           scale=None, interpret=None):
    """q: [B,1,H,hd]; k_blocks, v_blocks: [N, page, Hkv, hd] block pool;
    page_table: [B, n_pages] int32; lengths: [B] -> [B,1,H,hd].

    Paged variant of :func:`decode_attention`: the kernel's KV index_map
    dereferences the page table, streaming each row's blocks from the
    shared pool (block size = page, length-clamped like the ring kernel).
    """
    interpret = _interpret_default() if interpret is None else interpret
    B, _, H, hd = q.shape
    Hkv = k_blocks.shape[2]
    G = H // Hkv
    qg = q.reshape(B, 1, Hkv, G, hd)[:, 0]  # [B,Hkv,G,hd]
    kt = jnp.moveaxis(k_blocks, 1, 2)  # [N,Hkv,page,hd]
    vt = jnp.moveaxis(v_blocks, 1, 2)
    o = _dec.paged_decode_attention_bhgd(
        qg, kt, vt, page_table.astype(jnp.int32), lengths.astype(jnp.int32),
        scale=scale, interpret=interpret,
        w_real=page_table.shape[1] * k_blocks.shape[1],
    )
    return o.reshape(B, 1, H, hd)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, B, C, *, chunk=128, interpret=None):
    """Model layout: x [b,S,nh,hd]; dt [b,S,nh]; A [nh]; B,C [b,S,1,ds].

    Returns (y [b,S,nh,hd], final_state [b,nh,hd,ds] fp32).
    """
    interpret = _interpret_default() if interpret is None else interpret
    b, S, nh, hd = x.shape
    ds = B.shape[-1]
    chunk = min(chunk, S)
    Sp = S + ((-S) % chunk)
    xt = _pad_to(jnp.moveaxis(x, 1, 2), 2, chunk)  # [b,nh,S,hd]
    dtt = _pad_to(jnp.moveaxis(dt, 1, 2), 2, chunk)  # [b,nh,S]
    Bb = jnp.broadcast_to(B, (b, S, nh, ds))
    Cc = jnp.broadcast_to(C, (b, S, nh, ds))
    Bt = _pad_to(jnp.moveaxis(Bb, 1, 2), 2, chunk)
    Ct = _pad_to(jnp.moveaxis(Cc, 1, 2), 2, chunk)
    y, state = _ssd.ssd_scan_bhsd(
        xt, dtt, A.astype(jnp.float32), Bt, Ct, chunk=chunk, interpret=interpret
    )
    return jnp.moveaxis(y[:, :, :S], 2, 1), state


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(x, w, *, eps=1e-5, block_rows=256, interpret=None):
    """x: [..., D]; w: [D]."""
    interpret = _interpret_default() if interpret is None else interpret
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    x2p = _pad_to(x2, 0, block_rows) if x2.shape[0] > block_rows else x2
    o = _rn.rmsnorm_2d(
        x2p, w, eps=eps, block_rows=min(block_rows, x2p.shape[0]),
        interpret=interpret,
    )
    return o[: x2.shape[0]].reshape(shape)


@functools.partial(jax.jit, static_argnames=("out_dtype", "block_rows", "interpret"))
def preprocess(x_u8, mean, std, *, out_dtype=jnp.bfloat16, block_rows=512,
               interpret=None):
    """x_u8: [..., D] uint8; mean/std: [D]."""
    interpret = _interpret_default() if interpret is None else interpret
    shape = x_u8.shape
    x2 = x_u8.reshape(-1, shape[-1])
    x2p = _pad_to(x2, 0, block_rows) if x2.shape[0] > block_rows else x2
    o = _pp.preprocess_2d(
        x2p, mean, std, out_dtype=out_dtype,
        block_rows=min(block_rows, x2p.shape[0]), interpret=interpret,
    )
    return o[: x2.shape[0]].reshape(shape[:-1] + (shape[-1],))
