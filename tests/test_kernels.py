"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def arr(rng, *s, dtype=jnp.float32):
    return jnp.asarray(rng.normal(size=s), dtype)


TOLS = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Sq,Skv,H,Hkv,hd",
    [
        (1, 128, 128, 4, 4, 64),  # MHA, block-aligned
        (2, 200, 200, 8, 2, 64),  # GQA, ragged
        (1, 64, 256, 4, 1, 32),  # MQA, cross-length
        (2, 33, 129, 6, 3, 128),  # odd sizes
    ],
)
@pytest.mark.parametrize("causal,window", [(True, 0), (False, 0), (True, 64)])
def test_flash_attention_sweep(rng, dtype, B, Sq, Skv, H, Hkv, hd, causal, window):
    if causal and Sq != Skv:
        pytest.skip("causal requires square")
    q = arr(rng, B, Sq, H, hd, dtype=dtype)
    k = arr(rng, B, Skv, Hkv, hd, dtype=dtype)
    v = arr(rng, B, Skv, Hkv, hd, dtype=dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(
        out.astype(jnp.float32), want.astype(jnp.float32), atol=TOLS[dtype], rtol=1e-2
    )


# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,W,H,Hkv,hd,block_k",
    [
        (1, 128, 4, 4, 64, 64),
        (2, 300, 8, 2, 64, 128),
        (3, 64, 6, 1, 128, 512),
        (2, 1024, 16, 8, 32, 256),
    ],
)
def test_decode_attention_sweep(rng, dtype, B, W, H, Hkv, hd, block_k):
    q = arr(rng, B, 1, H, hd, dtype=dtype)
    k = arr(rng, B, W, Hkv, hd, dtype=dtype)
    v = arr(rng, B, W, Hkv, hd, dtype=dtype)
    lens = jnp.asarray(rng.integers(1, W + 1, (B,)), jnp.int32)
    out = ops.decode_attention(q, k, v, lens, block_k=block_k)
    want = ref.decode_attention_ref(q, k, v, lens)
    np.testing.assert_allclose(
        out.astype(jnp.float32), want.astype(jnp.float32), atol=TOLS[dtype], rtol=1e-2
    )


# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,S,nh,hd,ds,chunk",
    [
        (1, 64, 2, 32, 16, 16),
        (2, 96, 4, 32, 16, 32),
        (1, 128, 1, 64, 64, 64),
        (2, 100, 3, 16, 8, 32),  # ragged seq (padded inside ops)
    ],
)
def test_ssd_scan_sweep(rng, dtype, b, S, nh, hd, ds, chunk):
    x = arr(rng, b, S, nh, hd, dtype=dtype)
    dt = jnp.abs(arr(rng, b, S, nh)) * 0.1 + 0.01
    A = -jnp.abs(arr(rng, nh)) - 0.1
    B = arr(rng, b, S, 1, ds)
    C = arr(rng, b, S, 1, ds)
    y, st = ops.ssd_scan(x, dt.astype(dtype), A, B.astype(dtype), C.astype(dtype), chunk=chunk)
    yw, stw = ref.ssd_scan_ref(x, dt.astype(dtype), A, B.astype(dtype), C.astype(dtype))
    atol = 3e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(
        y.astype(jnp.float32), yw.astype(jnp.float32), atol=atol, rtol=2e-2
    )
    np.testing.assert_allclose(st, stw, atol=atol, rtol=2e-2)


# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("N,D", [(16, 128), (300, 512), (1, 64), (257, 384)])
def test_rmsnorm_sweep(rng, dtype, N, D):
    x = arr(rng, N, D, dtype=dtype)
    w = arr(rng, D, dtype=dtype)
    out = ops.rmsnorm(x, w)
    want = ref.rmsnorm_ref(x, w)
    np.testing.assert_allclose(
        out.astype(jnp.float32), want.astype(jnp.float32), atol=TOLS[dtype], rtol=1e-2
    )


@pytest.mark.parametrize("N,D", [(8, 128), (100, 512), (1000, 64)])
def test_preprocess_sweep(rng, N, D):
    x = jnp.asarray(rng.integers(0, 256, (N, D)), jnp.uint8)
    mean = jnp.abs(arr(rng, D)) * 0.4 + 0.1
    std = jnp.abs(arr(rng, D)) * 0.2 + 0.3
    out = ops.preprocess(x, mean, std)
    want = ref.preprocess_ref(x, mean, std)
    np.testing.assert_allclose(
        out.astype(jnp.float32), want.astype(jnp.float32), atol=1e-2, rtol=1e-2
    )


# --------------------------------------------------------------------------- #
def _odd_op_calls(rng):
    """One call per public op, at shapes no other test traces (a cached
    trace would skip the backend check)."""
    q = arr(rng, 1, 3, 2, 8)
    kv = arr(rng, 1, 3, 1, 8)
    lens = jnp.asarray([3], jnp.int32)
    return {
        "flash_attention": lambda: ops.flash_attention(q, kv, kv),
        "decode_attention": lambda: ops.decode_attention(
            arr(rng, 1, 1, 2, 8), kv, kv, lens),
        "paged_decode_attention": lambda: ops.paged_decode_attention(
            arr(rng, 1, 1, 2, 8), arr(rng, 2, 3, 1, 8), arr(rng, 2, 3, 1, 8),
            jnp.asarray([[1]], jnp.int32), lens),
        "ssd_scan": lambda: ops.ssd_scan(
            arr(rng, 1, 3, 1, 8), arr(rng, 1, 3, 1), arr(rng, 1),
            arr(rng, 1, 3, 1, 4), arr(rng, 1, 3, 1, 4)),
        "rmsnorm": lambda: ops.rmsnorm(arr(rng, 3, 24), arr(rng, 24)),
        "preprocess": lambda: ops.preprocess(
            jnp.zeros((3, 24), jnp.uint8), arr(rng, 24), arr(rng, 24)),
    }


@pytest.mark.parametrize(
    "name",
    ["flash_attention", "decode_attention", "paged_decode_attention",
     "ssd_scan", "rmsnorm", "preprocess"],
)
def test_ops_refuse_backend_without_kernels(rng, monkeypatch, name):
    """Interpret mode is for the CPU backend only: on a backend that is
    neither 'cpu' nor 'tpu' the ops raise instead of interpreting."""
    call = _odd_op_calls(rng)[name]
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu' backend"):
        call()
