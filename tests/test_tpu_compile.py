"""Ahead-of-time compiles for a described TPU v5e (2x2), with no chip.

The Pallas kernels and the full-width decode step are compiled by the
TPU compiler at starcoder2-3b widths, and the widest packed prefill of
the granite-completion deployment at granite-34b-10l widths: what Mosaic
would refuse (a block not aligned to the tiling, too much VMEM) or a
program that does not fit one chip's 16 GiB fails here, before any chip
time is spent. Nothing runs, so these tests say nothing about results or
times.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and every
test worker imports this file.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.models import Model
from repro.serving import ServingEngine

GIB = 1 << 30
CHIP_HBM = 16 * GIB

# starcoder2-3b attention widths (configs/starcoder2_3b.py)
H, HKV, HD, D = 24, 2, 128, 3072


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _kernel_cases(c):
    """(jitted op, abstract args, static kwargs) per kernel entry point."""
    B, S, W, page = 8, 2048, 2048, 16
    n_blocks = B * W // page
    return {
        "flash_attention": (
            ops.flash_attention,
            (c((1, S, H, HD)), c((1, S, HKV, HD)), c((1, S, HKV, HD))),
            {"causal": True},
        ),
        "decode_attention": (
            ops.decode_attention,
            (c((B, 1, H, HD)), c((B, W, HKV, HD)), c((B, W, HKV, HD)),
             c((B,), jnp.int32)),
            {"block_k": 512},
        ),
        "paged_decode_attention": (
            ops.paged_decode_attention,
            (c((B, 1, H, HD)), c((n_blocks, page, HKV, HD)),
             c((n_blocks, page, HKV, HD)), c((B, W // page), jnp.int32),
             c((B,), jnp.int32)),
            {},
        ),
        "rmsnorm": (ops.rmsnorm, (c((B * 512, D)), c((D,))), {}),
    }


@pytest.mark.parametrize(
    "name",
    ["flash_attention", "decode_attention", "paged_decode_attention",
     "rmsnorm"],
)
def test_kernel_compiles_with_mosaic(one_chip, name):
    fn, args, kw = _kernel_cases(lambda *s: _spec(one_chip, *s))[name]
    # interpret=False: this process's backend is the CPU, where the ops
    # would otherwise pick interpret mode
    compiled = fn.lower(*args, interpret=False, **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_full_width_decode_step_fits_one_chip(one_chip):
    model = Model(get_config("starcoder2-3b"))
    place = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
    params = jax.tree.map(place, model.param_specs())
    B, max_seq = 8, 2048
    caches = jax.tree.map(place, model.cache_specs(B, max_seq))
    tokens = _spec(one_chip, (B, 1), jnp.int32)
    lengths = _spec(one_chip, (B,), jnp.int32)
    compiled = jax.jit(model.decode_step).lower(
        params, caches, tokens, lengths
    ).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes > 7 * GIB  # the whole model is there
    assert used < CHIP_HBM, f"{used / GIB:.2f} GiB does not fit one chip"


def test_widest_packed_prefill_fits_one_chip(one_chip):
    """granite-34b at its published widths, 10 layers, served with
    max_batch 4 and max_seq 2112: the packed prefill at the engine's cap
    (a 4096-token row, unpacked to the pool's rows in the same program)
    fits one chip beside the stage's weights and the decode pool."""
    model = Model(dataclasses.replace(get_config("granite-34b"), n_layers=10))
    eng = ServingEngine(model, None, max_batch=4, max_seq=2112,
                        min_bucket=128)
    T = eng.packed_cap()
    assert eng.packed and T == 4096
    place = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
    params = jax.tree.map(place, model.param_specs())
    row = _spec(one_chip, (1, T), jnp.int32)
    per_slot = _spec(one_chip, (eng.max_batch,), jnp.int32)
    compiled = jax.jit(eng._prefill_packed_impl).lower(
        params, row, row, row, per_slot, per_slot, place(eng.prefill_key)
    ).compile()
    mem = compiled.memory_analysis()
    pool = sum(x.nbytes for x in jax.tree.leaves(eng.pool.caches))
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes + pool)
    assert mem.argument_size_in_bytes > 11 * 10**9  # the stage's weights
    assert used < CHIP_HBM, f"{used / GIB:.2f} GiB does not fit one chip"
