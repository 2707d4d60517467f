"""Parameter schema: declare each weight once (shape + logical axes + init).

A schema is a nested dict whose leaves are :class:`ParamSpec`. From one schema
we derive (a) initialized params, (b) ``ShapeDtypeStruct`` stand-ins for the
dry-run, and (c) ``PartitionSpec`` trees for pjit — guaranteeing the three
always agree.

Logical axis names used across the models:
  batch, seq, embed, heads, kv_heads, head_dim, ffn, vocab, experts,
  expert_ffn, ssm_heads, ssm_in, state, conv, lora, rope, layers (stacking)
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: tuple
    axes: tuple  # logical axis name (or None) per dim
    init: str = "normal"  # normal | zeros | ones | small_normal
    scale: Optional[float] = None  # fan-in scale override

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _init_leaf(key, spec: ParamSpec, dtype) -> jax.Array:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    fan_in = spec.shape[0] if len(spec.shape) > 1 else max(spec.shape[0], 1)
    scale = spec.scale if spec.scale is not None else 1.0 / math.sqrt(fan_in)
    if spec.init == "small_normal":
        scale = 0.02
    # the barrier keeps XLA from folding ``scale`` into the sampler's own
    # constants under jit, which would change low bits against eager init
    x = jax.lax.optimization_barrier(
        jax.random.normal(key, spec.shape, jnp.float32)
    )
    return (x * scale).astype(dtype)


def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


# One compiled program per leaf: the scale and the cast fuse, so a leaf
# holds one f32 temporary (the sample) besides its result. Eager, the
# scaled product was a second f32 copy: 4.2 GiB more per FFN leaf at full
# starcoder2-3b width. Bits match the eager init (tests/test_models_smoke.py).
_init_leaf_jit = jax.jit(_init_leaf, static_argnums=(1, 2))


def init_params(rng, schema, dtype=jnp.bfloat16):
    leaves, treedef = jax.tree.flatten(schema, is_leaf=is_spec)
    keys = jax.random.split(rng, len(leaves))
    return jax.tree.unflatten(
        treedef, [_init_leaf_jit(k, s, dtype) for k, s in zip(keys, leaves)]
    )


def abstract_params(schema, dtype=jnp.bfloat16):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype), schema, is_leaf=is_spec
    )


def partition_specs(schema, rules: dict):
    """Map logical axes -> mesh axes via ``rules`` (name -> mesh axis or None)."""

    def one(s: ParamSpec):
        return P(*[rules.get(a) if a is not None else None for a in s.axes])

    return jax.tree.map(one, schema, is_leaf=is_spec)


def stack(schema, n: int):
    """Prepend a 'layers' stacking dim of size ``n`` to every leaf."""
    return jax.tree.map(
        lambda s: ParamSpec((n,) + s.shape, ("layers",) + s.axes, s.init, s.scale),
        schema,
        is_leaf=is_spec,
    )


def param_bytes(schema, bytes_per_el: int = 2) -> int:
    total = 0
    for leaf in jax.tree.leaves(schema, is_leaf=is_spec):
        total += math.prod(leaf.shape) * bytes_per_el
    return total
