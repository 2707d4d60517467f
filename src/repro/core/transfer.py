"""JAX ingress / KV-cache transfer — the paper's transport taxonomy expressed
as real collectives on the production mesh (DESIGN.md §2).

In disaggregated serving the prefill pod produces a KV cache that must land
in the decode pod's HBM. The three mechanisms:

  DIRECT_HBM (GDR analogue)   : one collective_permute across the "pod" axis
                                — NIC-to-HBM, zero staging copies.
  DIRECT_DMA (RDMA analogue)  : permute + an explicit staging round-trip
                                buffer copy on the destination (host-pinned
                                bounce modeled as an extra copy pair).
  HOST_STAGED (TCP analogue)  : permute of an int8-requantized payload via a
                                host-layout buffer: dst pays decode + two
                                copies (stack staging + H2D). Each SOURCE pod
                                quantizes with its own scale, and the scales
                                ppermute alongside the int8 payload. Integer
                                leaves (slot metadata, token ids) cross at
                                full width, unquantized.

The multi-pod dry-run lowers kv_transfer to prove the pod-axis collective
compiles; `transfer_bytes()` feeds the §Roofline collective term, and the
simulator's profile constants time the same byte counts. The disaggregated
serving tier (serving/disagg.py) runs the same collective per admission and
charges `TransportProfile.handoff_time` on the counted bytes.

Under per-pod stage placement the serving tier lays the pod-tiled payload
out sharded along the 'pod' axis — the live bytes committed to the
prefill slice, zeros on the decode slice — so the ppermute here is the
ONLY hop that crosses the two stages' compute boundary (see
serving/disagg.py and docs/architecture.md). `pod_tile`/`pod_take`
construct and unpack that [npods, ...] layout.
"""

from __future__ import annotations

import enum

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.transport import Transport


class TransferMode(enum.Enum):
    DIRECT_HBM = "direct_hbm"  # GDR
    DIRECT_DMA = "direct_dma"  # RDMA
    HOST_STAGED = "host_staged"  # TCP


# Inter-stage mechanism -> the transport whose calibrated constants time it.
MODE_TRANSPORT = {
    TransferMode.DIRECT_HBM: Transport.GDR,
    TransferMode.DIRECT_DMA: Transport.RDMA,
    TransferMode.HOST_STAGED: Transport.TCP,
}


def _quantizes(dtype) -> bool:
    """HOST_STAGED requantizes float payloads to int8; everything else
    (slot metadata, token ids) crosses at full width."""
    return jnp.issubdtype(jnp.dtype(dtype), jnp.floating)


def wire_itemsize(dtype, mode: TransferMode) -> int:
    """Bytes per element a leaf of ``dtype`` is actually permuted at."""
    if mode is TransferMode.HOST_STAGED and _quantizes(dtype):
        return 1  # int8 payload; the per-pod fp32 scale is counted separately
    return jnp.dtype(dtype).itemsize


def _pod_scales(x):
    """Per-SOURCE-pod int8 scales for a pod-tiled leaf [npods, ...].

    Each pod quantizes its own shard only — a scale taken over the globally
    tiled leaf would fold the destination pod's data into the quantization
    step and blow up the error whenever magnitudes differ across pods.
    """
    axes = tuple(range(1, x.ndim))
    return jnp.maximum(jnp.max(jnp.abs(x), axis=axes), 1e-6) / 127.0


def _permute_leaf(x, mesh, perm):
    """collective_permute along the 'pod' axis for one cache leaf."""

    def body(x_l):
        return jax.lax.ppermute(x_l, "pod", perm)

    spec = P(*(("pod",) + (None,) * (x.ndim - 1)))
    return jax.shard_map(body, mesh=mesh, in_specs=spec, out_specs=spec)(x)


def kv_transfer(caches, mesh, *, mode: TransferMode = TransferMode.DIRECT_HBM,
                perm=None):
    """Move a prefill-pod KV cache tree to the decode pod.

    caches: pytree whose leaves carry a leading pod-sharded dim (we tile the
    tree leaves with a [npods, ...] leading axis in the launcher — see
    :func:`pod_tile`). Integer leaves may ride along as per-request slot
    metadata; they cross unquantized under every mode. perm: [(src, dst)]
    pod pairs; default ring 0->1, 1->0.

    The wire cost of what this permutes is exactly
    :func:`payload_wire_bytes` of the untiled payload — the reconciliation
    invariant the serving tier's ``handoff_wire_bytes`` counter is tested
    against.
    """
    npods = mesh.shape["pod"]
    perm = perm or [(i, (i + 1) % npods) for i in range(npods)]

    if mode is TransferMode.DIRECT_HBM:
        return jax.tree.map(lambda x: _permute_leaf(x, mesh, perm), caches)

    if mode is TransferMode.DIRECT_DMA:
        # staging bounce on the destination: permute, then a copy through a
        # bounce buffer (optimization barrier keeps XLA from eliding it)
        def leaf(x):
            y = _permute_leaf(x, mesh, perm)
            bounce = jax.lax.optimization_barrier(y + 0)
            return jax.lax.optimization_barrier(bounce * 1)

        return jax.tree.map(leaf, caches)

    # HOST_STAGED: requantize to int8 (host-format payload) with one scale
    # per source pod, permute payload + scales, then dequantize + two
    # staging copies on the destination.
    def staged(x):
        if not _quantizes(x.dtype):
            return _permute_leaf(x, mesh, perm)
        scale = _pod_scales(x)  # [npods]
        bshape = scale.shape + (1,) * (x.ndim - 1)
        q = jnp.clip(jnp.round(x / scale.reshape(bshape)), -127, 127)
        qq = _permute_leaf(q.astype(jnp.int8), mesh, perm)
        ss = _permute_leaf(scale.astype(jnp.float32), mesh, perm)
        bounce = jax.lax.optimization_barrier(qq)  # stack staging + H2D
        return (bounce.astype(jnp.float32) * ss.reshape(bshape)).astype(x.dtype)

    return jax.tree.map(staged, caches)


def pod_tile(tree, npods: int, src: int):
    """Tile a payload for the pod axis: [npods, ...] leaves carrying the real
    payload in pod ``src``'s slot and zeros elsewhere."""

    def tile(x):
        return jnp.zeros((npods,) + x.shape, x.dtype).at[src].set(x)

    return jax.tree.map(tile, tree)


def pod_take(tree, pod: int):
    """Extract pod ``pod``'s slice from a pod-tiled tree."""
    return jax.tree.map(lambda x: x[pod], tree)


def transfer_bytes(caches, mode: TransferMode) -> int:
    """Wire bytes per pod for the §Roofline collective term.

    Counts the itemsize each leaf is ACTUALLY permuted at: HOST_STAGED moves
    float leaves as int8 plus a per-pod fp32 scale, but integer leaves
    (metadata, token ids) cross at full width under every mode.
    """
    total = 0
    for leaf in jax.tree.leaves(caches):
        n = leaf.size // leaf.shape[0] if leaf.shape else leaf.size
        total += n * wire_itemsize(leaf.dtype, mode)
        if mode is TransferMode.HOST_STAGED and _quantizes(leaf.dtype):
            total += 4  # the ppermuted per-pod fp32 scale
    return total


def payload_wire_bytes(tree, mode: TransferMode) -> int:
    """``transfer_bytes`` for an UNTILED payload: the bytes one pod puts on
    the wire when ``tree`` is pod-tiled and permuted."""
    total = 0
    for leaf in jax.tree.leaves(tree):
        total += leaf.size * wire_itemsize(leaf.dtype, mode)
        if mode is TransferMode.HOST_STAGED and _quantizes(leaf.dtype):
            total += 4
    return total
