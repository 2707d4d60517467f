"""Disaggregated prefill->decode serving tier: the paper's multi-stage
pipeline study on the REAL JAX serving path.

A :class:`DisaggregatedEngine` runs admission+prefill as one stage and the
decode slot pool as another, and hands each admitted request's KV cache
across the mesh "pod" axis via ``core.transfer.kv_transfer``. The hop
mechanism is selectable per deployment and maps onto the paper's taxonomy:

  DIRECT_HBM  (GDR)  : collective permute straight into decode-pod HBM.
  DIRECT_DMA  (RDMA) : permute + one pinned-host bounce copy.
  HOST_STAGED (TCP)  : int8-requantized payload (per-source-pod scales),
                       two staging copies, CPU on the data path.

**Per-pod compute placement** (:class:`PodPlacement`, on by default):
prefill params and the prefill/slice jits are committed to the PREFILL
pod slice, the decode pool's params and entire device state to the DECODE
slice (``sharding.partition.place_on_slice``), so each stage's jitted
compute provably executes on its own devices — jit placement follows its
committed arguments, and every stage output reports its slice as the
device set. The handoff collective is then the ONLY cross-slice hop: the
pod-tiled payload is laid out with the live bytes on the prefill slice
(``P('pod')`` over the full mesh), the ``ppermute`` crosses a genuine
compute boundary, and the landed prefix is committed to the decode slice
before the regrow/splice. ``placement=False`` restores the pre-placement
behavior (both stages on the default device sharding).

The collective moves ONLY the valid KV prefix: the artifact's occupied
rows and their max true prompt length (both rounded up to powers of two,
the prefix floored at ``handoff_block`` — bounding jit shapes like the
prefill buckets) are sliced out of the max_batch x max_seq pool tree
before tiling (``kvcache.slice_cache``, ring-dim aware), and the landed
prefix is grown back to the pool's ring width on the DECODE side — after
the wire — so the splice's OOB-drop scatter is unchanged. The three byte
counters reconcile exactly: ``handoff_wire_bytes`` is
``payload_wire_bytes`` of the sliced payload the collective actually
permutes, and ``handoff_request_bytes`` (per-request true-prefix bytes)
is <= wire bytes by only the pow2/block rounding.

**Warmup** (``warmup=True``): engine construction pre-traces the whole
pow2 shape grid — every prefill bucket, and every (rows, prefix-blocks)
handoff extent through the slice/tile/collective/land jits, plus the
splice and decode step — so a warmed engine charges no XLA compile inside
any timed serving stage (compile-count-asserted in tests and the
benchmark's warmed smoke).

Every handoff carries per-request slot metadata (true lengths, first
tokens, slot indices, budgets) alongside the cache leaves, so the decode
pool splices a FOREIGN artifact through the same entry point a local
prefill uses. The handoff cost lands in the request's 'transfer' stage and
its TTFT: measured (``block_until_ready`` wall) on real multi-pod
hardware, or charged from the calibrated ``TransportProfile.handoff_time``
model on host-device runs — where the collective's CPU wall says nothing
about NIC mechanisms — with the non-representative measured wall swapped
out of the latency stamps.

On a multi-device backend the collective genuinely crosses the pod axis
(CI runs it on 8 forced host devices); on one device the pod axis
degenerates to an identity permute and both slices collapse onto the same
device, so the full tier — placement, tiling, quantization, metadata
round-trip, splice — still executes in tier-1 tests.

See docs/architecture.md for the end-to-end pipeline and the mapping of
every hop onto the paper's GDR/RDMA/TCP mechanisms.
"""

from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import trace
from repro.core.transfer import (
    MODE_TRANSPORT,
    TransferMode,
    _quantizes,
    kv_transfer,
    payload_wire_bytes,
    pod_take,
    pod_tile,
    wire_itemsize,
)
from repro.core.transport import Transport
from repro.models import kvcache as kvc
from repro.serving.engine import PrefillArtifact, ServingEngine, _next_pow2
from repro.sharding.partition import place_on_slice, pod_slice_mesh

# per-row slot metadata riding the handoff: lengths/next_token/slot/max_new
_META_BYTES = 16
# paged handoffs additionally carry cached_lens (the reused-prefix split)
_META_BYTES_PAGED = 20

# tools/reprolint RL005 contract (see serving/engine.py): jits listed
# here are pre-traced by warm() over the pow2 bucket x handoff-extent
# grid, so none compiles inside a timed stage on the bucketed path.
# (Exact-shape extents still compile lazily — ROADMAP carry-over.)
WARM_PRETRACE_TABLE = frozenset({
    "_slice_jit",          # prefill-side prefix slice, per extent
    "_land_jit",           # decode-side regrow, per extent
    "_land_paged_jit",     # paged twin
    "_store_scatter_jit",  # prefix-store block scatter
    "_xfer_jit",           # per-mechanism (prep, move) pair
    "coll_jit",            # placement collective inside _xfer
})


def make_pod_mesh(npods: Optional[int] = None):
    """('pod',)-axis mesh over the first ``npods`` devices (default 2; the
    CPU backend clamps to the 1-pod degenerate mesh). Thin re-export of
    ``launch.mesh.make_serving_pod_mesh``."""
    from repro.launch.mesh import make_serving_pod_mesh

    return make_serving_pod_mesh(npods)


@dataclasses.dataclass(frozen=True)
class PodPlacement:
    """Which pod-axis slices the two serving stages' compute lives on.

    ``prefill_pods`` / ``decode_pods`` are index tuples into the mesh's
    "pod" axis; each stage's params (and, for decode, the whole pool
    state) are replicated onto its slice, so the stage's jits compile for
    exactly those devices. The handoff collective permutes from
    ``prefill_pods[0]`` to ``decode_pods[0]``. Slices may overlap — the
    1-pod degenerate mesh collapses both onto one device (``disjoint``
    False), which is what lets the tier run on a single test CPU; a real
    two-pool deployment uses disjoint slices.
    """

    mesh: object
    prefill_pods: tuple
    decode_pods: tuple

    def __post_init__(self):
        object.__setattr__(self, "prefill_pods", tuple(self.prefill_pods))
        object.__setattr__(self, "decode_pods", tuple(self.decode_pods))
        # pod_slice_mesh validates indices; build each slice's mesh once
        object.__setattr__(
            self, "_prefill_mesh", pod_slice_mesh(self.mesh, self.prefill_pods)
        )
        object.__setattr__(
            self, "_decode_mesh", pod_slice_mesh(self.mesh, self.decode_pods)
        )

    @classmethod
    def from_mesh(cls, mesh, prefill_pod: int = 0,
                  decode_pod: Optional[int] = None) -> "PodPlacement":
        """Single-pod-per-stage placement: prefill on ``prefill_pod``,
        decode on ``decode_pod`` (default: the last pod)."""
        npods = mesh.shape["pod"]
        decode_pod = (npods - 1) if decode_pod is None else decode_pod
        return cls(mesh, (prefill_pod,), (decode_pod,))

    @property
    def disjoint(self) -> bool:
        """True when the stages share no pod — a genuine two-pool split."""
        return not set(self.prefill_pods) & set(self.decode_pods)

    def prefill_sharding(self, spec: P = P()) -> NamedSharding:
        """Sharding scoped to the prefill slice (replicated by default)."""
        return NamedSharding(self._prefill_mesh, spec)

    def decode_sharding(self, spec: P = P()) -> NamedSharding:
        """Sharding scoped to the decode slice (replicated by default)."""
        return NamedSharding(self._decode_mesh, spec)

    def prefill_devices(self) -> tuple:
        return tuple(self._prefill_mesh.devices.flat)

    def decode_devices(self) -> tuple:
        return tuple(self._decode_mesh.devices.flat)


class DisaggregatedEngine(ServingEngine):
    """ServingEngine whose prefill output crosses a pod boundary before it
    reaches the decode slot pool.

    placement: True (default) derives a :class:`PodPlacement` from
    ``prefill_pod``/``decode_pod`` and commits each stage's params and
    compute to its own pod slice; pass an explicit PodPlacement for
    multi-pod slices, or False for the pre-placement behavior (both
    stages on the default device sharding, the collective still crossing
    the pod axis).

    charge: 'measured' bills the handoff's block_until_ready wall,
    'modeled' bills ``profile.handoff_time`` on the request's wire bytes,
    'auto' (default) picks measured on accelerator backends and modeled on
    host-device (CPU) runs.

    handoff_block: floor granularity of the moved KV prefix. The prefix
    rounds up to a power of two (floored at this block, clamped to
    max_seq) and the row count rounds up to a power of two likewise, so
    the slice/collective/regrow jits compile O(log max_batch * log
    max_seq) shapes per mechanism — matching the pow2 prefill buckets —
    instead of one shape per distinct admission extent. Coarser blocks
    cut recompiles further at the cost of more dead ring slots on the
    wire.

    warmup: pre-trace the full bucket + handoff extent grid at
    construction (see :meth:`ServingEngine.warm`), so the serving path
    never compiles.
    """

    def __init__(self, model, params, *,
                 transfer_mode: TransferMode = TransferMode.DIRECT_HBM,
                 mesh=None, prefill_pod: int = 0,
                 decode_pod: Optional[int] = None,
                 placement=True, charge: str = "auto",
                 handoff_block: int = 16, warmup: bool = False, **kw):
        if kw.get("legacy"):
            raise ValueError(
                "disaggregated tier requires the fast path (legacy=True "
                "keeps prefill and decode fused in one synchronous loop)"
            )
        if charge not in ("auto", "measured", "modeled"):
            raise ValueError(f"charge must be auto|measured|modeled: {charge}")
        super().__init__(model, params, **kw)  # base never warms: placement
        self.mesh = mesh if mesh is not None else make_pod_mesh()
        self.npods = self.mesh.shape["pod"]
        self.transfer_mode = transfer_mode
        self.hop = MODE_TRANSPORT[transfer_mode]
        self.prefill_pod = prefill_pod
        self.decode_pod = (self.npods - 1) if decode_pod is None else decode_pod
        self.charge = charge
        if handoff_block < 1:
            raise ValueError(f"handoff_block must be >= 1: {handoff_block}")
        self.handoff_block = handoff_block
        self.handoffs = 0
        self.handoff_wire_bytes = 0  # bytes the collective actually moved
        self.handoff_request_bytes = 0  # useful bytes (true KV prefixes)
        # paged reconciliation oracle: expected wire bytes from the HOST-
        # SIDE admission plan alone (rows x suffix bucket x per-token wire
        # bytes + metadata) — never reads the device payload, and must
        # equal handoff_wire_bytes exactly at every prefix hit rate
        self.handoff_payload_bytes = 0
        self.handoff_wall_s = 0.0
        self._xfer_jit: dict = {}
        self._xfer_warm: set = set()  # (mode, rows, prefix) extents warmed
        # dead filler shards for the placed tiling: LRU, capped at one
        # pool-tree's worth of bytes so the extent grid can't pin a
        # multiple of the pool in never-read zeros
        self._zero_shards: OrderedDict = OrderedDict()
        self._zero_bytes = 0
        self._zero_budget = sum(
            leaf.nbytes for leaf in jax.tree.leaves(
                self.pool.blocks if self.paged else self.pool.caches
            )
        )

        # --- per-pod compute placement -------------------------------- #
        self.placement: Optional[PodPlacement] = None
        if placement:
            if placement is True:
                placement = PodPlacement.from_mesh(
                    self.mesh, prefill_pod=self.prefill_pod,
                    decode_pod=self.decode_pod,
                )
            if placement.mesh != self.mesh:
                raise ValueError("placement.mesh differs from engine mesh")
            if int(np.asarray(self.mesh.devices).size) != self.npods:
                # the placed tiling enumerates one device per pod slot
                raise ValueError(
                    "per-pod placement requires a mesh whose only "
                    f"non-trivial axis is 'pod' (got {dict(self.mesh.shape)}"
                    "); pass placement=False for multi-axis meshes"
                )
            self.placement = placement
            # the collective's endpoints follow the placement
            self.prefill_pod = placement.prefill_pods[0]
            self.decode_pod = placement.decode_pods[0]
            # each stage serves from params committed to ITS slice; every
            # jit consuming them then executes on that slice's devices.
            # Equal slices (the 1-pod degenerate mesh) share ONE committed
            # replica — two device_put copies on the same device would
            # triple resident weight memory for nothing.
            self.prefill_params = place_on_slice(
                params, self.mesh, placement.prefill_pods
            )
            self.decode_params = (
                self.prefill_params
                if placement.decode_pods == placement.prefill_pods
                else place_on_slice(params, self.mesh, placement.decode_pods)
            )
            self.pool.place(placement.decode_sharding())

        # prefill-side prefix slice and decode-side regrow around the wire;
        # both retrace per (extent, payload-shape) like the collective itself
        self._slice_jit = jax.jit(kvc.slice_cache, static_argnums=(1, 2))
        self._land_jit = jax.jit(self._land_impl)
        if self.paged:
            self._land_paged_jit = jax.jit(self._land_paged_impl)
            # dense-shaped template (abstract, never materialized) for the
            # byte accountants that sized payloads off the ring pool tree
            self._dense_template = jax.eval_shape(
                lambda: self.model.init_cache(self.max_batch, self.max_seq)
            )
        # prefill-side prefix store (paged reuse): suffix prefills gather
        # their prior HERE, on the prefill pod — reused prefix KV never
        # re-crosses the pod boundary. Its blocks pair 1:1 (by index page)
        # with decode-pool blocks in the radix payloads.
        if self.prefix_reuse:
            self._store_pool = kvc.PagedKVPool(
                self.pool.allocator.num_blocks, self.page
            )
            blocks = kvc.init_paged(
                self.model.cache_specs(self.max_batch, self.max_seq),
                self._store_pool.num_blocks, self.page,
            )
            if self.placement is not None:
                blocks = jax.device_put(
                    blocks, self.placement.prefill_sharding()
                )
            self._prefix_store_blocks = blocks
            self._store_scatter_jit = jax.jit(
                kvc.scatter_pages, donate_argnums=(0,)
            )

        self.warmup = warmup
        if warmup:
            self.warm_s = self.warm()  # buckets + extent grid + splice/step

    # ------------------------------------------------------------------ #
    def _measured(self) -> bool:
        if self.charge == "auto":
            return jax.default_backend() != "cpu"
        return self.charge == "measured"

    def _xfer(self, mode: TransferMode):
        """(prep, move) pair for one mechanism.

        ``prep`` assembles the wire payload (host-side, charged to no wire
        stage); ``move`` is the hop itself — the part the measured wall
        times. Without placement, prep is the identity and move is one jit
        doing tile -> permute -> take (compiles once per payload
        shape-set). With placement, prep lays the [npods, ...] pod-sharded
        payload out from per-device shards — live bytes on the prefill
        slice, cached dead zeros elsewhere (:meth:`_tile_committed`) — and
        move runs the collective and commits the landed payload to the
        decode slice, so the wire wall covers exactly the cross-slice
        hop."""
        if mode not in self._xfer_jit:
            perm = ([(self.prefill_pod, self.decode_pod)]
                    if self.npods > 1 else [(0, 0)])

            if self.placement is None:
                def impl(payload, *, _mode=mode, _perm=perm):
                    tiled = pod_tile(payload, self.npods, self.prefill_pod)
                    moved = kv_transfer(tiled, self.mesh, mode=_mode,
                                        perm=_perm)
                    return pod_take(moved, self.decode_pod)

                self._xfer_jit[mode] = ((lambda p: p), jax.jit(impl))
            else:
                decode_sh = self.placement.decode_sharding()

                def collective(tiled, *, _mode=mode, _perm=perm):
                    moved = kv_transfer(tiled, self.mesh, mode=_mode,
                                        perm=_perm)
                    return pod_take(moved, self.decode_pod)

                coll_jit = jax.jit(collective)

                def move(tiled):
                    return jax.device_put(coll_jit(tiled), decode_sh)

                self._xfer_jit[mode] = (self._tile_committed, move)
        return self._xfer_jit[mode]

    def _tile_committed(self, payload):
        """Pod-tile ``payload`` without moving a byte across the slice
        boundary: each leaf becomes a [npods, ...] array sharded P('pod')
        over the full mesh, assembled from single-device shards — the live
        payload on the prefill pod, per-(shape, dtype, device)-cached zero
        buffers on every other pod (``ppermute`` under a [(src, dst)] perm
        never delivers those shards anywhere, so their values are dead).
        The subsequent collective is therefore the ONLY cross-slice hop."""
        wire_sh = NamedSharding(self.mesh, P("pod"))
        devs = list(np.asarray(self.mesh.devices).flat)

        def tile(x):
            shape = (1,) + tuple(x.shape)
            shards = [
                jax.device_put(x[None], d) if i == self.prefill_pod
                else self._zero_shard(shape, x.dtype, d)
                for i, d in enumerate(devs)
            ]
            return jax.make_array_from_single_device_arrays(
                (self.npods,) + tuple(x.shape), wire_sh, shards
            )

        return jax.tree.map(tile, payload)

    def _zero_shard(self, shape, dtype, device):
        """Dead filler shard for the non-source pods of the tiled wire
        layout, created host->device once per (shape, dtype, device) and
        LRU-cached under a one-pool-tree byte budget: hot extents reuse
        resident buffers (first touch happens in the warm pass or the
        out-of-band extent warm), cold extents evicted past the budget
        pay a compile-free zero re-upload."""
        key = (shape, str(dtype), device)
        buf = self._zero_shards.get(key)
        if buf is None:
            buf = jax.device_put(np.zeros(shape, dtype), device)
            self._zero_shards[key] = buf
            self._zero_bytes += buf.nbytes
            while (self._zero_bytes > self._zero_budget
                   and len(self._zero_shards) > 1):
                # callers hold refs to shards mid-tile, so eviction here
                # never invalidates an in-flight handoff
                _, old = self._zero_shards.popitem(last=False)
                self._zero_bytes -= old.nbytes
        else:
            self._zero_shards.move_to_end(key)
        return buf

    def request_handoff_bytes(self, true_len: int) -> int:
        """Wire bytes one request's KV prefix + slot metadata put on the
        inter-stage hop under this deployment's mechanism (paged: the
        ``true_len`` tokens that actually ride — the caller passes the
        UNCACHED suffix length there, and the metadata row is wider)."""
        if self.paged:
            return _META_BYTES_PAGED + kvc.request_cache_nbytes(
                self._dense_template, true_len, itemsize=self._wire_isz,
            )
        return _META_BYTES + kvc.request_cache_nbytes(
            self.pool.caches, true_len, itemsize=self._wire_isz,
        )

    def padded_tree_wire_bytes(self) -> int:
        """Wire bytes ONE pre-prefix-slicing handoff moved: the full
        max_batch x max_seq pool cache tree plus full-width slot metadata.
        The benchmark/test baseline the prefix-only collective is held
        against."""
        meta = {k: jnp.zeros((self.max_batch,), jnp.int32)
                for k in ("lengths", "next_tokens", "slot_idx", "max_new")}
        dense = (self._dense_template if self.paged else self.pool.caches)
        return payload_wire_bytes(
            {"caches": dense, "meta": meta}, self.transfer_mode
        )

    def _wire_isz(self, leaf) -> int:
        return wire_itemsize(leaf.dtype, self.transfer_mode)

    def _land_impl(self, caches, meta):
        """Decode-side regrow, AFTER the wire: pad the landed prefix back to
        the pool's fixed admission width (rows) and ring width (seq), with
        padding rows carrying OOB slot indices so the pool's existing
        drop-OOB splice scatter sees one fixed shape and ignores them."""
        caches = kvc.grow_cache(
            kvc.pad_cache_rows(caches, self.max_batch), self.max_seq
        )
        n = meta["lengths"].shape[0]
        width = (0, self.max_batch - n)

        def pad(x, fill=0):
            return jnp.pad(x, width, constant_values=fill)

        meta = {
            "lengths": pad(meta["lengths"]),
            "next_tokens": pad(meta["next_tokens"]),
            "slot_idx": pad(meta["slot_idx"], self.max_batch),  # OOB
            "max_new": pad(meta["max_new"]),
        }
        return caches, meta

    def handoff_prefix(self, true_len: int) -> int:
        """Ring slots the collective moves for a ``true_len``-token row:
        next power of two, floored at ``handoff_block``, clamped to the
        pool's ring width."""
        p = max(_next_pow2(max(true_len, 1)), self.handoff_block)
        return min(p, self.max_seq)

    def _prefix_extent(self, art: PrefillArtifact) -> tuple[int, int]:
        """(rows, prefix) extent the wire carries: both round up to powers
        of two — bounding jit shapes like the prefill buckets do — with
        rows clamped to the artifact's actual width (the extra rows are the
        artifact's own OOB-slot dummies, dropped by the far-side splice)."""
        n = min(_next_pow2(max(art.n_rows, 1)), len(art.slot_idx))
        return n, self.handoff_prefix(art.prefix_len)

    def handoff_extent_grid(self) -> list:
        """Every (rows, prefix) wire extent a bucketed admission can
        produce: pow2 row counts clamped to max_batch x pow2 prefixes
        floored at handoff_block and clamped to max_seq — the grid
        :meth:`warm` pre-traces."""
        rows = sorted({min(_next_pow2(r), self.max_batch)
                       for r in range(1, self.max_batch + 1)})
        prefixes, L = set(), 1
        while True:
            prefixes.add(self.handoff_prefix(L))
            if L >= self.max_seq:
                break
            L *= 2
        return [(r, p) for r in rows for p in sorted(prefixes)]

    def _wire_payload(self, art: PrefillArtifact, n: int, prefix: int):
        """The exact pytree the collective permutes for one admission: the
        [rows, prefix_blocks] cache slice plus those rows' slot metadata.
        Shared by :meth:`_handoff` and the warmup pass so both hit the
        same jit cache entries."""
        return {
            "caches": self._slice_jit(art.caches, n, prefix),
            "meta": {
                "lengths": art.lengths[:n],
                "next_tokens": art.next_tokens[:n],
                "slot_idx": jnp.asarray(art.slot_idx[:n]),
                "max_new": art.max_new[:n],
            },
        }

    def _wire_payload_paged(self, art: PrefillArtifact, n: int):
        """The paged handoff's wire pytree: the bucket-width SUFFIX cache
        sliced to ``n`` rows (seq already at the bucket — reused prefix KV
        is not aboard) plus those rows' slot metadata, cached_lens
        included. dest_blocks stay on the host: they index the decode
        pool's block ids, pure control plane."""
        return {
            "caches": self._slice_jit(art.caches, n, art.bucket),
            "meta": {
                "lengths": art.lengths[:n],
                "next_tokens": art.next_tokens[:n],
                "slot_idx": jnp.asarray(art.slot_idx[:n]),
                "max_new": art.max_new[:n],
                "cached_lens": jnp.asarray(art.cached_lens[:n]),
            },
        }

    def _land_paged_impl(self, caches, meta):
        """Decode-side landing for a paged handoff: pad ROWS back to the
        admission width (padding rows carry OOB slots and dest block 0, so
        the paged splice drops them) — the seq dim stays at the suffix
        bucket; the splice scatters pages, never a max_seq ring."""
        caches = kvc.pad_cache_rows(caches, self.max_batch)
        n = meta["lengths"].shape[0]
        width = (0, self.max_batch - n)

        def pad(x, fill=0):
            return jnp.pad(x, width, constant_values=fill)

        meta = {
            "lengths": pad(meta["lengths"]),
            "next_tokens": pad(meta["next_tokens"]),
            "slot_idx": pad(meta["slot_idx"], self.max_batch),  # OOB
            "max_new": pad(meta["max_new"]),
            "cached_lens": pad(meta["cached_lens"]),
        }
        return caches, meta

    def _paged_geometry_bytes(self, n: int, L: int) -> int:
        """Expected wire bytes of an [n rows x L suffix tokens] paged
        payload, from the admission plan alone: per-token KV wire bytes
        (dense template, so it never touches the device payload) times the
        refcount-trimmed extent, plus per-row metadata and the HOST_STAGED
        per-leaf quantization scales. The reconciliation oracle
        ``handoff_wire_bytes`` must match exactly."""
        total = n * _META_BYTES_PAGED
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                self._dense_template)[0]:
            per_tok = leaf.size // (self.max_batch * self.max_seq)
            total += n * L * per_tok * wire_itemsize(
                leaf.dtype, self.transfer_mode
            )
            if (self.transfer_mode is TransferMode.HOST_STAGED
                    and _quantizes(leaf.dtype)):
                total += 4
        return total

    def _new_chunk_prior(self):
        """Chunked prefill's per-request prior lives on the PREFILL pod
        slice: every chunk's suffix prefill and splice execute there, and
        only the final chunk's artifact crosses the pod boundary (through
        the same :meth:`_handoff` every admission takes)."""
        prior = super()._new_chunk_prior()
        if self.placement is not None:
            prior = jax.device_put(prior, self.placement.prefill_sharding())
        return prior

    # ------------------------------------------------------------------ #
    # prefill-side prefix store hooks (paged reuse)
    # ------------------------------------------------------------------ #
    def _store_alloc(self):
        return self._store_pool if self.prefix_reuse else self.pool.allocator

    def _store_deref(self, ids: list):
        self._store_alloc().deref(ids)

    def _prior_blocks(self):
        return self._prefix_store_blocks

    def _store_alloc_blocks(self, n: int) -> list:
        """Allocate prefill-store blocks, evicting cold index pages under
        pressure (each eviction releases BOTH payload sides)."""
        while True:
            got = self._store_pool.alloc(n)
            if got is not None:
                return got
            payload = self.prefix_index.evict_lru()
            if payload is None:
                raise RuntimeError(
                    "prefill-side prefix store exhausted with no evictable "
                    "index pages"
                )
            self._evict_index_page(payload)

    def _store_prepare(self, jobs: list, caches, L: int):
        """Scatter each job's fully-in-prompt suffix pages into the
        prefill-side store BEFORE the handoff, so future suffix prefills
        gather their prior on the prefill pod without re-crossing the
        wire. Returns job -> freshly allocated store block ids (rc=1 —
        the index's reference if the page gets created, orphan-deref'd
        otherwise in :meth:`_index_insert`)."""
        if not self.prefix_reuse:
            return None
        page = self.page
        dest = np.zeros((self.max_batch, L // page), np.int32)
        ctx: dict = {}
        for j, job in enumerate(jobs):
            n_ins = len(job.req.prompt_tokens) // page
            cpages = job.cached // page
            store_ids = self._store_alloc_blocks(max(n_ins - cpages, 0))
            ctx[id(job)] = store_ids
            for k, p in enumerate(store_ids):
                dest[j, k] = p
        self._prefix_store_blocks = self._store_scatter_jit(
            self._prefix_store_blocks, caches, jnp.asarray(dest)
        )
        return ctx

    def _index_insert(self, jobs: list, store_ctx):
        """Store-aware radix insert: page ``i``'s payload pairs the
        prefill-store block (gathered by future suffix prefills) with the
        decode-pool block (aliased into future rows' page tables).
        Created pages keep the store block's alloc-time rc=1 as the
        index's prefill-side reference and take one decode-side ref;
        orphans — the page already indexed by a same-batch sibling, or
        the row skipped after a mid-admission eviction — deref once and
        free."""
        if not self.prefix_reuse:
            return
        for job in jobs:
            store_ids = store_ctx.get(id(job), []) if store_ctx else []
            toks = job.req.prompt_tokens
            n_ins = len(toks) // self.page
            cpages = job.cached // self.page
            if n_ins == 0:
                continue
            depth = len(self.prefix_index.match(toks, n_ins, peek=True))
            if depth < cpages:
                self._store_pool.deref(store_ids)
                continue
            payloads = (
                [(job.p_ids[i], job.d_ids[i]) for i in range(cpages)]
                + [(store_ids[i - cpages], job.pt_row[i])
                   for i in range(cpages, n_ins)]
            )
            created = self.prefix_index.insert(toks, payloads, n_ins)
            created_p = set()
            for (p, d) in created:
                created_p.add(p)
                self.pool.allocator.ref([d])
            self._store_pool.deref(
                [p for p in store_ids if p not in created_p]
            )

    # ------------------------------------------------------------------ #
    def _warm_admit(self, art: Optional[PrefillArtifact]):
        """Pre-trace the handoff chain — slice, tile, collective, land —
        for EVERY (rows, prefix) extent in the grid, then splice one
        landed all-dummy artifact so the decode-side splice compiles on
        decode-slice-committed inputs. Called from :meth:`warm` with an
        artifact produced by the real prefill jit, so shapes, dtypes, and
        committed shardings all match the serving path exactly.

        Paged engines warm per suffix BUCKET (the seam is called once per
        bucket from the base warm loop): every pow2 row extent at that
        bucket width, plus the prefill-store scatter when reuse is on."""
        if art is None:  # exact-shape path: ragged per-request shapes
            return
        if self.paged:
            self._warm_admit_paged(art)
            return
        prep, move = self._xfer(self.transfer_mode)
        landed_art = None
        for n, prefix in self.handoff_extent_grid():
            key = (self.transfer_mode, n, prefix)
            if key in self._xfer_warm:
                continue
            landed = move(prep(self._wire_payload(art, n, prefix)))
            caches, meta = self._land_jit(landed["caches"], landed["meta"])
            jax.block_until_ready(caches)
            self._xfer_warm.add(key)
            landed_art = (caches, meta)
        if landed_art is not None:
            caches, meta = landed_art
            self.pool.splice(dataclasses.replace(
                art, caches=caches, slot_idx=np.asarray(meta["slot_idx"]),
                lengths=meta["lengths"], next_tokens=meta["next_tokens"],
                max_new=meta["max_new"],
            ))  # every row OOB: compiles the splice, writes nothing

    def _warm_admit_paged(self, art: PrefillArtifact):
        """Per-bucket paged extent warm: (rows_pow2 x this bucket) through
        slice/tile/collective/land, one all-dummy splice (per-bucket splice
        shapes), and the prefill-store scatter (dest 0 = sentinel drop)."""
        prep, move = self._xfer(self.transfer_mode)
        L = art.bucket
        rows = sorted({min(_next_pow2(r), self.max_batch)
                       for r in range(1, self.max_batch + 1)})
        landed_art = None
        for n in rows:
            key = (self.transfer_mode, "paged", n, L)
            if key in self._xfer_warm:
                continue
            landed = move(prep(self._wire_payload_paged(art, n)))
            caches, meta = self._land_paged_jit(
                landed["caches"], landed["meta"]
            )
            jax.block_until_ready(caches)
            self._xfer_warm.add(key)
            landed_art = (caches, meta)
        if landed_art is not None:
            caches, meta = landed_art
            self.pool.splice(dataclasses.replace(
                art, caches=caches, slot_idx=np.asarray(meta["slot_idx"]),
                lengths=meta["lengths"], next_tokens=meta["next_tokens"],
                max_new=meta["max_new"],
            ))  # every row OOB + dest 0: compiles, writes nothing
        if self.prefix_reuse:
            self._prefix_store_blocks = self._store_scatter_jit(
                self._prefix_store_blocks, art.caches,
                jnp.zeros((self.max_batch, L // self.page), jnp.int32),
            )

    # ------------------------------------------------------------------ #
    def _trace_handoff(self, wire_bytes: int, requests: int,
                       measured: bool) -> trace.span:
        """``engine.handoff``: the collective plus its landing, the wall
        the 'transfer' stage charges."""
        return self._trace_span(
            "engine.handoff", mechanism=self.transfer_mode.name,
            wire_bytes=wire_bytes, requests=requests,
            charge="measured" if measured else "modeled",
        )

    def _handoff_paged(self, art: PrefillArtifact):  # reprolint: disable=RL001 the block IS the measurement: 'transfer' wall must cover wire completion
        """Paged pod-boundary handoff: move the bucket-width SUFFIX cache
        only. Reused prefix KV already lives in decode-pool blocks (it
        crossed the wire exactly once, when first computed), so the wire
        carries ``rows_pow2 x suffix_bucket`` tokens — the refcount-
        trimmed payload — and ``handoff_wire_bytes`` drops with the hit
        rate while reconciling exactly against the host-side geometry
        oracle ``handoff_payload_bytes``."""
        n = min(_next_pow2(max(art.n_rows, 1)), len(art.slot_idx))
        L = art.bucket
        payload = self._wire_payload_paged(art, n)
        prep, move = self._xfer(self.transfer_mode)
        measured = self._measured()
        key = (self.transfer_mode, "paged", n, L)
        warm_s = 0.0
        if key not in self._xfer_warm:
            tw = time.perf_counter()
            jax.block_until_ready(move(prep(payload)))
            self._xfer_warm.add(key)
            warm_s = time.perf_counter() - tw
        tiled = prep(payload)
        jax.block_until_ready(tiled)
        wire_now = payload_wire_bytes(payload, self.transfer_mode)
        with self._trace_handoff(wire_now, len(art.reqs), measured):
            t0 = time.perf_counter()
            landed = move(tiled)
            jax.block_until_ready(landed)
            wall = time.perf_counter() - t0

        self.handoffs += 1
        self.handoff_wall_s += wall
        self.handoff_wire_bytes += wire_now
        self.handoff_payload_bytes += self._paged_geometry_bytes(n, L)
        share = wall / max(len(art.reqs), 1)
        # per-request useful bytes = each row's UNCACHED suffix (its reused
        # prefix rode an earlier handoff; charging it again would double-
        # count the very bytes the prefix cache saved)
        total_lens = np.asarray(landed["meta"]["lengths"])
        req_bytes = [
            _META_BYTES_PAGED + kvc.request_cache_nbytes(
                art.caches,
                int(total_lens[j]) - int(art.cached_lens[j]),
                itemsize=self._wire_isz,
            )
            for j in range(len(art.reqs))
        ]
        tot_bytes = max(sum(req_bytes), 1)
        for req, nbytes in zip(art.reqs, req_bytes):
            rec = self._records[req.request_id]
            self.handoff_request_bytes += nbytes
            wire_share = wire_now * nbytes / tot_bytes
            rec.transfer_wall_s += wall
            rec.add(
                "transfer",
                share if measured
                else self.profile.handoff_time(self.hop, wire_share),
            )
            if self.hop is Transport.TCP:
                rec.cpu_s += wire_share * self.profile.tcp_cpu_per_byte
        caches, meta = self._land_paged_jit(landed["caches"], landed["meta"])
        # dest_blocks/cached_lens pass through untouched: host control
        # plane, aligned with the artifact's (unchanged) row order
        art = dataclasses.replace(
            art, caches=caches,
            slot_idx=np.asarray(meta["slot_idx"]), lengths=meta["lengths"],
            next_tokens=meta["next_tokens"], max_new=meta["max_new"],
        )
        return art, wall + warm_s

    # ------------------------------------------------------------------ #
    def _handoff(self, art: PrefillArtifact):  # reprolint: disable=RL001 the block IS the measurement: 'transfer' wall must cover wire completion
        """Move the prefill artifact's VALID KV PREFIX across the pod
        boundary and charge each riding request for its share.

        The prefill jit grows caches to max_seq for the single-node splice;
        here that padding is sliced back off to [rows, prefix_blocks] (plus
        the rows' slot metadata) before the collective, so the wire carries
        only live cache bytes. The landed prefix regrows to the ring width
        on the decode side, after the wire."""
        if self.paged:
            return self._handoff_paged(art)
        n, prefix = self._prefix_extent(art)
        payload = self._wire_payload(art, n, prefix)
        prep, move = self._xfer(self.transfer_mode)
        measured = self._measured()
        key = (self.transfer_mode, n, prefix)
        warm_s = 0.0
        if key not in self._xfer_warm:
            # ONCE per pow2 extent (not per handoff): compile plus one
            # throwaway out-of-band collective — jit's cache isn't
            # populated by AOT lowering — outside the timed window, and
            # hand the warm wall back to the caller so it stays out of
            # 'preprocess' too. No charged stage ever bills XLA
            # compilation, and the wall counters stay steady-state on
            # measured and modeled backends alike. warmup=True engines
            # pre-trace the whole grid at construction and never take
            # this branch.
            tw = time.perf_counter()
            jax.block_until_ready(move(prep(payload)))
            self._xfer_warm.add(key)
            warm_s = time.perf_counter() - tw
        # payload assembly (placed tiling, zero-shard residency) is prep,
        # not wire: block on it OUTSIDE the timed window so the measured
        # wall — and the per-request 'transfer' charge on accelerator
        # backends — covers exactly the collective + decode-slice landing
        tiled = prep(payload)
        jax.block_until_ready(tiled)
        wire_now = payload_wire_bytes(payload, self.transfer_mode)
        with self._trace_handoff(wire_now, len(art.reqs), measured):
            t0 = time.perf_counter()
            landed = move(tiled)
            jax.block_until_ready(landed)
            wall = time.perf_counter() - t0

        self.handoffs += 1
        self.handoff_wall_s += wall
        self.handoff_wire_bytes += wire_now
        share = wall / max(len(art.reqs), 1)
        # per-request TRUE cache lengths ride the (already materialized)
        # landed metadata — for feature-carrying requests the cache extends
        # past the prompt, so len(prompt_tokens) would undercount
        true_lens = np.asarray(landed["meta"]["lengths"])
        req_bytes = [
            _META_BYTES + kvc.request_cache_nbytes(
                art.caches, int(true_lens[j]), itemsize=self._wire_isz,
            )
            for j in range(len(art.reqs))
        ]
        tot_bytes = max(sum(req_bytes), 1)
        for req, nbytes in zip(art.reqs, req_bytes):
            rec = self._records[req.request_id]
            self.handoff_request_bytes += nbytes
            # each request's prefix-proportional share of the bytes the
            # collective ACTUALLY moved (block rounding + co-rider dummy
            # rows included): modeled hop and TCP CPU both charge on this,
            # so the per-request stages sum to the real wire cost
            wire_share = wire_now * nbytes / tot_bytes
            # every co-admitted request waits the FULL collective wall
            # before its first token; the charged stage splits it (measured
            # attribution, like preprocess/inference) or models the hop on
            # this request's share of the moved bytes
            rec.transfer_wall_s += wall
            rec.add(
                "transfer",
                share if measured
                else self.profile.handoff_time(self.hop, wire_share),
            )
            if self.hop is Transport.TCP:
                # the host stack keeps the CPU on the handoff data path,
                # symmetric with the gateway's ingress/egress accounting;
                # sum(cpu_s) == wire * tcp_cpu_per_byte exactly
                rec.cpu_s += wire_share * self.profile.tcp_cpu_per_byte
        caches, meta = self._land_jit(landed["caches"], landed["meta"])
        # n_rows stays == len(reqs): the pow2-rounded wire extent is a
        # transport detail, not part of the artifact's occupancy contract
        art = dataclasses.replace(
            art, caches=caches,
            slot_idx=np.asarray(meta["slot_idx"]), lengths=meta["lengths"],
            next_tokens=meta["next_tokens"], max_new=meta["max_new"],
        )
        # warm_s rides along so the caller excludes it from 'preprocess';
        # the charged transfer wall above is the steady-state `wall` only
        return art, wall + warm_s

    def counters(self) -> dict:
        out = super().counters()
        out.update(
            handoffs=self.handoffs,
            handoff_wire_bytes=self.handoff_wire_bytes,
            handoff_request_bytes=self.handoff_request_bytes,
            handoff_payload_bytes=self.handoff_payload_bytes,
        )
        return out

    def _ttft_adjust(self, rec) -> float:
        # measured charge: the handoff wall is already inside the latency
        # stamps — adjust by 0. modeled charge (host-device runs): swap the
        # FULL non-representative collective wall the request waited for
        # out of the stamps and fold the profile-modeled hop in.
        if self._measured():
            return 0.0
        return rec.stage_s.get("transfer", 0.0) - rec.transfer_wall_s
