"""Real-compute serving engine: continuous batching over a slot-based KV pool.

This is the executable twin of ``core/simulator.py``: the same four-stage
pipeline (request -> [copy] -> preprocess/prefill -> decode -> response), but
inference is REAL JAX compute (reduced-config models on CPU; the same code
drives full configs on TPU). Transport and copy-engine stage times come from
the calibrated TransportProfile so a request's end-to-end record composes
measured compute with modeled wires, exactly like the paper's Table I.

The engine is two separable stages:

* **Admission + prefill** (this class): the request queue, priority pick,
  bucketed/exact prefill, and per-request records. Prefill produces a
  :class:`PrefillArtifact` — the max_seq-grown cache plus per-row slot
  metadata — which is everything a decode stage needs to take over a
  request.
* **Decode slot pool** (:class:`DecodePool`): slot occupancy, the ring KV
  pool, the per-slot device decode state, the jitted splice and decode
  step, and the async in-flight window. It knows nothing about transports
  or records, so a FOREIGN artifact — one produced on a different mesh pod
  and moved through ``core.transfer.kv_transfer`` — splices through the
  same entry point (see serving/disagg.py, which overrides the
  :meth:`ServingEngine._handoff` seam between the two stages).

Fast path (the serving hot loop, rebuilt for throughput):

* **Bucketed prefill** — prompts are right-padded to power-of-two length
  buckets and queued admissions sharing a bucket run as ONE padded prefill
  call (batch dim padded to the FIXED admission width max_batch — trading
  up to max_batch x prefill FLOPs on sparse admissions for exactly one
  compile per bucket; dummy rows scatter out-of-bounds and drop). Compile
  count is O(log max_seq) instead of O(distinct prompt lengths), and an
  admission burst is a single device dispatch.
* **Device-resident decode loop** — sampling (greedy argmax by default;
  temperature/top-k categorical with an in-jit threaded PRNG key when
  ``temperature > 0``), EOS detection, per-slot done flags, and length
  updates all live inside one jitted decode step that returns a
  device-side ``done`` mask. The host never syncs per token: up to
  ``inflight`` steps are dispatched ahead — capped adaptively at the live
  slots' outstanding token budget (``adaptive_window``), so the window
  stops paying overshoot steps past finishing requests — and each step's
  tokens+done arrive in one host transfer at harvest time. The KV pool is
  donated through the step, so steady-state decode holds a single cache
  buffer.
* **Fused admission splice** — growing a prefill cache to the pool window
  and scattering it into the free slots (plus lengths/tokens/flag updates)
  is one jitted, donated call instead of a per-leaf ``.at[].set`` chain.
* **Token-packed prefill** (``packed=True``) — instead of right-padding
  each prompt to its bucket, an admission concatenates every prompt into
  ONE ``[1, pow2(total_true_tokens)]`` sequence with per-token segment
  ids (cross-prompt attention masked in the kernel, positions
  segment-relative), so a ragged admission's prefill cost tracks the
  tokens it actually has. The packed cache unpacks per segment in-jit
  into the same bucketed-shaped artifact, and ``packed=False`` keeps the
  bucketed path as the measured A/B baseline.
* **Chunked prefill** (``prefill_chunk=C``) — prompts longer than C admit
  as fixed-width suffix-prefill chunks, ONE per engine iteration after
  the decode window top-up, so a long admission interleaves with live
  decodes instead of head-of-line blocking them for its full prefill
  wall (decode TPOT stays flat through a max_seq-token admission).

``legacy=True`` preserves the original synchronous loop (per-length jitted
prefill, ``block_until_ready`` + host argmax + per-slot Python bookkeeping
every token) as the measured A/B baseline for ``benchmarks/serving.py`` and
the drain-equivalence test.

Because every hot-loop shape is pow2-bounded, ``warmup=True`` can
pre-trace the whole grid at construction (:meth:`ServingEngine.warm`):
a warmed engine charges no XLA compile inside any timed serving stage.
The disaggregated tier extends the same warm pass over its handoff
extents and additionally commits each stage's params/compute to its own
mesh pod slice (see serving/disagg.py and docs/architecture.md).

Continuous batching: a fixed pool of ``max_batch`` slots; finished sequences
free their slot, queued requests join at the next step boundary; every decode
step runs the whole active batch through one jitted step.
"""

from __future__ import annotations

import contextlib
import dataclasses
import queue as queue_mod
import threading
import time
import traceback
from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import trace
from repro.core.obs import Registry
from repro.core.profiler import ProfileStore, RequestRecord
from repro.core.transport import PAPER_A2, Transport, TransportProfile
from repro.models import Model
from repro.models import kvcache as kvc
from repro.serving.prefix import RadixPrefixIndex
from repro.serving.request import Request, Response


# Every jax.jit created in serving/ must either appear here — meaning
# warm() pre-traces it at construction, so it never compiles inside a
# timed stage — or carry a `# reprolint: disable=RL005` with the reason
# it cannot be pre-traced. tools/reprolint RL005 checks the union of
# these tables across serving/ against every jit creation site.
WARM_PRETRACE_TABLE = frozenset({
    "_step_jit",            # DecodePool: warmed by warm()'s fill_one
    "_splice_jit",          # DecodePool: warmed via _warm_admit's splice
    "_prefill_bucket_jit",  # one compile per pow2 bucket in warm()
    "_prefill_paged_jit",   # paged twin, same bucket grid
    "_prefill_suffix_jit",  # warmed per bucket when prefix_reuse is on
    "_prefill_packed_jit",  # packed=True: one compile per pow2 packed width
    "_chunk_jit",           # prefill_chunk>0: ONE shape (fixed-width prior)
    "_chunk_pad_jit",       # chunk artifact row pad, one shape
})


def _next_pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


@dataclasses.dataclass
class _InFlight:
    """One dispatched-but-unharvested decode step."""

    tokens: jax.Array  # [B, 1] device
    done: jax.Array  # [B] device
    slots: tuple  # Request-or-None per slot, snapshotted at dispatch


@dataclasses.dataclass
class PrefillArtifact:
    """Everything a prefill stage must deliver to a decode slot pool.

    Row j of every per-row array belongs to ``reqs[j]``; padding rows carry
    slot index == max_batch, which is out of bounds for the splice scatter
    and therefore dropped. ``caches`` is already grown to the pool's ring
    width (max_seq), so the splice sees one fixed shape.

    ``n_rows``/``prefix_len`` record the artifact's VALID extent — the
    occupied leading rows and the max true cache length among them (prompt
    tokens, plus feature frames on the vlm/audio exact path) — so a
    pod-boundary handoff can move only the live KV prefix
    (``kvcache.slice_cache``) instead of the padded admission tree, and
    grow back to the pool shape on the far side.
    """

    caches: object  # cache tree, ring dim grown to max_seq
    slot_idx: np.ndarray  # [npad] int32 host-side (OOB => dummy row)
    lengths: jax.Array  # [npad] true prompt lengths
    next_tokens: jax.Array  # [npad] greedy first token per row
    max_new: jax.Array  # [npad] per-request token budget
    reqs: list  # the real requests (row-aligned prefix)
    slots: list  # pool slot per request
    n_rows: int = 0  # occupied leading rows (== len(reqs))
    prefix_len: int = 0  # max true cache length among occupied rows
    # paged-mode extras: ``caches`` then holds the SUFFIX cache at bucket
    # width (never grown to max_seq); the splice scatters its pages into
    # the block pool at ``dest_blocks`` (0 => dropped), and ``cached_lens``
    # records each row's reused prefix (its KV already lives in shared
    # blocks, so it never rides the artifact — or, disaggregated, the wire)
    dest_blocks: Optional[np.ndarray] = None  # [npad, bucket/page] int32
    cached_lens: Optional[np.ndarray] = None  # [npad] int32 reused prefix
    bucket: int = 0  # suffix bucket width (paged handoff extent)


@dataclasses.dataclass
class _PagedJob:
    """Per-request admission bookkeeping for a paged prefill group."""

    req: Request
    slot: int
    cached: int  # reused prefix tokens (page-aligned)
    p_ids: list  # prior-side blocks gathered for the suffix prefill
    d_ids: list  # shared decode-side blocks (the row's pt prefix)
    own: list  # freshly-allocated blocks (suffix + decode growth)
    pt_row: list  # d_ids + own = the row's page table


@dataclasses.dataclass
class _ChunkJob:
    """One in-progress chunked admission: its reserved slot, its
    fixed-width prior cache tree, and the tokens prefilled so far."""

    req: Request
    slot: int
    prior: object  # [.., 1, max_seq, ..] cache tree, donated per chunk
    done: int = 0  # prompt tokens already prefilled + spliced


class DecodePool:
    """Decode-side slot pool, separable from admission/prefill.

    Owns slot occupancy, the ring KV pool, the per-slot device decode state
    (tokens/lengths/gen/done/max_new), the jitted splice and decode step,
    and the async in-flight window. A local prefill stage and a remote pod
    handing a cache off through ``core.transfer`` splice through the same
    :meth:`splice` entry point. :meth:`place` commits the whole pool to a
    device slice (per-pod placement); :meth:`reset_state` re-zeros it
    after a construction-time warmup without dropping compiled jits.
    """

    def __init__(self, model: Model, *, max_batch: int, max_seq: int,
                 eos_token: Optional[int], inflight: int,
                 temperature: float = 0.0, top_k: int = 0,
                 sample_seed: int = 0, paged: bool = False,
                 page_size: int = 16, cache_blocks: Optional[int] = None):
        self.model = model
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.inflight = inflight
        self.slots: list[Optional[Request]] = [None] * max_batch
        self.eos_arr = jnp.int32(eos_token if eos_token is not None else -1)
        # paged mode: the ring pool becomes a block pool + per-slot page
        # tables (host-built, pushed before each dispatch). Block count =
        # sentinel + worst-case live rows + cache_blocks headroom the
        # prefix index can keep warm (default: another full pool's worth).
        self.paged = bool(paged)
        self.page = int(page_size)
        if paged:
            if max_seq % self.page:
                raise ValueError(
                    f"max_seq {max_seq} must be a multiple of page_size "
                    f"{page_size}"
                )
            self.pages_per_seq = max_seq // self.page
            need = max_batch * self.pages_per_seq
            extra = need if cache_blocks is None else int(cache_blocks)
            self.allocator = kvc.PagedKVPool(1 + need + extra, self.page)
        # device-side sampling: temperature 0 keeps the greedy argmax path
        # (the test baseline); temperature > 0 samples inside the jitted
        # step from top_k-filtered logits with a PRNG key threaded through
        # the pool state — no host round-trip per token.
        if temperature < 0.0:
            raise ValueError(f"temperature must be >= 0: {temperature}")
        if top_k < 0:
            raise ValueError(f"top_k must be >= 0: {top_k}")
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.sample_seed = int(sample_seed)
        self.window: deque[_InFlight] = deque()
        self._sharding = None  # optional committed placement (pod slice)
        self._init_state()
        if self.paged:
            self._step_jit = jax.jit(self._step_paged_impl, donate_argnums=(1,))
            self._splice_jit = jax.jit(self._splice_paged_impl,
                                       donate_argnums=(0,))
        else:
            self._step_jit = jax.jit(self._step_impl, donate_argnums=(1,))
            self._splice_jit = jax.jit(self._splice_impl, donate_argnums=(0,))

    # every device-state array the pool owns: _init_state (re)builds them
    # and place() commits them — keep the two in sync through this tuple
    _STATE_FIELDS = ("caches", "lengths", "tokens", "gen", "maxn", "done",
                     "eos_arr", "key")

    def _state_field_names(self) -> tuple:
        if self.paged:  # the block pool + page table replace the ring tree
            return tuple(f for f in self._STATE_FIELDS if f != "caches") + (
                "blocks", "page_table")
        return self._STATE_FIELDS

    def _init_state(self):
        """(Re)build the device-side slot state (the ``_STATE_FIELDS``
        arrays, minus the constant eos_arr): empty pool, all slots done.
        Re-placed onto the committed sharding when one is set."""
        if self.paged:
            self.caches = None
            self.blocks = kvc.init_paged(
                self.model.cache_specs(self.max_batch, self.max_seq),
                self.allocator.num_blocks, self.page,
            )
            self.pt_host = np.zeros((self.max_batch, self.pages_per_seq),
                                    np.int32)
            self.page_table = jnp.asarray(self.pt_host)
            self._pt_dirty = False
            self._slot_blocks: list[list] = [[] for _ in range(self.max_batch)]
            self.allocator.reset()
        else:
            self.caches = self.model.init_cache(self.max_batch, self.max_seq)
        self.lengths = jnp.zeros((self.max_batch,), jnp.int32)
        self.tokens = jnp.zeros((self.max_batch, 1), jnp.int32)
        self.gen = jnp.zeros((self.max_batch,), jnp.int32)
        self.maxn = jnp.zeros((self.max_batch,), jnp.int32)
        self.done = jnp.ones((self.max_batch,), bool)
        # raw uint32 key data (not a typed key array) so the whole state
        # tuple stays plain arrays for place()/device_put
        self.key = jax.random.PRNGKey(self.sample_seed)
        if self._sharding is not None:
            self.place(self._sharding)

    def place(self, sharding):
        """Commit the pool's entire device state (``_STATE_FIELDS``) to
        ``sharding`` (a pod slice in the disaggregated tier): every
        subsequent splice/step jit then compiles for — and provably
        executes on — exactly that slice's devices, since jit placement
        follows its committed arguments."""
        self._sharding = sharding
        for name in self._state_field_names():
            setattr(self, name, jax.device_put(getattr(self, name), sharding))

    def reset_state(self):
        """Re-zero the slot state (post-warmup): a pristine pool, with the
        compiled splice/step executables and the placement retained."""
        if any(s is not None for s in self.slots):
            raise RuntimeError("reset_state on an occupied pool")
        self.window.clear()
        self._init_state()

    # ------------------------------------------------------------------ #
    # jitted bodies
    # ------------------------------------------------------------------ #
    def _step_impl(self, params, caches, tokens, lengths, gen, maxn, done,
                   eos, key):
        """One whole-batch decode step, sampling and stop logic on device.

        Frozen (done/empty) slots keep their token and length so their ring
        slot stays put; their lane still flows through the batched compute
        (the output is discarded), which is what keeps the loop shape-stable.

        Sampling is greedy argmax at temperature 0 (the default and the
        token-identity baseline); otherwise one categorical draw per slot
        from the temperature-scaled, top_k-filtered logits, with the PRNG
        key split in-jit and threaded back through the state — the whole
        batch consumes one split per step, so the token stream is a pure
        function of (sample_seed, step index, slot).
        """
        active = ~done
        logits, caches, lengths2 = self.model.decode_step(
            params, caches, tokens, lengths
        )
        if self.temperature > 0.0:
            key, sub = jax.random.split(key)
        else:
            sub = key
        next_tok = self._sample(logits, sub)
        next_tok = jnp.where(active, next_tok, tokens[:, 0])
        gen = gen + active.astype(jnp.int32)
        done = done | (gen >= maxn) | (active & (next_tok == eos))
        lengths = jnp.where(active, lengths2, lengths)
        return next_tok[:, None], caches, lengths, gen, done, key

    def _sample(self, logits, key):
        """Next-token choice on device: argmax, or temperature/top-k
        categorical (``top_k == 0`` keeps the full vocabulary; ``top_k ==
        1`` degenerates to argmax exactly, temperature notwithstanding)."""
        if self.temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        lg = logits.astype(jnp.float32) / self.temperature
        if self.top_k > 0:
            kth = jax.lax.top_k(lg, self.top_k)[0][:, -1:]
            lg = jnp.where(lg < kth, -jnp.inf, lg)
        return jax.random.categorical(key, lg, axis=-1).astype(jnp.int32)

    def _step_paged_impl(self, params, blocks, page_table, tokens, lengths,
                         gen, maxn, done, eos, key):
        """Paged decode step: gather -> ring decode -> scatter one token.

        The per-row dense caches are materialized from the block pool
        through the page table, the UNCHANGED ``Model.decode_step`` runs on
        them (so the math — and at temperature 0 the token stream — is
        bitwise the ring path's: unallocated pages gather the zero
        sentinel, exactly what grow_cache pads), and only the ONE ring slot
        the step wrote is scattered back per row. Freed slots' page-table
        rows are zero, so their frozen-lane writes drop at the sentinel
        redirect. The TPU-optimal variant that skips the gather entirely is
        ``kernels.ops.paged_decode_attention`` (equivalence-tested); this
        reference path stays pure-jnp like the model's.
        """
        active = ~done
        dense = kvc.gather_pages(blocks, page_table)
        logits, dense, lengths2 = self.model.decode_step(
            params, dense, tokens, lengths
        )
        blocks = kvc.scatter_token(blocks, dense, lengths, page_table)
        if self.temperature > 0.0:
            key, sub = jax.random.split(key)
        else:
            sub = key
        next_tok = self._sample(logits, sub)
        next_tok = jnp.where(active, next_tok, tokens[:, 0])
        gen = gen + active.astype(jnp.int32)
        done = done | (gen >= maxn) | (active & (next_tok == eos))
        lengths = jnp.where(active, lengths2, lengths)
        return next_tok[:, None], blocks, lengths, gen, done, key

    def _splice_paged_impl(self, blocks, suffix, dest_blocks, slots,
                           true_lens, next_toks, maxn_new, lengths, tokens,
                           gen, done, maxn):
        """Paged admission: scatter the bucket-width suffix cache into the
        block pool page-wise, plus the same per-slot state updates as the
        ring splice. Dummy rows carry dest block 0 (the zero sentinel) and
        slot index max_batch — both dropped by their scatters."""
        blocks = kvc.scatter_pages(blocks, suffix, dest_blocks)
        lengths = lengths.at[slots].set(true_lens)
        tokens = tokens.at[slots, 0].set(next_toks)
        gen = gen.at[slots].set(1)
        done = done.at[slots].set(maxn_new <= 1)
        maxn = maxn.at[slots].set(maxn_new)
        return blocks, lengths, tokens, gen, done, maxn

    def _splice_impl(self, pool, group, slots, true_lens, next_toks, maxn_new,
                     lengths, tokens, gen, done, maxn):
        """Scatter a (max_seq-grown) prefill cache into ``slots``, updating
        all per-slot decode state in the same dispatch.

        Dummy rows (batch padding) carry slot index == max_batch, which is
        out of bounds: JAX scatters drop OOB updates, so they vanish without
        a separate code path or extra compile.
        """
        out = {}
        for gi, g in enumerate(self.model.groups):
            stacked = g.count > 1

            def leaf(p, n, _stacked=stacked):
                if _stacked:  # [L, B, ...] pool, [L, N, ...] group
                    return p.at[:, slots].set(n.astype(p.dtype))
                return p.at[slots].set(n.astype(p.dtype))

            out[f"g{gi}"] = jax.tree.map(leaf, pool[f"g{gi}"], group[f"g{gi}"])
        lengths = lengths.at[slots].set(true_lens)
        tokens = tokens.at[slots, 0].set(next_toks)
        gen = gen.at[slots].set(1)
        # the prefill token may already exhaust the budget (max_new=1):
        # such slots start done so decode never advances them
        done = done.at[slots].set(maxn_new <= 1)
        maxn = maxn.at[slots].set(maxn_new)
        return out, lengths, tokens, gen, done, maxn

    # ------------------------------------------------------------------ #
    def free_slots(self) -> list[int]:
        return [i for i, s in enumerate(self.slots) if s is None]

    @property
    def all_free(self) -> bool:
        return all(s is None for s in self.slots)

    @property
    def done_mask(self) -> np.ndarray:
        """Host copy of the device-side per-slot done flags."""
        return np.asarray(self.done)

    def splice(self, art: PrefillArtifact):
        """Admit a prefill artifact (local or transferred) into the pool."""
        if self.paged:
            (self.blocks, self.lengths, self.tokens, self.gen, self.done,
             self.maxn) = self._splice_jit(
                self.blocks, art.caches, jnp.asarray(art.dest_blocks),
                jnp.asarray(art.slot_idx), art.lengths, art.next_tokens,
                art.max_new, self.lengths, self.tokens, self.gen, self.done,
                self.maxn,
            )
            return
        (self.caches, self.lengths, self.tokens, self.gen, self.done,
         self.maxn) = self._splice_jit(
            self.caches, art.caches, jnp.asarray(art.slot_idx), art.lengths,
            art.next_tokens, art.max_new, self.lengths, self.tokens,
            self.gen, self.done, self.maxn,
        )

    # ------------------------------------------------------------------ #
    # paged page-table plumbing (host-authored, device-consumed)
    # ------------------------------------------------------------------ #
    def set_row(self, slot: int, blocks_list: list):
        """Install a slot's page table row (admission). The device copy is
        pushed lazily before the next dispatch; steps already in flight
        read the OLD table, whose entries for this row are zero — their
        writes drop at the sentinel, so a stale window is harmless."""
        self.pt_host[slot, :] = 0
        self.pt_host[slot, : len(blocks_list)] = blocks_list
        self._pt_dirty = True
        self._slot_blocks[slot] = list(blocks_list)

    def release_slot(self, slot: int):
        """Drop a finished row's block references and zero its page-table
        row. Shared prefix blocks survive as long as the prefix index (or
        another row) still holds them — the refcount, not the slot, owns
        block lifetime."""
        if not self.paged:
            return
        self.allocator.deref(self._slot_blocks[slot])
        self._slot_blocks[slot] = []
        self.pt_host[slot, :] = 0
        self._pt_dirty = True

    def _sync_pt(self):
        if self._pt_dirty:
            pt = jnp.asarray(self.pt_host)
            if self._sharding is not None:
                pt = jax.device_put(pt, self._sharding)
            self.page_table = pt
            self._pt_dirty = False

    def fill_one(self, params, limit: Optional[int] = None) -> bool:
        """Dispatch one decode step if the in-flight window has room.

        ``limit`` caps the window below ``inflight`` (adaptive dispatch:
        the engine passes the live slots' max outstanding token budget, so
        the device never runs steps no request can consume — the overshoot
        the fixed-depth window wasted on every finishing request).
        """
        cap = self.inflight if limit is None else max(0, min(self.inflight,
                                                             limit))
        if len(self.window) >= cap:
            return False
        if self.paged:
            self._sync_pt()
            (self.tokens, self.blocks, self.lengths, self.gen,
             self.done, self.key) = self._step_jit(
                params, self.blocks, self.page_table, self.tokens,
                self.lengths, self.gen, self.maxn, self.done, self.eos_arr,
                self.key,
            )
        else:
            (self.tokens, self.caches, self.lengths, self.gen,
             self.done, self.key) = self._step_jit(
                params, self.caches, self.tokens, self.lengths,
                self.gen, self.maxn, self.done, self.eos_arr, self.key,
            )
        self.window.append(_InFlight(self.tokens, self.done, tuple(self.slots)))
        return True

    def pop_oldest(self) -> Optional[_InFlight]:
        return self.window.popleft() if self.window else None


class ServingEngine:
    """Continuous-batching serving engine over a slot-based KV pool.

    The public surface is three calls: :meth:`submit` queues a request,
    :meth:`step` runs one continuous-batching iteration (admit -> dispatch
    -> harvest) and returns any finished :class:`~repro.serving.request.
    Response` objects, and :meth:`run_until_drained` loops :meth:`step`
    until queue, slots, and in-flight window are all empty. Per-request
    stage accounting accumulates in ``self.store`` (a ProfileStore); the
    pre-admission wait (submit -> the admission that picks the request)
    is charged as the 'queue' stage, so single-engine and cluster
    breakdowns compare like for like.

    ``warmup=True`` pre-traces the pow2 serving shape grid at
    construction (see :meth:`warm`), so no timed serving stage ever
    charges an XLA compile. ``legacy=True`` keeps the seed synchronous
    loop as the measured A/B baseline.
    """

    def __init__(
        self,
        model: Model,
        params,
        *,
        max_batch: int = 8,
        max_seq: int = 256,
        transport: Transport = Transport.GDR,
        profile: TransportProfile = PAPER_A2,
        eos_token: Optional[int] = None,
        bucketed_prefill: bool = True,
        inflight: int = 4,
        min_bucket: int = 16,
        legacy: bool = False,
        warmup: bool = False,
        adaptive_window: bool = True,
        temperature: float = 0.0,
        top_k: int = 0,
        sample_seed: int = 0,
        paged: bool = False,
        page_size: int = 16,
        cache_blocks: Optional[int] = None,
        prefix_reuse: bool = True,
        packed: bool = False,
        prefill_chunk: int = 0,
        debug_stamps: bool = False,
        trace_tag: str = "engine",
    ):
        self.model = model
        self.params = params
        # per-stage param handles: the fused engine serves both stages
        # from one (uncommitted) copy; the disaggregated tier replaces
        # these with copies committed to each stage's pod slice.
        self.prefill_params = params
        self.decode_params = params
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.transport = transport
        self.profile = profile
        self.eos = eos_token
        # bucketed (right-padded) prefill is only sound when trailing pad
        # cannot leak into cached state: pure-attention stacks. SSM/hybrid
        # recurrences integrate pad tokens into conv/state, so those archs
        # take the exact-shape path (see Model.prefill_bucketed).
        attention_only = all(
            kind == "attn" for g in model.groups for (kind, _) in g.sigs
        )
        self.bucketed_prefill = bucketed_prefill and attention_only and not legacy
        if model.cfg.sliding_window and model.cfg.sliding_window < max_seq:
            # the slot pool is sized to max_seq but a sliding-window cache
            # rings at W=window: growing/splicing prefill caches into the
            # pool would mismatch (and right-pad past the window would
            # clobber live slots). Serve with max_seq <= window instead.
            raise ValueError(
                f"slot-pool engine requires max_seq <= sliding_window "
                f"({max_seq} > {model.cfg.sliding_window})"
            )
        self.inflight = 1 if legacy else max(1, inflight)
        self.min_bucket = min_bucket
        self.legacy = legacy
        # adaptive in-flight window: never dispatch deeper than the live
        # slots' outstanding token budget (fixed-depth windows waste up to
        # inflight-1 steps per finishing request)
        self.adaptive_window = adaptive_window and not legacy
        if legacy and temperature > 0.0:
            raise ValueError(
                "device-side sampling requires the fast path (the legacy "
                "loop argmaxes on host)"
            )
        # paged KV pool: fixed-size blocks + per-slot page tables, with the
        # ring pool kept as the A/B baseline (paged=False). Rides the
        # bucketed fast path only — the exact/legacy paths splice max_seq
        # ring trees.
        self.paged = bool(paged)
        self.page = int(page_size)
        if self.paged and not self.bucketed_prefill:
            raise ValueError(
                "paged KV pool requires the bucketed fast path "
                "(attention-only stack, legacy=False, bucketed_prefill=True)"
            )
        if self.paged and self.min_bucket % self.page:
            raise ValueError(
                f"min_bucket {self.min_bucket} must be a multiple of "
                f"page_size {self.page} (suffix buckets scatter page-wise)"
            )
        # token-packed prefill: admitted prompts concatenate into ONE
        # [1, pow2(total_tokens)] sequence with per-token segment ids, so
        # prefill cost tracks total TRUE tokens instead of rows x bucket.
        # Same soundness gate as bucketing (attention-only), plus non-MLA:
        # segment masking rides chunked_attention's plain-score path.
        # Auto-downgrades silently (like bucketed_prefill) so cross-arch
        # callers can set packed=True unconditionally.
        self.packed = (
            bool(packed) and self.bucketed_prefill and model.cfg.mla is None
        )
        # chunked prefill: prompts longer than prefill_chunk admit as a
        # sequence of fixed-width suffix-prefill chunks interleaved with
        # decode steps (one chunk per engine iteration), so a long
        # admission never stalls live decodes for its full prefill wall.
        self.prefill_chunk = int(prefill_chunk)
        if self.prefill_chunk < 0:
            raise ValueError(f"prefill_chunk must be >= 0: {prefill_chunk}")
        if self.prefill_chunk:
            if self.paged:
                raise ValueError(
                    "chunked prefill rides the ring pool (its fixed-width "
                    "prior splices via dense dynamic_update_slice); use "
                    "paged=False with prefill_chunk"
                )
            if self.prefill_chunk > max_seq:
                raise ValueError(
                    f"prefill_chunk {prefill_chunk} exceeds max_seq {max_seq}"
                )
        self._chunk_enabled = (
            self.prefill_chunk > 0 and self.bucketed_prefill
            and model.cfg.mla is None
        )
        self._chunk_jobs: deque = deque()  # in-progress chunked admissions
        self._chunk_slots: set = set()  # slots reserved by chunk jobs
        # shared-prefix reuse rides the paged pool; MLA suffix prefill can't
        # consume a gathered latent prior, so MLA pages without reuse.
        # Packed admissions interleave segments inside one sequence, so
        # their pages never align with the prefix index — reuse turns off.
        self.prefix_reuse = bool(
            self.paged and prefix_reuse and model.cfg.mla is None
            and not self.packed
        )
        self.prefix_index = (RadixPrefixIndex(self.page)
                             if self.prefix_reuse else None)
        # prefill telemetry: total vs uncached prompt tokens. The ring path
        # tracks the same counters (everything uncached) so A/B runs share
        # a schema; with reuse on, uncached is what prefill actually paid.
        self.prefill_tokens_total = 0
        self.prefill_tokens_uncached = 0
        self.prefix_hits = 0
        self.prefix_hit_tokens = 0
        # padded token-rows actually dispatched to prefill jits: a
        # deterministic FLOPs proxy (bucketed pays npad*L per group,
        # packed pays the pow2 packed width) — the A/B win the packing
        # bench asserts without depending on wall-clock noise
        self.prefill_padded_tokens = 0
        # prefill sampling key: its own stream (decoupled from the decode
        # pool's by fold_in), only ever consumed when temperature > 0
        self.prefill_key = jax.random.fold_in(
            jax.random.PRNGKey(sample_seed), 1
        )
        self.store = ProfileStore()

        self.queue: deque[Request] = deque()
        self.pool = DecodePool(
            model, max_batch=max_batch, max_seq=max_seq,
            eos_token=eos_token, inflight=self.inflight,
            temperature=temperature, top_k=top_k, sample_seed=sample_seed,
            paged=self.paged, page_size=page_size, cache_blocks=cache_blocks,
        )
        self._records: dict[int, RequestRecord] = {}

        self._finished_ids: set[int] = set()
        # entries popped from the window but not yet finalized — empty on
        # the synchronous step() path; EnginePipeline parks its harvest/
        # detokenize backlog here so _finished_ids pruning sees them
        self._backlog_entries: deque = deque()
        self._prefill_finished: list[Response] = []
        self._t_mark = time.perf_counter()
        self.decode_steps = 0  # total whole-batch decode dispatches
        self.useful_steps = 0  # harvested steps that advanced a live request
        # tracing (core/trace): request-scoped queue/prefill spans, a root
        # span per finished request, and engine.* phase spans (step,
        # admit, prefill, dispatch, harvest) that also land on the
        # profiler's clock. trace_tag names this engine's process-level
        # span lane so co-resident engines (in-process cluster replicas)
        # don't share a timeline check lane.
        self.trace_tag = trace_tag
        # debug-mode stamp validation: every finished request's
        # t_arrival/t_first_token/t_done monotonicity is checked (a stage
        # clock running backwards here means a bad cross-process rebase)
        self.debug_stamps = bool(debug_stamps)

        # jitted entry points; jax.jit retraces per input shape, so the
        # prefill compile count equals the number of distinct bucket shapes.
        self._decode = jax.jit(  # reprolint: disable=RL005 legacy-loop only; legacy retraces per shape by design (warm() is a no-op under legacy=True)
            lambda p, c, t, l: model.decode_step(p, c, t, l)
        )
        self._prefill_bucket_jit = jax.jit(self._prefill_bucket_impl)
        self._prefill_exact_jit = jax.jit(self._prefill_exact_impl)  # reprolint: disable=RL005 exact-shape path (feature payloads/SSM) compiles per ragged request shape and cannot be pre-traced; see warm() docstring
        self._prefill_paged_jit = jax.jit(self._prefill_paged_impl)
        self._prefill_suffix_jit = jax.jit(self._prefill_suffix_impl)
        self._prefill_packed_jit = jax.jit(self._prefill_packed_impl)
        # the chunk jits see ONE shape each (fixed-width prior + chunk), so
        # chunked prefill adds exactly two compiles per engine; the prior
        # is donated through every chunk (steady chunking holds one tree)
        self._chunk_jit = jax.jit(self._chunk_impl, donate_argnums=(1,))
        # (no donation: the row pad GROWS every leaf, so no buffer reuses)
        self._chunk_pad_jit = jax.jit(self._chunk_pad_impl)
        self._prefill_shapes: set = set()
        self._prefill_cache = {}  # legacy per-(S, features) jit cache

        self.warmup = warmup
        self.warm_s = 0.0  # construction-time warm wall, outside all stages
        if warmup:
            self.warm_s = self.warm()

    # ------------------------------------------------------------------ #
    # decode-pool delegation (legacy loop + external callers)
    # ------------------------------------------------------------------ #
    @property
    def slots(self):
        return self.pool.slots

    @property
    def caches(self):
        return self.pool.caches

    @caches.setter
    def caches(self, v):
        self.pool.caches = v

    @property
    def lengths(self):
        return self.pool.lengths

    @lengths.setter
    def lengths(self, v):
        self.pool.lengths = v

    @property
    def tokens(self):
        return self.pool.tokens

    @tokens.setter
    def tokens(self, v):
        self.pool.tokens = v

    @property
    def done_mask(self) -> np.ndarray:
        """Host copy of the device-side per-slot done flags."""
        return self.pool.done_mask

    # ------------------------------------------------------------------ #
    # jitted prefill bodies
    # ------------------------------------------------------------------ #
    def _prefill_bucket_impl(self, params, tokens, lengths, key):
        """Padded-bucket prefill + first token sampled on device (argmax at
        temperature 0 — the token-identity baseline — else the same
        temperature/top-k categorical the decode step uses, from the
        engine's own prefill key stream).

        The cache ring dim is grown to max_seq HERE, inside the same jit:
        the admission splice then sees one fixed shape regardless of bucket,
        so it compiles exactly once per engine.
        """
        logits, caches, lens = self.model.prefill_bucketed(
            params, {"tokens": tokens}, lengths
        )
        caches = kvc.grow_cache(caches, self.max_seq)
        return self.pool._sample(logits, key), caches, lens

    def _prefill_paged_impl(self, params, tokens, lengths, key):
        """Paged-bucket prefill: same padded prefill + device sampling, but
        the cache stays at BUCKET width — the paged splice scatters its
        pages straight into the block pool, so nothing grows to max_seq."""
        logits, caches, lens = self.model.prefill_bucketed(
            params, {"tokens": tokens}, lengths
        )
        return self.pool._sample(logits, key), caches, lens

    def _prefill_suffix_impl(self, params, blocks, prior_pt, tokens, lengths,
                             cached, key):
        """Suffix prefill over a reused prefix: the prior KV is gathered
        from the block pool THROUGH the page table inside the same jit (the
        shared blocks never copy host-side), and suffix queries attend to
        prior + suffix keys at per-row absolute positions. Returns the
        bucket-width SUFFIX cache; the reused prefix never moves again."""
        prior = kvc.gather_pages(blocks, prior_pt)
        logits, caches, lens = self.model.prefill_suffix(
            params, {"tokens": tokens}, lengths, cached, prior
        )
        return self.pool._sample(logits, key), caches, lens

    def _prefill_packed_impl(self, params, tokens, positions, seg_ids,
                             seg_starts, last_idx, key):
        """Token-packed prefill: ONE [1, T] sequence holding every admitted
        prompt back to back, masked by per-token segment ids. Positions are
        segment-relative (RoPE matches the unpacked run bitwise) and each
        segment's first-token logits gather at its last real token.

        The packed cache unpacks per segment in the SAME jit —
        ``kvcache.unpack_segments`` windows each segment's rows out to the
        pool's splice width — so the artifact downstream machinery sees is
        shaped exactly like a bucketed admission's.
        """
        logits, packed = self.model.prefill_packed(
            params, tokens, positions, seg_ids, last_idx
        )
        if self.paged:
            out_w = min(tokens.shape[1], self.max_seq)
        else:
            out_w = self.max_seq
        caches = kvc.unpack_segments(packed, seg_starts, out_w)
        return self.pool._sample(logits, key), caches

    def _chunk_impl(self, params, prior, tokens, lengths, cached, key):
        """One chunk of a chunked prefill: suffix-prefill the [1, C] chunk
        against the request's fixed-width prior tree (``prior_valid`` =
        ``cached`` masks the unwritten tail), then splice the suffix cache
        back into the prior at the chunk's offset. ``cached`` is traced, so
        ONE compile serves every chunk of every request."""
        logits, suffix, _total = self.model.prefill_suffix(
            params, {"tokens": tokens}, lengths, cached, prior
        )
        prior = kvc.splice_suffix(prior, suffix, cached[0])
        return self.pool._sample(logits, key), prior

    def _chunk_pad_impl(self, prior):
        """Final-chunk artifact shaping: pad the single-row prior tree out
        to the admission width so the standard fused splice consumes it."""
        return kvc.pad_cache_rows(prior, self.max_batch)

    def _new_chunk_prior(self):
        """Fresh fixed-width prior tree for one chunked admission (the
        disaggregated tier overrides this to place it on the prefill pod
        slice, so every chunk computes there and only the final artifact
        crosses the pod boundary)."""
        return self.model.init_cache(1, self.max_seq)

    def _next_prefill_key(self):
        """Advance the prefill sampling stream (one split per prefill
        dispatch). Temperature 0 never consumes entropy — the key passes
        through unsplit, so greedy runs stay bit-stable regardless of how
        many admissions preceded any given one."""
        if self.pool.temperature == 0.0:
            return self.prefill_key
        self.prefill_key, sub = jax.random.split(self.prefill_key)
        return sub

    def _prefill_exact_impl(self, params, batch):
        """Exact-shape prefill (feature payloads / non-bucketable archs),
        grown to max_seq in-jit so the splice shape stays fixed."""
        logits, caches, lens = self.model.prefill(params, batch)
        caches = kvc.grow_cache(caches, self.max_seq)
        return logits, caches, lens

    # ------------------------------------------------------------------ #
    def submit(self, req: Request, now: Optional[float] = None):
        """Queue a request for admission at the next step boundary.

        Stamps the arrival clock and charges the modeled INGRESS stages
        (request wire + copy engine, per the deployment's transport) to
        the request's record; both reach its TTFT/total at finish time,
        symmetric with the egress wire. Raises if the prompt exceeds
        ``max_seq``.
        """
        # one clock source (perf_counter) for arrival, first token, and done
        # stamps; the caller's ``now`` is accepted for API compatibility but
        # no longer mixed into latency math.
        req.t_arrival = time.perf_counter()
        if len(req.prompt_tokens) > self.max_seq:
            raise ValueError(
                f"prompt length {len(req.prompt_tokens)} exceeds max_seq "
                f"{self.max_seq}"
            )
        if self.paged:
            if req.features is not None:
                raise ValueError(
                    "paged KV pool serves token prompts only (feature "
                    "payloads take the exact-shape ring path)"
                )
            if len(req.prompt_tokens) + req.max_new_tokens > self.max_seq:
                # the ring pool wraps a long generation over its own oldest
                # positions; a paged row may SHARE its prefix blocks, so
                # wrapping would corrupt other readers — reject instead
                raise ValueError(
                    f"prompt + max_new ({len(req.prompt_tokens)} + "
                    f"{req.max_new_tokens}) exceeds max_seq {self.max_seq}: "
                    "the paged pool never ring-wraps"
                )
        rec = RequestRecord(
            request_id=req.request_id, client_id=req.client_id,
            priority=req.priority, t_issue=req.t_arrival,
            bytes_in=req.payload_bytes, bytes_out=4 * req.max_new_tokens,
        )
        # modeled ingress: wire + (copy engine for staged transports)
        rec.add("request", self.profile.wire_time(self.transport, rec.bytes_in))
        if self.transport.uses_copy_engine:
            rec.add("copy_in", self.profile.copy_time(rec.bytes_in))
        self._records[req.request_id] = rec
        self.queue.append(req)
        # instant span marking arrival (the modeled ingress charges are
        # attrs, not wall: they never happened on this clock)
        trace.tracer().emit(
            "submit", req.t_arrival, req.t_arrival,
            request_id=req.request_id, bytes_in=rec.bytes_in,
            charge="modeled",
        )

    def _free_slots(self):
        """Admittable slots: the pool's free list minus slots a chunked
        admission has reserved but not yet occupied (its request only
        lands in ``pool.slots`` at the final chunk)."""
        if self._chunk_slots:
            return [s for s in self.pool.free_slots()
                    if s not in self._chunk_slots]
        return self.pool.free_slots()

    @property
    def prefill_compile_count(self) -> int:
        """Distinct prefill shapes compiled so far (bucketed + exact)."""
        return len(self._prefill_shapes) + len(self._prefill_cache)

    def _bucket(self, s: int) -> int:
        return min(max(_next_pow2(s), self.min_bucket), self.max_seq)

    # ------------------------------------------------------------------ #
    # Construction-time warmup: pre-trace the serving shape grid
    # ------------------------------------------------------------------ #
    def bucket_grid(self) -> list:
        """Every pow2 prefill bucket this engine can admit into:
        ``min_bucket, 2*min_bucket, ..., max_seq`` (clamped)."""
        out, L = [], min(self.min_bucket, self.max_seq)
        while True:
            out.append(L)
            if L >= self.max_seq:
                return out
            L = min(L * 2, self.max_seq)

    def packed_grid(self) -> list:
        """Every pow2 packed width a packed admission can dispatch:
        ``min_bucket .. pow2(max_batch * max_seq)``."""
        out, T = [], min(self.min_bucket, self.packed_cap())
        while True:
            out.append(T)
            if T >= self.packed_cap():
                return out
            T = min(T * 2, self.packed_cap())

    def warm(self) -> float:
        """Pre-trace every shape the bucketed serving path can hit, so no
        timed serving stage ever charges an XLA compile.

        Runs the jits for REAL on dummy inputs (jit's executable cache is
        not populated by AOT lowering): one prefill per pow2 bucket, the
        fused admission splice (with every row's slot index out of bounds,
        so nothing is written), and one decode step on the all-done pool
        (whose outputs are discarded and the state re-zeroed). The
        disaggregated tier extends this over its (mode, rows, prefix)
        handoff extent grid via the :meth:`_warm_admit` seam. Returns the
        warm wall seconds — charged to no request stage.

        The exact-shape path (feature payloads / SSM-hybrid stacks)
        compiles per ragged request shape and cannot be pre-traced; under
        ``legacy=True`` this is a no-op (the legacy loop retraces per
        prompt length by design).
        """
        if self.legacy:
            return 0.0
        t0 = time.perf_counter()
        art = None
        if self.bucketed_prefill:
            if self.packed:
                # packed admissions replace the bucket groups entirely:
                # warm the pow2 PACKED width grid instead
                for T in self.packed_grid():
                    art = self._warm_packed(T)
                    if self.paged:
                        self._warm_admit(art)
            else:
                for L in self.bucket_grid():
                    art = self._warm_bucket(L)
                    if self.paged:
                        # paged splice/handoff shapes follow the bucket width
                        # (the suffix cache is never grown to max_seq), so the
                        # admission path warms once per bucket, not once total
                        self._warm_admit(art)
                        if self.prefix_reuse:
                            self._warm_suffix(L)
        if self._chunk_enabled:
            # one chunk + one row-pad compile covers every chunked
            # admission; the artifact is ring-shaped like a bucket's
            art = self._warm_chunk()
        if not self.paged:
            self._warm_admit(art)
        # the decode step compiles once; its ring writes land in rows the
        # next real splice overwrites, but reset anyway for a bit-pristine
        # pool
        self.pool.fill_one(self.decode_params)
        jax.block_until_ready(self.pool.tokens)
        self.pool.reset_state()
        return time.perf_counter() - t0

    def _warm_bucket(self, L: int) -> PrefillArtifact:
        """Compile one pow2 prefill bucket and return the (all-dummy-row)
        artifact — shaped and placed exactly like a real admission's, so
        downstream warm calls hit the same jit cache entries."""
        npad = self.max_batch
        toks = jnp.asarray(np.zeros((npad, L), np.int32))
        lens = jnp.asarray(np.ones((npad,), np.int32))
        if self.paged:
            next_toks, cache1, lens_d = self._prefill_paged_jit(
                self.prefill_params, toks, lens, self.prefill_key
            )
            self._prefill_shapes.add(("paged", L))
            return PrefillArtifact(
                cache1, np.full((npad,), npad, np.int32),  # every row OOB
                lens_d, next_toks, jnp.asarray(np.ones((npad,), np.int32)),
                [], [], n_rows=0, prefix_len=1,
                # dest block 0 = zero sentinel: the splice writes nothing
                dest_blocks=np.zeros((npad, L // self.page), np.int32),
                cached_lens=np.zeros((npad,), np.int32), bucket=L,
            )
        next_toks, cache1, lens_d = self._prefill_bucket_jit(
            self.prefill_params, toks, lens, self.prefill_key
        )
        self._prefill_shapes.add(("bucket", L))
        return PrefillArtifact(
            cache1, np.full((npad,), npad, np.int32),  # every row OOB
            lens_d, next_toks, jnp.asarray(np.ones((npad,), np.int32)),
            [], [], n_rows=0, prefix_len=1,
        )

    def _warm_packed(self, T: int) -> PrefillArtifact:
        """Compile one pow2 packed width and return the all-dummy-row
        artifact (every token pad, every slot OOB), shaped and placed like
        a real packed admission's."""
        npad = self.max_batch
        next_toks, caches = self._prefill_packed_jit(
            self.prefill_params,
            jnp.asarray(np.zeros((1, T), np.int32)),
            jnp.asarray(np.zeros((1, T), np.int32)),
            jnp.asarray(np.full((1, T), -1, np.int32)),
            jnp.asarray(np.zeros((npad,), np.int32)),
            jnp.asarray(np.zeros((npad,), np.int32)),
            self.prefill_key,
        )
        self._prefill_shapes.add(("packed", T))
        lens_d = jnp.asarray(np.ones((npad,), np.int32))
        ones = jnp.asarray(np.ones((npad,), np.int32))
        oob = np.full((npad,), npad, np.int32)
        if self.paged:
            out_w = min(T, self.max_seq)
            return PrefillArtifact(
                caches, oob, lens_d, next_toks, ones, [], [],
                n_rows=0, prefix_len=1,
                dest_blocks=np.zeros((npad, out_w // self.page), np.int32),
                cached_lens=np.zeros((npad,), np.int32), bucket=out_w,
            )
        return PrefillArtifact(caches, oob, lens_d, next_toks, ones, [], [],
                               n_rows=0, prefix_len=1)

    def _warm_chunk(self) -> PrefillArtifact:
        """Compile the chunk + row-pad jits (their shapes never vary) and
        return an all-dummy ring-shaped artifact for the splice warm."""
        npad = self.max_batch
        C = self.prefill_chunk
        next_tok, prior = self._chunk_jit(
            self.prefill_params, self._new_chunk_prior(),
            jnp.asarray(np.zeros((1, C), np.int32)),
            jnp.asarray(np.ones((1,), np.int32)),
            jnp.asarray(np.zeros((1,), np.int32)),
            self.prefill_key,
        )
        caches = self._chunk_pad_jit(prior)
        self._prefill_shapes.add(("chunk", C))
        jax.block_until_ready(next_tok)
        ones = jnp.asarray(np.ones((npad,), np.int32))
        return PrefillArtifact(
            caches, np.full((npad,), npad, np.int32), ones,
            jnp.asarray(np.zeros((npad,), np.int32)), ones, [], [],
            n_rows=0, prefix_len=1,
        )

    def _warm_suffix(self, L: int):
        """Compile the suffix-prefill jit for bucket ``L``: the prior is
        gathered from the pristine block pool through an all-sentinel page
        table (reads zeros), and the output shapes match the plain paged
        bucket's, so the splice jit entry is already warm."""
        npad = self.max_batch
        out = self._prefill_suffix_jit(
            self.prefill_params, self._prior_blocks(),
            jnp.asarray(np.zeros((npad, self.pool.pages_per_seq), np.int32)),
            jnp.asarray(np.zeros((npad, L), np.int32)),
            jnp.asarray(np.ones((npad,), np.int32)),
            jnp.asarray(np.zeros((npad,), np.int32)),
            self.prefill_key,
        )
        jax.block_until_ready(out[0])
        self._prefill_shapes.add(("suffix", L))

    def _warm_admit(self, art: Optional[PrefillArtifact]):
        """Warm the admission path for one all-dummy artifact. The fused
        engine compiles the pool splice; the disaggregated tier overrides
        this to also pre-trace its handoff extent grid."""
        if art is not None:
            self.pool.splice(art)  # all rows OOB: compiles, writes nothing

    # ------------------------------------------------------------------ #
    # Stage seams (overridden by the disaggregated tier)
    # ------------------------------------------------------------------ #
    def _handoff(self, art: PrefillArtifact):
        """Hook between prefill and the decode-pool splice.

        The single-node engine is a no-op. The disaggregated tier moves
        ``art`` across the mesh pod boundary here and returns the handoff
        wall seconds alongside, so the caller charges that time to the
        'transfer' stage instead of 'preprocess'.
        """
        return art, 0.0

    def _ttft_adjust(self, rec: RequestRecord) -> float:
        """Modeled latency folded into ttft/total beyond the measured stamps
        (the disagg tier swaps the measured handoff wall for the
        profile-modeled hop on host-device runs)."""
        return 0.0

    # ------------------------------------------------------------------ #
    # Tracing emitters (core/trace)
    # ------------------------------------------------------------------ #
    def _trace_span(self, name: str, **attrs) -> trace.span:
        """An ``engine.*`` phase span on this engine's lane."""
        return trace.span(name, tag=self.trace_tag, **attrs)

    def _trace_prefill(self, path: str, width: int, rows: int) -> trace.span:
        """``engine.prefill`` over one prefill call, dispatch through its
        host fence; ``live_rows`` counts the decode rows it holds up."""
        live = sum(1 for r in self.pool.slots if r is not None)
        return self._trace_span("engine.prefill", path=path, bucket=width,
                                rows=rows, live_rows=live)

    def _trace_admission(self, path: str, reqs: list, t0: float, now: float,
                         dt: float, n: int, **attrs):
        """Per admitted request: the measured queue-wait span (submit ->
        admission pick, exactly the charged 'queue' stage) and the
        prefill span over the admission's dispatch->completion interval
        (each request's charge is its dt/n share, carried as an attr)."""
        tr = trace.tracer()
        if not tr.enabled:
            return
        for req in reqs:
            rec = self._records[req.request_id]
            tr.emit("queue", rec.t_issue, t0, request_id=req.request_id)
            tr.emit(f"prefill.{path}", t0, now, request_id=req.request_id,
                    share_s=dt / max(n, 1), n=n, **attrs)

    # ------------------------------------------------------------------ #
    # Metrics registry (core/obs): the query plane over the ad-hoc
    # counter attributes the hot paths charge with bare integer adds
    # ------------------------------------------------------------------ #
    def counters(self) -> dict:
        """The engine's ad-hoc counters as one plain dict."""
        return {
            "prefill_tokens_total": self.prefill_tokens_total,
            "prefill_tokens_uncached": self.prefill_tokens_uncached,
            "prefill_padded_tokens": self.prefill_padded_tokens,
            "prefix_hits": self.prefix_hits,
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "prefill_compiles": self.prefill_compile_count,
            "decode_steps": self.decode_steps,
            "useful_steps": self.useful_steps,
            "requests_finished": len(self.store.records),
        }

    def metrics_snapshot(self) -> dict:
        """Counters + live-load gauges absorbed into a fresh
        :class:`~repro.core.obs.Registry` and snapshotted (what
        ``ServingCluster.telemetry()`` embeds per replica)."""
        reg = Registry()
        reg.ingest_counters(self.counters(), prefix="engine.")
        reg.gauge("engine.queue_depth").set(len(self.queue))
        reg.gauge("engine.occupancy").set(
            sum(1 for s in self.pool.slots if s is not None)
        )
        return reg.snapshot()

    # ------------------------------------------------------------------ #
    # Admission
    # ------------------------------------------------------------------ #
    def _admit(self):
        free = self._free_slots()
        if not self.queue or not free:
            return
        order = sorted(
            range(len(self.queue)),
            key=lambda i: (-self.queue[i].priority, i),
        )[: len(free)]
        picked = [self.queue[i] for i in order]
        for i in sorted(order, reverse=True):
            del self.queue[i]
        with self._trace_span("engine.admit") as sp:
            chunking = len(self._chunk_jobs)
            self._admit_picked(picked, free)
            # a long prompt handed to chunked prefill is placed later, by
            # the _chunk_step that runs its final chunk
            sp.set(admitted=len(picked) - (len(self._chunk_jobs) - chunking))

    def _admit_picked(self, picked: list, free: list):
        if self.paged:
            self._admit_paged(picked, free)
            return
        free_it = iter(free)
        if not self.bucketed_prefill:
            # exact-shape path still initializes the device-side decode
            # state (gen/done/max_new) — _prefill_one is legacy-loop-only.
            for req in picked:
                self._prefill_exact(next(free_it), req)
            return
        packables: list[Request] = []
        buckets: dict[int, list[Request]] = {}
        for req in picked:
            if req.features is not None:  # ragged feature payloads: exact path
                self._prefill_exact(next(free_it), req)
            elif (self._chunk_enabled
                  and len(req.prompt_tokens) > self.prefill_chunk):
                # long prompts admit chunk-by-chunk, one chunk per engine
                # iteration, interleaved with decode dispatches
                self._chunk_admit(req, next(free_it))
            elif self.packed:
                packables.append(req)
            else:
                buckets.setdefault(self._bucket(len(req.prompt_tokens)), []).append(req)
        if packables:
            self._prefill_packed(packables, [next(free_it) for _ in packables])
        for L, reqs in buckets.items():
            self._prefill_bucket(L, reqs, [next(free_it) for _ in reqs])

    def _prefill_bucket(self, L: int, reqs: list, slots: list):
        """One padded prefill + fused splice for every request in a bucket.

        The batch dim is padded to a FIXED width (max_batch, the most an
        admission can hold), so the prefill compile count is exactly the
        number of length buckets — O(log max_seq) — with no batch-size
        shape axis."""
        n = len(reqs)
        npad = self.max_batch
        toks = np.zeros((npad, L), np.int32)
        lens = np.zeros((npad,), np.int32)
        maxn = np.zeros((npad,), np.int32)
        slot_idx = np.full((npad,), self.max_batch, np.int32)  # OOB => dropped
        for j, (req, slot) in enumerate(zip(reqs, slots)):
            s = len(req.prompt_tokens)
            toks[j, :s] = req.prompt_tokens
            lens[j] = s
            maxn[j] = req.max_new_tokens
            slot_idx[j] = slot
        self.prefill_tokens_total += int(lens[:n].sum())
        self.prefill_tokens_uncached += int(lens[:n].sum())
        self.prefill_padded_tokens += npad * L
        with self._trace_prefill("bucket", L, n):
            t0 = time.perf_counter()
            next_toks, cache1, lens_d = self._prefill_bucket_jit(
                self.prefill_params, jnp.asarray(toks), jnp.asarray(lens),
                self._next_prefill_key(),
            )
            art = PrefillArtifact(cache1, slot_idx, lens_d, next_toks,
                                  jnp.asarray(maxn), reqs, list(slots),
                                  n_rows=n, prefix_len=int(lens.max()))
            art, t_xfer = self._handoff(art)  # disagg: pod-boundary KV handoff
            self.pool.splice(art)
            toks_host = np.asarray(art.next_tokens)  # reprolint: disable=RL001 deliberate fence: 'preprocess' must include prefill device completion
        dt = max(time.perf_counter() - t0 - t_xfer, 0.0)
        self._prefill_shapes.add(("bucket", L))
        now = time.perf_counter()
        self._trace_admission("bucket", reqs, t0, now, dt, n, bucket=L)
        for j, (req, slot) in enumerate(zip(reqs, slots)):
            rec = self._records[req.request_id]
            # pre-admission wait: submit -> this admission picking the
            # request. Measured wall inside [t_issue, t_done], so
            # total_s >= sum(stage_s) still holds.
            rec.add("queue", max(t0 - rec.t_issue, 0.0))
            rec.add("preprocess", dt / n)  # prefill = serving "preprocessing"
            req.generated.append(int(toks_host[j]))
            req.t_first_token = now
            self._place(req, slot)
        self._t_mark = now  # prefill time is "preprocess", not "inference"

    def _prefill_packed(self, reqs: list, slots: list, jobs: list = None):
        """One token-packed prefill for every admitted prompt.

        Prompts concatenate back to back into a single [1, T] sequence
        (T = pow2 of the TOTAL true tokens, clamped to min_bucket), so a
        ragged admission pays for the tokens it actually has instead of
        rows x bucket width. Segment ids forbid cross-prompt attention and
        segment-relative positions keep RoPE bitwise identical to the
        unpacked run; the in-jit unpack emits the same bucketed-shaped
        artifact every downstream path (splice, disagg handoff, paged
        scatter) already consumes.

        ``jobs`` is the paged admission's planned block rows; counters for
        that path were already charged by :meth:`_admit_paged`.
        """
        n = len(reqs)
        npad = self.max_batch
        total = sum(len(r.prompt_tokens) for r in reqs)
        T = min(max(_next_pow2(total), self.min_bucket), self.packed_cap())
        toks = np.zeros((1, T), np.int32)
        pos = np.zeros((1, T), np.int32)
        seg = np.full((1, T), -1, np.int32)  # -1 = pad: matches nothing
        seg_starts = np.zeros((npad,), np.int32)
        last_idx = np.zeros((npad,), np.int32)
        lens = np.zeros((npad,), np.int32)
        maxn = np.zeros((npad,), np.int32)
        slot_idx = np.full((npad,), npad, np.int32)  # OOB => dropped
        off = 0
        for j, (req, slot) in enumerate(zip(reqs, slots)):
            s = len(req.prompt_tokens)
            toks[0, off:off + s] = req.prompt_tokens
            pos[0, off:off + s] = np.arange(s)
            seg[0, off:off + s] = j
            seg_starts[j] = off
            last_idx[j] = off + s - 1
            lens[j] = s
            maxn[j] = req.max_new_tokens
            slot_idx[j] = slot
            off += s
        # dummy rows keep seg_starts/last_idx 0: their unpacked rows and
        # logits are garbage the OOB slot scatter drops
        if jobs is None:
            self.prefill_tokens_total += total
            self.prefill_tokens_uncached += total
        self.prefill_padded_tokens += T
        with self._trace_prefill("packed", T, n):
            t0 = time.perf_counter()
            next_toks, caches = self._prefill_packed_jit(
                self.prefill_params, jnp.asarray(toks), jnp.asarray(pos),
                jnp.asarray(seg), jnp.asarray(seg_starts),
                jnp.asarray(last_idx), self._next_prefill_key(),
            )
            self._prefill_shapes.add(("packed", T))
            if self.paged:
                out_w = min(T, self.max_seq)
                dest = np.zeros((npad, out_w // self.page), np.int32)
                for j, job in enumerate(jobs):
                    for k in range(out_w // self.page):
                        if k < len(job.pt_row):
                            dest[j, k] = job.pt_row[k]
                art = PrefillArtifact(
                    caches, slot_idx, jnp.asarray(lens), next_toks,
                    jnp.asarray(maxn), reqs, list(slots),
                    n_rows=n, prefix_len=int(lens.max()),
                    dest_blocks=dest, cached_lens=np.zeros((npad,), np.int32),
                    bucket=out_w,
                )
            else:
                art = PrefillArtifact(
                    caches, slot_idx, jnp.asarray(lens), next_toks,
                    jnp.asarray(maxn), reqs, list(slots),
                    n_rows=n, prefix_len=int(lens.max()),
                )
            art, t_xfer = self._handoff(art)  # disagg: pod-boundary handoff
            self.pool.splice(art)
            toks_host = np.asarray(art.next_tokens)  # reprolint: disable=RL001 deliberate fence: packed 'preprocess' includes prefill device completion
        dt = max(time.perf_counter() - t0 - t_xfer, 0.0)
        now = time.perf_counter()
        self._trace_admission("packed", reqs, t0, now, dt, n, packed_width=T)
        for j, (req, slot) in enumerate(zip(reqs, slots)):
            rec = self._records[req.request_id]
            rec.add("queue", max(t0 - rec.t_issue, 0.0))
            rec.add("preprocess", dt / n)
            req.generated.append(int(toks_host[j]))
            req.t_first_token = now
            self._place(req, slot)
        self._t_mark = now

    def packed_cap(self) -> int:
        """Widest packed sequence this engine can dispatch: every slot
        admitted at once, each at a full max_seq prompt, rounded to pow2."""
        return _next_pow2(self.max_batch * self.max_seq)

    # ------------------------------------------------------------------ #
    # Chunked prefill: fixed-width chunks interleaved with decode steps
    # ------------------------------------------------------------------ #
    def _chunk_admit(self, req: Request, slot: int):
        """Reserve ``slot`` and enqueue the request as a chunk job; the
        prompt prefills ``prefill_chunk`` tokens per engine iteration from
        :meth:`_chunk_step` until the final chunk splices it in."""
        self._chunk_slots.add(slot)
        self._chunk_jobs.append(_ChunkJob(req, slot, self._new_chunk_prior()))
        P = len(req.prompt_tokens)
        self.prefill_tokens_total += P
        self.prefill_tokens_uncached += P

    def _chunk_step(self):
        """Run ONE chunk of the oldest chunk job (called once per engine
        iteration, after decode dispatch, so live slots' decode steps are
        already queued ahead of the chunk on the device stream).

        The REMAINDER chunk runs FIRST (sizes r, C, C, ..., C with
        r = ((P-1) % C) + 1): every later chunk is exactly C wide, so the
        final chunk's logits gather at a fixed index and no splice can
        overrun the prior (done + C <= P <= max_seq always). The first
        chunk's pad-token rows write garbage KV beyond r that the next
        chunk's splice overwrites; ``prior_valid`` masks them meanwhile.
        """
        if not self._chunk_jobs:
            return
        with self._trace_span("engine.admit") as sp:
            sp.set(admitted=int(self._chunk_one(self._chunk_jobs[0])))

    def _chunk_one(self, job: _ChunkJob) -> bool:
        """One chunk of ``job``; True when it was the final chunk and the
        request took its slot."""
        C = self.prefill_chunk
        P = len(job.req.prompt_tokens)
        t0 = time.perf_counter()
        rec = self._records[job.req.request_id]
        if job.done == 0:
            # pre-admission wait ends at the first chunk's dispatch
            rec.add("queue", max(t0 - rec.t_issue, 0.0))
            trace.tracer().emit("queue", rec.t_issue, t0,
                                request_id=job.req.request_id)
        n = ((P - 1) % C) + 1 if job.done == 0 else C
        toks = np.zeros((1, C), np.int32)
        toks[0, :n] = job.req.prompt_tokens[job.done:job.done + n]
        with self._trace_prefill("chunk", C, 1):
            next_tok, job.prior = self._chunk_jit(
                self.prefill_params, job.prior, jnp.asarray(toks),
                jnp.asarray(np.asarray([n], np.int32)),
                jnp.asarray(np.asarray([job.done], np.int32)),
                self._next_prefill_key(),
            )
            job.done += n
            self.prefill_padded_tokens += C
            self._prefill_shapes.add(("chunk", C))
            if job.done < P:
                np.asarray(next_tok)  # reprolint: disable=RL001 deliberate fence: chunk 'preprocess' includes device completion (and bounds host run-ahead to one chunk)
                t1 = time.perf_counter()
                rec.add("preprocess", max(t1 - t0, 0.0))
                trace.tracer().emit("prefill.chunk", t0, t1,
                                    request_id=job.req.request_id,
                                    chunk=C, done=job.done, prompt=P)
                return False
            # final chunk: shape the prior into a standard bucketed-style
            # artifact (row dim padded to npad, OOB dummy rows) and splice
            self._chunk_jobs.popleft()
            self._chunk_slots.discard(job.slot)
            npad = self.max_batch
            caches = self._chunk_pad_jit(job.prior)
            job.prior = None  # donated away
            slot_idx = np.full((npad,), npad, np.int32)
            slot_idx[0] = job.slot
            lens = np.zeros((npad,), np.int32)
            lens[0] = P
            maxn = np.zeros((npad,), np.int32)
            maxn[0] = job.req.max_new_tokens
            tok0 = int(np.asarray(next_tok)[0])  # reprolint: disable=RL001 deliberate fence: final-chunk 'preprocess' includes device completion
            next_full = np.zeros((npad,), np.int32)
            next_full[0] = tok0
            art = PrefillArtifact(
                caches, slot_idx, jnp.asarray(lens), jnp.asarray(next_full),
                jnp.asarray(maxn), [job.req], [job.slot],
                n_rows=1, prefix_len=P,
            )
            art, t_xfer = self._handoff(art)  # disagg: pod-boundary handoff
            self.pool.splice(art)
            dt = max(time.perf_counter() - t0 - t_xfer, 0.0)
        rec.add("preprocess", dt)
        job.req.generated.append(tok0)
        now = time.perf_counter()
        trace.tracer().emit("prefill.chunk", t0, now,
                            request_id=job.req.request_id,
                            chunk=C, done=job.done, prompt=P, final=True)
        job.req.t_first_token = now
        self._place(job.req, job.slot)
        self._t_mark = now  # chunk time is "preprocess", not "inference"
        return True

    def _prefill_exact(self, slot: int, req: Request):
        """Exact-shape prefill for feature-carrying (vlm/audio) requests."""
        toks = jnp.asarray(req.prompt_tokens, jnp.int32)[None, :]
        batch = {"tokens": toks}
        if req.features is not None:
            batch["features"] = jnp.asarray(req.features)
        self.prefill_tokens_total += len(req.prompt_tokens)
        self.prefill_tokens_uncached += len(req.prompt_tokens)
        self.prefill_padded_tokens += len(req.prompt_tokens)
        with self._trace_prefill("exact", toks.shape[1], 1):
            t0 = time.perf_counter()
            logits, cache1, lengths1 = self._prefill_exact_jit(
                self.prefill_params, batch
            )
            # eager sample (the exact path compiles per ragged shape anyway);
            # temperature 0 stays the argmax baseline bit-for-bit
            next_tok = self.pool._sample(logits, self._next_prefill_key())
            # feature frames (vlm) prepend to the token sequence, so the cache's
            # true length is frames + prompt — len(prompt_tokens) alone would
            # let a pod handoff slice live KV off the wire. Derived host-side
            # (no device sync on the single-node hot path); the disagg feature
            # regression test pins it against the model-returned lengths.
            frames = 0 if req.features is None else int(np.shape(req.features)[-2])
            art = PrefillArtifact(
                cache1, np.asarray([slot], np.int32), lengths1, next_tok,
                jnp.asarray([req.max_new_tokens], jnp.int32), [req], [slot],
                n_rows=1, prefix_len=len(req.prompt_tokens) + frames,
            )
            art, t_xfer = self._handoff(art)
            self.pool.splice(art)
            tok_host = int(np.asarray(art.next_tokens)[0])  # reprolint: disable=RL001 deliberate fence: exact-path 'preprocess' includes device completion
        dt = max(time.perf_counter() - t0 - t_xfer, 0.0)
        self._prefill_shapes.add(
            ("exact", toks.shape[1],
             None if req.features is None else np.shape(req.features))
        )
        rec = self._records[req.request_id]
        rec.add("queue", max(t0 - rec.t_issue, 0.0))  # submit -> admission
        rec.add("preprocess", dt)
        req.generated.append(tok_host)
        req.t_first_token = time.perf_counter()
        self._trace_admission("exact", [req], t0, req.t_first_token, dt, 1,
                              prompt=len(req.prompt_tokens))
        self._place(req, slot)
        self._t_mark = req.t_first_token  # prefill time is not "inference"

    # ------------------------------------------------------------------ #
    # Paged admission: prefix match -> block plan -> grouped prefill
    # ------------------------------------------------------------------ #
    def _admit_paged(self, picked: list, free: list):
        """Plan every picked request's page table, then prefill in groups.

        All prefix MATCHES happen before any INSERT, so two requests
        sharing a prefix admitted in the same batch can't false-match
        pages whose KV this very admission is still computing — the second
        request recomputes the shared prefix once; reuse starts at the
        next admission. Matched blocks are refcount-pinned here (the
        d-side for the row's lifetime, the p-side until its suffix jit
        has the prior in hand), so index eviction under pool pressure can
        never free KV a picked request is about to read.
        """
        page = self.page
        jobs: list[_PagedJob] = []
        for req, slot in zip(picked, free):
            P = len(req.prompt_tokens)
            p_ids: list = []
            d_ids: list = []
            cached = 0
            if self.prefix_reuse:
                # cap the match below the full prompt: at least one suffix
                # token must remain to produce the first-token logits
                payloads = self.prefix_index.match(
                    req.prompt_tokens, (P - 1) // page
                )
                if payloads:
                    cached = len(payloads) * page
                    p_ids = [p for (p, _) in payloads]
                    d_ids = [d for (_, d) in payloads]
                    self._store_alloc().ref(p_ids)
                    self.pool.allocator.ref(d_ids)
                    self.prefix_hits += 1
                    self.prefix_hit_tokens += cached
            n_pages = -(-(P + req.max_new_tokens) // page)
            own = self._alloc_blocks(n_pages - cached // page)
            pt_row = d_ids + own
            self.pool.set_row(slot, pt_row)
            self.prefill_tokens_total += P
            self.prefill_tokens_uncached += P - cached
            jobs.append(_PagedJob(req, slot, cached, p_ids, d_ids, own,
                                  pt_row))
        if self.packed:
            # prefix reuse is off under packing (cached == 0 for every
            # job): one packed dispatch replaces the bucket groups
            self._prefill_packed(
                [job.req for job in jobs], [job.slot for job in jobs],
                jobs=jobs,
            )
            return
        groups: dict[tuple, list[_PagedJob]] = {}
        for job in jobs:
            L = self._bucket(len(job.req.prompt_tokens) - job.cached)
            groups.setdefault((L, job.cached > 0), []).append(job)
        for (L, has_prior), gjobs in sorted(groups.items()):
            self._prefill_paged_group(L, has_prior, gjobs)

    def _alloc_blocks(self, n: int) -> list:
        """Allocate ``n`` fresh blocks, LRU-evicting cold prefix-index
        pages under pool pressure. Eviction only drops the INDEX's
        references — a block a live row still reads is refcount-protected
        and stays resident until its last reader releases it."""
        while True:
            got = self.pool.allocator.alloc(n)
            if got is not None:
                return got
            payload = (self.prefix_index.evict_lru()
                       if self.prefix_reuse else None)
            if payload is None:
                raise RuntimeError(
                    "paged KV pool exhausted with no evictable prefix "
                    "pages; raise cache_blocks or lower max_batch"
                )
            self._evict_index_page(payload)

    def _evict_index_page(self, payload):
        """Drop the index's references on one evicted page (fused engine:
        both payload sides name the same decode-pool block)."""
        p, d = payload
        self._store_alloc().deref([p])
        self.pool.allocator.deref([d])

    def _index_insert(self, jobs: list, store_ctx):
        """Index each admitted prompt's fully-in-prompt pages.

        Existing pages keep their first writer's blocks (matches ref THOSE
        at admission); only newly-created nodes take references — one per
        payload side — so the index keeps a released slot's prefix KV
        alive for future hits. A row whose matched interior was LRU-evicted
        during this very admission's allocations is skipped: its chain
        would root orphaned payloads the index can no longer reach.
        """
        if not self.prefix_reuse:
            return
        for job in jobs:
            toks = job.req.prompt_tokens
            n_ins = len(toks) // self.page
            if n_ins == 0:
                continue
            depth = len(self.prefix_index.match(toks, n_ins, peek=True))
            if depth < job.cached // self.page:
                continue
            payloads = [(job.pt_row[i], job.pt_row[i])
                        for i in range(n_ins)]
            created = self.prefix_index.insert(toks, payloads, n_ins)
            for (p, d) in created:
                self._store_alloc().ref([p])
                self.pool.allocator.ref([d])

    # hooks the disaggregated tier overrides: the prior side of a reused
    # prefix lives wherever prefill runs (fused: the decode pool itself;
    # disagg: a prefill-pod block store, so suffix prefill never re-crosses
    # the pod boundary for prefix KV)
    def _store_alloc(self):
        return self.pool.allocator

    def _store_deref(self, ids: list):
        self.pool.allocator.deref(ids)

    def _prior_blocks(self):
        return self.pool.blocks

    def _store_prepare(self, jobs: list, caches, L: int):
        """Seam before the handoff plans wire bytes (disagg stashes the
        suffix cache into the prefill-side store here). Fused: no-op."""
        return None

    def prefix_lookup_tokens(self, tokens) -> int:
        """Router scoring hook: matched prefix length in tokens, LRU- and
        counter-neutral (a peek, not a hit). 0 when reuse is off."""
        if not self.prefix_reuse:
            return 0
        return self.prefix_index.lookup_tokens(tokens)

    def _prefill_paged_group(self, L: int, has_prior: bool, jobs: list):
        """One padded (suffix-)prefill + paged splice for a group of
        admissions sharing a suffix bucket.

        Groups with no reused prefix run the plain paged prefill — bitwise
        the ring bucket path's math. Groups with a prior gather it from the
        block pool inside the suffix jit. Either way the artifact carries
        the bucket-width SUFFIX cache only: reused prefix KV never moves
        again (and, disaggregated, never re-rides the wire).
        """
        page = self.page
        n = len(jobs)
        npad = self.max_batch
        toks = np.zeros((npad, L), np.int32)
        lens = np.zeros((npad,), np.int32)
        cached = np.zeros((npad,), np.int32)
        maxn = np.zeros((npad,), np.int32)
        slot_idx = np.full((npad,), self.max_batch, np.int32)  # OOB => drop
        dest = np.zeros((npad, L // page), np.int32)  # 0 => sentinel drop
        prior_pt = np.zeros((npad, self.pool.pages_per_seq), np.int32)
        for j, job in enumerate(jobs):
            suffix = job.req.prompt_tokens[job.cached:]
            s = len(suffix)
            toks[j, :s] = suffix
            lens[j] = s
            cached[j] = job.cached
            maxn[j] = job.req.max_new_tokens
            slot_idx[j] = job.slot
            cpages = job.cached // page
            for k in range(L // page):
                if cpages + k < len(job.pt_row):
                    dest[j, k] = job.pt_row[cpages + k]
            prior_pt[j, : len(job.p_ids)] = job.p_ids
        self.prefill_padded_tokens += npad * L
        with self._trace_prefill("suffix" if has_prior else "paged", L, n):
            t0 = time.perf_counter()
            key = self._next_prefill_key()
            if has_prior:
                next_toks, cacheL, lens_d = self._prefill_suffix_jit(
                    self.prefill_params, self._prior_blocks(),
                    jnp.asarray(prior_pt), jnp.asarray(toks), jnp.asarray(lens),
                    jnp.asarray(cached), key,
                )
                self._prefill_shapes.add(("suffix", L))
                # the p-pins held the gathered prior across the dispatch; the
                # page-table row (d-side) keeps the row's own hold from here
                for job in jobs:
                    self._store_deref(job.p_ids)
            else:
                next_toks, cacheL, lens_d = self._prefill_paged_jit(
                    self.prefill_params, jnp.asarray(toks), jnp.asarray(lens),
                    key,
                )
                self._prefill_shapes.add(("paged", L))
            store_ctx = self._store_prepare(jobs, cacheL, L)
            art = PrefillArtifact(
                cacheL, slot_idx, lens_d, next_toks, jnp.asarray(maxn),
                [job.req for job in jobs], [job.slot for job in jobs],
                n_rows=n, prefix_len=int((cached + lens).max()),
                dest_blocks=dest, cached_lens=cached, bucket=L,
            )
            art, t_xfer = self._handoff(art)  # disagg: pod-boundary handoff
            self.pool.splice(art)
            toks_host = np.asarray(art.next_tokens)  # reprolint: disable=RL001 deliberate fence: paged 'preprocess' includes prefill device completion
        dt = max(time.perf_counter() - t0 - t_xfer, 0.0)
        # index the prompts' pages BEFORE the records loop: a request the
        # prefill token already finishes releases its slot there, and the
        # index must take its block references first
        self._index_insert(jobs, store_ctx)
        now = time.perf_counter()
        self._trace_admission(
            "suffix" if has_prior else "paged",
            [job.req for job in jobs], t0, now, dt, n, bucket=L,
        )
        for j, job in enumerate(jobs):
            rec = self._records[job.req.request_id]
            rec.add("queue", max(t0 - rec.t_issue, 0.0))
            rec.add("preprocess", dt / n)
            job.req.generated.append(int(toks_host[j]))
            job.req.t_first_token = now
            self._place(job.req, job.slot)
        self._t_mark = now

    def _place(self, req: Request, slot: int):
        """Occupy ``slot`` — or, if the prefill token already exhausted the
        budget (max_new_tokens <= 1), finish the request right away (the
        legacy loop instead runs one decode step and returns 2 tokens; the
        fast path honors the budget)."""
        if req.max_new_tokens <= 1:
            # never occupies the slot, so no in-flight snapshot can
            # reference it — no _finished_ids entry needed. Paged rows
            # still release their page-table hold (the prefix index has
            # already taken its own references by this point).
            self.pool.release_slot(slot)
            self._prefill_finished.append(
                self._finish(req, self._records[req.request_id])
            )
            return
        self.pool.slots[slot] = req

    # ------------------------------------------------------------------ #
    # Decode: async dispatch window + single-transfer harvest
    # ------------------------------------------------------------------ #
    def _window_limit(self) -> Optional[int]:
        """Adaptive dispatch depth: the max outstanding token budget among
        live slots. Steps dispatched beyond it cannot advance any request
        (every slot's device-side done flag freezes first), so they are
        pure waste — the fixed window paid up to inflight-1 of them per
        finishing request. EOS can still finish a request earlier than its
        budget; the cap only removes the waste the budget proves."""
        if not self.adaptive_window:
            return None
        out = [
            req.max_new_tokens - len(req.generated)
            for req in self.pool.slots if req is not None
        ]
        return max(out, default=0)

    def _dispatch(self, outstanding: int = 0):
        """Top up the in-flight window. ``outstanding`` is the number of
        steps already popped from the window but not yet finalized (the
        threaded pipeline's harvest/detokenize backlogs); the inference
        clock only restarts when the device is genuinely idle — window
        empty AND nothing in the backlogs."""
        if self.pool.all_free:
            return
        if not self.pool.window and outstanding == 0:
            # pipeline (re)start: don't charge idle time to "inference"
            self._t_mark = time.perf_counter()
        limit = self._window_limit()
        with self._trace_span("engine.dispatch") as sp:
            steps = 0
            while self.pool.fill_one(self.decode_params, limit=limit):
                steps += 1
            self.decode_steps += steps
            sp.set(steps=steps)

    def _harvest(self) -> list[Response]:
        """Synchronous harvest: device transfer + finalize in one call (the
        ``step()`` path). The threaded pipeline runs the same two stages on
        separate threads — :class:`EnginePipeline` pops the entry, moves the
        device transfer onto its harvest thread, and hands
        :meth:`_finalize_harvest` to its detokenize thread."""
        e = self.pool.pop_oldest()
        if e is None:
            return []
        live = sum(1 for r in e.slots
                   if r is not None and r.request_id not in self._finished_ids)
        with self._trace_span("engine.harvest", live=live):
            toks, _done = jax.device_get((e.tokens, e.done))  # one host transfer
            now = time.perf_counter()
            dt = max(now - self._t_mark, 0.0)
            self._t_mark = now
            return self._finalize_harvest(e, toks, dt)

    def _finalize_harvest(self, e: _InFlight, toks, dt: float) -> list[Response]:
        """Detokenize/record-finalize stage: pure host bookkeeping over one
        harvested step's tokens — per-request records, EOS/budget checks,
        slot release. No device work happens here, which is what lets the
        threaded pipeline run it concurrently with the next dispatch."""
        live = [
            (i, r) for i, r in enumerate(e.slots)
            if r is not None and r.request_id not in self._finished_ids
        ]
        if live:
            self.useful_steps += 1
        done: list[Response] = []
        for i, req in live:
            rec = self._records[req.request_id]
            rec.add("inference", dt / len(live))
            tok = int(toks[i, 0])
            req.generated.append(tok)
            finished = len(req.generated) >= req.max_new_tokens or (
                self.eos is not None and tok == self.eos
            )
            if finished:
                done.append(self._finish(req, rec))
                self._finished_ids.add(req.request_id)
                if self.pool.slots[i] is req:
                    self.pool.slots[i] = None
                    # paged: drop the row's block references (safe while
                    # stale in-flight steps remain — their frozen-lane
                    # writes are dispatched before any splice that could
                    # reuse a freed block, and device order is dispatch
                    # order)
                    self.pool.release_slot(i)
        if done and self._finished_ids:
            # ids only matter while an in-flight snapshot still references
            # them — prune so the set stays O(max_batch * inflight). The
            # threaded pipeline holds popped-but-unfinalized entries in
            # ``_backlog_entries``; their snapshots count as in-flight too,
            # or a stale step could double-finish a pruned request.
            live_ids = {
                r.request_id
                for ent in (*self.pool.window, *self._backlog_entries)
                for r in ent.slots if r is not None
            }
            self._finished_ids &= live_ids
        return done

    def _finish(self, req: Request, rec: RequestRecord) -> Response:
        rsp_wire = self.profile.wire_time(self.transport, rec.bytes_out)
        rec.add("response", rsp_wire)
        egress = rsp_wire
        if self.transport.uses_copy_engine:
            copy_out = self.profile.copy_time(rec.bytes_out)
            rec.add("copy_out", copy_out)
            egress += copy_out
        # the modeled ingress stages (request wire + copy_in) were charged
        # to stage_s at submit but never reached the latency stamps, while
        # the egress wire was folded into total only — include BOTH hops
        # symmetrically so total_s >= sum(stage_s) holds end to end
        ingress = (rec.stage_s.get("request", 0.0)
                   + rec.stage_s.get("copy_in", 0.0))
        adj = self._ttft_adjust(rec)
        rec.t_done = time.perf_counter() + ingress + egress + adj
        req.t_done = rec.t_done
        if self.debug_stamps:
            trace.validate_stamps(
                req.t_arrival, req.t_first_token, req.t_done,
                where=f"request {req.request_id} at finish",
            )
        # root span: the whole request interval (modeled ingress/egress
        # folded into t_done bounds every charged stage, measured or not)
        trace.tracer().emit(
            "request", rec.t_issue, rec.t_done, request_id=req.request_id,
            tokens=len(req.generated), bytes_in=rec.bytes_in,
            bytes_out=rec.bytes_out,
        )
        self.store.add(rec)
        return Response(
            request_id=req.request_id,
            tokens=list(req.generated),
            ttft_s=req.t_first_token - req.t_arrival + ingress + adj,
            total_s=rec.t_done - rec.t_issue,
            stage_s=dict(rec.stage_s),
        )

    # ------------------------------------------------------------------ #
    def step(self) -> list[Response]:
        """One continuous-batching iteration. Returns finished responses.

        Fast path: top up the in-flight window (dispatch-ahead, no sync),
        then harvest the OLDEST dispatched step — the host runs up to
        ``inflight`` steps behind the device and never blocks on the newest
        work.
        """
        with self._trace_span("engine.step"):
            if self.legacy:
                return self._step_legacy()
            self._admit()
            self._dispatch()
            # one chunk AFTER the decode top-up: live slots' steps are
            # already on the device stream, so the chunk interleaves
            # instead of head-of-line blocking a full prefill
            self._chunk_step()
            done = self._harvest()
            if self._prefill_finished:  # budget met by the prefill token
                done = self._prefill_finished + done
                self._prefill_finished = []
            return done

    @property
    def idle(self) -> bool:
        """No queued requests, no occupied slots, no in-flight steps —
        the drain condition, shared with the cluster tier's router and
        the open-loop load generator."""
        return (not self.queue and self.pool.all_free
                and not self.pool.window and not self._chunk_jobs)

    def run_until_drained(self, max_steps: int = 10_000) -> list[Response]:
        out = []
        for _ in range(max_steps):
            out.extend(self.step())
            if self.idle:
                break
        return out

    # ------------------------------------------------------------------ #
    # Legacy synchronous loop (seed behavior): the A/B baseline.
    # ------------------------------------------------------------------ #
    def _prefill_one(self, slot: int, req: Request):  # reprolint: disable=RL001 legacy A/B baseline: the seed loop blocks per token by design
        S = len(req.prompt_tokens)
        toks = jnp.asarray(req.prompt_tokens, jnp.int32)[None, :]
        batch = {"tokens": toks}
        if req.features is not None:
            batch["features"] = jnp.asarray(req.features)
        key = (S, req.features is not None)
        if key not in self._prefill_cache:
            self._prefill_cache[key] = jax.jit(  # reprolint: disable=RL005 legacy loop retraces per (S, features) key by design — the measured A/B baseline
                lambda p, b: self.model.prefill(p, b)
            )
        t0 = time.perf_counter()
        logits, cache1, lengths1 = self._prefill_cache[key](self.params, batch)
        logits.block_until_ready()
        dt = time.perf_counter() - t0
        rec = self._records[req.request_id]
        rec.add("queue", max(t0 - rec.t_issue, 0.0))  # submit -> admission
        rec.add("preprocess", dt)

        cache1 = kvc.grow_cache(cache1, self.max_seq)

        # splice the single-sequence cache into the pool at `slot`
        def splice_group(pool, one, stacked):
            if stacked:  # [L, B, ...]
                return jax.tree.map(
                    lambda p, n: p.at[:, slot].set(n[:, 0].astype(p.dtype)),
                    pool, one,
                )
            return jax.tree.map(
                lambda p, n: p.at[slot].set(n[0].astype(p.dtype)), pool, one,
            )

        self.caches = {
            f"g{gi}": splice_group(
                self.caches[f"g{gi}"], cache1[f"g{gi}"], g.count > 1
            )
            for gi, g in enumerate(self.model.groups)
        }
        self.lengths = self.lengths.at[slot].set(int(lengths1[0]))
        next_tok = int(jnp.argmax(logits[0]))
        self.tokens = self.tokens.at[slot, 0].set(next_tok)
        req.generated.append(next_tok)
        self.pool.slots[slot] = req
        req.t_first_token = time.perf_counter()
        self._trace_admission("legacy", [req], t0, req.t_first_token, dt, 1)

    def _admit_legacy(self):
        while self.queue and self._free_slots():
            best = max(range(len(self.queue)), key=lambda i: self.queue[i].priority)
            req = self.queue[best]
            del self.queue[best]
            self._prefill_one(self._free_slots()[0], req)

    def _step_legacy(self) -> list[Response]:  # reprolint: disable=RL001 legacy A/B baseline: the seed loop blocks per token by design
        """Seed loop: host sync + host argmax + per-slot Python loop.

        Kept byte-faithful to the seed, including its max_new_tokens=1
        quirk (always runs one decode step, returning 2 tokens); the fast
        path finishes such requests at prefill time instead.
        """
        self._admit_legacy()
        active = [i for i, s in enumerate(self.pool.slots) if s is not None]
        if not active:
            return []
        # the synchronous loop's decode step is its own harvest
        with self._trace_span("engine.harvest", live=len(active)):
            t0 = time.perf_counter()
            logits, self.caches, self.lengths = self._decode(
                self.params, self.caches, self.tokens, self.lengths
            )
            self.decode_steps += 1
            self.useful_steps += 1  # sync loop only ever steps live slots
            logits.block_until_ready()
            dt = time.perf_counter() - t0
            next_tokens = np.asarray(jnp.argmax(logits, axis=-1))
            self.tokens = jnp.asarray(next_tokens[:, None], jnp.int32)

            done: list[Response] = []
            for i in active:
                req = self.pool.slots[i]
                rec = self._records[req.request_id]
                rec.add("inference", dt / max(len(active), 1))
                tok = int(next_tokens[i])
                req.generated.append(tok)
                finished = len(req.generated) >= req.max_new_tokens or (
                    self.eos is not None and tok == self.eos
                )
                if finished:
                    done.append(self._finish(req, rec))
                    self.pool.slots[i] = None
            return done


class EnginePipeline:
    """Threaded host pipeline over a (fast-path) :class:`ServingEngine`.

    The single-threaded ``step()`` loop interleaves three host jobs —
    admission+dispatch, the blocking device->host harvest transfer, and
    per-token record bookkeeping — on one thread, so the device waits
    whenever the host is busy detokenizing. This class decouples them onto
    three daemon threads joined by BOUNDED backlog queues, the
    JetStream-style shape (dispatch / device harvest / detokenize backlog):

      dispatch thread   : admits queued requests (prefill + splice) and
                          tops up the in-flight decode window, then moves
                          the oldest dispatched step onto the harvest
                          backlog. All jit dispatch happens here.
      harvest thread    : ``jax.device_get`` of each step's tokens+done —
                          the only stage that blocks on the device.
      detokenize thread : :meth:`ServingEngine._finalize_harvest` — record
                          bookkeeping, EOS/budget checks, slot release,
                          response finalization.

    Each queue edge has a single producer and a single consumer and every
    queue is FIFO, so steps are finalized in dispatch order: records can
    neither reorder nor drop (``submitted``/``emitted`` count the
    conservation invariant, asserted in tests). When detokenize falls
    behind, the harvest thread blocks on its bounded put and dispatch
    blocks in turn — backpressure, never loss.

    The facade stays step()-compatible with a single engine (``submit`` /
    ``step`` / ``queue`` / ``store`` / ``_records`` / ``idle`` /
    ``run_until_drained``), so the Gateway, the load generators, and the
    cluster Router drive it unchanged; ``step()`` just drains finished
    responses (``async_draining = True`` tells the open-loop driver that
    stepping is not what makes progress, so it may sleep instead of spin).
    Engine state is guarded by one lock; the device transfer and the queue
    hand-offs run outside it. Thread failures are captured and re-raised
    on the caller's next ``submit``/``step``/``idle`` touch, so a broken
    pipeline surfaces instead of hanging.

    This is the per-replica host pipeline of the process-per-replica
    cluster tier: ``serving/worker.py`` runs one of these inside each
    replica process behind the socket RPC control plane (serving/ipc.py).
    """

    # tools/reprolint RL003 contract: these attributes are only touched
    # under `with self._lock`, and nothing blocks while the lock is held
    # (a blocking put under the lock is the deadlock shape: a full queue
    # parks every thread that needs the lock)
    _REPROLINT_GUARDED = (
        "_outputs", "_outstanding", "submitted", "emitted",
        "submitted_bytes", "steps", "busy_slot_steps",
    )

    def __init__(self, engine: ServingEngine, *, backlog: int = 2,
                 poll_s: float = 0.0005):
        if engine.legacy:
            raise ValueError(
                "EnginePipeline requires the fast path (the legacy loop "
                "is synchronous by design)"
            )
        if backlog < 1:
            raise ValueError(f"backlog must be >= 1: {backlog}")
        self.engine = engine
        self.poll_s = poll_s
        self.async_draining = True  # step() drains results; threads drive
        self._lock = threading.RLock()
        self._harvest_q: queue_mod.Queue = queue_mod.Queue(maxsize=backlog)
        self._detok_q: queue_mod.Queue = queue_mod.Queue(maxsize=backlog)
        self._outputs: deque = deque()
        self._outstanding = 0  # popped from the window, not yet finalized
        self._stop = threading.Event()
        self._exc: Optional[str] = None
        # conservation + occupancy telemetry (the worker's load snapshot)
        self.submitted = 0
        self.emitted = 0
        self.submitted_bytes = 0
        self.steps = 0  # finalized decode steps (occupancy samples)
        self.busy_slot_steps = 0
        self._threads = [
            threading.Thread(target=self._run_guarded, args=(fn,),
                             name=f"engine-pipeline-{tag}", daemon=True)
            for tag, fn in (("dispatch", self._dispatch_loop),
                            ("harvest", self._harvest_loop),
                            ("detokenize", self._detok_loop))
        ]
        for t in self._threads:
            t.start()

    # ------------------------------------------------------------------ #
    # thread bodies
    # ------------------------------------------------------------------ #
    def _run_guarded(self, fn):
        try:
            fn()
        except BaseException:  # noqa: BLE001 — surface to the caller
            self._exc = traceback.format_exc()
            self._stop.set()

    def _put(self, q, item) -> bool:
        """Bounded put that stays responsive to shutdown."""
        while not self._stop.is_set():
            try:
                q.put(item, timeout=0.05)
                return True
            except queue_mod.Full:
                continue
        return False

    def _get(self, q):
        while not self._stop.is_set():
            try:
                return q.get(timeout=0.05)
            except queue_mod.Empty:
                continue
        return None

    def _dispatch_loop(self):
        eng = self.engine
        while not self._stop.is_set():
            entry = None
            with self._lock, (
                    # an idle poll is no dispatch: it opens no span
                    contextlib.nullcontext() if eng.idle else
                    trace.span("pipeline.dispatch", tag="pipeline")):
                eng._admit()
                eng._dispatch(outstanding=self._outstanding)
                eng._chunk_step()  # one chunk behind the decode top-up
                # after the chunk step, under the same lock: a final chunk
                # whose budget its prefill token met is emitted before
                # the facade can see the engine idle
                if eng._prefill_finished:  # budget met at prefill time
                    done = list(eng._prefill_finished)
                    eng._prefill_finished = []
                    self._outputs.extend(done)
                    self.emitted += len(done)
                if eng.pool.window:
                    entry = eng.pool.pop_oldest()
                    eng._backlog_entries.append(entry)
                    self._outstanding += 1
            if entry is not None:
                # NEVER under the lock: a full backlog must block dispatch
                # without blocking the detokenize thread's finalize
                self._put(self._harvest_q, entry)
            else:
                time.sleep(self.poll_s)

    def _harvest_loop(self):
        while not self._stop.is_set():
            entry = self._get(self._harvest_q)
            if entry is None:
                continue
            # the blocking device->host transfer, off every other thread's
            # critical path (no lock: snapshot arrays are read-only here)
            with trace.span("pipeline.harvest", tag="pipeline"):
                toks, _done = jax.device_get((entry.tokens, entry.done))
            t_h = time.perf_counter()
            self._put(self._detok_q, (entry, toks, t_h))

    def _detok_loop(self):
        eng = self.engine
        while not self._stop.is_set():
            item = self._get(self._detok_q)
            if item is None:
                continue
            entry, toks, t_h = item
            with trace.span("pipeline.detokenize", tag="pipeline"), \
                    self._lock:
                # FIFO edges: the entry being finalized is always the
                # oldest backlog entry; drop it BEFORE finalize so the
                # _finished_ids prune is tight
                if eng._backlog_entries and eng._backlog_entries[0] is entry:
                    eng._backlog_entries.popleft()
                dt = max(t_h - eng._t_mark, 0.0)
                eng._t_mark = t_h
                done = eng._finalize_harvest(entry, toks, dt)
                self.steps += 1
                self.busy_slot_steps += sum(
                    1 for r in entry.slots if r is not None
                )
                self._outputs.extend(done)
                self.emitted += len(done)
                self._outstanding -= 1

    # ------------------------------------------------------------------ #
    # step()-compatible facade
    # ------------------------------------------------------------------ #
    def _check(self):
        if self._exc is not None:
            raise RuntimeError(
                f"engine pipeline thread failed:\n{self._exc}"
            )

    def submit(self, req: Request, now: Optional[float] = None):
        self._check()
        with self._lock:
            self.engine.submit(req, now)
            self.submitted += 1
            self.submitted_bytes += req.payload_bytes

    def step(self) -> list[Response]:
        """Drain finished responses (completion order). The pipeline
        threads make the actual progress; this never blocks."""
        self._check()
        with self._lock:
            out = list(self._outputs)
            self._outputs.clear()
        return out

    @property
    def idle(self) -> bool:
        self._check()
        with self._lock:
            eng = self.engine
            return (not eng.queue and eng.pool.all_free
                    and not eng.pool.window and not eng._chunk_jobs
                    and self._outstanding == 0 and not self._outputs)

    def run_until_drained(self, max_steps: int = 10_000) -> list[Response]:
        out = []
        for _ in range(max_steps):
            out.extend(self.step())
            if self.idle:
                break
            time.sleep(self.poll_s)
        return out

    def load_snapshot(self) -> dict:
        """Router-visible load + conservation counters, read atomically
        (what the worker returns on every RPC round-trip)."""
        with self._lock:
            eng = self.engine
            free = len(eng._free_slots())  # chunk-reserved slots are busy
            queued = sum(r.max_new_tokens for r in eng.queue)
            live = sum(
                r.max_new_tokens - len(r.generated)
                for r in eng.pool.slots if r is not None
            )
            chunking = sum(j.req.max_new_tokens for j in eng._chunk_jobs)
            return {
                "queue_depth": len(eng.queue),
                "occupancy": eng.max_batch - free,
                "free_slots": free,
                "outstanding_tokens": queued + live + chunking,
                "steps": self.steps,
                "busy_slot_steps": self.busy_slot_steps,
                "submitted": self.submitted,
                "emitted": self.emitted,
                "submitted_bytes": self.submitted_bytes,
                "idle": (not eng.queue and eng.pool.all_free
                         and not eng.pool.window and not eng._chunk_jobs
                         and self._outstanding == 0 and not self._outputs),
            }

    def metrics_snapshot(self) -> dict:
        """Engine registry snapshot, read atomically."""
        with self._lock:
            return self.engine.metrics_snapshot()

    # passthroughs (Gateway / loadgen / tests reach the engine surface)
    @property
    def queue(self):
        return self.engine.queue

    @property
    def store(self):
        return self.engine.store

    @property
    def _records(self):
        return self.engine._records

    @property
    def max_batch(self):
        return self.engine.max_batch

    @property
    def pool(self):
        return self.engine.pool

    def close(self, timeout: float = 5.0):
        """Stop the pipeline threads (idempotent). In-flight entries are
        abandoned — close after draining if the results matter."""
        self._stop.set()
        for t in self._threads:
            t.join(timeout=timeout)

    def __enter__(self) -> "EnginePipeline":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
