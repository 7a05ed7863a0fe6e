"""Continuous-batching inference engine: one jitted decode step, N slots.

The design inverts ``generate_fast``'s: instead of one compiled program
per request signature (prompt length × new tokens × sampling config —
every new shape recompiles), the engine compiles a FIXED-SHAPE program
set once and runs every request through it:

- **One KV cache, a pool of pages** (PagedAttention, arXiv
  2309.06180): fixed-size pages addressed through a per-slot block table
  (``models/nanogpt.py:_decode_attend_paged`` — off the TPU static
  ``[block_size]`` reductions and masks, the sums ``generate_fast``'s
  own attend makes, which keeps the served token streams bit-identical
  to it; on a TPU with a float32 pool a Pallas kernel that reads only
  each row's live pages in place (``ops/paged_attention.py``), the same
  products summed in another order, held to a logits tolerance instead:
  ``EngineStats.paged_kernel_dispatches`` and the ``path`` id of the
  dispatch spans say which ran). The pool is batch-shape independent: a
  1-row prefill and an S-row decode run against the SAME buffers.
- **Decode step** (compiled once per ``(config, num_slots)``): the whole
  slot batch advances one token. Each slot is an independent sequence at
  its own cursor, reading and writing only the pages of its own block
  table, and the per-slot sampling params (temperature / top-k / top-p /
  PRNG key) ride in as vectors, applied by ``sample_rows``: the shared
  ``sample_logits`` a row, whose full-vocabulary sorts are taken only in
  a step in which a LIVE row filters (``1 < top_k < V`` or ``top_p < 1``;
  one conditional a step for the whole batch, the same tokens either
  way; ``stats.sampler_sorted_steps`` counts those steps).
  Inactive slots compute garbage that is never read, written to the null
  page, and their cursors are frozen, so a free slot can idle forever.
- **Prefill** (compiled once per power-of-two bucket): a single request's
  prompt suffix, right-padded to the bucket length, is written into the
  slot's pages and the first token sampled at the TRUE last prompt
  position (padded positions are causally masked away from real queries
  and overwritten before any later query can attend to them). Total
  prefill compilations are bounded by ``⌈log2(block_size)⌉ + 1`` — the
  bucket count — instead of one per distinct prompt length.
- **Prefix sharing**: a ref-counted ``BlockAllocator`` plus an
  exact-content prefix hash table admit a prompt whose longest
  block-aligned prefix is already resident WITHOUT re-prefilling or
  copying those blocks: prefill processes only the suffix (one
  bucket-padded dispatch), and a fully-matched final block is
  copy-on-written so its last token can be re-forwarded for the
  first-token logits without perturbing other readers. Blocks a request
  may ever write (suffix pads + the whole decode budget) are reserved at
  admit, so shared pages are full, immutable prompt blocks by
  construction and the jitted programs never need to allocate.
- **Admit/evict** ride the device's queue (continuous batching): the
  prefill program itself writes the admitted row into the decode state,
  the decode programs decide EOS, budget and quarantine themselves, and
  the host learns of both one read later — a finished slot's pages go
  back to the allocator while its neighbors keep decoding, no
  drain-the-batch barrier, and no wait before the next step is queued.

- **Two kinds of row beside the run of pages** (``models/serving.py``
  says what each model owes): a row that is ONE block of fixed size (a
  recurrent state in every layer: ``row_cache``; a page is then the whole
  row) and a row that holds one STATE BLOCK beside its run of pages (a
  state in some layers, pages in the others: ``row_state``; the block
  table's last column names the block, so it rides in the decode state
  on the device as the pages do, is cleared with them when a row stops
  and is pinned with them while a row is parked). Such rows share no
  prefix, need no copy-on-write page and refuse speculation; the second
  kind is admitted only when pages AND a state block are to be had.

Parity oracle (tests/test_serve.py): for a single request the engine's
token stream is IDENTICAL to ``generate_fast`` with the same sampling
config and seed off the TPU — both use the shared ``sample_logits``
kernel and the ``fold_in(PRNGKey(seed), token_index)`` key schedule, and
the gather path of the paged attend reduces exactly like
``generate_fast``'s.

**Speculative decoding** (``spec_tokens=γ``; arXiv 2302.01318), fused
into the ``decode_chunk`` scan: draft γ tokens per slot by on-device
n-gram lookup over the slot's token history, verify them in ONE batched
``γ+1``-token model call, vectorized per-slot accept/reject with a
cursor-rewind rollback (rejected K/V sit past the cursor in slot-owned
blocks, masked until overwritten). Every position is sampled from the
true conditional with the request's own key schedule, so the emitted
stream equals the non-speculative engine's EXACTLY for every sampling
configuration — drafts only decide how many samples one dispatch keeps.

**What crosses between host and device** (ISSUE 28, ISSUE 30). The
decode programs' per-slot state (input token, active flag, cursor, key
index, remaining budget, key, EOS id, temperature, top-k, top-p, block
table; speculative: the token history) lives ON THE DEVICE and threads
through every dispatch: a prefill returns it with the admitted row set,
a decode step with what it advanced, and the next dispatch takes it as
it is (``programs/serve_defs.py``: ``PAGED_STATE`` / ``SPEC_STATE``).
The engine's NumPy arrays are the host's MIRROR of it, brought up to a
step by replaying that step's small download.

**A decode step always in flight.** ``step(ahead=True)`` (the
scheduler's call) dispatches step K from the device's own state BEFORE it reads step
K-1's download, so the mirrors, the events, the free slots and the
allocator are those of step K-1 while the device runs step K. A row
that stopped at K-1 is inactive in the device's state already, so K
computes nothing for it that is read; its slot is refilled one step
later. A prefill's first token stays a device array and comes down in
the same ``jax.device_get`` as the next read: nothing on the round's
path waits for the device before the device has its next step.
``admit`` and a plain ``step()`` are the same calls with a wait on top
(``admit_nowait`` / ``step(ahead=True)``, then ``_settle``), for
callers that want a step's tokens from the call that made them.

A write by the HOST to a row the device may be computing (``release``
of an active slot, ``park``, ``resume``, a forced token, ``last_logits``)
first waits out what is in flight (``_settle``: its events are kept for
the next ``step`` / ``drain``, and their slots are not
handed out before), which brings the mirrors level with the device;
then it names the mirror stale, and the next dispatch, prefill or
decode, is handed the stale mirrors themselves, as NumPy arguments.
``stats.drains`` counts those waits, ``stats.steps_ahead`` the decode
steps dispatched ahead of the read before them, ``stats.upload_arrays``
the mirrors that went up. The round's path calls no eager device
operation (no ``jnp.asarray``, no ``PRNGKey``: the base key is made on
the host), a step in steady state has no host argument at all, and a
read is tokens and flags, a few KiB: the logits stay on the device
until something asks for ``last_logits``.
"""

from __future__ import annotations

import dataclasses
import heapq
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.serving import attend_path_id
from ..ops.paged_attention import GATHER
from ..programs import default_registry
from ..programs.serve_defs import (PAGED_STATE, SPEC_STATE, cow_def,
                                   paged_decode_def, paged_prefill_def,
                                   row_cow_def, row_state, spec_decode_def)
from ..utils.resilience import fault_point
from ..utils.trace import span

PyTree = Any


_KV_BYTES = {"int8": 1, "bf16": 2, "f32": 4}

# the engine's attribute that mirrors each entry of a decode program's
# state (``programs/serve_defs.py``); ``remaining`` and ``eos`` are made
# from theirs (``InferenceEngine._mirror``)
_MIRROR = {"tok": "_next_tok", "active": "_active", "pos": "_pos",
           "gen_idx": "_gen_idx", "base_keys": "_base_keys",
           "temp": "_temp", "top_k": "_top_k", "top_p": "_top_p",
           "bt": "_bt", "hist": "_hist"}


def derive_base_key(seed: int) -> np.ndarray:
    """The two words of ``jax.random.PRNGKey(seed)``, made on the host:
    under threefry the seed's high and low 32 bits (the high word is 0
    without 64-bit mode, where JAX keeps the seed's low 32 bits only).
    ``PRNGKey`` itself is a dispatch and a read-back; any other key
    implementation, and a seed offset, keep it."""
    if (jax.config.jax_default_prng_impl != "threefry2x32"
            or jax.config.jax_random_seed_offset):
        return np.asarray(jax.random.PRNGKey(seed), np.uint32)
    bits = int(np.int64(seed)) & (2 ** 64 - 1)   # OverflowError as JAX's
    high = (bits >> 32) if jax.config.jax_enable_x64 else 0
    return np.array([high, bits & 0xFFFFFFFF], np.uint32)


class NoFreeSlotError(RuntimeError):
    """``admit()`` was called with every slot occupied — a scheduler bug
    (the driver must check ``free_slots()`` first). Subclasses
    ``RuntimeError`` so pre-existing callers keep working."""


class UnsettledWriteError(RuntimeError):
    """A mirror of the decode state was about to go up while a dispatch
    was still unread — an engine bug: the mirrors are level with the
    device only once everything in flight was read, and every host write
    to a live row settles first."""


class NoFreeBlocksError(RuntimeError):
    """The paged KV pool cannot currently supply enough blocks for this
    admission. Unlike ``NoFreeSlotError`` this is an EXPECTED transient
    under load (an undersized pool serving long requests): the scheduler
    keeps the request queued and retries once running requests release
    their blocks. ``InferenceEngine.validate`` rejects up front any
    request whose worst-case block need exceeds the whole pool, so a
    queued request always eventually fits."""


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling configuration — mirrors ``generate_fast``'s
    signature so a request and a ``generate_fast`` call are comparable.
    ``eos_token`` stops the request early (in addition to
    ``max_new_tokens``); ``None`` disables the check."""

    max_new_tokens: int = 32
    temperature: float = 1.0
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    eos_token: Optional[int] = None
    seed: int = 0


@dataclasses.dataclass
class ParkedSlot:
    """Host-side snapshot of one preempted slot.
    The block-table REFERENCES move into the snapshot — pages (and a
    ``row_state`` model's state block, the table's last entry) stay
    pinned in the pool at their current refcounts, exactly like the
    slot-owned write blocks the spec-decode rewind masks — so a later
    ``resume`` continues the generation byte-identical to an
    uncontended run: everything a decode dispatch reads about a slot
    (block table, cursors, token history, sampling vectors, the
    ``fold_in(base, gen_idx)`` key schedule) has a mirror on the host,
    which ``resume`` writes and the next dispatch uploads.
    ``released`` marks a consumed snapshot (resumed or dropped)."""

    block_table: np.ndarray
    pos: int
    hist: np.ndarray
    prompt_len: int
    next_tok: int
    gen_idx: int
    generated: int
    max_new: int
    eos: int
    temp: float
    top_k: int
    top_p: float
    base_key: np.ndarray
    released: bool = False


@dataclasses.dataclass
class TokenEvent:
    """One generated token, as seen by the scheduler. ``poisoned`` marks
    a token from a quarantined slot (non-finite logits): the value is
    garbage and the scheduler must fail the request, not deliver it."""

    slot: int
    token: int
    finished: bool
    poisoned: bool = False


@dataclasses.dataclass
class _Flight:
    """A decode dispatch whose ``read`` the host has not taken yet."""

    read: Any                  # the program's small download, on the device
    spec_run: bool             # the speculative program's layout
    ahead: bool                # dispatched before the step before was read


@dataclasses.dataclass
class EngineStats:
    tokens_generated: int = 0
    decode_steps: int = 0
    prefills: int = 0
    prefill_compiles: int = 0            # new bucket programs THIS engine hit
    prefill_buckets: Tuple[int, ...] = ()
    prefill_tokens: int = 0              # padded tokens dispatched through
    #                                      prefill — the prefix-sharing
    #                                      work-elision observable
    prefill_tokens_run: int = 0          # of them, the positions the
    #                                      prefills' passes ran: a model
    #                                      that takes a bucket in passes
    #                                      skips those that hold only its
    #                                      padding (``prefill_positions_run``)
    active_slots: int = 0
    num_slots: int = 0
    readback_bytes: int = 0              # cumulative bytes decode steps read
    #                                      back from the device (the logits
    #                                      only when something asked for
    #                                      them: ``last_logits``)
    upload_arrays: int = 0               # host arrays of the decode state
    #                                      handed to dispatches (stale
    #                                      mirrors: only a host write to a
    #                                      live row makes one)
    resident_steps: int = 0              # decode dispatches that uploaded
    #                                      none: all state was on the device
    steps_ahead: int = 0                 # of ``decode_steps``, those
    #                                      dispatched while the step before
    #                                      had not yet been read
    sampler_sorted_steps: int = 0        # of ``decode_steps``, those whose
    #                                      sampler sorted the vocabulary: a
    #                                      live row had ``1 < top_k < V``
    #                                      or ``top_p < 1``
    drains: int = 0                      # times a host write or an idle
    #                                      round waited out what was in
    #                                      flight
    paged_kernel_dispatches: int = 0     # decode + prefill dispatches whose
    #                                      attend ran the Pallas page walk
    #                                      (ops/paged_attention.py); 0 on
    #                                      the gather path
    quarantined: int = 0                 # slots shut down on NaN/Inf logits
    # page-pool observables
    kv_blocks_in_use: int = 0            # pages referenced by live slots
    kv_blocks_cached: int = 0            # resident reusable prefix blocks
    kv_evictions: int = 0                # cumulative pages evicted from the
    #                                      prefix cache: a full pool's
    #                                      admissions take their pages there
    kv_evict_visits: int = 0             # cumulative entries of the recency
    #                                      heap those evictions looked at
    #                                      (a few a page, never the cache)
    state_blocks: int = 0                # state blocks of a model whose rows
    #                                      hold one beside their pages (the
    #                                      null one too; 0: no such model)
    state_blocks_in_use: int = 0         # of them, held by live and parked
    #                                      rows
    prefix_hit_blocks: int = 0           # cumulative blocks served from the
    #                                      prefix cache instead of prefilled
    # speculative-decoding counters (0 with speculation off)
    spec_drafted: int = 0
    spec_accepted: int = 0
    # preemptible-decode counters (ISSUE 17): slots parked for a more
    # urgent request / parked snapshots resumed into a slot
    preemptions: int = 0
    resumes: int = 0
    # quantized-serving config echo (ISSUE 11): which dtypes this
    # engine's params and KV pools are stored in — ride on stats so
    # metrics/serve.csv/stats report them without reaching into config
    weights_dtype: str = "f32"
    kv_dtype: str = "f32"
    # what the model counted in its decode steps (and its config of a
    # dispatched prefill: ``prefill_counted``), summed since the engine
    # was built: {"<layer>/<module>/<name>": integers}; empty for a
    # model that counts nothing (models/serving.py: ``counters``)
    model_counters: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def spec_accept_rate(self) -> Optional[float]:
        """Accepted / drafted speculative tokens (None before the first
        draft) — the EWMA-priceable acceptance observable."""
        if not self.spec_drafted:
            return None
        return self.spec_accepted / self.spec_drafted


def prompt_bucket(n: int, block_size: int) -> int:
    """Power-of-two prefill bucket for an ``n``-token prompt, capped at
    ``block_size`` — the compile-bound lever: all prompt lengths map to at
    most ``⌈log2(block_size)⌉ + 1`` distinct shapes."""
    if n < 1:
        raise ValueError("empty prompt")
    b = 1 << (n - 1).bit_length()
    return min(b, block_size)


def prefill_positions_run(config: Any, bucket: int, suffix: int) -> int:
    """Positions a dispatched prefill of ``suffix`` tokens padded to
    ``bucket`` runs on the device: the bucket, unless the model takes a
    bucket in passes through all its layers and skips the passes that
    hold only padding. ``config.prefill_pass(bucket)`` says how long a
    pass is (``models/serving.py``); a config that does not answer runs,
    or is counted as running, its whole bucket."""
    ask = getattr(config, "prefill_pass", None)
    if ask is None:
        return bucket
    step = ask(bucket)
    return min(bucket, -(-suffix // step) * step)


def row_cache(config: Any) -> bool:
    """Whether this model's per-row cache is ONE block of fixed size (a
    recurrent state) and not a run of pages that grows with the row
    (``models/serving.py``: ``fixed_row_cache``)."""
    return bool(getattr(config, "fixed_row_cache", False))


def fit_page_size(asked: int, block_size: int) -> int:
    """The page size a server gives a checkpoint: ``asked`` where it
    divides the checkpoint's ``block_size``, else the largest divisor of
    ``block_size`` not above it (a page may not straddle the window's
    end, and a checkpoint is not refused over it). Below 1 there is
    nothing to fit: the page pool is the only KV cache."""
    if asked < 1:
        raise ValueError(
            f"page_size must be >= 1, got {asked}: the page pool is the "
            f"engine's only KV cache (page_size 0 used to select the "
            f"unpaged slot ring, which is gone)")
    return max(d for d in range(1, min(asked, block_size) + 1)
               if block_size % d == 0)


def fit_pool(page_size: int, block_size: int,
             kv_pages: Optional[int] = None,
             config: Any = None) -> Tuple[int, Optional[int]]:
    """``(page_size, kv_pages)`` a server builds its engine with. A pool
    size that was given counted pages of the size asked for: where the
    page is fitted down it is scaled up, so that the pool holds the
    tokens that were asked for (``None`` stays: the engine sizes it).
    For a ``config`` whose cache is one block a row (``row_cache``) the
    page is the whole row and ``kv_pages`` counts blocks, not positions:
    it stays as given."""
    if row_cache(config):
        return int(block_size), kv_pages
    fitted = fit_page_size(page_size, block_size)
    if kv_pages is not None:
        kv_pages = -(-kv_pages * page_size // fitted)
    return fitted, kv_pages


def max_prefill_buckets(block_size: int) -> int:
    """The compile-count bound serving any mix of prompt lengths:
    buckets are {1, 2, 4, ..., 2^⌈log2(block_size)⌉ capped} — at most
    ``⌈log2(block_size)⌉ + 1`` of them."""
    return (block_size - 1).bit_length() + 1


class BlockAllocator:
    """Host-side ref-counted page allocator + prefix hash table for the
    paged KV pool (PagedAttention, arXiv 2309.06180).

    Page ids index the device pools (``[kv_pages, page_size, n_embd]``
    per layer); page 0 is the reserved NULL page — never allocated,
    the write-redirect target for deactivated rows. A page's refcount
    counts ACTIVE slot users; pages holding full, block-aligned PROMPT
    blocks are additionally content-registered in the prefix cache under
    an exact chain key ``(parent_chain_id, block_token_bytes)``. The
    parent id is a monotonically increasing content id — never a page
    id — so a recycled page can never falsely revalidate a stale child
    entry. A cached page at refcount 0 stays RESIDENT (that is the
    point: the next request with the same prefix reuses it copy-free)
    and is evicted LRU only when the free list runs dry.

    **Recency** is the cache's, not the users': ``register`` stamps an
    entry, ``lookup`` and ``touch`` stamp it anew, a release does not.
    The victim is the refcount-0 cached page with the oldest stamp.
    What every call pays is its own pages, never the pool's
    (ISSUE 40): the evictable pages stand in a heap of ``(stamp,
    page)``, pushed when a cached page falls to refcount 0 or is
    stamped anew there; an entry whose page was pinned, stamped anew,
    condemned or evicted since is dropped when it surfaces. Supply and
    use are counters.
    """

    def __init__(self, num_pages: int, page_size: int):
        if num_pages < 2:
            raise ValueError(
                f"kv_pages must be >= 2 (null page + one real page), "
                f"got {num_pages}")
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self._free = list(range(num_pages - 1, 0, -1))   # pop() → low ids
        self._ref: Dict[int, int] = {}
        # chain key → (page, content id)
        self._cache: Dict[Tuple[int, bytes], Tuple[int, int]] = {}
        self._key_of: Dict[int, Tuple[int, bytes]] = {}
        self._cid = 0
        self._stamp: Dict[int, int] = {}     # cached page → recency
        self._clock = 0
        self._heap: List[Tuple[int, int]] = []
        self._evictable = 0                  # cached pages at refcount 0
        self.evictions = 0                   # pages evicted from the cache
        self.evict_visits = 0                # heap entries those looked at

    # -- observables ------------------------------------------------------

    def in_use(self) -> int:
        # ``_ref`` holds the pages in use and, at 0, the evictable ones
        return len(self._ref) - self._evictable

    def cached(self) -> int:
        return len(self._cache)

    def available(self, exclude=()) -> int:
        """Pages an ``alloc`` burst could obtain right now: the free list
        plus evictable (refcount-0 cached) pages. ``exclude`` treats the
        given pages as unavailable — a planned admission must not count
        the very prefix blocks it is about to pin as evictable slack."""
        n = len(self._free) + self._evictable
        for pg in set(exclude):
            if pg in self._key_of and not self._ref.get(pg):
                n -= 1
        return n

    # -- allocation -------------------------------------------------------

    def alloc(self) -> int:
        """Allocate a page at refcount 1, evicting the LRU refcount-0
        cached page when the free list is empty."""
        return self.alloc_many(1)[0]

    def alloc_many(self, n: int) -> List[int]:
        """``n`` pages at refcount 1, the pages ``n`` calls of ``alloc``
        would give in their order; all of them or, where the pool cannot
        supply them, none and ``NoFreeBlocksError``."""
        if n > len(self._free) + self._evictable:
            raise NoFreeBlocksError(
                f"paged KV pool cannot supply {n} blocks right now — "
                f"retry after running requests release")
        cut = max(len(self._free) - n, 0)
        pages = self._free[cut:][::-1]       # pop() by pop()
        del self._free[cut:]
        pages.extend(self._evict_one() for _ in range(n - len(pages)))
        self._ref.update(dict.fromkeys(pages, 1))
        return pages

    def _evict_one(self) -> int:
        heap, stamp, ref = self._heap, self._stamp, self._ref
        while heap:
            st, pg = heapq.heappop(heap)
            self.evict_visits += 1
            if stamp.get(pg) != st or ref.get(pg):
                continue                     # stamped anew, pinned or gone
            del self._cache[self._key_of.pop(pg)]
            del stamp[pg]
            ref.pop(pg, None)
            self._evictable -= 1
            self.evictions += 1
            return pg
        raise NoFreeBlocksError(
            f"paged KV pool exhausted: all {self.num_pages - 1} pages "
            f"are referenced by running requests")

    def _queue(self, page: int) -> None:
        """``page`` became evictable, or was stamped anew while it was:
        it goes on the heap under its stamp. The heap is made anew from
        the evictable pages when stale entries outnumber the cache (a
        pool that never evicts would keep one for every release of a
        shared page)."""
        if len(self._heap) > 2 * len(self._key_of) + 64:
            self._heap = [(st, pg) for pg, st in self._stamp.items()
                          if not self._ref.get(pg)]
            heapq.heapify(self._heap)
        else:
            heapq.heappush(self._heap, (self._stamp[page], page))

    def incref(self, page: int) -> None:
        r = self._ref.get(page, 0)
        self._ref[page] = r + 1
        if r == 0 and page in self._key_of:
            self._evictable -= 1

    def incref_many(self, pages) -> None:
        for pg in pages:
            self.incref(pg)

    def decref(self, page: int) -> None:
        r = self._ref.get(page, 0) - 1
        if r < 0:
            raise ValueError(f"page {page} double-freed")
        self._ref[page] = r
        if r:
            return
        if page in self._key_of:
            # cached pages stay resident (evictable) for future prefix
            # hits
            self._evictable += 1
            self._queue(page)
        else:
            # plain owned page → straight back to the free list
            del self._ref[page]
            self._free.append(page)

    def decref_many(self, pages) -> None:
        for pg in pages:
            self.decref(pg)

    def condemn(self, page: int) -> bool:
        """``page`` belongs to a quarantined row, so its content is no
        longer trusted. False while another user still reads it (the
        last of them condemns it); else it leaves the prefix cache, so
        the decref that follows frees it, and True tells the engine to
        write it over."""
        if self._ref.get(page, 0) != 1:
            return False
        key = self._key_of.pop(page, None)
        if key is not None:
            del self._cache[key]
            del self._stamp[page]
        return True

    # -- prefix cache -----------------------------------------------------

    def _restamp(self, page: int) -> None:
        self._clock += 1
        self._stamp[page] = self._clock
        if not self._ref.get(page):
            self._queue(page)

    def lookup(self, parent_cid: int, block: bytes):
        """Resident ``(page, cid)`` for this chain link, or None. A hit
        refreshes the entry's LRU recency."""
        ent = self._cache.get((parent_cid, block))
        if ent is not None:
            self._restamp(ent[0])
        return ent

    def touch(self, page: int) -> None:
        """Refresh a cached page's LRU recency by page id — admission
        commits touch their hit pages so a hot prefix is not the
        eviction victim just because planning probes never counted."""
        if page in self._key_of:
            self._restamp(page)

    def probe(self, parent_cid: int, block: bytes):
        """``lookup`` without the LRU touch — for capacity planning and
        scheduler ordering probes that may never admit."""
        return self._cache.get((parent_cid, block))

    def register(self, parent_cid: int, block: bytes, page: int) -> int:
        """Content-register an owned full prompt block; returns the chain
        id for the NEXT block's parent. If the key is already cached the
        existing entry wins (its cid is returned and our page stays a
        plain owned page) — chains dedupe onto the canonical lineage."""
        key = (parent_cid, block)
        ent = self._cache.get(key)
        if ent is not None:
            return ent[1]
        self._cid += 1
        self._cache[key] = (page, self._cid)
        self._key_of[page] = key
        self._restamp(page)          # owned: evictable at its release
        return self._cid


class InferenceEngine:
    """Slot-level mechanics: caches, prefill, the shared decode step.

    Request-level concerns (queueing, backpressure, completion futures)
    live in ``scheduler.Scheduler``; the engine only knows slots. Not
    thread-safe — one driver thread calls ``admit_nowait`` /
    ``step(ahead=True)`` / ``release`` (the scheduler serializes
    access); ``admit`` and ``step()`` are those with a wait, for direct
    callers.
    """

    def __init__(self, params: PyTree, config: Any,
                 num_slots: int = 8, decode_chunk: int = 1,
                 paged: bool = True, page_size: int = 16,
                 kv_pages: Optional[int] = None, spec_tokens: int = 0,
                 weights_tag: Optional[str] = None,
                 state_blocks: Optional[int] = None):
        """``decode_chunk``: decode steps fused into one dispatch (a
        device-side scan with on-device EOS/max-token bookkeeping).
        1 = purest continuous batching — admission/eviction can happen
        after every token. Larger chunks amortize per-dispatch overhead
        (the lever that beats ``generate_fast``'s whole-request scan on
        throughput) at the cost of slot-turnaround latency: a slot
        finishing mid-chunk frees only at the chunk boundary.

        The KV cache is ``kv_pages`` pages of ``page_size`` tokens
        (default: 1 null page + ``num_slots`` full windows + 1
        copy-on-write page). It is the only cache: ``paged`` is accepted
        for callers that still name it, and ``False`` is refused.
        ``spec_tokens=γ > 0`` drafts γ tokens a decode iteration and
        verifies them in one model call (the module docstring has both).
        ``state_blocks``: for a model whose rows hold one state block
        beside their pages (``row_state``), how many there are (default:
        the config's, else the null block + one a slot + one spare for a
        parked row).

        ``weights_tag`` names the parameter set this engine serves (e.g.
        ``"step-120"``) — pure observability for the fleet router's
        zero-downtime weight hot-swap: after a rolling reload, ``/stats``
        proves which checkpoint each replica is generating from."""
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        if decode_chunk < 1:
            raise ValueError(
                f"decode_chunk must be >= 1, got {decode_chunk}")
        if spec_tokens < 0:
            raise ValueError(
                f"spec_tokens must be >= 0, got {spec_tokens}")
        if not paged:
            raise ValueError(
                "paged=False: the unpaged slot ring is gone, the page "
                "pool is the engine's only KV cache (drop the argument)")
        self.spec_tokens = int(spec_tokens)
        self.weights_tag = weights_tag
        self.weights_dtype = str(getattr(config, "weights_dtype", "f32"))
        self.kv_dtype = str(getattr(config, "kv_dtype", "f32"))
        # the dtypes any model may serve in; each model refuses those it
        # cannot (its module raises when the programs are traced)
        if self.weights_dtype not in ("f32", "bf16", "int8", "int4"):
            raise ValueError(
                f"weights_dtype must be 'f32', 'bf16', 'int8' or 'int4', "
                f"got {self.weights_dtype!r}")
        if self.kv_dtype not in _KV_BYTES:
            raise ValueError(
                f"kv_dtype must be 'f32', 'bf16' or 'int8', got "
                f"{self.kv_dtype!r}")
        base_cfg = config.decode_config()
        self.block_size = int(config.block_size)
        self.num_slots = int(num_slots)
        self.decode_chunk = int(decode_chunk)
        # a model whose cache is one block of state a row: a page is the
        # whole row (one table entry a row, ``kv_pages`` counts blocks),
        # nothing of it can be shared, copied on write or rewound
        self.row_cache = row_cache(config)
        # a model whose rows hold ONE state block beside their pages: the
        # block table's last column names it, both are planned, parked,
        # freed and scrubbed as one, and as with ``row_cache`` nothing of
        # such a row can be shared, copied on write or rewound
        self.row_state = row_state(config)
        if self.row_cache:
            page_size = self.block_size
        # either kind of row with state shares no prefix, needs no
        # copy-on-write page and cannot be rewound
        self._stateful = self.row_cache or self.row_state
        if self._stateful and self.spec_tokens:
            raise ValueError(
                "spec_tokens > 0 with a model whose cache is a "
                "recurrent state: rejected drafts cannot be rewound "
                "out of a state without a copy of it")
        # pages an admission may need beyond its own: the copy-on-write
        # of a shared last block
        self._cow_room = 0 if self._stateful else 1
        if page_size < 1 or self.block_size % page_size:
            raise ValueError(
                f"page_size must be >= 1 and divide block_size "
                f"{self.block_size}, got {page_size}")
        self.page_size = int(page_size)
        self.max_blocks = self.block_size // self.page_size
        if kv_pages is None:
            # null page + one full window per slot + one page of
            # copy-on-write headroom (also satisfies the 1-slot
            # minimum below; with one block a row, the spare is a parked
            # row's)
            kv_pages = 2 + self.num_slots * self.max_blocks
        if kv_pages < 1 + self.max_blocks + self._cow_room:
            raise ValueError(
                f"kv_pages={kv_pages} too small: need the null page "
                f"+ one full window ({self.max_blocks} blocks)"
                + (" + one copy-on-write page" if self._cow_room else ""))
        self.kv_pages = int(kv_pages)
        self.config = dataclasses.replace(
            base_cfg, page_size=self.page_size, kv_pages=self.kv_pages)
        self._alloc = BlockAllocator(self.kv_pages, self.page_size)
        self.state_blocks = 0
        self._state_free: List[int] = []
        if self.row_state:
            n = int(state_blocks or getattr(config, "state_blocks", 0)
                    or 2 + self.num_slots)
            if n < 2:
                raise ValueError(
                    f"state_blocks={n} too small: need the null block + "
                    f"one row's")
            self.state_blocks = n
            self._state_free = list(range(n - 1, 0, -1))     # pop() → low
            self.config = dataclasses.replace(self.config, state_blocks=n)
        # the model's own dispatch point, asked with what its layers
        # will ask: the id on the dispatch spans and what /stats counts
        self.attend_path = attend_path_id(self.config)
        self._kernel_attend = GATHER not in self.attend_path
        self.params = jax.tree.map(jnp.asarray,
                                   self.config.prepare_params(params))
        self.weights_bytes = int(sum(x.nbytes
                                     for x in jax.tree.leaves(self.params)))
        self._cfg_tuple = self.config.program_key()
        # every program comes from the process-wide device-program
        # registry (gym_tpu.programs): engines over the same config —
        # replicas, supervisor rebuilds, hot-swapped generations —
        # share ONE compiled executable per key, and the entries this
        # engine holds are pinned against capacity eviction for its
        # lifetime (released via weakref when the engine is collected)
        self._registry = default_registry()
        decode_def = paged_decode_def(self._cfg_tuple, self.num_slots,
                                      self.decode_chunk)
        self._decode_prog = self._acquire(decode_def)
        # the page copy; under ``row_state`` over the page leaves alone,
        # with a twin over the state blocks (the scrub's)
        self._cow_prog, self._state_cow_prog = (
            d and self._acquire(d) for d in self._cow_defs())
        self._spec_prog = (
            self._acquire(spec_decode_def(
                self._cfg_tuple, self.num_slots, self.decode_chunk,
                self.spec_tokens))
            if self.spec_tokens else None)
        self._step1_prog = None          # lazy chunk-1 twin (teacher forcing)
        self._prefill_progs: Dict[int, Any] = {}   # bucket → handle
        self._seen_buckets: set = set()
        # the pool as the programs take it. It is batch-shape independent
        # ([kv_pages, page, n_embd] per layer): a 1-row prefill and an
        # S-row decode run against the SAME buffers — that is what makes
        # the prefix blocks shareable without an admit-scatter program
        self._cache = jax.tree.map(
            lambda sh: jnp.zeros(sh.shape, sh.dtype), decode_def.args[1])
        s = self.num_slots
        # a row's pages and, under ``row_state``, its state block last
        self._bt = np.zeros((s, self.max_blocks + self.row_state), np.int32)
        self._pos = np.zeros(s, np.int32)          # per-slot KV cursor
        self._hist = np.zeros((s, self.block_size), np.int32)
        self._prompt_len = np.zeros(s, np.int32)
        self._active = np.zeros(s, bool)
        self._next_tok = np.zeros(s, np.int32)     # input token per slot
        self._gen_idx = np.zeros(s, np.int32)      # key-schedule index
        self._generated = np.zeros(s, np.int64)    # tokens emitted so far
        self._max_new = np.zeros(s, np.int64)
        self._eos = np.full(s, -1, np.int64)       # -1 = disabled
        self._temp = np.ones(s, np.float32)
        self._top_k = np.full(s, self.config.vocab_size, np.int32)
        self._top_p = np.ones(s, np.float32)
        self._base_keys = np.zeros((s, 2), np.uint32)
        # The arrays above are the host's MIRROR of the decode programs'
        # per-slot state (events, park, release and the scheduler read
        # them), as of the last read. The state itself lives on the
        # device: ``_dev`` is the dict the last dispatch (prefill or
        # decode) returned and the next one's argument as it is. A host
        # write to a live row settles first, then names its mirror in
        # ``_stale``, and the next dispatch is handed that mirror, a
        # NumPy array, in the device copy's place.
        self._state_names = SPEC_STATE if self.spec_tokens else PAGED_STATE
        self._dev: Dict[str, Any] = {
            name: jnp.asarray(self._mirror(name))
            for name in self._state_names}
        self._stale: set = set()
        self._flight: Optional[_Flight] = None   # the unread decode step
        # prefills whose first token is still on the device, in dispatch
        # order: (slot, token array); all of them younger than ``_flight``
        self._firsts: List[Tuple[int, Any]] = []
        self._held: List[TokenEvent] = []    # read, not yet handed out
        self._logits: Any = None         # [S, V] post-step, on the device
        self._logits_host: Optional[np.ndarray] = None
        self.stats = EngineStats(num_slots=s,
                                 weights_dtype=self.weights_dtype,
                                 kv_dtype=self.kv_dtype,
                                 state_blocks=self.state_blocks)

    @property
    def last_logits(self) -> Optional[np.ndarray]:
        """The last decode step's logits ``[S, V]`` (None before the
        first). They stay on the device: nothing on the serving path
        reads them. The first access after a step transfers them and
        adds their bytes to ``stats.readback_bytes``."""
        self._settle()
        if self._logits_host is None and self._logits is not None:
            self._logits_host = np.asarray(self._logits)
            self.stats.readback_bytes += self._logits_host.nbytes
        return self._logits_host

    # -- quantized-serving observables ------------------------------------

    @property
    def kv_elem_bytes(self) -> int:
        """Bytes per stored KV element (1 under int8, 2 under bf16, 4
        under f32)."""
        return _KV_BYTES[self.kv_dtype]

    @property
    def kv_blocks_capacity_effective(self) -> int:
        """Usable block capacity normalized to the f32 payload budget:
        an int8 pool stores 4 KV elements in every f32 element's bytes,
        so the byte budget an f32 ``kv_pages`` pool's PAYLOAD occupies
        holds ``4 x (kv_pages - 1)`` usable int8 blocks. The per-(page
        slot, head) scale sidecar (4/hd of the int8 payload — 6.25% at
        head dim 64) is NOT hidden inside this number: it is reported
        separately by ``kv_pool_bytes``. Equals the plain usable-block
        count on an f32 engine."""
        return (self.kv_pages - 1) * (4 // self.kv_elem_bytes)

    def kv_pool_bytes(self) -> Dict[str, int]:
        """Actual device bytes of the KV cache, split into the K/V
        payload and the quantization-scale sidecar (0 at f32) — the
        honest-accounting observable behind the 4x capacity claim. A
        ``row_state`` model's state blocks are counted apart, under
        ``state``."""
        out = {"payload": 0, "scales": 0}
        state_names = ()
        if self.row_state:
            out["state"] = 0
            state_names = set(self.config.row_state_names())

        def walk(node, state):
            if hasattr(node, "items"):
                for name, sub in node.items():
                    if hasattr(sub, "items"):
                        walk(sub, state or name in state_names)
                    elif state:
                        out["state"] += int(sub.nbytes)
                    elif name.endswith("_scale"):
                        out["scales"] += int(sub.nbytes)
                    else:       # keys, values, whatever else a layer keeps
                        out["payload"] += int(sub.nbytes)

        walk(self._cache, False)
        return out

    # -- device programs (registry-backed) --------------------------------

    def _acquire(self, pdef):
        return self._registry.acquire(pdef, pin_owner=self)

    def _cow_defs(self):
        """``(page copy, state-block copy or None)``."""
        if not self.row_state:
            return cow_def(self._cfg_tuple), None
        return (row_cow_def(self._cfg_tuple, False),
                row_cow_def(self._cfg_tuple, True))

    def _prefill_prog(self, bucket: int):
        """Registry handle for this bucket's prefill program, ensured
        built; bumps ``stats.prefill_compiles`` when the acquisition
        actually built a new program (the bounded-compilation
        observable — a program another engine over the same config
        already built is a hit, not a compile)."""
        h = self._prefill_progs.get(bucket)
        if h is None:
            h = self._acquire(paged_prefill_def(
                self._cfg_tuple, bucket, self.num_slots,
                bool(self.spec_tokens)))
            self._prefill_progs[bucket] = h
        # exact per-key attribution: ensure_reporting is True only if
        # THIS call ran the build — a global-counter diff would charge
        # concurrent warmup/sibling-replica builds to this request
        if h.ensure_reporting():
            self.stats.prefill_compiles += 1
        return h

    def warmup_defs(self) -> List[Any]:
        """This engine's COMPLETE program family — what the background
        warmup precompiles so no request ever pays a compile: the full
        power-of-two prefill-bucket family plus the decode, CoW and
        speculative programs, traffic-critical first."""
        buckets: List[int] = []
        b = 1
        while b < self.block_size:
            buckets.append(b)
            b <<= 1
        buckets.append(self.block_size)
        cfg, s, chunk = self._cfg_tuple, self.num_slots, self.decode_chunk
        defs = [paged_decode_def(cfg, s, chunk)]
        if self.spec_tokens:
            defs.append(spec_decode_def(cfg, s, chunk, self.spec_tokens))
        defs.extend(d for d in self._cow_defs() if d is not None)
        if chunk != 1 or self.spec_tokens:
            # the lazy chunk-1 twin (teacher forcing / eval harnesses)
            # is part of the family too — without it a warmed or
            # disk-restored process pays its compile on the first
            # override_tokens step
            defs.append(paged_decode_def(cfg, s, 1))
        defs.extend(paged_prefill_def(cfg, b, s, bool(self.spec_tokens))
                    for b in buckets)
        return defs

    def _count(self, counted: PyTree) -> None:
        """Add what the model counted in this dispatch (already on the
        host) to ``stats.model_counters``."""
        totals = self.stats.model_counters
        for path, leaf in jax.tree_util.tree_flatten_with_path(counted)[0]:
            name = "/".join(str(getattr(k, "key", k)) for k in path)
            totals[name] = totals.get(name, 0) + leaf.astype(np.int64)

    def _mirror(self, name: str) -> np.ndarray:
        """The host's copy of one entry of the decode state, in the
        dtype the programs take."""
        if name == "remaining":
            return (self._max_new - self._generated).astype(np.int32)
        if name == "eos":
            return self._eos.astype(np.int32)
        return getattr(self, _MIRROR[name])

    # -- slot lifecycle ---------------------------------------------------

    def free_slots(self) -> List[int]:
        """Slots an admission may take: inactive as of the last read,
        and with no event still waiting to be handed out (whoever maps
        slots to requests must see a slot's last token before its next
        occupant)."""
        held = {ev.slot for ev in self._held}
        return [i for i in range(self.num_slots)
                if not self._active[i] and i not in held]

    # -- what is in flight ------------------------------------------------

    def _state_args(self, names) -> Tuple[Dict[str, Any], int]:
        """The decode state as the next dispatch takes it: the device's
        own arrays, and in their place the mirrors the host wrote since
        (or an entry the last dispatch did not return: ``hist`` after a
        step of the plain program, which the host replays tokens into
        whatever ran). Returns it with the number of mirrors in it."""
        up = [n for n in names if n in self._stale or n not in self._dev]
        if up and (self._flight is not None or self._firsts):
            # a mirror is level with the device only once all was read:
            # every writer of one settles first
            raise UnsettledWriteError(
                f"decode-state mirrors {up} would go up while a dispatch "
                f"is unread (a host write did not settle)")
        state = {n: self._dev[n] for n in names if n not in up}
        state.update((n, self._mirror(n)) for n in up)
        self.stats.upload_arrays += len(up)
        return state, len(up)

    def _take(self) -> List[TokenEvent]:
        events, self._held = self._held, []
        return events

    def _settle(self) -> None:
        """Wait out what is in flight (a decode step, first tokens) and
        bring the mirrors level with the device. The events stay held
        for the next ``step`` / ``drain``."""
        if self._flight is None and not self._firsts:
            return
        self.stats.drains += 1
        flight, firsts = self._flight, self._firsts
        self._flight, self._firsts = None, []
        self._collect(flight, firsts)

    def drain(self) -> List[TokenEvent]:
        """``_settle``, and hand out every event not yet handed out:
        after it nothing is in flight and nothing is held."""
        self._settle()
        return self._take()

    def validate(self, prompt: np.ndarray, sp: SamplingParams) -> None:
        """Typed rejection of requests the decode path cannot serve
        honestly — callers (scheduler.submit, the HTTP handler) fail fast
        with a ValueError instead of poisoning a slot: cache overflow
        (the same error ``generate_fast`` raises), out-of-vocab token ids
        (XLA's gather would silently CLAMP them to vocab_size-1 and serve
        a completion for a prompt the client never sent), and
        non-positive temperature (logits/0 → NaN → garbage tokens;
        greedy decoding is ``top_k=1``, not ``temperature=0``)."""
        prompt = np.asarray(prompt)
        n = int(prompt.size)
        if n < 1:
            raise ValueError("empty prompt")
        if prompt.min() < 0 or prompt.max() >= self.config.vocab_size:
            raise ValueError(
                f"prompt token ids must be in [0, {self.config.vocab_size})"
                f"; got range [{int(prompt.min())}, {int(prompt.max())}]")
        if sp.max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {sp.max_new_tokens}")
        if not sp.temperature > 0:
            raise ValueError(
                f"temperature must be > 0 (got {sp.temperature}); use "
                f"top_k=1 for greedy decoding")
        if n + sp.max_new_tokens > self.block_size:
            raise ValueError(
                f"prompt {n} + {sp.max_new_tokens} new tokens exceeds the "
                f"KV cache (block_size {self.block_size}); crop the prompt "
                f"to block_size - max_new_tokens, or use `generate`, whose "
                f"full-context resampling slides the context window")
        # worst case (zero prefix hits, +1 copy-on-write headroom) must
        # fit the pool EVER, so a queued request always eventually
        # admits once running slots release their blocks
        worst = (-(-(n + sp.max_new_tokens) // self.page_size)
                 + self._cow_room)
        if worst > self.kv_pages - 1:
            raise ValueError(
                f"request needs up to {worst} KV blocks but the "
                f"paged pool holds {self.kv_pages - 1}; raise "
                f"kv_pages or shrink prompt/max_new_tokens")

    # -- paged planning ---------------------------------------------------

    def _walk_prefix(self, prompt: np.ndarray):
        """Consecutive resident full prompt blocks: ``(hit_pages,
        chain_cids)`` — THE prefix probe, shared by planning, capacity
        checks and the scheduler's ordering score (no LRU touch; only a
        committing admission refreshes recency)."""
        page, al = self.page_size, self._alloc
        hit_pages: List[int] = []
        chain: List[int] = []
        cid = 0
        if self._stateful:
            # a state is not addressed by position: no prefix of a
            # prompt is served from one (nor, beside one, from pages:
            # the state at the prefix's end is not kept), and none is
            # registered
            return hit_pages, chain
        buf, step = prompt.tobytes(), page * prompt.itemsize
        for b in range(len(prompt) // page):
            ent = al.probe(cid, buf[b * step:(b + 1) * step])
            if ent is None:
                break
            hit_pages.append(ent[0])
            cid = ent[1]
            chain.append(cid)
        return hit_pages, chain

    def _plan_paged(self, prompt: np.ndarray, max_new: int):
        """Plan a paged admission without mutating allocator state:
        returns ``(hit_pages, chain_cids, cow_src, parent_cid, start,
        suffix, bucket, n_new, need)``. ``hit_pages`` are the resident
        shared-prefix blocks (to be pinned), ``cow_src`` a fully-matched
        final block to copy-on-write (its last token is re-forwarded for
        the first-token logits — recomputing INTO the shared page would
        perturb other readers by the recompute's rounding), ``n_new``
        the fresh blocks to allocate and ``need`` the total pages the
        admission must obtain (n_new + the CoW page)."""
        n = len(prompt)
        page, s_max = self.page_size, self.block_size
        full = n // page
        hit_pages, chain = self._walk_prefix(prompt)
        cid = chain[-1] if chain else 0
        cow_src = None
        if hit_pages and len(hit_pages) * page == n:
            cow_src = hit_pages.pop()
            chain.pop()
            cid = chain[-1] if chain else 0
        matched = len(hit_pages) * page
        suffix = n - matched
        # pad writes (suffix rounded up to its bucket) must stay inside
        # the [block_size] window: un-share blocks until they do. Rare —
        # only near-full-window prompts with a large unshared suffix.
        # The CoW path is exempt: its real suffix is ONE token (bucket
        # 1, start n-1 ≤ block_size-1 always fits) — running the guard
        # on the stale pre-override suffix could otherwise pop hits
        # whose table slots the CoW branch does not re-point.
        while cow_src is None and hit_pages \
                and matched + prompt_bucket(suffix, s_max) > s_max:
            hit_pages.pop()
            chain.pop()
            cid = chain[-1] if chain else 0
            matched -= page
            suffix += page
        if cow_src is not None:
            start, suffix, bucket = n - 1, 1, 1
            first_new = full                 # CoW page covers block full-1
        else:
            start = matched
            bucket = prompt_bucket(suffix, s_max)
            first_new = matched // page
        end_tokens = max(n + max_new, start + bucket)
        n_new = -(-end_tokens // page) - first_new
        need = n_new + (1 if cow_src is not None else 0)
        return (hit_pages, chain, cow_src, cid, start, suffix, bucket,
                n_new, need)

    def admit_probe(self, prompt, sp: SamplingParams) -> Tuple[bool, int]:
        """ONE planning walk answering both scheduler questions:
        ``(would admit() succeed right now, resident-prefix score)``.
        The capacity answer is exact, not conservative — it runs the
        same plan ``admit`` would and excludes the would-be-pinned
        prefix blocks from the evictable supply."""
        p = np.asarray(prompt, np.int32).reshape(-1)
        hit_pages, _chain, cow_src, _cid, _start, _suffix, _bucket, \
            _n_new, need = self._plan_paged(p, sp.max_new_tokens)
        pinned = hit_pages + ([cow_src] if cow_src is not None else [])
        score = len(hit_pages) + (1 if cow_src is not None else 0)
        fits = self._alloc.available(exclude=pinned) >= need
        if self.row_state:                   # both, or not admitted
            fits = fits and bool(self._state_free)
        return fits, score

    def admit(self, prompt: np.ndarray,
              sp: SamplingParams) -> Tuple[int, TokenEvent]:
        """``admit_nowait`` and a wait for its first token: returns
        ``(slot, event)``; when the first token already finishes the
        request (``max_new_tokens == 1`` or instant EOS) the slot is
        free again before returning. For direct callers; the scheduler
        takes the first token as an event of its next ``step(ahead=True)``."""
        slot = self.admit_nowait(prompt, sp)
        self._settle()
        at = next(i for i, ev in enumerate(self._held) if ev.slot == slot)
        return slot, self._held.pop(at)

    def admit_nowait(self, prompt: np.ndarray, sp: SamplingParams) -> int:
        """Dispatch ``prompt``'s prefill into a free slot and return the
        slot. The program samples the first token and writes the slot's
        row of the decode state itself; the token stays on the device
        and arrives as this slot's first ``TokenEvent`` with the next
        read (``step`` / ``drain``)."""
        with span("serve.prefill.args"):
            prompt = np.asarray(prompt, np.int32).reshape(-1)
            self.validate(prompt, sp)
            free = self.free_slots()
            if not free:
                raise NoFreeSlotError(
                    "no free slot — admit() requires one (scheduler bug: "
                    "check free_slots() first)")
            slot = free[0]
            fault_point("serve.prefill")
            n = len(prompt)
            key = derive_base_key(sp.seed)
            top_k = (self.config.vocab_size if sp.top_k is None
                     else int(sp.top_k))
            top_p = 1.0 if sp.top_p is None else float(sp.top_p)
            eos = -1 if sp.eos_token is None else int(sp.eos_token)
        tok = self._prefill_paged(slot, prompt, sp, key, top_k, top_p, eos)
        self.stats.prefills += 1
        # the host's mirror of the row the program wrote: the first token
        # comes from the prefill (key index 0), decode steps continue the
        # schedule at index 1. ``active`` until the token is read and
        # says otherwise; the token itself (``_next_tok``, the history's
        # entry n) is written when it is read
        self._active[slot] = True
        self._gen_idx[slot] = 1
        self._generated[slot] = 1
        self._max_new[slot] = sp.max_new_tokens
        self._eos[slot] = eos
        self._temp[slot] = sp.temperature
        self._top_k[slot] = top_k
        self._top_p[slot] = top_p
        self._base_keys[slot] = key
        # no wait: the token is read with the next step's download
        self._firsts.append((slot, tok))
        self.stats.active_slots = int(self._active.sum())
        self.stats.prefill_buckets = tuple(sorted(self._seen_buckets))
        return slot

    def _first_token(self, slot: int, tok: int) -> None:
        """A prefill's token, read: finish the mirror's row as the
        program finished the device's, and hold the event."""
        self._next_tok[slot] = tok
        # token history feeds the n-gram draft; the first token is
        # emitted (index n), giving hist_len == cursor + 1
        self._hist[slot, self._prompt_len[slot]] = tok
        finished = bool(self._max_new[slot] <= 1 or tok == self._eos[slot])
        if finished:
            self._active[slot] = False
            self._release_pages(slot)
        self.stats.tokens_generated += 1
        self._held.append(TokenEvent(slot, tok, finished))

    def _prefill_paged(self, slot: int, prompt: np.ndarray,
                       sp: SamplingParams, base_key, top_k: int,
                       top_p: float, eos: int):
        """Prefix-aware paged prefill: pin the resident shared-prefix
        blocks, copy-on-write a fully-matched final block, allocate the
        owned blocks (prefill pads + the whole decode budget — blocks
        are reserved at admit, so mid-decode writes can never need an
        allocation the jitted program couldn't perform), dispatch the
        SUFFIX-only prefill (which also writes the slot's row of the
        decode state), then content-register this prompt's own full
        blocks for future requests to hit. Returns the first token, a
        device array nobody has waited for."""
        n = len(prompt)
        page, al = self.page_size, self._alloc
        full = n // page
        # `held` tracks every page reference this admission currently
        # owns; ANY failure past this point (capacity shortfall, a
        # compile/dispatch error in CoW or prefill) unwinds it exactly —
        # an admission that fails its request must not shrink the pool
        held: List[int] = []
        state_block = 0
        evicted = al.evictions
        try:
            with span("serve.prefill.plan") as sp_plan:
                hit_pages, chain, cow_src, cid, start, suffix, bucket, \
                    n_new, need = self._plan_paged(prompt,
                                                   sp.max_new_tokens)
                # pin before the capacity check: a pinned page is neither
                # evictable nor double-counted as supply
                pinned = hit_pages + ([] if cow_src is None else [cow_src])
                al.incref_many(pinned)
                held += pinned
                # all of them or none (``NoFreeBlocksError``), in the
                # order a page at a time would come: the copy-on-write's
                # first
                fresh = al.alloc_many(need)
                held += fresh
                if self.row_state:
                    if not self._state_free:
                        raise NoFreeBlocksError(
                            "no state block is free right now — retry "
                            "after running requests release")
                    state_block = self._state_free.pop()
                    sp_plan.ids["state_block"] = state_block
                if cow_src is not None:
                    self._cache = self._cow_prog(
                        self._cache, np.int32(cow_src), np.int32(fresh[0]))
                    al.decref(cow_src)       # pinned only for the copy
                    held.remove(cow_src)
                row = hit_pages + fresh
                self._bt[slot] = 0
                self._bt[slot, :len(row)] = row
                if self.row_state:
                    self._bt[slot, -1] = state_block
                sp_plan.ids["pages"] = need
                sp_plan.ids["evicted"] = al.evictions - evicted
                self._seen_buckets.add(bucket)
                prefill = self._prefill_prog(bucket)
                padded = np.zeros((1, bucket), np.int32)
                padded[0, :suffix] = prompt[start:]
                state, ups = self._state_args(self._state_names)
                if ups:
                    sp_plan.ids["uploads"] = ups
                # NumPy as it is: the executable's own argument path
                # transfers the batch
                args = (state, np.int32(slot), self._bt[slot][None],
                        np.asarray([start], np.int32), padded,
                        np.int32(suffix), base_key,
                        np.float32(sp.temperature), np.int32(top_k),
                        np.float32(top_p), np.int32(sp.max_new_tokens),
                        np.int32(eos))
                if self.spec_tokens:
                    whole = np.zeros(self.block_size, np.int32)
                    whole[:n] = prompt
                    args += (whole,)
            with span("serve.prefill.dispatch", path=self.attend_path):
                tok, self._dev, self._cache = prefill(
                    self.params, self._cache, *args)
            self._stale.clear()
            self.stats.paged_kernel_dispatches += self._kernel_attend
        except BaseException:
            al.decref_many(held)
            if state_block:
                self._state_free.append(state_block)
            self._bt[slot] = 0
            raise
        # only a COMMITTING admission refreshes hit recency — planning
        # probes must not keep a never-admitted prefix artificially hot
        for pg in hit_pages:
            al.touch(pg)
        if cow_src is None and not self._stateful:
            # register the freshly-prefilled full PROMPT blocks (their
            # content is immutable — decode writes start past them);
            # the CoW path has nothing new: every block was cached
            reg_cid = cid
            buf, step = prompt.tobytes(), page * prompt.itemsize
            for b in range(len(hit_pages), full):
                reg_cid = al.register(
                    reg_cid, buf[b * step:(b + 1) * step], row[b])
        self._pos[slot] = n
        self._hist[slot] = 0
        self._hist[slot, :n] = prompt
        self._prompt_len[slot] = n
        self.stats.prefix_hit_blocks += (len(hit_pages)
                                         + (1 if cow_src is not None
                                            else 0))
        self.stats.prefill_tokens += bucket
        self.stats.prefill_tokens_run += prefill_positions_run(
            self.config, bucket, suffix)
        counted = getattr(self.config, "prefill_counted", None)
        if counted is not None:
            self._count(counted(bucket, start, suffix))
        self._pool_stats()
        return tok

    def _pool_stats(self) -> None:
        al = self._alloc
        self.stats.kv_blocks_in_use = al.in_use()
        self.stats.kv_blocks_cached = al.cached()
        self.stats.kv_evictions = al.evictions
        self.stats.kv_evict_visits = al.evict_visits
        if self.row_state:
            self.stats.state_blocks_in_use = (
                self.state_blocks - 1 - len(self._state_free))

    def _free_row(self, row: np.ndarray) -> None:
        """Give back what a block-table row references: its pages and,
        under ``row_state``, its state block (the last entry)."""
        pages = row[:self.max_blocks]
        self._alloc.decref_many(pages[pages != 0].tolist())
        if self.row_state and row[-1]:
            self._state_free.append(int(row[-1]))

    def state_block(self, slot: int) -> int:
        """The state block slot ``slot`` holds (0: none, or no such
        model)."""
        return int(self._bt[slot, -1]) if self.row_state else 0

    def _release_pages(self, slot: int) -> None:
        """Drop this slot's block-table references (idempotent: an
        already-cleared row is a no-op). Cached prefix blocks stay
        resident at refcount 0; plain owned blocks return to the free
        list, and a ``row_state`` row's state block to its own."""
        self._free_row(self._bt[slot])
        # the mirror's row only: the programs clear the device's when the
        # row stops, and read no inactive row's table before that
        self._bt[slot] = 0
        self._pool_stats()

    def _scrub_pages(self, slot: int) -> None:
        """Write over the pages only this quarantined row holds, before
        they are freed: a masked position still multiplies (0 x NaN), so
        a page that kept its NaNs would poison its next owner, and that
        one's, which is no recovery. The source is the null page, finite
        or every row would be poisoned through its unallocated table
        entries; the copy is the admit path's own program. Rare path:
        one small dispatch a page."""
        for pg in self._bt[slot, :self.max_blocks]:
            if pg and self._alloc.condemn(int(pg)):
                self._cache = self._cow_prog(
                    self._cache, np.int32(0), np.int32(pg))
        block = self.state_block(slot)
        if block:
            # the row's state block: the null block's zeros over it
            self._cache = self._state_cow_prog(
                self._cache, np.int32(0), np.int32(block))

    def release(self, slot: int) -> None:
        """Free a slot (a cancelled request, a deadline): the slot's
        block-table references are dropped (shared prefix blocks stay
        resident for future hits). A slot the device may still be
        advancing is a host write to a live row: what is in flight is
        waited out first, so that no step that saw the old occupant is
        unread when the slot is handed out again, and the events it
        still had for this slot are dropped with it. A slot that already
        stopped costs no wait and no upload."""
        if self._active[slot]:
            self._settle()
        if self._active[slot]:               # the wait may have ended it
            self._active[slot] = False
            self._stale.add("active")
        self._held = [ev for ev in self._held if ev.slot != slot]
        self._release_pages(slot)
        self.stats.active_slots = int(self._active.sum())

    # -- preemptible decode (park / resume) --------------------------------

    def park(self, slot: int) -> ParkedSlot:
        """Preempt an ACTIVE slot at a chunk boundary: wait out what is
        in flight (the snapshot is of the mirrors, which must be level
        with the device; the caller takes the held events with
        ``drain`` BEFORE parking if it routes them by slot), snapshot its
        entire host-side cursor state and block table WITHOUT decreffing
        the pages — the snapshot owns the references — deactivate the
        row, and return the snapshot. No copies of KV state."""
        self._settle()
        if not self._active[slot]:
            raise ValueError(f"slot {slot} is not active — nothing to park")
        parked = ParkedSlot(
            block_table=self._bt[slot].copy(),
            pos=int(self._pos[slot]),
            hist=self._hist[slot].copy(),
            prompt_len=int(self._prompt_len[slot]),
            next_tok=int(self._next_tok[slot]),
            gen_idx=int(self._gen_idx[slot]),
            generated=int(self._generated[slot]),
            max_new=int(self._max_new[slot]),
            eos=int(self._eos[slot]),
            temp=float(self._temp[slot]),
            top_k=int(self._top_k[slot]),
            top_p=float(self._top_p[slot]),
            base_key=self._base_keys[slot].copy())
        self._active[slot] = False
        # references moved to the snapshot: zero the row WITHOUT decref
        # so release()/step()'s page sweep cannot double-free them
        self._bt[slot] = 0
        self._stale.add("active")
        self.stats.preemptions += 1
        self.stats.active_slots = int(self._active.sum())
        return parked

    def resume(self, parked: ParkedSlot) -> int:
        """Restore a parked snapshot into a free slot. No device work —
        the KV pool is shared across slots and the slot's state is
        written to the host's mirrors (level with the device once what
        is in flight was waited out), which go up with the next
        dispatch, so the resumed generation continues from exactly the
        token it was preempted at, byte-identical by the per-token key
        schedule. Raises ``NoFreeSlotError`` when
        every slot is busy (the scheduler checks first)."""
        if parked.released:
            raise ValueError("parked snapshot already consumed")
        self._settle()
        free = self.free_slots()
        if not free:
            raise NoFreeSlotError(
                "no free slot to resume the parked request into")
        slot = free[0]
        self._bt[slot] = parked.block_table
        self._pos[slot] = parked.pos
        self._hist[slot] = parked.hist
        self._prompt_len[slot] = parked.prompt_len
        self._active[slot] = True
        self._next_tok[slot] = parked.next_tok
        self._gen_idx[slot] = parked.gen_idx
        self._generated[slot] = parked.generated
        self._max_new[slot] = parked.max_new
        self._eos[slot] = parked.eos
        self._temp[slot] = parked.temp
        self._top_k[slot] = parked.top_k
        self._top_p[slot] = parked.top_p
        self._base_keys[slot] = parked.base_key
        self._stale.update(SPEC_STATE)
        parked.released = True
        self.stats.resumes += 1
        self.stats.active_slots = int(self._active.sum())
        return slot

    def release_parked(self, parked: ParkedSlot) -> None:
        """Drop a parked snapshot's page references without resuming it
        (deadline/cancel/shutdown caught the request while parked).
        Idempotent via the ``released`` flag."""
        if parked.released:
            return
        parked.released = True
        self._free_row(parked.block_table)
        self._pool_stats()

    def step(self, override_tokens: Optional[Dict[int, int]] = None,
             ahead: bool = False) -> List[TokenEvent]:
        """Dispatch one decode step (every active slot advances by up to
        ``decode_chunk`` tokens) and return the tokens read, in
        generation order.

        ``ahead=True`` is the scheduler's call, one decode step AHEAD:
        step K is dispatched from the device's own state, then what the
        device finished BEFORE it is read — step K-1's small download
        and the first tokens of the prefills dispatched since — and
        those events are the return (each prefill's first token after
        the step's tokens). The driver thread waits for step K-1 while
        step K is already queued, and delivers, picks and admits while
        it runs. Slots that finish (EOS / max-tokens / non-finite
        logits, decided ON DEVICE) come back inactive one read later and
        are free for the next admit: continuous batching at chunk
        granularity, one step behind. With no row active any more
        nothing is dispatched and the step in flight is waited out (its
        events are the return), also the one dispatched just before the
        read that said so; with nothing in flight either, ``[]``.

        The default is that and a wait for the step just dispatched, for
        direct callers: the return ends with this step's tokens and
        nothing is in flight afterwards.

        ``override_tokens`` (teacher forcing, tests/eval only) replaces a
        slot's INPUT token for ONE single step — the call runs a chunk-1
        program regardless of ``decode_chunk`` and the returned logits
        (``self.last_logits``) are the model's prediction conditioned on
        the forced history, while sampling proceeds normally.
        """
        if override_tokens:
            self._settle()                   # a host write to live rows
            for slot, tok in override_tokens.items():
                self._next_tok[slot] = int(tok)
            self._stale.add("tok")
        self._advance(forced=bool(override_tokens))
        if not ahead:
            self._settle()
        return self._take()

    def _advance(self, forced: bool = False) -> None:
        """Step K out, then step K-1 (and the prefills between them)
        in: the events are held."""
        if self._active.any():
            flight, firsts = self._flight, self._firsts
            launched = self._launch(forced, ahead=flight is not None)
            self._flight, self._firsts = launched, []
            if flight is not None or firsts:
                self._collect(flight, firsts)
        if not self._active.any():
            # no row is left, or the read just said the last ones
            # stopped: a step dispatched ahead of that read computes
            # nothing, and is waited out here, so that an engine with no
            # request has nothing in flight
            self._settle()

    def _launch(self, forced: bool, ahead: bool) -> _Flight:
        """Dispatch one decode step and wait for nothing. The caller saw
        a row active in the mirrors (rows only ever stop on the device
        first, so they never say too little). What crosses to the device
        is in the module docstring: only a mirror the host wrote since
        (``_stale``) goes up, so a step after which no live row was
        written by the host uploads nothing (``stats.resident_steps``)."""
        prog = self._decode_prog
        spec_run = self._spec_prog is not None and not forced
        if spec_run:
            prog = self._spec_prog
        elif forced and (self.decode_chunk != 1
                         or self._spec_prog is not None):
            if self._step1_prog is None:
                self._step1_prog = self._acquire(paged_decode_def(
                    self._cfg_tuple, self.num_slots, 1))
            prog = self._step1_prog
        # hit-counted AFTER the idle early-out so hit N is the Nth REAL
        # decode dispatch — "hang at dispatch 2" reproduces exactly
        fault_point("serve.decode")
        with span("serve.decode.args") as sp:
            state, ups = self._state_args(
                SPEC_STATE if spec_run else PAGED_STATE)
            sp.ids["uploads"] = ups
            self.stats.resident_steps += int(not ups)
        with span("serve.decode.dispatch", path=self.attend_path):
            read, self._logits, self._dev, self._cache = prog(
                self.params, self._cache, state)
        self._logits_host = None
        self._stale.clear()
        self.stats.paged_kernel_dispatches += self._kernel_attend
        return _Flight(read, spec_run, ahead)

    def _collect(self, flight: Optional[_Flight],
                 firsts: List[Tuple[int, Any]]) -> None:
        """Read a decode step's download and the first tokens of the
        prefills dispatched after it, in ONE transfer, bring the mirrors
        up to them and hold the events."""
        first_toks = [tok for _slot, tok in firsts]
        if flight is not None:
            with span("serve.decode.readback") as rb:
                # one wait for the step (and the prefills behind it),
                # then a few KiB
                read, first_toks = jax.device_get((flight.read, first_toks))
                nbytes = sum(a.nbytes for a in jax.tree.leaves(read))
                rb.ids["bytes"] = nbytes
                self.stats.readback_bytes += nbytes
        with span("serve.decode.events"):
            if flight is None:
                # no step to read them with: the device was idle before
                # these prefills (the first round, or after a drain)
                with span("serve.prefill.readback"):
                    first_toks = jax.device_get(first_toks)
            else:
                self._replay(flight, read, [slot for slot, _tok in firsts])
            for (slot, _dev), tok in zip(firsts, first_toks):
                self._first_token(slot, int(tok[0]))
            self.stats.active_slots = int(self._active.sum())

    def _replay(self, flight: _Flight, read: Dict[str, Any],
                admitted: List[int]) -> None:
        """One decode step's download into mirrors and events.
        ``admitted``: slots a prefill wrote AFTER this step (the step saw
        them inactive, and the mirrors' rows are the newer)."""
        toks, emitted = read["toks"], read["emitted"]
        nan_seen = read["nan_seen"]
        self._count(read["counted"])
        if toks.ndim == 2:
            # non-speculative programs emit one token per scanned step;
            # widen to the speculative [chunk, S, γ+1] layout so ONE host
            # replay path routes both
            toks = toks[..., None]
            emitted = emitted[..., None]
        was_active = self._active.copy()
        # the mirrors take the device's final state (``_gen_idx``,
        # ``_generated`` and ``_hist`` follow below, token by token)
        newer = np.zeros(self.num_slots, bool)
        newer[admitted] = True
        self._next_tok = np.where(newer, self._next_tok,
                                  read["tok"]).astype(np.int32)
        self._active = np.where(newer, self._active, read["active"])
        self._pos = np.where(newer, self._pos, read["pos"]).astype(np.int32)
        # numerical quarantine: non-finite logits fail ONLY their own
        # slot — the model's per-row cache math keeps rows isolated (and
        # the decode attends NaN-poison an overflowing row/position on
        # purpose, so this is the designated catch point). Every decode
        # program LATCHES non-finite logits per iteration while the row
        # is active (``nan_seen``) and stops the row itself: the last
        # step's logits could not witness a poison that struck a paged
        # row mid-chunk (a finished row's table is redirected to the
        # null page, so its later iterations read clean garbage), and no
        # path reads logits. Quarantine = evict, with no host write.
        self.stats.quarantined += int(nan_seen.sum())
        events: List[TokenEvent] = []
        n_steps = toks.shape[0]
        for k in range(n_steps):
            for slot in np.nonzero(emitted[k].any(axis=1))[0]:
                if flight.spec_run:
                    # acceptance accounting: γ drafted per active slot
                    # per iteration; all emitted beyond the one
                    # guaranteed token were accepted drafts
                    self.stats.spec_drafted += self.spec_tokens
                    self.stats.spec_accepted += int(
                        emitted[k, slot].sum()) - 1
                for j in np.nonzero(emitted[k, slot])[0]:
                    tok = int(toks[k, slot, j])
                    hl = (int(self._prompt_len[slot])
                          + int(self._generated[slot]))
                    if hl < self.block_size:
                        self._hist[slot, hl] = tok
                    self._gen_idx[slot] += 1
                    self._generated[slot] += 1
                    # finished iff the device stopped emitting for this
                    # slot (its last emitted token) and it came back
                    # inactive
                    last_emit = (not emitted[k, slot, j + 1:].any()
                                 and not emitted[k + 1:, slot].any())
                    finished = bool(last_emit and not self._active[slot])
                    events.append(TokenEvent(
                        int(slot), tok, finished,
                        poisoned=bool(nan_seen[slot])))
        # blocks of slots that finished (or were quarantined) this
        # chunk go back to the allocator; shared prefix blocks stay
        # resident for future hits. The scrub and whatever takes the
        # pages next are queued behind the step in flight, which has
        # these rows inactive already
        for slot in np.nonzero(was_active & ~self._active)[0]:
            if nan_seen[slot]:
                self._scrub_pages(slot)
            self._release_pages(slot)
        self.stats.tokens_generated += len(events)
        self.stats.decode_steps += n_steps
        self.stats.steps_ahead += n_steps * flight.ahead
        self.stats.sampler_sorted_steps += int(read["sorted"].sum())
        self._held.extend(events)
