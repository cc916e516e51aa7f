"""Paged KV cache: fixed-size pages, block tables, paged decode compute.

The dense engine treats a lane as the unit of KV residency: spill copies
all ``max_len`` rows to host, restore copies them all back, commit splices
a full padded lane — even when the request only wrote 20 tokens.  This
module makes the *page* (``page_size`` token rows) the unit instead,
vLLM-style:

* :class:`PagedKVPool` — the allocation layer: a free list of physical
  pages, per-request block tables (logical slot ``j`` → physical page),
  refcounted pages so tables may *share* a prefix (``share``), and
  LRU eviction of unpinned tables to a host record when an allocation
  cannot be satisfied (``host_tables``).
* :class:`PagedKVView` — the :class:`~repro.serving.kv.KVView` the
  scheduler consumes: lane allocation delegated to the dense
  :class:`~repro.serving.engine.KVPartition` (reservations keep working),
  capacity additionally min-bounded by the page budget, and per-template
  lane reservations translated into **page quotas** (a template's
  guaranteed share of physical pages).
* :class:`PagedInferenceEngine` — the serving engine at page granularity,
  for the architectures :func:`~repro.models.paged_decode.
  supports_paged_decode` accepts (full-context dense/MoE stacks; it
  refuses any other, which :class:`~repro.serving.engine.InferenceEngine`
  serves).  KV lives only in shared physical page arrays
  ``(L, n_pages + 1, page_size, Hkv, hd)`` — no dense per-lane cache is
  ever built — and every decode tick dispatches
  :func:`~repro.models.paged_decode.paged_decode_step`, whose attention
  goes through the registry's ``paged_decode_attention`` kernel/ref pair
  (Pallas on TPU or under interpret mode, pure-jnp ref elsewhere).
  Outputs stay bit-identical to the dense engine at the greedy-token
  level.  Three consequences:

  - **oversubscription** — ``n_pages`` decouples from
    ``n_lanes * max_len / page_size``: an under-provisioned pool admits
    on instantaneous page budgets, and mid-decode growth past the pool's
    capacity evicts the least-recently-touched lane's KV to the host
    spill pool (``page_evictions``), notifying the scheduler through
    ``on_lane_evicted`` / :meth:`~PagedInferenceEngine.drain_evictions`
    so the victim re-queues and later restores;
  - **spill/restore/commit** move pages through arbitrary physical
    frames, page-granular: spill copies only the
    ``ceil(length / page_size)`` valid pages, restore splices the first
    ``prefetch_pages`` now and queues the tail, commit splices only the
    pages each prompt actually fills;
  - **fused megabatch dispatch** — :meth:`~PagedInferenceEngine.
    stage_chunk` lets the scheduler fold the next staged chunked-prefill
    chunk *into* the decode tick's device program: one dispatch covers
    the decode batch (over shared block tables) plus the chunk's scan,
    so overlap mode stops paying two dispatches per tick boundary.

Stale rows past a request's valid pages are never read: attention masks
``kpos < length + 1`` and decode writes position ``length`` before ever
attending it; inactive lanes scatter into a reserved trash page (physical
slot ``n_pages``) that no block table references.
"""
from __future__ import annotations

import dataclasses
import functools
from collections import OrderedDict
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.lane_policy import PrefixIndex
from repro.kernels import registry
from repro.models.paged_decode import (
    paged_decode_step,
    sample_tokens,
    supports_paged_decode,
)
from repro.serving.engine import InferenceEngine, KVPartition, StagedPrefill

__all__ = ["PagedInferenceEngine", "PagedKVPool", "PagedKVView",
           "paged_step_programs"]


# XLA's memory-space assignment on a TPU: no prefetch that moves more
# bytes than its uses read (a pool staged whole into VMEM for one page).
MSA_OPTIONS = {"xla_tpu_msa_inefficient_use_to_copy_ratio": 1.0}


def paged_step_programs(arch, *, use_kernel: bool, interpret: bool):
    """The paged engine's two decode programs, jitted with the page pools
    (``cache``, argument 2) donated: ``_paged``, one decode step, and
    ``_fused``, one decode step plus one chunk of a staged prefill.

    Each consumes the pools it is given and returns them updated in
    place (:func:`~repro.models.paged_decode.paged_decode_step` carries
    them through its layer scan), so a caller must hold no pool array
    across a call.

    On a TPU both compile with ``MSA_OPTIONS``: a stacked pool that fits
    in VMEM (ChatGLM3-6B's two kv heads: 59 MB a pool at 14 layers) is
    otherwise staged there and back around every layer's token scatter
    and kernel call, a whole-pool copy each way per layer.  Where no pool
    fits (OLMo-1B's cell) the compiled programs are the same with or
    without the option.
    """
    cfg = arch.cfg
    jit = functools.partial(
        jax.jit, donate_argnums=(2,),
        compiler_options=MSA_OPTIONS if jax.default_backend() == "tpu"
        else None)

    @jit
    def _paged(params, token, cache, lengths, tables, active, temps, seeds):
        logits, new_cache = paged_decode_step(
            cfg, params, token, cache, tables, lengths, active,
            use_kernel=use_kernel, interpret=interpret)
        nxt = sample_tokens(logits, temps, seeds, lengths)
        return nxt, new_cache

    @jit
    def _fused(params, token, cache, lengths, tables, active,
               temps, seeds, ctoks, ccache, clens):
        # Chunk side: the same lax.scan of decode_step the standalone
        # _extend performs, over the staged (dense, batch-1) cache —
        # fused into ONE device program with the paged decode batch.
        def step(carry, tok):
            c, ln = carry
            logits, c = arch.decode_step(params, tok, c, ln)
            return (c, ln + 1), logits

        (ccache, clens), clogits = jax.lax.scan(
            step, (ccache, clens), jnp.swapaxes(ctoks, 0, 1))
        cfirst = jnp.argmax(clogits[-1], axis=-1).astype(jnp.int32)
        logits, new_cache = paged_decode_step(
            cfg, params, token, cache, tables, lengths, active,
            use_kernel=use_kernel, interpret=interpret)
        nxt = sample_tokens(logits, temps, seeds, lengths)
        return nxt, new_cache, cfirst, ccache, clens

    return _paged, _fused


class PagedKVPool:
    """Refcounted physical pages + per-request block tables.

    Pure bookkeeping: the pool tracks which physical page backs each
    logical slot of each table, not the page contents (those live in
    whatever array the caller pages — the engine's page arrays, a host
    buffer).  ``alloc_table(key, n=...)`` takes any ``n`` free pages,
    evicting least-recently-used unpinned tables to :attr:`host_tables`
    (or the ``on_evict`` callback) when the free list runs dry.  Pages are
    refcounted so :meth:`share` can alias a prefix across tables; a page
    returns to the free list only when its last table drops it.
    """

    def __init__(self, n_pages: int, page_size: int,
                 on_evict: Optional[Callable[[object, list[int]], None]] = None):
        if n_pages < 1 or page_size < 1:
            raise ValueError("n_pages and page_size must be >= 1")
        self.n_pages = n_pages
        self.page_size = page_size
        self.on_evict = on_evict
        self._free: list[int] = list(range(n_pages))
        self._ref = [0] * n_pages
        self._tables: "OrderedDict[object, list[int]]" = OrderedDict()
        self._pinned: set = set()
        self.host_tables: dict[object, list[int]] = {}
        self.evicted = 0

    # ------------------------------------------------------------- capacity
    @property
    def n_free_pages(self) -> int:
        """Pages on the free list right now (eviction can raise this)."""
        return len(self._free)

    def pages_for(self, length: int) -> int:
        """Pages needed to hold ``length`` token rows (0 for 0)."""
        return -(-length // self.page_size)

    # --------------------------------------------------------------- tables
    def has_table(self, key) -> bool:
        """Whether ``key`` currently owns a block table."""
        return key in self._tables

    def table(self, key) -> tuple[int, ...]:
        """``key``'s physical pages in logical-slot order (LRU-touching)."""
        self._tables.move_to_end(key)
        return tuple(self._tables[key])

    def pages(self, key) -> tuple[int, ...]:
        """``key``'s physical pages WITHOUT touching LRU order — for bulk
        snapshots (device block tables each tick) that must not mask the
        recency signal eviction relies on."""
        return tuple(self._tables[key])

    def lru_tables(self) -> list:
        """Table keys from least- to most-recently touched (victim scan)."""
        return list(self._tables)

    def block_table(self, key, max_pages: int) -> np.ndarray:
        """``key``'s table as a fixed-width int32 row, padded with page 0
        (padding slots are masked by length, never read — the layout the
        paged attention kernel consumes)."""
        pages = self.table(key)
        out = np.zeros((max_pages,), np.int32)
        out[: len(pages)] = pages
        return out

    def alloc_table(self, key, n: int) -> list[int]:
        """Create ``key``'s table from ``n`` free pages (LRU-evicting on
        pressure)."""
        if key in self._tables:
            raise ValueError(f"table {key!r} already allocated")
        got = self._claim(n)
        self._tables[key] = got
        return list(got)

    def extend_table(self, key, n: int) -> list[int]:
        """Append ``n`` pages to ``key``'s table (decode crossed a
        boundary)."""
        new = self._claim(n)
        self._tables[key].extend(new)
        self._tables.move_to_end(key)
        return new

    def free_table(self, key) -> None:
        """Drop ``key``'s table; pages with no remaining owner are freed."""
        self._pinned.discard(key)
        for p in self._tables.pop(key):
            self._decref(p)

    def adopt_table(self, key, pages: list[int]) -> None:
        """Create ``key``'s table from pages the caller already holds a
        reference on (a spill entry's prefix hold): ownership of exactly
        one reference per page TRANSFERS into the table — no incref, no
        claim.  The refcount-transfer twin of :meth:`alloc_table`."""
        if key in self._tables:
            raise ValueError(f"table {key!r} already allocated")
        for p in pages:
            if self._ref[p] <= 0:
                raise RuntimeError(
                    f"page {p} is free; cannot adopt an unreferenced page")
        self._tables[key] = list(pages)

    def share(self, src, dst, n_pages: Optional[int] = None) -> list[int]:
        """Alias ``src``'s first ``n_pages`` pages (default: all) under a
        new table ``dst`` — prefix-granular sharing: every aliased page's
        refcount rises, nothing is copied.  The caller typically extends
        ``dst`` with private tail pages afterwards
        (:meth:`extend_table`); a write into an aliased page must fork it
        first (:meth:`fork_page` — copy-on-write)."""
        if dst in self._tables:
            raise ValueError(f"table {dst!r} already allocated")
        pages = list(self._tables[src])
        if n_pages is not None:
            if not 0 <= n_pages <= len(pages):
                raise ValueError(
                    f"share of {n_pages} pages but {src!r} has {len(pages)}")
            pages = pages[:n_pages]
        for p in pages:
            self._ref[p] += 1
        self._tables[dst] = pages
        return list(pages)

    def page_ref(self, p: int) -> int:
        """Physical page ``p``'s current refcount (0 = on the free list)."""
        return self._ref[p]

    def shared_prefix_pages(self, key) -> int:
        """How many LEADING pages of ``key``'s table are aliased by
        another live owner (refcount above 1).  Aliased pages always form
        a prefix — :meth:`share` copies a table head and a fork replaces
        the writer's page, never a reader's — so this is the page count
        partial eviction keeps resident."""
        n = 0
        for p in self._tables[key]:
            if self._ref[p] > 1:
                n += 1
            else:
                break
        return n

    def fork_page(self, key, slot: int) -> Optional[tuple[int, int]]:
        """Copy-on-write fork: give ``key`` a private page at logical
        ``slot`` before a write would be visible to the other readers of
        a shared page.  Returns ``(old_page, new_page)`` — the caller
        copies the page CONTENTS old → new (the pool tracks placement
        only) — or ``None`` when the page is already private.  Needs a
        free page (the caller makes room first); the shared page keeps
        its remaining readers untouched."""
        pages = self._tables[key]
        old = pages[slot]
        if self._ref[old] <= 1:
            return None
        if not self._free:
            raise RuntimeError(
                "KV pool out of pages: no free page for a copy-on-write fork")
        new = self._free.pop(0)
        self._ref[new] = 1
        self._ref[old] -= 1  # stays >= 1: the other readers still hold it
        pages[slot] = new
        self._tables.move_to_end(key)
        return old, new

    def incref_pages(self, pages: list[int]) -> None:
        """Take one extra reference on each (live) page — how a host
        spill entry keeps a shared prefix resident while its reader is
        evicted (partial eviction)."""
        for p in pages:
            if self._ref[p] <= 0:
                raise RuntimeError(
                    f"page {p} is free; cannot reference a free page")
        for p in pages:
            self._ref[p] += 1

    def decref_pages(self, pages: list[int]) -> None:
        """Release references taken by :meth:`incref_pages` (a dropped
        spill entry's prefix hold); pages reaching zero return to the
        free list.  Double-frees raise instead of corrupting the pool."""
        for p in pages:
            self._decref(p)

    def pin(self, key) -> None:
        """Exempt ``key`` from OOM eviction (an active decode lane)."""
        self._pinned.add(key)

    def unpin(self, key) -> None:
        """Make ``key`` evictable again."""
        self._pinned.discard(key)

    def snapshot(self) -> dict:
        """Occupancy + eviction counters (introspection/benchmarks)."""
        return {"free_pages": len(self._free), "tables": len(self._tables),
                "evicted": self.evicted, "host_tables": len(self.host_tables)}

    # ------------------------------------------------------------- internals
    def _claim(self, n: int) -> list[int]:
        while len(self._free) < n:
            self._evict_one()
        got = [self._free.pop(0) for _ in range(n)]
        for p in got:
            self._ref[p] = 1
        return got

    def _evict_one(self) -> None:
        for key in self._tables:  # OrderedDict order == LRU
            if key in self._pinned:
                continue
            pages = self._tables[key]
            if any(self._ref[p] > 1 for p in pages):
                # A live alias group references this table's pages: a
                # whole-table spill would snapshot rows another reader is
                # still extending from.  Skip it — partial eviction at the
                # engine layer spills only the unshared tail.
                continue
            self._tables.pop(key)
            self.evicted += 1
            if self.on_evict is not None:
                self.on_evict(key, list(pages))
            else:
                self.host_tables[key] = list(pages)
            for p in pages:
                self._decref(p)
            return
        raise RuntimeError(
            "KV pool out of pages: every table is pinned or aliased by a "
            "live table")

    def _decref(self, p: int) -> None:
        if self._ref[p] <= 0:
            raise RuntimeError(f"page {p} is already free (double free)")
        self._ref[p] -= 1
        if self._ref[p] == 0:
            self._free.append(p)


class PagedKVView:
    """:class:`~repro.serving.kv.KVView` over (lane partition, page pool).

    Allocation units stay lanes — per-template reservations, ``benefits``
    and the free-lane snapshot all delegate to the dense
    :class:`KVPartition` — but every capacity read is additionally
    min-bounded by the page budget: a free lane is only admissible if the
    pool could still back a full lane's worth of pages for it.  With a
    fully-provisioned pool (``n_pages = n_lanes * pages_per_lane``) the
    bound is never the binding constraint, so paged admission behaves
    exactly like dense admission; an **oversubscribed** pool
    (``n_pages`` below that) admits on instantaneous free-page budgets
    and relies on the engine's mid-decode eviction for growth pressure.

    ``page_quota`` (template → guaranteed pages, derived from the
    partition's lane shares) carries reservations to page granularity:
    :meth:`n_free_for` subtracts every OTHER template's unmet quota from
    the free-page budget before bounding, so a shared-pool burst cannot
    consume the pages a reserved template is owed.  ``used_pages`` is the
    engine callback reporting a template's currently-held pages.
    """

    def __init__(self, partition: KVPartition, pool: PagedKVPool,
                 pages_per_lane: int,
                 page_quota: Optional[dict] = None,
                 used_pages: Optional[Callable[[Optional[str]], int]] = None):
        self.partition = partition
        self.pool = pool
        self.pages_per_lane = pages_per_lane
        self.page_quota = dict(page_quota or {})
        self.used_pages = used_pages

    @property
    def _page_bound(self) -> int:
        return self.pool.n_free_pages // self.pages_per_lane

    def _quota_bound(self, template: Optional[str]) -> int:
        """Free-lane bound after honoring other templates' page quotas."""
        free = self.pool.n_free_pages
        if self.page_quota and self.used_pages is not None:
            owed = sum(max(0, q - self.used_pages(t))
                       for t, q in self.page_quota.items() if t != template)
            free = max(0, free - owed)
        return free // self.pages_per_lane

    @property
    def n_free(self) -> int:
        """Free lanes, min-bounded by whole-lane page budgets."""
        return min(self.partition.n_free, self._page_bound)

    def n_free_for(self, template: Optional[str]) -> int:
        """Free lanes ``template`` may take, page-budget- and
        page-quota-bounded."""
        return min(self.partition.n_free_for(template),
                   self._quota_bound(template))

    def alloc(self, template: Optional[str]) -> int:
        """Take one lane for ``template`` (reserved pool first)."""
        return self.partition.alloc(template)

    def release(self, lane: int) -> None:
        """Return a lane to its home pool."""
        self.partition.release(lane)

    def benefits(self, lane: int, template: Optional[str]) -> bool:
        """Whether releasing ``lane`` raises ``n_free_for(template)``."""
        return self.partition.benefits(lane, template)

    def quarantine(self, lane: int) -> None:
        """Hold a crashed lane out of circulation (crash recovery)."""
        self.partition.quarantine(lane)

    def unquarantine(self, lane: int) -> None:
        """Return a quarantined lane to its home pool."""
        self.partition.unquarantine(lane)

    @property
    def quarantined(self) -> frozenset:
        """Snapshot of lanes currently held out of circulation."""
        return self.partition.quarantined

    @property
    def free_lanes(self) -> list[int]:
        """Sorted snapshot of every free lane (introspection)."""
        return self.partition.free_lanes


@dataclasses.dataclass
class PagedInferenceEngine(InferenceEngine):
    """Serving engine with paged KV compute + motion (module docstring).

    Raises ``ValueError`` for an architecture paged decode cannot run
    (:func:`~repro.models.paged_decode.supports_paged_decode`), before
    allocating anything: :class:`~repro.serving.engine.InferenceEngine`
    serves those.  ``page_size`` must divide ``max_len``;
    ``prefetch_pages`` is how many pages a restore splices synchronously
    before resuming decode (the tail streams in before the next tick).
    ``n_pages`` sizes the physical pool — default
    ``n_lanes * max_len / page_size`` (full provisioning); smaller values
    oversubscribe and lean on mid-decode eviction.
    ``use_kernel``/``interpret`` feed the registry dispatch policy for the
    paged attention op; ``interpret=None`` reads ``REPRO_KERNEL_INTERPRET``
    (the CI kernels job's switch).  ``prefix_share`` turns on
    prefix-granular cross-request KV sharing: synchronous admission
    consults a :class:`~repro.core.lane_policy.PrefixIndex`, aliases the
    page-aligned prompt prefix a resident lane already computed
    (copy-on-write, zero bytes moved) and prefills only the novel tail —
    ``prefix_hits`` / ``prefill_flops_saved`` count the wins.
    """

    page_size: int = 16
    prefetch_pages: int = 2
    n_pages: Optional[int] = None
    use_kernel: bool = True
    interpret: Optional[bool] = None
    prefix_share: bool = False

    def __post_init__(self):
        if not supports_paged_decode(self.arch.cfg):
            raise ValueError(
                f"paged decode cannot run {self.arch.cfg.name!r} (sliding-"
                "window, recurrent or encoder-decoder layers): serve it "
                "with InferenceEngine")
        if self.max_len % self.page_size:
            raise ValueError("page_size must divide max_len")
        if self.prefetch_pages < 1:
            raise ValueError("prefetch_pages must be >= 1")
        self.pages_per_lane = self.max_len // self.page_size
        if self.n_pages is None:
            self.n_pages = self.n_lanes * self.pages_per_lane
        if self.n_pages < self.pages_per_lane:
            raise ValueError(
                "n_pages must cover at least one lane "
                f"({self.pages_per_lane} pages)")
        super().__post_init__()
        self.pool = PagedKVPool(self.n_pages, self.page_size)
        quota = None
        if self.partition.shares:
            quota = {t: k * self.n_pages // self.n_lanes
                     for t, k in self.partition.shares.items()}
        self._kv_view = PagedKVView(self.partition, self.pool,
                                    self.pages_per_lane, page_quota=quota,
                                    used_pages=self._pages_used_by)
        # lane -> (host rows pytree, start_row, stop_row): restore tails
        # not yet on device; flushed before the next decode step.
        self._pending_restore: dict[int, tuple] = {}
        # lane -> (request key, template): identity for mid-decode eviction.
        self._lane_meta: dict[int, tuple] = {}
        # (lane, key, template, spilled) records for drain_evictions();
        # a registered on_lane_evicted callback bypasses the list.
        self._evicted: list[tuple] = []
        self.on_lane_evicted: Optional[Callable] = None
        self.page_evictions = 0   # lanes evicted by page pressure
        self.fused_folds = 0      # prefill chunks folded into decode ticks
        # Paged decode dispatches after which the previous pools read
        # deleted: the runtime took the donation and updated them in place.
        self.pool_in_place_ticks = 0
        self._fused_chunk: Optional[StagedPrefill] = None
        # Per-lane sampling params for the cross-template decode
        # megabatch: one dispatch covers every active lane, so the
        # sampling knobs ride along per lane (temperature 0 = greedy
        # argmax, the bit-identity default).
        self.lane_temps = np.zeros((self.n_lanes,), np.float32)
        self.lane_seeds = np.zeros((self.n_lanes,), np.int32)
        # Prefix sharing: index + counters.  The analytic per-token FLOPs
        # (2 * params, the standard dense-forward estimate) turns pages
        # aliased instead of prefilled into prefill_flops_saved.
        self.prefix_index: Optional[PrefixIndex] = (
            PrefixIndex(self.page_size) if self.prefix_share else None)
        self.prefix_hits = 0
        self.prefill_flops_saved = 0
        self.prefill_flops_total = 0
        self._flops_per_token = 2 * sum(
            int(np.prod(a.shape))
            for a in jax.tree_util.tree_leaves(self.params))
        # Partial eviction leaves refcounted prefix pages resident while
        # their spill entry lives on host; if the spill pool silently
        # drops the entry, those holds must be released or the pages leak.
        spill = self.partition.spill
        if spill is not None and getattr(spill, "on_drop", None) is None:
            spill.on_drop = self._release_entry_holds
        self._interpret = (self.interpret if self.interpret is not None
                           else registry.interpret_default())
        self._paged_decode, self._fused = paged_step_programs(
            self.arch, use_kernel=self.use_kernel, interpret=self._interpret)

        max_len = self.max_len

        @jax.jit
        def _shared_tail(params, cache, prefix_idx, tail_toks, start):
            # Prefix-hit tail prefill: gather the owner's aliased pages
            # into a dense batch-1 cache, then scan the NOVEL tail through
            # the ordinary decode step — one device program computing
            # exactly the positions the alias did not cover
            # (chunked-prefill reuse).  Compiles per (k_pages, tail_len).
            def seed_leaf(a):
                pre = a[:, prefix_idx].reshape(
                    a.shape[0], 1, -1, *a.shape[3:])
                dense = jnp.zeros((a.shape[0], 1, max_len) + a.shape[3:],
                                  a.dtype)
                return dense.at[:, :, : pre.shape[2]].set(pre)

            dcache = jax.tree_util.tree_map(seed_leaf, cache)

            def step(carry, tok):
                c, ln = carry
                logits, c = self.arch.decode_step(params, tok, c, ln)
                return (c, ln + 1), logits

            (dcache, _), logits = jax.lax.scan(
                step, (dcache, start), jnp.swapaxes(tail_toks, 0, 1))
            first = jnp.argmax(logits[-1], axis=-1).astype(jnp.int32)
            return first, dcache

        self._shared_tail_fn = _shared_tail

    def _build_cache(self):
        """Zeroed page pools ``(L, n_pages + 1, page_size, Hkv, hd)``, one
        per leaf of the dense cache, shaped from it without allocating it.
        Slot ``n_pages`` is the trash page inactive lanes scatter into;
        block tables never reference it and the pool never allocates it."""
        shape = (self.n_pages + 1, self.page_size)
        dense = jax.eval_shape(
            lambda: self.arch.init_cache(self.n_lanes, self.max_len))
        return jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape[:1] + shape + a.shape[3:], a.dtype),
            dense)

    @property
    def kv(self) -> PagedKVView:
        """The page-budget-bounded :class:`~repro.serving.kv.KVView`."""
        return self._kv_view

    # ---------------------------------------------------------- page tables
    def _pages_used_by(self, template: Optional[str]) -> int:
        """Physical pages currently held by ``template``'s lanes (the
        page-quota accounting hook the :class:`PagedKVView` consults)."""
        return sum(len(self.pool.pages(lane))
                   for lane, (_key, t) in self._lane_meta.items()
                   if t == template and self.pool.has_table(lane))

    def _open_table(self, lane: int, length: int, avoid=frozenset()) -> None:
        """(Re)create ``lane``'s pinned block table covering ``length``
        written rows plus the next write position, from any free pages
        (evicting other lanes under pressure, never one in ``avoid``)."""
        n = min(self.pages_per_lane, length // self.page_size + 1)
        self._make_room(n, avoid=set(avoid) | {lane})
        self.pool.alloc_table(lane, n=n)
        self.pool.pin(lane)

    def _ensure_pages(self, lane: int, n: int) -> None:
        n = min(n, self.pages_per_lane)
        have = len(self.pool.table(lane))
        if n > have:
            self._make_room(n - have, avoid={lane})
            self.pool.extend_table(lane, n=n - have)

    # --------------------------------------------------- page-pressure evict
    def _make_room(self, need: int, avoid=frozenset()) -> None:
        """Free pages until ``need`` are available, spilling the least-
        recently-touched lanes (their decode resumes after a restore) —
        the oversubscription pressure valve.  Raises when every table
        belongs to ``avoid`` (the requesting lanes themselves)."""
        while self.pool.n_free_pages < need:
            victim = next((k for k in self.pool.lru_tables()
                           if k not in avoid), None)
            if victim is None:
                raise RuntimeError(
                    "KV pool out of pages: every table is pinned by the "
                    "lanes requesting growth")
            self._evict_lane(int(victim))

    def _evict_lane(self, lane: int) -> None:
        """Spill one active lane to host under page pressure and record
        the eviction for the scheduler (callback or drain list)."""
        key, template = self._lane_meta.get(lane, (lane, None))
        spilled = self.spill(lane, key, template)
        self.page_evictions += 1
        cb = self.on_lane_evicted
        if cb is not None:
            cb(lane, key, template, spilled)
        else:
            self._evicted.append((lane, key, template, spilled))

    def drain_evictions(self) -> list[tuple]:
        """Return and clear ``(lane, key, template, spilled)`` records of
        page-pressure evictions since the last drain.  Schedulers that
        registered :attr:`on_lane_evicted` are notified synchronously at
        eviction time instead (before the lane can be reused) and never
        see these."""
        out, self._evicted = self._evicted, []
        return out

    # ------------------------------------------------------------ admission
    def admit(self, requests, template: Optional[str] = None
              ) -> tuple[int, int]:
        """Admission with prefix-granular sharing (when enabled).

        Runs ONLY on the synchronous admission path — the speculative
        prefill thread keeps the plain batched prefill, so the prefix
        index and page pool are never touched concurrently.  Two phases:
        requests whose prompts match no resident prefix are prefilled as
        one ordinary batch first (registering their prompts), then each
        remaining request re-checks the index — so a batch containing an
        owner plus its sharers still shares within the batch — and either
        takes the alias path (:meth:`_admit_prefix_hit`) or joins a final
        miss batch.
        """
        if self.prefix_index is None or not requests:
            return super().admit(requests, template)
        assert len(requests) <= self.n_free_for(template), \
            "admit() caller must respect n_free_for(template)"
        # Phase 1: classify.  A probe index over this batch's own prompts
        # catches sharers whose owner arrives in the SAME batch (the
        # owner is not resident yet, but will be once the miss batch
        # commits below).
        probe = PrefixIndex(self.page_size)
        misses, deferred = [], []
        for r in requests:
            toks = tuple(
                int(t) for t in np.asarray(r.prompt)[-self.max_prompt_len:])
            if (self._prefix_match(r) is not None
                    or probe.lookup(toks) is not None):
                deferred.append(r)
            else:
                probe.insert(id(r), toks)
                misses.append(r)
        shape = (len(requests), 0)
        if misses:
            shape = super().admit(misses, template)
        late = []
        for r in deferred:
            hit = self._prefix_match(r)
            if hit is None:  # owner left between the two phases
                late.append(r)
            else:
                self._admit_prefix_hit(r, template, *hit)
        if late:
            shape = super().admit(late, template)
        return shape

    def _prefix_match(self, r) -> Optional[tuple[int, int]]:
        """``(owner_lane, k_pages)`` for the longest resident page-aligned
        prefix of ``r``'s (truncated) prompt, or ``None``.  Stale index
        owners (no live table) are pruned on sight."""
        toks = tuple(
            int(t) for t in np.asarray(r.prompt)[-self.max_prompt_len:])
        while True:
            hit = self.prefix_index.lookup(toks)
            if hit is None:
                return None
            owner, k = hit
            if (self.pool.has_table(owner)
                    and len(self.pool.pages(owner)) >= k):
                return owner, k
            self.prefix_index.remove(owner)

    def _admit_prefix_hit(self, r, template: Optional[str],
                          owner: int, k: int) -> None:
        """Admit one request by aliasing ``k`` prefix pages from ``owner``
        and prefilling only the novel tail.

        The aliased pages are full prompt pages on both sides (the index
        only matches ``k * page_size < plen``), their contents a pure
        function of the shared tokens and absolute positions — so the
        alias is exact, zero bytes move (``kv_bytes_moved`` unchanged for
        them) and ``k * page_size`` token positions of prefill FLOPs are
        saved.  Decode writes land at positions ``>= plen``, i.e. in the
        request's private tail pages, never in a shared page — the COW
        guard in :meth:`decode_tick` enforces this defensively.
        """
        prompt = np.asarray(r.prompt)[-self.max_prompt_len:]
        plen = len(prompt)
        ps = self.page_size
        shared_rows = k * ps
        lane = self.partition.alloc(template)
        total = min(self.pages_per_lane, plen // ps + 1)
        need = total - k
        if need > 0:
            self._make_room(need, avoid={lane, owner})
        self.pool.share(owner, lane, n_pages=k)
        if need > 0:
            self.pool.extend_table(lane, n=need)
        self.pool.pin(lane)
        self._lane_meta[lane] = (getattr(r, "rid", lane), template)
        # One device program: gather the aliased prefix, scan the tail.
        prefix_idx = jnp.asarray(np.asarray(self.pool.pages(lane)[:k],
                                            np.int32))
        tail = jnp.asarray(prompt[None, shared_rows:], jnp.int32)
        first, dcache = self._shared_tail_fn(
            self.params, self.cache, prefix_idx, tail,
            jnp.asarray([shared_rows], jnp.int32))
        self._count_dispatch()
        # Scatter ONLY the tail pages into physical frames; the k aliased
        # pages cost zero bytes by construction.
        npg = max(1, self.pool.pages_for(plen))
        if npg > k:
            idx = jnp.asarray(np.asarray(self.pool.pages(lane)[k:npg],
                                         np.int32))

            def one(dst, src, idx=idx, k=k, npg=npg):
                s = src[:, 0, k * ps: npg * ps]
                return dst.at[:, idx].set(
                    s.reshape(s.shape[0], npg - k, ps, *s.shape[2:])
                    .astype(dst.dtype))

            self.cache = jax.tree_util.tree_map(one, self.cache, dcache)
            for a in jax.tree_util.tree_leaves(dcache):
                self.kv_bytes_moved += (a.dtype.itemsize * a.shape[0]
                                        * (npg - k) * ps
                                        * int(np.prod(a.shape[3:])))
        self.prefix_index.insert(lane, prompt)
        first_tok = int(self._host(first)[0])
        r.lane = lane
        r.generated.append(first_tok)
        ln = self._host(self.lengths, copy=True)
        lt = self._host(self.last_token, copy=True)
        ln[lane] = plen
        lt[lane] = first_tok
        self.lengths = jnp.asarray(ln)
        self.last_token = jnp.asarray(lt)
        self.active[lane] = True
        self.lane_temps[lane] = getattr(r, "temperature", 0.0)
        self.lane_seeds[lane] = getattr(r, "sample_seed", 0)
        self.prefill_calls += 1
        self.prefix_hits += 1
        self.prefill_flops_saved += shared_rows * self._flops_per_token
        self.prefill_flops_total += plen * self._flops_per_token

    def _release_entry_holds(self, key, template: Optional[str],
                             entry: dict) -> None:
        """Spill-pool ``on_drop`` hook: a dropped entry's prefix-page
        holds (partial eviction) return to the pool."""
        pages = entry.get("prefix_pages")
        if pages:
            self.pool.decref_pages(pages)

    def _insert_staged(self, staged: StagedPrefill, lanes: list[int]) -> None:
        """Page-granular commit splice: per-request tables are opened
        (never evicting a batch-mate) and the pages each prompt fills are
        scattered into physical frames (:meth:`_scatter_prompt`)."""
        ps = self.page_size
        avoid = set(lanes)
        for i, lane in enumerate(lanes):
            r = staged.requests[i]
            plen = int(staged.plens[i])
            self._open_table(lane, plen, avoid=avoid)
            self._lane_meta[lane] = (getattr(r, "rid", lane), staged.template)
            self.lane_temps[lane] = getattr(r, "temperature", 0.0)
            self.lane_seeds[lane] = getattr(r, "sample_seed", 0)
            self.prefill_flops_total += plen * self._flops_per_token
            if self.prefix_index is not None:
                # This lane now owns resident KV for exactly the last
                # `plen` prompt tokens (cache-relative positions 0..plen):
                # register them so later prompts can alias the prefix.
                self.prefix_index.insert(
                    lane, np.asarray(r.prompt)[-plen:])
            npg = max(1, self.pool.pages_for(plen))
            self._scatter_prompt(staged.cache, i, self.pool.pages(lane)[:npg],
                                 staged.shape[1])
            for a in jax.tree_util.tree_leaves(staged.cache):
                self.kv_bytes_moved += (a.dtype.itemsize * a.shape[0]
                                        * npg * ps * int(np.prod(a.shape[3:])))

    def _scatter_prompt(self, src, i: int, pages, width: int) -> None:
        """Scatter row ``i`` of the staged KV ``src`` into ``pages``.

        The scatter covers the ``ceil(width / page_size)`` pages that the
        staged program's prompt axis (``width``) spans, the pages past
        ``pages`` going to the trash page, so the insert compiles once per
        staged shape and not once per page count."""
        ps = self.page_size
        n = self.pool.pages_for(width)
        idx = jnp.asarray(np.asarray(
            list(pages) + [self.n_pages] * (n - len(pages)), np.int32))

        def one(dst, s):
            s = s[:, i, : n * ps]
            return dst.at[:, idx].set(
                s.reshape(s.shape[0], n, ps, *s.shape[2:]).astype(dst.dtype))

        self.cache = jax.tree_util.tree_map(one, self.cache, src)

    def _warm_inserts(self, src) -> None:
        """Compile the page insert of every rung: ``src`` scattered whole
        into the trash page."""
        for rung in self.prefill_rungs():
            self._scatter_prompt(src, 0, [], rung)

    # ------------------------------------------------------- fused dispatch
    def stage_chunk(self, staged: StagedPrefill) -> bool:
        """Adopt ``staged``'s next pending chunk into this tick's decode
        dispatch (fused megabatch): the chunk's decode-path scan and the
        paged decode batch compile into ONE device program, so overlap
        mode pays one dispatch per tick boundary instead of two.  Returns
        ``False`` when fusion does not apply (a chunk already staged,
        nothing pending, or no active decode batch to fuse with) — the
        caller then advances the chunk on its own.
        """
        if self._fused_chunk is not None:
            return False
        part = staged
        if staged.parts:
            part = next((p for p in staged.parts if not p.complete), None)
        if part is None or part.complete or not part.pending:
            return False
        if not self.active.any():
            return False
        self._fused_chunk = part
        return True

    # ----------------------------------------------------------------- tick
    def _prepare_decode(self) -> Optional[tuple]:
        """Host work of a paged decode tick before its dispatch: flush
        restore tails, grow block tables (evicting under page pressure),
        fence copy-on-write pages, and upload the tables, the lane mask and
        the sampling parameters.  The step is fused with any staged
        prefill chunk."""
        self._flush_restores()
        part, self._fused_chunk = self._fused_chunk, None
        if not self.active.any():
            if part is not None:  # nothing to fuse with: plain resume
                self.prefill_resume(part)
            return None
        for lane in np.nonzero(self.active)[0]:
            lane = int(lane)
            if not self.active[lane]:
                continue  # evicted by an earlier lane's growth this tick
            length = int(self._host(self.lengths)[lane])
            self._ensure_pages(lane, length // self.page_size + 1)
            if self.active[lane]:
                self._cow_guard(lane, length)
        if not self.active.any():  # growth pressure evicted every lane
            if part is not None:
                self.prefill_resume(part)
            return None
        return (part, self._device_tables(), jnp.asarray(self.active),
                jnp.asarray(self.lane_temps), jnp.asarray(self.lane_seeds))

    def _dispatch_decode(self, part, tables, active_dev, temps, seeds):
        """Dispatch the paged decode step prepared by
        :meth:`_prepare_decode` (fused with ``part``'s next chunk when one
        is staged); returns the new tokens, still on the device."""
        old_pools = jax.tree_util.tree_leaves(self.cache)
        if part is None:
            nxt, self.cache = self._paged_decode(
                self.params, self.last_token, self.cache, self.lengths,
                tables, active_dev, temps, seeds)
        else:
            toks = part.pending.pop(0)
            nxt, self.cache, cfirst, part.cache, part.lengths_dev = \
                self._fused(self.params, self.last_token, self.cache,
                            self.lengths, tables, active_dev, temps, seeds,
                            jnp.asarray(toks), part.cache, part.lengths_dev)
            if not part.pending:
                part.first = cfirst
            self.fused_folds += 1
        if all(a.is_deleted() for a in old_pools):
            self.pool_in_place_ticks += 1
        self._advance_lanes(nxt)
        return nxt

    def _device_tables(self):
        """All lanes' block tables as one (n_lanes, pages_per_lane) int32
        device array (tableless lanes read page 0, masked by length)."""
        tabs = np.zeros((self.n_lanes, self.pages_per_lane), np.int32)
        for lane in range(self.n_lanes):
            if self.pool.has_table(lane):
                pages = self.pool.pages(lane)
                tabs[lane, : len(pages)] = pages
        return jnp.asarray(tabs)

    def _cow_guard(self, lane: int, length: int) -> None:
        """Copy-on-write fence for this tick's KV write.

        Decode scatters the new token's KV into the page backing position
        ``min(length, max_len - 1)``; if that page is aliased (refcount
        above 1), fork a private copy first — pool placement via
        :meth:`PagedKVPool.fork_page`, contents via one device copy — so
        the write can never be observed by the other readers.  With
        prefix sharing only FULL prompt pages are aliased and decode
        writes land past the prompt, so this fires only for exotic
        sharing set up directly against the pool — but the invariant is
        enforced here, not assumed.
        """
        slot = min(length, self.max_len - 1) // self.page_size
        pages = self.pool.pages(lane)
        if slot >= len(pages) or self.pool.page_ref(pages[slot]) <= 1:
            return
        if self.pool.n_free_pages < 1:
            self._make_room(1, avoid={lane})
        old, new = self.pool.fork_page(lane, slot)
        self.cache = jax.tree_util.tree_map(
            lambda a: a.at[:, new].set(a[:, old]), self.cache)

    def retire(self, lane: int) -> None:
        """Free the lane's block table along with the lane."""
        self._pending_restore.pop(lane, None)
        self._lane_meta.pop(lane, None)
        if self.prefix_index is not None:
            self.prefix_index.remove(lane)
        if self.pool.has_table(lane):
            self.pool.free_table(lane)
        self.lane_temps[lane] = 0.0
        self.lane_seeds[lane] = 0
        super().retire(lane)

    # ---------------------------------------------------------------- spill
    def spill(self, lane: int, key, template: Optional[str] = None) -> bool:
        """Stage only the lane's VALID pages to host (vs the dense
        engine's full ``max_len`` rows) — the page-granularity bytes win.
        The pages are gathered from their physical frames into contiguous
        host rows.

        **Partial eviction**: leading pages still aliased by another live
        table (a shared prefix) are NOT copied — they stay resident, kept
        alive by an extra refcount the spill entry holds
        (``prefix_pages``), and cost zero spill bytes.  Only the lane's
        private tail rows (from ``tail_start``) move to host; restore
        re-adopts the resident prefix and splices just the tail back.
        """
        pool = self.partition.spill
        if pool is None or not pool.accepts(template):
            self.retire(lane)
            return False
        self._flush_restores(lane)  # device rows must be whole before copy
        length = int(self._host(self.lengths)[lane])
        ps = self.page_size
        npg = max(1, self.pool.pages_for(length))
        n_rows = min(self.max_len, npg * ps)
        pages = self.pool.pages(lane)[:npg]
        keep = min(self.pool.shared_prefix_pages(lane), npg)
        tail_start = keep * ps
        prefix_pages = list(pages[:keep])
        idx = jnp.asarray(np.asarray(pages[keep:npg], np.int32))
        rows = jax.tree_util.tree_map(
            lambda a: self._host(
                a[:, idx].reshape(a.shape[0], (npg - keep) * ps,
                                  *a.shape[3:])
                [:, : n_rows - tail_start]),
            self.cache)
        entry = {
            "rows": rows,
            "n_rows": n_rows,
            "length": length,
            "last": int(self._host(self.last_token)[lane]),
            "tail_start": tail_start,
            "prefix_pages": prefix_pages,
            "temp": float(self.lane_temps[lane]),
            "seed": int(self.lane_seeds[lane]),
        }
        self.kv_bytes_moved += sum(
            a.nbytes for a in jax.tree_util.tree_leaves(entry["rows"]))
        if prefix_pages:
            # The entry's hold: the prefix pages survive retire() below
            # (which drops the lane's own references) and any sibling
            # retirements, until the entry restores or is dropped.
            self.pool.incref_pages(prefix_pages)
        staged = pool.put(key, template, entry)
        self.retire(lane)
        return staged

    def try_restore(self, key, template: Optional[str] = None) -> Optional[int]:
        """Restore spilled pages: first ``prefetch_pages`` now, tail
        queued for the next tick — decode resumes after the prefetch
        instead of waiting for the whole lane.  The pages must be free
        RIGHT NOW (a restore never evicts — that would thrash against the
        eviction that spilled it)."""
        pool = self.partition.spill
        if pool is None or key not in pool or self.n_free_for(template) <= 0:
            return None
        entry = pool.take(key)
        if entry is None:  # raced away (defensive: tick loop is 1-threaded)
            return None
        rows = entry["rows"]
        n_rows = entry["n_rows"]
        tail_start = entry.get("tail_start", 0)
        prefix_pages = entry.get("prefix_pages") or []
        k = len(prefix_pages)
        total = min(self.pages_per_lane,
                    entry["length"] // self.page_size + 1)
        need = max(0, total - k)
        if self.pool.n_free_pages < need:
            pool.put(key, template, entry)  # not enough pages yet
            return None
        lane = self.partition.alloc(template)
        if k:
            # Re-adopt the still-resident shared prefix: the entry's
            # refcount hold TRANSFERS into the new table (no copy, no
            # incref), and only the private tail needs page claims +
            # a host→device splice.
            self.pool.adopt_table(lane, prefix_pages)
            if need > 0:
                self.pool.extend_table(lane, n=need)
            self.pool.pin(lane)
        else:
            self._open_table(lane, entry["length"])
        self._lane_meta[lane] = (key, template)
        self.lane_temps[lane] = entry.get("temp", 0.0)
        self.lane_seeds[lane] = entry.get("seed", 0)
        head = min(n_rows, tail_start + self.prefetch_pages * self.page_size)
        self._write_rows(lane, rows, tail_start, head, base=tail_start)
        if head < n_rows:
            self._pending_restore[lane] = (rows, head, n_rows, tail_start)
        ln = self._host(self.lengths, copy=True)
        lt = self._host(self.last_token, copy=True)
        ln[lane] = entry["length"]
        lt[lane] = entry["last"]
        self.lengths = jnp.asarray(ln)
        self.last_token = jnp.asarray(lt)
        self.active[lane] = True
        return lane

    def _write_rows(self, lane: int, rows, start: int, stop: int,
                    base: int = 0) -> None:
        """Scatter host rows covering logical positions [start, stop)
        (page-aligned bounds) into ``lane``'s physical frames, with byte
        accounting.  ``base`` is the logical position of
        ``rows``' first row — a partial eviction's host copy starts at
        ``tail_start``, not 0."""
        if stop <= start:
            return
        ps = self.page_size
        p0, p1 = start // ps, stop // ps
        idx = jnp.asarray(self.pool.pages(lane)[p0:p1])

        def one(dst, src, idx=idx, p0=p0, p1=p1):
            s = jnp.asarray(src)[:, start - base: stop - base]
            return dst.at[:, idx].set(
                s.reshape(s.shape[0], p1 - p0, ps, *s.shape[2:])
                .astype(dst.dtype))

        self.cache = jax.tree_util.tree_map(one, self.cache, rows)
        for a in map(np.asarray, jax.tree_util.tree_leaves(rows)):
            self.kv_bytes_moved += (a.dtype.itemsize * a.shape[0]
                                    * (stop - start)
                                    * int(np.prod(a.shape[2:])))

    def _flush_restores(self, lane: Optional[int] = None) -> None:
        """Splice queued restore tails into the page arrays (all lanes, or
        one lane about to be copied out again)."""
        if lane is not None:
            items = ([(lane, self._pending_restore.pop(lane))]
                     if lane in self._pending_restore else [])
        else:
            items = list(self._pending_restore.items())
            self._pending_restore.clear()
        for ln_, (rows, start, stop, base) in items:
            self._write_rows(ln_, rows, start, stop, base=base)

    # ------------------------------------------------------------ paged view
    def paged_view(self, stack: str = "layers") -> Optional[dict]:
        """The active lanes' KV as the paged-kernel layout.

        Returns ``{"k_pages", "v_pages", "block_tables", "lengths",
        "lanes"}`` for one transformer ``stack`` (layer 0): the live page
        arrays, the layout decode_tick consumes.  ``None`` when the stack
        has no k/v leaves or nothing is active.
        """
        entry = self.cache.get(stack) if hasattr(self.cache, "get") else None
        if not entry or "k" not in entry or not self.active.any():
            return None
        lanes = [int(x) for x in np.nonzero(self.active)[0]]
        tables = np.stack([self.pool.block_table(lane, self.pages_per_lane)
                           for lane in lanes])
        lengths = self._host(self.lengths)[lanes].astype(np.int32)
        return {"k_pages": entry["k"][0], "v_pages": entry["v"][0],
                "block_tables": jnp.asarray(tables),
                "lengths": jnp.asarray(lengths), "lanes": lanes}
