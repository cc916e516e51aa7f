"""Inference engine: lane-based KV cache + jitted prefill/decode steps.

The engine owns ``n_lanes`` decode slots (the thread-pool "connections" of
the paper's Fig. 3, device edition).  Admission inserts a prefilled
request's KV into a free lane; every engine tick runs ONE batched decode
step over all lanes (inactive lanes are masked).  The admission policy —
how many queued requests to prefill together — is the scheduler's call
(:mod:`repro.serving.scheduler`), where the paper's §5.2 strategies live.

Two production mechanisms live at this layer:

* **Per-template KV partitioning** (``kv_shares={template: n_lanes}``).
  Lanes are a shared cache: without reservations a burst on one template
  can occupy every free lane and starve the others' cache residency (the
  serving analogue of one tenant evicting everyone's buffer pool).  A
  :class:`KVPartition` reserves a fixed lane count per named template;
  reserved lanes are only ever allocated to (and released back to) their
  owning template, the remainder form a shared pool any template may use.
  :func:`proportional_shares` derives a share map from
  :class:`~repro.core.lane_policy.LanePolicy` ``lane_weights``.
* **Split prefill dispatch** (:meth:`InferenceEngine.prefill_dispatch` /
  :meth:`InferenceEngine.commit_prefill`).  ``admit`` = dispatch + commit
  in one call; the split form lets the scheduler *dispatch* the next
  batch's padded prefill while the current decode tick runs (JAX dispatch
  is asynchronous — the jitted call returns before the device finishes)
  and *commit* the staged KV into lanes at the next tick boundary.
  Dispatch mutates no engine or request state, so an uncommitted
  :class:`StagedPrefill` can simply be dropped (speculation abort).

Two further mechanisms extend the split dispatch path:

* **Chunked prefill** (``prefill_dispatch(..., chunk=n)``).  One huge
  prompt dispatched as a single prefill stalls the next commit boundary
  for its full duration.  With ``chunk``, the prompt is processed as
  resumable chunks: the first ``chunk`` tokens go through the ordinary
  prefill, every later chunk extends the staged KV through the decode
  path (:meth:`InferenceEngine.prefill_resume`, one scan of
  ``decode_step`` per chunk) — so the scheduler can interleave decode
  ticks between chunks instead of stalling on one monolithic prefill.
  The staged result is bit-for-bit the computation the one-shot path
  performs (same causal attention, incrementally), just split in time.
* **Host KV spill** (``KVPartition(spill=HostSpillPool(...))``).
  Evicting a running request (straggler force-retire) normally drops its
  KV, so re-admission pays a full re-prefill AND restarts generation.
  With a spill pool the evicted lane's KV rows are staged to host memory
  (:meth:`InferenceEngine.spill`); re-admission of the same request
  restores the rows into a fresh lane (:meth:`InferenceEngine.try_restore`)
  and decode continues where it stopped.

**Prefill ladder** (``prefill_dispatch(..., template=t)``).  Each prompt
runs as its own ``(1, rung)`` prefill program, the rung being its
power-of-two length bucket between :data:`RUNG_FLOOR` and
``max_prompt_len``; a batch's programs are enqueued back to back under one
aggregate :class:`StagedPrefill`.  The template's first dispatch runs
every rung once on zeros and its first commit compiles their inserts, so
steady traffic compiles nothing.  Dispatches without a template pad one
batch to power-of-two buckets.
"""
from __future__ import annotations

import dataclasses
import threading
from collections import OrderedDict
from functools import partial
from typing import Callable, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.attention import query_chunk
from repro.models.paged_decode import supports_paged_decode
from repro.models.registry import Arch
from repro.serving.tracing import span

__all__ = ["HostSpillPool", "InferenceEngine", "KVPartition", "StagedPrefill",
           "proportional_shares"]

_SHARED = "__shared__"  # KVPartition pool key for unreserved lanes

# The prefill ladder's shortest rung.  A v5e computes 197 TFLOP/s in bf16
# and streams 819 GB/s, a ridge point of about 240 FLOP a byte: below about
# 240 rows a bf16 prefill is bound by streaming its weights, so fewer rows
# save no time.  From 256 rows up it is bound by compute, so k one-prompt
# programs cost what one k-row program costs, and a prompt pays only for
# its own rung.  A property of the chip, not a setting.
RUNG_FLOOR = 256


def _bucket(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


def proportional_shares(weights: Mapping[str, float], n_lanes: int,
                        reserve: float = 0.5) -> dict[str, int]:
    """Derive ``kv_shares`` from :class:`LanePolicy` ``lane_weights``.

    Distributes ``floor(n_lanes * reserve)`` reserved lanes across the
    weighted templates proportionally to their weights (largest-remainder
    rounding, name breaking ties), leaving the rest as the shared pool —
    so the templates the operator already marked as mattering
    (``lane_weights``) get KV residency guarantees in the same proportion
    as their service shares.  Zero-lane templates are dropped from the map
    (they use the shared pool like any unreserved template).
    """
    if not 0.0 <= reserve <= 1.0:
        raise ValueError("reserve must be in [0, 1]")
    budget = int(n_lanes * reserve)
    if not weights or budget <= 0:
        return {}
    for t, w in weights.items():
        if w <= 0:
            raise ValueError(f"weights[{t!r}] must be > 0, got {w}")
    total = float(sum(weights.values()))
    quotas = {t: budget * w / total for t, w in weights.items()}
    shares = {t: int(q) for t, q in quotas.items()}
    remaining = budget - sum(shares.values())
    for t in sorted(quotas, key=lambda t: (-(quotas[t] - shares[t]), t)):
        if remaining <= 0:
            break
        shares[t] += 1
        remaining -= 1
    return {t: s for t, s in shares.items() if s > 0}


class HostSpillPool:
    """Host-side LRU staging area for evicted decode-lane KV.

    Keys are request identities (the scheduler uses ``Request.rid``); each
    entry holds one lane's KV rows plus the decode cursor (length + last
    token), copied to host memory at eviction time.  ``max_entries``
    bounds the pool globally; ``budget_for`` (e.g.
    :meth:`~repro.core.lane_policy.LanePolicy.spill_budget_for`) bounds
    entries *per template*, so one template's straggler churn cannot evict
    everyone else's staged KV — the host-memory analogue of the lane
    reservations above.  Over-budget inserts evict the least-recently-used
    entry (of that template for the per-template bound, globally for
    ``max_entries``); a re-admitted request whose entry survived restores
    instead of re-prefilling.

    Thread-safe (a lock per op): the scheduler spills/restores from its
    tick loop, but introspection (stats, ``in``) may come from anywhere.

    ``on_drop`` is invoked (under the pool lock) for every entry the pool
    discards without a restore — stale duplicates, per-template budget
    evictions and global LRU evictions — with ``(key, template, entry)``.
    Entries may own resources beyond host bytes: a partial eviction's
    entry holds refcounts on the shared prefix pages it left resident in
    the device pool, and dropping the entry must release them or the
    pages leak.  ``take`` never triggers it (the restoring caller owns
    the entry's resources from then on).
    """

    def __init__(self, max_entries: int = 32,
                 budget_for: Optional[Callable[[Optional[str]],
                                               Optional[int]]] = None,
                 on_drop: Optional[Callable[[object, Optional[str], dict],
                                            None]] = None):
        if max_entries < 1:
            raise ValueError("max_entries must be >= 1")
        self.max_entries = max_entries
        self.budget_for = budget_for
        self.on_drop = on_drop
        self._lock = threading.Lock()
        self._lru: "OrderedDict[object, tuple[Optional[str], dict]]" = OrderedDict()
        self.spilled = 0    # entries accepted
        self.restored = 0   # entries taken back by a re-admission
        self.dropped = 0    # entries evicted (LRU / budget) before restore

    def _drop(self, key, template: Optional[str], entry: dict) -> None:
        """Account one discarded entry and release its resources."""
        self.dropped += 1
        if self.on_drop is not None:
            self.on_drop(key, template, entry)

    def accepts(self, template: Optional[str]) -> bool:
        """Whether a new entry for ``template`` would be stored at all —
        ``False`` only for a zero-budget (fenced) template.  Callers
        check this BEFORE paying the device→host KV copy; a positive
        budget always admits the new entry (evicting older ones)."""
        budget = self.budget_for(template) if self.budget_for else None
        return budget is None or budget > 0

    def put(self, key, template: Optional[str], entry: dict) -> bool:
        """Stage one evicted lane's KV under ``key`` (replacing any stale
        entry for the same key), evicting LRU entries that break the
        global or per-template budget.  Returns whether the entry was
        stored (``False`` for a zero-budget fenced template)."""
        with self._lock:
            if key in self._lru:
                stale_t, stale_e = self._lru.pop(key)
                self._drop(key, stale_t, stale_e)  # the new KV wins
            budget = self.budget_for(template) if self.budget_for else None
            if budget is not None and budget <= 0:
                self._drop(key, template, entry)  # template fenced out
                return False
            if budget is not None:
                mine = [k for k, (t, _) in self._lru.items() if t == template]
                while len(mine) >= budget:
                    victim = mine.pop(0)  # oldest of THIS template
                    v_t, v_e = self._lru.pop(victim)
                    self._drop(victim, v_t, v_e)
            while len(self._lru) >= self.max_entries:
                v_key, (v_t, v_e) = self._lru.popitem(last=False)
                self._drop(v_key, v_t, v_e)
            self._lru[key] = (template, entry)
            self.spilled += 1
            return True

    def take(self, key) -> Optional[dict]:
        """Remove and return ``key``'s staged entry (``None`` on miss)."""
        with self._lock:
            hit = self._lru.pop(key, None)
            if hit is None:
                return None
            self.restored += 1
            return hit[1]

    def __contains__(self, key) -> bool:
        with self._lock:
            return key in self._lru

    def __len__(self) -> int:
        with self._lock:
            return len(self._lru)

    def snapshot(self) -> dict:
        """Counters + occupancy (introspection/benchmark reporting)."""
        with self._lock:
            return {"entries": len(self._lru), "spilled": self.spilled,
                    "restored": self.restored, "dropped": self.dropped}


class KVPartition:
    """Per-template lane reservations over a fixed set of decode lanes.

    ``shares[template] = k`` pins ``k`` specific lanes to ``template``:
    they are allocated only to that template and return to its pool on
    release, so no burst elsewhere can take them.  Unreserved lanes form
    the shared pool; a reserved template drains its own pool first and
    then competes for shared lanes like everyone else, while a template
    with no reservation sees only the shared pool.

    Single-threaded by design (the scheduler tick loop): allocation and
    release happen on the scheduler thread only — the speculative prefill
    thread never touches the partition (dispatch is stateless; see
    :meth:`InferenceEngine.prefill_dispatch`).
    """

    def __init__(self, n_lanes: int, shares: Optional[Mapping[str, int]] = None,
                 spill: Optional[HostSpillPool] = None):
        self.spill = spill  # host-side LRU for evicted lanes' KV (optional)
        shares = dict(shares or {})
        for t, k in shares.items():
            if t == _SHARED:
                raise ValueError(f"{_SHARED!r} is a reserved pool name")
            if k < 0:
                raise ValueError(f"kv_shares[{t!r}] must be >= 0, got {k}")
        if sum(shares.values()) > n_lanes:
            raise ValueError(
                f"kv_shares reserve {sum(shares.values())} lanes but the "
                f"engine only has {n_lanes}")
        self.shares = {t: k for t, k in shares.items() if k > 0}
        lanes = list(range(n_lanes))
        self._home: dict[int, str] = {}
        self._free: dict[str, list[int]] = {}
        for t, k in self.shares.items():
            pool = [lanes.pop(0) for _ in range(k)]
            for lane in pool:
                self._home[lane] = t
            self._free[t] = pool
        self._free[_SHARED] = lanes
        self._quarantined: set[int] = set()

    def quarantine(self, lane: int) -> None:
        """Remove ``lane`` from circulation: it will not be allocated again
        until :meth:`unquarantine` returns it to its home pool.  Used by
        crash recovery — a lane whose device step faulted sits out a
        cooldown instead of immediately hosting the next request.  The
        lane must currently be free (retire/release it first)."""
        for pool in self._free.values():
            if lane in pool:
                pool.remove(lane)
                self._quarantined.add(lane)
                return
        if lane in self._quarantined:
            return
        raise ValueError(f"lane {lane} is not free; cannot quarantine")

    def unquarantine(self, lane: int) -> None:
        """Return a quarantined lane to its home pool (no-op otherwise)."""
        if lane in self._quarantined:
            self._quarantined.discard(lane)
            self.release(lane)

    @property
    def quarantined(self) -> frozenset:
        """Snapshot of lanes currently held out of circulation."""
        return frozenset(self._quarantined)

    @property
    def n_free(self) -> int:
        """Total free lanes across every pool."""
        return sum(len(p) for p in self._free.values())

    def n_free_for(self, template: Optional[str]) -> int:
        """Free lanes ``template`` may allocate right now: its own reserved
        pool (if any) plus the shared pool.  ``None`` (untemplated
        admission) sees only the shared pool."""
        n = len(self._free[_SHARED])
        if template is not None:
            n += len(self._free.get(template, ()))
        return n

    def alloc(self, template: Optional[str]) -> int:
        """Take one lane for ``template`` — its reserved pool first (keeps
        the shared pool liquid for everyone else), then shared.  Raises
        ``IndexError`` when neither pool has a free lane."""
        pool = self._free.get(template) if template is not None else None
        if not pool:
            pool = self._free[_SHARED]
        return pool.pop(0)

    def release(self, lane: int) -> None:
        """Return a lane to its home pool (owning template's reservation,
        or shared for unreserved lanes)."""
        self._free[self._home.get(lane, _SHARED)].append(lane)

    def benefits(self, lane: int, template: Optional[str]) -> bool:
        """Whether releasing ``lane`` would raise ``n_free_for(template)``:
        true for shared lanes and for ``template``'s own reserved lanes.
        The scheduler's speculative sizing uses this to bet only on
        retirements that can actually serve the speculated template —
        a lane going home to ANOTHER template's reservation is a
        guaranteed miss, not a speculation."""
        home = self._home.get(lane, _SHARED)
        return home == _SHARED or home == template

    @property
    def free_lanes(self) -> list[int]:
        """Sorted snapshot of every free lane (introspection/debugging)."""
        return sorted(lane for p in self._free.values() for lane in p)


@dataclasses.dataclass
class StagedPrefill:
    """A dispatched-but-uncommitted prefill batch.

    Produced by :meth:`InferenceEngine.prefill_dispatch`; holds the padded
    batch's device results (``first`` tokens + KV ``cache`` — possibly
    still being computed: JAX dispatch is asynchronous) and the request
    list, but no engine state.  :meth:`InferenceEngine.commit_prefill`
    materializes it into lanes; dropping it instead is a zero-cost abort
    (beyond the device work already paid, which the scheduler reports via
    ``observe_abort``).
    """

    template: Optional[str]
    requests: list
    first: object   # (bsz,) int32 device array — argmax token 0 per row
    cache: object   # KV pytree, batch axis sized to the padded bucket
    plens: np.ndarray
    shape: tuple[int, int]  # the padded (batch, prompt) bucket dispatched
    # Chunked dispatch state (``prefill_dispatch(..., chunk=)``): token
    # chunks not yet folded into the staged cache, and the device-side
    # lengths cursor the next :meth:`InferenceEngine.prefill_resume` call
    # extends from.  ``first`` stays ``None`` until the final chunk.
    pending: list = dataclasses.field(default_factory=list)
    lengths_dev: object = None
    # Batched-chunk dispatch (``prefill_dispatch([r0, r1, ...], chunk=)``):
    # one single-request staged prefill per prompt.  The parent is a pure
    # aggregate — ``cache``/``first`` stay ``None``; resume advances one
    # part-chunk per call, commit delegates to the parts in order.
    parts: list = dataclasses.field(default_factory=list)

    @property
    def complete(self) -> bool:
        """Whether every chunk has been processed (always true for the
        one-shot dispatch path) — only a complete staged prefill may be
        committed."""
        if self.parts:
            return all(p.complete for p in self.parts)
        return not self.pending


@dataclasses.dataclass
class InferenceEngine:
    """Lane-based KV cache + jitted prefill/decode (see module docstring).

    ``kv_shares`` reserves decode lanes per template
    (:class:`KVPartition`); the default ``None`` keeps every lane in the
    shared pool (pre-partitioning behaviour).  ``kv_spill`` attaches a
    :class:`HostSpillPool` so evicted lanes stage their KV to host memory
    (:meth:`spill` / :meth:`try_restore`) instead of dropping it.
    """

    arch: Arch
    params: object
    n_lanes: int = 8
    max_prompt_len: int = 64
    max_len: int = 128
    kv_shares: Optional[Mapping[str, int]] = None
    kv_spill: Optional[HostSpillPool] = None

    def __post_init__(self):
        self.cache = self._build_cache()
        self.lengths = jnp.zeros((self.n_lanes,), jnp.int32)
        self.active = np.zeros((self.n_lanes,), bool)
        self.last_token = jnp.zeros((self.n_lanes,), jnp.int32)
        self.partition = KVPartition(self.n_lanes, self.kv_shares,
                                     spill=self.kv_spill)
        self.decode_steps = 0
        self.prefill_calls = 0
        # KV bytes copied across the device boundary (spill + restore).
        # The dense engine moves whole lanes (max_len rows regardless of
        # how many are valid); the paged engine moves only valid pages —
        # this counter is what the Part 8 A/B compares.
        self.kv_bytes_moved = 0
        # Jitted model-step device programs launched (decode ticks, prefill
        # batches, chunk extends, fused ticks).  The fused-dispatch gate
        # asserts a paged decode tick that also folds a staged prefill
        # chunk raises this by exactly 1 — one device program, not two.
        # Lock-guarded: the speculative prefill thread dispatches too.
        self.dispatches = 0
        self._dispatch_lock = threading.Lock()
        # Blocking reads of device arrays into host memory, each of which
        # waits for the device (:meth:`_host`): a paged decode tick reads
        # one length per active lane, then its new tokens.
        self.host_reads = 0
        # Real prompt tokens and padded bucket slots (batch x prompt, summed
        # over an aggregate's parts) of every prefill dispatch; 1 - real /
        # bucket is the padding share.
        self.prefill_real_tokens = 0
        self.prefill_bucket_tokens = 0
        # rung -> (1, rung) ladder programs dispatched.
        self.prefill_rung_programs: dict[int, int] = {}
        # Query chunks of the prefill's full-sequence attention, per
        # layer, summed over prefill dispatches (0 while unchunked).
        self.prefill_attn_chunks = 0
        # template -> monotone max of the (batch bucket, largest rung) its
        # ladder dispatches spanned: a record of the widest batch a
        # template has sent (warm-ups size page inserts from it); no
        # prefill dispatches it.
        self.template_shapes: dict[str, tuple[int, int]] = {}
        # Right padding leaves a prompt's logits and KV exact only where
        # every layer attends over the full causal context: windowed ring
        # buffers and recurrent state take in the pad tokens, so there the
        # ladder pads no further than the prompt's power-of-two bucket.
        self._rung_floor = (RUNG_FLOOR if supports_paged_decode(self.arch.cfg)
                            else 1)
        # Set once the template path's first dispatch has run every rung
        # program on zeros; the staged KV of the last of those runs waits
        # for the first commit to compile the inserts (on the thread that
        # owns the cache, which a decode step may be donating meanwhile).
        self._rungs_warm = False
        self._warm_src = None

        @partial(jax.jit, static_argnums=())
        def _decode(params, token, cache, lengths):
            logits, new_cache = self.arch.decode_step(params, token, cache, lengths)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return nxt, new_cache

        self._decode = _decode

        from repro.models import transformer as _tf

        @partial(jax.jit, static_argnums=(3,))
        def _prefill(params, tokens, plens, max_len):
            logits, cache = _tf.prefill(
                self.arch.cfg, params, tokens=tokens, max_len=max_len,
                return_all_logits=True,
            )
            last = jnp.take_along_axis(
                logits, (plens - 1)[:, None, None], axis=1
            )[:, 0]
            nxt = jnp.argmax(last, axis=-1).astype(jnp.int32)
            return nxt, cache

        self._prefill = _prefill

        @partial(jax.jit, static_argnums=())
        def _extend(params, cache, toks, lengths):
            # toks: (B, C) — C further prompt tokens per row, fed through
            # the decode path one position at a time (a lax.scan, ONE
            # compiled dispatch per chunk shape).  Exactly the computation
            # prefill performs for those positions, split in time.
            def step(carry, tok):
                c, ln = carry
                logits, c = self.arch.decode_step(params, tok, c, ln)
                return (c, ln + 1), logits

            (cache, lengths), logits = jax.lax.scan(
                step, (cache, lengths), jnp.swapaxes(toks, 0, 1))
            return logits[-1], cache, lengths

        self._extend = _extend

    def _build_cache(self):
        """The decode cache of every stack, one batch row per lane: KV of
        ``max_len`` rows (the window, for windowed layers) and any
        recurrent state.  The paged engine builds page pools instead."""
        return self.arch.init_cache(self.n_lanes, self.max_len)

    def _count_dispatch(self, n: int = 1, rung: Optional[int] = None) -> None:
        """Record ``n`` jitted model-step dispatches, one of them a ladder
        program of ``rung`` if given (thread-safe)."""
        with self._dispatch_lock:
            self.dispatches += n
            if rung is not None:
                self.prefill_rung_programs[rung] = (
                    self.prefill_rung_programs.get(rung, 0) + 1)

    def _count_prefill(self, staged: StagedPrefill, n: int) -> StagedPrefill:
        """Record the real and bucket tokens of a prefill dispatch of
        ``n`` requests (thread-safe: the speculative thread dispatches).
        An aggregate's bucket is the sum of its parts'."""
        with self._dispatch_lock:
            self.prefill_real_tokens += int(np.sum(staged.plens[:n]))
            self.prefill_bucket_tokens += sum(
                p.shape[0] * p.shape[1] for p in staged.parts or [staged])
        return staged

    def _prefill_span(self, requests: Sequence, shape: tuple, real: list):
        """The ``engine.prefill`` span of one padded prefill dispatch of
        ``shape`` holding ``real`` prompt tokens in its rows, with the
        query rows per attention chunk it runs (``attn_chunk``, 0 for
        unchunked); counts its chunks in :attr:`prefill_attn_chunks`."""
        chunk = query_chunk(self.arch.cfg, shape[1])
        if chunk:
            with self._dispatch_lock:
                self.prefill_attn_chunks += -(-shape[1] // chunk)
        return span("engine.prefill", rids=[r.rid for r in requests],
                    shape=shape, real=real, attn_chunk=chunk)

    def _host(self, x, copy: bool = False) -> np.ndarray:
        """``x`` in host memory, a writable copy with ``copy``: one
        blocking read of a device array, counted in :attr:`host_reads`."""
        self.host_reads += 1
        return np.array(x) if copy else np.asarray(x)

    # ------------------------------------------------------------- admission
    def admit(self, requests: Sequence, template: Optional[str] = None
              ) -> tuple[int, int]:
        """Prefill ``requests`` and insert them into lanes.

        One call for k requests is the set-oriented execution: the batch
        is dispatched and committed as one unit — the serving analogue of
        the paper's batched query.  With ``template`` each prompt runs at
        its own ladder rung (:meth:`prefill_dispatch`); without, the batch
        is one padded program.  With ``kv_shares``, ``template`` also
        selects which lane pools the batch may draw from
        (:meth:`n_free_for` bounds the batch size).

        Equivalent to :meth:`prefill_dispatch` immediately followed by
        :meth:`commit_prefill` — the synchronous path, paying the prefill
        inline; the scheduler's overlap mode uses the split form instead.
        """
        if not requests:
            return (0, 0)
        assert len(requests) <= self.n_free_for(template), \
            "admit() beyond this template's free lanes"
        return self.commit_prefill(self.prefill_dispatch(requests, template))

    def prefill_dispatch(self, requests: Sequence,
                         template: Optional[str] = None,
                         chunk: Optional[int] = None) -> StagedPrefill:
        """Dispatch (but do not commit) the prefill of ``requests``.

        Issues the jitted prefill — an *asynchronous* device dispatch: the
        call returns as soon as the computation is enqueued, so the caller
        can overlap it with a decode tick and commit at the next tick
        boundary.  No engine or request state is mutated (the writes are
        counters and the :attr:`template_shapes` record), so this is safe
        to call from the scheduler's speculative-dispatch thread while
        :meth:`decode_tick` runs on the main thread, and an uncommitted
        result can be dropped.

        With ``template``, each prompt runs as its own ``(1, rung)``
        program (:meth:`_ladder_dispatch`); without, the batch runs as one
        program padded to power-of-two (batch, prompt) buckets.

        ``chunk`` enables resumable chunked prefill for ONE oversized
        prompt (the scheduler dispatches such prompts alone): the first
        ``chunk`` tokens prefill now, the rest stay ``pending`` on the
        returned staged object for :meth:`prefill_resume` to fold in one
        chunk at a time — each resume is one compiled dispatch the caller
        can overlap with a decode tick.  Prompts that fit in one chunk
        fall through to the ordinary path.  Chunk shapes compile per
        distinct (final-remainder) width; steady traffic converges on two
        compiled shapes (``chunk`` and its remainder bucket).
        """
        if chunk is not None and chunk >= 1:
            cprompts = [np.asarray(r.prompt[-(self.max_len - 1):], np.int32)
                        for r in requests]
            if len(requests) == 1:
                if len(cprompts[0]) > chunk:
                    return self._count_prefill(self._chunked_dispatch(
                        requests[0], cprompts[0], template, chunk), 1)
                # A prompt that fits one chunk: ordinary one-shot below.
            elif any(len(p) > chunk for p in cprompts):
                # A BATCH of oversized prompts: one single-request chunked
                # part per prompt under an aggregate parent, so resumable
                # chunking no longer forces oversized prompts to dispatch
                # alone — the scheduler admits them as one unit and
                # interleaves decode ticks between every part's chunks.
                parts = [self._chunked_dispatch(r, p, template, chunk)
                         for r, p in zip(requests, cprompts)]
                return self._count_prefill(StagedPrefill(
                    template, list(requests), None, None,
                    np.concatenate([pt.plens for pt in parts]),
                    (len(requests), int(max(len(p) for p in cprompts))),
                    parts=parts), len(requests))
        if template is not None:
            return self._ladder_dispatch(requests, template)
        bsz = _bucket(len(requests))
        # Bucket the prompt axis to the batch's longest (truncated) prompt;
        # right-padding + causal mask keeps logits exact.
        prompts = [r.prompt[-self.max_prompt_len:] for r in requests]
        plen = min(self.max_prompt_len, _bucket(max(len(p) for p in prompts)))
        with self._prefill_span(requests, (bsz, plen),
                                [len(p) for p in prompts]):
            toks = np.zeros((bsz, plen), np.int32)
            plens = np.ones((bsz,), np.int32)
            for i, p in enumerate(prompts):
                toks[i, : len(p)] = p  # right-pad; causal mask hides pad keys
                plens[i] = len(p)
            first, cache = self._prefill(
                self.params, jnp.asarray(toks), jnp.asarray(plens),
                self.max_len)
        self._count_dispatch()
        return self._count_prefill(
            StagedPrefill(template, list(requests), first, cache, plens,
                          (bsz, plen)), len(requests))

    def _rung(self, n: int) -> int:
        """The ladder rung of an ``n``-token prompt: its power-of-two
        bucket, at least the floor, at most ``max_prompt_len``."""
        return min(self.max_prompt_len, max(self._rung_floor, _bucket(n)))

    def prefill_rungs(self) -> list[int]:
        """Every rung a prompt of up to ``max_prompt_len`` tokens takes."""
        return sorted({self._rung(n) for n in range(1, self.max_prompt_len + 1)})

    def _ladder_dispatch(self, requests: Sequence,
                         template: str) -> StagedPrefill:
        """One ``(1, rung)`` prefill program per prompt, enqueued back to
        back with no host read between them.

        One prompt gives a plain staged prefill of shape ``(1, rung)``;
        k prompts give an aggregate whose ``parts`` are the k single-prompt
        stagings (the form the chunked path commits) and whose ``shape``
        is ``(k, largest rung)``.  Each program opens its own
        ``engine.prefill`` span and counts in :attr:`prefill_rung_programs`.
        """
        self._warm_rungs()
        parts = []
        for r in requests:
            p = np.asarray(r.prompt[-self.max_prompt_len:], np.int32)
            rung = self._rung(len(p))
            toks = np.zeros((1, rung), np.int32)
            toks[0, : len(p)] = p  # right-pad; causal mask hides pad keys
            plens = np.asarray([len(p)], np.int32)
            with self._prefill_span([r], (1, rung), [len(p)]):
                first, cache = self._prefill(
                    self.params, jnp.asarray(toks), jnp.asarray(plens),
                    self.max_len)
            self._count_dispatch(rung=rung)
            parts.append(StagedPrefill(template, [r], first, cache, plens,
                                       (1, rung)))
        shape = (len(parts), max(pt.shape[1] for pt in parts))
        pinned = self.template_shapes.get(template, (1, 1))
        self.template_shapes[template] = (max(pinned[0], _bucket(shape[0])),
                                          max(pinned[1], shape[1]))
        if len(parts) == 1:
            return self._count_prefill(parts[0], 1)
        return self._count_prefill(StagedPrefill(
            template, list(requests), None, None,
            np.concatenate([pt.plens for pt in parts]), shape, parts=parts),
            len(parts))

    def _warm_rungs(self) -> None:
        """Run every rung program once on zeros, once per engine: under
        the dispatch lock, as the scheduler's overlap mode dispatches from
        a second thread, and outside every counter and span.  The first
        commit compiles the inserts from the staged KV kept here."""
        if self._rungs_warm:
            return
        with self._dispatch_lock:
            if self._rungs_warm:
                return
            for rung in self.prefill_rungs():
                _, cache = self._prefill(
                    self.params, jnp.zeros((1, rung), jnp.int32),
                    jnp.ones((1,), jnp.int32), self.max_len)
            self._warm_src = cache
            self._rungs_warm = True

    def _warm_inserts(self, src) -> None:
        """Compile the commit's insert of a ladder program's staged KV
        ``src`` without writing any lane (the result is dropped)."""
        _insert_lanes(self.cache, src, [0])

    def _chunked_dispatch(self, r, prompt: np.ndarray,
                          template: Optional[str], chunk: int) -> StagedPrefill:
        """Chunked-path dispatch: prefill the first chunk, stage the rest.

        The staged cache is batch-1 and already padded to ``max_len``;
        later chunks extend it in place through the decode path (positions
        ``chunk..S-1``), so the committed KV matches what a one-shot
        prefill of the full prompt would have produced.  A prompt that
        fits one chunk degenerates to a batch-1 one-shot (complete
        immediately) so batched-chunk parents may mix sizes.  Chunk
        shapes are their own (bounded) compile family, apart from the
        ladder's rungs."""
        S = len(prompt)
        c0 = min(chunk, S)
        with self._prefill_span([r], (1, c0), [c0]):
            first, cache = self._prefill(
                self.params, jnp.asarray(prompt[None, :c0]),
                jnp.asarray([c0], jnp.int32), self.max_len)
        self._count_dispatch()
        pending = [prompt[None, i: i + chunk] for i in range(c0, S, chunk)]
        return StagedPrefill(
            template, [r], None if pending else first, cache,
            np.asarray([S], np.int32), (1, S),
            pending=pending, lengths_dev=jnp.asarray([c0], jnp.int32))

    def prefill_resume(self, staged: StagedPrefill) -> bool:
        """Fold the next pending chunk into a chunked staged prefill.

        One compiled dispatch (a ``lax.scan`` of ``decode_step`` over the
        chunk's positions) extends the staged KV and advances the length
        cursor; the final chunk also yields the first generated token,
        making the staged prefill :attr:`~StagedPrefill.complete` and
        commit-eligible.  Returns completeness.  Like ``prefill_dispatch``
        this mutates only the staged object, never engine or request
        state — safe on the scheduler's speculation thread.

        A batched-chunk parent advances ONE chunk of its first incomplete
        part per call — the one-dispatch-per-resume contract the
        scheduler's tick interleaving relies on is preserved."""
        if staged.complete:
            return True
        if staged.parts:
            for part in staged.parts:
                if not part.complete:
                    self.prefill_resume(part)
                    break
            return staged.complete
        toks = staged.pending.pop(0)
        with span("engine.prefill_resume",
                  rids=[r.rid for r in staged.requests]):
            logits, staged.cache, staged.lengths_dev = self._extend(
                self.params, staged.cache, jnp.asarray(toks),
                staged.lengths_dev)
        self._count_dispatch()
        if not staged.pending:
            staged.first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return staged.complete

    def commit_prefill(self, staged: StagedPrefill,
                       n: Optional[int] = None) -> tuple[int, int]:
        """Materialize a staged prefill into decode lanes.

        Commits the first ``n`` requests of ``staged`` (default: all),
        blocking until the device results are ready, allocating each a
        lane from its template's pools and splicing its KV rows into the
        lane cache.  The caller bounds ``n`` by :meth:`n_free_for` —
        requests beyond ``n`` are the caller's to re-queue (speculation
        abort: the rows were computed but never inserted).  Returns the
        padded ``(batch, prompt)`` bucket actually dispatched (cost-model
        feedback, same as :meth:`admit`).
        """
        assert staged.complete, \
            "commit_prefill() of a chunked staged prefill with pending chunks"
        if staged.parts:
            take = len(staged.requests) if n is None else n
            for part in staged.parts:
                k = min(len(part.requests), take)
                if k <= 0:
                    break
                self.commit_prefill(part, k)
                take -= k
            return staged.shape
        reqs = staged.requests if n is None else staged.requests[:n]
        assert len(reqs) <= self.n_free_for(staged.template), \
            "commit_prefill() beyond this template's free lanes"
        if not reqs:
            return staged.shape
        if self._warm_src is not None:
            src, self._warm_src = self._warm_src, None
            self._warm_inserts(src)
        with span("engine.commit", rids=[r.rid for r in reqs]):
            first = self._host(staged.first)  # waits for the async dispatch
            lanes = [self.partition.alloc(staged.template) for _ in reqs]
            self._insert_staged(staged, lanes)
            lt = self._host(self.last_token, copy=True)
            ln = self._host(self.lengths, copy=True)
            for i, (r, lane) in enumerate(zip(reqs, lanes)):
                r.lane = lane
                r.generated.append(int(first[i]))
                lt[lane] = first[i]
                ln[lane] = staged.plens[i]  # real prompt length; decode writes here
                self.active[lane] = True
            self.last_token = jnp.asarray(lt)
            self.lengths = jnp.asarray(ln)
        self.prefill_calls += 1
        return staged.shape

    def _insert_staged(self, staged: StagedPrefill, lanes: list[int]) -> None:
        """Splice the staged batch's cache into ``lanes`` — the KV-motion
        hook the paged engine overrides.  The dense engine always moves
        full lanes (all ``max_len`` rows, valid or not) and accounts them
        against :attr:`kv_bytes_moved`."""
        self.cache = _insert_lanes(self.cache, staged.cache, lanes)
        for a in jax.tree_util.tree_leaves(staged.cache):
            self.kv_bytes_moved += (a.dtype.itemsize * a.shape[0] * len(lanes)
                                    * int(np.prod(a.shape[2:])))

    # ----------------------------------------------------------------- tick
    def decode_tick(self) -> dict[int, int]:
        """One batched decode step over all lanes → ``{lane: token}``.

        Three steps, each a span: ``engine.decode.prepare`` (host work
        before the step: :meth:`_prepare_decode`), ``engine.decode.dispatch``
        (the step program and the eager length update, no read:
        :meth:`_dispatch_decode`) and ``engine.decode.readback`` (the one
        read of the new tokens).  The tick's span carries the lanes it
        found active and :attr:`host_reads` at its start."""
        with span("engine.decode_tick", lanes=np.nonzero(self.active)[0],
                  host_reads=self.host_reads):
            with span("engine.decode.prepare"):
                args = self._prepare_decode()
            if args is None:
                return {}
            with span("engine.decode.dispatch"):
                nxt = self._dispatch_decode(*args)
            with span("engine.decode.readback"):
                out = self._host(nxt)
                return {lane: int(out[lane])
                        for lane in np.nonzero(self.active)[0]}

    def _prepare_decode(self) -> Optional[tuple]:
        """The arguments of :meth:`_dispatch_decode`, or ``None`` when no
        lane is active."""
        return () if self.active.any() else None

    def _dispatch_decode(self):
        """Dispatch one dense decode step; returns the new tokens, still
        on the device."""
        nxt, self.cache = self._decode(
            self.params, self.last_token, self.cache, self.lengths
        )
        self._advance_lanes(nxt)
        return nxt

    def _advance_lanes(self, nxt) -> None:
        """After a decode dispatch: every active lane's length grows by
        one (an eager device op; nothing is read) and ``nxt`` becomes its
        last token."""
        self._count_dispatch()
        self.lengths = jnp.where(
            jnp.asarray(self.active), jnp.minimum(self.lengths + 1, self.max_len - 1),
            self.lengths,
        )
        self.last_token = nxt
        self.decode_steps += 1

    def retire(self, lane: int) -> None:
        """Free a lane (request finished or force-retired); the lane
        returns to its home pool — a reserved lane back to its template's
        reservation, a shared lane back to the shared pool."""
        self.active[lane] = False
        self.partition.release(lane)

    # ---------------------------------------------------------------- spill
    def spill(self, lane: int, key, template: Optional[str] = None) -> bool:
        """Retire ``lane``, staging its KV to the host spill pool.

        Copies the lane's cache rows plus the decode cursor (length, last
        token) to host memory under ``key`` (the request identity) before
        releasing the lane, so a later re-admission of the same request
        can :meth:`try_restore` instead of re-prefilling.  Returns whether
        the KV was actually staged — ``False`` (plain retire) when no
        pool is configured or the template is fenced out of it
        (zero spill budget, checked BEFORE paying the device→host copy);
        an LRU/budget eviction later is the pool's business."""
        pool = self.partition.spill
        if pool is None or not pool.accepts(template):
            self.retire(lane)
            return False
        entry = {
            "rows": jax.tree_util.tree_map(
                lambda a: self._host(a[:, lane]), self.cache),
            "length": int(self._host(self.lengths)[lane]),
            "last": int(self._host(self.last_token)[lane]),
        }
        self.kv_bytes_moved += sum(
            a.nbytes for a in jax.tree_util.tree_leaves(entry["rows"]))
        staged = pool.put(key, template, entry)
        self.retire(lane)
        return staged

    def has_spill(self, key) -> bool:
        """Whether ``key`` currently has staged KV in the spill pool (the
        scheduler's cue to restore at admission instead of re-prefilling
        — and to keep the request out of speculative prefill batches)."""
        pool = self.partition.spill
        return pool is not None and key in pool

    def try_restore(self, key, template: Optional[str] = None) -> Optional[int]:
        """Restore ``key``'s spilled KV into a fresh lane, if possible.

        On a pool hit with a free lane admissible for ``template``, the
        staged rows are spliced back, the decode cursor resumes where the
        eviction stopped, and the lane index is returned — generation
        continues with no re-prefill and no token restart.  Returns
        ``None`` on a pool miss (entry evicted or never spilled) or when
        the template has no admissible free lane (the entry stays staged
        for a later attempt)."""
        pool = self.partition.spill
        if pool is None or key not in pool or self.n_free_for(template) <= 0:
            return None
        entry = pool.take(key)
        if entry is None:  # raced away (defensive: tick loop is 1-threaded)
            return None
        lane = self.partition.alloc(template)
        rows = entry["rows"]
        self.kv_bytes_moved += sum(
            np.asarray(a).nbytes for a in jax.tree_util.tree_leaves(rows))
        self.cache = jax.tree_util.tree_map(
            lambda dst, src: dst.at[:, lane].set(
                jnp.asarray(src).astype(dst.dtype)),
            self.cache, rows)
        ln = self._host(self.lengths, copy=True)
        lt = self._host(self.last_token, copy=True)
        ln[lane] = entry["length"]
        lt[lane] = entry["last"]
        self.lengths = jnp.asarray(ln)
        self.last_token = jnp.asarray(lt)
        self.active[lane] = True
        return lane

    @property
    def kv(self):
        """The engine's :class:`~repro.serving.kv.KVView` — the one
        capacity/placement surface the scheduler consumes.  Dense engines
        expose their :class:`KVPartition`; the paged engine overrides
        this with a page-budget-bounded view."""
        return self.partition

    @property
    def n_free(self) -> int:
        """Total free lanes across every pool."""
        return self.partition.n_free

    def n_free_for(self, template: Optional[str]) -> int:
        """Free lanes admissible for ``template`` right now (its reserved
        pool plus the shared pool; see :class:`KVPartition`)."""
        return self.partition.n_free_for(template)

    def lane_benefits(self, lane: int, template: Optional[str]) -> bool:
        """Whether retiring ``lane`` would free capacity ``template`` can
        use (:meth:`KVPartition.benefits`) — the scheduler's speculative
        sizing hint."""
        return self.partition.benefits(lane, template)

    @property
    def free_lanes(self) -> list[int]:
        """Sorted snapshot of every free lane (introspection/debugging)."""
        return self.partition.free_lanes


def _insert_lanes(lane_cache, new_cache, lanes: list[int]):
    """Copy per-request cache entries (batch axis=1 after the layer axis)
    into lane slots.  Works on the nested {stack: {k,v,ssm,conv}} pytree."""
    idx = jnp.asarray(lanes)

    def one(dst, src):
        # dst: (L, B_lanes, ...); src: (L, B_new_bucket, ...)
        take = src[:, : len(lanes)]
        return dst.at[:, idx].set(take.astype(dst.dtype))

    return jax.tree_util.tree_map(one, lane_cache, new_cache)
