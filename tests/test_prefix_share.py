"""Prefix-granular KV sharing: COW pool invariants, PrefixIndex matching,
alias-at-admit bit-identity, partial eviction of shared readers, the
cross-template decode megabatch, and chaos recovery with sharing on."""
from __future__ import annotations

import dataclasses

import jax
import numpy as np
import pytest

from repro.core.lane_policy import PrefixIndex
from repro.core.strategies import OneOrAll
from repro.models.registry import get_arch
from repro.serving.engine import HostSpillPool, InferenceEngine
from repro.serving.paged_kv import PagedInferenceEngine, PagedKVPool
from repro.serving.request import Request
from repro.serving.scheduler import ContinuousBatchingScheduler

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - optional dependency
    HAVE_HYPOTHESIS = False


@pytest.fixture(scope="module")
def setup():
    arch = get_arch("llama3-8b")
    arch = dataclasses.replace(arch, cfg=arch.cfg.reduced())
    params = arch.init(jax.random.PRNGKey(0))
    return arch, params


def _run_sched(eng, reqs, **kw):
    sched = ContinuousBatchingScheduler(eng, strategy=OneOrAll(), **kw)
    for r in reqs:
        sched.submit(r)
    sched.producer_done()
    sched.run_until_drained(max_ticks=2000)
    return sched


def _shared_prompts(rng, n_readers=2, prefix_tokens=16, tail_tokens=4):
    """One owner + n_readers prompts sharing a page-aligned prefix."""
    shared = rng.integers(1, 200, size=prefix_tokens).astype(np.int32)
    return [np.concatenate([shared,
                            rng.integers(1, 200, size=tail_tokens)
                            .astype(np.int32)])
            for _ in range(1 + n_readers)]


# ------------------------------------------------------------ PrefixIndex

def test_prefix_index_longest_proper_match():
    idx = PrefixIndex(page_size=4)
    idx.insert("a", range(100, 112))  # 12 tokens: prefixes of 4 and 8
    # identical 12-token prompt: the 8-token prefix wins (k*ps < len
    # keeps the match strictly proper — a 12-token match would leave no
    # novel tail to prefill)
    assert idx.lookup(range(100, 112)) == ("a", 2)
    # diverges inside page 2: only the first page matches
    assert idx.lookup([100, 101, 102, 103, 99, 99, 99, 99, 1]) == ("a", 1)
    assert idx.lookup([1, 2, 3, 4, 5]) is None
    assert idx.lookup([100, 101, 102]) is None  # shorter than one page
    assert idx.hits == 2 and idx.misses == 2
    assert idx.lookup(range(100, 112), exclude={"a"}) is None


def test_prefix_index_remove_and_reregister():
    idx = PrefixIndex(page_size=4)
    idx.insert("a", range(8))
    idx.insert("b", range(8))
    assert idx.lookup(range(9))[0] == "a"  # insertion order breaks ties
    idx.remove("a")
    assert idx.lookup(range(9))[0] == "b"
    idx.remove("b")
    assert idx.lookup(range(9)) is None and len(idx) == 0
    idx.insert("a", range(4, 12))  # re-register under new tokens
    assert idx.lookup(range(4, 10)) == ("a", 1)


# -------------------------------------------------------- pool COW units

def test_pool_share_prefix_and_cow_fork():
    pool = PagedKVPool(8, page_size=4)
    src = pool.alloc_table("src", n=3)
    shared = pool.share("src", "dst", n_pages=2)
    assert shared == src[:2] and pool.n_free_pages == 5
    assert pool.page_ref(src[0]) == 2 and pool.page_ref(src[2]) == 1
    assert pool.shared_prefix_pages("src") == 2
    assert pool.shared_prefix_pages("dst") == 2
    # private page: fork declines
    assert pool.fork_page("src", 2) is None
    # shared page: the writer gets a fresh page, the reader keeps the old
    old, new = pool.fork_page("dst", 1)
    assert old == src[1] and new not in src
    assert pool.pages("dst")[1] == new and pool.pages("src")[1] == old
    assert pool.page_ref(old) == 1 and pool.page_ref(new) == 1
    pool.free_table("src")
    pool.free_table("dst")
    assert pool.n_free_pages == 8
    pool.alloc_table("s2", n=1)
    with pytest.raises(ValueError, match="has"):
        pool.share("s2", "d2", n_pages=5)  # longer than the source table


def test_pool_adopt_transfers_holds():
    pool = PagedKVPool(4, page_size=4)
    pages = pool.alloc_table("a", n=2)
    pool.incref_pages(pages)     # a spill entry's hold
    pool.free_table("a")
    assert pool.n_free_pages == 2  # the hold keeps them alive
    pool.adopt_table("b", pages)   # transfer: no extra incref
    assert pool.pages("b") == tuple(pages)
    pool.free_table("b")
    assert pool.n_free_pages == 4
    with pytest.raises(RuntimeError, match="free"):
        pool.adopt_table("c", pages)  # pages no longer referenced


def test_pool_all_shared_eviction_raises_typed():
    """An all-shared pool raises the same typed error as all-pinned
    instead of corrupting a live alias group (whole-table LRU eviction
    must be refcount-aware)."""
    pool = PagedKVPool(4, page_size=4)
    pool.alloc_table("a", n=2)
    pool.alloc_table("b", n=2)
    pool.free_table("b")
    pool.share("a", "alias")  # every resident page now refcounted > 1
    with pytest.raises(RuntimeError, match="pinned"):
        pool.alloc_table("c", n=3)
    # both tables intact: no alias group was corrupted
    assert pool.pages("a") == pool.pages("alias")
    pool.free_table("alias")
    pool.alloc_table("c", n=3)  # unshared again: LRU eviction of "a" works
    assert not pool.has_table("a") and pool.evicted == 1


def test_pool_double_free_raises():
    pool = PagedKVPool(4, page_size=4)
    pages = pool.alloc_table("a", n=2)
    pool.free_table("a")
    with pytest.raises(RuntimeError, match="double free"):
        pool.decref_pages(pages)
    with pytest.raises(RuntimeError, match="cannot reference"):
        pool.incref_pages(pages)


def _run_cow_invariants(seed: int, n_ops: int = 60) -> None:
    """Seeded random alias/fork/write/free workload on the pool against a
    shadow model: every table always reads its own values, forks never
    perturb siblings, refcounts return to zero after all owners retire."""
    rng = np.random.default_rng(seed)
    pool = PagedKVPool(16, page_size=4)
    phys: dict[int, int] = {}   # physical page -> symbolic contents
    shadow: dict[str, list] = {}  # table -> expected contents per slot
    stamp = 0

    def check():
        for key, vals in shadow.items():
            got = [phys[p] for p in pool.pages(key)]
            assert got == vals, (key, got, vals)

    for i in range(n_ops):
        op = rng.integers(0, 4)
        if op == 0 and pool.n_free_pages > 0:  # alloc
            n = int(rng.integers(1, min(4, pool.n_free_pages) + 1))
            key = f"t{i}"
            try:
                pages = pool.alloc_table(key, n=n)
            except RuntimeError:
                continue  # nothing evictable (all shared): acceptable
            for p in pages:
                stamp += 1
                phys[p] = stamp
            shadow[key] = [phys[p] for p in pages]
        elif op == 1 and shadow:  # share a prefix
            src = str(rng.choice(sorted(shadow)))
            k = int(rng.integers(1, len(shadow[src]) + 1))
            dst = f"s{i}"
            pool.share(src, dst, n_pages=k)
            shadow[dst] = list(shadow[src][:k])
        elif op == 2 and shadow:  # write one slot (COW when aliased)
            key = str(rng.choice(sorted(shadow)))
            slot = int(rng.integers(0, len(shadow[key])))
            page = pool.pages(key)[slot]
            if pool.page_ref(page) > 1:
                if pool.n_free_pages < 1:
                    continue  # no room to fork: the engine makes room
                old, new = pool.fork_page(key, slot)
                phys[new] = phys[old]  # the device-copy step
            stamp += 1
            phys[pool.pages(key)[slot]] = stamp
            shadow[key][slot] = stamp
        elif op == 3 and shadow:  # retire a reader
            key = str(rng.choice(sorted(shadow)))
            pool.free_table(key)
            del shadow[key]
        check()
    for key in sorted(shadow):
        pool.free_table(key)
    assert pool.n_free_pages == 16
    assert all(pool.page_ref(p) == 0 for p in range(16))
    with pytest.raises(RuntimeError, match="double free"):
        pool.decref_pages([0])


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 1337])
def test_cow_invariants_seeded(seed):
    _run_cow_invariants(seed)


if HAVE_HYPOTHESIS:  # pragma: no cover - optional dependency
    @given(seed=st.integers(min_value=0, max_value=2**16))
    @settings(max_examples=25, deadline=None)
    def test_cow_invariants_hypothesis(seed):
        _run_cow_invariants(seed)


# ----------------------------------------------- engine: prefix-hit admit

def test_prefix_hit_bit_identical_and_zero_cost(setup):
    """Admitting prompts with a shared page-aligned prefix aliases the
    prefix pages (zero KV bytes moved for them), prefills only the novel
    tail, and produces bit-identical outputs to the unshared engine —
    including intra-batch sharing (the owner arrives in the same batch)."""
    arch, params = setup
    rng = np.random.default_rng(41)
    prompts = _shared_prompts(rng, n_readers=2)

    def run(prefix_share):
        eng = PagedInferenceEngine(arch, params, n_lanes=4,
                                   max_prompt_len=32, max_len=32,
                                   page_size=8, prefix_share=prefix_share)
        reqs = [Request(rid=i, prompt=p, max_new_tokens=4)
                for i, p in enumerate(prompts)]
        eng.admit(reqs, None)
        for _ in range(4):
            out = eng.decode_tick()
            for r in reqs:
                r.generated.append(out[r.lane])
        return eng, [r.generated for r in reqs]

    e0, g0 = run(False)
    e1, g1 = run(True)
    assert g1 == g0
    assert e1.prefix_hits == 2  # both readers aliased the in-batch owner
    assert e1.prefill_flops_saved > 0
    assert e1.kv_bytes_moved < e0.kv_bytes_moved  # aliased pages are free
    ratio = e1.prefill_flops_total / (
        e1.prefill_flops_total - e1.prefill_flops_saved)
    assert ratio > 1.5


@pytest.mark.parametrize("prefix_share", [False, True])
def test_paged_engine_refuses_an_arch_paged_decode_cannot_run(
        setup, monkeypatch, prefix_share):
    """A windowed arch is refused, with or without prefix sharing, before
    the engine builds any cache: InferenceEngine serves it."""
    arch, params = setup
    win = dataclasses.replace(arch, cfg=dataclasses.replace(arch.cfg,
                                                            attn_window=8))
    p = win.init(jax.random.PRNGKey(0))

    def no_cache(self):
        raise AssertionError("a cache was built before the refusal")

    monkeypatch.setattr(InferenceEngine, "_build_cache", no_cache)
    monkeypatch.setattr(PagedInferenceEngine, "_build_cache", no_cache)
    with pytest.raises(ValueError, match="InferenceEngine"):
        PagedInferenceEngine(win, p, n_lanes=2, max_prompt_len=16,
                             max_len=32, page_size=8,
                             prefix_share=prefix_share)


def test_cow_guard_forks_before_shared_page_write(setup):
    """A decode write into an aliased page forks a private copy first:
    the sibling's page bytes stay bit-identical and the writer's tokens
    are unchanged vs an unshared run."""
    arch, params = setup
    rng = np.random.default_rng(43)
    prompt = rng.integers(1, 200, size=12).astype(np.int32)

    def run(share):
        eng = PagedInferenceEngine(arch, params, n_lanes=2,
                                   max_prompt_len=16, max_len=32,
                                   page_size=8)
        r = Request(rid=0, prompt=prompt, max_new_tokens=4)
        eng.admit([r], None)
        ghost_page = None
        before = None
        if share:
            # Alias BOTH pages (incl. the one decode writes next) to a
            # ghost reader, as a raw-pool consumer might.
            pages = eng.pool.share(r.lane, "ghost", n_pages=2)
            ghost_page = pages[1]
            before = [np.asarray(a[:, ghost_page])
                      for a in jax.tree_util.tree_leaves(eng.cache)]
        for _ in range(4):
            r.generated.append(eng.decode_tick()[r.lane])
        if share:
            # the writer forked: the ghost's page is untouched
            assert eng.pool.pages(r.lane)[1] != ghost_page
            assert eng.pool.page_ref(ghost_page) == 1
            after = [np.asarray(a[:, ghost_page])
                     for a in jax.tree_util.tree_leaves(eng.cache)]
            for b, a in zip(before, after):
                np.testing.assert_array_equal(b, a)
            eng.pool.free_table("ghost")
        return r.generated

    assert run(share=True) == run(share=False)


# ------------------------------------------- partial eviction (satellite)

def test_shared_prefix_survives_straggler_spill(setup):
    """Regression: spilling one reader of a shared prefix moves only its
    private tail to host — the refcounted prefix pages stay resident for
    the sibling readers, and the restore re-adopts them with outputs
    bit-identical to an uninterrupted unshared run."""
    arch, params = setup
    rng = np.random.default_rng(47)
    prompts = _shared_prompts(rng, n_readers=2)

    def baseline():
        eng = PagedInferenceEngine(arch, params, n_lanes=4,
                                   max_prompt_len=32, max_len=32,
                                   page_size=8)
        reqs = [Request(rid=i, prompt=p, max_new_tokens=6)
                for i, p in enumerate(prompts)]
        eng.admit(reqs, None)
        for _ in range(6):
            out = eng.decode_tick()
            for r in reqs:
                r.generated.append(out[r.lane])
        return [r.generated for r in reqs]

    eng = PagedInferenceEngine(arch, params, n_lanes=4, max_prompt_len=32,
                               max_len=32, page_size=8, prefix_share=True,
                               kv_spill=HostSpillPool(8))
    reqs = [Request(rid=i, prompt=p, max_new_tokens=6)
            for i, p in enumerate(prompts)]
    eng.admit(reqs, None)
    for _ in range(2):
        out = eng.decode_tick()
        for r in reqs:
            r.generated.append(out[r.lane])
    victim = reqs[1]  # a reader, not the owner
    owner_prefix = eng.pool.pages(reqs[0].lane)[:2]
    free_before = eng.pool.n_free_pages
    bytes_before = eng.kv_bytes_moved
    assert eng.spill(victim.lane, victim.rid, None)
    # Only the victim's PRIVATE tail pages returned to the free list; the
    # 2 shared prefix pages stay resident under the spill entry's hold.
    assert all(eng.pool.page_ref(p) >= 2 for p in owner_prefix)
    spilled_bytes = eng.kv_bytes_moved - bytes_before
    prefix_rows_bytes = sum(  # what copying the 16 shared rows would cost
        a.dtype.itemsize * a.shape[0] * 16 * int(np.prod(a.shape[3:]))
        for a in jax.tree_util.tree_leaves(eng.cache))
    assert 0 < spilled_bytes < prefix_rows_bytes  # only the private tail
    assert eng.pool.n_free_pages > free_before
    # siblings keep decoding over the still-shared prefix
    for _ in range(1):
        out = eng.decode_tick()
        for r in (reqs[0], reqs[2]):
            r.generated.append(out[r.lane])
    lane = eng.try_restore(victim.rid, None)
    assert lane is not None
    victim.lane = lane
    for i in range(4):
        out = eng.decode_tick()
        victim.generated.append(out[lane])
        for r in (reqs[0], reqs[2]):
            if len(r.generated) < 7:  # admit added the first prefill token
                r.generated.append(out[r.lane])
    assert [r.generated for r in reqs] == baseline()


def test_spill_entry_drop_releases_prefix_holds(setup):
    """A spill entry that silently drops out of the host pool (LRU
    pressure) releases the refcounts it held on resident prefix pages —
    no page leak, the owner becomes the sole reader again."""
    arch, params = setup
    rng = np.random.default_rng(53)
    prompts = _shared_prompts(rng, n_readers=1)

    eng = PagedInferenceEngine(arch, params, n_lanes=4, max_prompt_len=32,
                               max_len=32, page_size=8, prefix_share=True,
                               kv_spill=HostSpillPool(max_entries=1))
    reqs = [Request(rid=i, prompt=p, max_new_tokens=6)
            for i, p in enumerate(prompts)]
    eng.admit(reqs, None)
    owner_prefix = eng.pool.pages(reqs[0].lane)[:2]
    assert eng.spill(reqs[1].lane, reqs[1].rid, None)
    assert all(eng.pool.page_ref(p) == 2 for p in owner_prefix)
    # An unrelated spill evicts the reader's entry (max_entries=1): the
    # on_drop hook must return the prefix holds.
    other = Request(rid=9, prompt=rng.integers(1, 200, size=5)
                    .astype(np.int32), max_new_tokens=2)
    eng.admit([other], None)
    assert eng.spill(other.lane, other.rid, None)
    assert reqs[1].rid not in eng.partition.spill
    assert all(eng.pool.page_ref(p) == 1 for p in owner_prefix)


def test_scheduler_prefix_hits_stat(setup):
    """End-to-end scheduler run with sharing on: outputs match the dense
    engine and stats.prefix_hits mirrors the engine counter."""
    arch, params = setup
    rng = np.random.default_rng(59)
    prompts = _shared_prompts(rng, n_readers=3)

    dense = InferenceEngine(arch, params, n_lanes=4, max_prompt_len=32,
                            max_len=32)
    d_reqs = [Request(rid=i, prompt=p.copy(), max_new_tokens=5)
              for i, p in enumerate(prompts)]
    _run_sched(dense, d_reqs)

    eng = PagedInferenceEngine(arch, params, n_lanes=4, max_prompt_len=32,
                               max_len=32, page_size=8, prefix_share=True)
    p_reqs = [Request(rid=i, prompt=p.copy(), max_new_tokens=5)
              for i, p in enumerate(prompts)]
    sched = _run_sched(eng, p_reqs)
    assert sched.stats.prefix_hits >= 1
    assert sched.stats.prefix_hits == eng.prefix_hits
    for dr, pr in zip(d_reqs, p_reqs):
        assert dr.generated == pr.generated, (dr.rid,)


# --------------------------------------------------- megabatch + sampling

def test_megabatch_one_dispatch_across_templates(setup):
    """ONE decode dispatch per tick covers every active lane regardless
    of template/partition — the cross-template megabatch gate."""
    arch, params = setup
    rng = np.random.default_rng(61)
    eng = PagedInferenceEngine(arch, params, n_lanes=4, max_prompt_len=16,
                               max_len=32, page_size=8,
                               kv_shares={"chat": 2, "embed": 1})
    ra = Request(rid=0, prompt=rng.integers(1, 200, size=6)
                 .astype(np.int32), max_new_tokens=4, template="chat")
    rb = Request(rid=1, prompt=rng.integers(1, 200, size=9)
                 .astype(np.int32), max_new_tokens=4, template="embed")
    eng.admit([ra], "chat")
    eng.admit([rb], "embed")
    for _ in range(4):
        before = eng.dispatches
        out = eng.decode_tick()
        assert eng.dispatches - before == 1  # one program, both templates
        assert ra.lane in out and rb.lane in out
        ra.generated.append(out[ra.lane])
        rb.generated.append(out[rb.lane])
    assert len(ra.generated) == 5 and len(rb.generated) == 5  # 1 + 4 ticks


def test_per_lane_sampling_in_one_megabatch(setup):
    """Per-lane sampling params ride through the single dispatch: a
    temperature-0 lane stays bit-identical to the all-greedy run while a
    sampled lane draws reproducibly — including across a spill/restore
    (the key is counter-based on the request's own position)."""
    arch, params = setup
    rng = np.random.default_rng(67)
    p0 = rng.integers(1, 200, size=6).astype(np.int32)
    p1 = rng.integers(1, 200, size=9).astype(np.int32)

    def run(sampled, interrupt=False):
        eng = PagedInferenceEngine(arch, params, n_lanes=2,
                                   max_prompt_len=16, max_len=32,
                                   page_size=8, kv_spill=HostSpillPool(4))
        r0 = Request(rid=0, prompt=p0, max_new_tokens=6)
        r1 = Request(rid=1, prompt=p1, max_new_tokens=6,
                     temperature=5.0 if sampled else 0.0, sample_seed=7)
        eng.admit([r0, r1], None)
        for i in range(6):
            if interrupt and i == 3:  # evict + restore the sampled lane
                assert eng.spill(r1.lane, r1.rid, None)
                r1.lane = eng.try_restore(r1.rid, None)
                assert r1.lane is not None
            out = eng.decode_tick()
            r0.generated.append(out[r0.lane])
            r1.generated.append(out[r1.lane])
        return r0.generated, r1.generated

    greedy0, greedy1 = run(sampled=False)
    s0_a, s1_a = run(sampled=True)
    s0_b, s1_b = run(sampled=True)
    assert s0_a == greedy0          # temp-0 lane untouched by the sampler
    assert s1_a == s1_b             # seeded sampling is deterministic
    assert s1_a != greedy1          # temp 5.0 actually samples
    _, s1_c = run(sampled=True, interrupt=True)
    assert s1_c == s1_a             # draws survive spill/restore


# ------------------------------------------------------- chaos (satellite)

def test_chaos_crash_on_shared_reader_bit_identical(setup):
    """Part 9 recovery with prefix sharing on: seeded lane crashes (which
    hit shared-prefix readers) quarantine, salvage the private tail,
    restore and resume — every request's tokens stay bit-identical to the
    fault-free unshared run, siblings unperturbed."""
    from repro.core.faults import ChaosEngine, ChaosPlan, chaos_seed
    from repro.core.resilience import Resilience

    arch, params = setup
    rng = np.random.default_rng(71)
    prompts = _shared_prompts(rng, n_readers=4, tail_tokens=3)

    def run(chaos, prefix_share):
        reqs = [Request(rid=i, prompt=p.copy(), max_new_tokens=5)
                for i, p in enumerate(prompts)]
        eng = PagedInferenceEngine(arch, params, n_lanes=3,
                                   max_prompt_len=32, max_len=32,
                                   page_size=8, prefix_share=prefix_share,
                                   kv_spill=HostSpillPool(max_entries=16))
        if chaos:
            eng = ChaosEngine(eng, ChaosPlan(seed=chaos_seed(0),
                                             decode_fault_rate=0.25))
        sched = ContinuousBatchingScheduler(
            eng, strategy=OneOrAll(),
            resilience=Resilience(quarantine_ticks=1) if chaos else None)
        for r in reqs:
            sched.submit(r)
        sched.producer_done()
        done = sched.run_until_drained(max_ticks=2000)
        assert len(done) == len(reqs)
        return {r.rid: list(r.generated) for r in reqs}, eng, sched

    baseline, _, _ = run(chaos=False, prefix_share=False)
    chaotic, eng, sched = run(chaos=True, prefix_share=True)
    assert eng.injected_decode_faults > 0, "chaos never bit: rate too low"
    assert sched.stats.quarantined > 0
    assert sched.stats.prefix_hits >= 1  # sharing was actually exercised
    assert chaotic == baseline
