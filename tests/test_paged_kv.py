"""Paged KV: block-table pool units, page-granular spill/restore, and
paged-vs-dense bit-identity through the scheduler's eviction path."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.strategies import OneOrAll
from repro.models.registry import get_arch
from repro.serving.engine import HostSpillPool, InferenceEngine, KVPartition
from repro.serving.kv import KVView
from repro.serving.paged_kv import PagedInferenceEngine, PagedKVPool, PagedKVView
from repro.serving.request import Request
from repro.serving.scheduler import ContinuousBatchingScheduler


@pytest.fixture(scope="module")
def setup():
    arch = get_arch("llama3-8b")
    arch = dataclasses.replace(arch, cfg=arch.cfg.reduced())
    params = arch.init(jax.random.PRNGKey(0))
    return arch, params


# ------------------------------------------------------------- pool units

def test_pool_alloc_free_round_trip():
    pool = PagedKVPool(8, page_size=4)
    assert pool.pages_for(0) == 0
    assert pool.pages_for(1) == 1
    assert pool.pages_for(4) == 1
    assert pool.pages_for(5) == 2
    got = pool.alloc_table("a", n=3)
    assert len(got) == 3 and pool.n_free_pages == 5
    pool.extend_table("a", n=2)
    assert len(pool.table("a")) == 5 and pool.n_free_pages == 3
    pool.free_table("a")
    assert pool.n_free_pages == 8 and not pool.has_table("a")


def test_pool_refuses_a_second_table_for_a_key():
    pool = PagedKVPool(6, page_size=4)
    pool.alloc_table("a", n=2)
    with pytest.raises(ValueError, match="already allocated"):
        pool.alloc_table("a", n=1)
    pool.free_table("a")
    assert pool.n_free_pages == 6


def test_pool_refcounted_prefix_sharing():
    """share() aliases pages without copying; a page frees only when its
    LAST owner drops it."""
    pool = PagedKVPool(4, page_size=4)
    src = pool.alloc_table("src", n=2)
    shared = pool.share("src", "dst")
    assert shared == src and pool.n_free_pages == 2  # no new pages taken
    pool.free_table("src")
    assert pool.n_free_pages == 2  # dst still owns them
    pool.free_table("dst")
    assert pool.n_free_pages == 4


def test_pool_oom_evicts_lru_unpinned_to_host():
    pool = PagedKVPool(4, page_size=4)
    pool.alloc_table("old", n=2)
    pool.alloc_table("new", n=2)
    pool.pin("new")
    pool.table("old")  # touch: old is now MRU...
    pool.alloc_table("big", n=2)  # ...but still the only evictable table
    assert pool.host_tables.keys() == {"old"}
    assert pool.evicted == 1 and not pool.has_table("old")
    # every remaining table pinned → OOM is an error, not a spin
    pool.pin("big")
    with pytest.raises(RuntimeError, match="pinned"):
        pool.alloc_table("doomed", n=1)


def test_pool_block_table_padding():
    pool = PagedKVPool(8, page_size=4)
    pool.alloc_table("a", n=3)
    bt = pool.block_table("a", max_pages=6)
    assert bt.dtype == np.int32
    np.testing.assert_array_equal(bt, list(pool.table("a")) + [0, 0, 0])


def test_paged_kv_view_page_budget_bound():
    """The view is the partition min-bounded by whole-lane page budgets;
    an under-provisioned pool admits less, a full one changes nothing."""
    part = KVPartition(4, {"x": 1})
    pool = PagedKVPool(2 * 4, page_size=4)  # only 2 lanes' worth of pages
    view = PagedKVView(part, pool, pages_per_lane=4)
    assert isinstance(view, KVView) and isinstance(part, KVView)
    assert view.n_free == 2 and view.n_free_for("x") == 2
    pool.alloc_table("r0", n=4)
    assert view.n_free == 1
    lane = view.alloc("x")
    view.release(lane)
    assert view.benefits(lane, "x") and not view.benefits(lane, "y")


# ------------------------------------------------------------ paged engine

def _run_sched(eng, prompts, max_new=8, **kw):
    sched = ContinuousBatchingScheduler(eng, strategy=OneOrAll(), **kw)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=max_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        sched.submit(r)
    sched.producer_done()
    sched.run_until_drained()
    return reqs, sched


def test_paged_matches_dense_through_straggler_spill(setup):
    """The acceptance gate: paged and dense engines produce bit-identical
    outputs per request through a spill/restore-heavy scheduler run, while
    the paged engine moves strictly fewer KV bytes."""
    arch, params = setup
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, 200, size=n).astype(np.int32)
               for n in (5, 9, 13, 7)]

    dense = InferenceEngine(arch, params, n_lanes=2, max_prompt_len=16,
                            max_len=48, kv_spill=HostSpillPool(8))
    d_reqs, d_sched = _run_sched(dense, prompts, lane_timeout=2)

    paged = PagedInferenceEngine(arch, params, n_lanes=2, max_prompt_len=16,
                                 max_len=48, kv_spill=HostSpillPool(8),
                                 page_size=8, prefetch_pages=1)
    p_reqs, p_sched = _run_sched(paged, prompts, lane_timeout=2)

    assert d_sched.stats.kv_spilled >= 1  # the scenario actually evicts
    assert p_sched.stats.kv_spilled == d_sched.stats.kv_spilled
    for dr, pr in zip(d_reqs, p_reqs):
        assert dr.generated == pr.generated, (dr.rid, dr.generated, pr.generated)
    assert paged.kv_bytes_moved < dense.kv_bytes_moved
    assert paged.kv_bytes_moved <= 0.5 * dense.kv_bytes_moved


def test_paged_spill_restore_round_trip_and_prefetch_tail(setup):
    """Restore splices prefetch_pages synchronously and queues the tail;
    the tail lands before the next decode step, so generation resumes
    exactly where the eviction stopped."""
    arch, params = setup
    rng = np.random.default_rng(11)
    prompt = rng.integers(1, 200, size=14).astype(np.int32)

    eng = PagedInferenceEngine(arch, params, n_lanes=2, max_prompt_len=16,
                               max_len=32, kv_spill=HostSpillPool(4),
                               page_size=8, prefetch_pages=1)
    r = Request(rid=0, prompt=prompt, max_new_tokens=6)
    eng.admit([r], None)
    for _ in range(3):
        out = eng.decode_tick()
        r.generated.append(out[r.lane])
    before = list(r.generated)
    assert eng.spill(r.lane, r.rid, None)
    lane = eng.try_restore(r.rid, None)
    assert lane is not None
    # 14 prompt tokens + 3 decodes = 17 rows = 3 pages > 1 prefetched page
    assert lane in eng._pending_restore
    r.lane = lane
    out = eng.decode_tick()  # flushes the tail, then decodes
    assert not eng._pending_restore
    r.generated.append(out[lane])

    ref_eng = PagedInferenceEngine(arch, params, n_lanes=2, max_prompt_len=16,
                                   max_len=32, page_size=8)
    ref = Request(rid=1, prompt=prompt, max_new_tokens=6)
    ref_eng.admit([ref], None)
    for _ in range(4):
        ref.generated.append(ref_eng.decode_tick()[ref.lane])
    assert r.generated == ref.generated and r.generated[:3] == before[:3]


def test_paged_block_tables_grow_with_decode(setup):
    """A lane's block table starts at the prompt's pages and gains one
    page each time decode crosses a page boundary."""
    arch, params = setup
    eng = PagedInferenceEngine(arch, params, n_lanes=2, max_prompt_len=16,
                               max_len=32, page_size=8)
    r = Request(rid=0, prompt=np.arange(1, 7, dtype=np.int32),
                max_new_tokens=16)
    eng.admit([r], None)
    assert len(eng.pool.table(r.lane)) == 1  # 6 rows + next write < 8
    for _ in range(3):
        eng.decode_tick()
    # length 9: decode wrote position 8 → page 1 must be in the table
    assert len(eng.pool.table(r.lane)) == 2
    for _ in range(8):
        eng.decode_tick()
    assert len(eng.pool.table(r.lane)) == 3
    eng.retire(r.lane)
    assert not eng.pool.has_table(r.lane)
    assert eng.pool.n_free_pages == eng.n_lanes * eng.pages_per_lane


def test_batched_oversized_prompts_admit_together(setup):
    """Carried-over fix: a burst of oversized prompts goes through the
    chunk pipeline as ONE batched dispatch (per-request resumable parts),
    not one prompt per speculation bet — outputs still exact."""
    arch, params = setup
    rng = np.random.default_rng(13)
    prompts = [rng.integers(1, 200, size=13).astype(np.int32)
               for _ in range(3)]

    ref_eng = InferenceEngine(arch, params, n_lanes=4, max_prompt_len=16,
                              max_len=48)
    ref_reqs, _ = _run_sched(ref_eng, prompts, max_new=4)

    eng = InferenceEngine(arch, params, n_lanes=4, max_prompt_len=16,
                          max_len=48)
    sched = ContinuousBatchingScheduler(eng, strategy=OneOrAll(),
                                        overlap=True, chunk_tokens=4)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=4)
            for i, p in enumerate(prompts)]
    for r in reqs:
        sched.submit(r)
    sched.producer_done()
    sched.run_until_drained()
    # one batched chunked bet, not three serialized ones
    assert sched.stats.spec_dispatched == 3
    trace = [n for _, n in sched.stats.admission_trace]
    assert 3 in trace  # the three oversized prompts landed together
    for rr, r in zip(ref_reqs, reqs):
        assert rr.generated == r.generated


def test_paged_view_feeds_paged_kernel(setup):
    """paged_view() exposes live KV as (pages, block tables); the Pallas
    paged kernel over that view agrees with the dense oracle over a DENSE
    engine's cache rows for the same requests — the end-to-end bridge from
    pool bookkeeping through page contents to the kernel."""
    from repro.kernels.decode_attention.ref import decode_ref
    from repro.kernels.paged_attention.ops import paged_decode_op

    arch, params = setup
    rng = np.random.default_rng(17)
    prompts = [rng.integers(1, 200, size=6 + 4 * i).astype(np.int32)
               for i in range(2)]
    eng = PagedInferenceEngine(arch, params, n_lanes=2, max_prompt_len=16,
                               max_len=32, page_size=8)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=4)
            for i, p in enumerate(prompts)]
    eng.admit(reqs, None)
    for _ in range(2):
        eng.decode_tick()
    view = eng.paged_view()
    assert view is not None and view["lanes"] == [0, 1]
    hkv, hd = view["k_pages"].shape[2], view["k_pages"].shape[3]
    q = jax.random.normal(jax.random.PRNGKey(0), (2, hkv * 2, hd))
    paged = paged_decode_op(q, view["k_pages"], view["v_pages"],
                            view["block_tables"], view["lengths"],
                            interpret=True)
    # Dense oracle: an ordinary dense engine run of the same requests —
    # its per-lane cache rows must equal what the pages hold.
    dense = InferenceEngine(arch, params, n_lanes=2, max_prompt_len=16,
                            max_len=32)
    dreqs = [Request(rid=i, prompt=p, max_new_tokens=4)
             for i, p in enumerate(prompts)]
    dense.admit(dreqs, None)
    for _ in range(2):
        dense.decode_tick()
    lanes = jnp.asarray([r.lane for r in dreqs])
    k = dense.cache["layers"]["k"][0][lanes]
    v = dense.cache["layers"]["v"][0][lanes]
    ref = decode_ref(q, k, v, view["lengths"])
    np.testing.assert_allclose(np.asarray(paged), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------------- pools updated in place

def _watch_pools(eng) -> list:
    """Record, for every decode dispatch of ``eng``, whether the pool
    arrays it was handed read deleted afterwards (donated and reused)."""
    seen = []
    dispatch = eng._dispatch_decode

    def watched(*args):
        old = jax.tree_util.tree_leaves(eng.cache)
        nxt = dispatch(*args)
        seen.append(all(a.is_deleted() for a in old))
        return nxt

    eng._dispatch_decode = watched
    return seen


def _pool_run(arch, params, scenario, engine_cls, **kw):
    """Drive one engine through ``scenario``; returns each request's
    tokens.  The paged engine takes the scenario's path (fused chunk
    tick, spill and restore, copy-on-write fork, prefix share); the dense
    engine serves the same requests straight, as the oracle."""
    rng = np.random.default_rng(53)
    paged = engine_cls is PagedInferenceEngine
    if scenario == "prefix_share":
        prompts = [np.concatenate([rng.integers(1, 200, size=16),
                                   rng.integers(1, 200, size=4)]
                                  ).astype(np.int32) for _ in range(3)]
        prompts[1][:16] = prompts[2][:16] = prompts[0][:16]
    else:
        prompts = [rng.integers(1, 200, size=n).astype(np.int32)
                   for n in (6, 13)]
    if paged:
        kw.update(page_size=8, prefetch_pages=1,
                  prefix_share=scenario == "prefix_share")
    eng = engine_cls(arch, params, n_lanes=4, max_prompt_len=32, max_len=48,
                     kv_spill=HostSpillPool(4), **kw)
    seen = _watch_pools(eng) if paged else None
    reqs = [Request(rid=i, prompt=p, max_new_tokens=12)
            for i, p in enumerate(prompts)]

    def tick(rs):
        out = eng.decode_tick()
        for r in rs:
            r.generated.append(out[r.lane])

    if scenario == "fused":
        eng.admit(reqs[:1], None)
        if paged:
            staged = eng.prefill_dispatch(reqs[1:], template=None, chunk=4)
            while not staged.complete:
                assert eng.stage_chunk(staged)
                tick(reqs[:1])
            eng.commit_prefill(staged)
            assert eng.fused_folds == 3
        else:
            for _ in range(3):  # the paged engine's three fused ticks
                tick(reqs[:1])
            eng.admit(reqs[1:], None)
        for _ in range(3):
            tick(reqs)
    else:
        eng.admit(reqs, None)
        for _ in range(3):
            tick(reqs)
        if paged and scenario == "spill_restore":
            r = reqs[1]
            assert eng.spill(r.lane, r.rid, None)
            r.lane = eng.try_restore(r.rid, None)
            assert r.lane in eng._pending_restore  # tail flushed next tick
        if paged and scenario == "cow_fork":
            # alias every page of each lane to a ghost reader: a write
            # into a shared page forks it first
            for r in reqs:
                n = len(eng.pool.pages(r.lane))
                eng.pool.share(r.lane, ("ghost", r.rid), n_pages=n)
        for _ in range(4):
            tick(reqs)
    if paged:
        assert len(seen) == eng.decode_steps and all(seen)
        assert eng.pool_in_place_ticks == eng.decode_steps
        if scenario == "prefix_share":
            assert eng.prefix_hits == 2
        if scenario == "cow_fork":
            # request 0 (length 9) wrote into its shared second page, and
            # forked it first
            ghost = eng.pool.pages(("ghost", 0))
            assert eng.pool.pages(reqs[0].lane)[1] != ghost[1]
    return [r.generated for r in reqs]


@pytest.mark.parametrize("scenario", ["fused", "spill_restore", "cow_fork",
                                      "prefix_share"])
def test_paged_decode_updates_pool_in_place(setup, scenario):
    """Every paged decode dispatch, a fused chunk tick included, consumes
    the pools it is handed: they read deleted afterwards and
    ``pool_in_place_ticks`` counts each tick.  Spill and restore, a
    copy-on-write fork and prefix sharing around the donated step still
    give the dense engine's tokens bit for bit."""
    arch, params = setup
    assert (_pool_run(arch, params, scenario, PagedInferenceEngine)
            == _pool_run(arch, params, scenario, InferenceEngine))
