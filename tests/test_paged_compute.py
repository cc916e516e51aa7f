"""Paged decode compute: oversubscribed pools, mid-decode eviction with
bit-identical resume, page quotas, and the fused prefill+decode dispatch."""
from __future__ import annotations

import dataclasses

import jax
import numpy as np
import pytest

from repro.core.strategies import OneOrAll
from repro.kernels import registry
from repro.models.registry import get_arch
from repro.serving.engine import HostSpillPool, InferenceEngine, KVPartition
from repro.serving.paged_kv import PagedInferenceEngine, PagedKVPool, PagedKVView
from repro.serving.request import Request
from repro.serving.scheduler import ContinuousBatchingScheduler


@pytest.fixture(scope="module")
def setup():
    arch = get_arch("llama3-8b")
    arch = dataclasses.replace(arch, cfg=arch.cfg.reduced())
    params = arch.init(jax.random.PRNGKey(0))
    return arch, params


def _run_sched(eng, prompts, max_new=8, **kw):
    sched = ContinuousBatchingScheduler(eng, strategy=OneOrAll(), **kw)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=max_new)
            for i, p in enumerate(prompts)]
    for r in reqs:
        sched.submit(r)
    sched.producer_done()
    sched.run_until_drained()
    return reqs, sched


# -------------------------------------------------------- oversubscription

def test_oversubscribed_admission_bound(setup):
    """With n_pages < n_lanes * max_len / page_size, admission is bounded
    by instantaneous whole-lane page budgets, not free lanes."""
    arch, params = setup
    eng = PagedInferenceEngine(arch, params, n_lanes=4, max_prompt_len=16,
                               max_len=32, page_size=8, n_pages=8)
    assert eng.paged_compute and eng.pages_per_lane == 4
    # 4 free lanes, but only 8 pages = 2 whole-lane budgets.
    assert eng.partition.n_free == 4
    assert eng.kv.n_free == 2 and eng.kv.n_free_for(None) == 2
    r = Request(rid=0, prompt=np.arange(1, 7, dtype=np.int32),
                max_new_tokens=4)
    eng.admit([r], None)  # 6-token prompt: one page
    assert eng.pool.n_free_pages == 7 and eng.kv.n_free == 1


def test_oversubscribed_constructor_guards(setup):
    arch, params = setup
    with pytest.raises(ValueError, match="at least one lane"):
        PagedInferenceEngine(arch, params, n_lanes=2, max_prompt_len=16,
                             max_len=32, page_size=8, n_pages=3)


def test_mid_decode_eviction_and_restore_bit_identical(setup):
    """An oversubscribed pool evicts the LRU lane mid-decode under page
    pressure; the scheduler re-queues it, the restore resumes it, and the
    final outputs are bit-identical to a fully-provisioned dense run."""
    arch, params = setup
    rng = np.random.default_rng(23)
    prompts = [rng.integers(1, 200, size=n).astype(np.int32) for n in (6, 5)]

    dense = InferenceEngine(arch, params, n_lanes=2, max_prompt_len=16,
                            max_len=32)
    d_reqs, _ = _run_sched(dense, prompts, max_new=16)

    paged = PagedInferenceEngine(arch, params, n_lanes=2, max_prompt_len=16,
                                 max_len=32, page_size=8, n_pages=5,
                                 kv_spill=HostSpillPool(8), prefetch_pages=1)
    p_reqs, p_sched = _run_sched(paged, prompts, max_new=16)

    # Growth to 3 pages per lane exceeds the 5-page pool: pressure evicted
    # at least one lane mid-decode, and the restore resumed it.
    assert paged.page_evictions >= 1
    assert p_sched.stats.kv_spilled >= 1
    assert p_sched.stats.kv_restored >= 1
    for dr, pr in zip(d_reqs, p_reqs):
        assert dr.generated == pr.generated, (dr.rid, dr.generated,
                                              pr.generated)


def test_all_pinned_pressure_raises(setup):
    """When every page is held by the lanes requesting growth themselves,
    eviction has no victim and the pool raises instead of spinning."""
    arch, params = setup
    eng = PagedInferenceEngine(arch, params, n_lanes=2, max_prompt_len=16,
                               max_len=16, page_size=8, n_pages=2)
    rng = np.random.default_rng(3)
    reqs = [Request(rid=i, prompt=rng.integers(1, 200, size=9)
                    .astype(np.int32), max_new_tokens=2) for i in range(2)]
    # Each 9-token prompt needs 2 pages; committing both needs 4 > 2, and
    # both lanes are in the commit's avoid set — no evictable victim.
    with pytest.raises(RuntimeError, match="pinned"):
        eng.admit(reqs, None)


def test_page_quota_reserves_pages_for_template():
    """Lane reservations translate into page quotas: a shared-pool burst
    cannot consume the pages a reserved template is owed."""
    part = KVPartition(4, {"x": 2})
    pool = PagedKVPool(16, page_size=4)
    used = {"x": 0}
    view = PagedKVView(part, pool, pages_per_lane=4,
                       page_quota={"x": 8}, used_pages=lambda t: used.get(t, 0))
    # x sees its reservation + shared; y sees the shared pool minus the
    # 8 pages still owed to x: (16 - 8) // 4 = 2 lane-equivalents.
    assert view.n_free_for("x") == 4
    assert view.n_free_for("y") == 2 and view.n_free_for(None) == 2
    used["x"] = 8  # x's lanes now hold their quota: nothing is owed
    pool.alloc_table("x0", n=8)
    assert view.n_free_for("y") == 2  # (16 - 8 free) // 4, no owed pages
    used["x"] = 0  # quota unmet again while only 8 pages remain free
    assert view.n_free_for("y") == 0


# ------------------------------------------------------------ fused dispatch

def test_fused_tick_is_one_dispatch_and_exact(setup):
    """A decode tick that folds a staged prefill chunk issues exactly ONE
    jitted device program, and both the decode lane's tokens and the
    chunked prompt's first token match the unfused engines."""
    arch, params = setup
    rng = np.random.default_rng(29)
    p0 = rng.integers(1, 200, size=6).astype(np.int32)
    pbig = rng.integers(1, 200, size=13).astype(np.int32)

    eng = PagedInferenceEngine(arch, params, n_lanes=2, max_prompt_len=16,
                               max_len=32, page_size=8)
    r0 = Request(rid=0, prompt=p0, max_new_tokens=12)
    eng.admit([r0], None)
    big = Request(rid=1, prompt=pbig, max_new_tokens=4)
    staged = eng.prefill_dispatch([big], template=None, chunk=4)
    assert staged.pending and not staged.complete
    fused_ticks = 0
    while not staged.complete:
        assert eng.stage_chunk(staged)
        before = eng.dispatches
        out = eng.decode_tick()
        assert eng.dispatches - before == 1  # decode + chunk, one program
        r0.generated.append(out[r0.lane])
        fused_ticks += 1
    assert eng.fused_folds == fused_ticks and fused_ticks >= 2
    assert not eng.stage_chunk(staged)  # nothing pending: fusion declines
    eng.commit_prefill(staged)

    # Unfused oracle: dense engine, same decode cadence, one-shot prefill.
    dense = InferenceEngine(arch, params, n_lanes=2, max_prompt_len=16,
                            max_len=32)
    d0 = Request(rid=0, prompt=p0, max_new_tokens=12)
    dense.admit([d0], None)
    for _ in range(fused_ticks):
        d0.generated.append(dense.decode_tick()[d0.lane])
    dbig = Request(rid=1, prompt=pbig, max_new_tokens=4)
    dense.admit([dbig], None)
    assert r0.generated == d0.generated
    assert big.generated == dbig.generated  # == the first token each


def test_fused_overlap_scheduler_bit_identical(setup):
    """End-to-end overlap + chunked run: the paged engine folds chunks
    into decode ticks (fused megabatch) and still matches the dense
    engine's outputs bit-for-bit."""
    arch, params = setup
    rng = np.random.default_rng(31)
    prompts = [rng.integers(1, 200, size=5).astype(np.int32),
               rng.integers(1, 200, size=13).astype(np.int32),
               rng.integers(1, 200, size=7).astype(np.int32)]

    dense = InferenceEngine(arch, params, n_lanes=4, max_prompt_len=16,
                            max_len=48)
    d_reqs, _ = _run_sched(dense, prompts, max_new=6, overlap=True,
                           chunk_tokens=4)

    paged = PagedInferenceEngine(arch, params, n_lanes=4, max_prompt_len=16,
                                 max_len=48, page_size=8)
    p_reqs, _ = _run_sched(paged, prompts, max_new=6, overlap=True,
                           chunk_tokens=4)

    for dr, pr in zip(d_reqs, p_reqs):
        assert dr.generated == pr.generated, (dr.rid, dr.generated,
                                              pr.generated)


# ------------------------------------------------------- kernel dispatch path

def test_interpret_kernel_matches_ref_path(setup):
    """The Pallas paged kernel under interpret mode and the pure-jnp ref
    produce the same greedy tokens — the CI kernels job's exercise."""
    arch, params = setup
    rng = np.random.default_rng(37)
    prompts = [rng.integers(1, 200, size=n).astype(np.int32) for n in (6, 9)]

    def run(**kw):
        eng = PagedInferenceEngine(arch, params, n_lanes=2, max_prompt_len=16,
                                   max_len=32, page_size=8, **kw)
        reqs = [Request(rid=i, prompt=p, max_new_tokens=3)
                for i, p in enumerate(prompts)]
        eng.admit(reqs, None)
        for _ in range(3):
            out = eng.decode_tick()
            for r in reqs:
                r.generated.append(out[r.lane])
        return [r.generated for r in reqs]

    assert run(use_kernel=False) == run(interpret=True)


def test_interpret_default_env(setup, monkeypatch):
    """REPRO_KERNEL_INTERPRET flips the engine's default dispatch to
    interpret mode (how CI runs kernel bodies on CPU)."""
    arch, params = setup
    monkeypatch.delenv("REPRO_KERNEL_INTERPRET", raising=False)
    assert not registry.interpret_default()
    eng = PagedInferenceEngine(arch, params, n_lanes=1, max_prompt_len=16,
                               max_len=16, page_size=8)
    assert eng._interpret is False
    monkeypatch.setenv("REPRO_KERNEL_INTERPRET", "1")
    assert registry.interpret_default()
    eng = PagedInferenceEngine(arch, params, n_lanes=1, max_prompt_len=16,
                               max_len=16, page_size=8)
    assert eng._interpret is True


def test_serve_requests_drives_paged_main_path(setup):
    """The launcher's entry point serves through the scheduler into the
    paged engine (paged compute, one decode dispatch per tick) and returns
    every request with its full token budget."""
    from repro.launch.serve import serve_requests

    arch, params = setup
    rng = np.random.default_rng(3)
    reqs = [Request(rid=i, prompt=rng.integers(1, 200, size=n).astype(np.int32),
                    max_new_tokens=4) for i, n in enumerate((5, 12, 9))]
    eng, sched, done = serve_requests(arch, params, reqs, lanes=2,
                                      max_len=32, max_prompt_len=16,
                                      page_size=8)
    assert isinstance(eng, PagedInferenceEngine) and eng.paged_compute
    assert sched.resilience is None
    assert sorted(r.rid for r in done) == [0, 1, 2]
    assert all(len(r.generated) == 4 for r in done)
    assert eng.decode_steps > 0 and eng.dispatches > 0
