"""Continuous batching scheduler: correctness vs sequential generation,
strategy behaviour, straggler re-queue, data pipeline determinism."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.strategies import (
    GrowingUpperThreshold,
    LowerThreshold,
    OneOrAll,
    PureAsync,
)
from repro.core.strategies import BatchingStrategy
from repro.data.pipeline import PrefetchLoader, SyntheticLMStream
from repro.models.registry import get_arch
from repro.serving.engine import InferenceEngine, KVPartition, proportional_shares
from repro.serving.request import Request
from repro.serving.scheduler import ContinuousBatchingScheduler


@pytest.fixture(scope="module")
def setup():
    arch = get_arch("llama3-8b")
    arch = dataclasses.replace(arch, cfg=arch.cfg.reduced())
    params = arch.init(jax.random.PRNGKey(0))
    return arch, params


def _requests(n, rng, max_new=6):
    return [
        Request(rid=i, prompt=rng.integers(1, 200, size=rng.integers(3, 14)).astype(np.int32),
                max_new_tokens=max_new)
        for i in range(n)
    ]


def _sequential_reference(arch, params, req, max_len=48):
    toks = jnp.asarray(req.prompt)[None]
    last, cache = arch.prefill(params, {"tokens": toks}, max_len=max_len)
    out = [int(jnp.argmax(last, -1)[0])]
    lengths = jnp.asarray([len(req.prompt)], jnp.int32)
    cur = jnp.asarray(out, jnp.int32)
    for _ in range(req.max_new_tokens - 1):
        lg, cache = arch.decode_step(params, cur, cache, lengths)
        nxt = int(jnp.argmax(lg, -1)[0])
        out.append(nxt)
        cur = jnp.asarray([nxt], jnp.int32)
        lengths = lengths + 1
    return out


@pytest.mark.parametrize("strategy", [
    PureAsync(), OneOrAll(), LowerThreshold(bt=3),
    GrowingUpperThreshold(initial_upper=2, bt=None),
])
def test_scheduler_matches_sequential(setup, strategy):
    arch, params = setup
    eng = InferenceEngine(arch, params, n_lanes=4, max_prompt_len=16, max_len=48)
    sched = ContinuousBatchingScheduler(eng, strategy=strategy)
    rng = np.random.default_rng(42)
    reqs = _requests(9, rng)
    for r in reqs:
        sched.submit(r)
    sched.producer_done()
    done = sched.run_until_drained()
    assert len(done) == 9
    for r in reqs[:3]:  # spot-check 3 against the sequential oracle
        ref = _sequential_reference(arch, params, r)
        assert r.generated[: len(ref)] == ref, (r.rid, r.generated, ref)


def test_admission_trace_recorded(setup):
    arch, params = setup
    eng = InferenceEngine(arch, params, n_lanes=8, max_prompt_len=16, max_len=48)
    sched = ContinuousBatchingScheduler(eng, strategy=OneOrAll())
    rng = np.random.default_rng(1)
    for r in _requests(8, rng):
        sched.submit(r)
    sched.producer_done()
    sched.run_until_drained()
    assert sum(n for _, n in sched.stats.admission_trace) == 8
    # OneOrAll with an empty engine admits everything at once
    assert sched.stats.admission_trace[0][1] == 8


def test_straggler_requeue(setup):
    arch, params = setup
    eng = InferenceEngine(arch, params, n_lanes=2, max_prompt_len=16, max_len=64)
    sched = ContinuousBatchingScheduler(eng, strategy=OneOrAll(), lane_timeout=3)
    rng = np.random.default_rng(2)
    reqs = _requests(2, rng, max_new=10)  # 10 tokens > timeout 3 → requeue
    for r in reqs:
        sched.submit(r)
    sched.producer_done()
    # run a bounded number of ticks; requests keep being requeued
    for _ in range(30):
        sched.tick()
    assert sched.stats.requeued >= 1


def test_lanes_respected(setup):
    arch, params = setup
    eng = InferenceEngine(arch, params, n_lanes=2, max_prompt_len=16, max_len=48)
    sched = ContinuousBatchingScheduler(eng, strategy=OneOrAll())
    rng = np.random.default_rng(3)
    for r in _requests(7, rng):
        sched.submit(r)
    sched.producer_done()
    done = sched.run_until_drained()
    assert len(done) == 7
    assert max(n for _, n in sched.stats.admission_trace) <= 2


def test_mixed_template_lane_admissions(setup):
    """Requests with different templates are admitted from per-template
    lanes: every admission batch is homogeneous and each lane's trace is
    recorded separately."""
    arch, params = setup
    eng = InferenceEngine(arch, params, n_lanes=8, max_prompt_len=16, max_len=48)
    sched = ContinuousBatchingScheduler(eng, strategy=OneOrAll())
    rng = np.random.default_rng(5)
    reqs = []
    for i in range(8):
        tmpl = "chat" if i % 2 == 0 else "summarize"
        size = 4 if tmpl == "chat" else 13
        reqs.append(Request(rid=i, prompt=rng.integers(1, 200, size=size).astype(np.int32),
                            max_new_tokens=4, template=tmpl))
    for r in reqs:
        sched.submit(r)
    sched.producer_done()
    done = sched.run_until_drained()
    assert len(done) == 8
    assert set(sched.stats.lane_admissions) == {"chat", "summarize"}
    # each lane admitted its 4 requests; totals agree with the global trace
    for tmpl, trace in sched.stats.lane_admissions.items():
        assert sum(n for _, n in trace) == 4
    assert sum(n for _, n in sched.stats.admission_trace) == 8
    assert sched.queues == {}  # drained lanes are garbage-collected


# ---------------------------------------------------------------------------
# KV partitioning (per-template lane reservations)
# ---------------------------------------------------------------------------


def test_kv_partition_reservations_and_release():
    part = KVPartition(6, {"a": 2, "b": 2})
    assert part.n_free == 6
    assert part.n_free_for("a") == 4          # own 2 + shared 2
    assert part.n_free_for("c") == 2          # unreserved: shared only
    assert part.n_free_for(None) == 2
    # a's burst drains its reservation first, then the shared pool…
    taken = [part.alloc("a") for _ in range(4)]
    assert part.n_free_for("a") == 0
    # …but b's reservation is untouched by the burst
    assert part.n_free_for("b") == 2
    assert part.n_free_for("c") == 0
    b_lanes = [part.alloc("b"), part.alloc("b")]
    # releases go home: a's reserved lanes back to a, shared back to shared
    for lane in taken:
        part.release(lane)
    assert part.n_free_for("a") == 4 and part.n_free_for("c") == 2
    for lane in b_lanes:
        part.release(lane)
    assert part.n_free == 6


def test_kv_partition_validates_shares():
    with pytest.raises(ValueError):
        KVPartition(4, {"a": 3, "b": 2})  # over-reserved
    with pytest.raises(ValueError):
        KVPartition(4, {"a": -1})


def test_proportional_shares_follow_weights():
    shares = proportional_shares({"chat": 3.0, "embed": 1.0}, n_lanes=8,
                                 reserve=0.5)
    assert shares == {"chat": 3, "embed": 1}  # 4 reserved, 4 shared
    assert proportional_shares({}, 8) == {}
    # tiny budgets round by largest remainder, deterministically
    shares = proportional_shares({"a": 1.0, "b": 1.0, "c": 1.0}, n_lanes=4,
                                 reserve=0.5)
    assert sum(shares.values()) == 2 and all(v >= 0 for v in shares.values())
    with pytest.raises(ValueError):
        proportional_shares({"a": 0.0}, 8)


def test_engine_kv_burst_cannot_take_reserved_lanes(setup):
    """A single-template admission burst may drain its own reservation and
    the shared pool, but other templates' reserved lanes stay free — the
    contention guarantee the partition exists for."""
    arch, params = setup
    eng = InferenceEngine(arch, params, n_lanes=6, max_prompt_len=16,
                          max_len=48, kv_shares={"a": 2, "b": 2})
    rng = np.random.default_rng(11)
    burst = _requests(4, rng, max_new=2)
    assert eng.n_free_for("a") == 4
    eng.admit(burst, template="a")            # burst takes ALL of a's lanes
    assert eng.n_free_for("a") == 0
    assert eng.n_free_for("b") == 2           # b's reservation never evicted
    with pytest.raises(AssertionError):
        eng.admit(_requests(1, rng, max_new=2), template="a")
    b_reqs = _requests(2, rng, max_new=2)
    eng.admit(b_reqs, template="b")           # b admits despite the burst
    for r in burst:
        eng.retire(r.lane)
    assert eng.n_free_for("a") == 4           # lanes went home on release
    for r in b_reqs:
        eng.retire(r.lane)
    assert eng.n_free == 6


# ---------------------------------------------------------------------------
# speculative prefill / decode overlap
# ---------------------------------------------------------------------------


def test_overlap_matches_sequential(setup):
    """overlap=True pipelines prefill under decode but must not change a
    single generated token (same greedy decode, same KV)."""
    arch, params = setup
    eng = InferenceEngine(arch, params, n_lanes=4, max_prompt_len=16, max_len=48)
    sched = ContinuousBatchingScheduler(eng, strategy=OneOrAll(), overlap=True)
    rng = np.random.default_rng(42)
    reqs = _requests(9, rng)
    for r in reqs:
        sched.submit(r)
    sched.producer_done()
    done = sched.run_until_drained()
    assert len(done) == 9
    for r in reqs[:3]:
        ref = _sequential_reference(arch, params, r)
        assert r.generated[: len(ref)] == ref, (r.rid, r.generated, ref)
    st = sched.stats
    # the pipeline actually ran, its ledger balances, and nothing is staged
    assert st.spec_dispatched >= 1
    assert st.spec_dispatched == st.spec_committed + st.spec_aborted
    assert not sched._staged
    # every request lands exactly once (aborted speculations re-land later)
    assert sum(n for _, n in st.admission_trace) == 9
    assert sum(1 for r in done if r.metrics.speculative) == st.spec_committed


def test_overlap_with_policy_and_kv_shares(setup):
    """The full tentpole wiring: LanePolicy weights → proportional KV
    shares → overlapped scheduler; mixed templates all complete and each
    lane's admissions stay homogeneous."""
    arch, params = setup
    from repro.core.lane_policy import LanePolicy

    weights = {"chat": 2.0, "summarize": 1.0}
    shares = proportional_shares(weights, n_lanes=8, reserve=0.5)
    eng = InferenceEngine(arch, params, n_lanes=8, max_prompt_len=16,
                          max_len=48, kv_shares=shares)
    policy = LanePolicy(hot_threshold=10**9, lane_weights=weights)
    sched = ContinuousBatchingScheduler(eng, policy=policy, overlap=True)
    rng = np.random.default_rng(6)
    reqs = []
    for i in range(10):
        tmpl = "chat" if i % 2 == 0 else "summarize"
        size = 4 if tmpl == "chat" else 13
        reqs.append(Request(rid=i,
                            prompt=rng.integers(1, 200, size=size).astype(np.int32),
                            max_new_tokens=4, template=tmpl))
    for r in reqs:
        sched.submit(r)
    sched.producer_done()
    done = sched.run_until_drained()
    assert len(done) == 10
    assert set(sched.stats.lane_admissions) == {"chat", "summarize"}


class _AbortRecorder(BatchingStrategy):
    """OneOrAll that records observe_abort feedback."""

    def __init__(self):
        self.aborts: list = []

    def decide(self, n_pending, producer_done):
        return n_pending

    def observe_abort(self, duration, depth=1):
        self.aborts.append((duration, depth))


def test_spec_abort_requeues_and_feeds_observe_abort(setup):
    """A speculation whose freed lane lands in another template's
    reservation misses: the staged requests go back to the queue head and
    the wasted prefill feeds observe_abort."""
    arch, params = setup
    # Every lane reserved to "x": template "y" has NO admissible lane, so a
    # speculative dispatch for y (betting on x's imminent retirement) must
    # abort at commit — deterministically.  The pool-aware sizing hint
    # (lane_benefits) would refuse that bet outright, so disable it: this
    # exercises the documented fallback for engines without the hint,
    # whose speculations CAN miss.
    eng = InferenceEngine(arch, params, n_lanes=2, max_prompt_len=16,
                          max_len=48, kv_shares={"x": 2})
    eng.kv.benefits = None  # instance attr shadows the method → optimistic
    strat = _AbortRecorder()
    sched = ContinuousBatchingScheduler(eng, strategy=strat, overlap=True)
    rng = np.random.default_rng(3)
    rx = Request(rid=0, prompt=rng.integers(1, 200, 6).astype(np.int32),
                 max_new_tokens=2, template="x")
    ry = Request(rid=1, prompt=rng.integers(1, 200, 6).astype(np.int32),
                 max_new_tokens=2, template="y")
    sched.submit(rx)
    sched.submit(ry)
    sched.producer_done()
    sched.tick()   # admits x; speculates y on x's imminent retirement
    assert sched.stats.spec_dispatched == 1
    sched.tick()   # x's lane went home to x's pool: y's commit finds 0 lanes
    assert sched.stats.spec_aborted == 1
    assert sched.stats.spec_committed == 0
    assert len(sched.queues["y"]) == 1        # back at the head of its lane
    assert ry.generated == []                 # nothing committed
    assert ry.metrics.speculative is False    # the attempt did not land
    assert len(strat.aborts) == 1
    assert strat.aborts[0][0] > 0.0 and strat.aborts[0][1] >= 1
    assert rx.done                            # x finished untouched


class _SplitStubEngine:
    """No-JAX engine with the full split dispatch surface (a KVPartition
    exposed as ``kv``, dispatch/commit) for scheduler-logic tests."""

    def __init__(self, n_lanes=2, kv_shares=None):
        self.partition = KVPartition(n_lanes, kv_shares)
        self.active: dict = {}

    @property
    def kv(self):
        return self.partition  # the KVView the scheduler binds

    @property
    def n_free(self):
        return self.partition.n_free

    def n_free_for(self, template):
        return self.partition.n_free_for(template)

    def prefill_dispatch(self, requests, template=None):
        return dataclasses.make_dataclass("S", ["template", "requests"])(
            template, list(requests))

    def commit_prefill(self, staged, n=None):
        reqs = staged.requests if n is None else staged.requests[:n]
        for r in reqs:
            r.lane = self.partition.alloc(staged.template)
            r.generated.append(0)
            self.active[r.lane] = r
        return (len(staged.requests), 8)

    def admit(self, requests, template=None):
        return self.commit_prefill(self.prefill_dispatch(requests, template))

    def decode_tick(self):
        return {lane: 1 for lane in self.active}

    def retire(self, lane):
        self.active.pop(lane, None)
        self.partition.release(lane)


def test_weighted_spec_scan_passes_a_declining_lane():
    """Under weighted-fair picking, a head lane whose strategy declines
    must not blind the speculator: the scan filters declined lanes out of
    the candidate set and speculates the next dispatchable one."""
    from repro.core.lane_policy import LanePolicy

    class _Wait(BatchingStrategy):
        def decide(self, n_pending, producer_done):
            return 0  # always "wait" — e.g. AdaptiveCost below threshold

    class _TakeAll(BatchingStrategy):
        def decide(self, n_pending, producer_done):
            return n_pending

    eng = _SplitStubEngine(n_lanes=1)
    policy = LanePolicy(lane_weights={"a": 1.0, "b": 1.0},
                        overrides={"a": _Wait(), "b": _TakeAll(),
                                   "c": _TakeAll()})
    sched = ContinuousBatchingScheduler(eng, policy=policy, overlap=True)
    rng = np.random.default_rng(0)
    # occupy the only lane; rid=0 retires at the NEXT tick's decode
    # (token 0 at admit + one token per decode tick → 3 tokens = 2 ticks)
    sched.submit(Request(rid=0, prompt=rng.integers(1, 9, 4).astype(np.int32),
                         max_new_tokens=3, template="c"))
    sched.tick()
    assert eng.n_free == 0 and len(sched.running) == 1
    # "a" wins the weighted-fair pick (earlier join at the vtime floor)…
    sched.submit(Request(rid=1, prompt=rng.integers(1, 9, 4).astype(np.int32),
                         max_new_tokens=2, template="a"))
    sched.submit(Request(rid=2, prompt=rng.integers(1, 9, 4).astype(np.int32),
                         max_new_tokens=2, template="b"))
    done = sched.tick()
    assert [r.rid for r in done] == [0]  # rid=0 retired during this tick
    # …but "a" declines, so the speculation must land on "b", not nothing
    assert sched._staged and sched._staged[0].template == "b"
    assert sched.stats.spec_dispatched == 1
    # and the declined lane kept its queue position (no rotation)
    assert sched._ready.peek(select=policy.lane_min) == "a"
    done = sched.tick()  # commits b's spec prefill; decode finishes it
    assert [r.rid for r in done] == [2]
    assert sched.stats.spec_committed == 1 and sched.stats.spec_aborted == 0
    assert len(sched.queues["a"]) == 1  # "a" still parked, untouched


# ---------------------------------------------------------------------------
# depth-k speculation pipeline
# ---------------------------------------------------------------------------


class _TakeAllRec(BatchingStrategy):
    """Take-all strategy that records observe_abort feedback."""

    def __init__(self):
        self.aborts: list = []

    def decide(self, n_pending, producer_done):
        return n_pending

    def observe_abort(self, duration, depth=1):
        self.aborts.append((duration, depth))


def test_spec_depth_validation():
    eng = _SplitStubEngine(n_lanes=2)
    with pytest.raises(ValueError):
        ContinuousBatchingScheduler(eng, spec_depth=0)
    with pytest.raises(ValueError):
        ContinuousBatchingScheduler(eng, spec_depth=2)  # needs overlap
    with pytest.raises(ValueError):
        ContinuousBatchingScheduler(eng, chunk_tokens=4)  # needs overlap
    with pytest.raises(ValueError):
        # stub engine has no prefill_resume: chunking must be refused
        ContinuousBatchingScheduler(eng, overlap=True, chunk_tokens=4)
    s = ContinuousBatchingScheduler(eng, overlap=True, spec_depth=4)
    assert s.spec_depth == 4


def test_depth_k_pipeline_stages_multiple_bets():
    """With spec_depth=3 and several ready lanes, one tick stages multiple
    bets, each sized against capacity net of older bets' promises."""
    from repro.serving.scheduler import _SpecTask  # noqa: F401 (API check)

    eng = _SplitStubEngine(n_lanes=4)
    sched = ContinuousBatchingScheduler(eng, strategy=OneOrAll(),
                                        overlap=True, spec_depth=3)
    rng = np.random.default_rng(0)
    # occupy all 4 lanes with requests retiring one tick later (token 0 at
    # admit + decode per tick: remaining hits 1 during the NEXT tick)
    runners = [Request(rid=i, prompt=rng.integers(1, 9, 4).astype(np.int32),
                       max_new_tokens=3, template=f"run{i}")
               for i in range(4)]
    for r in runners:
        sched.submit(r)
    sched.tick()  # all 4 admitted (OneOrAll per lane, 1 each)
    assert eng.n_free == 0
    # 4 lanes retire next tick (remaining == 1) → speculative capacity 4,
    # split across bets: older bets' promises shrink younger bets.
    for i, tmpl in enumerate(("a", "a", "b", "b", "c")):
        sched.submit(Request(rid=10 + i,
                             prompt=rng.integers(1, 9, 4).astype(np.int32),
                             max_new_tokens=1, template=tmpl))
    sched.tick()
    staged = list(sched._staged)
    # a promises 2 of the 4 speculative lanes, b the other 2; c sees
    # 4 − 2 − 2 = 0 remaining capacity and is DECLINED — the pipeline
    # fills to available capacity, not blindly to spec_depth.
    assert [t.template for t in staged] == ["a", "b"]
    assert [len(t.batch) for t in staged] == [2, 2]
    assert len(sched.queues["c"]) == 1  # declined, still queued
    assert sched.stats.spec_dispatched == 4
    sched.tick()  # both bets commit oldest-first at this boundary
    assert sched.stats.spec_committed == 4
    assert sched.stats.spec_aborted == 0


def test_depth_k_abort_cascade_oldest_first():
    """The cascade discipline: the oldest bet settles first (partial
    commit + shortfall abort); after the miss, a younger bet covered by
    its own reservation survives staged, an uncovered one aborts NOW and
    feeds observe_abort with its pipeline depth."""
    from repro.core.lane_policy import LanePolicy
    from repro.serving.scheduler import _SpecTask

    eng = _SplitStubEngine(n_lanes=3, kv_shares={"b": 1})  # 2 shared + b's 1
    rec_a, rec_b, rec_c = _TakeAllRec(), _TakeAllRec(), _TakeAllRec()
    policy = LanePolicy(overrides={"a": rec_a, "b": rec_b, "c": rec_c})
    sched = ContinuousBatchingScheduler(eng, policy=policy, overlap=True,
                                        spec_depth=3)
    rng = np.random.default_rng(1)

    def mk(rid, tmpl):
        return Request(rid=rid, prompt=rng.integers(1, 9, 4).astype(np.int32),
                       max_new_tokens=8, template=tmpl)

    # Occupy ONE shared lane with a long runner so only 1 shared lane +
    # b's reserved lane are free at the boundary.
    runner = mk(0, "long")
    eng.admit([runner], template="long")
    sched.running[runner.lane] = runner
    sched._lane_age[runner.lane] = 0
    # Stage three bets by hand (deterministic pipeline state):
    #   oldest: "a" wants 2 shared lanes — only 1 free → partial miss
    #   middle: "b" wants 1 — its own reservation covers it → survives
    #   youngest: "c" wants 1 shared — uncovered after the miss → aborts
    a1, a2, b1, c1 = mk(1, "a"), mk(2, "a"), mk(3, "b"), mk(4, "c")
    for t in (_SpecTask(eng, "a", [a1, a2]), _SpecTask(eng, "b", [b1]),
              _SpecTask(eng, "c", [c1])):
        t.join()
        sched._staged.append(t)
    # Boundaries 1 and 2: the oldest bet's shortfall is within its
    # spec_depth horizon — it WAITS (no split, no abort), younger bets
    # queue behind it.
    sched.tick()
    sched.tick()
    st = sched.stats
    assert st.spec_committed == 0 and st.spec_aborted == 0
    assert [t.template for t in sched._staged] == ["a", "b", "c"]
    # Boundary 3: the horizon expired → the miss settles the cascade.
    sched.tick()
    # oldest: committed 1, aborted 1 (back at a's queue head)
    assert st.spec_committed == 1 and a1.lane is not None
    assert list(sched.queues["a"]) == [a2]
    # youngest: uncovered → aborted at the SAME boundary, its pipeline
    # depth (3 boundaries staged) attributed to the abort penalty
    assert list(sched.queues["c"]) == [c1]
    assert st.spec_aborted == 2
    assert len(rec_c.aborts) == 1 and rec_c.aborts[0][1] == 3
    # partial commits carry no penalty; the surviving bet none either
    assert rec_a.aborts == [] and rec_b.aborts == []
    # middle bet survived the cascade and is still staged, oldest-first
    assert [t.template for t in sched._staged] == ["b"]
    sched.tick()  # b's reservation still holds its lane → commits now
    assert b1.lane is not None and sched.stats.spec_committed == 2


# ---------------------------------------------------------------------------
# chunked prefill
# ---------------------------------------------------------------------------


def test_engine_chunked_prefill_matches_one_shot(setup):
    """Resume-equivalence at the engine level: dispatch(chunk=) + resume
    loop + commit generates EXACTLY the tokens one-shot admit does."""
    arch, params = setup
    rng = np.random.default_rng(21)
    prompt = rng.integers(1, 200, size=13).astype(np.int32)

    eng1 = InferenceEngine(arch, params, n_lanes=2, max_prompt_len=16, max_len=48)
    r1 = Request(rid=0, prompt=prompt, max_new_tokens=6)
    eng1.admit([r1], template="t")
    for _ in range(5):
        for lane, tok in eng1.decode_tick().items():
            if lane == r1.lane:
                r1.generated.append(tok)

    eng2 = InferenceEngine(arch, params, n_lanes=2, max_prompt_len=16, max_len=48)
    r2 = Request(rid=1, prompt=prompt, max_new_tokens=6)
    staged = eng2.prefill_dispatch([r2], template="t", chunk=4)
    assert not staged.complete and staged.first is None
    resumes = 0
    while not eng2.prefill_resume(staged):
        resumes += 1
    assert resumes + 1 == 3  # ceil((13-4)/4) = 3 chunks after the first
    eng2.commit_prefill(staged)
    for _ in range(5):
        for lane, tok in eng2.decode_tick().items():
            if lane == r2.lane:
                r2.generated.append(tok)
    assert r2.generated == r1.generated

    # a prompt that fits one chunk falls through to the one-shot path
    short = Request(rid=2, prompt=prompt[:3], max_new_tokens=2)
    st = eng2.prefill_dispatch([short], template="t", chunk=4)
    assert st.complete and st.first is not None


def test_scheduler_chunked_prefill_overlaps_and_matches(setup):
    """A huge prompt under chunk_tokens rides the speculation thread one
    chunk per tick and still produces the one-shot tokens; decode of
    other lanes keeps running while the chunks fold in."""
    arch, params = setup
    rng = np.random.default_rng(22)
    big_prompt = rng.integers(1, 200, size=14).astype(np.int32)

    # reference: one-shot admit of the same prompt
    ref_eng = InferenceEngine(arch, params, n_lanes=4, max_prompt_len=16,
                              max_len=48)
    ref = Request(rid=0, prompt=big_prompt, max_new_tokens=5)
    ref_sched = ContinuousBatchingScheduler(ref_eng, strategy=OneOrAll())
    ref_sched.submit(ref)
    ref_sched.producer_done()
    ref_sched.run_until_drained()

    eng = InferenceEngine(arch, params, n_lanes=4, max_prompt_len=16,
                          max_len=48)
    sched = ContinuousBatchingScheduler(eng, strategy=OneOrAll(),
                                        overlap=True, chunk_tokens=4)
    big = Request(rid=1, prompt=big_prompt, max_new_tokens=5,
                  template="big")
    small = [Request(rid=10 + i,
                     prompt=rng.integers(1, 200, 4).astype(np.int32),
                     max_new_tokens=4, template="small") for i in range(3)]
    sched.submit(small[0])
    sched.tick()          # occupy a lane so decode has work under the chunks
    sched.submit(big)
    for r in small[1:]:
        sched.submit(r)
    sched.producer_done()
    done = sched.run_until_drained()
    assert len(done) == 4
    assert big.generated == ref.generated  # chunked ≡ one-shot
    assert sched.stats.spec_chunks >= 2   # the chunk pipeline actually ran
    assert big.metrics.speculative        # landed via the overlap path


# ---------------------------------------------------------------------------
# host KV spill
# ---------------------------------------------------------------------------


def test_host_spill_pool_lru_and_budget():
    from repro.serving.engine import HostSpillPool

    pool = HostSpillPool(max_entries=2)
    pool.put(1, "a", {"x": 1})
    pool.put(2, "a", {"x": 2})
    pool.put(3, "b", {"x": 3})  # over max_entries: LRU (key 1) dropped
    assert 1 not in pool and 2 in pool and 3 in pool
    assert pool.take(2) == {"x": 2}
    assert pool.take(2) is None  # taken once
    assert pool.snapshot()["spilled"] == 3
    assert pool.snapshot()["dropped"] == 1
    assert pool.snapshot()["restored"] == 1

    # per-template budget: one template's churn cannot evict another's
    budgets = {"a": 1}
    pool2 = HostSpillPool(max_entries=8,
                          budget_for=lambda t: budgets.get(t))
    pool2.put(1, "a", {"x": 1})
    pool2.put(2, "b", {"x": 2})
    pool2.put(3, "a", {"x": 3})  # a over budget: drops a's LRU (key 1)
    assert 1 not in pool2 and 2 in pool2 and 3 in pool2
    # budget 0 fences a template out entirely — put REPORTS the refusal
    # (and accepts() lets callers skip the KV copy up front)
    budgets["c"] = 0
    assert pool2.accepts("a") and not pool2.accepts("c")
    assert pool2.put(4, "c", {"x": 4}) is False
    assert 4 not in pool2
    assert pool2.put(5, "a", {"x": 5}) is True

    with pytest.raises(ValueError):
        HostSpillPool(max_entries=0)


def test_spill_restore_round_trip_preserves_decode_output(setup):
    """A straggler-evicted request whose KV was spilled resumes decoding
    on re-admission with its tokens intact — final output identical to an
    uninterrupted run, with zero extra prefills."""
    from repro.serving.engine import HostSpillPool

    arch, params = setup
    rng = np.random.default_rng(31)
    prompt = rng.integers(1, 200, size=9).astype(np.int32)

    ref_eng = InferenceEngine(arch, params, n_lanes=2, max_prompt_len=16,
                              max_len=48)
    ref = Request(rid=0, prompt=prompt, max_new_tokens=8)
    ref_sched = ContinuousBatchingScheduler(ref_eng, strategy=OneOrAll())
    ref_sched.submit(ref)
    ref_sched.producer_done()
    ref_sched.run_until_drained()

    eng = InferenceEngine(arch, params, n_lanes=2, max_prompt_len=16,
                          max_len=48, kv_spill=HostSpillPool(max_entries=4))
    sched = ContinuousBatchingScheduler(eng, strategy=OneOrAll(),
                                        lane_timeout=2)
    r = Request(rid=1, prompt=prompt, max_new_tokens=8)
    sched.submit(r)
    sched.producer_done()
    done = sched.run_until_drained()
    assert [x.rid for x in done] == [1]
    st = sched.stats
    assert st.kv_spilled >= 1          # the straggler actually evicted
    assert st.kv_restored == st.kv_spilled  # every eviction restored
    assert r.generated == ref.generated     # decode output preserved
    assert eng.prefill_calls == 1      # restored, never re-prefilled
    assert eng.kv_spill.snapshot()["restored"] == st.kv_restored


def test_spill_miss_restarts_cleanly(setup):
    """If the spill entry is evicted before re-admission (pool budget),
    the request re-prefills from scratch — stale partial generation is
    discarded, output still correct."""
    from repro.serving.engine import HostSpillPool

    arch, params = setup
    rng = np.random.default_rng(32)
    prompt = rng.integers(1, 200, size=9).astype(np.int32)

    ref_eng = InferenceEngine(arch, params, n_lanes=2, max_prompt_len=16,
                              max_len=48)
    ref = Request(rid=0, prompt=prompt, max_new_tokens=6)
    ref_sched = ContinuousBatchingScheduler(ref_eng, strategy=OneOrAll())
    ref_sched.submit(ref)
    ref_sched.producer_done()
    ref_sched.run_until_drained()

    # budget_for returns 0: every spill is dropped on arrival (the
    # degenerate pool) — restores always miss.
    eng = InferenceEngine(arch, params, n_lanes=2, max_prompt_len=16,
                          max_len=48,
                          kv_spill=HostSpillPool(max_entries=4,
                                                 budget_for=lambda t: 0))
    sched = ContinuousBatchingScheduler(eng, strategy=OneOrAll(),
                                        lane_timeout=3)
    r = Request(rid=1, prompt=prompt, max_new_tokens=6)
    sched.submit(r)
    sched.producer_done()
    for _ in range(10):  # tick until the straggler is evicted once
        sched.tick()
        if sched.stats.requeued:
            break
    # the fenced pool refused the entry: spill() reported the truth, so
    # kv_spilled stays 0 and the partial generation was discarded at once
    assert sched.stats.requeued == 1 and sched.stats.kv_spilled == 0
    assert r.generated == []
    sched.lane_timeout = None  # let the restart run to completion
    done = sched.run_until_drained()
    assert [x.rid for x in done] == [1]
    assert sched.stats.kv_restored == 0  # nothing staged: nothing restored
    assert r.generated == ref.generated  # restarted cleanly, same output
    assert eng.prefill_calls >= 2        # the restart re-prefilled


def test_spilled_oversized_prompt_is_restored_not_starved(setup):
    """Regression: a spilled request whose prompt exceeds chunk_tokens
    used to starve forever — the admission oversized-prompt gate skipped
    the lane before the restore path ran, while the spec path declined it
    because has_spill() was true.  The restore path must win (it pays no
    prefill, so prompt width is irrelevant) and the request completes
    with its decode output preserved."""
    from repro.serving.engine import HostSpillPool

    arch, params = setup
    rng = np.random.default_rng(41)
    prompt = rng.integers(1, 200, size=15).astype(np.int32)

    ref_eng = InferenceEngine(arch, params, n_lanes=2, max_prompt_len=16,
                              max_len=64)
    ref = Request(rid=0, prompt=prompt, max_new_tokens=12)
    ref_sched = ContinuousBatchingScheduler(ref_eng, strategy=OneOrAll())
    ref_sched.submit(ref)
    ref_sched.producer_done()
    ref_sched.run_until_drained()

    eng = InferenceEngine(arch, params, n_lanes=2, max_prompt_len=16,
                          max_len=64, kv_spill=HostSpillPool(max_entries=4))
    sched = ContinuousBatchingScheduler(eng, strategy=OneOrAll(),
                                        overlap=True, chunk_tokens=8,
                                        lane_timeout=3)
    r = Request(rid=1, prompt=prompt, max_new_tokens=12, template="doc")
    sched.submit(r)
    sched.producer_done()
    done = sched.run_until_drained(max_ticks=500)  # pre-fix: RuntimeError
    assert [x.rid for x in done] == [1]
    assert sched.stats.kv_spilled >= 1
    assert sched.stats.kv_restored == sched.stats.kv_spilled
    assert r.generated == ref.generated
    assert eng.prefill_calls == 1  # chunked prefill once, then restores only


def test_abort_cascade_keeps_same_template_fifo_order():
    """Regression: when an older and a younger same-template bet both
    abort at one boundary, the younger batch must requeue BEHIND the
    older one (requeues flush youngest-first), preserving arrival order
    at the queue head."""
    from repro.serving.scheduler import _SpecTask

    eng = _SplitStubEngine(n_lanes=1)
    sched = ContinuousBatchingScheduler(eng, strategy=_TakeAllRec(),
                                        overlap=True, spec_depth=2)
    rng = np.random.default_rng(2)

    def mk(rid):
        return Request(rid=rid, prompt=rng.integers(1, 9, 4).astype(np.int32),
                       max_new_tokens=8, template="t")

    # the only lane is held by a long runner: both bets must miss
    runner = mk(0)
    eng.admit([runner], template="hold")
    sched.running[runner.lane] = runner
    sched._lane_age[runner.lane] = 0
    r1, r2, r3 = mk(1), mk(2), mk(3)
    for t in (_SpecTask(eng, "t", [r1, r2]), _SpecTask(eng, "t", [r3])):
        t.join()
        sched._staged.append(t)
    sched.tick()   # boundary 1: within the depth-2 horizon → both wait
    assert sched.stats.spec_aborted == 0
    sched.tick()   # boundary 2: horizon expired → cascade settles
    assert sched.stats.spec_aborted == 3
    # arrival order survives: the older bet's requests lead the queue
    assert [x.rid for x in sched.queues["t"]] == [1, 2, 3]


def test_example_overlap_kv_demo_smoke(setup):
    """The examples/serve_continuous_batching.py overlap demo runs end to
    end on the reduced model: every request finishes and the demo's stats
    ledger balances."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "examples"))
    try:
        from serve_continuous_batching import depth_spill_demo, overlap_kv_demo
    finally:
        sys.path.pop(0)
    arch, params = setup
    done, st = overlap_kv_demo(arch, params, n_requests=8, verbose=False)
    assert len(done) == 8
    assert all(r.done for r in done)
    assert st.spec_dispatched == st.spec_committed + st.spec_aborted

    done, st = depth_spill_demo(arch, params, n_requests=6, verbose=False)
    assert len(done) == 6
    assert all(r.done for r in done)
    assert st.spec_dispatched == st.spec_committed + st.spec_aborted
    assert st.spec_chunks >= 1  # the oversized prompt actually chunked
    assert st.kv_restored == st.kv_spilled  # every spill restored


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------


def test_stream_deterministic():
    s1 = SyntheticLMStream(1000, 32, 4, seed=9)
    s2 = SyntheticLMStream(1000, 32, 4, seed=9)
    np.testing.assert_array_equal(s1.batch_at(17)["tokens"], s2.batch_at(17)["tokens"])
    assert not np.array_equal(s1.batch_at(17)["tokens"], s1.batch_at(18)["tokens"])


def test_prefetch_loader_order_and_bound():
    stream = SyntheticLMStream(100, 8, 2, seed=1)
    loader = PrefetchLoader(stream, n_prefetch=3, max_steps=10)
    batches = list(loader)
    assert len(batches) == 10
    np.testing.assert_array_equal(batches[4]["tokens"], stream.batch_at(4)["tokens"])


@pytest.mark.parametrize("lengths", [(3,), (300, 20), (600, 257, 9),
                                     (1000, 256, 40, 513, 2)])
def test_engine_pins_one_prefill_shape_per_template(setup, lengths):
    """template= dispatch runs each prompt as its own (1, rung) program,
    the rung its power-of-two bucket between 256 and max_prompt_len; a
    batch's parent shape is (k, largest rung); ``template_shapes`` keeps
    the monotone max of (batch bucket, largest rung) it has dispatched."""
    arch, params = setup
    eng = InferenceEngine(arch, params, n_lanes=8, max_prompt_len=1000,
                          max_len=1024)
    assert eng.prefill_rungs() == [256, 512, 1000]

    def rung(n):
        return min(1000, max(256, 1 << (n - 1).bit_length()))

    def reqs(ns, rid0=0):
        rng = np.random.default_rng(rid0)
        return [Request(rid=rid0 + i,
                        prompt=rng.integers(1, 200, size=n).astype(np.int32),
                        max_new_tokens=2) for i, n in enumerate(ns)]

    staged = eng.prefill_dispatch(reqs(lengths), template="chat")
    parts = staged.parts or [staged]
    assert [p.shape for p in parts] == [(1, rung(n)) for n in lengths]
    assert [int(p.plens[0]) for p in parts] == list(lengths)
    top = max(rung(n) for n in lengths)
    assert staged.shape == (len(lengths), top)
    record = (1 << (len(lengths) - 1).bit_length(), top)
    assert eng.template_shapes["chat"] == record
    assert eng.prefill_rung_programs == {
        r: sum(rung(n) == r for n in lengths) for r in {rung(n) for n in lengths}}
    # a later, smaller batch runs at its own rung and leaves the record
    one = eng.prefill_dispatch(reqs([5], rid0=10), template="chat")
    assert one.shape == (1, 256) and not one.parts
    assert eng.template_shapes["chat"] == record
    assert eng.admit(reqs([5], rid0=11), template="chat") == (1, 256)
    # an unrelated template's record starts from scratch
    eng.prefill_dispatch(reqs(lengths[:1], rid0=20), template="embed")
    assert eng.template_shapes["embed"] == (1, rung(lengths[0]))


# ---------------------------------------------------------------------------
# seeded chaos: serving output must be bit-identical under injected faults
# (REPRO_CHAOS_SEED selects the schedule; the CI chaos job runs two seeds)
# ---------------------------------------------------------------------------


def test_chaos_decode_faults_preserve_outputs_bit_identical(setup):
    """Acceptance: with seeded decode-tick crashes injected into the real
    engine, every request completes with EXACTLY the tokens the
    fault-free run produces — crashed lanes are quarantined, their KV
    salvaged through the spill pool (or re-prefilled), and the requests
    resume with no token lost, duplicated, or changed."""
    from repro.core.faults import ChaosEngine, ChaosPlan, chaos_seed
    from repro.core.resilience import Resilience
    from repro.serving.engine import HostSpillPool

    arch, params = setup
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, 200, size=rng.integers(3, 12)).astype(np.int32)
               for _ in range(6)]

    def run(chaos: bool):
        reqs = [Request(rid=i, prompt=p.copy(), max_new_tokens=5)
                for i, p in enumerate(prompts)]
        eng = InferenceEngine(arch, params, n_lanes=3, max_prompt_len=16,
                              max_len=48, kv_spill=HostSpillPool(max_entries=16))
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

    baseline, _, _ = run(chaos=False)
    chaotic, eng, sched = run(chaos=True)
    assert eng.injected_decode_faults > 0, "chaos never bit: rate too low"
    assert sched.stats.quarantined > 0
    assert chaotic == baseline  # bit-identical to the fault-free run
