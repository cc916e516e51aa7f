"""Program spans and counters of the serving hot path (serving/tracing.py):
the switch, the span tree of one scheduler tick over a paged engine, the
engine's host-read and prefill-token counters, the profiler's host plane
and the compile listener."""
from __future__ import annotations

import dataclasses
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core.strategies import OneOrAll
from repro.models.registry import get_arch
from repro.serving import tracing
from repro.serving.paged_kv import PagedInferenceEngine
from repro.serving.request import Request
from repro.serving.scheduler import ContinuousBatchingScheduler


@pytest.fixture(autouse=True)
def fresh_tracing():
    """Each test starts with tracing following the profiler and no
    records, and leaves it so."""
    tracing.enable(None)
    tracing.clear()
    yield
    tracing.enable(None)
    tracing.clear()


@pytest.fixture(scope="module")
def setup():
    arch = get_arch("olmo-1b")
    arch = dataclasses.replace(arch, cfg=arch.cfg.reduced())
    return arch, arch.init(jax.random.PRNGKey(0))


def _engine(setup, **kw):
    arch, params = setup
    return PagedInferenceEngine(arch, params, n_lanes=4, max_prompt_len=16,
                                max_len=32, page_size=8, **kw)


def _requests(lengths, max_new=6, rid0=0):
    rng = np.random.default_rng(rid0)
    return [Request(rid=rid0 + i,
                    prompt=rng.integers(1, 200, size=n).astype(np.int32),
                    max_new_tokens=max_new)
            for i, n in enumerate(lengths)]


def _by_name(recs):
    out: dict = {}
    for r in recs:
        out.setdefault(r[0], []).append(r)
    return out


# ------------------------------------------------------------------ switch

def test_off_returns_the_shared_no_op_and_records_nothing():
    tracing.enable(False)
    a = tracing.span("engine.decode_tick", lanes=[0, 1])
    b = tracing.span("sched.tick")
    assert a is b
    with a as inner:
        assert inner is a
        with b:
            assert tracing.current() is None
    assert tracing.records() == []
    # following the profiler, with none running: off as well
    tracing.enable(None)
    assert not tracing.is_on()
    assert tracing.span("sched.tick") is a


def test_nested_spans_carry_parent_and_ids():
    tracing.enable(True)
    with tracing.span("sched.tick"):
        with tracing.span("sched.admit"):
            assert tracing.current() == "sched.admit"
            with tracing.span("engine.prefill", rids=[7, 9], shape=(2, 16)):
                pass
            with tracing.span("engine.commit", rids=[7, 9]):
                pass
        assert tracing.current() == "sched.tick"
    assert tracing.current() is None
    recs = _by_name(tracing.records())
    (pre,), (com,) = recs["engine.prefill"], recs["engine.commit"]
    (adm,), (tick,) = recs["sched.admit"], recs["sched.tick"]
    assert pre[1] == com[1] == "sched.admit"
    assert adm[1] == "sched.tick" and tick[1] is None
    assert pre[5] == {"rids": [7, 9], "shape": (2, 16)}
    assert com[5] == {"rids": [7, 9]}
    assert tick[2] <= adm[2] <= pre[2] <= pre[3] <= com[2] <= com[3] \
        <= adm[3] <= tick[3]
    assert len({r[4] for r in tracing.records()}) == 1   # one thread


def test_profiler_turns_spans_on_and_writes_them_to_its_host_plane(tmp_path):
    from jax.profiler import ProfileData

    assert not tracing.is_on()
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert tracing.is_on()
        with tracing.span("engine.decode_tick", lanes=np.array([0, 3]),
                          host_reads=12):
            with tracing.span("engine.decode.readback"):
                pass
    finally:
        jax.profiler.stop_trace()
    assert not tracing.is_on()
    assert [r[0] for r in tracing.records()] == ["engine.decode.readback",
                                                "engine.decode_tick"]
    path = sorted(Path(tmp_path).rglob("*.xplane.pb"))[-1]
    events = {e.name: (e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
              for p in ProfileData.from_file(str(path)).planes
              if p.name.startswith("/host") for line in p.lines
              for e in line.events}
    tick, read = events["engine.decode_tick"], events["engine.decode.readback"]
    assert tick[2] == {"lanes": "0 3", "host_reads": 12}
    assert tick[0] <= read[0] <= read[1] <= tick[1]


# ------------------------------------------------------- one scheduler tick

def test_one_tick_yields_the_span_tree(setup):
    eng = _engine(setup)
    sched = ContinuousBatchingScheduler(eng, strategy=OneOrAll())
    for r in _requests([5, 11]):
        sched.submit(r)
    tracing.enable(True)
    sched.tick()
    recs = _by_name(tracing.records())
    (tick,) = recs["sched.tick"]
    (admit,) = recs["sched.admit"]
    (tokens,) = recs["sched.tokens"]
    pres, coms = recs["engine.prefill"], recs["engine.commit"]
    (dec,) = recs["engine.decode_tick"]
    steps = [recs[f"engine.decode.{s}"][0]
             for s in ("prepare", "dispatch", "readback")]

    def inside(child, parent):
        assert child[1] == parent[0]
        assert parent[2] <= child[2] <= child[3] <= parent[3]

    assert tick[1] is None
    inside(admit, tick)
    for sp in pres + coms:
        inside(sp, admit)
    inside(dec, tick)
    inside(tokens, tick)
    for s in steps:
        inside(s, dec)
    # prepare, dispatch, readback follow one another
    assert [s[2] for s in steps] == sorted(s[2] for s in steps)
    assert steps[0][3] <= steps[1][2] and steps[1][3] <= steps[2][2]
    assert admit[3] <= dec[2] and dec[3] <= tokens[2]
    # one ladder program, and one commit, per prompt
    assert [p[5]["rids"] for p in pres] == [c[5]["rids"] for c in coms] \
        == [[0], [1]]
    assert [p[5]["shape"] for p in pres] == [(1, 16), (1, 16)]
    assert [p[5]["real"] for p in pres] == [[5], [11]]
    assert [p[5]["attn_chunk"] for p in pres] == [0, 0]
    assert list(dec[5]["lanes"]) == [0, 1]


def test_host_reads_per_tick_are_active_lanes_plus_one(setup):
    eng = _engine(setup)
    eng.admit(_requests([4, 9, 13], max_new=3))
    active = []
    for traced in (False, True, False):
        tracing.enable(traced)
        for _ in range(2):
            n0 = eng.host_reads
            lanes = int(eng.active.sum())
            eng.decode_tick()
            assert eng.host_reads - n0 == lanes + 1
            active.append(lanes)
        eng.retire(int(np.nonzero(eng.active)[0][0]))
    assert active == [3, 3, 2, 2, 1, 1]
    # the traced ticks carry the counter at their start
    ticks = _by_name(tracing.records())["engine.decode_tick"]
    assert [len(t[5]["lanes"]) for t in ticks] == [2, 2]
    assert ticks[1][5]["host_reads"] - ticks[0][5]["host_reads"] == 3


def test_prefill_token_counters_sum_each_staged_prefill(setup):
    eng = _engine(setup)
    staged = [
        (eng.prefill_dispatch(_requests([3], rid0=0), "chat"), 1),
        (eng.prefill_dispatch(_requests([9, 5, 2], rid0=1), "chat"), 3),
        (eng.prefill_dispatch(_requests([16, 7], rid0=4), None), 2),
        (eng.prefill_dispatch(_requests([14], rid0=6), None, chunk=4), 1),
        (eng.prefill_dispatch(_requests([12, 10, 3], rid0=7), None,
                              chunk=5), 3),
    ]
    real = sum(int(np.sum(s.plens[:n])) for s, n in staged)
    # an aggregate counts its parts: "chat" pads each prompt to its rung
    # (16, the prompt cap), the chunked parts are their prompts' lengths
    bucket = sum(p.shape[0] * p.shape[1]
                 for s, _ in staged for p in s.parts or [s])
    assert eng.prefill_real_tokens == real == 3 + 16 + 23 + 14 + 25
    assert eng.prefill_bucket_tokens == bucket \
        == 16 + 3 * 16 + 2 * 16 + 14 + (12 + 10 + 3)
    assert bucket > real


def test_prefill_spans_and_counter_say_how_attention_was_chunked(setup):
    """With ``attn_chunk`` on, each ``engine.prefill`` span carries the
    real prompt tokens of its rows and the query rows per attention chunk
    it ran, and ``prefill_attn_chunks`` counts the chunks per layer: a
    16-row bucket at chunk 6 runs 3 (the last padded), a 5-token first
    chunk of a chunked prefill none.  Each "chat" prompt is its own
    ladder program at rung 16."""
    arch, params = setup
    arch = dataclasses.replace(arch, cfg=dataclasses.replace(
        arch.cfg, attn_chunk=6))
    eng = _engine((arch, params))
    tracing.enable(True)
    eng.prefill_dispatch(_requests([9, 13], rid0=0), "chat")
    eng.prefill_dispatch(_requests([4], rid0=2), "chat")
    eng.prefill_dispatch(_requests([14], rid0=3), None, chunk=5)
    eng.prefill_dispatch(_requests([12], rid0=4), None, chunk=8)
    pre = _by_name(tracing.records())["engine.prefill"]
    assert [(p[5]["shape"], p[5]["real"], p[5]["attn_chunk"])
            for p in pre] == [((1, 16), [9], 6), ((1, 16), [13], 6),
                              ((1, 16), [4], 6), ((1, 5), [5], 0),
                              ((1, 8), [8], 6)]
    assert eng.prefill_attn_chunks == 3 + 3 + 3 + 0 + 2
    # the count does not depend on tracing
    tracing.enable(False)
    eng.prefill_dispatch(_requests([3], rid0=5), "chat")
    assert eng.prefill_attn_chunks == 14


# ------------------------------------------------------------ compile log

def test_compile_log_charges_the_innermost_span():
    log = tracing.CompileLog()
    tracing.enable(True)
    with tracing.span("sched.tick"):
        with tracing.span("engine.decode.dispatch"):
            jax.jit(lambda x: x * 3 + 1)(np.ones(37, np.float32))
    tracing.enable(False)
    jax.jit(lambda x: x * 5 - 2)(np.ones(41, np.float32))
    assert log.compiles >= 2
    assert log.by_span["engine.decode.dispatch"] >= 1
    assert log.by_span[None] >= 1
    assert "sched.tick" not in log.by_span
    assert "engine.decode.dispatch" in log.line()
