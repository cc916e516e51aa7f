"""Device idle time set against the program's spans (``program_spans``):
the time-weighted split on synthetic intervals worked out by hand, and on
two small traces of the tiny cell recorded on one v5e chip, one before the
program had spans and one with them (``tiny_spans.xplane.pb``, 0.28 s
traced): each split has to sum to ``reduce()``'s idle time."""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from chipbench import program_spans, trace_reduce

DATA = Path(__file__).resolve().parent / "data" / "tiny.xplane.pb"
SPANS = DATA.parent / "tiny_spans.xplane.pb"
US = 1000.0   # the synthetic trace counts in microseconds


def _event(name, a, b, **stats):
    return NS(name=name, start_ns=a * US, duration_ns=(b - a) * US,
              stats=list(stats.items()))


def _trace(clock=0):
    """One device busy over [0, 10], [30, 40] and [70, 94] of a 100-us
    traced span; the server thread in one tick, with a decode tick whose
    program ran over [37, 70]; a second thread in a prefill.  ``clock``
    moves every device time by that many microseconds."""
    server = [
        _event("bench.traced", 0, 100),
        _event("bench.tick", 5, 95),
        _event("sched.tick", 6, 90),
        _event("sched.admit", 6, 19),
        _event("engine.decode_tick", 20, 80, lanes="0 1", host_reads=40),
        _event("engine.decode.prepare", 20, 35),
        _event("engine.decode.dispatch", 35, 36),
        _event("engine.decode.readback", 36, 78),
        _event("sched.tokens", 81, 89),
        _event("bench.wait", 96, 100),
        _event("PjitFunction", 35, 36),          # not a span of ours
    ]
    spec = [_event("engine.prefill", 50, 60, rids="7")]
    c = clock
    device = [
        NS(name="XLA Modules", events=[_event("jit__paged(3)", 37 + c, 70 + c),
                                       _event("jit_where(4)", 0 + c, 10 + c)]),
        NS(name="XLA Ops", events=[
            _event("%a = f32[] add(x, y)", 0 + c, 10 + c),
            _event("%b = f32[] add(x, y)", 30 + c, 40 + c),
            _event("%c = f32[] add(x, y)", 70 + c, 94 + c)]),
    ]
    return NS(planes=[
        NS(name="/host:CPU", lines=[NS(name="python", events=server),
                                    NS(name="spec", events=spec)]),
        NS(name="/device:TPU:0", lines=device),
    ])


def test_idle_split_is_time_weighted_over_the_deepest_open_span():
    red = program_spans.reduce_spans(_trace())
    got = {k: v / (US * 1e-9) for k, v in red["idle"].items()}
    # gap [10, 30]: admission to 19, the tick alone to 20, then prepare;
    # gap [40, 70]: readback, deeper than the other thread's prefill;
    # gap [94, 100]: bench.tick, then nothing, then bench.wait
    assert got == pytest.approx({"sched.tick": 1, "sched.admit": 9,
                                 "engine.decode.prepare": 10,
                                 "engine.decode.readback": 30,
                                 "bench.tick": 1, "none": 1,
                                 "bench.wait": 4})
    assert red["program_spans"] == 8
    assert red["window_s"] == pytest.approx(100 * US * 1e-9)
    # the same idle time as the benchmark's own reduction
    idle = trace_reduce.reduce(_trace())["idle"]
    assert sum(red["idle"].values()) == pytest.approx(sum(idle.values()))


def test_idle_inside_the_tick():
    red = program_spans.reduce_spans(_trace())
    assert red["tick_idle_s"] / (US * 1e-9) == pytest.approx(20 + 30)
    under = {k: v / (US * 1e-9) for k, v in red["idle_in_bench_tick"].items()}
    assert under == pytest.approx({"sched.tick": 1, "sched.admit": 9,
                                   "engine.decode.prepare": 10,
                                   "engine.decode.readback": 30,
                                   "bench.tick": 1})


def test_decode_ticks_and_the_clock_check():
    red = program_spans.reduce_spans(_trace())
    (tick,) = red["ticks"]
    assert tick["stats"] == {"lanes": "0 1", "host_reads": 40}
    assert tick["run"] == (37 * US, 70 * US)
    assert tick["prepare"][:2] == (20 * US, 35 * US)
    assert red["offset_ns"] == 0      # the clocks agree: nothing shifted
    lines = program_spans.notes(red, 0.5)
    assert "readback" in lines[0] and "bench.wait" in lines[0]
    assert lines[2].startswith("clock check over 1 decode ticks")
    # run start - dispatch start = 2 us; readback end - run end = 8 us
    assert "dispatch span start: min 2.0," in lines[2]
    assert "device run end: min 8.0," in lines[2]


def test_metrics_read_the_reduction(monkeypatch):
    red = program_spans.reduce_spans(_trace())
    red["ticks"].append(dict(red["ticks"][0], stats={"host_reads": 46}))
    monkeypatch.setattr(program_spans, "of", lambda ctx: red)
    ctx = NS(trace={"idle": {"bench.tick": 1.0}}, notes=[])
    ctx.note = ctx.notes.append
    assert program_spans.decode_prepare_ms(ctx) == pytest.approx(0.015)
    assert program_spans.decode_host_reads(ctx) == pytest.approx(6.0)
    assert program_spans.tick_idle_share(ctx) == pytest.approx(50.0)
    assert len(ctx.notes) == 3
    monkeypatch.setattr(program_spans, "of", lambda ctx: None)
    for f in (program_spans.decode_prepare_ms,
              program_spans.decode_host_reads,
              program_spans.tick_idle_share):
        assert f(ctx) is None


def test_on_the_chip_trace_the_split_sums_to_reduce():
    data = trace_reduce.load(DATA)
    red = program_spans.reduce_spans(data)
    idle = trace_reduce.reduce(data)["idle"]
    assert sum(red["idle"].values()) == pytest.approx(sum(idle.values()),
                                                      rel=1e-9)
    # a trace written before the program had spans: the benchmark's spans
    # alone, and nothing for the metrics to read
    assert red["program_spans"] == 0
    assert all(k == "none" or k.startswith("bench.") for k in red["idle"])
    assert red["ticks"] == [] and red["tick_idle_s"] == 0


def test_on_a_chip_trace_with_program_spans():
    data = trace_reduce.load(SPANS)
    red = program_spans.reduce_spans(data)
    idle = trace_reduce.reduce(data)["idle"]
    assert sum(red["idle"].values()) == pytest.approx(sum(idle.values()),
                                                      rel=1e-9)
    assert red["program_spans"] == 16
    assert red["offset_ns"] == 0
    assert 0 < red["tick_idle_s"] < red["window_s"]
    assert [t["stats"]["host_reads"] for t in red["ticks"]] == [264, 269]
    for t in red["ticks"]:
        # prepare, dispatch and readback follow one another, and the
        # device ran the step between the dispatch's start and the
        # readback's end: the host and device clocks agree
        assert t["prepare"][1] <= t["dispatch"][0]
        assert t["dispatch"][1] <= t["readback"][0]
        assert t["dispatch"][0] <= t["run"][0] <= t["run"][1] \
            <= t["readback"][1]


def test_a_device_clock_behind_the_host_is_shifted_back():
    # the device's times read 5 us early: the decode program would start
    # 3 us before its dispatch span, which cannot be, so the device's
    # times move 3 us later, the least shift that puts the run back
    red = program_spans.reduce_spans(_trace(clock=-5))
    assert red["offset_ns"] == pytest.approx(3 * US)
    got = {k: v / (US * 1e-9) for k, v in red["idle"].items()}
    # device gaps [5, 25], [35, 65], [89, 100] set at [8, 28], [38, 68]
    # and [92, 103] of the host's clock
    assert got == pytest.approx({"sched.admit": 11, "sched.tick": 1,
                                 "engine.decode.prepare": 8,
                                 "engine.decode.readback": 30,
                                 "bench.tick": 3, "none": 1 + 3,
                                 "bench.wait": 4})
    idle = trace_reduce.reduce(_trace(clock=-5))["idle"]
    assert sum(red["idle"].values()) == pytest.approx(sum(idle.values()))
    assert "device times shifted by 3.0 us" in program_spans.notes(red, 0)[2]
