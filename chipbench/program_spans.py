"""Device idle time set against the program's own spans.

The program (``repro.serving.tracing``) writes its spans, ``sched.*`` and
``engine.*``, into the profiler's host plane, the plane that holds the
benchmark's ``bench.*`` spans, on the device trace's clock.  This module
reads them from the same ``.xplane.pb`` that ``trace_reduce`` reduces,
over the same traced span, and leaves ``trace_reduce.reduce`` as it is.

- idle split: every interval of the traced span in which no operation ran
  on the device is cut wherever a span opens or closes, and each part is
  charged, by its length, to the deepest program span open during it
  (any thread); a part with none open goes to the innermost ``bench.*``
  span open, else to ``none``.  The split sums to ``reduce()``'s idle
  time.
- idle inside ``sched.tick``: the idle time during which a ``sched.tick``
  span was open.
- decode ticks: each ``engine.decode_tick`` with its ``prepare``,
  ``dispatch`` and ``readback`` children, the engine's ``host_reads``
  at its start, and the device run of the paged decode program it made.
- clock offset: a device run cannot start before its dispatch span starts
  nor end after its readback span ends.  Where some tick says otherwise,
  the host and device clocks disagree, and the least shift of the device's
  times that puts every run back inside its tick is fitted and applied
  before idle time is set against host spans (0 where they agree).

On a trace with no program span (a program without them) the split
falls back to the ``bench.*`` spans alone, and the metrics read nothing.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from pathlib import Path

from chipbench import trace_reduce

PROGRAM = ("sched.", "engine.")
TICK = "sched.tick"
DECODE = "engine.decode_tick"
STEPS = ("engine.decode.prepare", "engine.decode.dispatch",
         "engine.decode.readback")
DECODE_PROGRAM = "jit__paged"


def _host_spans(data):
    """Program and ``bench.*`` spans of the host plane, each
    ``(start, end, name, line, depth, stats)``; ``depth`` counts the
    spans of its own kind that enclose it on its thread (line)."""
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host"):
            continue
        for k, line in enumerate(plane.lines):
            mine = sorted(
                ((float(e.start_ns), float(e.start_ns + e.duration_ns),
                  e.name, e) for e in line.events
                 if e.name.startswith(PROGRAM) or e.name.startswith("bench.")),
                key=lambda t: (t[0], -t[1]))  # a parent before its child
            stacks = {True: [], False: []}
            for a, b, name, e in mine:
                stack = stacks[name.startswith("bench.")]
                while stack and stack[-1] <= a:
                    stack.pop()
                stats = dict(e.stats) if name == DECODE else {}
                out.append((a, b, name, k, len(stack), stats))
                stack.append(b)
    return out


def _idle_gaps(data, lo, hi):
    """Per device, the intervals of [lo, hi] in which no operation ran
    (``trace_reduce.reduce``'s gaps), and the device planes."""
    devices = [p for p in data.planes if p.name.startswith("/device:TPU:")
               and p.name[len("/device:TPU:"):].isdigit()]
    gaps = []
    for plane in devices:
        iv = []
        for _, a, b in trace_reduce._events(plane, "XLA Ops"):
            a, b = trace_reduce._clip(a, b, lo, hi)
            if b > a:
                iv.append((a, b))
        u = trace_reduce._union(iv)
        edges = [lo] + [x for ab in u for x in ab] + [hi]
        gaps += [(edges[k], edges[k + 1]) for k in range(0, len(edges), 2)
                 if edges[k + 1] > edges[k]]
    return gaps, devices


def _labels(spans, lo, hi):
    """Cut [lo, hi] wherever a span opens or closes; returns the cut
    points and the name charged with each piece between them."""
    points = sorted({lo, hi} | {x for a, b, *_ in spans for x in (a, b)
                                if lo < x < hi})
    opening = defaultdict(list)
    for s in spans:
        opening[max(s[0], lo)].append(s)
    open_, names = [], []
    for p in points[:-1]:
        # every span that ends inside the piece would have cut it
        open_ = [s for s in open_ if s[1] > p] + opening.get(p, [])
        prog = [s for s in open_ if not s[2].startswith("bench.")]
        pick = prog or [s for s in open_ if s[2] != trace_reduce.SPAN]
        names.append(max(pick, key=lambda s: (s[4], s[0]))[2] if pick
                     else "none")
    return points, names


def _charge(gaps, points, names, scale):
    """Seconds of ``gaps`` under each name, each piece by its length."""
    out = defaultdict(float)
    for a, b in gaps:
        k = max(0, bisect.bisect_right(points, a) - 1)
        while k < len(names) and points[k] < b:
            part = min(b, points[k + 1]) - max(a, points[k])
            if part > 0:
                out[names[k]] += part * 1e-9 * scale
            k += 1
    return dict(out)


def _inside(gaps, intervals):
    """The parts of ``gaps`` that lie inside the union of ``intervals``."""
    u = trace_reduce._union(intervals)
    starts = [a for a, _ in u]
    out = []
    for a, b in gaps:
        k = max(0, bisect.bisect_right(starts, a) - 1)
        while k < len(u) and u[k][0] < b:
            if min(b, u[k][1]) > max(a, u[k][0]):
                out.append((max(a, u[k][0]), min(b, u[k][1])))
            k += 1
    return out


def _decode_ticks(spans, runs, lo, hi):
    """Each decode tick inside [lo, hi] with its three steps, in order,
    and the device run of the decode program nearest to it."""
    ticks = sorted((s for s in spans if s[2] == DECODE
                    and lo <= s[0] and s[1] <= hi), key=lambda s: s[0])
    steps = {n: sorted((s for s in spans if s[2] == n), key=lambda s: s[0])
             for n in STEPS}
    starts = {n: [s[0] for s in v] for n, v in steps.items()}
    run_mid = [0.5 * (a + b) for a, b in runs]
    out = []
    for t in ticks:
        found = {}
        for n in STEPS:
            k = bisect.bisect_left(starts[n], t[0])
            if k < len(steps[n]) and steps[n][k][1] <= t[1]:
                found[n] = steps[n][k]
        tick = {"start": t[0], "end": t[1], "stats": t[5], "run": None,
                **{n.rsplit(".", 1)[-1]: found.get(n) for n in STEPS}}
        if tick["dispatch"] and tick["readback"] and runs:
            a, b = tick["dispatch"][0], tick["readback"][1]
            k = bisect.bisect_left(run_mid, a)
            near = [j for j in (k - 1, k) if 0 <= j < len(runs)]
            j = min(near, key=lambda j: 0 if a <= run_mid[j] <= b
                    else min(abs(run_mid[j] - a), abs(run_mid[j] - b)))
            tick["run"] = runs[j]
        out.append(tick)
    return out


def _offset(ticks) -> float:
    """The device-to-host clock shift, ns: the least that puts every
    decode tick's device run after its dispatch span's start and before
    its readback span's end; 0 where the clocks already agree, and the
    middle of the two bounds where no single shift fits every tick."""
    runs = [t for t in ticks if t["run"] is not None]
    if not runs:
        return 0.0
    lo = max(t["dispatch"][0] - t["run"][0] for t in runs)
    hi = min(t["readback"][1] - t["run"][1] for t in runs)
    if lo > hi:
        return 0.5 * (lo + hi)
    return min(max(0.0, lo), hi)


def reduce_spans(data):
    """The idle split, idle inside ``sched.tick`` and the decode ticks of
    the ``bench.traced`` span of ``data`` (a ``ProfileData``), and how many
    program spans it holds."""
    spans = _host_spans(data)
    traced = [(a, b) for a, b, n, *_ in spans if n == trace_reduce.SPAN]
    if not traced:
        raise ValueError(f"the trace holds no {trace_reduce.SPAN!r} host span")
    lo, hi = traced[0]
    inside = [s for s in spans if s[1] > lo and s[0] < hi]
    gaps, devices = _idle_gaps(data, lo, hi)
    scale = 1.0 / len(devices)
    runs = sorted((a, b) for plane in devices[:1]
                  for name, a, b in trace_reduce._events(plane, "XLA Modules")
                  if name.startswith(DECODE_PROGRAM + "("))
    ticks = _decode_ticks(inside, runs, lo, hi)
    shift = _offset(ticks)
    # each idle interval at its place on the host's clock, length unchanged
    gaps = [(a + shift, b + shift) for a, b in gaps]
    pad = abs(shift)
    near = [s for s in spans if s[1] > lo - pad and s[0] < hi + pad]
    points, names = _labels(near, lo - pad, hi + pad)

    def within(name):
        return _inside(gaps, [(a, b) for a, b, n, *_ in near if n == name])

    return {
        "program_spans": sum(s[2].startswith(PROGRAM) for s in inside),
        "window_s": (hi - lo) * 1e-9,
        "offset_ns": shift,
        "idle": _charge(gaps, points, names, scale),
        "idle_in_bench_tick": _charge(within("bench.tick"), points, names,
                                      scale),
        "tick_idle_s": sum(b - a for a, b in within(TICK)) * 1e-9 * scale,
        "ticks": ticks,
    }


_cache: dict = {}


def of(ctx):
    """:func:`reduce_spans` of a ``--trace 1`` run's trace, read once per
    run; ``None`` for an untraced run or a trace without program spans."""
    if ctx.trace is None:
        return None
    from chipbench import run

    path = trace_reduce.find_xplane(run.TRACE_DIR)
    key = (str(path), Path(path).stat().st_mtime_ns)
    if key not in _cache:
        _cache.clear()
        _cache[key] = reduce_spans(trace_reduce.load(path))
    red = _cache[key]
    return red if red["program_spans"] else None


def _ms(values) -> str:
    v = sorted(values)
    if not v:
        return "none"
    q = lambda p: v[min(len(v) - 1, int(p * len(v)))]  # noqa: E731
    return (f"min {v[0] * 1e-3:.1f}, p5 {q(0.05) * 1e-3:.1f}, median "
            f"{q(0.5) * 1e-3:.1f}, max {v[-1] * 1e-3:.1f} us")


def notes(red, reduce_idle_s: float) -> list:
    """The lines a traced run prints: the idle split (and its part under
    ``bench.tick``) against ``reduce()``'s idle total, and the clock check
    of each decode tick's device run against its host spans."""
    split = trace_reduce.top(red["idle"], n=len(red["idle"]))
    total = sum(red["idle"].values())
    under = red["idle_in_bench_tick"]
    out = [
        f"program spans: idle {total:.6g}s split time-weighted (reduce() "
        f"idle {reduce_idle_s:.6g}s): "
        + ", ".join(f"{n} {s:.6g}s" for n, s in split),
        f"program spans: idle under bench.tick {sum(under.values()):.6g}s: "
        + ", ".join(f"{n} {s:.6g}s"
                    for n, s in trace_reduce.top(under, n=len(under))),
    ]
    runs = [t for t in red["ticks"] if t["run"] is not None]
    after = [t["run"][0] - t["dispatch"][0] for t in runs]
    before = [t["readback"][1] - t["run"][1] for t in runs]
    out.append(
        f"clock check over {len(runs)} decode ticks: device run start - "
        f"dispatch span start: {_ms(after)}; readback span end - device run "
        f"end: {_ms(before)}; device times shifted by "
        f"{red['offset_ns'] * 1e-3:.1f} us")
    return out


# ------------------------------------------------------------ the metrics

def decode_prepare_ms(ctx):
    """Mean duration of ``engine.decode.prepare`` over the traced span's
    decode ticks, ms."""
    red = of(ctx)
    d = [t["prepare"][1] - t["prepare"][0] for t in (red or {}).get(
        "ticks", []) if t["prepare"] is not None]
    return sum(d) / len(d) * 1e-6 if d else None


def decode_host_reads(ctx):
    """The engine's ``host_reads`` count from the first to the last decode
    tick of the traced span, per tick."""
    red = of(ctx)
    n = [t["stats"]["host_reads"] for t in (red or {}).get("ticks", [])
         if "host_reads" in t["stats"]]
    return (n[-1] - n[0]) / (len(n) - 1) if len(n) > 1 else None


def tick_idle_share(ctx):
    """Share of the traced span in which the device ran nothing while a
    ``sched.tick`` span was open, %; notes the idle split and the clock
    check."""
    red = of(ctx)
    if red is None or not red["window_s"]:
        return None
    for line in notes(red, sum(ctx.trace["idle"].values())):
        ctx.note(line)
    return 100.0 * red["tick_idle_s"] / red["window_s"]
