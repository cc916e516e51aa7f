"""The traced prefills, read from the program's ``engine.prefill`` spans.

Each padded prefill dispatch of the program (``repro.serving.engine``)
writes an ``engine.prefill`` span into the profiler's host plane, on the
device trace's clock.  Its arguments give the real prompt tokens of the
dispatch's rows (``real``: space-separated integers, or one integer) and
the query rows per chunk its attention ran (``attn_chunk``, 0 for
unchunked).  A program older than those arguments writes spans without
them, and then nothing here reads a value.

Each span is paired with the device run of the prefill program it
dispatched: the first ``jit__prefill`` run on the first device that
starts after the span does, and after the run paired with the span
before it; the spans that start inside ``bench.traced`` are kept.  A
host clock that reads up to ``SLACK_NS`` late against the device's still
pairs (``program_spans`` fits such an offset of about 1 ms on some
runs).
"""
from __future__ import annotations

import bisect
from pathlib import Path

from chipbench import trace_reduce
from chipbench.costs import model_step

PREFILL = "engine.prefill"
PREFILL_PROGRAM = "jit__prefill"
SLACK_NS = 5e6


def _ints(v) -> list:
    return [int(x) for x in str(v).split()]


def prefills(data) -> list:
    """Each ``engine.prefill`` span of ``data`` (a ``ProfileData``) that
    starts inside ``bench.traced``, in order, as ``{"start", "end",
    "real", "attn_chunk", "run_s"}``; ``real`` and ``attn_chunk`` are
    None where the span lacks them, and ``run_s`` (the paired device
    run's seconds) where no run follows."""
    traced, spans = [], []
    for plane in data.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == trace_reduce.SPAN:
                    traced.append((float(e.start_ns),
                                   float(e.start_ns + e.duration_ns)))
                elif e.name == PREFILL:
                    spans.append((float(e.start_ns),
                                  float(e.start_ns + e.duration_ns),
                                  dict(e.stats)))
    if not traced:
        return []
    lo, hi = traced[0]
    devices = sorted((p for p in data.planes
                      if p.name.startswith("/device:TPU:")),
                     key=lambda p: p.name)
    runs = sorted((a, b) for plane in devices[:1]
                  for name, a, b in trace_reduce._events(plane, "XLA Modules")
                  if name.startswith(PREFILL_PROGRAM + "("))
    starts = [a for a, _ in runs]
    out, k = [], 0
    for a, b, stats in sorted(spans, key=lambda s: s[0]):
        k = max(k, bisect.bisect_left(starts, a - SLACK_NS))
        run = runs[k] if k < len(runs) else None
        k += 1
        if not lo <= a <= hi:
            continue
        out.append({
            "start": a, "end": b,
            "real": _ints(stats["real"]) if "real" in stats else None,
            "attn_chunk": (int(stats["attn_chunk"]) if "attn_chunk" in stats
                           else None),
            "run_s": (run[1] - run[0]) * 1e-9 if run else None,
        })
    return out


def mfu(pre: list, model: dict, peak: dict):
    """Model operations of the real prompt tokens of the prefills ``pre``
    that have a device run (``costs.model_step.prefill_flops``) over those
    runs' device time times the chip's bf16 peak, %; None where a span
    lacks ``real`` or no prefill ran."""
    if not pre or any(p["real"] is None for p in pre):
        return None
    ran = [p for p in pre if p["run_s"]]
    if not ran:
        return None
    flops = sum(model_step.prefill_flops(model, n)
                for p in ran for n in p["real"])
    return 100.0 * flops / (sum(p["run_s"] for p in ran) * peak["bf16_flops"])


_cache: dict = {}


def of(ctx) -> list:
    """:func:`prefills` of a ``--trace 1`` run's trace, read once per run;
    empty for an untraced run."""
    if ctx.trace is None:
        return []
    from chipbench import run

    path = trace_reduce.find_xplane(run.TRACE_DIR)
    key = (str(path), Path(path).stat().st_mtime_ns)
    if key not in _cache:
        _cache.clear()
        _cache[key] = prefills(trace_reduce.load(path))
    return _cache[key]


def prefill_mfu(ctx):
    """:func:`mfu` of the traced prefills; notes how many there were, the
    real tokens and the attention chunking they ran."""
    if ctx.peak is None:
        return None
    pre = of(ctx)
    value = mfu(pre, ctx.model, ctx.peak)
    if value is not None:
        chunks = sorted({p["attn_chunk"] for p in pre})
        ctx.note(f"prefill_mfu: {len(pre)} traced prefills, "
                 f"{sum(sum(p['real']) for p in pre)} real prompt tokens, "
                 f"{sum(p['run_s'] or 0.0 for p in pre):.6g}s of "
                 f"{PREFILL_PROGRAM}; attn_chunk {chunks}")
    return value
