"""Serving launcher — the main path: scheduler → paged engine → kernel.

Requests go through :class:`ContinuousBatchingScheduler` (admission under
one of the paper's strategies) into a :class:`PagedInferenceEngine`,
whose decode ticks run the Pallas ``paged_decode_attention`` kernel over
the shared page pool (compiled on TPU; the jnp reference on CPU).  An
architecture paged decode cannot run (sliding-window, recurrent or
encoder-decoder layers) is served by the dense :class:`InferenceEngine`.

    PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b --reduced \
        --requests 32 --strategy growing_upper

``--reduced`` shrinks the model for CPU runs; without it the model is
built at its published widths from random weights.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional, Sequence

import jax
import numpy as np

from repro.core.strategies import from_name
from repro.launch.compile_cache import setup_compile_cache
from repro.models.paged_decode import supports_paged_decode
from repro.models.registry import Arch, get_arch
from repro.serving.engine import InferenceEngine
from repro.serving.paged_kv import PagedInferenceEngine
from repro.serving.request import Request
from repro.serving.scheduler import ContinuousBatchingScheduler

__all__ = ["serve_requests", "main"]

STRATEGIES = ["async", "one_or_all", "lower_threshold", "growing_upper"]


def serve_requests(arch: Arch, params, requests: Sequence[Request], *,
                   lanes: int = 8, max_len: int = 1024,
                   max_prompt_len: int = 256, page_size: int = 16,
                   strategy: str = "growing_upper",
                   lane_timeout: Optional[int] = None,
                   use_kernel: bool = True):
    """Build the engine and its scheduler, serve ``requests`` until
    drained, and return ``(engine, scheduler, finished requests)``.

    The engine is the :class:`PagedInferenceEngine` where
    :func:`~repro.models.paged_decode.supports_paged_decode` accepts
    ``arch``, and the dense :class:`InferenceEngine` otherwise (which
    ignores ``page_size`` and ``use_kernel``).  The scheduler runs without
    a failure domain (``resilience=None``), so a device fault propagates
    instead of being retried away.
    """
    if supports_paged_decode(arch.cfg):
        eng = PagedInferenceEngine(arch, params, n_lanes=lanes,
                                   max_prompt_len=max_prompt_len,
                                   max_len=max_len, page_size=page_size,
                                   use_kernel=use_kernel)
    else:
        eng = InferenceEngine(arch, params, n_lanes=lanes,
                              max_prompt_len=max_prompt_len, max_len=max_len)
    kw = {"initial_upper": 2} if strategy == "growing_upper" else {}
    sched = ContinuousBatchingScheduler(
        eng, strategy=from_name(strategy, **kw), lane_timeout=lane_timeout,
        resilience=None)
    for r in requests:
        sched.submit(r)
    sched.producer_done()
    return eng, sched, sched.run_until_drained()


def main(argv=None) -> None:
    """Serve seeded random prompts and print tokens served and TTFTs."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--reduced", action="store_true",
                    help="shrink the model (CPU runs)")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--lanes", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=1024)
    ap.add_argument("--max-prompt-len", type=int, default=256)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--strategy", default="growing_upper", choices=STRATEGIES)
    ap.add_argument("--lane-timeout", type=int, default=None,
                    help="decode ticks before a lane is declared a straggler")
    args = ap.parse_args(argv)

    setup_compile_cache()
    arch = get_arch(args.arch)
    if args.reduced:
        arch = dataclasses.replace(arch, cfg=arch.cfg.reduced())
    params = arch.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(
                1, arch.cfg.vocab_size,
                size=int(rng.integers(4, args.max_prompt_len + 1))
            ).astype(np.int32), max_new_tokens=args.max_new)
            for i in range(args.requests)]
    t0 = time.perf_counter()
    _eng, sched, done = serve_requests(
        arch, params, reqs, lanes=args.lanes, max_len=args.max_len,
        max_prompt_len=args.max_prompt_len, page_size=args.page_size,
        strategy=args.strategy, lane_timeout=args.lane_timeout)
    dt = time.perf_counter() - t0
    toks = sum(len(r.generated) for r in done)
    ttfts = sorted(r.metrics.ttft for r in done)
    print(f"served {len(done)} requests, {toks} tokens in {dt:.2f}s wall "
          f"(compilation included) on {jax.devices()[0].device_kind}")
    print(f"ttft p50/p95: {ttfts[len(ttfts)//2]*1e3:.0f}/"
          f"{ttfts[int(len(ttfts)*0.95)]*1e3:.0f} ms; "
          f"admission trace: {sched.stats.admission_trace[:10]}...")


if __name__ == "__main__":
    main()
