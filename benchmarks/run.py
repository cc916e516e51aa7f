"""Benchmark orchestrator — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]

Output: ``name,value,derived`` CSV on stdout (and results/bench.csv).
Figures covered: Fig 9 (strategies), Fig 10 (batch trace), Fig 11
(time-to-k-th), Fig 5/8 (threads), Table 1 (applicability), plus the
device-fission and serving instantiations (§3 on device / §5.2 as
continuous batching).
"""
from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

from benchmarks import (
    bench_applicability,
    bench_batch_trace,
    bench_fission,
    bench_lanes,
    bench_response_time,
    bench_strategies,
)
from benchmarks.common import CSV
from repro.launch.compile_cache import setup_compile_cache

MODULES = {
    "applicability": bench_applicability,
    "strategies": bench_strategies,
    "batch_trace": bench_batch_trace,
    "response_time": bench_response_time,
    "lanes": bench_lanes,
    "fission": bench_fission,
}


def main(argv=None) -> None:
    """Run the selected benchmark modules and write results/bench.csv."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None, choices=list(MODULES))
    args = ap.parse_args(argv)

    setup_compile_cache()
    csv = CSV()
    csv.header()
    mods = {args.only: MODULES[args.only]} if args.only else MODULES
    for name, mod in mods.items():
        t0 = time.perf_counter()
        mod.main(csv, quick=args.quick)
        csv.add(f"bench.{name}.wall", f"{time.perf_counter()-t0:.1f}", "s")

    out = Path(__file__).resolve().parents[1] / "results" / "bench.csv"
    out.parent.mkdir(exist_ok=True)
    out.write_text("name,value,derived\n" + "\n".join(
        f"{n},{v},{d}" for n, v, d in csv.rows))
    print(f"# wrote {out}", file=sys.stderr)


if __name__ == "__main__":
    main()
