"""Benchmark harness — one function per paper figure/table.

Prints ``name,us_per_call,derived`` CSV (us_per_call = wall time per simulated
round; derived = accuracy/resource/waste/unique metrics).

Usage:
  PYTHONPATH=src python -m benchmarks.run               # all figures
  PYTHONPATH=src python -m benchmarks.run fig02 fig10   # subset
  REPRO_BENCH_SCALE=full ... python -m benchmarks.run   # paper-scale (slow)

Every selected figure runs even when an earlier one raises; the exit code
is non-zero if any of them did.
"""
from __future__ import annotations

import sys
import time
import traceback


def main() -> int:
    from benchmarks.figures import ALL_FIGURES
    sel = set(sys.argv[1:])
    print("name,us_per_call,derived")
    t0 = time.time()
    failed = []
    for fn in ALL_FIGURES:
        tag = fn.__name__.split("_")[0]
        if sel and tag not in sel and fn.__name__ not in sel:
            continue
        try:
            fn()
        except Exception as e:  # noqa: BLE001 — a figure failing must not hide others
            traceback.print_exc()
            print(f"{fn.__name__},0,ERROR={e!r}")
            failed.append(fn.__name__)
    print(f"# total wall time: {time.time()-t0:.0f}s", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
