"""Benchmark driver: one section per paper table/figure, comparing losses.

Usage: PYTHONPATH=src python -m benchmarks.run [--fast]
Prints CSV sections; results are cached under artifacts/bench3/.  A section
that raises is reported and the rest still run; the script then exits
non-zero, naming the failed sections."""
from __future__ import annotations

import sys
import time
import traceback


def _section(title, fn, failed: list[str]):
    print(f"\n{'=' * 72}\n{title}\n{'=' * 72}", flush=True)
    t0 = time.perf_counter()
    try:
        fn()
    except Exception:  # noqa: BLE001 - one section's failure must not stop the rest
        traceback.print_exc()
        print(f"SECTION FAILED: {title}", flush=True)
        failed.append(title)
    print(f"[section time: {time.perf_counter() - t0:.1f}s]", flush=True)


def main() -> None:
    from . import (
        bench_b3_alphabeta,
        bench_b4_buffers,
        bench_fig3_tau,
        bench_sec6_noaverage,
        bench_table1,
    )

    fast = "--fast" in sys.argv
    failed: list[str] = []
    sections = [
        ("Table 1: base algorithms with/without SlowMo", bench_table1.main),
    ]
    if not fast:
        sections += [
            ("Figure 3: effect of tau", bench_fig3_tau.main),
            ("Appendix B.3: alpha/beta sweep", bench_b3_alphabeta.main),
            ("Appendix B.4: buffer strategies", bench_b4_buffers.main),
        ]
    sections += [
        ("Section 6: SlowMo-noaverage", bench_sec6_noaverage.main),
    ]
    for title, fn in sections:
        _section(title, fn, failed)
    if failed:
        sys.exit(f"{len(failed)} benchmark section(s) failed: {'; '.join(failed)}")


if __name__ == "__main__":
    main()
