"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Sizes are CPU-calibrated;
the scale-out story is carried by the dry-run roofline (bench_roofline
reads its artifacts).

    PYTHONPATH=src python -m benchmarks.run [--only fig6,fig9] [--fast]
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="comma-separated bench keys")
    ap.add_argument("--fast", action="store_true",
                    help="smaller datasets / fewer replicates")
    args = ap.parse_args()

    from benchmarks import (
        bench_kernels, bench_opts, bench_roofline,
        bench_sharding, bench_strong, bench_sweep, bench_teps,
        bench_validation, bench_weak,
    )

    fast = args.fast
    suites = {
        "fig1_config": lambda: bench_sharding.run(days=6 if fast else 10),
        "fig5_opts": lambda: bench_opts.run(
            dataset="twin-2k" if fast else "md-mini"),
        "fig6_strong": lambda: bench_strong.run(
            datasets=("twin-2k",) if fast else ("twin-2k", "md-mini", "ws-50k"),
            days=10 if fast else 30),
        "fig8_weak": lambda: bench_weak.run(days=7 if fast else 14),
        "fig9_validation": lambda: bench_validation.run(
            replicates=6 if fast else 30, days=60 if fast else 120),
        "table1_teps": lambda: bench_teps.run(
            dataset="twin-2k" if fast else "md-mini", days=10 if fast else 20),
        "sweep": lambda: bench_sweep.run(
            dataset="twin-2k", batch_size=4 if fast else 8,
            days=10 if fast else 20),
        "kernels": bench_kernels.run,
        "roofline": bench_roofline.run,
    }
    only = [s for s in args.only.split(",") if s]
    print("name,us_per_call,derived")
    failures = 0
    for key, fn in suites.items():
        if only and not any(key.startswith(o) or o.startswith(key) for o in only):
            continue
        t0 = time.time()
        try:
            fn()
            print(f"# {key} done in {time.time()-t0:.1f}s", flush=True)
        except Exception:
            failures += 1
            print(f"# {key} FAILED:", file=sys.stderr)
            traceback.print_exc()
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
