"""Run one cell of the benchmark on the chip it finds.

    python3 bench/run.py --workload md-ens.onset --seed 7 --seconds 10 --trace 0

Set-up, a measured window of ``--seconds``, then the check of what the
window produced against the plain reference. Earlier lines of standard
output carry the run's other numbers; the last line is one JSON object
with ``correct``, ``attempted``, ``failed``, ``metrics`` and ``device``
(and, with ``--trace 1``, ``breakdown``), then ``checks``: each number
compared, with its limit. The same numbers close standard error. Without
a TPU, or with fewer chips than the cell asks for, it exits non-zero and
prints no result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# The checkout's root, not this directory: bench/trace.py must not shadow
# the standard library's module of that name.
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    import repro
    from repro.launch.cli import use_compile_cache

    where = [os.path.abspath(p) for p in repro.__path__]
    if where != [os.path.join(ROOT, "src", "repro")]:
        print(f"the program under test is not in this checkout: {where}",
              file=sys.stderr)
        return 2

    try:
        harness.require_chips(harness.load_cell(ROOT, args.workload))
    except harness.NoChip as e:
        print(e, file=sys.stderr)
        return 2
    use_compile_cache()
    run, line = harness.run_cell(ROOT, args.workload, args.seed,
                                 args.seconds, bool(args.trace), t_start=T0)
    harness.report(run)
    for k, c in run.checks.items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
