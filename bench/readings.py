"""The readings a cell's limits are set from, on the chip, in one process.

    python3 bench/readings.py --workload md-ens.onset --seeds 11,12,13 \\
        --control-seeds 11,12,13

For each seed: the program's outputs as a run compares them (the advance
to the window's start and one chunk, through the same compiled runner)
against the plain reference in the configuration's precision; and, for
each control seed, the control (the reference in bfloat16, in the
program's place) against the same. One JSON line per seed. The twin, the
engine and the compile are set up once; each seed only swaps the
scenarios' parameters, which the runner takes as an argument. The
benchmark's own runs do not run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))

CONTROL_DTYPE = "bfloat16"


def seed_params(pop, cfg: dict, traffic: dict, seed: int):
    """The batch's parameters under another Monte Carlo seed."""
    from bench import harness
    from repro.engine.core import build_batch_params, stack_params

    batch = harness.build_batch(cfg, traffic, seed)
    return stack_params(build_batch_params(pop, batch)[2])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control_seeds.split(",") if s}

    import jax

    from bench import harness, twin_cache
    from bench.reference import compare, stretches
    from repro.launch.cli import use_compile_cache

    use_compile_cache()
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    cell = harness.load_cell(ROOT, args.workload)
    cfg, traffic = cell.config, cell.traffic
    K, d0 = int(traffic["chunk_days"]), int(traffic["start_day"])
    pop, _ = twin_cache.load_or_build(ROOT, cfg["dataset"], cfg["block_size"])
    twin = harness.raw_twin(pop)
    core, runner, carries = harness.make_engine(pop, cfg, traffic, seeds[0])
    week, route, state0 = core.week, core.route, core.init_state()
    dtype = cfg.get("dtype", "float32")
    for seed in seeds:
        t0 = time.perf_counter()
        params = seed_params(pop, cfg, traffic, seed)
        state, prog = harness.advance_chunks(runner, params, state0, carries,
                                             week, route, d0 // K + 1)
        del state, params
        t1 = time.perf_counter()
        want = stretches(cfg, traffic, twin, seed, dtype)
        line = {"cell": args.workload, "seed": seed,
                "program": compare(prog, want),
                "cumulative": [int(c) for c in want[-1][1]["cumulative"][-1]]}
        t2 = time.perf_counter()
        line.update(program_s=t1 - t0, reference_s=t2 - t1)
        if seed in control:
            line["control"] = compare(
                stretches(cfg, traffic, twin, seed, CONTROL_DTYPE), want)
            line["control_s"] = time.perf_counter() - t2
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
