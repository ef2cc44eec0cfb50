"""Table I headline: measured TEPS (traversed edges per second) per backend.

The paper reports 4.6B TEPS for 200 days of the California digital twin on
512 nodes (PAPER.md); this bench produces the comparable figure for every
interaction backend on whatever hardware runs it, from *measured* traversed
edges — the per-day edge counters threaded through ``day_step`` — over the
wall clock of the whole compiled scan. On the ``pallas-compact`` backend the
edge count comes from the kernel's own accumulator; every run asserts
it equals the host-side fold (``contacts``), so the headline number is a
cross-checked measurement, not an estimate.

Also emits the v5e-projected kernel-roofline TEPS (VPU ops per candidate
pair x pairs per day) for context against the paper's scale.

CI runs the tiny gate (writes + checks ``BENCH_teps.json``):

    python benchmarks/bench_teps.py --tiny --out BENCH_teps.json \
        --check --tolerance 0.15

``--check`` compares against the committed baseline
(``benchmarks/baselines/BENCH_teps_baseline.json``): traversed-edge totals
must match *exactly* (they are deterministic), measured TEPS may not regress
more than ``--tolerance`` below baseline. ``--update-baseline`` rewrites the
baseline file from the current run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

if __package__ in (None, ""):  # `python benchmarks/bench_teps.py`
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

BASELINE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "baselines", "BENCH_teps_baseline.json")

# Per candidate pair in the kernel tile: overlap (4 VPU ops), masks (~6),
# hash (2x fmix32 chain ~ 22 u32 ops), propensity (~4) => ~36 VPU ops.
OPS_PER_PAIR = 36.0
V5E_VPU_OPS = 197e12 / 2 / 128 * 8  # ~ f32 VPU throughput proxy (ops/s)


def run(dataset="md-mini", days=20,
        backends=("jnp", "compact", "pallas-compact"), out=None):
    from benchmarks.common import calibrated_tau, emit, get_pop, time_fn
    from repro.core import disease, transmission
    from repro.engine.core import EngineCore

    pop = get_pop(dataset)
    rows = {}
    edges_ref = None
    for backend in backends:
        sim = EngineCore.single(
            pop, disease.covid_model(),
            transmission.TransmissionModel(tau=calibrated_tau(dataset)),
            seed=1, backend=backend,
        )
        # Warm-up run doubles as the edge measurement (identical re-run).
        _, hist = sim.run1(days)
        edges = int(np.asarray(hist["edges"], np.int64).sum())
        host_edges = int(np.asarray(hist["contacts"], np.int64).sum())
        # On pallas-compact "edges" is the kernel's own accumulator; it
        # must equal the host-side fold exactly — else the telemetry lies.
        assert edges == host_edges, (
            f"{backend}: in-kernel edge counter {edges} != "
            f"host-side count {host_edges}")
        if edges_ref is None:
            edges_ref = edges
        else:
            assert edges == edges_ref, \
                f"{backend} traversed {edges} edges, expected {edges_ref}"
        t = time_fn(sim.bench_fn(days), warmup=0, iters=1)
        teps = edges / t
        rows[backend] = {
            "wall_s": round(t, 4),
            "edges_total": edges,
            "edge_counter": ("in-kernel" if backend == "pallas-compact"
                             else "host"),
            "teps": round(teps, 1),
        }
        emit(f"table1_teps/{backend}", t / days * 1e6,
             f"teps={teps:.3g};edges_total={edges:.3g};"
             f"counter={rows[backend]['edge_counter']}")

    # PR 7 gate: a per-agent intervention slot that is *disabled* (the TTI
    # layer statically compiled out) or *enabled with zero budget* (the
    # traced program with an identically-zero source channel) must not
    # perturb a single traversed edge relative to the plain run.
    from repro.core import interventions as iv_lib

    pa_variants = {
        "disabled_pa_slot": dict(iv_enabled=[False]),
        "zero_budget_pa_slot": dict(iv_enabled=[True]),
    }
    for label, en in pa_variants.items():
        budget = 0 if label == "zero_budget_pa_slot" else 50
        sim_pa = EngineCore.single(
            pop, disease.covid_model(),
            transmission.TransmissionModel(tau=calibrated_tau(dataset)),
            seed=1, backend=backends[0],
            interventions=[iv_lib.TestTraceIsolate(
                "tti", tests_per_day=budget)],
            **en,
        )
        _, hist_pa = sim_pa.run1(days)
        edges_pa = int(np.asarray(hist_pa["edges"], np.int64).sum())
        assert edges_pa == edges_ref, (
            f"{label}: traversed {edges_pa} edges, expected {edges_ref} — "
            "an inert per-agent intervention perturbed the trajectory")
        assert int(np.asarray(hist_pa["tests_used"]).sum()) == 0, label
        rows.setdefault("_pa_noop", {})[label] = edges_pa
        emit(f"table1_teps/{label}", 0.0, f"edges_total={edges_pa:.3g};ok")
    pa_noop = rows.pop("_pa_noop")

    # kernel-level v5e projection: candidate pairs per day from the block
    # schedule (post-packing); edges/candidates from the measured run.
    pairs_per_day = float(sim.week_data.row_idx.shape[1]) * sim.block_size**2
    proj_days_per_s = V5E_VPU_OPS / (pairs_per_day * OPS_PER_PAIR)
    proj_teps_chip = (edges_ref / days) * proj_days_per_s
    emit("table1_teps/v5e_projection_per_chip", 0.0,
         f"teps={proj_teps_chip:.3g};"
         f"x256_chips={proj_teps_chip*256:.3g};paper_512nodes=4.6e9")

    result = {
        "bench": "teps",
        "dataset": dataset,
        "days": days,
        "edges_total": edges_ref,
        "edges_total_pa_noop": pa_noop,
        "backends": rows,
        "v5e_projection_per_chip_teps": proj_teps_chip,
    }
    if out:
        os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
        with open(out, "w") as f:
            json.dump(result, f, indent=1)
        print(f"# wrote {out}")
    return result


def check(result, baseline_path=BASELINE, tolerance=0.15) -> list[str]:
    """Regression gate vs the committed baseline. Returns failure strings
    (empty = pass). Edge totals are deterministic => exact; TEPS is wall-
    clock => bounded relative regression."""
    with open(baseline_path) as f:
        base = json.load(f)
    fails = []
    if (result["dataset"], result["days"]) != (base["dataset"], base["days"]):
        return [f"baseline is {base['dataset']}/{base['days']}d, "
                f"run is {result['dataset']}/{result['days']}d — not comparable"]
    if result["edges_total"] != base["edges_total"]:
        fails.append(f"edges_total {result['edges_total']} != baseline "
                     f"{base['edges_total']} (determinism broken)")
    for label, e in result.get("edges_total_pa_noop", {}).items():
        if e != result["edges_total"]:
            fails.append(
                f"{label}: edges_total {e} != plain run "
                f"{result['edges_total']} (an inert per-agent intervention "
                "slot must not perturb the traversed-edge count)")
    for be, b_row in base["backends"].items():
        row = result["backends"].get(be)
        if row is None:
            fails.append(f"backend '{be}' missing from run")
            continue
        floor = b_row["teps"] * (1.0 - tolerance)
        if row["teps"] < floor:
            fails.append(
                f"{be}: teps {row['teps']:.3g} < {floor:.3g} "
                f"(baseline {b_row['teps']:.3g} - {tolerance:.0%})")
    return fails


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dataset", default="md-mini")
    ap.add_argument("--days", type=int, default=20)
    ap.add_argument("--backends", default="jnp,compact,pallas-compact")
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke size: twin-2k, 10 days")
    ap.add_argument("--out", default=None, help="write BENCH_teps.json here")
    ap.add_argument("--check", action="store_true",
                    help="fail on TEPS regression vs the committed baseline")
    ap.add_argument("--baseline", default=BASELINE)
    ap.add_argument("--tolerance", type=float, default=0.15)
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite the committed baseline from this run")
    args = ap.parse_args()
    if args.tiny:
        args.dataset, args.days = "twin-2k", 10
    print("name,us_per_call,derived")
    result = run(dataset=args.dataset, days=args.days,
                 backends=tuple(args.backends.split(",")), out=args.out)
    if args.update_baseline:
        os.makedirs(os.path.dirname(args.baseline), exist_ok=True)
        with open(args.baseline, "w") as f:
            json.dump(result, f, indent=1)
        print(f"# baseline updated: {args.baseline}")
    if args.check:
        fails = check(result, args.baseline, args.tolerance)
        for msg in fails:
            print(f"FAIL {msg}")
        if fails:
            sys.exit(1)
        print(f"# TEPS gate passed (tolerance {args.tolerance:.0%})")


if __name__ == "__main__":
    main()
