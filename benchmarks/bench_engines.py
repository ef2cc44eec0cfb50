"""Engine-parity benchmark: the one unified scan, timed on every topology.

All five legacy layouts now execute the identical topology-parameterized
day loop (repro/engine); this bench pins the refactor's perf against the
per-engine numbers PR 3 tracked: per-topology wall clock, TEPS (traversed
edges per second, the paper's Table I metric), and the parity of the
trajectories it timed (a wrong-result fast engine is not a fast engine).

Emits ``BENCH_engines.json`` (uploaded as a CI artifact by the smoke-bench
job):

    python benchmarks/bench_engines.py --tiny --out BENCH_engines.json

Topologies needing more devices than visible (dist/sharded/hybrid run on
1-device meshes in --tiny mode) are still exercised through their real
shard_map programs — axis size 1, same code path.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

if __package__ in (None, ""):  # `python benchmarks/bench_engines.py`
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


LAYOUTS = (
    # (engine label, EngineCore layout, kwargs)
    ("ensemble", "local", {}),
    ("sharded", "scenarios", {"scen_shards": None}),  # None = all devices
    ("hybrid", "hybrid", {"workers": 1, "scen_shards": None}),
    ("dist", "workers", {"workers": None}),  # B=1, all devices as workers
    ("single", "local", {}),  # B=1 local
)


def run(dataset="twin-2k", batch_size=4, days=10, backend="jnp", out=None):
    import jax

    from benchmarks.common import calibrated_tau, emit, get_pop, time_fn
    from repro.configs import ScenarioBatch
    from repro.core import disease
    from repro.engine import EngineCore

    pop = get_pop(dataset)
    tau = calibrated_tau(dataset)
    ndev = len(jax.devices())
    batch = ScenarioBatch.from_product(
        disease=disease.covid_model(), tau=tau,
        seeds=list(range(1, batch_size + 1)),
    )
    one = ScenarioBatch.from_scenarios(batch[:1])

    results, ref_hist = [], None
    for label, layout, kw in LAYOUTS:
        kw = dict(kw)
        b = one if label in ("single", "dist") else batch
        if "workers" in kw and kw["workers"] is None:
            kw["workers"] = ndev
        if "scen_shards" in kw and kw["scen_shards"] is None:
            kw["scen_shards"] = max(1, min(ndev, len(b)))
        if layout == "hybrid":
            kw["scen_shards"] = max(1, min(ndev // kw["workers"], len(b)))
        core = EngineCore(pop, b, layout=layout, backend=backend, **kw)

        # Parity first: the trajectories this timing run produces.
        _, _, hist, _ = core.run_days(days)
        if label == "ensemble":
            ref_hist = hist
        if ref_hist is not None:
            Bb = hist["cumulative"].shape[1]
            np.testing.assert_array_equal(
                hist["cumulative"], ref_hist["cumulative"][:, :Bb],
                err_msg=f"{label}: trajectory diverged from ensemble")

        # "edges" is the telemetry stat (the in-kernel counter on the
        # pallas-compact backend, cnt.sum() elsewhere); "contacts" is always
        # the host-side fold. Equality cross-checks the measurement.
        edges = float(np.asarray(hist["edges"], np.int64).sum())
        host_edges = float(np.asarray(hist["contacts"], np.int64).sum())
        assert edges == host_edges, \
            f"{label}: edge telemetry {edges} != host count {host_edges}"
        t = time_fn(core.bench_fn(days), warmup=1, iters=3)
        teps = edges / t
        row = {
            "engine": label,
            "layout": layout,
            "topology": type(core.topo).__name__,
            "batch": len(b),
            "workers": core.workers,
            "scen_shards": core.scen_shards,
            "wall_s": round(t, 4),
            "interactions_total": edges,
            "edge_counter": ("in-kernel" if backend == "pallas-compact"
                             else "host"),
            "teps": round(teps, 1),
        }
        results.append(row)
        emit(f"engines/{label}", t / days * 1e6,
             f"teps={teps:.3g};topology={row['topology']};"
             f"mesh={core.workers}x{core.scen_shards}")

    # --- per-agent TTI phase: tracing-on vs the plain ensemble ------------
    # Same batch, one TestTraceIsolate slot per scenario: the interaction
    # pass carries the second (traced-contact) accumulator and the day
    # step runs the capacity-limited budget. TEPS versus the plain
    # ensemble row is the whole-engine cost of contact tracing.
    from repro.core import interventions as iv_lib

    tti_batch = ScenarioBatch.from_product(
        interventions={"tti": [iv_lib.TestTraceIsolate(
            "tti", tests_per_day=max(4, pop.num_people // 100))]},
        disease=disease.covid_model(), tau=tau,
        seeds=list(range(1, batch_size + 1)),
    )
    core = EngineCore(pop, tti_batch, layout="local", backend=backend)
    _, _, hist, _ = core.run_days(days)
    edges = float(np.asarray(hist["edges"], np.int64).sum())
    host_edges = float(np.asarray(hist["contacts"], np.int64).sum())
    assert edges == host_edges, \
        f"tti: edge telemetry {edges} != host count {host_edges}"
    t = time_fn(core.bench_fn(days), warmup=1, iters=3)
    plain = next(r for r in results if r["engine"] == "ensemble")
    tti_row = {
        "engine": "ensemble+tti",
        "layout": "local",
        "topology": type(core.topo).__name__,
        "batch": len(tti_batch),
        "workers": 1,
        "scen_shards": 1,
        "wall_s": round(t, 4),
        "interactions_total": edges,
        "edge_counter": ("in-kernel" if backend == "pallas-compact"
                         else "host"),
        "teps": round(edges / t, 1),
        "tests_used": int(np.asarray(hist["tests_used"]).sum()),
        "teps_vs_plain": round((edges / t) / max(plain["teps"], 1e-9), 3),
    }
    results.append(tti_row)
    emit("engines/ensemble+tti", t / days * 1e6,
         f"teps={tti_row['teps']:.3g};"
         f"vs_plain={tti_row['teps_vs_plain']:.3f};"
         f"tests_used={tti_row['tests_used']}")

    result = {
        "bench": "engines",
        "dataset": dataset,
        "batch": batch_size,
        "days": days,
        "backend": backend,
        "num_devices": ndev,
        "parity": "bitwise (asserted in-run vs ensemble layout)",
        "engines": results,
    }
    if out:
        os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
        with open(out, "w") as f:
            json.dump(result, f, indent=1)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="twin-2k")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--days", type=int, default=10)
    ap.add_argument("--backend", default="jnp")
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke size: B=4, 10 days on the test twin")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    if args.tiny:
        args.dataset, args.batch, args.days = "twin-2k", 4, 10
    r = run(args.dataset, args.batch, args.days, args.backend, args.out)
    print(json.dumps(r))


if __name__ == "__main__":
    main()
