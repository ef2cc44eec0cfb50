"""Scenario-ensemble driver — a thin wrapper over ``repro.api.run``.

    PYTHONPATH=src python -m repro.launch.sweep --dataset twin-2k --days 60 \
        --interventions none,school-closure,lockdown --replicates 3 \
        --tau-scales 1.0,0.75 --out artifacts/sweep.json

The flags build (or, with ``--spec``, override) a declarative
:class:`~repro.api.ExperimentSpec` whose sweep axes (interventions x tau x
replicate seeds) expand to a ScenarioBatch; the facade picks the ensemble
engine from the mesh shape (``--workers W`` selects the hybrid 2-D
workers x scenarios mesh; multiple visible devices shard the scenario
axis automatically) and reports per-scenario attack-rate summaries plus
ensemble throughput (TEPS x batch).
"""

from __future__ import annotations

import argparse
import json

import jax

from repro import api
from repro.analysis.report import sweep_table
from repro.launch import cli


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    cli.add_common_args(ap)
    ap.add_argument("--interventions", default=None,
                    help="comma list of preset names "
                         "(see repro/configs/presets.py)")
    ap.add_argument("--tau-scales", default=None,
                    help="comma list of multipliers on the base tau")
    ap.add_argument("--sharded", action="store_true",
                    help="force sharding the scenario axis over all devices")
    args = ap.parse_args()
    cli.use_compile_cache()

    extra = {}
    if args.interventions is not None:
        extra["interventions"] = cli.parse_intervention_axis(args.interventions)
    if args.tau_scales is not None:
        extra["tau_scales"] = cli.parse_float_axis(args.tau_scales,
                                                   "--tau-scales")
    if args.sharded and args.engine is None:
        extra["engine"] = "sharded"  # force the shard_map path, any devices

    spec = cli.build_spec(args, dict(
        name="sweep", days=60,
        interventions=("none", "school-closure"), replicates=2,
    ), **extra)

    # Auto-fill the scenario mesh axis, clamped to the batch size (a
    # 1-scenario study must not request a multi-device scenario axis):
    # --sharded shards over visible devices; flag-built hybrid runs
    # (--workers W) give the scenario axis the devices the workers leave;
    # other flag-built multi-device runs shard over everything. A --spec
    # file's declared mesh always wins unless --scenarios overrides it.
    ndev = len(jax.devices())
    if args.scenarios is None:
        B = spec.num_scenarios
        if args.sharded:
            spec = spec.with_overrides(scenarios=min(ndev, B))
        elif args.spec is None and spec.mesh.workers > 1:
            spec = spec.with_overrides(
                scenarios=max(1, min(ndev // spec.mesh.workers, B)))
        elif args.spec is None and ndev > 1 and B > 1:
            spec = spec.with_overrides(scenarios=min(ndev, B))

    result = api.run(spec)
    prov = result.provenance
    print(f"dataset={result.spec.dataset} engine={prov['engine']} "
          f"scenarios={result.num_scenarios} days={result.days} "
          f"devices={prov['num_devices']}")
    sweep_table(result.summaries)
    edges = float(sum(r["interactions"] for r in result.summaries))
    # Throughput from the day-loop wall clock (excl. pop build), keeping
    # the TEPS breadcrumbs comparable with the pre-facade artifacts.
    wall = prov["run_wall_s"]
    print(json.dumps({
        "dataset": result.spec.dataset,
        "engine": prov["engine"],
        "scenarios": result.num_scenarios,
        "days": result.days,
        "wall_s": wall,
        "s_per_scenario_day": round(
            wall / (result.days * result.num_scenarios), 5),
        "ensemble_teps": round(edges / wall, 1) if wall else None,
    }))

    if args.out:
        result.save(args.out)  # creates parent dirs


if __name__ == "__main__":
    main()
