"""Epidemic simulation driver — a thin wrapper over ``repro.api.run``.

    PYTHONPATH=src python -m repro.launch.simulate --spec examples/experiment.toml
    PYTHONPATH=src python -m repro.launch.simulate --dataset md-mini \
        --days 200 --tau 8e-6 --ckpt-dir /tmp/epi --replicates 3

The flags build (or, with ``--spec``, override) a declarative
:class:`~repro.api.ExperimentSpec`; engine selection, checkpoint/resume,
and observables all live behind the facade. Distributed mode engages
automatically when multiple JAX devices are visible
(XLA_FLAGS=--xla_force_host_platform_device_count=8 to emulate), or
explicitly via ``--workers``/``--distributed``.
"""

from __future__ import annotations

import argparse
import json

import jax

from repro import api
from repro.configs.presets import (  # noqa: F401  (legacy import path)
    DISEASES,
    INTERVENTION_PRESETS,
)
from repro.launch import cli


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    cli.add_common_args(ap)
    ap.add_argument("--interventions", default=None,
                    choices=sorted(INTERVENTION_PRESETS),
                    help="single intervention preset for this run")
    ap.add_argument("--distributed", action="store_true",
                    help="force people/location sharding over all devices")
    args = ap.parse_args()
    cli.use_compile_cache()

    extra = {}
    if args.interventions is not None:
        extra["interventions"] = (args.interventions,)
    # Auto-distribute over visible devices — but never behind a --spec's
    # back: a spec's declared mesh wins unless a flag explicitly overrides.
    if args.workers is None and (
        args.distributed or (args.spec is None and len(jax.devices()) > 1)
    ):
        extra["workers"] = len(jax.devices())

    spec = cli.build_spec(args, dict(
        name="simulate", days=100, interventions=("none",), replicates=1,
    ), **extra)

    result = api.run(spec)
    print(f"dataset={result.spec.dataset} engine={result.provenance['engine']} "
          f"scenarios={result.num_scenarios} days={result.days}")
    for row in result.summaries:
        print(json.dumps(row), flush=True)
    print(json.dumps({k: result.provenance[k]
                      for k in ("engine", "wall_s", "chunks",
                                "resumed_from_day")}))
    if "resilience" in result.provenance:
        print(json.dumps({"resilience": result.provenance["resilience"]}))
    if args.out:
        result.save(args.out)


if __name__ == "__main__":
    main()
