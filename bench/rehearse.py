"""Compile each cell's chunk runner for a described TPU v5e at the twin's
full widths, without a chip, and report its memory and structure.

    JAX_PLATFORMS=cpu python bench/rehearse.py md-ens.onset

The engine is built at test size (``twin-2k``) with the cell's arms and
traffic; every array is then swapped for a shape of the full ``md`` twin
(``MD`` below) placed on one described v5e chip, and the runner of
``chunk_days`` days is lowered and compiled with the Pallas kernel
compiled (not interpreted). Prints, per cell, the compiled program's
``memory_analysis()`` bytes and whether the outbreak-seeding branch stayed
a conditional or became a select under the scenario vmap. Takes minutes:
a whole-program compile at full size.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

# The md twin's sizes as the chip run measured them (PERF.md): people,
# locations, visit slots per day (packed, padded), schedule tiles per day.
MD = {"people": 5_513_000, "locations": 2_894_325, "slots": 17_399_552,
      "tiles": 149_885}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cells", nargs="+")
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import harness
    from repro.api import observables as obs_lib
    from repro.configs import get_epidemic
    from repro.engine.core import EngineCore
    from repro.kernels.interactions import ops

    jax.config.update("jax_enable_compilation_cache", False)
    ops.pallas_interpret = lambda: False
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    pop = get_epidemic("twin-2k").build()
    P, L, V, NP = MD["people"], MD["locations"], MD["slots"], MD["tiles"]
    small = {"P": pop.num_people, "L": pop.num_locations}

    for name in args.cells:
        cell = harness.load_cell(ROOT, name)
        cfg, traffic = cell.config, cell.traffic
        batch = harness.build_batch(cfg, traffic, seed=0)
        core = EngineCore(pop, batch, layout=cfg["layout"],
                          backend=cfg["backend"],
                          block_size=cfg["block_size"],
                          max_seed_per_day=traffic["seed_per_day"])
        B = len(batch)
        core.static = dataclasses.replace(
            core.static, num_people=P, num_locations=L, people_per_worker=P,
            visits_per_worker=V,
            seed_topk=max(1, min(traffic["seed_per_day"], P)))
        small_v = core.week["pid"].shape[1]
        small_np = core.week["row"].shape[1]

        def full(x, last):
            shape = tuple(last.get(d, d) if i == x.ndim - 1 else d
                          for i, d in enumerate(x.shape))
            return jax.ShapeDtypeStruct(shape, x.dtype, sharding=chip)

        person = {small["P"]: P, small["L"]: L}
        params = jax.tree.map(lambda x: full(x, person), core.params)
        state = jax.tree.map(lambda x: full(x, person), core.init_state())
        week = jax.tree.map(
            lambda x: full(x, {small_v: V, small_np: NP}), core.week)
        obs = obs_lib.make_observables(cfg["observables"])
        ctx = obs_lib.ObsContext(
            num_people=P, num_scenarios=B,
            sweep_axes=(("interventions", tuple(range(B))),))
        carries = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype,
                                           sharding=chip),
            obs_lib.init_carries(obs, ctx))
        runner = core.runner_fn(traffic["chunk_days"], obs)
        t0 = time.perf_counter()
        lowered = runner.lower(params, state, carries, week, None)
        compiled = lowered.compile()
        secs = time.perf_counter() - t0
        mem = compiled.memory_analysis()
        hlo = compiled.as_text()
        text = lowered.as_text()
        out = {
            "cell": name, "B": B, "compile_s": round(secs, 1),
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "generated_code_bytes": mem.generated_code_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "tpu_custom_call": hlo.count("tpu_custom_call"),
            "hlo_conditional": len(re.findall(r"\bconditional\(", hlo)),
            "hlo_sort": len(re.findall(r"\bsort\(", hlo)),
            "stablehlo_case": text.count("stablehlo.case"),
            "stablehlo_sort": text.count("stablehlo.sort"),
        }
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
