"""Run the simulator's main path on a TPU and check what comes out.

    python chip_smoke.py              # one chip: phases (a) and (b)
    python chip_smoke.py --chips 4    # four chips: phase (c) only

(a) Cross-check. ``md-mini`` (55,130 people), interventions none and
    lockdown, 20 days, through ``repro.api.run`` twice: with the ``jnp``
    backend and with the compiled ``pallas-compact`` kernel. Every history
    key must be bitwise equal, the runner of the second must hold the
    Pallas kernel (``tpu_custom_call``), and every in-scan observable must
    equal its post-scan replay bit for bit.
(b) The main path. The ``md`` twin at full scale (5.513M people),
    interventions none, lockdown and tti (B=3), 14 days, ``pallas-compact``,
    through ``repro.api.run`` with checkpointing and the invariant guards
    on (any violation fails the run). The in-kernel edge counter must equal
    the ``contacts`` stat on every day, every scenario must have new
    infections after the seeding days, and the TEPS ``edges_total`` must
    equal the int64 sum of the daily series.
(c) Four chips. ``md``, 14 days, no intervention, two Monte Carlo
    replicates run once on one chip through ``repro.api.run``. Against it,
    bitwise: the ``workers`` layout (W=4, B=1, the first replicate) and
    ``hybrid`` (2x2, both replicates). The sharded runs use the engine core
    that ``repro.api.run`` builds for those layouts, so that the final
    state can be checked to sit on all four devices.

Earlier lines of the output report phase wall times, sizes and rates. The
last line is one JSON object naming the device. Without a TPU, or when any
check fails, the script exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402


def log(*parts):
    print(*parts, flush=True)


def require(ok, what):
    if not ok:
        raise RuntimeError(f"check failed: {what}")


class CompileClock:
    """When, and for how long, XLA compiled: JAX's monitoring events for
    backend compiles, each stamped with the time it ended. (Tracing and
    lowering events nest, so summing them would count time twice; they
    stay in the run time.)"""

    def __init__(self, monitoring):
        self.events = []
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((time.perf_counter(), duration))

    def seconds(self, since):
        return sum(d for t, d in self.events if t >= since)


def timed_run(api, spec, pop, clock):
    """``api.run`` with its wall time split into XLA compile and the rest
    (``run_s``): the engine build, the day loop, and checkpoint writes
    where the spec asks for them. The engine build is reported too, as a
    part of ``run_s``; it is not subtracted, because compiles made while
    it runs are already counted in ``compile_s``."""
    t0 = time.perf_counter()
    r = api.run(spec, population=pop)
    wall = time.perf_counter() - t0
    compile_s = clock.seconds(t0)
    run_s = wall - compile_s
    return r, {
        "wall_s": wall,
        "compile_s": compile_s,
        "run_s": run_s,
        "engine_build_s": r.provenance["engine_build_s"],
        "days_per_s": spec.days / run_s,
    }


def build_twin(name):
    from repro.configs import get_epidemic

    t0 = time.perf_counter()
    pop = get_epidemic(name).build()
    build_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    packed = pop.packed_week(128)  # the engine reuses this packing
    sizes = {
        "people": pop.num_people,
        "locations": pop.num_locations,
        "visits_per_day": [d.num_real for d in pop.week],
        "visits_per_week": pop.visits_per_week,
        "V_slots": max(len(d) for d in packed),
        "NP_tiles_per_day": [d.np_after for d in packed],
    }
    log(f"[{name}] build_s={build_s:.1f} pack_s="
        f"{time.perf_counter() - t0:.1f} {json.dumps(sizes)}")
    return pop


def same_history(a, b, label):
    for k in a:
        if not np.array_equal(a[k], b[k]):
            diff = np.argwhere(a[k] != b[k])
            raise RuntimeError(
                f"check failed: {label}: history '{k}' differs first at "
                f"(day, scenario) {tuple(diff[0])}: {a[k][tuple(diff[0])]} "
                f"vs {b[k][tuple(diff[0])]}")
    log(f"{label}: all {len(a)} history keys bitwise equal")


def phase_a(api, clock):
    import jax
    from repro.api import observables as obs_lib
    from repro.api import runner as runner_lib

    pop = build_twin("md-mini")
    base = api.ExperimentSpec(
        name="smoke-a", dataset="md-mini", days=20,
        interventions=("none", "lockdown"))
    runs = {}
    for backend in ("jnp", "pallas-compact"):
        spec = dataclasses.replace(base, backend=backend)
        runs[backend], t = timed_run(api, spec, pop, clock)
        log(f"[a:{backend}] {json.dumps(t)}")
    same_history(runs["jnp"].history, runs["pallas-compact"].history,
                 "[a] jnp vs pallas-compact")

    r = runs["pallas-compact"]
    B = r.num_scenarios
    obs = obs_lib.make_observables(spec.observables)
    ctx = obs_lib.ObsContext(num_people=pop.num_people, num_scenarios=B,
                             sweep_axes=runner_lib._sweep_axes(spec, B))
    core = runner_lib._make_core(r.provenance["engine"], spec, pop,
                                 spec.build_batch())
    lowered = core.runner_fn(spec.days, obs).lower(
        core.params, core.init_state(), obs_lib.init_carries(obs, ctx),
        core.week, core.route)
    require("tpu_custom_call" in lowered.as_text(),
            "the pallas-compact runner holds no compiled Pallas kernel")
    log("[a] the pallas-compact runner holds tpu_custom_call")

    replay = obs_lib.observables_to_numpy(
        obs_lib.observe_history(obs, r.history, ctx))
    for name in r.observables:
        got = jax.tree.leaves(r.observables[name])
        ref = jax.tree.leaves(replay[name])
        require(len(got) == len(ref) and all(
            np.array_equal(x, y) for x, y in zip(got, ref)),
            f"in-scan observable '{name}' differs from its replay")
    log(f"[a] {len(r.observables)} in-scan observables equal their "
        "post-scan replay bitwise")


def phase_b(api, clock):
    pop = build_twin("md")
    ckpt = os.path.join(ROOT, ".smoke_ckpt")
    shutil.rmtree(ckpt, ignore_errors=True)
    spec = api.ExperimentSpec(
        name="smoke-b", dataset="md", days=14,
        interventions=("none", "lockdown", "tti"),
        backend="pallas-compact",
        checkpoint=api.CheckpointSpec(directory=ckpt, every=14,
                                      resume=False),
        resilience=api.ResilienceSpec(enabled=True, max_restarts=0),
    )
    try:
        r, t = timed_run(api, spec, pop, clock)
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)
    h = r.history
    edges = h["edges"].astype(np.int64)
    t["edges_per_day"] = edges.sum(axis=1).tolist()
    t["edges_total"] = int(edges.sum())
    t["max_daily_contacts"] = int(h["contacts"].max())
    t["new_infections"] = h["new_infections"].sum(axis=0).tolist()
    log(f"[b] {json.dumps(t)}")
    log(f"[b] provenance {json.dumps(r.provenance, default=str)}")

    require(np.array_equal(h["edges"], h["contacts"]),
            "in-kernel edges differ from the contacts stat")
    res = r.provenance["resilience"]
    require(res["restarts"] == 0 and not res["guard_violations"],
            f"the run recovered from faults: {res}")
    after = h["new_infections"][spec.seed_days:].sum(axis=0)
    require(np.all(after > 0), f"no new infections after seeding: {after}")
    require(int(r.observables["teps"]["edges_total"]) == t["edges_total"],
            "edges_total differs from the int64 sum of the daily series")
    log("[b] edges == contacts on every day; guards passed; infections "
        "after seeding in every scenario; edges_total exact")


def phase_c(api, clock):
    import jax
    from repro.api import runner as runner_lib

    require(len(jax.devices()) == 4, "--chips 4 needs four devices")
    pop = build_twin("md")
    ensemble = api.ExperimentSpec(
        name="smoke-c", dataset="md", days=14, replicates=2,
        backend="pallas-compact")
    ref, t = timed_run(api, ensemble, pop, clock)
    log(f"[c] one-chip ensemble (B=2) {json.dumps(t)}")
    cases = (
        # scenario 0 of the ensemble is this spec's one scenario (seed 0)
        ("workers", "dist", 1, api.MeshSpec(workers=4)),
        ("hybrid", "hybrid", 2, api.MeshSpec(workers=2, scenarios=2)),
    )
    for label, engine, replicates, mesh in cases:
        spec = dataclasses.replace(ensemble, replicates=replicates,
                                   mesh=mesh)
        t0 = time.perf_counter()
        core = runner_lib._make_core(engine, spec, pop, spec.build_batch())
        built = time.perf_counter() - t0
        t0 = time.perf_counter()
        final, _, hist, _ = core.run_days(spec.days)
        wall = time.perf_counter() - t0
        compile_s = clock.seconds(t0)
        log(f"[c:{label}] {core.layout} engine_build_s={built} "
            f"compile_s={compile_s} run_s={wall - compile_s}")
        devices = final.health.sharding.device_set
        require(len(devices) == 4,
                f"{label}: final state on {len(devices)} device(s)")
        same_history({k: v[:, :replicates] for k, v in ref.history.items()},
                     hist, f"[c] {label} vs one chip")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the four-chip layouts only")
    args = ap.parse_args(argv)

    import jax
    from jax import monitoring

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX found '{dev.platform}'", file=sys.stderr)
        return 1

    from repro import api
    from repro.launch.cli import use_compile_cache

    log(f"compile cache: {use_compile_cache()}")
    log(f"device: {dev.device_kind} x{len(jax.devices())}")
    clock = CompileClock(monitoring)
    t0 = time.perf_counter()
    phases = (phase_c,) if args.chips == 4 else (phase_a, phase_b)
    for phase in phases:
        p0 = time.perf_counter()
        phase(api, clock)
        log(f"{phase.__name__} done in {time.perf_counter() - p0:.1f}s")
    log(f"total {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
