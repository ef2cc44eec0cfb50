"""``run(spec) -> RunResult``: the one front door to the one engine core.

The facade derives the *layout* from the spec's batch size and mesh shape
(never hand-picked, though ``spec.engine`` can pin one for parity tests)
and hands everything to :mod:`repro.engine`: every layout executes the
identical topology-parameterized day-loop scan —

  =========  =========================  ================================
  engine     selected when              engine-core placement
  =========  =========================  ================================
  single     B == 1, workers == 1       ``EngineCore(layout="local")``
  dist       B == 1, workers > 1        ``EngineCore(layout="workers")``
  ensemble   B > 1, 1×1 mesh            ``EngineCore(layout="local")``
  sharded    B > 1, scenarios > 1       ``EngineCore(layout="scenarios")``
  hybrid     B > 1, workers > 1         ``EngineCore(layout="hybrid")``
  =========  =========================  ================================

Observable ``update()`` hooks run *inside* the scan body on every
placement (cross-scenario reductions see the full batch through the
topology's scenario-axis gather — a collective when the batch is sharded).
The only exception is a pinned single/dist engine with B > 1, which runs
scenarios sequentially through one compiled program and replays the pure
reductions post-run (bitwise-identical by purity).

The day-chunked checkpoint/resume loop lives in the engine core
(:func:`repro.engine.core.run_chunked`) and is bitwise on every layout.
Resume keys carry the engine-core generation marker — checkpoints written
by the pre-refactor per-engine loops are refused, not spliced.

Histories are normalized day-major with a scenario axis: every array is
``(days, B)``, B=1 included, so downstream analysis never branches on
engine; padded batch slots are inert no-ops that never appear here.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np
import jax

from repro.analysis.report import summarize_sweep
from repro.api import observables as obs_lib
from repro.api.result import RunResult
from repro.api.spec import ExperimentSpec
from repro.checkpoint import CheckpointManager
from repro.configs import get_epidemic
from repro.engine import core as engine_lib
from repro.runtime import resilience as resilience_lib


def _resume_key(spec: ExperimentSpec, engine: str) -> dict:
    """What must match for a checkpoint to be resumable under this spec:
    everything that shapes the state pytree or the science — but not the
    run length (extending a run IS the resume use case), the checkpoint
    policy itself, the study's display name, or the observables (pure
    reductions replayed from the restored history, never checkpointed).
    ``core`` marks the engine generation: checkpoints written by the
    pre-refactor engines carry no (or another) marker and are refused."""
    d = spec.to_dict()
    # resilience is pure recovery policy — it never changes the science,
    # so toggling it must not invalidate existing checkpoints.
    for k in ("days", "checkpoint", "name", "engine", "observables",
              "resilience"):
        d.pop(k, None)
    d["engine_resolved"] = engine
    d["core"] = engine_lib.CORE_VERSION
    return d


def _resolve_engine(spec: ExperimentSpec, B: int) -> str:
    if spec.engine != "auto":
        return spec.engine
    W, S = spec.mesh.workers, spec.mesh.scenarios
    if W > 1:
        return "hybrid" if B > 1 else "dist"
    if B > 1:
        return "sharded" if S > 1 else "ensemble"
    return "single"


_LAYOUTS = {
    "single": "local",
    "ensemble": "local",
    "dist": "workers",
    "sharded": "scenarios",
    "hybrid": "hybrid",
}


def _make_core(engine: str, spec: ExperimentSpec, pop, batch):
    if engine == "sharded" and spec.mesh.scenarios > len(jax.devices()):
        raise ValueError(
            f"mesh.scenarios={spec.mesh.scenarios} but only "
            f"{len(jax.devices())} devices are visible")
    return engine_lib.EngineCore(
        pop, batch,
        layout=_LAYOUTS[engine],
        workers=spec.mesh.workers,
        scen_shards=spec.mesh.scenarios,
        backend=spec.backend,
        block_size=spec.block_size,
        pack_visits=spec.pack_visits,
        max_seed_per_day=max(s.seed_per_day for s in batch),
    )


def _sweep_axes(spec: ExperimentSpec, B: int) -> tuple:
    """Per-scenario level assignments of the factorial sweep axes (axes
    with a single level carry no information and are dropped). Order
    matches ScenarioBatch.from_product: interventions × tau × replicates,
    replicates innermost."""
    n_iv = len(spec.interventions)
    n_tau = len(spec.tau_scales)
    n_rep = spec.replicates
    if n_iv * n_tau * n_rep != B:  # hand-built batch: no factorial info
        return ()
    idx = np.arange(B)
    axes = []
    if n_iv > 1:
        axes.append(("interventions", tuple((idx // (n_tau * n_rep)).tolist())))
    if n_tau > 1:
        axes.append(("tau_scales", tuple(((idx // n_rep) % n_tau).tolist())))
    if n_rep > 1:
        axes.append(("replicates", tuple((idx % n_rep).tolist())))
    return tuple(axes)


def run(spec: ExperimentSpec, *, population=None, chaos=None,
        on_straggler=None) -> RunResult:
    """Execute an :class:`ExperimentSpec` end to end; the one public entry
    point. ``population=`` substitutes a prebuilt Population for
    ``spec.dataset`` (a testing hook — parity tests reuse one build).

    ``chaos=`` injects a deterministic fault schedule
    (:class:`repro.runtime.chaos.ChaosSchedule`) into the chunk loop and
    implies the resilient path — the chaos-harness hook the recovery
    matrix in CI runs through. ``on_straggler(day, dt, median)`` observes
    straggler detections (the adaptive-repartition seam)."""
    spec = spec.validate()
    t0 = time.time()
    pop = population if population is not None else \
        get_epidemic(spec.dataset).build()
    batch = spec.build_batch()
    B = len(batch)
    engine = _resolve_engine(spec, B)
    observables = obs_lib.make_observables(spec.observables)
    ctx = obs_lib.ObsContext(
        num_people=pop.num_people, num_scenarios=B,
        sweep_axes=_sweep_axes(spec, B),
    )

    # Pinned one-scenario-at-a-time layouts run sequentially: lowest
    # memory footprint; cross-scenario reductions replay post-run
    # (pure => bitwise).
    in_scan = not (engine in ("single", "dist") and B > 1)
    built = {}  # the most recently constructed core (provenance below)

    def make_driver(workers=None):
        """(Re)build the chunk driver — ``workers`` overrides the mesh
        width, the elastic-degradation / repartition rebuild seam."""
        t = time.time()
        s = spec
        if workers is not None and workers != spec.mesh.workers:
            s = dataclasses.replace(
                spec, mesh=dataclasses.replace(spec.mesh, workers=workers))
        core = _make_core(engine, s, pop, batch)
        built["core"] = core
        built["build_s"] = built.get("build_s", 0.0) + time.time() - t
        if not in_scan:
            return engine_lib.SequentialDriver(core)
        return engine_lib.CoreDriver(core, observables)

    ck = spec.checkpoint
    mgr = CheckpointManager(ck.directory, keep=ck.keep) if ck.directory else None
    rs = spec.resilience
    resilient = rs.enabled or chaos is not None
    report = None

    t_run = time.time()
    if resilient:
        if mgr is None:
            raise ValueError(
                "the resilient path (resilience.enabled or chaos injection) "
                "needs checkpoint.directory — recovery restores from "
                "snapshots")
        policy = resilience_lib.ResiliencePolicy(
            max_restarts=rs.max_restarts, backoff_s=rs.backoff_s,
            guards=rs.guards, elastic=rs.elastic,
            straggler_window=rs.straggler_window,
            straggler_factor=rs.straggler_factor,
            repartition_on_straggler=rs.repartition_on_straggler,
        )
        state, hist, carries, dailies, resumed_from, num_chunks, report = \
            resilience_lib.run_resilient(
                make_driver, spec.days, observables, ctx,
                manager=mgr, every=ck.every, resume=ck.resume,
                resume_key=_resume_key(spec, engine),
                policy=policy, chaos=chaos, on_straggler=on_straggler,
            )
    else:
        state, hist, carries, dailies, resumed_from, num_chunks = \
            engine_lib.run_chunked(
                make_driver(None), spec.days, observables, ctx,
                manager=mgr, every=ck.every, resume=ck.resume,
                resume_key=_resume_key(spec, engine),
            )
    run_wall = time.time() - t_run
    core = built["core"]

    # --- observables ----------------------------------------------------
    if in_scan:
        obs = obs_lib.finalize_all(observables, carries, dailies, ctx)
    else:
        obs = obs_lib.observe_history(observables, hist, ctx)
    obs = obs_lib.observables_to_numpy(obs)

    # Padded batch slots are inert no-ops inside the core and must never
    # surface: every history column corresponds to a real scenario.
    assert all(v.shape[1] == B for v in hist.values()), \
        "engine core leaked padded scenario slots into the history"

    summaries = summarize_sweep(hist, batch.names, pop.num_people)
    wall = time.time() - t0
    provenance = {
        "engine": engine,
        "layout": core.layout,
        "topology": type(core.topo).__name__,
        "num_people": int(pop.num_people),
        "mesh": {"workers": spec.mesh.workers,
                 "scenarios": spec.mesh.scenarios},
        "num_devices": len(jax.devices()),
        "jax_backend": jax.default_backend(),
        "wall_s": round(wall, 3),  # end to end, incl. pop build + compile
        "run_wall_s": round(run_wall, 3),  # the day-chunk loop only
        # engine build (week schedules, params, device transfer), a part
        # of run_wall_s
        "engine_build_s": built["build_s"],
        "chunks": num_chunks,
        "chunk_days": ck.every if mgr is not None else spec.days,
        "resumed_from_day": resumed_from,
        "observables_in_scan": in_scan,
        "core": engine_lib.CORE_VERSION,
    }
    if report is not None:
        # What recovery actually did: restarts, chunks replayed, snapshots
        # quarantined, straggler/device-loss events, final layout.
        provenance["resilience"] = report.to_dict()
    # Measured TEPS: the observables' (deterministic, bitwise-tested) edge
    # total over the measured scan wall clock. The rate mixes in host time,
    # so it lives with the other wall-clock facts here — not in the pure
    # observable outputs.
    if "teps" in obs:
        provenance["edges_total"] = float(obs["teps"]["edges_total"])
        provenance["teps"] = float(obs["teps"]["edges_total"]) / max(
            run_wall, 1e-9
        )
    return RunResult(
        spec=spec,
        scenario_names=batch.names,
        history=hist,
        observables=obs,
        summaries=summaries,
        provenance=provenance,
    )


def run_file(path: str, **overrides) -> RunResult:
    """Load a JSON/TOML spec from disk (``--spec`` path) and run it."""
    return run(ExperimentSpec.from_file(path).with_overrides(**overrides))
