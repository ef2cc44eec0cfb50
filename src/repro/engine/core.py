"""EngineCore: place the one day loop on a topology, batch it, chunk it.

``repro.engine.day.run_days`` is the single scan every layout executes;
this module owns everything around it:

  * **building** — one shared path compiles a ScenarioBatch into stacked
    ``SimParams``/``SimState`` pytrees (worker-padded when the people
    axis is sharded, scenario-padded with *no-op* params when the batch
    axis is sharded) plus the week/route device arrays the step consumes.
  * **placement** — the four layouts are four ``(topology, mesh)`` pairs;
    vmap is applied inside :func:`repro.engine.day.run_days` and
    shard_map is applied here, by composition, never per-layout loops.
  * **chunking** — :func:`run_chunked` is the day-chunked checkpoint /
    resume loop (moved here from repro.api.runner so every layout resumes
    bitwise, not just single + ensemble).

Scenario padding is *inert*: padded batch slots run with
:func:`no_op_params` (zero betas, zero seeding, every intervention slot
disabled), so no one is ever seeded or infected in a pad slot — under the
``compact`` interaction backend the live-tile count is 0 and the pad
column costs almost nothing. Padded slots are sliced off before any
history leaves the core.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional, Sequence, Union

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.sweep import Scenario, ScenarioBatch
from repro.engine.cache import BoundedLRU
from repro.core import interactions as inter_lib
from repro.core import interventions as iv_lib
from repro.core import population as pop_lib
from repro.core import simulator as sim_lib
from repro.core import simulator_dist as sd
from repro.engine import day as day_lib
from repro.engine.topology import Topology, make_topology

WORKER_AXIS = sd.AXIS  # "workers"
SCENARIO_AXIS = "scenarios"

LAYOUTS = ("local", "workers", "scenarios", "hybrid")

#: Engine-core generation marker; part of every checkpoint's resume key so
#: checkpoints written by incompatible engine generations are refused
#: rather than silently spliced into a trajectory. v2: history gained the
#: "edges" stat (in-kernel traversed-edge telemetry). v3: per-agent
#: interventions — SimState gained tested/traced/isolated_until and
#: history gained the "tests_used"/"isolated"/"traced" stats. v4: history
#: gained the "live_tiles" stat (tiles with work each day).
CORE_VERSION = "engine-v4"

_STATE_FIELDS = tuple(f.name for f in dataclasses.fields(sim_lib.SimState))

#: SimState fields with a (worker-padded) person axis — the leaves an
#: elastic repartition must re-pad when the worker count changes.
PERSON_STATE_FIELDS = ("health", "dwell", "vaccinated", "tested", "traced",
                       "isolated_until")


class ResumeKeyError(ValueError):
    """A checkpoint exists but must not be resumed from under this spec
    (incompatible science/engine generation, or beyond the run length).
    A config error, not a fault — the resilient loop never retries it."""


def state_to_tree(state: sim_lib.SimState) -> dict:
    """SimState -> plain dict (stable checkpoint key paths)."""
    return {f: getattr(state, f) for f in _STATE_FIELDS}


def state_from_flat(flat: dict) -> sim_lib.SimState:
    return sim_lib.SimState(**{f: flat[f"state/{f}"] for f in _STATE_FIELDS})


# ---------------------------------------------------------------------------
# batch compilation (the one copy of the slot-structure loop)
# ---------------------------------------------------------------------------


def as_batch(batch: Union[ScenarioBatch, Sequence[Scenario]]) -> ScenarioBatch:
    if isinstance(batch, ScenarioBatch):
        return batch
    return ScenarioBatch.from_scenarios(tuple(batch))


def build_batch_params(pop, batch: ScenarioBatch):
    """Compile every scenario's configs into
    ``(iv_slots, pa_slots, [SimParams, ...])``, validating that the batch
    shares one trace-time slot structure (both intervention families)."""
    slots0, pa0, params_list = None, None, []
    for s in batch:
        slots, pa_slots, params = sim_lib.build_params(
            pop, s.disease, s.tm, s.interventions, s.seed,
            seed_per_day=s.seed_per_day, seed_days=s.seed_days,
            static_network=s.static_network, iv_enabled=s.iv_enabled,
        )
        if slots0 is None:
            slots0, pa0 = slots, pa_slots
        elif slots != slots0 or pa_slots != pa0:
            raise ValueError(
                f"scenario '{s.name}' intervention structure "
                f"{slots + pa_slots} differs from batch structure "
                f"{slots0 + pa0}; ensembles vary thresholds/factors/"
                "enabled, not slot kinds"
            )
        params_list.append(params)
    return slots0, pa0, params_list


def no_op_params(params: sim_lib.SimParams) -> sim_lib.SimParams:
    """An epidemiologically inert SimParams with the same structure:
    zero betas, zero outbreak seeding, every intervention slot disabled.
    A scenario run with these never seeds or infects anyone — the filler
    for padded batch slots."""
    return dataclasses.replace(
        params,
        beta_sus=jnp.zeros_like(params.beta_sus),
        beta_inf=jnp.zeros_like(params.beta_inf),
        seed_per_day=jnp.zeros_like(params.seed_per_day),
        seed_days=jnp.zeros_like(params.seed_days),
        iv=dataclasses.replace(
            params.iv,
            enabled=jnp.zeros_like(params.iv.enabled),
            pa_enabled=jnp.zeros_like(params.iv.pa_enabled),
        ),
    )


def pad_batch(batch: ScenarioBatch, multiple: int) -> ScenarioBatch:
    """Pad a batch to a multiple of the scenario-axis size by repeating
    the final scenario under ``__pad`` names. The *params* of pad slots
    are replaced by :func:`no_op_params` at build time — the repeated
    scenario only supplies trace-time structure."""
    B = len(batch)
    pad = (-B) % multiple
    if pad == 0:
        return batch
    filler = tuple(
        dataclasses.replace(batch[-1], name=f"__pad{i}") for i in range(pad)
    )
    return ScenarioBatch(scenarios=batch.scenarios + filler)


def local_week_arrays(pop, week: inter_lib.WeekData) -> dict:
    """The unified step's ``week`` dict for the unsharded layout: the
    stacked (7, ...) schedule plus per-visit contact probabilities
    gathered once (location attributes are static)."""
    contact_prob = jnp.asarray(pop.contact_prob)
    return {
        "pid": week.pid,
        "loc": week.loc,
        "start": week.start,
        "end": week.end,
        "p": contact_prob[week.loc],
        "row": week.row_idx,
        "col": week.col_idx,
        "rs": week.row_start,
        "pa": week.pair_active,
    }


# ---------------------------------------------------------------------------
# the core
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class EngineCore:
    """One ScenarioBatch placed on one topology, ready to scan.

    ``layout`` picks the placement:

      * ``"local"`` — no mesh; B scenarios vmapped (single runs are B=1).
      * ``"workers"`` — 1-D mesh, people/locations sharded per scenario.
      * ``"scenarios"`` — 1-D mesh, the batch axis sharded.
      * ``"hybrid"`` — 2-D (workers × scenarios) mesh, both.

    All placements execute the identical :func:`repro.engine.day.run_days`
    scan; per-scenario trajectories are bitwise-equal across layouts.
    """

    pop: pop_lib.Population
    batch: Union[ScenarioBatch, Sequence[Scenario]]
    layout: str = "local"
    mesh: Optional[Mesh] = None
    workers: int = 1
    scen_shards: int = 1
    backend: str = "jnp"
    block_size: int = 128
    balanced: bool = True
    pack_visits: bool = True
    max_seed_per_day: Optional[int] = None
    #: Max compiled runners held per core (one per ``(days, observables)``
    #: key), LRU-evicted beyond it. The serve tier's bucket table shares
    #: the same :class:`repro.engine.cache.BoundedLRU` policy. ``None`` =
    #: unbounded (the pre-PR behavior).
    max_runners: Optional[int] = 8

    def __post_init__(self):
        if self.layout not in LAYOUTS:
            raise ValueError(f"layout must be one of {LAYOUTS}, "
                             f"got '{self.layout}'")
        self.batch = as_batch(self.batch)
        self.num_real = len(self.batch)
        self._resolve_mesh()
        self.topo: Topology = make_topology(
            WORKER_AXIS if self._worker_sharded else None,
            SCENARIO_AXIS if self._scen_sharded else None,
        )
        self.padded = pad_batch(self.batch, self.scen_shards)

        self.iv_slots, self.pa_slots, params_list = build_batch_params(
            self.pop, self.padded
        )
        num_slots = len(self.iv_slots)

        if self._worker_sharded:
            self.plan = sd.build_dist_plan(
                self.pop, self.workers, self.block_size, self.balanced,
                pack=self.pack_visits,
            )
            self.week, self.route = sd.week_device_arrays(self.plan)
            self.week_data = None
            params_list = [sd.pad_params(p, self.plan) for p in params_list]
            people_per_worker = self.plan.people_per_worker
            visits_per_worker = self.plan.visits_per_worker
            self._init_one = lambda s: sd.dist_init_state(
                s.disease, self.plan, num_slots
            )
        else:
            self.plan = None
            self.week_data = inter_lib.build_week_data(
                self.pop, self.block_size, pack=self.pack_visits
            )
            self.week = local_week_arrays(self.pop, self.week_data)
            self.route = None
            people_per_worker = self.pop.num_people
            visits_per_worker = self.week_data.visits_per_day
            self._init_one = lambda s: sim_lib.init_state(
                s.disease, self.pop.num_people, num_slots
            )

        # Pad slots carry inert params: nothing is seeded or infected
        # there, so the compact backend's live-tile count stays 0.
        for i in range(self.num_real, len(self.padded)):
            params_list[i] = no_op_params(params_list[i])
        self.params = stack_params(params_list)

        max_spd = (self.max_seed_per_day
                   if self.max_seed_per_day is not None
                   else max(s.seed_per_day for s in self.padded))
        # Static top-k width for the testing budget's order statistic:
        # the largest daily capacity any scenario asks for, clamped to the
        # shard width (MeshTopology.rank_threshold is exact as long as
        # test_topk >= min(budget, people_per_worker)).
        max_tests = max(
            [iv.tests_per_day for s in self.padded
             for iv in s.interventions
             if isinstance(iv, iv_lib.TestTraceIsolate)] or [1]
        )
        self.static = day_lib.EngineStatic(
            num_people=self.pop.num_people,
            num_locations=self.pop.num_locations,
            people_per_worker=people_per_worker,
            visits_per_worker=visits_per_worker,
            block_size=self.block_size,
            seed_topk=max(1, min(int(max_spd), people_per_worker)),
            iv_slots=self.iv_slots,
            backend=self.backend,
            pa_slots=self.pa_slots,
            test_topk=max(1, min(int(max_tests), people_per_worker)),
        )
        self._specs = self._build_specs()
        self._runners = BoundedLRU(max_entries=self.max_runners)

    # ------------------------------------------------------------------
    def _resolve_mesh(self):
        from repro.launch import mesh as mesh_lib  # jax-device-state free

        self._worker_sharded = self.layout in ("workers", "hybrid")
        self._scen_sharded = self.layout in ("scenarios", "hybrid")
        if self.layout == "local":
            self.mesh = None
            self.workers, self.scen_shards = 1, 1
            return
        if self.mesh is None:
            if self.layout == "workers":
                self.mesh = mesh_lib.make_worker_mesh(self.workers)
            elif self.layout == "scenarios":
                self.mesh = mesh_lib.make_scenario_mesh(self.scen_shards)
            else:
                self.mesh = mesh_lib.make_hybrid_mesh(
                    self.workers, self.scen_shards
                )
        expect = {
            "workers": (WORKER_AXIS,),
            "scenarios": (SCENARIO_AXIS,),
            "hybrid": (WORKER_AXIS, SCENARIO_AXIS),
        }[self.layout]
        if self.mesh.axis_names != expect:
            raise ValueError(
                f"layout '{self.layout}' expects mesh axes {expect}, "
                f"got {self.mesh.axis_names}"
            )
        self.workers = (int(self.mesh.shape[WORKER_AXIS])
                        if self._worker_sharded else 1)
        self.scen_shards = (int(self.mesh.shape[SCENARIO_AXIS])
                            if self._scen_sharded else 1)

    def _build_specs(self):
        if self.mesh is None:
            return None
        batch = SCENARIO_AXIS if self._scen_sharded else None
        if self._worker_sharded:
            pbase = sd.dist_param_specs()
            sbase = sd.dist_state_specs()
            wspec = P(None, WORKER_AXIS)
        else:
            pbase = jax.tree.map(lambda _: P(), self.params)
            # SimState's structure is static — build the spec tree directly
            # rather than materializing a throwaway device state.
            sbase = sim_lib.SimState(
                day=P(), health=P(), dwell=P(), cumulative=P(),
                iv_active=P(), vaccinated=P(),
                tested=P(), traced=P(), isolated_until=P(),
            )
            wspec = P()
        prepend = lambda tree: jax.tree.map(lambda sp: P(batch, *sp), tree)
        pspec, sspec = prepend(pbase), prepend(sbase)
        hspec = P(None, SCENARIO_AXIS) if self._scen_sharded else P()
        return pspec, sspec, wspec, hspec

    # ------------------------------------------------------------------
    def init_state(self) -> sim_lib.SimState:
        """Stacked initial state over the padded batch (leading axis =
        scenarios; worker-padded person leaves when people are sharded)."""
        return stack_params([self._init_one(s) for s in self.padded])

    def scenario_params(self, i: int) -> sim_lib.SimParams:
        """Scenario ``i``'s un-stacked (possibly worker-padded) params."""
        return index_params(self.params, i)

    def adopt_state(self, state: sim_lib.SimState) -> sim_lib.SimState:
        """Re-home a stacked SimState (possibly from another worker
        layout) onto this core's person padding — the elastic-degradation
        seam: a checkpoint written on W workers continues on this core's
        worker count with the real people bitwise-preserved.

        Person leaves are repartitioned with
        :func:`repro.runtime.elastic.repartition_person_array` (real
        people occupy the first ``num_people`` flat slots in every
        layout — ``person_owner = arange // Pw``); pad entries are
        refilled from this core's :meth:`init_state` template (absorbing
        health, ``ABSORBING_DWELL``, cleared masks), so pad people stay
        epidemiologically inert. States already in this layout pass
        through untouched."""
        from repro.runtime.elastic import (
            plan_elastic_rescale, repartition_person_array,
        )

        tmpl = self.init_state()
        P = self.pop.num_people
        new_layout = plan_elastic_rescale(P, self.workers, self.workers)[1]
        ppad_new = new_layout["workers"] * new_layout["per_worker"]

        def adopt(name):
            old = np.asarray(jax.device_get(getattr(state, name)))
            t = np.asarray(jax.device_get(getattr(tmpl, name)))
            if name not in PERSON_STATE_FIELDS or old.shape == t.shape:
                return getattr(state, name)
            if old.ndim < 2 or old.shape[0] != t.shape[0]:
                raise ValueError(
                    f"adopt_state: cannot re-home leaf '{name}' of shape "
                    f"{old.shape} onto batch template {t.shape}")
            out = []
            for i in range(old.shape[0]):  # per scenario in the batch
                fill = t[i, -1] if ppad_new > P else 0
                out.append(repartition_person_array(
                    old[i], P, self.workers, fill=fill).reshape(-1))
            new = np.stack(out)
            assert new.shape == t.shape, (name, new.shape, t.shape)
            return jnp.asarray(new)

        return sim_lib.SimState(**{f: adopt(f) for f in _STATE_FIELDS})

    # ------------------------------------------------------------------
    def _runner(self, days: int, observables: tuple):
        key = (days, observables)
        cached = self._runners.get(key)
        if cached is not None:
            return cached
        topo, static, num_real = self.topo, self.static, self.num_real
        worker_sharded = self._worker_sharded

        def worker(params, state, carries, week, route):
            if worker_sharded:
                week = jax.tree.map(lambda a: a.squeeze(1), week)
                route = jax.tree.map(lambda a: a.squeeze(1), route)
            return day_lib.run_days(
                topo, static, route, week, params, state, days,
                observables, carries, num_real,
            )

        if self.mesh is None:
            runner = jax.jit(worker)
        else:
            pspec, sspec, wspec, hspec = self._specs
            runner = jax.jit(
                jax.shard_map(
                    worker,
                    mesh=self.mesh,
                    # carries/dailies ride replicated: every shard sees the
                    # full gathered stats, so their reductions are identical.
                    in_specs=(pspec, sspec, P(), wspec, wspec),
                    out_specs=(sspec, P(), hspec, P()),
                    check_vma=False,
                )
            )
        self._runners.put(key, runner)
        return runner

    # ------------------------------------------------------------------
    # runner-cache introspection (the serve tier's compile-once seam)
    # ------------------------------------------------------------------

    def runner_fn(self, days: int, observables: tuple = ()):
        """The compiled runner for ``(days, observables)`` — built (and
        cached) on first request. Public so the serving tier can wrap the
        steady-state loop in :class:`repro.analysis.hlo.recompile_sentinel`
        around the *actual* jitted callable, not a re-wrapped copy."""
        return self._runner(days, tuple(observables))

    def runner_cached(self, days: int, observables: tuple = ()) -> bool:
        """Whether the ``(days, observables)`` runner is already resident
        (no recency bump, no stats churn) — the warm/cold probe."""
        return self._runners.peek((days, tuple(observables))) is not None

    def runner_cache_stats(self) -> dict:
        """Size/budget and lifetime hit/miss/eviction counters of the
        per-core runner cache (see :class:`repro.engine.cache.BoundedLRU`)."""
        return self._runners.stats()

    def bench_fn(self, days: int, observables: tuple = ()):
        """A zero-argument timed callable for benchmarks: runs the whole
        compiled scan and returns a device scalar (no host transfer of
        the history), so ``block_until_ready``-style timers measure the
        program, not the gather."""
        runner = self._runner(days, tuple(observables))
        params, state = self.params, self.init_state()
        week, route = self.week, self.route
        carries = ()
        if observables:
            from repro.api import observables as obs_lib

            carries = obs_lib.init_carries(
                tuple(observables),
                obs_lib.ObsContext(num_people=self.pop.num_people,
                                   num_scenarios=self.num_real),
            )
        return lambda: runner(params, state, carries, week, route)[0].day

    def run_days(
        self,
        days: int,
        *,
        params: Optional[sim_lib.SimParams] = None,
        state: Optional[sim_lib.SimState] = None,
        observables: tuple = (),
        carries: tuple = (),
    ):
        """Run ``days`` days as one jitted scan on this core's topology.

        Returns ``(final_state, carries, hist, dailies)``: ``hist`` maps
        STAT_KEYS to host ``(days, B_real)`` arrays (padded slots sliced
        off — they never leave the core), ``carries``/``dailies`` are the
        threaded observable reductions (device carries, host dailies).
        ``params`` substitutes other same-structure params (it is a traced
        argument — one compiled program serves any same-shape batch).
        """
        params = params if params is not None else self.params
        state = state if state is not None else self.init_state()
        runner = self._runner(days, tuple(observables))
        state, carries, hist, dailies = runner(
            params, state, carries, self.week, self.route
        )
        hist = {
            k: np.asarray(v)[:, : self.num_real]
            for k, v in jax.device_get(hist).items()
        }
        return state, carries, hist, jax.device_get(dailies)

    # ------------------------------------------------------------------
    # convenience front doors (what the removed legacy engine classes
    # exposed; repro.api.run() remains the spec-driven entry point)
    # ------------------------------------------------------------------

    @classmethod
    def single(
        cls,
        pop: pop_lib.Population,
        disease,
        tm=None,
        *,
        interventions: Sequence = (),
        iv_enabled: Sequence = (),
        seed: int = 0,
        seed_per_day: int = 10,
        seed_days: int = 7,
        static_network: bool = False,
        name: str = "single",
        **core_kw,
    ) -> "EngineCore":
        """A one-scenario core — the single-run construction in one call. ``core_kw`` passes
        through the placement fields (``layout``, ``mesh``, ``workers``,
        ``backend``, ``block_size``, ``balanced``, ``pack_visits``,
        ``max_seed_per_day``); pair with :meth:`run1` for unbatched
        results."""
        from repro.core import transmission as tx_lib  # cycle-free late

        scen = Scenario(
            name=name, disease=disease,
            tm=tm if tm is not None else tx_lib.TransmissionModel(),
            interventions=tuple(interventions),
            iv_enabled=tuple(iv_enabled), seed=seed,
            seed_per_day=seed_per_day, seed_days=seed_days,
            static_network=static_network,
        )
        return cls(pop, [scen], **core_kw)

    def run(
        self,
        days: int,
        *,
        state: Optional[sim_lib.SimState] = None,
        params: Optional[sim_lib.SimParams] = None,
    ):
        """``(final_state, hist)`` over the batch — the legacy ensemble
        ``.run`` contract. ``hist`` arrays are ``(days, B_real)``; pad
        slots are dropped from the final state too (feed states back
        through :meth:`run_days` instead when day-chunking a padded
        batch)."""
        final, _, hist, _ = self.run_days(days, state=state, params=params)
        final = jax.tree.map(lambda x: x[: self.num_real], final)
        return final, hist

    def run1(
        self,
        days: int,
        *,
        state: Optional[sim_lib.SimState] = None,
        params: Optional[sim_lib.SimParams] = None,
    ):
        """B=1 convenience: :meth:`run` with the scenario axis squeezed —
        the legacy single-scenario ``.run`` contract. Accepts and returns
        *unbatched* state/params; ``hist`` arrays are ``(days,)``.

        ``params`` substitutes another scenario's :class:`SimParams`
        (same trace-time structure) without recompiling — params is a
        traced argument of the compiled scan, so one program serves a
        scenario batch run sequentially."""
        assert self.num_real == 1, "run1() needs a batch of exactly 1"
        add_b = lambda t: (
            None if t is None else jax.tree.map(lambda x: x[None], t)
        )
        final, _, hist, _ = self.run_days(
            days, state=add_b(state), params=add_b(params)
        )
        final = jax.tree.map(lambda x: x[0], final)
        return final, {k: v[:, 0] for k, v in hist.items()}

    def init_state1(self) -> sim_lib.SimState:
        """Unbatched initial state (B=1 cores; pairs with :meth:`run1`)."""
        assert self.num_real == 1, "init_state1() needs a batch of exactly 1"
        return index_params(self.init_state(), 0)


# ---------------------------------------------------------------------------
# stacked-pytree helpers
# ---------------------------------------------------------------------------


def stack_params(params_list: Sequence) -> object:
    """Stack identically-structured pytrees on a new leading batch axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *params_list)


def index_params(batched, i: int):
    """Slice scenario ``i`` back out of a stacked pytree (inverse of
    :func:`stack_params`)."""
    return jax.tree.map(lambda x: x[i], batched)


# ---------------------------------------------------------------------------
# the day-chunked checkpoint/resume loop (engine-level: all layouts)
# ---------------------------------------------------------------------------


def concat_hists(hists: list) -> dict:
    return {k: np.concatenate([h[k] for h in hists], axis=0)
            for k in hists[0]}


def concat_dailies(chunks: list):
    return jax.tree.map(lambda *xs: np.concatenate(xs, axis=0), *chunks)


def run_chunked(
    driver,
    days: int,
    observables: tuple,
    ctx,
    *,
    manager=None,
    every: int = 50,
    resume: bool = True,
    resume_key: Optional[dict] = None,
    hooks=None,
):
    """Scan ``every``-day chunks through ``driver``, checkpointing state +
    history-so-far at each boundary and resuming bitwise from the latest
    compatible checkpoint.

    ``driver`` is the minimal chunk surface: ``init_state()``,
    ``run_chunk(n, state, carries) -> (state, hist, carries, dailies)``,
    and an ``in_scan`` flag (False only for the sequential
    one-scenario-at-a-time facade, whose cross-scenario reductions replay
    post-run). Observable carries are never checkpointed: on resume the
    pure updates replay over the restored history, reconstructing them
    exactly (see repro.api.observables).

    Resume picks the newest snapshot that passes integrity verification —
    corrupt/truncated snapshots are quarantined by the checkpoint manager
    and the next-older valid step is used. If the driver exposes
    ``adapt_state`` (the engine drivers do), the restored state is passed
    through it, so a snapshot written under another worker layout
    continues on this one (elastic degradation).

    ``hooks`` (optional; see :mod:`repro.runtime.resilience`) observes the
    loop at chunk granularity: ``on_start(state, day)``,
    ``before_chunk(day, n)``, ``after_chunk(end_day, state, dt) -> state``
    (called *before* the boundary snapshot, so invariant guards can veto a
    poisoned state reaching disk), ``after_save(day)``. Hook exceptions
    propagate — they are the fault-injection and guard-violation surface.

    Returns ``(state, hist, carries, dailies, resumed_from, num_chunks)``.
    """
    from repro.api import observables as obs_lib  # cycle-free at call time

    state, carries, hists, daily_chunks = None, None, [], []
    day, resumed_from = 0, None
    step = manager.latest_valid_step() if manager is not None and resume \
        else None
    if step is not None:
        if step > days:
            raise ResumeKeyError(
                f"checkpoint at day {step} is beyond spec.days={days}")
        saved_key = manager.manifest(step).get("extra", {}).get("resume_key")
        if saved_key != resume_key:
            raise ResumeKeyError(
                f"checkpoint at day {step} in {manager.directory} was "
                + ("written by an incompatible spec or engine generation "
                   "(different parameters, sweep axes, mesh, or a "
                   "pre-refactor engine)" if saved_key is not None
                   else "not written by repro.api.run (no resume_key in "
                        "its manifest)")
                + "; refusing to splice trajectories — point "
                "checkpoint.directory elsewhere or set "
                "checkpoint.resume=false")
        flat = manager.restore_flat(step)
        state = state_from_flat(flat)
        if hasattr(driver, "adapt_state"):
            state = driver.adapt_state(state)
        hists = [{k: flat[f"hist/{k}"] for k in sim_lib.STAT_KEYS}]
        if driver.in_scan:
            # Replay the pure reductions over the restored history so the
            # carries continue exactly where the interrupted scan left off.
            carries, pre = obs_lib.scan_history(observables, hists[0], ctx)
            daily_chunks = [jax.device_get(pre)]
        day, resumed_from = step, step
    if state is None:
        state = driver.init_state()
    if carries is None and driver.in_scan:
        carries = obs_lib.init_carries(observables, ctx)
    if hooks is not None:
        hooks.on_start(state, day)

    chunk = every if manager is not None else days
    num_chunks = 0
    while day < days:
        n = min(chunk, days - day)
        t0 = time.perf_counter()
        if hooks is not None:
            hooks.before_chunk(day, n)
        state, hist, carries, dl = driver.run_chunk(n, state, carries)
        if hooks is not None:
            # May raise (guard veto of a poisoned state) — nothing below
            # runs, so the poison is never appended or checkpointed.
            state = hooks.after_chunk(day + n, state,
                                      time.perf_counter() - t0)
        hists.append(hist)
        if dl is not None:
            daily_chunks.append(dl)
        day += n
        num_chunks += 1
        if manager is not None:
            # Each boundary rewrites the full history-so-far: O(days^2)
            # bytes over a run, but history is ~6 scalars/scenario/day and
            # a self-contained latest checkpoint keeps restore trivial.
            manager.save(day, {
                "day": np.asarray(day, np.int32),
                "state": state_to_tree(state),
                "hist": concat_hists(hists),
            }, extra={"resume_key": resume_key})
            if hooks is not None:
                hooks.after_save(day)
    if manager is not None:
        manager.wait()

    hist = concat_hists(hists)
    dailies = concat_dailies(daily_chunks) if daily_chunks else None
    return state, hist, carries, dailies, resumed_from, num_chunks


# ---------------------------------------------------------------------------
# chunk drivers over the core
# ---------------------------------------------------------------------------


class CoreDriver:
    """One-program driver: the whole batch lives in one scan on one
    topology, so the observable updates run inside the scan body."""

    in_scan = True

    def __init__(self, core: EngineCore, observables: tuple):
        self.core = core
        self.observables = tuple(observables)

    def init_state(self):
        return self.core.init_state()

    def adapt_state(self, state):
        return self.core.adopt_state(state)

    def run_chunk(self, n, state, carries):
        state, carries, hist, dailies = self.core.run_days(
            n, state=state, observables=self.observables, carries=carries
        )
        return state, hist, carries, dailies


class SequentialDriver:
    """One scenario at a time through a B=1 slice of the core's program —
    the pinned single/dist layout with B > 1 (lowest memory footprint; one
    compiled scan serves the whole batch). Cross-scenario observables
    cannot live inside per-scenario scans, so reductions replay post-run
    (``in_scan = False``)."""

    in_scan = False

    def __init__(self, core: EngineCore):
        self.core = core
        self.params_list = [
            jax.tree.map(lambda x: x[i: i + 1], core.params)
            for i in range(core.num_real)
        ]

    def init_state(self):
        return self.core.init_state()

    def adapt_state(self, state):
        return self.core.adopt_state(state)

    def run_chunk(self, n, state, carries):
        finals, hists = [], []
        for i, params_i in enumerate(self.params_list):
            state_i = jax.tree.map(lambda x: x[i: i + 1], state)
            f, _, h, _ = self.core.run_days(n, params=params_i, state=state_i)
            finals.append(jax.tree.map(lambda x: x[0], f))
            hists.append({k: v[:, 0] for k, v in h.items()})
        state = stack_params(finals)
        hist = {k: np.stack([h[k] for h in hists], axis=1)
                for k in sim_lib.STAT_KEYS}
        return state, hist, carries, None
