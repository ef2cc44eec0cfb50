"""The single-device day loop (Algorithm 2): reference semantics.

Single-program, fixed-shape formulation of the paper's parallel control
flow: one jitted ``day_step`` handles any day (the weekly schedule is
stacked on a leading day-of-week axis), and a whole run is a ``lax.scan``
over days. Distribution over a device mesh is in
:mod:`repro.core.simulator_dist`; this module is the single-device
reference (bitwise identical by construction — all stochastic draws are
counter-based, see core/rng.py).

Execution lives in :mod:`repro.engine` — one topology-parameterized scan
serving every layout (``EngineCore.single(...).run1(...)`` is the
single-scenario front door; ``repro.api.run()`` the spec-driven one). The
pure functions here (``day_step``, ``run_scan``, ``phase_*``) remain the
*reference semantics* the engine core is pinned against bitwise
(tests/test_engine.py).

The day step is factored into pure functions of ``(static, week,
contact_prob, params, state)``:

  * ``SimStatic`` — trace-time structure (shapes, kernel backend, the
    intervention slot layout). Identical across a scenario ensemble.
  * ``SimParams`` — every scenario-varying numeric (seed, transmissibility,
    disease tables, per-person betas, intervention thresholds/masks,
    outbreak-seeding knobs) as device arrays. Because *values* live in this
    pytree rather than in closed-over Python attributes, ``day_step`` is
    vmappable over a leading batch axis — the engine core runs B scenarios
    in one ``lax.scan`` by stacking ``SimParams``/``SimState`` and
    vmapping, exactly the way the weekly schedule is stacked on a
    day-of-week axis here.

Phases per day (matching the paper's phase breakdown, Fig 7):
  1. *visits*    — intervention masks + per-visit person-value gather
                   (distributed: the visit-message all_to_all),
  2. *interact*  — block-scheduled interaction kernel + exposure combine
                   (distributed: exposure all_to_all),
  3. *update*    — infection sampling + FSA update + trigger evaluation.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import disease as disease_lib
from repro.core import interactions as inter_lib
from repro.core import interventions as iv_lib
from repro.core import population as pop_lib
from repro.core import rng
from repro.core import transmission as tx_lib


# History keys every engine's day step emits, in emission order. The
# distributed engine and the api facade key their stat pytrees on this.
# "edges" is the traversed-edge count (the TEPS numerator): numerically
# equal to "contacts", but measured *inside* the Pallas kernel on the
# pallas-compact backend and derived host-side everywhere else — keeping
# both makes the kernel counter a cross-checked quantity. "live_tiles"
# counts the tiles with work today (the ``live_tiles`` predicate every
# backend shares): the count the Pallas launchers compact to. Each worker
# tiles its own shard, so it follows the number of workers.
STAT_KEYS = ("day", "new_infections", "cumulative", "infectious",
             "susceptible", "contacts", "edges", "live_tiles",
             # Per-agent intervention telemetry (PR 7): constant zero when
             # no TestTraceIsolate slot exists (the reference path below
             # emits the zeros; the unified engine computes them).
             "tests_used", "isolated", "traced")

#: dtype of the daily "contacts"/"edges"/"live_tiles" counts. They are
#: non-negative, so unsigned 32 bits give twice int32's range with or
#: without x64: md's daily count is below 10^9 (visits x at most ~40
#: contacts per visit) and wraps at 2^32. Totals over days are summed
#: exactly by TEPS.
COUNT_DTYPE = jnp.uint32


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SimState:
    day: jnp.ndarray  # scalar int32
    health: jnp.ndarray  # (P,) int32 FSA state
    dwell: jnp.ndarray  # (P,) f32 days left in state
    cumulative: jnp.ndarray  # scalar int32 — infections so far (incl. seeds)
    iv_active: jnp.ndarray  # (K,) bool
    vaccinated: jnp.ndarray  # (P,) bool
    # --- persistent per-agent intervention state (PR 7) -----------------
    tested: jnp.ndarray  # (P,) bool — ever consumed a test
    traced: jnp.ndarray  # (P,) bool — ever traced as a contact of a positive
    isolated_until: jnp.ndarray  # (P,) int32 — isolation active while day <


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SimParams:
    """All scenario-varying numerics of a run, as device arrays.

    One scenario is a pytree of scalars/tables; a B-scenario ensemble is
    the same pytree with every leaf stacked on a leading batch axis
    (see :func:`repro.engine.core.stack_params`).
    """

    seed: jnp.ndarray  # () uint32 — Monte Carlo replicate stream
    tau_eff: jnp.ndarray  # () f32 — tau * time_unit (Eq. 2 prefactor)
    sus_table: jnp.ndarray  # (S,) f32 sigma(X)
    inf_table: jnp.ndarray  # (S,) f32 iota(X)
    sym_table: jnp.ndarray  # (S,) f32 — symptomatic states (test priority)
    cum_trans: jnp.ndarray  # (S, S) f32 cumulative transition rows
    dwell_mean: jnp.ndarray  # (S,) f32
    entry_state: jnp.ndarray  # () int32 — state entered on infection
    beta_sus: jnp.ndarray  # (P,) f32 person beta_sigma
    beta_inf: jnp.ndarray  # (P,) f32 person beta_iota
    seed_per_day: jnp.ndarray  # () int32 outbreak seeding intensity
    seed_days: jnp.ndarray  # () int32 outbreak seeding duration
    static_network: jnp.ndarray  # () bool — EpiHiper-style fixed weekly net
    iv: iv_lib.IvParams  # stacked intervention numerics


@dataclasses.dataclass(frozen=True)
class SimStatic:
    """Trace-time structure shared by every scenario in a batch."""

    num_people: int
    num_locations: int
    iv_slots: tuple  # tuple[iv_lib.IvSlotStatic, ...]
    backend: str = "jnp"


def build_params(
    pop: pop_lib.Population,
    disease: disease_lib.DiseaseModel,
    tm: tx_lib.TransmissionModel,
    interventions: Sequence[iv_lib.Intervention],
    seed: int,
    *,
    seed_per_day: int = 10,
    seed_days: int = 7,
    static_network: bool = False,
    iv_enabled: Sequence[bool] = (),
) -> tuple[tuple, tuple, SimParams]:
    """Compile one scenario's configs into
    (classic iv slot structure, per-agent slot structure, SimParams).

    ``iv_enabled`` (empty = all on) disables intervention slots without
    changing the slot structure — the mechanism scenario ensembles use to
    share one trace-time layout across design cells. It is positional over
    the *original* mixed intervention list; entries are routed to the
    matching family here.
    """
    iv_slots, pa_slots, iv_params = iv_lib.compile_iv_params(
        interventions, pop, seed
    )
    if len(iv_enabled):
        assert len(iv_enabled) == len(iv_slots) + len(pa_slots), \
            "iv_enabled/slot mismatch"
        en = np.asarray(iv_enabled, np.bool_)
        is_pa = np.asarray(
            [isinstance(iv, iv_lib.TestTraceIsolate) for iv in interventions],
            np.bool_,
        )
        iv_params = dataclasses.replace(
            iv_params,
            enabled=jnp.asarray(en[~is_pa]),
            pa_enabled=jnp.asarray(en[is_pa]),
        )
    params = SimParams(
        seed=jnp.asarray(np.uint32(seed & 0xFFFFFFFF)),
        tau_eff=jnp.asarray(np.float32(tm.tau * tm.time_unit)),
        sus_table=jnp.asarray(disease.susceptibility),
        inf_table=jnp.asarray(disease.infectivity),
        sym_table=jnp.asarray(disease.sym_table),
        cum_trans=jnp.asarray(disease.cum_trans),
        dwell_mean=jnp.asarray(disease.dwell_mean_days),
        entry_state=jnp.asarray(disease.entry_state, jnp.int32),
        beta_sus=jnp.asarray(pop.beta_sus, jnp.float32),
        beta_inf=jnp.asarray(pop.beta_inf, jnp.float32),
        seed_per_day=jnp.asarray(seed_per_day, jnp.int32),
        seed_days=jnp.asarray(seed_days, jnp.int32),
        static_network=jnp.asarray(static_network, bool),
        iv=iv_params,
    )
    return iv_slots, pa_slots, params


# --------------------------------------------------------------------------
# Pure per-day phases (vmappable over a leading batch axis of params/state)
# --------------------------------------------------------------------------


def phase_visits(static: SimStatic, params: SimParams, state: SimState):
    """Phase 1: intervention masks + per-person epidemiological values."""
    visit_ok, loc_open, sus_mult, inf_mult, vaccinated = iv_lib.apply_iv_params(
        static.iv_slots,
        params.iv,
        state.iv_active,
        state.vaccinated,
        static.num_people,
        static.num_locations,
    )
    person_sus = params.sus_table[state.health] * params.beta_sus * sus_mult
    person_inf = params.inf_table[state.health] * params.beta_inf * inf_mult
    return visit_ok, loc_open, person_sus, person_inf, vaccinated


def phase_interact(
    static, week, contact_prob, params, state, visit_ok, loc_open,
    person_sus, person_inf,
):
    """Phase 2: block-scheduled interactions + exposure combine."""
    dow = state.day % pop_lib.DAYS_PER_WEEK
    contact_day = jnp.where(
        params.static_network, dow, state.day
    )  # static net: draws keyed by day-of-week => identical every week
    return inter_lib.day_exposure(
        week,
        dow,
        static.num_people,
        person_sus,
        person_inf,
        contact_prob,
        visit_ok,
        loc_open,
        params.tau_eff,
        params.seed,
        contact_day,
        backend=static.backend,
    )


def phase_update(static, params, state, A, contacts, live, vaccinated):
    """Phase 3: infection sampling, seeding, FSA update, triggers."""
    infected = tx_lib.sample_infections(A, params.seed, state.day)

    def with_seeding(h_d):
        h, d = h_d
        pid = jnp.arange(static.num_people, dtype=jnp.uint32)
        u = rng.uniform(params.seed, rng.SEED_CHOICE, state.day, pid)
        sus = params.sus_table[h] > 0.0
        u = jnp.where(sus, u, 2.0)
        k = jnp.minimum(params.seed_per_day, static.num_people) - 1
        thresh = jnp.sort(u)[jnp.maximum(k, 0)]
        return (u <= thresh) & sus & (params.seed_per_day > 0)

    seeded = jax.lax.cond(
        state.day < params.seed_days,
        with_seeding,
        lambda _: jnp.zeros((static.num_people,), bool),
        (state.health, state.dwell),
    )
    can_infect = params.sus_table[state.health] > 0.0
    new_mask = (infected | seeded) & can_infect
    health, dwell = disease_lib.update_health_tables(
        params.cum_trans,
        params.dwell_mean,
        params.sus_table,
        params.entry_state,
        state.health,
        state.dwell,
        new_mask,
        params.seed,
        state.day,
    )
    new_count = new_mask.sum().astype(jnp.int32)
    cumulative = state.cumulative + new_count
    infectious = (params.inf_table[health] > 0.0).sum().astype(jnp.int32)
    stats = {
        "day": state.day,
        "new_infections": new_count,
        "cumulative": cumulative,
        "infectious": infectious,
        "susceptible": (params.sus_table[health] > 0.0).sum().astype(jnp.int32),
        "contacts": contacts.astype(COUNT_DTYPE),
        # Host-side traversed edges; the unified engine substitutes the
        # in-kernel counter on the pallas-compact backend.
        "edges": contacts.astype(COUNT_DTYPE),
        "live_tiles": live,
        # The reference path carries no per-agent interventions; the stats
        # are constant zeros (the engine must match them bitwise whenever
        # no TestTraceIsolate slot is configured).
        "tests_used": jnp.zeros((), jnp.int32),
        "isolated": jnp.zeros((), jnp.int32),
        "traced": jnp.zeros((), jnp.int32),
    }
    iv_active = iv_lib.evaluate_iv_triggers(
        static.iv_slots, params.iv, state.day, stats, state.iv_active
    )
    new_state = SimState(
        day=state.day + 1,
        health=health,
        dwell=dwell,
        cumulative=cumulative,
        iv_active=iv_active,
        vaccinated=vaccinated,
        tested=state.tested,
        traced=state.traced,
        isolated_until=state.isolated_until,
    )
    return new_state, stats


def day_step(static, week, contact_prob, params: SimParams, state: SimState):
    """One simulated day; pure in (params, state) given static structure."""
    visit_ok, loc_open, person_sus, person_inf, vaccinated = phase_visits(
        static, params, state
    )
    A, contacts, live = phase_interact(
        static, week, contact_prob, params, state,
        visit_ok, loc_open, person_sus, person_inf,
    )
    return phase_update(static, params, state, A, contacts, live, vaccinated)


def run_scan(static, week, contact_prob, params, state, days: int):
    """A whole run as one lax.scan over :func:`day_step`."""

    def body(s, _):
        return day_step(static, week, contact_prob, params, s)

    return jax.lax.scan(body, state, None, length=days)


def init_state(
    disease: disease_lib.DiseaseModel, num_people: int, num_iv_slots: int
) -> SimState:
    health, dwell = disease_lib.initial_health(disease, num_people)
    return SimState(
        day=jnp.asarray(0, jnp.int32),
        health=health,
        dwell=dwell,
        cumulative=jnp.asarray(0, jnp.int32),
        iv_active=jnp.zeros((num_iv_slots,), bool),
        vaccinated=jnp.zeros((num_people,), bool),
        tested=jnp.zeros((num_people,), bool),
        traced=jnp.zeros((num_people,), bool),
        isolated_until=jnp.zeros((num_people,), jnp.int32),
    )


def legacy_parts(core):
    """(static, week, contact_prob, params) for the legacy pure functions,
    extracted from a B=1 ``layout="local"`` EngineCore.

    This is the bridge between the unified engine (which owns population
    compilation) and the reference semantics in this module: parity tests
    drive ``day_step``/``phase_*`` with exactly the arrays the engine scans
    over."""
    from repro.engine.core import index_params  # cycle-free at call time

    assert core.layout == "local" and core.num_real == 1, \
        "legacy_parts() needs a B=1 local EngineCore"
    params = index_params(core.params, 0)
    static = SimStatic(
        num_people=core.pop.num_people,
        num_locations=core.pop.num_locations,
        iv_slots=core.iv_slots,
        backend=core.backend,
    )
    return static, core.week_data, jnp.asarray(core.pop.contact_prob), params


def attack_rate(hist) -> float:
    return float(hist["cumulative"][-1])
