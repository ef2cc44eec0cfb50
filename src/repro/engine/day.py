"""The one epidemic day loop, written against the Topology protocol.

This module is the entire runtime core: :func:`day_step` is Algorithm 2's
per-day body (visits → interactions → update) expressed once over
topology collectives, and :func:`run_days` is the whole run as a single
``lax.scan`` over the vmapped step, with observable reductions updating
*inside* the scan body. Every legacy engine layout is this scan placed on
a different :class:`~repro.engine.topology.Topology` — composition, not
per-layout loops (see repro/engine/core.py for the placement machinery).

Bitwise contract: on :class:`LocalTopology` the step performs the exact
arithmetic of the pre-refactor ``core/simulator.py:day_step``, and on
:class:`MeshTopology` the exact arithmetic of
``core/simulator_dist.py:dist_day_step`` — same counter-based draws on
global person ids, same accumulation orders, masks applied as exact
0.0/1.0 multiplies. tests/test_engine.py pins this against hand-rolled
scans over the legacy pure steps for all five layouts × backends.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import disease as disease_lib
from repro.core import interventions as iv_lib
from repro.core import population as pop_lib
from repro.core import rng
from repro.core import simulator as sim_lib
from repro.core import transmission as tx_lib
from repro.engine.topology import Topology
from repro.kernels.interactions import ops as iops

STAT_KEYS = sim_lib.STAT_KEYS

#: The named scopes of the day loop, in program order. Each phase of
#: :func:`day_step` runs under one, and :func:`run_days` runs the in-scan
#: observable update under ``observables``, so every device operation's
#: ``op_name`` metadata names its phase. The benchmark attributes device
#: time by these names (``bench/phases.py``): they are a contract, and
#: renaming one is a change to the benchmark.
SCOPES = ("interventions", "person_channels", "dispatch", "interactions",
          "combine", "infection", "seeding", "health_update", "reductions",
          "observables")

#: ``jax.named_scope``, looked up at trace time, so that a test can trace
#: the program without scopes and show that they change metadata alone.
_scope = jax.named_scope


@dataclasses.dataclass(frozen=True)
class EngineStatic:
    """Trace-time structure of the unified step: local-shard geometry plus
    the intervention slot layout and kernel backend. Identical across
    every scenario of a batch; on LocalTopology the "shard" is the whole
    population (``people_per_worker == num_people``)."""

    num_people: int  # global P (pre-padding)
    num_locations: int
    people_per_worker: int  # Pw — local person-shard width
    visits_per_worker: int  # Vw — local visit-slot width
    block_size: int
    seed_topk: int  # static per-worker top-k width for outbreak seeding
    iv_slots: tuple  # tuple[iv_lib.IvSlotStatic, ...]
    backend: str = "jnp"
    # Per-agent intervention structure (PR 7). Empty = the whole TTI layer
    # is statically compiled out: the traced program is the pre-PR one.
    pa_slots: tuple = ()  # tuple[iv_lib.PaSlotStatic, ...]
    test_topk: int = 1  # static per-worker top-k width for the test budget


def day_step(
    topo: Topology,
    static: EngineStatic,
    route,  # None (local) | dict of (7, W, C) exchange routing arrays
    week,  # dict of (7, ...) local weekly schedule + block schedules
    params: sim_lib.SimParams,  # per-person leaves are local (Pw,) shards
    state: sim_lib.SimState,
):
    """One simulated day on one local shard; pure in (params, state).

    vmappable over a leading scenario axis of (params, state) — that is
    how a batch rides along on any topology. Each phase runs under its
    named scope (:data:`SCOPES`).
    """
    Pw, Vw = static.people_per_worker, static.visits_per_worker
    day = state.day
    dow = day % pop_lib.DAYS_PER_WEEK
    take = lambda a: jax.lax.dynamic_index_in_dim(a, dow, 0, keepdims=False)

    w = topo.worker_index()
    gpid = (w * Pw + jnp.arange(Pw, dtype=jnp.int32)).astype(jnp.uint32)

    # ---- phase 1: interventions ------------------------------------------
    with _scope("interventions"):
        visit_ok, loc_open, sus_mult, inf_mult, vaccinated = (
            iv_lib.apply_iv_params(
                static.iv_slots,
                params.iv,
                state.iv_active,
                state.vaccinated,
                Pw,
                static.num_locations,
            )
        )

        # ---- phase 1b: per-agent interventions (test-trace-isolate) ------
        # Statically compiled out when no TestTraceIsolate slot exists: the
        # traced program below is then the exact pre-PR one (3 dispatch
        # channels, single-channel combine, constant-zero TTI stats).
        K2 = len(static.pa_slots)
        tracing_on = any(ps.trace for ps in static.pa_slots)
        takes, take_any = [], None
        tests_used = jnp.zeros((), jnp.int32)
        if K2:
            in_iso = day < state.isolated_until
            visit_ok = visit_ok & ~in_iso
            sym = disease_lib.lookup(params.sym_table, state.health) > 0.0
            detectable = (
                disease_lib.lookup(params.inf_table, state.health) > 0.0
            )
            take_any = jnp.zeros((Pw,), bool)
            for k2 in range(K2):
                act = params.iv.pa_enabled[k2] & (day >= params.iv.pa_start[k2])
                elig = (
                    act
                    & params.iv.pa_people[k2]
                    & ~state.tested
                    & ~in_iso
                    & (sym | state.traced)
                )
                # Symptomatic candidates draw in (0,1), traced-only in
                # (2,3), ineligible sit at 4.0 — one lexicographic top-k
                # over (score, gpid) is then an exact priority-tiered
                # budget.
                u = rng.uniform(params.seed, rng.TEST, day, k2, gpid)
                score = jnp.where(elig & sym, u, jnp.where(elig, u + 2.0, 4.0))
                T, G = topo.rank_threshold(
                    score, gpid, params.iv.pa_tests[k2], static.num_people,
                    static.test_topk,
                )
                take_k = (
                    elig
                    & (params.iv.pa_tests[k2] > 0)
                    & ((score < T) | ((score == T) & (gpid <= G)))
                )
                takes.append(take_k)
                take_any = take_any | take_k
                tests_used = tests_used + topo.psum(
                    take_k.sum().astype(jnp.int32)
                )
            # Result latency: positives circulate today as tracing sources
            # and enter isolation from day+1 (see docs/interventions.md).
            positives = take_any & detectable

    # ---- per-person epidemiological channels (disease-table lookups) -----
    with _scope("person_channels"):
        person_sus = (disease_lib.lookup(params.sus_table, state.health)
                      * params.beta_sus * sus_mult)
        person_inf = (disease_lib.lookup(params.inf_table, state.health)
                      * params.beta_inf * inf_mult)
        person_chans = [person_sus, person_inf, visit_ok.astype(jnp.float32)]
        if tracing_on:
            person_chans.append(positives.astype(jnp.float32))
        chans = jnp.stack(person_chans, axis=-1)

    # ---- visit dispatch (halo exchange): person channels to visit slots --
    with _scope("dispatch"):
        pid = take(week["pid"])  # (Vw,) global person ids, -1 pad
        loc = take(week["loc"])  # (Vw,) global location ids
        day_route = (
            None if route is None
            else (take(route["send"]), take(route["recv"]))
        )
        visit_vals = topo.dispatch(day_route, pid, chans)
        sus_v, inf_v, ok_v = (
            visit_vals[:, 0], visit_vals[:, 1], visit_vals[:, 2]
        )
        # Location-side closures: loc_open is (L,) replicated; gather per
        # visit.
        open_v = loc_open[jnp.minimum(loc, static.num_locations - 1)]
        active = (pid >= 0) & (ok_v > 0.0) & open_v
        eff_pid = jnp.where(active, pid, -1)
        sus_v = sus_v * active
        inf_v = inf_v * active

    # ---- phase 2: block-scheduled interactions ---------------------------
    with _scope("interactions"):
        vstart, vend = take(week["start"]), take(week["end"])
        p_v = take(week["p"])  # per-visit contact probability
        row_i, col_i = take(week["row"]), take(week["col"])
        row_s, pair_a = take(week["rs"]), take(week["pa"])
        contact_day = jnp.where(params.static_network, dow, day)
        col_inf = iops.col_has_infectious(
            inf_v, eff_pid, Vw // static.block_size, static.block_size
        )
        row_sus = iops.row_has_susceptible(
            sus_v, eff_pid, Vw // static.block_size, static.block_size
        )
        meta = jnp.stack(
            [params.seed.astype(jnp.uint32), contact_day.astype(jnp.uint32)]
        )
        if tracing_on:
            # Second accumulator: per-visit traced-contact counts ride the
            # same tiles and accumulation order as exposure
            # (bitwise-identical across all five backends, zero extra
            # passes).
            src_v = visit_vals[:, 3] * active
            acc, cnt, edges, trc, n_live = iops.interactions_auto_traced(
                eff_pid, loc, vstart, vend, p_v, sus_v, inf_v,
                row_i, col_i, row_s, pair_a, col_inf, row_sus, meta,
                block_size=static.block_size, backend=static.backend,
                src_val=src_v,
            )
        else:
            acc, cnt, edges, n_live = iops.interactions_auto_edges(
                eff_pid, loc, vstart, vend, p_v, sus_v, inf_v,
                row_i, col_i, row_s, pair_a, col_inf, row_sus, meta,
                block_size=static.block_size, backend=static.backend,
            )

    # ---- phase 3: exposure combine (adjoint exchange) --------------------
    with _scope("combine"):
        if tracing_on:
            # Traced-contact halo rides the exposure combine: channel 0 is
            # bitwise identical to the single-channel combine.
            combined = topo.combine_many(
                day_route, pid, active,
                jnp.stack([acc, trc.astype(jnp.float32)], axis=-1), Pw,
            )
            A = combined[:, 0] * params.tau_eff
            trc_p = combined[:, 1]
        else:
            A = topo.combine(day_route, pid, active, acc, Pw) * params.tau_eff

    with _scope("infection"):
        infected = tx_lib.sample_infections(A, params.seed, day, pid=gpid)

    with _scope("seeding"):

        def with_seeding(_):
            us = rng.uniform(params.seed, rng.SEED_CHOICE, day, gpid)
            sus_ok = disease_lib.lookup(params.sus_table, state.health) > 0.0
            us = jnp.where(sus_ok, us, 2.0)
            thresh = topo.seed_threshold(
                us, params.seed_per_day, static.num_people, static.seed_topk
            )
            return (us <= thresh) & sus_ok & (params.seed_per_day > 0)

        seeded = jax.lax.cond(
            day < params.seed_days,
            with_seeding,
            lambda _: jnp.zeros((Pw,), bool),
            None,
        )

    with _scope("health_update"):
        can_infect = disease_lib.lookup(params.sus_table, state.health) > 0.0
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
            day,
            pid=gpid,
        )

    # ---- global reductions (Algorithm 2 line 34) -------------------------
    with _scope("reductions"):
        new_count = topo.psum(new_mask.sum().astype(jnp.int32))
        cumulative = state.cumulative + new_count
        infectious = topo.psum(
            (disease_lib.lookup(params.inf_table, health) > 0.0)
            .sum().astype(jnp.int32)
        )
        susceptible = topo.psum(
            (disease_lib.lookup(params.sus_table, health) > 0.0)
            .sum().astype(jnp.int32)
        )
        # Summed unsigned before the cross-worker accumulation (see
        # simulator.COUNT_DTYPE).
        contacts = topo.psum(jnp.sum(cnt, dtype=sim_lib.COUNT_DTYPE))

        # ---- per-agent state advance (result-latency TTI semantics) ------
        if K2:
            tested = state.tested | take_any
            iso_until = state.isolated_until
            newly_traced = jnp.zeros((Pw,), bool)
            for k2, ps in enumerate(static.pa_slots):
                pos_k = takes[k2] & detectable
                iso_until = jnp.maximum(
                    iso_until,
                    jnp.where(pos_k, day + 1 + params.iv.pa_iso[k2], 0),
                )
                if ps.trace:
                    act = params.iv.pa_enabled[k2] & (
                        day >= params.iv.pa_start[k2]
                    )
                    nt_k = (trc_p > 0.0) & params.iv.pa_people[k2] & act
                    newly_traced = newly_traced | nt_k
                    iso_until = jnp.maximum(
                        iso_until,
                        jnp.where(
                            nt_k, day + 1 + params.iv.pa_trace_iso[k2], 0
                        ),
                    )
            traced_next = state.traced | newly_traced
            isolated = topo.psum(in_iso.sum().astype(jnp.int32))
            traced_new = topo.psum(newly_traced.sum().astype(jnp.int32))
        else:
            tested = state.tested
            traced_next = state.traced
            iso_until = state.isolated_until
            isolated = jnp.zeros((), jnp.int32)
            traced_new = jnp.zeros((), jnp.int32)

        stats = {
            "day": day,
            "new_infections": new_count,
            "cumulative": cumulative,
            "infectious": infectious,
            "susceptible": susceptible,
            "contacts": contacts,
            # Traversed-edge counter (TEPS numerator). On pallas-compact
            # this is the kernel's own accumulator; elsewhere it is
            # cnt.sum() — both equal `contacts` exactly, which tests
            # assert, making the in-kernel telemetry a cross-checked
            # measurement rather than a trusted one.
            "edges": topo.psum(edges.astype(sim_lib.COUNT_DTYPE)),
            # Tiles with work today: the count the Pallas launchers
            # compact to, live.sum() elsewhere — equal on every backend;
            # summed over the workers' own shards.
            "live_tiles": topo.psum(n_live.astype(sim_lib.COUNT_DTYPE)),
            "tests_used": tests_used,
            "isolated": isolated,
            "traced": traced_new,
        }
        iv_active = iv_lib.evaluate_iv_triggers(
            static.iv_slots, params.iv, day, stats, state.iv_active
        )
        new_state = sim_lib.SimState(
            day=day + 1,
            health=health,
            dwell=dwell,
            cumulative=cumulative,
            iv_active=iv_active,
            vaccinated=vaccinated,
            tested=tested,
            traced=traced_next,
            isolated_until=iso_until,
        )
    return new_state, stats


def run_days(
    topo: Topology,
    static: EngineStatic,
    route,
    week,
    params: sim_lib.SimParams,  # leaves carry a leading (B_local,) axis
    state: sim_lib.SimState,  # likewise
    days: int,
    observables: tuple = (),
    carries: tuple = (),
    num_real: int = None,
):
    """A whole run as ONE ``lax.scan`` over the vmapped day step, with
    observable reductions updating inside the scan body.

    ``params``/``state`` carry a leading local scenario axis (B_local >= 1
    on every topology — B=1 single runs included, so downstream code never
    branches on batch-ness). Observables see the *full* real scenario
    batch each day via ``topo.scen_gather`` (a collective over the
    scenario mesh axis when the batch is sharded, identity otherwise), so
    cross-scenario reductions are bitwise-identical on every topology.

    Returns ``(final_state, carries, hist, dailies)`` — ``hist`` leaves
    are day-major ``(days, B_local)``, ``dailies`` are the stacked per-day
    observable outputs over the real batch.
    """
    from repro.api import observables as obs_lib  # cycle-free at call time

    step = jax.vmap(
        lambda p, st: day_step(topo, static, route, week, p, st)
    )

    def body(carry, _):
        st, oc = carry
        st, stats = step(params, st)
        with _scope("observables"):
            gstats = jax.tree.map(
                lambda x: topo.scen_gather(x, num_real), stats
            )
            oc, daily = obs_lib.update_all(observables, oc, gstats)
        return (st, oc), (stats, daily)

    (state, carries), (hist, dailies) = jax.lax.scan(
        body, (state, carries), None, length=days
    )
    return state, carries, hist, dailies
