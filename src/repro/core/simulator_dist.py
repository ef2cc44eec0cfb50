"""Distributed epidemic day step (Algorithm 2, SPMD over a device mesh).

People and locations are partitioned exactly as in the paper: people in
uniform blocks, locations by the geo-sorted visit-weighted static scheme
(§V-B). Each simulated day runs three phases inside one `shard_map`:

  1. **visit dispatch** — per-person epidemiological channels (sus value,
     inf value, visit-ok flag) routed person-partition → location-partition
     through the capacity-bucketed all_to_all (core/exchange.py). This is
     the paper's visit-message exchange with aggregation built in.
  2. **interactions** — each worker runs the block-scheduled interaction
     kernel on its local, location-sorted visit arrays.
  3. **exposure combine + update** — per-visit propensities return to the
     person owners through the adjoint all_to_all (exposure messages);
     infection sampling, FSA update, and trigger reductions (psum) follow.

The day step is the pure function :func:`dist_day_step` of
``(static, plan, week, params, state)`` — the distributed twin of
``core/simulator.py:day_step``:

  * ``DistStatic`` — trace-time structure (partition geometry, intervention
    slot layout, kernel backend). Identical across a scenario ensemble.
  * ``plan``/``week`` — per-worker local shards of the static exchange
    routing and weekly visit schedule (device arrays; host construction in
    :func:`build_dist_plan` / :func:`week_device_arrays`).
  * ``params`` — the *same* ``SimParams`` pytree the single-device engine
    uses, with per-person leaves padded to the worker layout
    (:func:`pad_params`). Because every scenario-varying numeric is a leaf
    of this pytree, the step is vmappable over a leading scenario axis —
    the engine core's ``layout="hybrid"`` runs B scenarios × W workers on a
    2-D (workers × scenarios) mesh this way.

A whole run is a single jitted ``lax.scan`` over :func:`dist_day_step`
inside one ``shard_map`` — no host-side per-day dispatch, matching the
single-device and ensemble engines.

Because all stochastic draws are counter-based on *global* ids, the
distributed simulation is bitwise identical to the single-device
reference for any worker count — tested in tests/test_dist.py by spawning
a multi-device host-platform subprocess.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import disease as disease_lib
from repro.core import exchange as ex_lib
from repro.core import interventions as iv_lib
from repro.core import population as pop_lib
from repro.core import rng
from repro.core import simulator as sim_lib
from repro.core import transmission as tx_lib
from repro.kernels.interactions import ops as iops

AXIS = "workers"

STAT_KEYS = sim_lib.STAT_KEYS


@dataclasses.dataclass
class DistPlan:
    """Host-built static partition + routing data (all numpy)."""

    num_workers: int
    people_per_worker: int  # Pw (padded)
    num_people: int  # P real
    locs_per_worker: int  # Lw (padded)
    visits_per_worker: int  # Vw (padded, uniform across workers & days)
    pairs_per_worker: int  # NPw
    block_size: int
    # (7, W, Vw)
    week_pid: np.ndarray  # global person ids, -1 pad
    week_loc: np.ndarray  # *global* loc id (for the contact hash), pad ok
    week_start: np.ndarray
    week_end: np.ndarray
    week_p: np.ndarray  # per-visit contact probability (gathered at build)
    # (7, W, NPw) block schedules
    row_idx: np.ndarray
    col_idx: np.ndarray
    row_start: np.ndarray
    pair_active: np.ndarray
    # (7, W, W, C) exchange routing
    send_idx: np.ndarray
    recv_slot: np.ndarray
    capacity: int
    # location partition (for elastic re-partitioning / stats)
    loc_partition: np.ndarray  # (L,)


def build_dist_plan(
    pop: pop_lib.Population,
    num_workers: int,
    block_size: int = 128,
    balanced: bool = True,
    pack: bool = True,
) -> DistPlan:
    W = num_workers
    P_real = pop.num_people
    Pw = int(np.ceil(P_real / W))

    # Location partition: the paper's static load balancing (or naive).
    visits_per_loc = np.zeros((pop.num_locations,), np.int64)
    for d in pop.week:
        np.add.at(visits_per_loc, d.loc[: d.num_real], 1)
    if balanced:
        loc_part = pop_lib.balanced_location_partition(
            pop.geo_key, visits_per_loc, W
        )
    else:
        loc_part = pop_lib.naive_location_partition(pop.num_locations, W)

    person_owner = (np.arange(P_real) // Pw).astype(np.int32)
    person_local = (np.arange(P_real) % Pw).astype(np.int32)

    # Per-worker, per-day location-sorted visit arrays.
    days = []
    for d in pop.week:
        n = d.num_real
        v_part = loc_part[d.loc[:n]]
        per_worker = []
        for w in range(W):
            sel = np.flatnonzero(v_part == w)
            per_worker.append(
                pop_lib.pack_day(
                    d.person[:n][sel], d.loc[:n][sel],
                    d.start[:n][sel], d.end[:n][sel],
                    pad_multiple=block_size,
                )
            )
        days.append(per_worker)
    if pack:
        # Occupancy-aware run packing per worker shard (smaller block-pair
        # schedules; layout is epidemiologically free — global-id draws).
        days = [
            [pop_lib.pack_day_occupancy(pw, block_size) for pw in day]
            for day in days
        ]
        Vw = max(len(pw) for day in days for pw in day)
        Vw = int(np.ceil(Vw / block_size) * block_size)
        days = [[pop_lib.extend_packed(pw, Vw) for pw in day] for day in days]
        extents = [[pw.extent for pw in day] for day in days]
    else:
        Vw = max(len(pw) for day in days for pw in day)
        Vw = int(np.ceil(Vw / block_size) * block_size)
        days = [
            [
                pop_lib.pack_day(
                    pw.person[: pw.num_real], pw.loc[: pw.num_real],
                    pw.start[: pw.num_real], pw.end[: pw.num_real],
                    pad_to=Vw, pad_multiple=block_size,
                )
                for pw in day
            ]
            for day in days
        ]
        extents = [[pw.num_real for pw in day] for day in days]

    # Block schedules, padded to a uniform pair count.
    scheds = [
        [
            pop_lib.build_block_schedule(pw.loc, e, block_size)
            for pw, e in zip(day, ext)
        ]
        for day, ext in zip(days, extents)
    ]
    NPw = max(s.row_block.shape[0] for day in scheds for s in day)
    scheds = [
        [
            pop_lib.build_block_schedule(pw.loc, e, block_size, pad_to=NPw)
            for pw, e in zip(day, ext)
        ]
        for day, ext in zip(days, extents)
    ]

    # Exchange plans (same routing structure every day; capacity = max).
    plans = []
    for day in days:
        vp = np.stack([pw.person for pw in day])  # (W, Vw)
        plans.append(
            ex_lib.build_exchange_plan(vp, person_owner, person_local)
        )
    C = max(p.capacity for p in plans)
    send_idx = np.full((7, W, W, C), -1, np.int32)
    recv_slot = np.full((7, W, W, C), -1, np.int32)
    for d, p in enumerate(plans):
        send_idx[d, :, :, : p.capacity] = p.send_idx
        recv_slot[d, :, :, : p.capacity] = p.recv_slot

    stack = lambda f: np.stack([np.stack([f(x) for x in day]) for day in days])
    sstack = lambda f: np.stack([np.stack([f(s) for s in day]) for day in scheds])

    # Per-visit contact probability, gathered on host (location attrs are
    # static; this is the paper's "store p as a location attribute").
    week_p = np.stack(
        [
            np.stack([pop.contact_prob[np.minimum(pw.loc, pop.num_locations - 1)]
                      for pw in day])
            for day in days
        ]
    ).astype(np.float32)

    # Padded locations per worker (only used for closure masks / stats).
    Lw = int(np.max(np.bincount(loc_part, minlength=W)))

    return DistPlan(
        num_workers=W,
        people_per_worker=Pw,
        num_people=P_real,
        locs_per_worker=Lw,
        visits_per_worker=Vw,
        pairs_per_worker=NPw,
        block_size=block_size,
        week_pid=stack(lambda x: x.person),
        week_loc=stack(lambda x: x.loc),
        week_start=stack(lambda x: x.start),
        week_end=stack(lambda x: x.end),
        week_p=week_p,
        row_idx=sstack(lambda s: s.row_block),
        col_idx=sstack(lambda s: s.col_block),
        row_start=sstack(lambda s: s.row_start.astype(np.int32)),
        pair_active=sstack(lambda s: s.pair_active.astype(np.int32)),
        send_idx=send_idx,
        recv_slot=recv_slot,
        capacity=C,
        loc_partition=loc_part,
    )


# --------------------------------------------------------------------------
# Trace-time structure + device-array builders for the pure day step
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DistStatic:
    """Trace-time structure of the distributed step: partition geometry plus
    the same intervention slot layout / backend as ``SimStatic``. Identical
    across every scenario of a hybrid ensemble."""

    num_people: int  # real P (pre-padding)
    num_locations: int
    num_workers: int
    people_per_worker: int  # Pw
    visits_per_worker: int  # Vw
    block_size: int
    seed_topk: int  # static per-worker top-k width for outbreak seeding
    iv_slots: tuple  # tuple[iv_lib.IvSlotStatic, ...]
    backend: str = "jnp"


def make_dist_static(
    plan: DistPlan,
    num_locations: int,
    iv_slots: tuple,
    backend: str = "jnp",
    max_seed_per_day: int = 10,
) -> DistStatic:
    """``seed_topk`` must cover the largest ``seed_per_day`` any scenario
    will run with (clamped to the shard size) so the global order statistic
    in :func:`dist_day_step` is exact — see the seeding phase there."""
    return DistStatic(
        num_people=plan.num_people,
        num_locations=num_locations,
        num_workers=plan.num_workers,
        people_per_worker=plan.people_per_worker,
        visits_per_worker=plan.visits_per_worker,
        block_size=plan.block_size,
        seed_topk=max(1, min(int(max_seed_per_day), plan.people_per_worker)),
        iv_slots=iv_slots,
        backend=backend,
    )


def week_device_arrays(plan: DistPlan):
    """Device copies of the weekly schedule + exchange routing, split into
    the ``week`` (visit schedule) and ``plan`` (routing) arguments of
    :func:`dist_day_step`. All arrays are (7, W, ...) — sharded on axis 1.
    """
    week = {
        "pid": jnp.asarray(plan.week_pid),
        "loc": jnp.asarray(plan.week_loc),
        "start": jnp.asarray(plan.week_start),
        "end": jnp.asarray(plan.week_end),
        "p": jnp.asarray(plan.week_p),
        "row": jnp.asarray(plan.row_idx),
        "col": jnp.asarray(plan.col_idx),
        "rs": jnp.asarray(plan.row_start),
        "pa": jnp.asarray(plan.pair_active),
    }
    route = {
        "send": jnp.asarray(plan.send_idx),
        "recv": jnp.asarray(plan.recv_slot),
    }
    return week, route


def pad_params(params: sim_lib.SimParams, plan: DistPlan) -> sim_lib.SimParams:
    """Pad the per-person leaves of a single-device ``SimParams`` to the
    plan's W*Pw person axis. Pad people have zero betas and sit outside
    every selector mask, so they are epidemiologically inert."""
    pad = plan.num_workers * plan.people_per_worker - plan.num_people
    padp = lambda a: jnp.pad(a, ((0, pad),))
    return dataclasses.replace(
        params,
        beta_sus=padp(params.beta_sus),
        beta_inf=padp(params.beta_inf),
        iv=dataclasses.replace(
            params.iv,
            people=jnp.pad(params.iv.people, ((0, 0), (0, pad))),
            pa_people=jnp.pad(params.iv.pa_people, ((0, 0), (0, pad))),
        ),
    )


def _spec(batch_axis, *axes):
    return P(batch_axis, *axes) if batch_axis is not None else P(*axes)


def dist_param_specs(batch_axis: Optional[str] = None) -> sim_lib.SimParams:
    """SimParams-shaped PartitionSpec tree for the worker-padded layout.
    ``batch_axis`` prepends a scenario axis to every leaf (hybrid mesh)."""
    s = lambda *axes: _spec(batch_axis, *axes)
    iv = iv_lib.IvParams(
        enabled=s(), day_start=s(), day_end=s(), thresh_on=s(),
        thresh_off=s(), factor=s(), people=s(None, AXIS), locations=s(),
        pa_enabled=s(), pa_start=s(), pa_tests=s(), pa_iso=s(),
        pa_trace_iso=s(), pa_people=s(None, AXIS),
    )
    return sim_lib.SimParams(
        seed=s(), tau_eff=s(), sus_table=s(), inf_table=s(), sym_table=s(),
        cum_trans=s(),
        dwell_mean=s(), entry_state=s(), beta_sus=s(AXIS), beta_inf=s(AXIS),
        seed_per_day=s(), seed_days=s(), static_network=s(), iv=iv,
    )


def dist_state_specs(batch_axis: Optional[str] = None) -> sim_lib.SimState:
    s = lambda *axes: _spec(batch_axis, *axes)
    return sim_lib.SimState(
        day=s(), health=s(AXIS), dwell=s(AXIS), cumulative=s(),
        iv_active=s(), vaccinated=s(AXIS),
        tested=s(AXIS), traced=s(AXIS), isolated_until=s(AXIS),
    )


def dist_init_state(
    disease: disease_lib.DiseaseModel, plan: DistPlan, num_iv_slots: int
) -> sim_lib.SimState:
    """Worker-padded initial state; pad people enter an absorbing,
    non-susceptible state so they never participate."""
    Ppad = plan.num_workers * plan.people_per_worker
    non_sus = np.flatnonzero(np.asarray(disease.susceptibility) == 0.0)
    if Ppad > plan.num_people and len(non_sus) == 0:
        raise ValueError(
            f"disease model '{disease.name}' has no zero-susceptibility "
            "state to park the padded people in — they would be seedable "
            "and break dist<->single parity"
        )
    absorbing = int(non_sus[0]) if len(non_sus) else disease.initial_state
    health = np.full((Ppad,), absorbing, np.int32)
    health[: plan.num_people] = disease.initial_state
    return sim_lib.SimState(
        day=jnp.asarray(0, jnp.int32),
        health=jnp.asarray(health),
        dwell=jnp.full((Ppad,), disease_lib.ABSORBING_DWELL, jnp.float32),
        cumulative=jnp.asarray(0, jnp.int32),
        iv_active=jnp.zeros((num_iv_slots,), bool),
        vaccinated=jnp.zeros((Ppad,), bool),
        tested=jnp.zeros((Ppad,), bool),
        traced=jnp.zeros((Ppad,), bool),
        isolated_until=jnp.zeros((Ppad,), jnp.int32),
    )


# --------------------------------------------------------------------------
# The pure distributed day step (call inside shard_map over axis AXIS)
# --------------------------------------------------------------------------


def dist_day_step(
    static: DistStatic,
    plan,  # dict: local (7, W, C) exchange routing ("send", "recv")
    week,  # dict: local (7, ...) weekly visit schedule + block schedules
    params: sim_lib.SimParams,  # per-person leaves are local (Pw,) shards
    state: sim_lib.SimState,  # health/dwell/vaccinated local (Pw,) shards
):
    """One distributed day on one worker's local shard; pure in
    (params, state). The SPMD twin of ``simulator.day_step`` — same
    counter-based draws on global person ids, so results are bitwise equal
    to the single-device reference. vmappable over a leading scenario axis
    of (params, state) for hybrid (workers × scenarios) ensembles.
    """
    axis = AXIS
    Pw, Vw = static.people_per_worker, static.visits_per_worker
    w = jax.lax.axis_index(axis)
    day = state.day
    dow = day % pop_lib.DAYS_PER_WEEK
    take = lambda a: jax.lax.dynamic_index_in_dim(a, dow, 0, keepdims=False)
    pid = take(week["pid"])  # (Vw,) global person ids, -1 pad
    loc = take(week["loc"])
    vstart, vend = take(week["start"]), take(week["end"])
    p_v = take(week["p"])
    row_i, col_i = take(week["row"]), take(week["col"])
    row_s, pair_a = take(week["rs"]), take(week["pa"])
    send, recv = take(plan["send"]), take(plan["recv"])  # (W, C)

    # ---- phase 1: interventions + per-person channels (shared iv lib) ----
    visit_ok, loc_open, sus_mult, inf_mult, vaccinated = iv_lib.apply_iv_params(
        static.iv_slots,
        params.iv,
        state.iv_active,
        state.vaccinated,
        Pw,
        static.num_locations,
    )
    person_sus = params.sus_table[state.health] * params.beta_sus * sus_mult
    person_inf = params.inf_table[state.health] * params.beta_inf * inf_mult

    # ---- visit dispatch (all_to_all): route person channels to visits ----
    chans = jnp.stack(
        [person_sus, person_inf, visit_ok.astype(jnp.float32)], axis=-1
    )
    visit_vals = ex_lib.dispatch(send, recv, chans, Vw, axis)
    sus_v, inf_v, ok_v = visit_vals[:, 0], visit_vals[:, 1], visit_vals[:, 2]

    # Location-side closures: loc_open is (L,) replicated; gather per visit.
    open_v = loc_open[jnp.minimum(loc, static.num_locations - 1)]
    active = (pid >= 0) & (ok_v > 0.0) & open_v
    eff_pid = jnp.where(active, pid, -1)
    sus_v = sus_v * active
    inf_v = inf_v * active

    # ---- phase 2: interactions ----
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
    acc, cnt, _, n_live = iops.interactions_auto_edges(
        eff_pid, loc, vstart, vend, p_v, sus_v, inf_v,
        row_i, col_i, row_s, pair_a, col_inf, row_sus, meta,
        block_size=static.block_size, backend=static.backend,
    )

    # ---- phase 3: exposure combine (adjoint all_to_all) + update ----
    A = ex_lib.combine(send, recv, acc[:, None] * active[:, None], Pw, axis)
    A = A[:, 0] * params.tau_eff

    gpid = (w * Pw + jnp.arange(Pw, dtype=jnp.int32)).astype(jnp.uint32)
    infected = tx_lib.sample_infections(A, params.seed, day, pid=gpid)

    def with_seeding(_):
        # Global order statistic: union of per-worker top-k smallest draws.
        # static.seed_topk >= min(seed_per_day, Pw) guarantees the global
        # k-th smallest is inside the gathered union, so the threshold is
        # bitwise identical to the single-device full sort.
        us = rng.uniform(params.seed, rng.SEED_CHOICE, day, gpid)
        sus_ok = params.sus_table[state.health] > 0.0
        us = jnp.where(sus_ok, us, 2.0)
        local_small = -jax.lax.top_k(-us, static.seed_topk)[0]
        all_small = jnp.sort(
            jax.lax.all_gather(local_small, axis).reshape(-1)
        )
        k = jnp.minimum(params.seed_per_day, static.num_people) - 1
        thresh = all_small[jnp.clip(k, 0, all_small.shape[0] - 1)]
        return (us <= thresh) & sus_ok & (params.seed_per_day > 0)

    seeded = jax.lax.cond(
        day < params.seed_days,
        with_seeding,
        lambda _: jnp.zeros((Pw,), bool),
        None,
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
        day,
        pid=gpid,
    )

    # ---- global reductions (Algorithm 2 line 34's reduction) ----
    new_count = jax.lax.psum(new_mask.sum().astype(jnp.int32), axis)
    cumulative = state.cumulative + new_count
    infectious = jax.lax.psum(
        (params.inf_table[health] > 0.0).sum().astype(jnp.int32), axis
    )
    susceptible = jax.lax.psum(
        (params.sus_table[health] > 0.0).sum().astype(jnp.int32), axis
    )
    # Summed unsigned before the cross-worker accumulation (see
    # simulator.COUNT_DTYPE).
    contacts = jax.lax.psum(jnp.sum(cnt, dtype=sim_lib.COUNT_DTYPE), axis)
    stats = {
        "day": day,
        "new_infections": new_count,
        "cumulative": cumulative,
        "infectious": infectious,
        "susceptible": susceptible,
        "contacts": contacts,
        # Host-side traversed edges (== contacts by construction); see
        # simulator.STAT_KEYS for why it is a separate key.
        "edges": contacts,
        "live_tiles": jax.lax.psum(n_live.astype(sim_lib.COUNT_DTYPE), axis),
        # Legacy reference path: no per-agent interventions (zeros, like
        # simulator.phase_update — the unified engine computes these).
        "tests_used": jnp.zeros((), jnp.int32),
        "isolated": jnp.zeros((), jnp.int32),
        "traced": jnp.zeros((), jnp.int32),
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
        tested=state.tested,
        traced=state.traced,
        isolated_until=state.isolated_until,
    )
    return new_state, stats


def dist_run_scan(static, plan, week, params, state, days: int):
    """A whole distributed run as one lax.scan over :func:`dist_day_step`."""

    def body(s, _):
        return dist_day_step(static, plan, week, params, s)

    return jax.lax.scan(body, state, None, length=days)
