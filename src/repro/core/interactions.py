"""Day-level interaction pass orchestration (Algorithm 2, middle loop).

Bridges the Population's static week structure and the interaction kernels:
stacks the 7 day-of-week visit arrays + block schedules into fixed-shape
device arrays (so one jitted day step serves the whole run, selected by
``day % 7``), gathers per-visit person values, runs a kernel backend, and
segment-sums exposure back to people — the single-device equivalent of the
visit-message / exposure-message exchanges (the distributed version routes
the same values through core/exchange.py instead of gathers).
"""

from __future__ import annotations

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import population as pop_lib
from repro.kernels.interactions import ops as iops


@dataclasses.dataclass(frozen=True)
class WeekData:
    """Stacked (7, ...) device arrays for the weekly schedule."""

    pid: jnp.ndarray  # (7, V) int32, -1 padding
    loc: jnp.ndarray  # (7, V) int32
    start: jnp.ndarray  # (7, V) f32
    end: jnp.ndarray  # (7, V) f32
    row_idx: jnp.ndarray  # (7, NP) int32
    col_idx: jnp.ndarray  # (7, NP) int32
    row_start: jnp.ndarray  # (7, NP) int32
    pair_active: jnp.ndarray  # (7, NP) int32
    block_size: int
    num_blocks: int

    @property
    def visits_per_day(self) -> int:
        return self.pid.shape[1]


# Registered as a pytree (arrays as leaves, block geometry as aux data) so
# WeekData can cross jit/shard_map boundaries as an explicit argument — the
# scenario-ensemble sharding passes it with replicated specs instead of
# relying on closed-over constants.
jax.tree_util.register_pytree_node(
    WeekData,
    lambda w: (
        (w.pid, w.loc, w.start, w.end, w.row_idx, w.col_idx, w.row_start,
         w.pair_active),
        (w.block_size, w.num_blocks),
    ),
    lambda aux, ch: WeekData(*ch, block_size=aux[0], num_blocks=aux[1]),
)


def build_week_data(
    pop: pop_lib.Population, block_size: int, pack: bool = True
) -> WeekData:
    """Stack the weekly schedule for the kernels. ``pack`` applies the
    occupancy-aware run packing (population.py:pack_day_occupancy), which
    shrinks the block-pair schedule NP; layout is epidemiologically free
    (counter-based draws key on ids, not slots)."""
    if pack:
        week = pop.packed_week(block_size)
        size = max(len(d) for d in week)
        week = [pop_lib.extend_packed(d, size) for d in week]
        extents = [d.extent for d in week]
    else:
        week = pop_lib.pad_week_uniform(pop.week, pad_multiple=block_size)
        extents = [d.num_real for d in week]
    scheds = [
        pop_lib.build_block_schedule(d.loc, e, block_size)
        for d, e in zip(week, extents)
    ]
    np_max = max(s.row_block.shape[0] for s in scheds)
    scheds = [
        pop_lib.build_block_schedule(d.loc, e, block_size, pad_to=np_max)
        for d, e in zip(week, extents)
    ]

    def stack(getter, dtype):
        return jnp.asarray(np.stack([getter(x) for x in zip(week, scheds)]), dtype)

    return WeekData(
        pid=stack(lambda x: x[0].person, jnp.int32),
        loc=stack(lambda x: x[0].loc, jnp.int32),
        start=stack(lambda x: x[0].start, jnp.float32),
        end=stack(lambda x: x[0].end, jnp.float32),
        row_idx=stack(lambda x: x[1].row_block, jnp.int32),
        col_idx=stack(lambda x: x[1].col_block, jnp.int32),
        row_start=stack(lambda x: x[1].row_start.astype(np.int32), jnp.int32),
        pair_active=stack(lambda x: x[1].pair_active.astype(np.int32), jnp.int32),
        block_size=block_size,
        num_blocks=len(week[0]) // block_size,
    )


def day_exposure(
    week: WeekData,
    dow,  # scalar int day-of-week
    num_people: int,
    person_sus_val,  # (P,) sigma(X)*beta_sigma, already intervention-scaled
    person_inf_val,  # (P,) iota(X)*beta_iota
    contact_prob,  # (L,) per-location p
    visit_ok,  # (P,) bool — person-level intervention visit mask
    loc_open,  # (L,) bool — location-level intervention mask
    tau,  # scalar transmissibility
    seed,
    contact_day,  # day index for the contact hash (absolute day, or day%7
    #               for the EpiHiper-style static-network baseline)
    backend: str = "jnp",
):
    """Returns (per-person propensity A (P,), total sus-inf contacts, live
    tiles)."""
    take = lambda a: jax.lax.dynamic_index_in_dim(a, dow, 0, keepdims=False)
    pid, loc = take(week.pid), take(week.loc)
    start, end = take(week.start), take(week.end)
    row_idx, col_idx = take(week.row_idx), take(week.col_idx)
    row_start, pair_active = take(week.row_start), take(week.pair_active)

    safe_pid = jnp.maximum(pid, 0)
    active = (pid >= 0) & visit_ok[safe_pid] & loc_open[loc]
    eff_pid = jnp.where(active, pid, -1)
    sus_v = person_sus_val[safe_pid] * active
    inf_v = person_inf_val[safe_pid] * active
    p_v = contact_prob[loc]

    col_inf = iops.col_has_infectious(inf_v, eff_pid, week.num_blocks, week.block_size)
    row_sus = iops.row_has_susceptible(sus_v, eff_pid, week.num_blocks, week.block_size)
    meta = jnp.stack(
        [jnp.asarray(seed, jnp.uint32), jnp.asarray(contact_day, jnp.uint32)]
    )
    acc, cnt, _, n_live = iops.interactions_auto_edges(
        eff_pid, loc, start, end, p_v, sus_v, inf_v,
        row_idx, col_idx, row_start, pair_active, col_inf, row_sus, meta,
        block_size=week.block_size, backend=backend,
    )
    # Exposure combine: per-person total propensity (Eq. 3), times tau.
    A = jax.ops.segment_sum(
        jnp.where(active, acc, 0.0), safe_pid, num_segments=num_people
    ) * jnp.asarray(tau, jnp.float32)  # asarray: tau may be a traced scalar
    return A, jnp.sum(cnt, dtype=jnp.uint32), n_live.astype(jnp.uint32)
