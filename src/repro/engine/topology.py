"""The Topology protocol: where the one day loop runs.

The engine core (:mod:`repro.engine.day`) writes the epidemic day step
*once*, against this small protocol, and the runtime "places" it — the
paper's Charm++ move (PAPER.md §IV) translated to SPMD JAX. A topology
answers four questions:

  * **worker collectives** — ``psum``/``pmax`` over the people/location
    partition axis, and the visit/exposure halo exchange (``dispatch`` /
    ``combine``: person-partition → location-partition value routing and
    its additive adjoint). On :class:`LocalTopology` these are identity
    collectives: dispatch is a direct gather by person id, combine a
    segment-sum, psum the value itself.
  * **order statistics** — ``seed_threshold``, the global k-th smallest
    uniform draw that outbreak seeding thresholds on. Local: a full sort.
    Worker-sharded: the union of per-worker top-k candidates gathered over
    the axis (bitwise-equal by construction, see core/simulator_dist.py).
  * **scenario-axis reductions** — ``scen_gather`` reassembles the full
    scenario batch from a shard of it, so cross-scenario observables
    (mean/CI bands, Sobol indices) run *inside* the scan body on every
    topology and are bitwise-identical to a host-side reference: every
    shard sees the identical full ``(B,)`` stats vector and applies the
    identical jnp reduction.
  * **mesh placement** — which named axes exist, so the engine core knows
    which shard_map to wrap around the one scan.

The five legacy engine layouts are products of three topologies:

  ==========  =============================================  ===========
  layout      topology                                       batch axis
  ==========  =============================================  ===========
  single      ``LocalTopology()``                            B = 1
  ensemble    ``LocalTopology()``                            B > 1 (vmap)
  dist        ``MeshTopology("workers")``                    B = 1 (vmap)
  sharded     ``ScenarioTopology("scenarios", B)``           sharded
  hybrid      ``MeshTopology * ScenarioTopology``            sharded
  ==========  =============================================  ===========

vmap and shard_map are applied by *composition* around the one scan
(:func:`repro.engine.day.run_days`); no layout hand-writes its own loop.

Adding a new layout = writing a new Topology (see docs/architecture.md).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import exchange as ex_lib


@dataclasses.dataclass(frozen=True)
class Topology:
    """Identity collectives — the single-device placement, and the base
    class every other topology layers named-axis collectives onto.

    Frozen and field-light so instances hash (they are closed over by
    jitted programs and participate in compilation-cache keys).
    """

    #: mesh axis the people/location partition lives on (None = unsharded)
    worker_axis: Optional[str] = None
    #: mesh axis the scenario batch lives on (None = unsharded)
    scenario_axis: Optional[str] = None

    # -- mesh placement -------------------------------------------------
    @property
    def axis_names(self) -> tuple:
        """Named mesh axes, in (workers, scenarios) order."""
        return tuple(
            a for a in (self.worker_axis, self.scenario_axis) if a is not None
        )

    # -- worker collectives ---------------------------------------------
    def worker_index(self):
        """This worker's position on the worker axis (0 when unsharded)."""
        return jnp.asarray(0, jnp.int32)

    def psum(self, x):
        """Sum over the worker axis; identity on the local topology."""
        return x

    def pmax(self, x):
        """Max over the worker axis; identity on the local topology."""
        return x

    # -- halo exchange (visit dispatch / exposure combine) ---------------
    def dispatch(self, route, pid, chans):
        """Route per-person channels to per-visit slots.

        ``chans`` is ``(P_local, ch)``; returns ``(V_local, ch)`` with
        zeros in inactive slots. Locally the visit schedule indexes people
        directly, so dispatch is a gather masked by the ``pid >= 0``
        padding sentinel; worker-sharded it is the capacity-bucketed
        all_to_all of core/exchange.py (``route`` carries send/recv).
        """
        del route
        return chans[jnp.maximum(pid, 0)] * (pid >= 0)[:, None]

    def combine(self, route, pid, active, acc, num_people_local: int):
        """Adjoint of :meth:`dispatch`: additive per-visit propensities
        back to their owning people. Returns ``(P_local,)``."""
        del route
        return jax.ops.segment_sum(
            jnp.where(active, acc, 0.0),
            jnp.maximum(pid, 0),
            num_segments=num_people_local,
        )

    def combine_many(self, route, pid, active, accs, num_people_local: int):
        """Channel-stacked :meth:`combine`: ``accs`` is ``(V_local, C)``,
        returns ``(P_local, C)``, channel ``c`` being ``combine`` of
        ``accs[:, c]`` bit for bit.

        Each channel is summed on its own, never as one segment sum of
        ``(V, C)`` values. Under the scenario vmap that sum is a scatter
        whose window spans the scenario and channel axes, and XLA on the
        TPU lowers such a channel-windowed scatter to a plain scatter
        fusion, not to the sort and sorted reduction it gives a
        single-channel one: on the md twin the two-channel scatter took
        6.9 times one channel's sort-lowered combine (PERF.md).
        ``tests/test_engine.py`` guards the lowering.
        """
        return jnp.stack(
            [self.combine(route, pid, active, accs[:, c], num_people_local)
             for c in range(accs.shape[-1])],
            axis=-1,
        )

    # -- global order statistic for outbreak seeding ----------------------
    def seed_threshold(self, u, seed_per_day, num_people: int, topk: int):
        """The k-th smallest of the global draw vector ``u`` (k =
        min(seed_per_day, num_people)), computed from this worker's local
        shard of ``u``. Local: a full sort. Sharded: see MeshTopology."""
        del topk
        k = jnp.minimum(seed_per_day, num_people) - 1
        return jnp.sort(u)[jnp.maximum(k, 0)]

    # -- global order statistic for the testing-capacity budget ------------
    def rank_threshold(self, score, gpid, k, num_people: int, topk: int):
        """The k-th smallest *(score, gpid)* pair of the global score
        vector, lexicographically — ``(T, G)`` such that exactly
        ``min(k, count(score < 4.0))`` entries satisfy
        ``score < T or (score == T and gpid <= G)``.

        Because ``gpid`` is globally unique, the lexicographic order is
        total: f32 score ties cannot over-select, which makes the
        capacity-limited test budget *exact* (never exceeds k), not
        approximate — and bitwise identical across mesh shapes, the same
        argument as :meth:`seed_threshold`. Local: one full lexsort.
        Sharded: see MeshTopology.
        """
        del topk
        order = jnp.lexsort((gpid, score))
        idx = jnp.clip(jnp.minimum(k, num_people) - 1, 0, order.shape[0] - 1)
        pick = order[idx]
        return score[pick], gpid[pick]

    # -- scenario-axis reductions -----------------------------------------
    def scen_gather(self, x, num_real: Optional[int] = None):
        """Reassemble the full scenario batch from this shard's slice
        (leading axis), dropping padding slots. Identity when the batch
        axis is unsharded (the local batch IS the full batch)."""
        return x if num_real is None else x[:num_real]

    # -- composition ------------------------------------------------------
    def __mul__(self, other: "Topology"):
        """Product of a worker topology and a scenario topology — the
        hybrid placement. ``MeshTopology() * ScenarioTopology()`` is
        today's 2-D hybrid mesh. Returns ``NotImplemented`` for
        unsupported pairs so reflected compositions (``LocalTopology() *
        ScenarioTopology()``) can resolve via ``__rmul__``."""
        if (self.worker_axis is not None and self.scenario_axis is None
                and other.scenario_axis is not None
                and other.worker_axis is None):
            return ProductTopology(
                worker_axis=self.worker_axis,
                scenario_axis=other.scenario_axis,
            )
        return NotImplemented


class LocalTopology(Topology):
    """Single-device placement: every collective is the identity."""


@dataclasses.dataclass(frozen=True)
class MeshTopology(Topology):
    """People/locations sharded over a named worker axis: psums are real,
    the halo exchange is the capacity-bucketed all_to_all, and the seeding
    order statistic gathers per-worker top-k unions."""

    worker_axis: Optional[str] = "workers"

    def worker_index(self):
        return jax.lax.axis_index(self.worker_axis)

    def psum(self, x):
        return jax.lax.psum(x, self.worker_axis)

    def pmax(self, x):
        return jax.lax.pmax(x, self.worker_axis)

    def dispatch(self, route, pid, chans):
        send, recv = route
        return ex_lib.dispatch(send, recv, chans, pid.shape[0],
                               self.worker_axis)

    def combine(self, route, pid, active, acc, num_people_local: int):
        send, recv = route
        return ex_lib.combine(
            send, recv, acc[:, None] * active[:, None], num_people_local,
            self.worker_axis,
        )[:, 0]

    def combine_many(self, route, pid, active, accs, num_people_local: int):
        send, recv = route
        return ex_lib.combine(
            send, recv, accs * active[:, None], num_people_local,
            self.worker_axis,
        )

    def seed_threshold(self, u, seed_per_day, num_people: int, topk: int):
        # Union of per-worker top-k smallest draws: topk >=
        # min(seed_per_day, P_local) guarantees the global k-th smallest
        # is inside the gathered union, so the threshold is bitwise
        # identical to the local full sort (tests/test_dist.py).
        local_small = -jax.lax.top_k(-u, topk)[0]
        all_small = jnp.sort(
            jax.lax.all_gather(local_small, self.worker_axis).reshape(-1)
        )
        k = jnp.minimum(seed_per_day, num_people) - 1
        return all_small[jnp.clip(k, 0, all_small.shape[0] - 1)]

    def rank_threshold(self, score, gpid, k, num_people: int, topk: int):
        # Per-worker lexicographic top-k candidates, gathered and re-ranked
        # globally. topk >= min(k, P_local) guarantees the global k-th
        # smallest pair is inside the union (identical argument to
        # seed_threshold), so the result is bitwise equal to the local
        # full lexsort on the unsharded score vector.
        order = jnp.lexsort((gpid, score))
        cand = order[:topk]
        g_score = jax.lax.all_gather(
            score[cand], self.worker_axis
        ).reshape(-1)
        g_gpid = jax.lax.all_gather(
            gpid[cand], self.worker_axis
        ).reshape(-1)
        g_order = jnp.lexsort((g_gpid, g_score))
        idx = jnp.clip(
            jnp.minimum(k, num_people) - 1, 0, g_order.shape[0] - 1
        )
        pick = g_order[idx]
        return g_score[pick], g_gpid[pick]


@dataclasses.dataclass(frozen=True)
class ScenarioTopology(Topology):
    """Scenario batch sharded over a named axis; people stay local.
    Scenarios are independent, so the day loop itself needs no
    collectives — only the in-scan cross-scenario observables do, through
    :meth:`scen_gather`."""

    scenario_axis: Optional[str] = "scenarios"

    def scen_gather(self, x, num_real: Optional[int] = None):
        full = jax.lax.all_gather(x, self.scenario_axis, axis=0, tiled=True)
        return full if num_real is None else full[:num_real]

    def __rmul__(self, other):  # Local * Scenario == Scenario
        if isinstance(other, LocalTopology):
            return self
        return NotImplemented


@dataclasses.dataclass(frozen=True)
class ProductTopology(MeshTopology):
    """workers × scenarios: worker collectives from MeshTopology plus the
    scenario gather from ScenarioTopology (the hybrid placement)."""

    scenario_axis: Optional[str] = "scenarios"

    scen_gather = ScenarioTopology.scen_gather


def make_topology(worker_axis: Optional[str],
                  scenario_axis: Optional[str]) -> Topology:
    """The four placements, by which named axes exist."""
    if worker_axis and scenario_axis:
        return ProductTopology(worker_axis=worker_axis,
                               scenario_axis=scenario_axis)
    if worker_axis:
        return MeshTopology(worker_axis=worker_axis)
    if scenario_axis:
        return ScenarioTopology(scenario_axis=scenario_axis)
    return LocalTopology()
