"""Disease models as finite state automata (paper §III-A1).

Each state carries a susceptibility sigma and infectivity iota. Transitions
are stochastic both in the next state (categorical) and in dwell time
(exponential around a per-state mean, matching "non-deterministic both in
terms of the state transitioned to and how long a person remains").

The FSA is represented with small dense tables so the per-day update is a
handful of vectorized table reads over the (P,) person-state arrays — no
per-agent control flow, which is the TPU-native replacement for the paper's
per-person FSA objects stored on Charm++ node groups (here the tables live
replicated on every device, the moral equivalent of a node group).

Every per-person read of a table goes through :func:`lookup`, a select
over the S states rather than a gather: a TPU runs a gather from a
6-entry table at about 11 ns an element (62 ms for 22M people on a v5e),
while S compare-selects per element run at the speed of memory.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import numpy as np
import jax.numpy as jnp

from repro.core import rng

# Dwell value treated as "never times out" (absorbing states).
ABSORBING_DWELL = 1.0e9


@dataclasses.dataclass(frozen=True)
class DiseaseModel:
    """Immutable FSA description. All tables are small numpy arrays; they are
    closed over by the jitted day step (replicated constants on device)."""

    name: str
    states: tuple[str, ...]
    susceptibility: np.ndarray  # (S,) f32, sigma(X)
    infectivity: np.ndarray  # (S,) f32, iota(X)
    trans_probs: np.ndarray  # (S, S) f32, rows sum to 1 (absorbing: self=1)
    dwell_mean_days: np.ndarray  # (S,) f32; ABSORBING_DWELL for absorbing
    entry_state: int  # state entered on infection (e.g. E)
    initial_state: int  # state people start in (e.g. S)
    # Optional (S,) f32 mask of *symptomatic* states: the testing-priority
    # tier for per-agent interventions. None = "any infectious state".
    symptomatic: Optional[np.ndarray] = None

    @property
    def num_states(self) -> int:
        return len(self.states)

    @property
    def sym_table(self) -> np.ndarray:
        """(S,) f32: 1.0 for states that present symptoms (test priority)."""
        if self.symptomatic is not None:
            return np.asarray(self.symptomatic, np.float32)
        return (self.infectivity > 0).astype(np.float32)

    def state_index(self, name: str) -> int:
        return self.states.index(name)

    @property
    def cum_trans(self) -> np.ndarray:
        return np.cumsum(self.trans_probs, axis=-1).astype(np.float32)

    @property
    def infectious_mask(self) -> np.ndarray:
        return (self.infectivity > 0).astype(np.bool_)

    @property
    def susceptible_mask(self) -> np.ndarray:
        return (self.susceptibility > 0).astype(np.bool_)

    def validate(self) -> None:
        S = self.num_states
        assert self.trans_probs.shape == (S, S)
        np.testing.assert_allclose(self.trans_probs.sum(-1), 1.0, atol=1e-5)
        assert 0 <= self.entry_state < S and 0 <= self.initial_state < S


def make_disease(
    name: str,
    states: Sequence[str],
    susceptibility: Sequence[float],
    infectivity: Sequence[float],
    transitions: dict[str, dict[str, float]],
    dwell_mean_days: dict[str, float],
    entry_state: str,
    initial_state: str,
    symptomatic: Optional[Sequence[str]] = None,
) -> DiseaseModel:
    """Friendly constructor from dicts (the moral equivalent of the paper's
    Protobuf disease-model input format; see configs/ for concrete models)."""
    states = tuple(states)
    S = len(states)
    idx = {s: i for i, s in enumerate(states)}
    tp = np.zeros((S, S), np.float32)
    for s, outs in transitions.items():
        for t, p in outs.items():
            tp[idx[s], idx[t]] = p
    for i in range(S):
        if tp[i].sum() == 0.0:  # absorbing
            tp[i, i] = 1.0
    dwell = np.full((S,), ABSORBING_DWELL, np.float32)
    for s, d in dwell_mean_days.items():
        dwell[idx[s]] = d
    sym = None
    if symptomatic is not None:
        sym = np.zeros((S,), np.float32)
        for s in symptomatic:
            sym[idx[s]] = 1.0
    m = DiseaseModel(
        name=name,
        states=states,
        susceptibility=np.asarray(susceptibility, np.float32),
        infectivity=np.asarray(infectivity, np.float32),
        trans_probs=tp,
        dwell_mean_days=dwell,
        entry_state=idx[entry_state],
        initial_state=idx[initial_state],
        symptomatic=sym,
    )
    m.validate()
    return m


def covid_model() -> DiseaseModel:
    """Expanded SEIR tuned to represent COVID-19 (paper §III-A1): exposed,
    presymptomatic, symptomatic/asymptomatic branch, recovered."""
    return make_disease(
        name="covid-seir+",
        states=("S", "E", "Ipre", "Isym", "Iasym", "R"),
        susceptibility=[1.0, 0.0, 0.0, 0.0, 0.0, 0.0],
        infectivity=[0.0, 0.0, 0.8, 1.0, 0.5, 0.0],
        transitions={
            "E": {"Ipre": 1.0},
            "Ipre": {"Isym": 0.65, "Iasym": 0.35},
            "Isym": {"R": 1.0},
            "Iasym": {"R": 1.0},
        },
        dwell_mean_days={"E": 3.0, "Ipre": 2.0, "Isym": 5.0, "Iasym": 4.0},
        entry_state="E",
        initial_state="S",
        symptomatic=["Isym"],
    )


def sir_model(recovery_days: float = 7.0) -> DiseaseModel:
    """Simple SIR used for the EpiHiper validation study (paper §VI/§VIII)."""
    return make_disease(
        name="sir",
        states=("S", "I", "R"),
        susceptibility=[1.0, 0.0, 0.0],
        infectivity=[0.0, 1.0, 0.0],
        transitions={"I": {"R": 1.0}},
        dwell_mean_days={"I": recovery_days},
        entry_state="I",
        initial_state="S",
    )


def seir_model() -> DiseaseModel:
    """Classic SEIR (FRED-style fixed pipeline) — used in ablations."""
    return make_disease(
        name="seir",
        states=("S", "E", "I", "R"),
        susceptibility=[1.0, 0.0, 0.0, 0.0],
        infectivity=[0.0, 0.0, 1.0, 0.0],
        transitions={"E": {"I": 1.0}, "I": {"R": 1.0}},
        dwell_mean_days={"E": 3.0, "I": 6.0},
        entry_state="E",
        initial_state="S",
    )


# ----------------------------------------------------------------------------
# Vectorized per-day FSA update
# ----------------------------------------------------------------------------


def lookup(table: jnp.ndarray, state: jnp.ndarray) -> jnp.ndarray:
    """``table[state]`` for a per-state table, read without a gather.

    ``table`` is (S, ...) — an (S,) column or the (S, S) ``cum_trans``
    rows — and ``state`` holds per-person int32 state codes; the result
    has shape ``state.shape + table.shape[1:]``. The read unrolls over the
    static S as a chain of ``jnp.where(state == s, table[s], ...)``, so
    each element costs S compare-selects and no gather. The value is the
    gather's bit for bit for every int32 code: a negative code counts from
    the end, as in NumPy, and what is still out of range is clamped, as
    XLA's gather does. Under ``vmap`` a (B, S) table's ``table[s]`` is a
    slice, which broadcasts against the (B, P) codes.
    """
    S = table.shape[0]
    state = jnp.where(state < 0, state + S, state)
    tail = (1,) * (table.ndim - 1)
    at = lambda hit: hit.reshape(hit.shape + tail)
    out = jnp.broadcast_to(table[S - 1], state.shape + table.shape[1:])
    for s in range(S - 2, 0, -1):
        out = jnp.where(at(state == s), table[s], out)
    return jnp.where(at(state <= 0), table[0], out)


def initial_health(model: DiseaseModel, num_people: int):
    """(state, dwell_left) arrays for a fresh population."""
    state = jnp.full((num_people,), model.initial_state, jnp.int32)
    dwell = jnp.full((num_people,), ABSORBING_DWELL, jnp.float32)
    return state, dwell


def update_health_tables(
    cum_trans: jnp.ndarray,  # (S, S) cumulative transition rows
    dwell_mean: jnp.ndarray,  # (S,)
    susceptibility: jnp.ndarray,  # (S,)
    entry_state,  # scalar int32 (may be traced — scenario-ensemble path)
    state: jnp.ndarray,  # (P,) int32
    dwell_left: jnp.ndarray,  # (P,) f32 days remaining in current state
    newly_infected: jnp.ndarray,  # (P,) bool
    seed,
    day,
    pid=None,  # (P,) uint32 ids for the draws; default = arange (global ids)
):
    """End-of-day health update (Algorithm 2 line 30), table-driven.

    Order matters and matches the serial algorithm: infections landed this
    day take precedence (a susceptible cannot also make a timed transition),
    then timed transitions fire for anyone whose dwell expired.

    Every disease-model input is a (traceable) array, which makes this the
    FSA update used under vmap-over-scenarios where each scenario carries
    perturbed tables (:mod:`repro.engine`). Draws are keyed on ``pid`` —
    the distributed engine passes each worker's *global* person ids so a
    sharded update is bitwise identical to the single-device one.
    """
    if pid is None:
        pid = jnp.arange(state.shape[0], dtype=jnp.uint32)

    # Timed transition draws (only applied where dwell expires).
    next_state = rng.categorical(
        lookup(cum_trans, state), seed, rng.TRANSITION, day, pid
    )
    dwell_after = dwell_left - 1.0
    timed = dwell_after <= 0.0

    state_t = jnp.where(timed, next_state, state)
    # Infection overrides: susceptible -> entry state.
    can_infect = lookup(susceptibility, state) > 0.0
    infected = newly_infected & can_infect
    state_new = jnp.where(infected, entry_state, state_t)

    changed = infected | (timed & (state_new != state))
    mean_new = lookup(dwell_mean, state_new)
    new_dwell = rng.exponential(mean_new, seed, rng.DWELL, day, pid)
    # Keep at least one day in any transient state (paper's day granularity).
    new_dwell = jnp.maximum(new_dwell, 1.0)
    new_dwell = jnp.where(
        mean_new >= ABSORBING_DWELL, ABSORBING_DWELL, new_dwell
    )
    dwell_out = jnp.where(changed, new_dwell, dwell_after)
    return state_new, dwell_out


def update_health(
    model: DiseaseModel,
    state: jnp.ndarray,  # (P,) int32
    dwell_left: jnp.ndarray,  # (P,) f32 days remaining in current state
    newly_infected: jnp.ndarray,  # (P,) bool
    seed,
    day,
):
    """Model-object convenience wrapper over :func:`update_health_tables`."""
    return update_health_tables(
        jnp.asarray(model.cum_trans),
        jnp.asarray(model.dwell_mean_days),
        jnp.asarray(model.susceptibility),
        model.entry_state,
        state,
        dwell_left,
        newly_infected,
        seed,
        day,
    )


def seed_infections(
    model: DiseaseModel,
    state: jnp.ndarray,
    dwell_left: jnp.ndarray,
    num_to_seed: int,
    seed,
    day,
):
    """Infect ~num_to_seed random susceptible people (paper: 10/day for the
    first week). Partition-invariant: the chosen people are the ones with the
    smallest hash draw, a global order-statistic independent of sharding."""
    P = state.shape[0]
    pid = jnp.arange(P, dtype=jnp.uint32)
    u = rng.uniform(seed, rng.SEED_CHOICE, day, pid)
    sus = lookup(jnp.asarray(model.susceptibility), state) > 0.0
    u = jnp.where(sus, u, 2.0)  # non-susceptible sort last
    # threshold = (num_to_seed)-th smallest draw
    thresh = jnp.sort(u)[jnp.minimum(num_to_seed, P) - 1]
    chosen = (u <= thresh) & sus
    return update_health(model, state, dwell_left, chosen, seed, day)
