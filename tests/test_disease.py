import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import disease


@pytest.mark.parametrize("model", [disease.covid_model(), disease.sir_model(), disease.seir_model()])
def test_models_valid(model):
    model.validate()
    assert model.susceptibility[model.initial_state] > 0
    assert model.susceptibility[model.entry_state] == 0


def test_seeding_exact_count():
    m = disease.covid_model()
    state, dwell = disease.initial_health(m, 500)
    state, dwell = disease.seed_infections(m, state, dwell, 10, 1, 0)
    assert int((np.asarray(state) == m.entry_state).sum()) == 10


def test_progression_reaches_recovered():
    m = disease.covid_model()
    P = 200
    state, dwell = disease.initial_health(m, P)
    state, dwell = disease.seed_infections(m, state, dwell, 50, 1, 0)
    for day in range(1, 60):
        none = jnp.zeros((P,), bool)
        state, dwell = disease.update_health(m, state, dwell, none, 1, day)
    final = np.bincount(np.asarray(state), minlength=m.num_states)
    R = m.state_index("R")
    assert final[R] == 50  # everyone seeded eventually recovers
    assert final[m.initial_state] == P - 50  # no spontaneous infections


def test_infection_only_from_susceptible():
    m = disease.sir_model()
    P = 10
    state = jnp.full((P,), m.state_index("R"), jnp.int32)
    dwell = jnp.full((P,), disease.ABSORBING_DWELL, jnp.float32)
    all_inf = jnp.ones((P,), bool)
    s2, _ = disease.update_health(m, state, dwell, all_inf, 0, 0)
    assert (np.asarray(s2) == m.state_index("R")).all()


def test_branching_fractions():
    m = disease.covid_model()
    P = 20000
    ipre = m.state_index("Ipre")
    state = jnp.full((P,), ipre, jnp.int32)
    dwell = jnp.full((P,), 0.5, jnp.float32)  # expire today
    s2, _ = disease.update_health(m, state, dwell, jnp.zeros((P,), bool), 3, 11)
    counts = np.bincount(np.asarray(s2), minlength=m.num_states)
    frac_sym = counts[m.state_index("Isym")] / P
    assert abs(frac_sym - 0.65) < 0.02


def test_dwell_minimum_one_day():
    m = disease.covid_model()
    P = 1000
    state, dwell = disease.initial_health(m, P)
    s2, d2 = disease.update_health(
        m, state, dwell, jnp.ones((P,), bool), 0, 0
    )
    d2 = np.asarray(d2)
    assert (d2[np.asarray(s2) == m.entry_state] >= 1.0).all()


# ---------------------------------------------------------------------------
# lookup: a per-state table read as a select over the S states, bit for bit
# the gather ``table[h]`` (negative codes from the end, the rest clamped)
# ---------------------------------------------------------------------------

MODELS = {"covid": disease.covid_model, "sir": disease.sir_model,
          "seir": disease.seir_model}


def _tables(m, shape):
    if shape == "rows":
        return [m.cum_trans]
    return [m.susceptibility, m.infectivity, m.sym_table, m.dwell_mean_days]


def _codes(S, n):
    edge = [-2**31, -S - 1, -S, -1, S, S + 1, 2**31 - 1]
    return np.asarray(edge + [i % S for i in range(n)], np.int32)


def _bits(x):
    return np.asarray(x).view(np.uint32)


@pytest.mark.parametrize("shape", ["column", "rows"])
@pytest.mark.parametrize("name", sorted(MODELS))
def test_lookup_equals_gather(name, shape):
    m = MODELS[name]()
    S = m.num_states
    h = jnp.asarray(_codes(S, 3 * S))
    for t in _tables(m, shape):
        t = jnp.asarray(t)
        got = jax.jit(disease.lookup)(t, h)
        assert got.shape == h.shape + t.shape[1:]
        np.testing.assert_array_equal(_bits(got), _bits(t[h]))


@pytest.mark.parametrize("shape", ["column", "rows"])
@pytest.mark.parametrize("name", sorted(MODELS))
def test_lookup_equals_gather_under_vmap(name, shape):
    m = MODELS[name]()
    S, B = m.num_states, 3
    rng = np.random.default_rng(S)
    h = jnp.asarray(np.stack([rng.permutation(_codes(S, 4 * S))
                              for _ in range(B)]))
    gather = jax.jit(jax.vmap(lambda t, c: t[c]))
    select = jax.jit(jax.vmap(disease.lookup))
    for t in _tables(m, shape):
        # per-scenario tables that differ, -0.0 among them
        tb = jnp.asarray(np.stack([t * np.float32(1 - b) for b in range(B)]))
        np.testing.assert_array_equal(_bits(select(tb, h)),
                                      _bits(gather(tb, h)))
