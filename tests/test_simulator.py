import numpy as np
import pytest

from repro.core import disease, simulator, transmission
from repro.data import digital_twin_population
from repro.engine.core import EngineCore, state_to_tree

import jax.numpy as jnp


def make_sim(pop, *, seed, **kw):
    return EngineCore.single(
        pop, disease.covid_model(),
        transmission.TransmissionModel(tau=1.5e-5), seed=seed, **kw,
    )


@pytest.fixture(scope="module")
def pop():
    return digital_twin_population(1500, seed=2, name="t1500")


@pytest.fixture(scope="module")
def run60(pop):
    sim = make_sim(pop, seed=11)
    final, hist = sim.run1(60)
    return sim, final, hist


def test_monotone_cumulative(run60):
    _, _, hist = run60
    assert (np.diff(hist["cumulative"]) >= 0).all()


def test_population_conserved(run60):
    sim, final, hist = run60
    S = sim.batch[0].disease.num_states
    counts = np.bincount(np.asarray(final.health), minlength=S)
    assert counts.sum() == sim.pop.num_people


def test_bounded_by_population(run60):
    sim, _, hist = run60
    assert hist["cumulative"][-1] <= sim.pop.num_people
    assert (hist["infectious"] <= sim.pop.num_people).all()


def test_epidemic_occurs(run60):
    _, _, hist = run60
    assert hist["cumulative"][-1] > 100  # outbreak took off
    assert hist["contacts"].sum() > 0


def test_same_seed_identical(pop):
    h1 = make_sim(pop, seed=5).run1(20)[1]
    h2 = make_sim(pop, seed=5).run1(20)[1]
    np.testing.assert_array_equal(h1["cumulative"], h2["cumulative"])
    np.testing.assert_array_equal(h1["contacts"], h2["contacts"])


def test_different_seed_differs(pop):
    h1 = make_sim(pop, seed=5).run1(25)[1]
    h2 = make_sim(pop, seed=6).run1(25)[1]
    assert not np.array_equal(h1["cumulative"], h2["cumulative"])


def test_backends_agree_end_to_end(pop):
    hists = {}
    for backend in ("jnp", "scan", "compact"):
        hists[backend] = make_sim(pop, seed=5, backend=backend).run1(15)[1]
    for backend in ("scan", "compact"):
        np.testing.assert_array_equal(
            hists["jnp"]["cumulative"], hists[backend]["cumulative"]
        )
        np.testing.assert_array_equal(
            hists["jnp"]["contacts"], hists[backend]["contacts"]
        )


def test_packed_and_unpacked_layouts_agree(pop):
    """Occupancy-aware packing is epidemiologically inert end-to-end: the
    packed (default) and canonical layouts produce the same trajectory."""
    h_packed = make_sim(pop, seed=5, pack_visits=True).run1(15)[1]
    h_plain = make_sim(pop, seed=5, pack_visits=False).run1(15)[1]
    np.testing.assert_array_equal(h_packed["cumulative"], h_plain["cumulative"])
    np.testing.assert_array_equal(h_packed["contacts"], h_plain["contacts"])


def test_static_network_weekly_repeat(pop):
    """EpiHiper-mode: contact draws keyed by day-of-week => with everyone
    infectious+susceptible held fixed, contacts repeat weekly."""
    tm = transmission.TransmissionModel(tau=0.0)  # no state evolution
    sim = EngineCore.single(
        pop, disease.covid_model(), tm, seed=5, static_network=True,
        seed_per_day=0, seed_days=0,
    )
    # make everyone mildly infectious & susceptible so contacts are counted
    state = sim.init_state1()
    import dataclasses as dc
    # seed a fixed set of infectious people via the disease model
    h = np.zeros(pop.num_people, np.int32)
    h[:50] = sim.batch[0].disease.state_index("Isym")
    state = dc.replace(
        state, health=jnp.asarray(h),
        dwell=jnp.full((pop.num_people,), 1e9, jnp.float32),
    )
    _, hist = sim.run1(14, state=state)
    c = hist["contacts"]
    np.testing.assert_array_equal(c[:7], c[7:14])


def test_checkpoint_restore_exact(pop):
    sim = make_sim(pop, seed=5)
    s_mid, h1 = sim.run1(10)
    payload = {k: np.asarray(v) for k, v in state_to_tree(s_mid).items()}
    # run 10 more from the round-tripped checkpoint payload
    restored = simulator.SimState(
        **{k: jnp.asarray(v) for k, v in payload.items()}
    )
    _, h_resumed = sim.run1(10, state=restored)
    _, h_full = sim.run1(20)
    np.testing.assert_array_equal(
        h_full["cumulative"][10:], h_resumed["cumulative"]
    )
