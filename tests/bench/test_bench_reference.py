"""The plain reference's pair walk against brute force, and the reference
against the program at surge-like traffic.

The walk (``bench.reference.walk_pairs``) enumerates each row's partners
as the window of its location's visits, by size class. Brute force here
looks at every (j, i) pair of a tiny hand-made day, O(V^2), with the
model's rule written out in numpy. The day has a location wider than
any size class, a location with one visit, pairs whose times only touch,
a person who visits one location twice, a closed location and padding.
"""

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench import harness, reference  # noqa: E402
from test_bench_harness import throwaway_root  # noqa: E402

#: The widest window walked in one piece here: location 1's eleven visits
#: are wider than any size class and are walked in three chunks.
WIDTH = 4

# One day's visits as (person, location, start, end), sorted by location.
VISITS = [
    (0, 0, 0.0, 50.0),  # alone at location 0
    (1, 1, 0.0, 30.0), (2, 1, 10.0, 40.0), (3, 1, 30.0, 60.0),
    (1, 1, 50.0, 70.0),  # person 1 again
    (4, 1, 60.0, 90.0),  # touches person 3's visit: no overlap
    (5, 1, 5.0, 95.0), (6, 1, 20.0, 25.0), (7, 1, 0.0, 100.0),
    (8, 1, 40.0, 45.0), (9, 1, 70.0, 80.0), (10, 1, 15.0, 65.0),
    (2, 2, 0.0, 50.0), (5, 2, 0.0, 50.0), (11, 2, 10.0, 20.0),  # closed
    (3, 3, 0.0, 10.0), (6, 3, 10.0, 20.0),  # touching only
    (4, 4, 0.0, 40.0), (8, 4, 20.0, 60.0), (9, 4, 30.0, 35.0),
    (11, 4, 0.0, 100.0),
]
PADDING = 3
P, L = 12, 6
SUS = np.array([1, 0, 1, 1, .5, 1, 0, 1, 1, 1, .25, 1], np.float32)
INF = np.array([1, .8, 0, 1, 0, .5, 1, 0, 0, 1, 1, 0], np.float32)
POSITIVE = np.array([1, 0, 0, 1, 0, 0, 1, 0, 0, 0, 1, 0], bool)
OPEN = np.array([1, 1, 0, 1, 1, 1], bool)
PROB = np.array([1, 1, 1, 1, .5, 1], np.float32)
SEED, DAY = 2 ** 31 + 17, 9


def _day():
    person, loc, start, end = (np.array(c) for c in zip(*VISITS))
    n = len(VISITS)
    pad = lambda a, v, t: np.concatenate([a, np.full(PADDING, v)]).astype(t)
    person = pad(person, -1, np.int32)
    loc = pad(loc, 0, np.int32)
    start, end = pad(start, 0, np.float32), pad(end, 0, np.float32)
    wfirst, wsize, _ = reference.locations_of_day(loc, n, L)
    return {"person": person, "loc": loc, "start": start, "end": end,
            "wfirst": wfirst, "wsize": wsize}, n


def _classes(day):
    return reference.size_classes(int(day["wsize"].max()), width_max=WIDTH)


def test_windows_and_classes():
    day, n = _day()
    loc = day["loc"]
    for v in range(n):
        same = np.flatnonzero((loc[:n] == loc[v]))
        assert day["wfirst"][v] == same[0] and day["wsize"][v] == len(same)
    assert (day["wsize"][n:] == 0).all()
    _, _, last = reference.locations_of_day(loc, n, L)
    np.testing.assert_array_equal(last, [1, 23, 29, 33, 41, -1])
    assert _classes(day) == ((1, 1), (2, 1), (4, 1), (4, 3))
    assert reference.size_classes(11) == ((1, 1), (2, 1), (4, 1), (8, 1),
                                          (16, 1))
    assert reference.size_classes(1) == ((1, 1),)


def _peak_brute_force(loc, start, end, n):
    """The most visits present at once at each location: at each arrival
    time t, the visits with start <= t < end."""
    peak = np.zeros(L, np.int32)
    for v in range(n):
        same = loc[:n] == loc[v]
        here = same & (start[:n] <= start[v]) & (start[v] < end[:n])
        peak[loc[v]] = max(peak[loc[v]], here.sum())
    return peak


@pytest.mark.parametrize("times", ["hand_made", "random_with_ties"])
def test_peak_occupancy_matches_brute_force(times):
    day, n = _day()
    loc, start, end = day["loc"], day["start"], day["end"]
    if times == "random_with_ties":
        rng = np.random.default_rng(5)
        start = rng.integers(0, 6, len(loc)).astype(np.float32)
        end = start + rng.integers(0, 4, len(loc)).astype(np.float32)
    _, _, last = reference.locations_of_day(loc, n, L)
    got = reference._peak_one_day(jnp.asarray(loc), jnp.asarray(start),
                                  jnp.asarray(end),
                                  jnp.asarray(day["person"] >= 0),
                                  jnp.asarray(last))
    want = _peak_brute_force(loc, start, end, n)
    assert want.max() >= 3
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("rows", ["every_real_visit", "some"])
def test_walk_pairs_enumerates_each_pair_once(rows, monkeypatch):
    monkeypatch.setattr(reference, "PAIR_BLOCK", 8)  # several blocks a class
    day, n = _day()
    V = len(day["loc"])
    real = np.arange(V) < n
    is_row = real if rows == "every_real_visit" else real & (
        np.arange(V) % 3 != 1)

    def visit(m, j, i, ok):
        jj = jnp.broadcast_to(j[:, None], i.shape)
        return m.at[jj, i].add(ok.astype(jnp.int32))

    m = reference.walk_pairs(_classes(day), jnp.asarray(day["wfirst"]),
                             jnp.asarray(day["wsize"]), jnp.asarray(is_row),
                             visit, jnp.zeros((V, V), jnp.int32))
    loc = day["loc"]
    want = (is_row[:, None] & real[None, :]
            & (loc[:, None] == loc[None, :])).astype(np.int32)
    np.testing.assert_array_equal(np.asarray(m), want)


def _brute_force(day, n, f=np.float32):
    """Exposure, contacts and traced counts by looking at every pair."""
    acc = np.zeros(P, np.float64)
    trc = np.zeros(P, np.int32)
    contacts = 0
    person, loc = day["person"], day["loc"]
    start, end = day["start"], day["end"]
    for j in range(n):
        pj, lj = person[j], loc[j]
        if not (OPEN[lj] and INF[pj] > 0):
            continue
        for i in range(n):
            pi = person[i]
            if loc[i] != lj or pi == pj or SUS[pi] <= 0:
                continue
            overlap = f(max(min(end[i], end[j]) - max(start[i], start[j]),
                            0))
            if overlap <= 0:
                continue
            u = reference.draw_host(SEED, reference.CONTACT, DAY,
                                    int(min(pi, pj)), int(max(pi, pj)),
                                    int(lj))
            if not u < PROB[lj]:
                continue
            contacts += 1
            acc[pi] += f(f(overlap * SUS[pi]) * INF[pj])
            trc[pi] += int(POSITIVE[pj])
    return acc, contacts, trc


def test_exposure_matches_brute_force(monkeypatch):
    monkeypatch.setattr(reference, "PAIR_BLOCK", 8)
    monkeypatch.setattr(reference, "DRAWN_BLOCK", 4)  # several steps a block
    day, n = _day()
    st = reference.Structure(num_people=P, num_locations=L, classic=(),
                             tti=(True,), seed_per_day=0, dtype="float32",
                             classes=_classes(day))
    acc, contacts, trc = reference.exposure(
        st, jax.tree.map(jnp.asarray, day), jnp.asarray(PROB),
        jnp.asarray(SUS), jnp.asarray(INF), jnp.asarray(OPEN),
        jnp.asarray(POSITIVE), np.uint32(SEED), jnp.int32(DAY))
    want_acc, want_contacts, want_trc = _brute_force(day, n)
    assert want_contacts > 10 and (want_trc > 0).sum() >= 3
    assert int(contacts) == want_contacts
    np.testing.assert_array_equal(np.asarray(trc), want_trc)
    np.testing.assert_allclose(np.asarray(acc), want_acc, rtol=1e-6)
    assert acc[0] == 0  # alone at location 0


@pytest.mark.parametrize("config", ["md-ens", "md-tti"])
def test_reference_agrees_with_program_at_surge(tmp_path, config):
    """Twin-2k seeded at 1% a day for a week, the window on days 7-13:
    many infectious visits, every arm's interventions in play."""
    root = throwaway_root(str(tmp_path), config=config)
    run, line = harness.run_cell(root, "tiny.light", 2 ** 31 + 4242, 0.2,
                                 False, t_start=time.perf_counter())
    assert line["correct"] is True, line["checks"]
    assert run.compared["state_mismatch"] == 0.0
    assert run.compared["stat_gap"] == 0.0
    assert run.edges_per_chunk > 1000
    assert run.reference_s > 0
