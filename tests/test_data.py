import numpy as np
import pytest

from repro.core import population as pop_lib
from repro.data import (
    digital_twin_population,
    grid_population,
    watts_strogatz_population,
)


def test_ws_population_counts():
    pop = watts_strogatz_population(2000, 500, seed=0)
    assert pop.num_people == 2000
    assert pop.num_locations == 500
    # paper: 5-7 visits per person per day
    for d in pop.week:
        per_person = d.num_real / pop.num_people
        assert 4.9 <= per_person <= 7.1
    # visits sorted by location
    for d in pop.week:
        loc = d.loc[: d.num_real]
        assert (np.diff(loc) >= 0).all()


def test_ws_home_assignment_min_one():
    pop = watts_strogatz_population(300, 200, seed=1)
    counts = np.bincount(pop.home_loc, minlength=200)
    assert counts.sum() == 300
    assert (counts >= 1).all()


def test_grid_population():
    pop = grid_population(20, 20, density=3.0, seed=0)
    assert pop.num_locations == 400
    assert pop.num_people == 1200
    stats = pop.stats()
    assert 3.0 < stats["mean_visits_per_person_day"] < 7.0  # ~lambda 5.2


def test_twin_structure():
    pop = digital_twin_population(3000, seed=0)
    assert pop.num_people == 3000
    assert set(np.unique(pop.loc_type)) <= {0, 1, 2, 3}
    # geo keys sorted by hierarchy give contiguous partitions
    assert pop.geo_key.min() >= 0
    # weekday visits exceed weekend visits (work+school structure)
    weekday = pop.week[0].num_real
    weekend = pop.week[6].num_real
    assert weekday > weekend


def test_balanced_partition_better_than_naive():
    pop = digital_twin_population(4000, seed=1)
    visits = np.zeros(pop.num_locations, np.int64)
    for d in pop.week:
        np.add.at(visits, d.loc[: d.num_real], 1)
    W = 16
    bal = pop_lib.balanced_location_partition(pop.geo_key, visits, W)
    naive = pop_lib.naive_location_partition(pop.num_locations, W)
    imb_b = pop_lib.partition_imbalance(bal, visits, W)
    imb_n = pop_lib.partition_imbalance(naive, visits, W)
    assert imb_b < imb_n
    assert imb_b < 1.6  # near-balanced


def test_pack_day_padding():
    d = pop_lib.pack_day(
        np.array([3, 1]), np.array([5, 2]),
        np.array([1.0, 2.0], np.float32), np.array([9.0, 8.0], np.float32),
        pad_multiple=128,
    )
    assert len(d) == 128
    assert d.num_real == 2
    assert (d.person[2:] == -1).all()
    assert not d.active[2:].any()
    assert (np.diff(d.loc[:2]) >= 0).all()


def test_preprocess_records_packing_stats():
    pop = digital_twin_population(600, seed=4, name="prep")
    stats = pop.preprocess(block_size=64)
    assert stats is pop.preprocess_stats
    pk = stats["packing"]
    assert pk["block_size"] == 64
    assert 0 < pk["np_after"] <= pk["np_before"]
    assert pk["np_reduction"] >= 1.0
    # contact model was (re)finalized as part of preprocessing
    assert (pop.contact_prob > 0).all() and (pop.contact_prob <= 1).all()


def test_occupancy_packing_giant_alignment():
    """A giant location preceded by a small one gets block-aligned, so its
    band does not absorb the small run's block."""
    b = 32
    n_small, n_giant = 10, 3 * b
    person = np.arange(n_small + n_giant)
    loc = np.concatenate([np.zeros(n_small, np.int64),
                          np.ones(n_giant, np.int64)])
    start = np.zeros(n_small + n_giant, np.float32)
    end = np.full(n_small + n_giant, 10.0, np.float32)
    day = pop_lib.pack_day(person, loc, start, end, pad_multiple=b)
    sched_u = pop_lib.build_block_schedule(day.loc, day.num_real, b)
    packed = pop_lib.pack_day_occupancy(day, b)
    sched_p = pop_lib.build_block_schedule(packed.loc, packed.extent, b)
    # unpacked: giant straddles 4 blocks -> 16 tiles + small's 1 (shared);
    # packed: giant exactly 3 blocks (9 tiles) + small's own block (1).
    assert sched_p.num_pairs == 10
    assert sched_u.num_pairs > sched_p.num_pairs
    # giant run starts on a block boundary
    giant_slots = np.flatnonzero(
        (packed.person >= 0) & (packed.loc == 1)
    )
    assert giant_slots[0] % b == 0


def _first_fit_loop(loc_sorted, b):
    """The run-at-a-time first-fit-decreasing packing loop that
    ``occupancy_pack_order`` replaces, kept verbatim as its reference."""
    n = len(loc_sorted)
    if n == 0:
        return np.full((0,), -1, np.int64), 0
    change = np.flatnonzero(np.diff(loc_sorted)) + 1
    starts = np.concatenate([[0], change])
    ends = np.concatenate([change, [n]])
    counts = ends - starts
    run_order = sorted(range(len(starts)), key=lambda i: (-counts[i], i))
    segments, bins = [], []
    for r in run_order:
        c = int(counts[r])
        if c >= b:
            segments.append([r])
            free = (-c) % b
            if free:
                bins.append([len(segments) - 1, free])
        else:
            for entry in bins:
                if entry[1] >= c:
                    segments[entry[0]].append(r)
                    entry[1] -= c
                    break
            else:
                segments.append([r])
                bins.append([len(segments) - 1, b - c])
    slot_src = []
    for seg in segments:
        seg_start = len(slot_src)
        for r in seg:
            slot_src.extend(range(int(starts[r]), int(ends[r])))
        slot_src.extend([-1] * ((-(len(slot_src) - seg_start)) % b))
    return np.asarray(slot_src, np.int64), len(slot_src)


@pytest.mark.parametrize("seed", range(6))
def test_occupancy_pack_order_matches_first_fit_loop(seed):
    """Size-class-at-a-time packing gives the same slot order as the
    run-at-a-time loop, on random run lengths: small runs only, big and
    small mixed, and runs straddling the block size."""
    rs = np.random.default_rng(seed)
    for _ in range(40):
        b = int(rs.choice([8, 32, 128]))
        num_runs = int(rs.integers(0, 400))
        lens = [
            rs.geometric(0.15, num_runs),
            np.where(rs.random(num_runs) < 0.1,
                     rs.integers(b, 6 * b, num_runs),
                     rs.integers(1, b, num_runs)),
            rs.integers(1, 3 * b, num_runs),
        ][seed % 3]
        loc = np.repeat(np.arange(num_runs), lens)
        ref, ref_extent = _first_fit_loop(loc, b)
        got, extent = pop_lib.occupancy_pack_order(loc, b)
        assert extent == ref_extent
        np.testing.assert_array_equal(got, ref)
