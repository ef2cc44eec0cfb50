"""The central correctness test: all interaction backends vs the dense
oracle vs the literal serial event-queue DES (Algorithm 1), on both the
canonical (loc, start)-sorted layout and the occupancy-packed layout."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import contact as contact_lib
from repro.core import population as pop_lib
from repro.kernels.interactions import ops as iops
from repro.kernels.interactions import ref as iref

from des_oracle import serial_des_day

ALL_BACKENDS = ("jnp", "scan", "compact", "pallas", "pallas-compact")


def make_case(seed, Vn=220, L=30, P=90, b=64):
    rs = np.random.default_rng(seed)
    person = rs.integers(0, P, Vn)
    loc = rs.integers(0, L, Vn)
    start = rs.uniform(0, 80000, Vn).astype(np.float32)
    end = (start + rs.uniform(600, 20000, Vn)).astype(np.float32)
    day_v = pop_lib.pack_day(person, loc, start, end, pad_multiple=b)
    occ = contact_lib.max_occupancy_fast(L, loc, start, end)
    p_loc = np.asarray(contact_lib.MinMaxAlpha().probability(occ), np.float32)
    sus_pp = rs.uniform(0.0, 1.0, P).astype(np.float32)
    sus_pp[rs.random(P) < 0.3] = 0.0
    inf_pp = np.zeros(P, np.float32)
    inf_pp[rs.choice(P, 14, replace=False)] = rs.uniform(0.5, 1.0, 14)
    return day_v, p_loc, sus_pp, inf_pp, (person, loc, start, end)


def layout_args(layout, extent, p_loc, sus_pp, inf_pp, b, seed, day):
    """Backend args for any visit layout (DayVisits or PackedDayVisits)."""
    L = len(p_loc)
    sched = pop_lib.build_block_schedule(layout.loc, extent, b)
    safe = np.maximum(layout.person, 0)
    sus_v = jnp.asarray(sus_pp[safe] * layout.active)
    inf_v = jnp.asarray(inf_pp[safe] * layout.active)
    args = (
        jnp.asarray(layout.person), jnp.asarray(layout.loc),
        jnp.asarray(layout.start), jnp.asarray(layout.end),
        jnp.asarray(p_loc[np.minimum(layout.loc, L - 1)]),
        sus_v, inf_v,
        jnp.asarray(sched.row_block), jnp.asarray(sched.col_block),
        jnp.asarray(sched.row_start.astype(np.int32)),
        jnp.asarray(sched.pair_active.astype(np.int32)),
        iops.col_has_infectious(
            inf_v, jnp.asarray(layout.person), sched.num_blocks, b
        ),
        iops.row_has_susceptible(
            sus_v, jnp.asarray(layout.person), sched.num_blocks, b
        ),
        jnp.asarray([seed, day], jnp.uint32),
    )
    return args, sched


def backend_args(day_v, p_loc, sus_pp, inf_pp, b, seed, day):
    return layout_args(day_v, day_v.num_real, p_loc, sus_pp, inf_pp, b, seed, day)


def fold_to_people(num_people, layout, acc):
    A = np.zeros(num_people)
    np.add.at(A, np.maximum(layout.person, 0), np.asarray(acc) * layout.active)
    return A


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_backends_match_dense(seed, backend):
    b = 64
    day_v, p_loc, sus_pp, inf_pp, _ = make_case(seed, b=b)
    args, _ = backend_args(day_v, p_loc, sus_pp, inf_pp, b, 123, 5)
    acc_d, cnt_d = iref.interactions_dense(*args[:7], 123, 5)
    acc, cnt = iops.interactions_auto(*args, block_size=b, backend=backend)
    np.testing.assert_allclose(np.asarray(acc), np.asarray(acc_d), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(cnt_d))


@pytest.mark.parametrize("seed", [3, 4])
def test_matches_serial_event_queue_des(seed):
    """Tensorized pairwise-overlap == literal Algorithm 1, bitwise on the
    contact set and propensities (f32 sum tolerance)."""
    b = 64
    day_v, p_loc, sus_pp, inf_pp, raw = make_case(seed, b=b)
    person, loc, start, end = raw
    P = len(sus_pp)
    args, _ = backend_args(day_v, p_loc, sus_pp, inf_pp, b, 9, 2)
    acc, cnt = iops.interactions_auto(*args, block_size=b, backend="jnp")
    A_fast = fold_to_people(P, day_v, acc)
    A_serial, contacts_serial = serial_des_day(
        person, loc, start, end, p_loc, sus_pp, inf_pp, 9, 2
    )
    np.testing.assert_allclose(A_fast, A_serial, rtol=2e-4, atol=1e-4)
    assert int(np.asarray(cnt).sum()) == contacts_serial


def test_block_schedule_covers_all_same_loc_pairs():
    day_v, p_loc, sus_pp, inf_pp, _ = make_case(7, b=32)
    sched = pop_lib.build_block_schedule(day_v.loc, day_v.num_real, 32)
    covered = set(zip(sched.row_block[sched.pair_active].tolist(),
                      sched.col_block[sched.pair_active].tolist()))
    n = day_v.num_real
    for i in range(n):
        for j in range(n):
            if day_v.loc[i] == day_v.loc[j]:
                assert (i // 32, j // 32) in covered


# ---------------------------------------------------------------------------
# Epidemic extremes: every backend must agree bitwise with every other and
# allclose with the dense oracle when the short-circuit flags are all-dead,
# all-live, or the schedule is degenerate.
# ---------------------------------------------------------------------------


_EXTREME_SEEDS = {
    "zero_infectious": 100, "all_infectious": 101,
    "all_padding_block": 102, "single_giant_location": 103,
}


def _extreme_case(kind, b=64):
    rs = np.random.default_rng(_EXTREME_SEEDS[kind])
    L, P = 20, 80
    if kind == "all_padding_block":
        # Real visits fill exactly one block; two more blocks are padding.
        Vn = b
        person = rs.integers(0, P, Vn)
        loc = rs.integers(0, L, Vn)
        start = rs.uniform(0, 40000, Vn).astype(np.float32)
        end = (start + rs.uniform(600, 9000, Vn)).astype(np.float32)
        day_v = pop_lib.pack_day(person, loc, start, end, pad_to=3 * b,
                                 pad_multiple=b)
    elif kind == "single_giant_location":
        # One location spanning a multi-block band (the paper's worst case).
        Vn = 4 * b + 17
        person = rs.integers(0, P, Vn)
        loc = np.zeros(Vn, np.int64)
        start = rs.uniform(0, 40000, Vn).astype(np.float32)
        end = (start + rs.uniform(600, 9000, Vn)).astype(np.float32)
        day_v = pop_lib.pack_day(person, loc, start, end, pad_multiple=b)
    else:  # zero_infectious / all_infectious share a generic schedule
        Vn = 3 * b + 11
        person = rs.integers(0, P, Vn)
        loc = rs.integers(0, L, Vn)
        start = rs.uniform(0, 40000, Vn).astype(np.float32)
        end = (start + rs.uniform(600, 9000, Vn)).astype(np.float32)
        day_v = pop_lib.pack_day(person, loc, start, end, pad_multiple=b)
    p_loc = np.full(L, 0.6, np.float32)
    sus_pp = rs.uniform(0.1, 1.0, P).astype(np.float32)
    inf_pp = rs.uniform(0.1, 1.0, P).astype(np.float32)
    if kind == "zero_infectious":
        inf_pp[:] = 0.0
    elif kind == "all_infectious":
        pass  # everyone infectious AND susceptible: every tile live
    else:
        inf_pp[rs.random(P) < 0.7] = 0.0
    return day_v, p_loc, sus_pp, inf_pp


@pytest.mark.parametrize("kind", [
    "zero_infectious", "all_infectious", "all_padding_block",
    "single_giant_location",
])
@pytest.mark.parametrize("packed", [False, True])
def test_extremes_all_backends_bitwise_equal(kind, packed):
    b = 64
    day_v, p_loc, sus_pp, inf_pp = _extreme_case(kind, b=b)
    if packed:
        layout = pop_lib.pack_day_occupancy(day_v, b)
        extent = layout.extent
    else:
        layout, extent = day_v, day_v.num_real
    args, _ = layout_args(layout, extent, p_loc, sus_pp, inf_pp, b, 77, 3)
    acc_d, cnt_d = iref.interactions_dense(*args[:7], 77, 3)
    outs = {
        be: iops.interactions_auto(*args, block_size=b, backend=be)
        for be in ALL_BACKENDS
    }
    for be, (acc, cnt) in outs.items():
        np.testing.assert_allclose(
            np.asarray(acc), np.asarray(acc_d), rtol=1e-6, err_msg=be
        )
        np.testing.assert_array_equal(np.asarray(cnt), np.asarray(cnt_d),
                                      err_msg=be)
        # bitwise equality across backends (accumulation-order contract)
        np.testing.assert_array_equal(
            np.asarray(acc), np.asarray(outs["jnp"][0]), err_msg=be
        )
    if kind == "zero_infectious":
        assert float(np.abs(np.asarray(outs["jnp"][0])).sum()) == 0.0
        assert int(np.asarray(outs["jnp"][1]).sum()) == 0
    if kind == "all_infectious":
        assert int(np.asarray(outs["jnp"][1]).sum()) > 0


# ---------------------------------------------------------------------------
# Occupancy-aware packing: same epidemiology, smaller schedule.
# ---------------------------------------------------------------------------


def _skewed_case(seed, b=64):
    """Many small locations + a few giants — the layout packing targets."""
    rs = np.random.default_rng(seed)
    L, P, Vn = 40, 150, 800
    person = rs.integers(0, P, Vn)
    loc = rs.integers(0, L, Vn)
    loc[rs.random(Vn) < 0.35] = 3  # giant location
    start = rs.uniform(0, 60000, Vn).astype(np.float32)
    end = (start + rs.uniform(600, 15000, Vn)).astype(np.float32)
    day_v = pop_lib.pack_day(person, loc, start, end, pad_multiple=b)
    p_loc = rs.uniform(0.1, 0.9, L).astype(np.float32)
    sus_pp = rs.uniform(0.0, 1.0, P).astype(np.float32)
    inf_pp = np.where(rs.random(P) < 0.15,
                      rs.uniform(0.2, 1.0, P), 0.0).astype(np.float32)
    return day_v, p_loc, sus_pp, inf_pp


@pytest.mark.parametrize("seed", [11, 12])
def test_packed_layout_matches_dense_oracle(seed):
    """Per-person propensities on the packed layout == dense oracle on the
    canonical layout (layout is epidemiologically free), and the packed
    schedule is strictly smaller."""
    b = 64
    day_v, p_loc, sus_pp, inf_pp = _skewed_case(seed, b=b)
    P = len(sus_pp)
    packed = pop_lib.pack_day_occupancy(day_v, b)
    assert packed.num_real == day_v.num_real
    assert int((packed.person >= 0).sum()) == day_v.num_real

    args_u, sched_u = backend_args(day_v, p_loc, sus_pp, inf_pp, b, 5, 1)
    args_p, sched_p = layout_args(
        packed, packed.extent, p_loc, sus_pp, inf_pp, b, 5, 1
    )
    assert sched_p.num_pairs < sched_u.num_pairs

    acc_d, cnt_d = iref.interactions_dense(*args_u[:7], 5, 1)
    A_oracle = fold_to_people(P, day_v, acc_d)
    for backend in ALL_BACKENDS:
        acc, cnt = iops.interactions_auto(*args_p, block_size=b,
                                          backend=backend)
        A = fold_to_people(P, packed, acc)
        np.testing.assert_allclose(A, A_oracle, rtol=1e-5, atol=1e-6,
                                   err_msg=backend)
        assert int(np.asarray(cnt).sum()) == int(np.asarray(cnt_d).sum())


def test_packed_schedule_covers_all_same_loc_pairs():
    b = 32
    day_v, p_loc, sus_pp, inf_pp = _skewed_case(13, b=b)
    packed = pop_lib.pack_day_occupancy(day_v, b)
    sched = pop_lib.build_block_schedule(packed.loc, packed.extent, b)
    covered = set(zip(sched.row_block[sched.pair_active].tolist(),
                      sched.col_block[sched.pair_active].tolist()))
    real = np.flatnonzero(packed.person >= 0)
    loc = packed.loc
    for i in real:
        for j in real:
            if loc[i] == loc[j]:
                assert (i // b, j // b) in covered


def test_short_circuit_zero_infectious():
    b = 64
    day_v, p_loc, sus_pp, inf_pp, _ = make_case(8, b=b)
    inf_pp[:] = 0.0
    args, _ = backend_args(day_v, p_loc, sus_pp, inf_pp, b, 1, 0)
    for backend in ALL_BACKENDS:
        acc, cnt = iops.interactions_auto(*args, block_size=b, backend=backend)
        assert float(np.abs(np.asarray(acc)).sum()) == 0.0
        assert int(np.asarray(cnt).sum()) == 0


# ---------------------------------------------------------------------------
# In-kernel traversed-edge telemetry: the pallas-compact SMEM accumulator
# must equal the host-side count (sum of per-visit contact counts) exactly.
# The live-tile count rides beside it: equal on every backend, and equal to
# a numpy recount of the block flags.
# ---------------------------------------------------------------------------


def _numpy_live_tiles(args, b):
    """Tiles with work: scheduled, an infectious visit in the column block
    and a susceptible one in the row block, recounted from the visits."""
    pid, sus_v, inf_v = (np.asarray(args[i]) for i in (0, 5, 6))
    row, col, pair_active = (np.asarray(args[i]) for i in (7, 8, 10))
    real = (pid >= 0).reshape(-1, b)
    has_sus = ((sus_v.reshape(-1, b) > 0) & real).any(axis=1)
    has_inf = ((inf_v.reshape(-1, b) > 0) & real).any(axis=1)
    return int(((pair_active == 1) & has_inf[col] & has_sus[row]).sum())


@pytest.mark.parametrize("kind", [
    "zero_infectious", "all_infectious", "all_padding_block",
    "single_giant_location",
])
@pytest.mark.parametrize("packed", [False, True])
def test_in_kernel_edge_counter_matches_host(kind, packed):
    b = 64
    day_v, p_loc, sus_pp, inf_pp = _extreme_case(kind, b=b)
    if packed:
        layout = pop_lib.pack_day_occupancy(day_v, b)
        extent = layout.extent
    else:
        layout, extent = day_v, day_v.num_real
    args, _ = layout_args(layout, extent, p_loc, sus_pp, inf_pp, b, 21, 4)
    want_live = _numpy_live_tiles(args, b)
    for backend in ALL_BACKENDS:
        acc, cnt, edges, live = iops.interactions_auto_edges(
            *args, block_size=b, backend=backend
        )
        assert int(np.asarray(edges)) == int(np.asarray(cnt).sum()), backend
        assert int(np.asarray(live)) == want_live, backend
    if kind == "all_infectious":
        _, cnt, edges, live = iops.interactions_auto_edges(
            *args, block_size=b, backend="pallas-compact"
        )
        assert int(np.asarray(edges)) > 0
        assert int(np.asarray(live)) > 0
    if kind == "zero_infectious":
        assert want_live == 0


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_in_kernel_edge_counter_random_schedules(seed):
    b = 64
    day_v, p_loc, sus_pp, inf_pp, _ = make_case(seed, b=b)
    args, _ = backend_args(day_v, p_loc, sus_pp, inf_pp, b, 123, 5)
    _, cnt_ref, edges_ref, live_ref = iops.interactions_auto_edges(
        *args, block_size=b, backend="jnp"
    )
    _, cnt, edges, live = iops.interactions_auto_edges(
        *args, block_size=b, backend="pallas-compact"
    )
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(cnt_ref))
    assert int(np.asarray(edges)) == int(np.asarray(edges_ref))
    assert int(np.asarray(live)) == int(np.asarray(live_ref)) == \
        _numpy_live_tiles(args, b) > 0
    assert int(np.asarray(edges)) == int(np.asarray(cnt_ref).sum())


# ---------------------------------------------------------------------------
# Second kernel accumulator: per-visit traced-contact counts. Every backend
# must match the dense-numpy tracing oracle bitwise, leave the exposure/
# count/edge outputs bitwise-unchanged relative to the untraced call, and
# vanish exactly when the source channel is identically zero.
# ---------------------------------------------------------------------------


def _with_sources(sus_pp, inf_pp, layout, rs):
    """A per-visit tracing-source vector marking ~half the infectious
    people as today's positives (sources are always infectious)."""
    P = len(sus_pp)
    src_pp = np.where(
        (inf_pp > 0) & (rs.random(P) < 0.5), 1.0, 0.0
    ).astype(np.float32)
    safe = np.maximum(layout.person, 0)
    return src_pp, jnp.asarray(src_pp[safe] * layout.active)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_traced_accumulator_matches_dense_oracle(seed, backend):
    b = 64
    day_v, p_loc, sus_pp, inf_pp, _ = make_case(seed, b=b)
    rs = np.random.default_rng(1000 + seed)
    _, src_v = _with_sources(sus_pp, inf_pp, day_v, rs)
    args, _ = backend_args(day_v, p_loc, sus_pp, inf_pp, b, 123, 5)
    acc_d, cnt_d, trc_d = iref.interactions_dense_traced(
        *args[:7], src_v, 123, 5
    )
    acc, cnt, edges, trc, live = iops.interactions_auto_traced(
        *args, block_size=b, backend=backend, src_val=src_v
    )
    assert int(np.asarray(live)) == _numpy_live_tiles(args, b)
    np.testing.assert_array_equal(np.asarray(trc), np.asarray(trc_d))
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(cnt_d))
    np.testing.assert_allclose(np.asarray(acc), np.asarray(acc_d), rtol=1e-6)
    assert int(np.asarray(edges)) == int(np.asarray(cnt).sum())
    # tracing condition is a strict subset of the contact condition
    assert (np.asarray(trc) <= np.asarray(cnt)).all()
    assert int(np.asarray(trc).sum()) > 0  # the case actually exercises it


@pytest.mark.parametrize("seed", [0, 1])
def test_traced_call_leaves_exposure_bitwise_unchanged(seed):
    """Adding the second accumulator must not perturb a single bit of the
    exposure/count outputs on any backend (same tiles, same order)."""
    b = 64
    day_v, p_loc, sus_pp, inf_pp, _ = make_case(seed, b=b)
    rs = np.random.default_rng(2000 + seed)
    _, src_v = _with_sources(sus_pp, inf_pp, day_v, rs)
    args, _ = backend_args(day_v, p_loc, sus_pp, inf_pp, b, 9, 2)
    for backend in ALL_BACKENDS:
        acc0, cnt0, edges0, live0 = iops.interactions_auto_edges(
            *args, block_size=b, backend=backend
        )
        acc, cnt, edges, _, live = iops.interactions_auto_traced(
            *args, block_size=b, backend=backend, src_val=src_v
        )
        np.testing.assert_array_equal(np.asarray(acc), np.asarray(acc0))
        np.testing.assert_array_equal(np.asarray(cnt), np.asarray(cnt0))
        assert int(np.asarray(edges)) == int(np.asarray(edges0))
        assert int(np.asarray(live)) == int(np.asarray(live0))


def test_traced_accumulator_zero_sources():
    b = 64
    day_v, p_loc, sus_pp, inf_pp, _ = make_case(5, b=b)
    args, _ = backend_args(day_v, p_loc, sus_pp, inf_pp, b, 3, 1)
    src_v = jnp.zeros((args[0].shape[0],), jnp.float32)
    for backend in ALL_BACKENDS:
        _, _, _, trc, _ = iops.interactions_auto_traced(
            *args, block_size=b, backend=backend, src_val=src_v
        )
        assert int(np.abs(np.asarray(trc)).sum()) == 0, backend


@pytest.mark.parametrize("kind", [
    "zero_infectious", "all_infectious", "all_padding_block",
    "single_giant_location",
])
def test_traced_accumulator_extremes_bitwise_across_backends(kind):
    """Epidemic extremes: the tracing accumulator is bitwise identical
    across all five backends on the short-circuit edge cases (dead tiles,
    all-live tiles, padding blocks, one giant location)."""
    b = 64
    day_v, p_loc, sus_pp, inf_pp = _extreme_case(kind, b=b)
    rs = np.random.default_rng(_EXTREME_SEEDS[kind])
    _, src_v = _with_sources(sus_pp, inf_pp, day_v, rs)
    args, _ = layout_args(
        day_v, day_v.num_real, p_loc, sus_pp, inf_pp, b, 21, 4
    )
    ref_out = None
    for backend in ALL_BACKENDS:
        out = iops.interactions_auto_traced(
            *args, block_size=b, backend=backend, src_val=src_v
        )
        if ref_out is None:
            ref_out = out
        else:
            for a, r in zip(out, ref_out):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(r))


@pytest.mark.parametrize("traced", [False, True])
@pytest.mark.parametrize("backend", ["pallas", "pallas-compact"])
def test_kernel_launches_split_on_row_runs(monkeypatch, backend, traced):
    """A schedule longer than one launch takes (SMEM holds the schedule) is
    cut into launches that end on row-run boundaries; the outputs are
    bitwise those of a single pass."""
    import jax
    from repro.kernels.interactions import kernel

    b = 64
    day_v, p_loc, sus_pp, inf_pp, _ = make_case(3, Vn=900, L=40, P=300, b=b)
    args, sched = backend_args(day_v, p_loc, sus_pp, inf_pp, b, 11, 2)
    longest_run = int(np.bincount(sched.row_block).max())
    assert longest_run < sched.num_pairs // 2  # several launches
    src = None
    if traced:
        _, src = _with_sources(sus_pp, inf_pp, day_v,
                               np.random.default_rng(5))
    call = dict(block_size=b, backend=backend)
    ref = (iops.interactions_auto_traced(*args, src_val=src, **call)
           if traced else iops.interactions_auto_edges(*args, **call))
    monkeypatch.setattr(kernel, "MAX_ROW_TILES", longest_run)
    jax.clear_caches()
    try:
        out = (iops.interactions_auto_traced(*args, src_val=src, **call)
               if traced else iops.interactions_auto_edges(*args, **call))
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    for a, r in zip(out, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(r))


def test_pallas_refuses_other_platforms(monkeypatch):
    """Interpret mode is for CPU only: a platform that is neither TPU nor
    CPU gets an error, never the interpreter."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="not on 'gpu'"):
        iops.pallas_interpret()
