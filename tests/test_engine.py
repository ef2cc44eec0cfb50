"""The unified engine core: the acceptance matrix.

Every legacy layout (single / dist / ensemble / sharded / hybrid) now
dispatches through the single topology-parameterized scan
(repro.engine.day.run_days). These tests pin the refactor's contract
against the *pre-refactor* reference semantics — hand-rolled scans over
the legacy pure ``core/simulator.py:day_step`` and
``core/simulator_dist.py:dist_day_step`` (which remain in the tree as the
reference arithmetic) — bitwise, per scenario, for the ``jnp`` and
``compact`` interaction backends, plus the no-op scenario padding and the
in-scan observable path on sharded topologies.
"""

import dataclasses
import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ScenarioBatch
from repro.core import disease
from repro.core import interventions as iv
from repro.core import simulator as sim_lib
from repro.core import simulator_dist as sd
from repro.data import digital_twin_population
from repro.engine import (
    CoreDriver,
    EngineCore,
    LocalTopology,
    MeshTopology,
    ProductTopology,
    ScenarioTopology,
    index_params,
    make_topology,
    no_op_params,
    run_chunked,
)
from repro.launch.mesh import make_worker_mesh

DAYS = 10
BACKENDS = ("jnp", "compact")


@pytest.fixture(scope="module")
def pop():
    return digital_twin_population(900, seed=5, name="engine-t")


@pytest.fixture(scope="module")
def batch():
    return ScenarioBatch.from_product(
        interventions={
            "baseline": (),
            "schools": [iv.Intervention(
                "schools", iv.CaseThreshold(on=30), iv.LocTypeIs(2),
                iv.CloseLocations(),
            )],
        },
        tau=2e-5,
        seeds=[11],
    )


def _legacy_single_hist(pop, batch, days, backend):
    """Pre-refactor reference: a jitted lax.scan over the legacy pure
    ``day_step`` (exactly what EpidemicSimulator.run compiled before the
    refactor), one scenario at a time."""
    from repro.core import interactions as inter_lib

    week = inter_lib.build_week_data(pop, 128, pack=True)
    contact_prob = jnp.asarray(pop.contact_prob)
    hists, finals = [], []
    for s in batch:
        iv_slots, _, params = sim_lib.build_params(
            pop, s.disease, s.tm, s.interventions, s.seed,
            seed_per_day=s.seed_per_day, seed_days=s.seed_days,
            static_network=s.static_network, iv_enabled=s.iv_enabled,
        )
        static = sim_lib.SimStatic(
            num_people=pop.num_people, num_locations=pop.num_locations,
            iv_slots=iv_slots, backend=backend,
        )
        state = sim_lib.init_state(s.disease, pop.num_people, len(iv_slots))
        final, hist = jax.jit(
            lambda st, p: sim_lib.run_scan(
                static, week, contact_prob, p, st, DAYS
            )
        )(state, params)
        hists.append(jax.device_get(hist))
        finals.append(final)
    return finals, hists


def _legacy_dist_hist(pop, batch, days, backend, workers=1):
    """Pre-refactor reference: shard_map(lax.scan over the legacy pure
    ``dist_day_step``) — the program DistSimulator.run compiled before."""
    mesh = make_worker_mesh(workers)
    plan = sd.build_dist_plan(pop, workers, 128, True, pack=True)
    week, route = sd.week_device_arrays(plan)
    hists, finals = [], []
    for s in batch:
        iv_slots, _, params = sim_lib.build_params(
            pop, s.disease, s.tm, s.interventions, s.seed,
            seed_per_day=s.seed_per_day, seed_days=s.seed_days,
            static_network=s.static_network, iv_enabled=s.iv_enabled,
        )
        params = sd.pad_params(params, plan)
        static = sd.make_dist_static(
            plan, pop.num_locations, iv_slots, backend=backend,
            max_seed_per_day=s.seed_per_day,
        )

        def worker(state, wk, rt, p):
            wk = jax.tree.map(lambda a: a.squeeze(1), wk)
            rt = jax.tree.map(lambda a: a.squeeze(1), rt)
            return sd.dist_run_scan(static, rt, wk, p, state, days)

        wspec = jax.tree.map(lambda _: P(None, sd.AXIS), week)
        rspec = jax.tree.map(lambda _: P(None, sd.AXIS), route)
        fn = jax.jit(jax.shard_map(
            worker, mesh=mesh,
            in_specs=(sd.dist_state_specs(), wspec, rspec,
                      sd.dist_param_specs()),
            out_specs=(sd.dist_state_specs(),
                       {k: P() for k in sd.STAT_KEYS}),
            check_vma=False,
        ))
        state = sd.dist_init_state(s.disease, plan, len(iv_slots))
        final, hist = fn(state, week, route, params)
        hists.append(jax.device_get(hist))
        finals.append(final)
    return finals, hists


# ---------------------------------------------------------------------------
# the acceptance matrix: 5 layouts × {jnp, compact}, bitwise vs pre-refactor
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", BACKENDS)
def test_layout_matrix_bitwise_vs_prerefactor(pop, batch, backend):
    finals_ref, hists_ref = _legacy_single_hist(pop, batch, DAYS, backend)

    # single: local core, one scenario per B=1 run
    core1 = EngineCore(pop, batch, layout="local", backend=backend)
    for i in range(len(batch)):
        sl = lambda t: jax.tree.map(lambda x: x[i: i + 1], t)
        f, _, h, _ = core1.run_days(
            DAYS, params=sl(core1.params), state=sl(core1.init_state())
        )
        for k in sim_lib.STAT_KEYS:
            np.testing.assert_array_equal(
                hists_ref[i][k], h[k][:, 0], err_msg=f"single/{backend}/{k}")
        np.testing.assert_array_equal(
            np.asarray(finals_ref[i].health), np.asarray(f.health)[0])

    # ensemble: the same local core, whole batch in one scan
    _, _, hist_ens, _ = core1.run_days(DAYS)
    for i in range(len(batch)):
        for k in sim_lib.STAT_KEYS:
            np.testing.assert_array_equal(
                hists_ref[i][k], hist_ens[k][:, i],
                err_msg=f"ensemble/{backend}/{k}")

    # dist: workers topology, bitwise vs the legacy shard_map scan
    finals_d, hists_d = _legacy_dist_hist(pop, batch, DAYS, backend)
    corew = EngineCore(pop, batch, layout="workers", workers=1,
                       backend=backend)
    for i in range(len(batch)):
        sl = lambda t: jax.tree.map(lambda x: x[i: i + 1], t)
        f, _, h, _ = corew.run_days(
            DAYS, params=sl(corew.params), state=sl(corew.init_state())
        )
        for k in sim_lib.STAT_KEYS:
            np.testing.assert_array_equal(
                hists_d[i][k], h[k][:, 0], err_msg=f"dist/{backend}/{k}")
            np.testing.assert_array_equal(
                hists_ref[i][k], h[k][:, 0],
                err_msg=f"dist-vs-single/{backend}/{k}")
        np.testing.assert_array_equal(
            np.asarray(finals_d[i].health), np.asarray(f.health)[0])

    # sharded + hybrid: scenario-sharded placements of the same scan
    for layout, kw in (("scenarios", dict(scen_shards=1)),
                       ("hybrid", dict(workers=1, scen_shards=1))):
        core = EngineCore(pop, batch, layout=layout, backend=backend, **kw)
        _, _, h, _ = core.run_days(DAYS)
        for i in range(len(batch)):
            for k in sim_lib.STAT_KEYS:
                np.testing.assert_array_equal(
                    hists_ref[i][k], h[k][:, i],
                    err_msg=f"{layout}/{backend}/{k}")

    # the intervention trigger really fired in scenario 1 (non-trivial run)
    assert hist_ens["cumulative"][-1, 0] != hist_ens["cumulative"][-1, 1]


@pytest.mark.parametrize("layout,kw", [
    ("scenarios", dict(scen_shards=4)),
    ("hybrid", dict(workers=2, scen_shards=2)),
    ("workers", dict(workers=4)),
])
def test_layout_matrix_multidevice(pop, batch, layout, kw):
    """The same matrix on real >1-device meshes (CI multidevice job). Each
    worker tiles its own shard, so ``live_tiles`` is held to the legacy
    step at the same worker count; every other key to one device."""
    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=4)")
    _, hists_ref = _legacy_single_hist(pop, batch, DAYS, "jnp")
    workers = kw.get("workers", 1)
    if workers > 1:
        _, tiles_ref = _legacy_dist_hist(pop, batch, DAYS, "jnp", workers)
        for ref, tiles in zip(hists_ref, tiles_ref):
            ref["live_tiles"] = tiles["live_tiles"]
    core = EngineCore(pop, batch, layout=layout, backend="jnp", **kw)
    if layout == "workers":
        for i in range(len(batch)):
            sl = lambda t: jax.tree.map(lambda x: x[i: i + 1], t)
            _, _, h, _ = core.run_days(
                DAYS, params=sl(core.params), state=sl(core.init_state()))
            for k in sim_lib.STAT_KEYS:
                np.testing.assert_array_equal(hists_ref[i][k], h[k][:, 0],
                                              err_msg=f"{layout}/{k}")
    else:
        _, _, h, _ = core.run_days(DAYS)
        for i in range(len(batch)):
            for k in sim_lib.STAT_KEYS:
                np.testing.assert_array_equal(hists_ref[i][k], h[k][:, i],
                                              err_msg=f"{layout}/{k}")


# ---------------------------------------------------------------------------
# topology protocol
# ---------------------------------------------------------------------------


def test_topology_composition():
    assert isinstance(make_topology(None, None), LocalTopology)
    assert isinstance(make_topology("workers", None), MeshTopology)
    assert isinstance(make_topology(None, "scenarios"), ScenarioTopology)
    prod = make_topology("workers", "scenarios")
    assert isinstance(prod, ProductTopology)
    # operator composition mirrors the factory
    assert MeshTopology() * ScenarioTopology() == prod
    assert prod.axis_names == ("workers", "scenarios")
    # identity placement composes away (reflected via __rmul__)
    assert LocalTopology() * ScenarioTopology() == ScenarioTopology()
    with pytest.raises(TypeError):
        _ = ScenarioTopology() * MeshTopology()


def test_local_topology_identity_collectives():
    topo = LocalTopology()
    x = jnp.arange(5.0, dtype=jnp.float32)
    np.testing.assert_array_equal(topo.psum(x), x)
    np.testing.assert_array_equal(topo.pmax(x), x)
    assert int(topo.worker_index()) == 0
    np.testing.assert_array_equal(topo.scen_gather(x, 3), x[:3])
    # dispatch == masked gather; combine == segment_sum
    pid = jnp.asarray([0, 2, -1, 1], jnp.int32)
    chans = jnp.arange(3.0, dtype=jnp.float32)[:, None]
    out = topo.dispatch(None, pid, chans)
    np.testing.assert_array_equal(out[:, 0], [0.0, 2.0, 0.0, 1.0])
    acc = jnp.asarray([1.0, 2.0, 3.0, 4.0], jnp.float32)
    active = pid >= 0
    back = topo.combine(None, pid, active, acc, 3)
    np.testing.assert_array_equal(back, [1.0, 4.0, 2.0])


def _combine_many_inputs(C, integer):
    """A shared visit schedule (repeated people, ``-1`` padding) and
    per-scenario ``active``/``accs`` for B = 3, as ``run_days`` vmaps the
    combine."""
    rng = np.random.default_rng(100 + C)
    pid = np.asarray([3, 0, -1, 3, 5, 1, 3, -1, 0, 6, 2, 5, 3, -1, 4, 0,
                      1, 6, 6, 2, -1, 3, 5], np.int32)
    B, V = 3, pid.shape[0]
    active = (rng.random((B, V)) < 0.75) & (pid >= 0)
    if integer:
        accs = rng.integers(0, 1000, (B, V, C)).astype(np.float32)
    else:
        accs = rng.standard_normal((B, V, C)).astype(np.float32) * 1e-3
    return jnp.asarray(pid), jnp.asarray(active), jnp.asarray(accs)


def _vmapped(fn, pid, num_people):
    return jax.jit(jax.vmap(lambda a, x: fn(None, pid, a, x, num_people)))


@pytest.mark.parametrize("C", [1, 2, 3])
def test_local_combine_many_is_per_channel_combine(C):
    topo, P = LocalTopology(), 7
    for integer in (False, True):
        pid, active, accs = _combine_many_inputs(C, integer)
        many = np.asarray(_vmapped(topo.combine_many, pid, P)(active, accs))
        assert many.shape == (3, P, C)
        one = _vmapped(topo.combine, pid, P)
        for c in range(C):
            np.testing.assert_array_equal(
                many[:, :, c], np.asarray(one(active, accs[:, :, c])))
        if integer:
            want = np.zeros((3, P, C), np.float64)
            for b in range(3):
                np.add.at(want[b], np.maximum(np.asarray(pid), 0),
                          np.asarray(accs[b]) * np.asarray(active[b])[:, None])
            np.testing.assert_array_equal(many, want.astype(np.float32))


@pytest.mark.parametrize("C", [1, 2, 3])
def test_local_combine_many_lowers_one_scatter_per_channel(C):
    # A scatter whose window spans the channel axis misses XLA's sort
    # lowering on the TPU; each channel's scatter must window the scenario
    # axis alone, as the single-channel combine's does.
    pid, active, accs = _combine_many_inputs(C, False)
    text = _vmapped(LocalTopology().combine_many, pid, 7).lower(
        active, accs).as_text()
    windows = re.findall(r"update_window_dims = \[([^\]]*)\]", text)
    assert windows == ["0"] * C


def test_local_seed_threshold_matches_sort():
    topo = LocalTopology()
    u = jnp.asarray([0.9, 0.1, 0.5, 0.3], jnp.float32)
    t = topo.seed_threshold(u, jnp.asarray(2, jnp.int32), 4, 2)
    assert float(t) == pytest.approx(0.3)


# ---------------------------------------------------------------------------
# no-op padding (the padded-slot satellite)
# ---------------------------------------------------------------------------


def test_no_op_params_are_inert(pop):
    b = ScenarioBatch.from_product(disease=disease.covid_model(),
                                   tau=2e-5, seeds=[1])
    core = EngineCore(pop, b, layout="local")
    inert = no_op_params(index_params(core.params, 0))
    state, _, hist, _ = core.run_days(
        8, params=jax.tree.map(lambda x: x[None], inert))
    assert int(np.asarray(state.cumulative)[0]) == 0
    assert hist["new_infections"].sum() == 0
    assert hist["infectious"].max() == 0


def test_scenario_padding_never_in_results(pop):
    """A 3-real batch on a 4-shard scenario axis: the pad slot is inert
    and sliced off every returned history."""
    from repro.engine.core import pad_batch

    b = ScenarioBatch.from_product(disease=disease.covid_model(),
                                   tau=2e-5, seeds=[1, 2, 3])
    padded = pad_batch(b, 4)
    assert len(padded) == 4
    assert padded[3].name.startswith("__pad")

    if len(jax.devices()) >= 4:
        core4 = EngineCore(pop, b, layout="scenarios", scen_shards=4)
        assert len(core4.padded) == 4
        final, _, hist, _ = core4.run_days(DAYS)
        assert all(v.shape[1] == 3 for v in hist.values())
        # the pad column did no epidemiology at all
        assert int(np.asarray(final.cumulative)[3]) == 0
        ref = EngineCore(pop, b, layout="local").run_days(DAYS)[2]
        for k in sim_lib.STAT_KEYS:
            np.testing.assert_array_equal(ref[k], hist[k], err_msg=k)


# ---------------------------------------------------------------------------
# chunked checkpoint/resume at the engine level
# ---------------------------------------------------------------------------


def test_run_chunked_without_manager_single_chunk(pop, batch):
    from repro.api import observables as obs_lib

    obs = obs_lib.make_observables(("attack_rate",))
    ctx = obs_lib.ObsContext(num_people=pop.num_people,
                             num_scenarios=len(batch))
    core = EngineCore(pop, batch, layout="local")
    driver = CoreDriver(core, obs)
    state, hist, carries, dailies, resumed, chunks = run_chunked(
        driver, DAYS, obs, ctx)
    assert resumed is None and chunks == 1
    _, _, ref, _ = core.run_days(DAYS)
    for k in sim_lib.STAT_KEYS:
        np.testing.assert_array_equal(ref[k], hist[k], err_msg=k)
    final = obs_lib.observables_to_numpy(
        obs_lib.finalize_all(obs, carries, dailies, ctx))
    np.testing.assert_array_equal(final["attack_rate"]["cumulative"],
                                  hist["cumulative"][-1])


def test_engine_core_rejects_unknown_layout(pop, batch):
    with pytest.raises(ValueError, match="layout"):
        EngineCore(pop, batch, layout="banana")


def test_engine_core_rejects_mismatched_mesh(pop, batch):
    with pytest.raises(ValueError, match="mesh axes"):
        EngineCore(pop, batch, layout="scenarios",
                   mesh=make_worker_mesh(1))


def test_slot_structure_validation(pop):
    """Mixed intervention structures are rejected at batch-params build."""
    s0 = ScenarioBatch.from_product(disease=disease.covid_model(),
                                    tau=2e-5, seeds=[1])[0]
    s1 = dataclasses.replace(
        s0, name="other",
        interventions=(iv.Intervention(
            "schools", iv.CaseThreshold(on=30), iv.LocTypeIs(2),
            iv.CloseLocations()),),
        iv_enabled=(True,),
    )
    with pytest.raises(ValueError, match="intervention structure"):
        EngineCore(pop, ScenarioBatch(scenarios=(s0, s1)), layout="local")


# ---------------------------------------------------------------------------
# per-agent interventions (PR 7): the tracing accumulator and TTI state
# must be bitwise identical across every backend and every layout.
# ---------------------------------------------------------------------------

TTI_DAYS = 25


@pytest.fixture(scope="module")
def tti_kw():
    return dict(
        interventions=[iv.TestTraceIsolate(
            "tti", tests_per_day=15, start_day=3, isolation_days=6,
            trace_isolation_days=9,
        )],
        iv_enabled=[True], seed=7, seed_per_day=4,
    )


def _tti_hist(pop, tti_kw, **core_kw):
    core = EngineCore.single(pop, disease.covid_model(), **tti_kw, **core_kw)
    return core.run1(TTI_DAYS)[1]


def test_tti_bitwise_across_backends(pop, tti_kw):
    ref = _tti_hist(pop, tti_kw, backend="jnp")
    # the run exercises every new pathway
    assert ref["tests_used"].sum() > 0
    assert ref["traced"].sum() > 0
    assert ref["isolated"].sum() > 0
    assert ref["live_tiles"].sum() > 0
    for backend in ("scan", "compact", "pallas", "pallas-compact"):
        h = _tti_hist(pop, tti_kw, backend=backend)
        for k in sim_lib.STAT_KEYS:
            np.testing.assert_array_equal(
                ref[k], h[k], err_msg=f"{backend}/{k}")


def test_tti_bitwise_across_layouts(pop, tti_kw):
    ref = _tti_hist(pop, tti_kw)
    for layout, kw in (("workers", dict(workers=1)),
                       ("scenarios", dict(scen_shards=1)),
                       ("hybrid", dict(workers=1, scen_shards=1))):
        h = _tti_hist(pop, tti_kw, layout=layout, **kw)
        for k in sim_lib.STAT_KEYS:
            np.testing.assert_array_equal(
                ref[k], h[k], err_msg=f"{layout}/{k}")


@pytest.mark.parametrize("layout,kw", [
    ("scenarios", dict(scen_shards=4)),
    ("hybrid", dict(workers=2, scen_shards=2)),
    ("workers", dict(workers=4)),
])
def test_tti_multidevice(pop, tti_kw, layout, kw):
    """Tracing + test budget on real >1-device meshes: the traced-contact
    halo rides the exposure exchange and the budget's order statistic
    gathers per-worker candidates — both must stay bitwise. Each worker
    tiles its own shard, so ``live_tiles`` is held to another backend on
    the same mesh; every other key to one device."""
    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=4)")
    ref = _tti_hist(pop, tti_kw)
    h = _tti_hist(pop, tti_kw, layout=layout, **kw)
    if kw.get("workers", 1) > 1:
        ref["live_tiles"] = _tti_hist(pop, tti_kw, layout=layout,
                                      backend="compact", **kw)["live_tiles"]
    for k in sim_lib.STAT_KEYS:
        np.testing.assert_array_equal(ref[k], h[k], err_msg=f"{layout}/{k}")


def test_mixed_family_slot_structure_validated(pop):
    """A batch mixing TTI-present and TTI-absent scenarios has divergent
    per-agent slot structure and must be rejected like classic slots."""
    from repro.configs.sweep import Scenario
    from repro.core import transmission as tx

    mk = lambda name, ivs: Scenario(
        name=name, disease=disease.covid_model(), tm=tx.TransmissionModel(),
        interventions=tuple(ivs), iv_enabled=(), seed=0,
    )
    bad = [
        mk("a", [iv.Intervention("x", iv.DayRange(0), iv.Everyone(),
                                 iv.ScaleInfectivity(0.5))]),
        mk("b", [iv.TestTraceIsolate("x", tests_per_day=5)]),
    ]
    with pytest.raises(ValueError, match="intervention structure"):
        EngineCore(pop, bad)


# ---------------------------------------------------------------------------
# the collective schedule is part of the determinism contract: a fixed
# topology must emit a FIXED set of collectives (data-dependent counts
# would vary the reduction order, forking float summation run to run).
# Pinned per layout, next to the mesh shape they were derived on; the
# counts are per-shard jaxpr facts, so they hold for any mesh size.
# ---------------------------------------------------------------------------

# (workers axis) exposure all_to_all out+back, halo gather, psum reductions
# (one per worker-summed stat, live_tiles among them)
WORKERS_COLLECTIVES = {"all_to_all": 2, "all_gather": 1, "psum": 6}
# (scenarios axis) replicated-stat gathers only (one per stat) — no
# cross-scenario math
SCENARIOS_COLLECTIVES = {"all_gather": 11}
# (workers x scenarios) exactly the sum of the two axes' schedules, plus
# one extra all_gather where the scenario axis collects the worker-reduced
# stats
HYBRID_COLLECTIVES = {"all_to_all": 2, "all_gather": 12, "psum": 6}


@pytest.mark.parametrize("layout,kw,expected", [
    ("local", {}, {}),
    ("workers", dict(workers=1), WORKERS_COLLECTIVES),
    ("scenarios", dict(scen_shards=1), SCENARIOS_COLLECTIVES),
    ("hybrid", dict(workers=1, scen_shards=1), HYBRID_COLLECTIVES),
])
def test_collective_schedule_pinned_per_topology(pop, batch, layout, kw,
                                                 expected):
    from repro.analysis import hlo

    core = EngineCore(pop, batch, layout=layout, **kw)
    args = lambda days: (core.runner_fn(days, ()), core.params,
                         core.init_state(), (), core.week, core.route)
    counts = hlo.collective_count(*args(3))
    assert counts == expected, f"{layout} collective schedule changed"
    # ...and it must not scale with the day count: the collectives live in
    # the scan body, so a longer run replays the same schedule.
    assert hlo.collective_count(*args(6)) == expected


# ---------------------------------------------------------------------------
# bounded runner cache (the serve tier's executable-budget seam)
# ---------------------------------------------------------------------------


def test_runner_cache_bounded_lru(pop, batch):
    core = EngineCore(pop, batch, layout="local", max_runners=2)
    r3 = core.runner_fn(3, ())
    core.runner_fn(4, ())
    assert core.runner_cached(3, ()) and core.runner_cached(4, ())
    # a recency-bumping hit keeps (3,) alive through the next eviction
    assert core.runner_fn(3, ()) is r3
    core.runner_fn(5, ())  # evicts (4,), the least recently used
    assert core.runner_cached(3, ()) and core.runner_cached(5, ())
    assert not core.runner_cached(4, ())
    stats = core.runner_cache_stats()
    assert stats["size"] == 2 and stats["max_entries"] == 2
    assert stats["evictions"] == 1 and stats["hits"] == 1
    # re-building the evicted runner is correct, just a fresh trace
    assert core.runner_fn(4, ()) is not None
    assert core.runner_cache_stats()["evictions"] == 2


def test_local_rank_threshold_budget_semantics():
    topo = LocalTopology()
    score = jnp.asarray([0.5, 4.0, 0.1, 2.2, 4.0], jnp.float32)
    gpid = jnp.arange(5, dtype=jnp.uint32)
    T, G = topo.rank_threshold(score, gpid, jnp.asarray(2, jnp.int32), 5, 1)
    take = (score < T) | ((score == T) & (gpid <= G))
    np.testing.assert_array_equal(
        np.asarray(take), [True, False, True, False, False])
    # budget larger than the eligible pool: threshold lands on the 4.0 tier
    T, G = topo.rank_threshold(score, gpid, jnp.asarray(4, jnp.int32), 5, 1)
    assert float(T) == 4.0


# ---------------------------------------------------------------------------
# the day loop's named scopes are metadata: the optimised program with them
# is the program without them, once op_name and stack frames are stripped.
# ---------------------------------------------------------------------------


def _stripped_hlo(pop, batch, observables):
    import re

    from repro.api import observables as obs_lib

    core = EngineCore(pop, batch, layout="local", backend="jnp")
    ctx = obs_lib.ObsContext(num_people=pop.num_people,
                             num_scenarios=len(batch))
    text = core.runner_fn(2, observables).lower(
        core.params, core.init_state(),
        obs_lib.init_carries(observables, ctx), core.week, core.route,
    ).compile().as_text()
    return text, re.sub(r", metadata=\{[^}]*\}", "",
                        text.split("\nFileNames\n")[0])


def test_named_scopes_change_metadata_only(batch, monkeypatch):
    import contextlib

    from repro.api import observables as obs_lib
    from repro.configs import get_epidemic
    from repro.engine import day as day_lib

    pop = get_epidemic("md-mini").build()
    obs = obs_lib.make_observables(
        ["daily_new_infections", "attack_rate", "peak_day",
         "ensemble_mean_ci"])
    scoped, with_scopes = _stripped_hlo(pop, batch, obs)
    for name in day_lib.SCOPES:
        assert f"({name})/" in scoped or f"/{name}/" in scoped, name
    monkeypatch.setattr(day_lib, "_scope",
                        lambda name: contextlib.nullcontext())
    plain, without = _stripped_hlo(pop, batch, obs)
    assert not any(f"({name})/" in plain for name in day_lib.SCOPES)
    assert with_scopes == without


# ---------------------------------------------------------------------------
# the disease tables are read by a select over the S states: no gather in
# the day loop takes a table of shape (S,) or (S, S), batched or not (a TPU
# runs such a gather about one element at a time)
# ---------------------------------------------------------------------------


def _md_tti_arms():
    tti = lambda name, trace: iv.TestTraceIsolate(
        name, tests_per_day=15, isolation_days=10, trace=trace,
        trace_isolation_days=14, start_day=0,
    )
    return ScenarioBatch.from_product(
        interventions={"none": [], "tti": [tti("tti", True)],
                       "tti-no-trace": [tti("test-isolate", False)]},
        tau=2e-5, seeds=[11],
    )


@pytest.mark.parametrize("arms", ["classic", "md-tti"])
def test_day_step_reads_disease_tables_without_gather(pop, batch, arms):
    from repro.analysis import hlo

    b = batch if arms == "classic" else _md_tti_arms()
    core = EngineCore(pop, b, layout="local", backend="jnp")
    B, S = len(b), core.params.sus_table.shape[-1]
    if arms == "md-tti":
        assert core.static.pa_slots
    tables = {(S,), (S, S), (B, S), (B, S, S)}
    jaxpr = jax.make_jaxpr(core.runner_fn(1, ()))(
        core.params, core.init_state(), (), core.week, core.route).jaxpr
    gathers = [tuple(e.invars[0].aval.shape)
               for j in hlo._iter_jaxprs(jaxpr) for e in j.eqns
               if e.primitive.name == "gather"]
    assert gathers, "the walk missed the slot and location gathers"
    assert not tables & set(gathers), gathers
