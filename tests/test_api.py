"""The unified front door (repro.api): spec round-trips, engine-dispatch
parity (one ExperimentSpec -> bitwise-equal trajectories through every
engine), on-device observables vs a host-side numpy reference, and
chunk-boundary checkpoint/resume bitwise equality."""

import dataclasses
import os

import numpy as np
import pytest

from repro import api
from repro.analysis.report import summarize_result
from repro.api import observables as obs_lib
from repro.configs import get_epidemic
from repro.core import simulator

EXAMPLES = os.path.join(os.path.dirname(__file__), "..", "examples")


@pytest.fixture(scope="module")
def pop():
    return get_epidemic("twin-2k").build()


def _spec(**kw):
    base = dict(dataset="twin-2k", days=8, tau=2e-5,
                interventions=("none", "school-closure"), replicates=1)
    base.update(kw)
    return api.ExperimentSpec(**base).validate()


# ---------------------------------------------------------------------------
# spec serialization round-trips
# ---------------------------------------------------------------------------


def test_spec_json_roundtrip():
    spec = _spec(
        tau_scales=(1.0, 0.8), replicates=2, backend="compact",
        mesh=api.MeshSpec(workers=2, scenarios=2),
        checkpoint=api.CheckpointSpec(directory="/tmp/x", every=25),
        observables=("attack_rate",),
    )
    again = api.ExperimentSpec.from_json(spec.to_json())
    assert again == spec
    assert api.ExperimentSpec.from_dict(spec.to_dict()) == spec
    # nested dataclasses survive the dict form
    assert again.mesh.workers == 2
    assert again.checkpoint.every == 25
    assert again.num_scenarios == 2 * 2 * 2


def test_spec_rejects_unknowns():
    with pytest.raises(ValueError, match="unknown ExperimentSpec field"):
        api.ExperimentSpec.from_dict({"dataset": "twin-2k", "dayz": 3})
    with pytest.raises(ValueError, match="intervention preset"):
        _spec(interventions=("no-such-preset",))
    with pytest.raises(ValueError, match="dataset"):
        _spec(dataset="no-such-dataset")
    with pytest.raises(ValueError, match="observable"):
        _spec(observables=("no-such-observable",))
    with pytest.raises(ValueError, match="engine"):
        _spec(engine="no-such-engine")


def test_spec_toml_golden():
    """The checked-in examples/experiment.toml is the TOML golden file."""
    spec = api.ExperimentSpec.from_file(
        os.path.join(EXAMPLES, "experiment.toml"))
    assert spec.dataset == "twin-2k"
    assert spec.interventions == ("none", "school-closure", "tti")
    assert spec.tau_scales == (1.0, 0.8)
    assert spec.replicates == 2
    assert spec.num_scenarios == 12
    assert spec.mesh == api.MeshSpec(workers=1, scenarios=1)
    assert spec.checkpoint.every == 10
    # TOML -> spec -> JSON -> spec is exact
    assert api.ExperimentSpec.from_json(spec.to_json()) == spec


def test_spec_cli_overrides():
    spec = _spec()
    over = spec.with_overrides(days=None, workers=2, ckpt_dir="/tmp/y",
                               backend="compact")
    assert over.days == spec.days  # None = flag not given
    assert over.mesh.workers == 2 and over.mesh.scenarios == 1
    assert over.checkpoint.directory == "/tmp/y"
    assert over.backend == "compact"


# ---------------------------------------------------------------------------
# engine-dispatch parity: one spec, every engine, bitwise-equal trajectories
# ---------------------------------------------------------------------------


def test_engine_dispatch_parity(pop):
    """The acceptance bar: the same ExperimentSpec dispatched through all
    engines yields bitwise-equal per-scenario trajectories and observables
    (1-device worker/scenario meshes, so it runs everywhere)."""
    spec = _spec()
    ref = api.run(spec, population=pop)
    assert ref.provenance["engine"] == "ensemble"  # B=2, 1x1 mesh
    assert ref.history["cumulative"].shape == (spec.days, 2)

    for engine in ("single", "dist", "sharded", "hybrid"):
        r = api.run(spec.with_overrides(engine=engine), population=pop)
        assert r.provenance["engine"] == engine
        for k in simulator.STAT_KEYS:
            np.testing.assert_array_equal(
                ref.history[k], r.history[k], err_msg=f"{engine}/{k}")
        # finalized observables agree bitwise too (same pure reductions)
        for name, vals in ref.observables.items():
            for leaf_a, leaf_b in zip(_leaves(vals),
                                      _leaves(r.observables[name])):
                np.testing.assert_array_equal(
                    leaf_a, leaf_b, err_msg=f"{engine}/{name}")
        assert r.scenario_names == ref.scenario_names

    # ...and the facade matches a hand-rolled single-scenario core run.
    from repro.engine.core import EngineCore
    batch = spec.build_batch()
    for i, s in enumerate(batch):
        sim = EngineCore.single(
            pop, s.disease, s.tm, interventions=s.interventions,
            seed=s.seed, iv_enabled=s.iv_enabled,
        )
        _, h = sim.run1(spec.days)
        np.testing.assert_array_equal(h["cumulative"],
                                      ref.history["cumulative"][:, i])


def _leaves(tree):
    import jax

    return [np.asarray(x) for x in jax.tree.leaves(tree)]


def test_auto_engine_single_for_one_scenario(pop):
    r = api.run(_spec(interventions=("none",), days=4), population=pop)
    assert r.provenance["engine"] == "single"
    assert r.history["cumulative"].shape == (4, 1)  # B axis kept at B=1


# ---------------------------------------------------------------------------
# observables: on-device (in-scan and post-scan) vs host-side numpy
# ---------------------------------------------------------------------------


def test_observables_match_numpy_reference(pop):
    spec = _spec(replicates=3, interventions=("none",))  # B=3 MC band
    r = api.run(spec, population=pop)
    assert r.provenance["observables_in_scan"] is True
    hist = r.history
    B = r.num_scenarios

    # attack rate & cumulative
    np.testing.assert_array_equal(
        r.observables["attack_rate"]["cumulative"], hist["cumulative"][-1])
    np.testing.assert_allclose(
        r.observables["attack_rate"]["attack_rate"],
        hist["cumulative"][-1].astype(np.float32) / pop.num_people,
        rtol=1e-6)

    # peak-day argmax (first-peak semantics == np.argmax)
    np.testing.assert_array_equal(
        r.observables["peak_day"]["peak_day"],
        np.argmax(hist["infectious"], axis=0))
    np.testing.assert_array_equal(
        r.observables["peak_day"]["peak_infectious"],
        hist["infectious"].max(axis=0))

    # daily incidence series is the history column
    np.testing.assert_array_equal(
        r.observables["daily_new_infections"]["daily"],
        hist["new_infections"])

    # cross-scenario mean/CI band vs numpy
    x = hist["new_infections"].astype(np.float32)
    m = x.mean(axis=1)
    sem = x.std(axis=1, ddof=1) / np.sqrt(B)
    band = r.observables["ensemble_mean_ci"]["new_infections"]
    np.testing.assert_allclose(band["mean"], m, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(band["lo"], m - 1.96 * sem, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(band["hi"], m + 1.96 * sem, rtol=1e-5, atol=1e-4)

    # the post-scan on-device driver is bitwise-identical to in-scan
    obs = obs_lib.make_observables(spec.observables)
    ctx = obs_lib.ObsContext(num_people=pop.num_people, num_scenarios=B)
    replay = obs_lib.observables_to_numpy(
        obs_lib.observe_history(obs, hist, ctx))
    for name in r.observables:
        for a, b in zip(_leaves(r.observables[name]), _leaves(replay[name])):
            np.testing.assert_array_equal(a, b, err_msg=name)


def test_sobol_first_order_matches_numpy(pop):
    """The streaming Sobol observable vs a host-side numpy reference on a
    2x2x2 factorial sweep, on an in-scan and a post-scan engine."""
    spec = _spec(
        interventions=("none", "school-closure"), tau_scales=(1.0, 0.7),
        replicates=2, days=10,
        observables=("attack_rate", "sobol_first_order"),
    )
    r = api.run(spec, population=pop)
    assert r.provenance["observables_in_scan"] is True
    y = r.history["cumulative"][-1].astype(np.float32)
    B = y.shape[0]
    assert B == 8
    mu, var = y.mean(), y.var()

    # factorial order: interventions x tau x replicates, replicates inner
    idx = np.arange(B)
    levels = {
        "interventions": idx // 4,
        "tau_scales": (idx // 2) % 2,
        "replicates": idx % 2,
    }
    got = r.observables["sobol_first_order"]
    np.testing.assert_allclose(got["variance"], var, rtol=1e-5)
    for axis, g in levels.items():
        gmeans = np.array([y[g == l].mean() for l in range(2)])
        cnts = np.array([(g == l).sum() for l in range(2)], np.float32)
        s1_ref = float((cnts * (gmeans - mu) ** 2).sum() / B / var)
        np.testing.assert_allclose(got["S1"][axis], s1_ref, rtol=1e-4,
                                   err_msg=axis)
    # sensible magnitudes: tau and intervention axes explain more variance
    # than Monte Carlo replicates on this config
    assert 0.0 <= got["S1"]["replicates"] <= 1.0 + 1e-6

    # a post-scan engine (pinned single, B>1) reproduces the same indices
    r2 = api.run(spec.with_overrides(engine="single"), population=pop)
    for axis in levels:
        np.testing.assert_array_equal(got["S1"][axis],
                                      r2.observables["sobol_first_order"]["S1"][axis])


# ---------------------------------------------------------------------------
# chunk-boundary checkpoint/resume
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("engine",
                         ["single", "ensemble", "dist", "sharded", "hybrid"])
def test_checkpoint_resume_bitwise(pop, tmp_path, engine):
    """A run interrupted at a chunk boundary and resumed is bitwise-equal
    to the uninterrupted run — state, history, and observable reductions —
    on every layout (1-device worker/scenario meshes, so it runs
    everywhere; the chunk loop lives in the engine core now)."""
    days = 12
    spec = _spec(days=days, engine=engine)
    ref = api.run(spec, population=pop)

    ck = spec.with_overrides(ckpt_dir=str(tmp_path / engine), ckpt_every=5)
    # "interrupt" after 5 days: a prefix run that checkpoints day 5
    api.run(dataclasses.replace(ck, days=5).validate(), population=pop)
    resumed = api.run(ck, population=pop)

    assert resumed.provenance["resumed_from_day"] == 5
    for k in simulator.STAT_KEYS:
        np.testing.assert_array_equal(ref.history[k], resumed.history[k],
                                      err_msg=k)
    for name in ref.observables:
        for a, b in zip(_leaves(ref.observables[name]),
                        _leaves(resumed.observables[name])):
            np.testing.assert_array_equal(a, b, err_msg=name)
    # a second resume from the final checkpoint is a no-op run
    again = api.run(ck, population=pop)
    assert again.provenance["resumed_from_day"] == days
    np.testing.assert_array_equal(ref.history["cumulative"],
                                  again.history["cumulative"])


def test_resume_rejects_prerefactor_checkpoint(pop, tmp_path):
    """A checkpoint written by the pre-refactor per-engine loops (whose
    resume keys carry no engine-core generation marker) must be refused by
    the resume-key guard, not spliced into a unified-core trajectory."""
    import json

    ck = _spec(days=6).with_overrides(ckpt_dir=str(tmp_path / "old"),
                                      ckpt_every=3)
    api.run(ck, population=pop)
    # Rewrite the manifest to the pre-refactor key format (no "core").
    step_dir = sorted((tmp_path / "old").glob("step-*"))[-1]
    manifest = json.loads((step_dir / "manifest.json").read_text())
    assert manifest["extra"]["resume_key"].pop("core") is not None
    (step_dir / "manifest.json").write_text(json.dumps(manifest))
    with pytest.raises(ValueError, match="incompatible spec or engine"):
        api.run(dataclasses.replace(ck, days=9).validate(), population=pop)


def test_resume_rejects_incompatible_spec(pop, tmp_path):
    """A checkpoint written under one parameterization must not be spliced
    into a run with another (same shapes, different science)."""
    ck = _spec(days=6).with_overrides(ckpt_dir=str(tmp_path / "ck"),
                                      ckpt_every=3)
    api.run(ck, population=pop)
    with pytest.raises(ValueError, match="incompatible spec"):
        api.run(dataclasses.replace(ck, tau=1e-5).validate(), population=pop)
    # ...but extending days (the resume use case) is allowed
    longer = dataclasses.replace(ck, days=9).validate()
    r = api.run(longer, population=pop)
    assert r.provenance["resumed_from_day"] == 6


def test_run_file_with_overrides(tmp_path):
    """The golden TOML runs end-to-end through run_file, flags-style
    overrides applying on top (the --spec CLI path in library form)."""
    r = api.run_file(os.path.join(EXAMPLES, "experiment.toml"),
                     days=3, replicates=1, tau_scales=(1.0,))
    assert r.spec.days == 3
    assert r.num_scenarios == 3  # replicates/tau_scales overridden
    assert r.history["cumulative"].shape == (3, 3)


# ---------------------------------------------------------------------------
# RunResult round-trip + report consumption
# ---------------------------------------------------------------------------


def test_run_result_json_roundtrip(pop, tmp_path):
    r = api.run(_spec(days=5), population=pop)
    path = str(tmp_path / "result.json")
    r.save(path)
    back = api.RunResult.load(path)
    assert back.spec == r.spec
    assert back.scenario_names == r.scenario_names
    np.testing.assert_array_equal(back.history["cumulative"],
                                  r.history["cumulative"])
    # report rows from observables == rows computed from history
    assert summarize_result(back) == r.summaries
    # legacy fallback path: strip the observables, rows still come out
    back.observables = {}
    assert summarize_result(back) == r.summaries


def test_teps_total_is_exact_beyond_float32():
    """The TEPS running total is exact far past 2^24 (where an f32 sum
    starts rounding) and past 2^32 (where one u32 word wraps), against the
    int64 sum of the daily series."""
    rs = np.random.default_rng(0)
    days, B = 50, 3
    edges = rs.integers(2**30, 2**32, size=(days, B), dtype=np.uint64)
    edges = edges.astype(np.uint32)
    hist = {"edges": edges}
    obs = obs_lib.make_observables(("teps",))
    ctx = obs_lib.ObsContext(num_people=1, num_scenarios=B)
    res = obs_lib.observables_to_numpy(
        obs_lib.observe_history(obs, hist, ctx))["teps"]
    total = int(edges.astype(np.int64).sum())
    assert total > 2**32
    assert int(res["edges_total"]) == total
    np.testing.assert_array_equal(res["daily"], edges)


def test_compile_cache_location(monkeypatch):
    """Entry points keep JAX's compile cache where JAX_COMPILATION_CACHE_DIR
    says, setting nothing themselves; otherwise at the fixed
    <checkout>/.jax_cache."""
    import jax
    from repro.launch import cli

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert cli.use_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = cli.use_compile_cache()
        assert path == str(cli.CHECKOUT / ".jax_cache")
        assert (cli.CHECKOUT / "src" / "repro").is_dir()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
