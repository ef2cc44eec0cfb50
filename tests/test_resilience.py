"""Resilient chunked runs: checkpoint integrity, invariant guards, the
deterministic chaos harness, and elastic degradation.

The end-to-end matrix is the PR's acceptance bar: under every chaos
schedule a resilient run must complete **bitwise-equal** to the same run
without faults, with the recovery actions recorded in
``RunResult.provenance["resilience"]``.
"""

import dataclasses
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro import api
from repro.api.spec import ResilienceSpec
from repro.checkpoint import (
    CheckpointCorruptionError,
    CheckpointManager,
    leaf_digest,
)
from repro.core import disease, transmission
from repro.data import digital_twin_population
from repro.engine.core import EngineCore
from repro.runtime import (
    ChaosError,
    ChaosEvent,
    ChaosSchedule,
    GuardContext,
    InvariantViolation,
)
from repro.runtime.elastic import plan_elastic_rescale, repartition_person_array
from repro.runtime.guards import check_state


# ---------------------------------------------------------------------------
# checkpoint integrity (satellite: digests, validation, async errors)
# ---------------------------------------------------------------------------

def _tree():
    return {"a": jnp.arange(12, dtype=jnp.int32),
            "b": jnp.linspace(0.0, 1.0, 400,
                              dtype=jnp.float32).reshape(20, 20)}


def _leaf_path(mgr, step, key):
    return os.path.join(mgr.directory, f"step-{step:010d}",
                        key.replace("/", "__") + ".npy")


def test_manifest_carries_leaf_digests(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(3, _tree(), blocking=True)
    leaves = mgr.manifest(3)["leaves"]
    assert set(leaves) == {"a", "b"}
    assert leaves["a"]["shape"] == [12] and leaves["a"]["dtype"] == "int32"
    assert leaves["b"]["sha256"] == leaf_digest(np.load(_leaf_path(mgr, 3, "b")))


def test_corrupt_leaf_detected_and_named(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, _tree(), blocking=True)
    path = _leaf_path(mgr, 1, "b")
    with open(path, "r+b") as f:  # flip trailing payload bytes
        f.seek(os.path.getsize(path) - 8)
        chunk = f.read(4)
        f.seek(os.path.getsize(path) - 8)
        f.write(bytes(b ^ 0xFF for b in chunk))
    assert any("'b'" in p for p in mgr.verify(1))
    with pytest.raises(CheckpointCorruptionError, match="'b'"):
        mgr.restore_flat(1)


def test_truncated_leaf_detected(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, _tree(), blocking=True)
    path = _leaf_path(mgr, 1, "b")
    with open(path, "r+b") as f:
        f.truncate(os.path.getsize(path) // 2)
    with pytest.raises(CheckpointCorruptionError, match="'b'"):
        mgr.restore_flat(1)


def test_missing_leaf_detected(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, _tree(), blocking=True)
    os.remove(_leaf_path(mgr, 1, "a"))
    with pytest.raises(CheckpointCorruptionError, match="'a' is missing"):
        mgr.restore_flat(1)


def test_shape_dtype_validated_against_manifest(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, _tree(), blocking=True)
    np.save(_leaf_path(mgr, 1, "a"), np.zeros((3, 3), np.int32))
    with pytest.raises(CheckpointCorruptionError, match="'a' has shape"):
        mgr.restore_flat(1)
    np.save(_leaf_path(mgr, 1, "a"), np.zeros(12, np.float64))
    with pytest.raises(CheckpointCorruptionError, match="'a' has dtype"):
        mgr.restore_flat(1)


def test_restore_template_leaf_not_in_manifest(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"a": jnp.arange(4, dtype=jnp.int32)}, blocking=True)
    like = {"a": jax.ShapeDtypeStruct((4,), jnp.int32),
            "ghost": jax.ShapeDtypeStruct((2,), jnp.float32)}
    with pytest.raises(CheckpointCorruptionError, match="'ghost'"):
        mgr.restore(like, 1)


def test_latest_valid_step_quarantines_and_falls_back(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, _tree(), blocking=True)
    mgr.save(2, _tree(), blocking=True)
    with open(_leaf_path(mgr, 2, "b"), "r+b") as f:
        f.truncate(10)
    assert mgr.latest_valid_step() == 1
    assert mgr.quarantined_steps == [2]
    assert mgr.all_steps() == [1]  # the corrupt snapshot was moved aside
    assert os.path.isdir(os.path.join(str(tmp_path), "quarantine",
                                      f"step-{2:010d}"))


def test_legacy_manifest_without_digests_restores(tmp_path):
    import json
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, _tree(), blocking=True)
    mpath = os.path.join(mgr.directory, f"step-{1:010d}", "manifest.json")
    with open(mpath) as f:
        meta = json.load(f)
    for entry in meta["leaves"].values():  # pre-integrity checkpoint format
        del entry["sha256"]
    with open(mpath, "w") as f:
        json.dump(meta, f)
    out = mgr.restore_flat(1)
    np.testing.assert_array_equal(out["a"], np.arange(12))


def test_async_writer_exception_surfaces(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "ck"))
    os.rmdir(mgr.directory)
    with open(mgr.directory, "w") as f:  # writer's makedirs will fail
        f.write("not a directory")
    mgr.save(1, {"x": jnp.zeros(3, jnp.float32)})  # non-blocking: the
    # error lands in the writer thread
    with pytest.raises(RuntimeError, match="background checkpoint write"):
        mgr.wait()
    mgr.wait()  # surfaced once, then cleared


def test_readers_join_inflight_writer(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(5, _tree())  # async
    assert mgr.latest_step() == 5  # wait()s internally, never races
    assert mgr.latest_valid_step() == 5


# ---------------------------------------------------------------------------
# invariant guards
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_core():
    pop = digital_twin_population(300, seed=7, name="grd")
    return EngineCore.single(
        pop, disease.covid_model(),
        transmission.TransmissionModel(tau=2e-5), seed=3)


def test_guards_pass_on_healthy_state(small_core):
    st = small_core.init_state1()
    n = int(small_core.params.sus_table.shape[-1])
    assert check_state(st, num_states=n) == []


def test_guards_catch_bad_health_and_nan(small_core):
    st = small_core.init_state1()
    n = int(small_core.params.sus_table.shape[-1])
    bad = dataclasses.replace(st, health=st.health.at[0].set(n + 3))
    assert any("health" in v for v in check_state(bad, num_states=n))
    nanned = dataclasses.replace(st, dwell=st.dwell.at[1].set(jnp.nan))
    assert any("dwell" in v and "non-finite" in v
               for v in check_state(nanned, num_states=n))


def test_guard_context_monotonicity(small_core):
    st = small_core.init_state1()
    n = int(small_core.params.sus_table.shape[-1])
    g = GuardContext(num_states=n)
    g.check(st)  # establishes the baseline
    shrunk = dataclasses.replace(
        st, isolated_until=st.isolated_until - 5)
    with pytest.raises(InvariantViolation, match="isolated_until"):
        g.check(shrunk)
    g.reset(st)  # rebase (restore semantics): same state is fine again
    g.check(st)


# ---------------------------------------------------------------------------
# elastic rescaling (satellite: runtime/elastic.py coverage)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("old_w,new_w", [(3, 4), (4, 3), (5, 1), (1, 5)])
def test_plan_elastic_rescale_uneven(old_w, new_w):
    P = 10
    old, new, plan = plan_elastic_rescale(P, old_w, new_w)
    assert old == {"workers": old_w, "per_worker": -(-P // old_w)}
    assert new == {"workers": new_w, "per_worker": -(-P // new_w)}
    assert new["workers"] * new["per_worker"] >= P
    assert plan == [(slice(0, P), slice(0, P))]


@pytest.mark.parametrize("new_w", [1, 2, 3, 7])
def test_repartition_preserves_people_and_fills_pads(new_w):
    P = 11
    arr = np.arange(12).reshape(2, 6)  # 2 workers, 1 pad slot
    out = repartition_person_array(arr, P, new_w, fill=-1)
    pw = -(-P // new_w)
    assert out.shape == (new_w, pw)
    np.testing.assert_array_equal(out.reshape(-1)[:P], np.arange(P))
    assert np.all(out.reshape(-1)[P:] == -1)


def test_repartition_roundtrip_bitwise():
    P = 23
    orig = np.random.default_rng(0).integers(0, 100, size=(1, P))
    shrunk = repartition_person_array(orig, P, 5)
    regrown = repartition_person_array(shrunk, P, 1)
    np.testing.assert_array_equal(regrown.reshape(-1)[:P],
                                  orig.reshape(-1)[:P])


# ---------------------------------------------------------------------------
# chaos harness determinism
# ---------------------------------------------------------------------------

def test_chaos_schedule_random_deterministic():
    a = ChaosSchedule.random(seed=42, days=60, every=10)
    b = ChaosSchedule.random(seed=42, days=60, every=10)
    assert a.events == b.events
    assert all(ev.day % 10 == 0 and 0 < ev.day < 60 for ev in a.events)


def test_chaos_events_fire_once():
    sched = ChaosSchedule((ChaosEvent("raise", day=5),))
    with pytest.raises(ChaosError):
        sched.before_chunk(5)
    sched.before_chunk(5)  # one-shot: the replayed boundary is quiet
    assert sched.log == [("raise", 5)]


def test_chaos_event_validates_kind():
    with pytest.raises(ValueError, match="chaos kind"):
        ChaosEvent("meteor", day=1)


# ---------------------------------------------------------------------------
# end-to-end: the recovery matrix (acceptance bar)
# ---------------------------------------------------------------------------

DAYS, EVERY = 12, 3
OBSERVABLES = ("daily_new_infections", "attack_rate", "peak_day")


@pytest.fixture(scope="module")
def pop():
    return digital_twin_population(400, seed=11, name="res")


def _spec(**kw):
    base = dict(dataset="twin-2k", days=DAYS, tau=2e-5,
                interventions=("none",), replicates=2,
                observables=OBSERVABLES)
    base.update(kw)
    return api.ExperimentSpec(**base)


@pytest.fixture(scope="module")
def reference(pop):
    """The fault-free run every recovered run must match bitwise."""
    return api.run(_spec(), population=pop)


def _assert_bitwise(ref, res, layout_from=None):
    """``layout_from``: the day the run changed its worker count. The
    ``live_tiles`` counter counts each worker's own tiles, so it is
    compared up to that day only; every other key on every day."""
    assert set(ref.history) == set(res.history)
    for k in ref.history:
        days = layout_from if k == "live_tiles" else None
        np.testing.assert_array_equal(ref.history[k][:days],
                                      res.history[k][:days],
                                      err_msg=f"history[{k}] diverged")
    for k in ref.observables:
        r, s = ref.observables[k], res.observables[k]
        if isinstance(r, dict):
            for kk in r:
                np.testing.assert_array_equal(r[kk], s[kk])
        else:
            np.testing.assert_array_equal(r, s)


@pytest.mark.parametrize("kind", ["raise", "nan", "corrupt", "truncate"])
def test_chaos_recovery_bitwise(pop, reference, tmp_path, kind):
    spec = _spec().with_overrides(ckpt_dir=str(tmp_path), ckpt_every=EVERY,
                                  resilient=True)
    chaos = ChaosSchedule((ChaosEvent(kind, day=6),))
    res = api.run(spec, population=pop, chaos=chaos)
    _assert_bitwise(reference, res)

    rep = res.provenance["resilience"]
    assert rep["restarts"] == 1
    assert rep["faults"], "recovery actions must be recorded"
    if kind in ("corrupt", "truncate"):
        assert rep["snapshots_quarantined"] >= 1
        assert os.path.isdir(os.path.join(str(tmp_path), "quarantine"))
        assert res.provenance["resumed_from_day"] == 3  # fell back past day 6
    if kind == "nan":
        assert any("non-finite" in v for v in rep["guard_violations"])
        # the poisoned state must never have reached disk
        mgr = CheckpointManager(str(tmp_path))
        for step in mgr.all_steps():
            flat = mgr.restore_flat(step)
            for k, v in flat.items():
                if np.issubdtype(v.dtype, np.floating):
                    assert np.all(np.isfinite(v)), f"step {step} leaf {k}"


def test_chaos_recovery_all_engines(pop, reference, tmp_path):
    """The recovery loop is layout-independent: a pinned single/dist
    (sequential, observables replayed) engine recovers bitwise too."""
    spec = _spec(engine="single").with_overrides(
        ckpt_dir=str(tmp_path), ckpt_every=EVERY, resilient=True)
    res = api.run(spec, population=pop,
                  chaos=ChaosSchedule((ChaosEvent("raise", day=6),)))
    _assert_bitwise(reference, res)
    assert res.provenance["resilience"]["restarts"] == 1


def test_straggler_detection_and_repartition(pop, reference, tmp_path):
    spec = _spec().with_overrides(ckpt_dir=str(tmp_path), ckpt_every=2)
    spec = dataclasses.replace(spec, resilience=ResilienceSpec(
        enabled=True, repartition_on_straggler=True, straggler_factor=3.0))
    calls = []
    res = api.run(spec, population=pop,
                  chaos=ChaosSchedule((ChaosEvent("slow", day=8, sleep_s=0.6),)),
                  on_straggler=lambda day, dt, med: calls.append((day, dt, med)))
    _assert_bitwise(reference, res)
    rep = res.provenance["resilience"]
    assert rep["straggler_events"] and calls
    assert rep["straggler_events"][0]["day"] == 10  # the slowed chunk's end
    assert rep["repartitions"] == 1  # rebuilt once, then the window resets
    assert rep["restarts"] == 0  # a repartition is not a failure


def test_restart_cap_exhausted(pop, tmp_path):
    spec = _spec().with_overrides(ckpt_dir=str(tmp_path), ckpt_every=EVERY,
                                  resilient=True, max_restarts=0)
    with pytest.raises(ChaosError):
        api.run(spec, population=pop,
                chaos=ChaosSchedule((ChaosEvent("raise", day=6),)))


def test_resilient_requires_checkpoint_dir(pop):
    with pytest.raises(ValueError, match="checkpoint"):
        _spec(resilience=ResilienceSpec(enabled=True)).validate()
    with pytest.raises(ValueError, match="resilient"):
        api.run(_spec(), population=pop,
                chaos=ChaosSchedule((ChaosEvent("raise", day=6),)))


def test_resume_falls_back_past_corrupt_newest(pop, reference, tmp_path):
    """Offline corruption of the newest snapshot: a plain (non-resilient)
    resume quarantines it and restarts from the next-older valid step."""
    spec6 = _spec(days=6).with_overrides(ckpt_dir=str(tmp_path),
                                         ckpt_every=EVERY)
    api.run(spec6, population=pop)  # leaves steps 3 and 6 on disk
    mgr = CheckpointManager(str(tmp_path))
    assert mgr.all_steps() == [3, 6]
    # damage the biggest leaf of step 6
    d = os.path.join(str(tmp_path), f"step-{6:010d}")
    names = [f for f in os.listdir(d) if f.endswith(".npy")]
    path = os.path.join(d, max(names, key=lambda f: os.path.getsize(
        os.path.join(d, f))))
    with open(path, "r+b") as f:
        f.truncate(os.path.getsize(path) // 2)

    res = api.run(_spec().with_overrides(ckpt_dir=str(tmp_path),
                                         ckpt_every=EVERY), population=pop)
    assert res.provenance["resumed_from_day"] == 3
    assert os.path.isdir(os.path.join(str(tmp_path), "quarantine",
                                      f"step-{6:010d}"))
    _assert_bitwise(reference, res)


# ---------------------------------------------------------------------------
# elastic degradation (device loss) — needs >= 2 devices; the CI
# chaos-matrix job runs this file with 4 emulated host devices.
# ---------------------------------------------------------------------------

@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs >= 2 devices")
@pytest.mark.parametrize("engine,replicates", [("dist", 1), ("hybrid", 2)])
def test_device_loss_elastic_shrink(pop, tmp_path, engine, replicates):
    spec = _spec(engine=engine, replicates=replicates,
                 mesh=api.MeshSpec(workers=2))
    ref = api.run(spec, population=pop)
    res = api.run(
        spec.with_overrides(ckpt_dir=str(tmp_path), ckpt_every=EVERY,
                            resilient=True),
        population=pop,
        chaos=ChaosSchedule((ChaosEvent("device_loss", day=6,
                                        workers_lost=1),)))
    _assert_bitwise(ref, res, layout_from=6)
    rep = res.provenance["resilience"]
    assert rep["device_losses"] == [{"workers_before": 2, "workers_after": 1}]
    assert rep["final_workers"] == 1
    assert rep["final_layout"] == ("workers" if engine == "dist" else "hybrid")


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs >= 2 devices")
def test_engine_adopt_state_repads_person_axis(pop):
    """EngineCore.adopt_state re-partitions person-axis leaves from a
    2-worker padded layout onto 1 worker, preserving every real person."""
    spec2 = _spec(engine="dist", replicates=1, mesh=api.MeshSpec(workers=2))
    from repro.api.runner import _make_core
    core2 = _make_core("dist", spec2.validate(), pop, spec2.build_batch())
    st2 = core2.init_state()
    spec1 = dataclasses.replace(spec2, mesh=api.MeshSpec(workers=1))
    core1 = _make_core("dist", spec1.validate(), pop, spec1.build_batch())
    adopted = core1.adopt_state(st2)
    tmpl = core1.init_state()
    assert adopted.health.shape == tmpl.health.shape
    P = pop.num_people
    np.testing.assert_array_equal(
        np.asarray(adopted.health).reshape(-1)[:P],
        np.asarray(st2.health).reshape(-1)[:P])


# ---------------------------------------------------------------------------
# spec / CLI plumbing
# ---------------------------------------------------------------------------

def test_resilience_spec_roundtrip_and_cli_flags(tmp_path):
    spec = _spec().with_overrides(ckpt_dir=str(tmp_path), resilient=True,
                                  max_restarts=7)
    assert spec.resilience.enabled and spec.resilience.max_restarts == 7
    back = api.ExperimentSpec.from_dict(spec.to_dict())
    assert back.resilience == spec.resilience

    import argparse
    from repro.launch import cli
    ap = cli.add_common_args(argparse.ArgumentParser())
    args = ap.parse_args(["--resilient", "--max-restarts", "2",
                          "--ckpt-dir", str(tmp_path)])
    built = cli.build_spec(args, dict(dataset="twin-2k", days=5))
    assert built.resilience.enabled and built.resilience.max_restarts == 2
    args2 = ap.parse_args(["--no-resilient"])
    built2 = cli.build_spec(args2, dict(dataset="twin-2k", days=5))
    assert built2.resilience.enabled is False
