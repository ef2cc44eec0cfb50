"""The benchmark harness on the CPU, at test size.

A cell is data: a configuration file, a traffic file, a limits file and a
reader per per-layer metric, all found by the names in ``BENCHMARK.json``.
These tests add a throwaway cell of that kind as files only and drive the
harness through it (``run_cell``; the command looks for a chip before
it), check the twin cache and the compile clock, and check that the
command itself refuses to run without a TPU or without the program
beside it.
"""

import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness, twin_cache  # noqa: E402
from bench.clock import CompileClock, HIT_EVENT  # noqa: E402

METRIC = '''"""Chunks the window completed (a throwaway per-layer metric)."""


def read(run):
    return float(run.chunks) if run.chunks else None
'''


def _write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        if isinstance(obj, str):
            f.write(obj)
        else:
            json.dump(obj, f)


def throwaway_root(root, *, backend="jnp", config="md-ens"):
    """A checkout whose BENCHMARK.json names one new cell ``tiny.light``:
    a new configuration (the md-ens arms on twin-2k), a new traffic mix
    and a new per-layer metric, each a file of its own."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cfg = json.load(open(os.path.join(ROOT, "bench", "configs",
                                      config + ".json")))
    cfg.update(name="tiny", dataset="twin-2k", people=2000, backend=backend)
    _write(os.path.join(root, "bench", "configs", "tiny.json"), cfg)
    _write(os.path.join(root, "bench", "traffic", "light.json"),
           {"seed_per_day": 20, "seed_days": 7, "start_day": 7,
            "chunk_days": 7})
    limits = json.load(open(os.path.join(ROOT, "bench", "limits",
                                         "md-ens.onset.json")))
    _write(os.path.join(root, "bench", "limits", "tiny.light.json"), limits)
    _write(os.path.join(root, "bench", "metrics", "window.chunks.py"),
           METRIC)
    metric = {"name": "window.chunks", "unit": "chunks", "better": "higher",
              "source": "host_clock", "layer": "harness",
              "moves": "scenario_days_per_s", "workloads": ["tiny.light"]}
    _write(os.path.join(root, "BENCHMARK.json"), {
        **bench,
        "configs": [{"name": "tiny", "source": "test",
                     "file": "bench/configs/tiny.json", "reduced": [],
                     "why": "test"}],
        "workloads": [{"name": "tiny.light", "config": "tiny",
                       "traffic": "light", "chips": 1, "why": "test"}],
        "per_layer": [metric],
    })
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return throwaway_root(str(tmp_path_factory.mktemp("checkout")))


@pytest.mark.parametrize("trace", [False, True])
def test_new_cell_from_files(root, trace):
    t0 = time.perf_counter()
    run, line = harness.run_cell(root, "tiny.light", 2 ** 31 + 12345, 0.3,
                                 trace, t_start=t0)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] == run.chunks >= 1 and line["failed"] == 0
    assert run.window_compiles == 0
    dev = line["device"]
    assert (dev["platform"], dev["count"]) == ("cpu", 1)
    assert set(line["checks"]) >= {"state_mismatch", "stat_gap"}
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}
    m = line["metrics"]
    if not trace:
        assert set(m) == {"scenario_days_per_s", "setup_s"}
        assert m["scenario_days_per_s"]["unit"] == "scenario-days/s"
        assert m["scenario_days_per_s"]["value"] == pytest.approx(
            4 * 7 * run.chunks / run.window_s)
        assert 0 < m["setup_s"]["value"] < time.perf_counter() - t0
        assert "breakdown" not in line
    else:
        assert m == {"window.chunks": {"value": float(run.chunks),
                                       "unit": "chunks"}}
        assert 0 < dev["busy_s"] <= dev["window_s"]
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        assert 0 < len(line["breakdown"]["device_ops"]) <= 10


def test_cells_of_benchmark_load():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for w in bench["workloads"]:
        cell = harness.load_cell(ROOT, w["name"])
        assert cell.traffic["start_day"] % cell.traffic["chunk_days"] == 0
        assert set(cell.limits) >= {"state_mismatch", "stat_gap"}
        for m in cell.per_layer:
            assert callable(harness.load_reader(ROOT, m["name"]))
        harness.build_batch(cell.config, cell.traffic, seed=1)
    with pytest.raises(KeyError, match="no workload"):
        harness.load_cell(ROOT, "no-such.cell")


def _arrays(obj, prefix=""):
    import dataclasses

    out = {}
    for f in dataclasses.fields(obj):
        v = getattr(obj, f.name)
        if isinstance(v, np.ndarray):
            out[prefix + f.name] = v
        elif not f.name.startswith("_") and not isinstance(v, list):
            out[prefix + f.name] = np.asarray(v)
    return out


def test_twin_cache_round_trip(tmp_path):
    from repro.engine.core import EngineCore

    built, was_built = twin_cache.load_or_build(str(tmp_path), "twin-2k", 128)
    assert was_built
    cached, was_built = twin_cache.load_or_build(str(tmp_path), "twin-2k",
                                                 128)
    assert not was_built
    pairs = [(_arrays(built), _arrays(cached))]
    pairs += [(_arrays(a), _arrays(b)) for a, b in zip(built.week,
                                                       cached.week)]
    pairs += [(_arrays(a), _arrays(b)) for a, b in
              zip(built.packed_week(128), cached.packed_week(128))]
    for a, b in pairs:
        assert a.keys() == b.keys()
        for k in a:
            assert a[k].dtype == b[k].dtype, k
            assert np.array_equal(a[k], b[k]), k

    cfg = json.load(open(os.path.join(ROOT, "bench", "configs",
                                      "md-tti.json")))
    traffic = {"seed_per_day": 20, "seed_days": 7}
    hists = []
    for pop in (built, cached):
        core = EngineCore(pop, harness.build_batch(cfg, traffic, seed=3),
                          layout="local", backend="jnp",
                          max_seed_per_day=20)
        _, _, hist, _ = core.run_days(10)
        hists.append(hist)
    for k in hists[0]:
        assert np.array_equal(hists[0][k], hists[1][k]), k
    assert hists[0]["cumulative"][-1].max() > 0


def test_twin_cache_key_follows_sources(tmp_path, monkeypatch):
    a = twin_cache.cache_path(str(tmp_path), "twin-2k")
    monkeypatch.setattr(twin_cache, "SOURCES", twin_cache.SOURCES[:-1])
    assert twin_cache.cache_path(str(tmp_path), "twin-2k") != a


def test_compile_clock():
    import jax
    import jax.numpy as jnp
    from jax import monitoring

    clock = CompileClock(monitoring)
    t0 = time.perf_counter()
    seed = np.float32(time.time() % 1)  # a program no cache has seen
    jax.jit(lambda x: jnp.cos(x) * seed + 1)(
        jnp.ones(7, jnp.float32)).block_until_ready()
    assert clock.count(t0) >= 1
    assert clock.seconds(t0) > 0
    assert clock.count(time.perf_counter()) == 0
    hits = clock.hits
    monitoring.record_event(HIT_EVENT)
    assert clock.hits == hits + 1


def _command(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "md-ens.onset",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _printed_result(stdout):
    lines = stdout.strip().splitlines()
    return bool(lines) and lines[-1].lstrip().startswith("{")


def test_command_refuses_cpu():
    p = _command(ROOT)
    assert p.returncode != 0
    assert not _printed_result(p.stdout)
    assert "no TPU" in p.stderr


def test_command_needs_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    p = _command(str(tmp_path))
    assert p.returncode != 0
    assert not _printed_result(p.stdout)


def test_seed_params_match_a_fresh_engine(root):
    """bench/readings.py swaps a seed's parameters into one engine; they
    are those an engine built for that seed holds."""
    import jax

    from bench import readings

    cell = harness.load_cell(root, "tiny.light")
    cfg, traffic = cell.config, cell.traffic
    pop, _ = twin_cache.load_or_build(root, cfg["dataset"], cfg["block_size"])
    core, _, _ = harness.make_engine(pop, cfg, traffic, 2 ** 31 + 5)
    swapped = readings.seed_params(pop, cfg, traffic, 2 ** 31 + 5)
    other = readings.seed_params(pop, cfg, traffic, 6)
    same = jax.tree.map(lambda a, b: bool(np.array_equal(a, b)),
                        core.params, swapped)
    assert all(jax.tree.leaves(same))
    assert not all(jax.tree.leaves(jax.tree.map(
        lambda a, b: bool(np.array_equal(a, b)), core.params, other)))
