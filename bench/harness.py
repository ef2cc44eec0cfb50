"""The benchmark harness: one run of one cell.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is a file found by the name ``BENCHMARK.json`` gives it:

* ``configs[].file``: the configuration (twin, disease, arms, backend);
* ``bench/traffic/<traffic>.json``: the traffic (outbreak seeding, the
  window's start day, the chunk length);
* ``bench/limits/<cell>.json``: the limit of each number that decides
  ``correct``, with the readings it was set from;
* ``bench/metrics/<metric>.py``: a reader with ``read(run) -> float|None``.

A run: set-up (twin from the cache, engine build, one compiled runner of
``chunk_days`` days, advance to the window's start day, one warm chunk),
then the window (the same chunk replayed from the same state until
``seconds`` have passed), then the check of the window's last chunk and of
the advance against the plain reference (``bench/reference.py``).
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import time

import numpy as np


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# finding a cell's files by name
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: str, name: str) -> Cell:
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = _read_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _read_json(os.path.join(root, "bench", "traffic",
                                      w["traffic"] + ".json"))
    limits = _read_json(os.path.join(root, "bench", "limits", name + ".json"))

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    return Cell(
        name=name, chips=int(w["chips"]), config=cfg, traffic=traffic,
        limits=limits,
        end_to_end=[m for m in bench["end_to_end"] if mine(m)],
        per_layer=[m for m in bench["per_layer"] if mine(m)],
    )


def load_reader(root: str, metric: str):
    path = os.path.join(root, "bench", "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# the configuration as the program runs it
# ---------------------------------------------------------------------------


def _selector(sel: dict):
    from repro.core import interventions as iv

    kind = sel["kind"]
    if kind == "everyone":
        return iv.Everyone()
    if kind == "age_group":
        return iv.AgeGroupIs(sel["group"])
    if kind == "loc_type":
        return iv.LocTypeIs(sel["loc_type"])
    if kind == "random_fraction":
        return iv.RandomFraction(sel["fraction"], salt=sel["salt"])
    raise ValueError(f"unknown selector {kind!r}")


def _intervention(d: dict):
    from repro.core import interventions as iv

    if d["action"] == "test_trace_isolate":
        return iv.TestTraceIsolate(
            d["name"], tests_per_day=d["tests_per_day"],
            selector=_selector(d["selector"]),
            isolation_days=d["isolation_days"], trace=d["trace"],
            trace_isolation_days=d.get("trace_isolation_days", 0),
            start_day=d["start_day"])
    t = d["trigger"]
    if t["kind"] == "day_range":
        trigger = iv.DayRange(t["start"], t.get("end", 10 ** 9))
    elif t["kind"] == "case_threshold":
        trigger = iv.CaseThreshold(t["on"], t.get("off"),
                                   t.get("metric", "infectious"))
    else:
        raise ValueError(f"unknown trigger {t['kind']!r}")
    action = {
        "isolate": lambda: iv.Isolate(),
        "close": lambda: iv.CloseLocations(),
        "vaccinate": lambda: iv.Vaccinate(d["efficacy"]),
    }[d["action"]]()
    return iv.Intervention(d["name"], trigger, _selector(d["selector"]),
                           action)


def build_batch(cfg: dict, traffic: dict, seed: int):
    """The cell's arms as one ScenarioBatch with a union slot layout, arm
    ``a`` under the Monte Carlo seed ``arm_seed(seed, a)``."""
    from bench.reference import arm_seed
    from repro.configs.sweep import ScenarioBatch
    from repro.core import disease as disease_lib

    d = cfg["disease"]
    disease = disease_lib.make_disease(
        d["name"], d["states"], d["susceptibility"], d["infectivity"],
        d["transitions"], d["dwell_mean_days"], d["entry_state"],
        d["initial_state"], symptomatic=d["symptomatic"])
    batch = ScenarioBatch.from_product(
        interventions={a["name"]: [_intervention(x) for x in
                                   a["interventions"]]
                       for a in cfg["arms"]},
        tau=cfg["tau"], disease=disease,
        time_unit=cfg.get("time_unit", 1.0),
        seed_per_day=traffic["seed_per_day"],
        seed_days=traffic["seed_days"],
        static_network=cfg.get("static_network", False))
    return ScenarioBatch.from_scenarios(
        [dataclasses.replace(s, seed=arm_seed(seed, a))
         for a, s in enumerate(batch)])


def raw_twin(pop):
    """The twin's raw data, as the reference takes it."""
    from bench.reference import Twin

    return Twin(
        num_people=pop.num_people, num_locations=pop.num_locations,
        age_group=np.asarray(pop.age_group), beta_sus=pop.beta_sus,
        beta_inf=pop.beta_inf, loc_type=np.asarray(pop.loc_type),
        week=[(d.person, d.loc, d.start, d.end) for d in pop.week],
        num_real=[d.num_real for d in pop.week])


def state_to_host(state) -> dict:
    import jax

    return {k: np.asarray(v) for k, v in
            jax.device_get(dataclasses.asdict(state)).items()}


def make_engine(pop, cfg: dict, traffic: dict, seed: int):
    """The program as the window drives it: the engine over the cell's
    arms, the observables' initial carries, and the compiled runner of one
    chunk. Returns ``(core, runner, carries)``."""
    from repro.api import observables as obs_lib
    from repro.engine.core import EngineCore

    core = EngineCore(
        pop, build_batch(cfg, traffic, seed), layout=cfg["layout"],
        backend=cfg["backend"], block_size=cfg["block_size"],
        max_seed_per_day=traffic["seed_per_day"])
    B = len(cfg["arms"])
    obs = obs_lib.make_observables(cfg["observables"])
    ctx = obs_lib.ObsContext(
        num_people=pop.num_people, num_scenarios=B,
        sweep_axes=(("interventions", tuple(range(B))),) if B > 1 else ())
    runner = core.runner_fn(int(traffic["chunk_days"]), obs)
    return core, runner, obs_lib.init_carries(obs, ctx)


def advance_chunks(runner, params, state, carries, week, route, n: int):
    """``n`` chunks from ``state``. Returns the last state and each
    chunk's ``(state, hist)`` on the host."""
    import jax

    out = []
    for _ in range(n):
        state, _, hist, _ = runner(params, state, carries, week, route)
        out.append((state_to_host(state), jax.device_get(hist)))
    return state, out


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Run:
    """What a run measured; per-layer readers take their numbers from it."""

    cell: Cell
    seed: int
    num_scenarios: int
    chunk_days: int
    setup_s: float = 0.0
    twin_s: float = 0.0
    twin_built: bool = False
    engine_build_s: float = 0.0
    compile_s: float = 0.0
    cache_hits: int = 0
    cache_misses: int = 0
    window_s: float = 0.0
    chunks: int = 0
    failed_chunks: int = 0
    window_compiles: int = 0
    peak_bytes: int = 0
    edges_per_chunk: int = 0
    trace: dict = None  # bench.trace.reduce of the traced window
    reference_s: float = 0.0
    compared: dict = None  # every number bench.reference.compare gives
    checks: dict = None  # the numbers held to a limit, with it

    @property
    def scenario_days(self) -> int:
        return self.num_scenarios * self.chunk_days * self.chunks


def _log(*parts):
    print(*parts, file=sys.stderr, flush=True)


def require_chips(cell: Cell) -> None:
    """Raise NoChip unless JAX finds TPUs, as many as the cell asks for,
    of a kind whose peaks ``bench/peaks.py`` knows."""
    import jax

    from bench import peaks

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found '{devices[0].platform}'")
    if len(devices) < cell.chips:
        raise NoChip(f"the cell needs {cell.chips} chips, JAX found "
                     f"{len(devices)}")
    peaks.lookup(devices[0].device_kind)


def run_cell(root: str, name: str, seed: int, seconds: float, trace: bool,
             *, t_start: float) -> tuple[Run, dict]:
    """Set up, measure and check one cell on the devices JAX finds.
    Returns the run and the result line."""
    import jax
    from jax import monitoring
    from jax import profiler

    from bench import twin_cache
    from bench.clock import CompileClock

    cell = load_cell(root, name)
    devices = jax.devices()
    dev = devices[0]

    cache_dir = jax.config.jax_compilation_cache_dir
    clock = CompileClock(monitoring)
    cfg, traffic = cell.config, cell.traffic
    K = int(traffic["chunk_days"])
    d0 = int(traffic["start_day"])
    if d0 % K:
        raise ValueError("start_day must be a whole number of chunks")
    B = len(cfg["arms"])
    run = Run(cell=cell, seed=seed, num_scenarios=B, chunk_days=K)
    _log(f"[{name}] device {dev.device_kind} x{len(devices)}; compile "
         f"cache {cache_dir}")

    t_setup = time.perf_counter()
    with profiler.TraceAnnotation("setup"):
        t0 = time.perf_counter()
        pop, run.twin_built = twin_cache.load_or_build(
            root, cfg["dataset"], cfg["block_size"])
        run.twin_s = time.perf_counter() - t0
        if pop.num_people != cfg["people"]:
            raise ValueError(f"twin has {pop.num_people} people, the "
                             f"configuration states {cfg['people']}")
        t0 = time.perf_counter()
        core, runner, carries0 = make_engine(pop, cfg, traffic, seed)
        params, week, route = core.params, core.week, core.route
        state = core.init_state()
        jax.block_until_ready((params, week, state))
        run.engine_build_s = time.perf_counter() - t0 - clock.seconds(t0)
        state, advance = advance_chunks(runner, params, state, carries0,
                                        week, route, d0 // K)
        s0 = state
        out = runner(params, s0, carries0, week, route)
        jax.block_until_ready(out)
    run.setup_s = time.perf_counter() - t_start
    run.compile_s = clock.seconds(t_setup)
    run.cache_hits, run.cache_misses = clock.hits, clock.misses

    trace_dir = os.path.join(root, "bench", ".cache", "trace")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        profiler.start_trace(trace_dir)
    first_hist = None
    t_window = time.perf_counter()
    with profiler.TraceAnnotation("window"):
        while True:
            with profiler.TraceAnnotation("chunk_dispatch"):
                out = runner(params, s0, carries0, week, route)
            with profiler.TraceAnnotation("device_wait"):
                jax.block_until_ready(out)
            with profiler.TraceAnnotation("history_fetch"):
                hist = jax.device_get(out[2])
            run.chunks += 1
            if first_hist is None:
                first_hist = hist
            elif any(not np.array_equal(hist[k], first_hist[k])
                     for k in first_hist):
                run.failed_chunks += 1
            if time.perf_counter() - t_window >= seconds:
                break
    run.window_s = time.perf_counter() - t_window
    run.window_compiles = clock.count(t_window)
    if run.window_compiles:
        raise RuntimeError(f"{run.window_compiles} compiles inside the "
                           "window: a shape was not warmed up")
    if trace:
        profiler.stop_trace()
        from bench import trace as trace_lib

        run.trace = trace_lib.summarize(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    stats = dev.memory_stats() or {}
    run.peak_bytes = int(stats.get("peak_bytes_in_use", 0))
    run.edges_per_chunk = int(np.asarray(hist["edges"], np.int64).sum())

    prog = advance + [(state_to_host(out[0]), hist)]
    del core, runner, params, week, route, state, s0, out, carries0
    gc.collect()

    from bench.reference import compare, stretches

    t0 = time.perf_counter()
    # The window replays one chunk from the start state; the reference
    # runs it once, after the advance.
    want = stretches(cfg, traffic, raw_twin(pop), seed,
                     cfg.get("dtype", "float32"))
    numbers = compare(prog, want)
    run.reference_s = time.perf_counter() - t0
    _log(f"[{name}] reference {run.reference_s:.1f}s")
    run.compared = numbers
    run.checks = {k: {"value": numbers[k], "limit": cell.limits[k]["limit"]}
                  for k in cell.limits}
    run.checks["window_chunks_differing"] = {
        "value": run.failed_chunks, "limit": 0}
    return run, result_line(root, run, dev, len(devices), trace)


def result_line(root: str, run: Run, dev, count: int, trace: bool) -> dict:
    correct = all(c["value"] <= c["limit"] for c in run.checks.values())
    metrics = {}
    if trace:
        for m in run.cell.per_layer:
            v = load_reader(root, m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = {
            "scenario_days_per_s": run.scenario_days / run.window_s,
            "setup_s": run.setup_s,
        }
        for m in run.cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": count, "memory_peak_bytes": run.peak_bytes}
    line = {"correct": correct, "attempted": run.chunks,
            "failed": run.failed_chunks, "metrics": metrics,
            "device": device}
    if trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        line["breakdown"] = {"device_ops": run.trace["device_ops"],
                             "idle_gaps": run.trace["idle_gaps"]}
    line["checks"] = run.checks
    return line


def report(run: Run) -> None:
    """The run's other numbers, on lines before the result."""
    info = {
        "cell": run.cell.name, "seed": run.seed, "scenarios":
        run.num_scenarios, "chunks": run.chunks, "window_s": run.window_s,
        "setup_s": run.setup_s, "twin_s": run.twin_s,
        "twin_built": run.twin_built, "engine_build_s": run.engine_build_s,
        "compile_s": run.compile_s, "cache_hits": run.cache_hits,
        "cache_misses": run.cache_misses,
        "window_compiles": run.window_compiles,
        "edges_per_chunk": run.edges_per_chunk,
        "reference_s": run.reference_s, "compared": run.compared,
        "teps": run.edges_per_chunk * run.chunks / run.window_s,
    }
    if run.trace is not None:
        info["trace_kind_s"] = run.trace["kind_s"]
    print(json.dumps(info), flush=True)
