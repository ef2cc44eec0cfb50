"""Shared benchmark helpers: timing, CSV emission, dataset cache."""

from __future__ import annotations

import functools
import time

import numpy as np
import jax

from repro.launch.cli import use_compile_cache

# Every benchmark script imports this module first: one persistent
# compilation cache for all of them.
use_compile_cache()

_ROWS: list[str] = []


def emit(name: str, us_per_call: float, derived: str = ""):
    row = f"{name},{us_per_call:.1f},{derived}"
    _ROWS.append(row)
    print(row, flush=True)


def rows() -> list[str]:
    return list(_ROWS)


def time_fn(fn, *args, warmup: int = 1, iters: int = 3):
    """Median wall time of fn(*args) with block_until_ready."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


@functools.lru_cache(maxsize=8)
def get_pop(name: str):
    from repro.configs import get_epidemic

    return get_epidemic(name).build()


def day_step_fn(core):
    """A jitted single-day step over a B=1 EngineCore's own scenario —
    ``state -> (state', stats)`` — for per-day microbenchmarks."""
    from repro.core import simulator

    static, week, contact_prob, params = simulator.legacy_parts(core)
    return jax.jit(
        lambda st: simulator.day_step(static, week, contact_prob, params, st)
    )


def calibrated_tau(pop_name: str) -> float:
    """Transmissibilities tuned (offline) so the infectious peak lands mid-
    run (paper §VI: 'tuned so that the number of infectious people peaked
    about halfway through the simulations')."""
    return {
        "twin-2k": 2.0e-5,
        "md-mini": 8.0e-6,
        "va-mini": 8.0e-6,
        "ws-50k": 5.0e-6,
        "ws-200k": 4.0e-6,
        "grid-tiny": 8.0e-6,
        "grid-1x": 6.0e-6,
    }.get(pop_name, 8.0e-6)
