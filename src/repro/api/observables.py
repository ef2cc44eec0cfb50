"""On-device observables: streaming reductions over the day loop.

An :class:`Observable` is an ``init / update / finalize`` triple over the
per-day stats pytree every engine's day step emits (keys
``repro.core.simulator.STAT_KEYS``, leaves carrying a leading scenario
axis ``(B,)``). ``update`` runs *inside* the scan — per-day outputs are
stacked by the scan itself and running reductions (attack rate, peak-day
argmax, cross-scenario mean/CI bands) live in the scan carry, so nothing
round-trips through the host per day. This closes the ROADMAP item
"cross-scenario reductions computed on-device inside the scan".

Two drivers consume the same observables:

  * the in-scan path — :func:`repro.api.runner` threads the carries through
    the vmapped day-loop scan for the ``ensemble`` engine, whose whole
    batch lives in one scan body;
  * :func:`observe_history` — an on-device ``lax.scan`` of the same update
    functions over a day-major history, used post-run for the shard_map
    engines (whose scan bodies only see a shard of the batch axis).

Because ``update`` is a pure deterministic function of the stats values,
both paths produce bit-identical results (tested in tests/test_api.py).

Observable carries are ordinary pytrees but are *not* persisted in
checkpoints: on resume, :func:`scan_history` replays the pure updates over
the checkpointed history-so-far, reconstructing the carries exactly. A
future observable whose carry is not a pure function of the daily stats
(e.g. one reading per-person state) would need its carry added to the
checkpoint payload in ``repro.api.runner``.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class ObsContext:
    """Static study geometry the reductions need at trace time.

    ``sweep_axes`` describes the factorial design the batch expands:
    ``((axis_name, (level_of_scenario_0, level_of_scenario_1, ...)), ...)``
    — one entry per sweep axis with more than one level, each scenario
    assigned its level index on that axis. Sensitivity observables (Sobol)
    group scenarios by level; everything is plain hashable tuples so the
    context can key jit caches."""

    num_people: int
    num_scenarios: int
    sweep_axes: tuple = ()


@dataclasses.dataclass(frozen=True)
class Observable:
    """Base streaming reduction. Subclasses override the three hooks;
    frozen/field-free so instances hash (jit-cache keys) and serialize by
    registry name."""

    name = "observable"

    def init(self, ctx: ObsContext):
        """Initial carry pytree (device arrays or empty tuples)."""
        return ()

    def update(self, carry, stats):
        """One day's update: ``(carry, stats) -> (carry, daily_output)``.
        ``stats`` leaves are ``(B,)``; runs inside jit/scan — jnp only.
        ``daily_output`` is stacked day-major by the scan; return ``()``
        for reductions with no per-day series."""
        return carry, ()

    def finalize(self, carry, ctx: ObsContext) -> dict:
        """Named end-of-run results from the final carry."""
        return {}

    def finalize_series(self, daily, ctx: ObsContext) -> dict:
        """Named results from the stacked day-major ``daily`` outputs of
        :meth:`update`; by default the series themselves."""
        return daily if isinstance(daily, dict) else {"daily": daily}

    def to_host(self, res: dict) -> dict:
        """The finalized results as host numpy values."""
        return res


@dataclasses.dataclass(frozen=True)
class DailyNewInfections(Observable):
    """The day-major incidence series (one column per scenario)."""

    name = "daily_new_infections"

    def update(self, carry, stats):
        return carry, {"daily": stats["new_infections"]}


@dataclasses.dataclass(frozen=True)
class AttackRate(Observable):
    """Final cumulative infections / population, per scenario."""

    name = "attack_rate"

    def init(self, ctx):
        return jnp.zeros((ctx.num_scenarios,), jnp.int32)

    def update(self, carry, stats):
        return stats["cumulative"], ()

    def finalize(self, carry, ctx):
        return {
            "cumulative": carry,
            "attack_rate": carry.astype(jnp.float32) / ctx.num_people,
        }


@dataclasses.dataclass(frozen=True)
class PeakDay(Observable):
    """Running argmax of the infectious curve (first-peak semantics,
    matching ``np.argmax``), per scenario."""

    name = "peak_day"

    def init(self, ctx):
        B = ctx.num_scenarios
        return (jnp.full((B,), -1, jnp.int32), jnp.zeros((B,), jnp.int32))

    def update(self, carry, stats):
        best, best_day = carry
        inf = stats["infectious"].astype(jnp.int32)
        better = inf > best  # strict: ties keep the earlier day
        return (
            jnp.where(better, inf, best),
            jnp.where(better, stats["day"].astype(jnp.int32), best_day),
        ), ()

    def finalize(self, carry, ctx):
        best, best_day = carry
        return {"peak_infectious": best, "peak_day": best_day}


@dataclasses.dataclass(frozen=True)
class EnsembleMeanCI(Observable):
    """Cross-scenario mean and normal-approximation 95% CI band of the
    daily incidence and infectious curves — the ensemble-aware reduction
    computed where the batch axis lives (on device, inside the scan).
    Degenerates to the trajectory itself (zero-width band) at B=1."""

    name = "ensemble_mean_ci"
    Z = 1.96
    KEYS = ("new_infections", "infectious")

    def update(self, carry, stats):
        return carry, {k: stats[k] for k in self.KEYS}

    def finalize_series(self, daily, ctx):
        # The band is computed after the scan, by one compiled program over
        # the stacked series, whichever path produced them: two programs
        # that each fuse the float arithmetic differ in its last bit (XLA
        # turns /B into *(1/B) and may fold it into the next add), and the
        # post-scan replay must match the in-scan run bit for bit.
        return {k: _mean_ci_band(daily[k], self.Z) for k in self.KEYS}


@functools.partial(jax.jit, static_argnames="z")
def _mean_ci_band(x, z):
    """Per-day mean and normal-approximation CI band over the scenario
    axis of a (days, B) series."""
    x = x.astype(jnp.float32)
    B = x.shape[1]  # static
    m = jnp.mean(x, axis=1)
    if B > 1:
        sem = jnp.std(x, axis=1, ddof=1) / np.sqrt(B).astype(np.float32)
    else:
        sem = jnp.zeros_like(m)
    return {"mean": m, "lo": m - z * sem, "hi": m + z * sem}


@dataclasses.dataclass(frozen=True)
class SobolFirstOrder(Observable):
    """First-order Sobol sensitivity indices of the final cumulative
    infection count over the study's sweep axes.

    For a full-factorial design the first-order index of axis ``a`` is
    estimated as the between-level variance fraction

        S1_a = Var_l( E[Y | X_a = l] ) / Var(Y),

    with ``E[Y | X_a = l]`` the mean outcome over the scenarios at level
    ``l`` (all other axes marginalized — exact for a balanced factorial,
    the classic Sobol/ANOVA decomposition) and both variances population
    variances over the batch. Streaming: the carry tracks the running
    cumulative count per scenario (the same carry AttackRate keeps);
    grouping happens once, in ``finalize``, from ``ctx.sweep_axes``.
    Host-side numpy reference in tests/test_api.py."""

    name = "sobol_first_order"

    def init(self, ctx):
        return jnp.zeros((ctx.num_scenarios,), jnp.int32)

    def update(self, carry, stats):
        return stats["cumulative"], ()

    def finalize(self, carry, ctx):
        y = carry.astype(jnp.float32)
        mu = jnp.mean(y)
        var = jnp.mean((y - mu) ** 2)
        s1 = {}
        for axis_name, levels in ctx.sweep_axes:
            g = jnp.asarray(levels, jnp.int32)
            L = int(max(levels)) + 1
            sums = jnp.zeros((L,), jnp.float32).at[g].add(y)
            cnts = jnp.zeros((L,), jnp.float32).at[g].add(1.0)
            gmean = sums / jnp.maximum(cnts, 1.0)
            var_between = jnp.sum(cnts * (gmean - mu) ** 2) / y.shape[0]
            s1[axis_name] = jnp.where(var > 0.0, var_between / var, jnp.nan)
        return {"variance": var, "S1": s1}


@dataclasses.dataclass(frozen=True)
class TEPS(Observable):
    """Traversed-edge telemetry: the day-major edge series per scenario
    plus a running total across days and scenarios — the numerator of the
    paper's headline metric (traversed edges per second; §VI reports 4.6B
    on the California twin). On the pallas-compact backend the per-day
    counts come from the kernel's own accumulator; elsewhere they are
    host-derived (and everywhere equal to ``stats["contacts"]``, which
    tests assert). The denominator (measured wall clock) is a host-side
    quantity: :func:`repro.api.runner.run` divides it in after the scan."""

    name = "teps"

    def init(self, ctx):
        # The running total as the (high, low) u32 words of a 64-bit
        # count: exact at any scale without x64 (no 64-bit ints then), where
        # an f32 sum stops being exact at 2^24 edges.
        return jnp.zeros((2,), jnp.uint32)

    def update(self, carry, stats):
        e = stats["edges"].astype(jnp.uint32)
        hi, lo = carry[0], carry[1]
        for i in range(e.shape[0]):  # static B
            nxt = lo + e[i]
            hi = hi + (nxt < lo).astype(jnp.uint32)  # carry out of lo
            lo = nxt
        return jnp.stack([hi, lo]), {"daily": e}

    def finalize(self, carry, ctx):
        return {"edges_total": carry}

    def to_host(self, res):
        hi, lo = (int(w) for w in np.asarray(res["edges_total"]))
        return {**res, "edges_total": np.int64((hi << 32) | lo)}


@dataclasses.dataclass(frozen=True)
class TestsUsed(Observable):
    """Day-major tests-administered series plus the running total per
    scenario — the utilization of the capacity-limited test budget."""

    name = "tests_used"

    def init(self, ctx):
        return jnp.zeros((ctx.num_scenarios,), jnp.int32)

    def update(self, carry, stats):
        t = stats["tests_used"].astype(jnp.int32)
        return carry + t, {"daily": t}

    def finalize(self, carry, ctx):
        return {"tests_total": carry}


@dataclasses.dataclass(frozen=True)
class IsolatedCount(Observable):
    """Day-major count of people in isolation, with the per-scenario peak
    (the isolation-capacity planning number)."""

    name = "isolated_count"

    def init(self, ctx):
        return jnp.zeros((ctx.num_scenarios,), jnp.int32)

    def update(self, carry, stats):
        iso = stats["isolated"].astype(jnp.int32)
        return jnp.maximum(carry, iso), {"daily": iso}

    def finalize(self, carry, ctx):
        return {"peak_isolated": carry}


@dataclasses.dataclass(frozen=True)
class AvertedByTTI(Observable):
    """Infections averted relative to scenario 0, per scenario.

    Convention: the study's first scenario is the no-TTI (or reference)
    arm — ``averted[b] = cumulative[0] - cumulative[b]``, so the baseline
    row reads 0 and intervention arms read their absolute effect size.
    Cross-scenario, so it sees the gathered full batch on every topology."""

    name = "averted_by_tti"

    def init(self, ctx):
        return jnp.zeros((ctx.num_scenarios,), jnp.int32)

    def update(self, carry, stats):
        return stats["cumulative"].astype(jnp.int32), ()

    def finalize(self, carry, ctx):
        return {"cumulative": carry, "averted": carry[0] - carry}


OBSERVABLES = {
    o.name: type(o)
    for o in (DailyNewInfections(), AttackRate(), PeakDay(), EnsembleMeanCI(),
              SobolFirstOrder(), TEPS(), TestsUsed(), IsolatedCount(),
              AvertedByTTI())
}


def make_observables(names) -> tuple:
    return tuple(OBSERVABLES[n]() for n in names)


# ---------------------------------------------------------------------------
# drivers
# ---------------------------------------------------------------------------


def init_carries(observables, ctx: ObsContext) -> tuple:
    return tuple(o.init(ctx) for o in observables)


def update_all(observables, carries, stats):
    """One day across every observable; returns (carries, {name: daily})."""
    new_carries, daily = [], {}
    for o, c in zip(observables, carries):
        c, d = o.update(c, stats)
        new_carries.append(c)
        daily[o.name] = d
    return tuple(new_carries), daily


def finalize_all(observables, carries, dailies, ctx: ObsContext) -> dict:
    """Merge each observable's finalized reductions with its stacked
    day-major series (under the ``"daily"``-rooted keys its update
    emitted)."""
    out = {}
    for o, c in zip(observables, carries):
        res = dict(o.finalize(c, ctx))
        d = dailies.get(o.name, ()) if dailies is not None else ()
        if jax.tree.leaves(d):
            res.update(o.finalize_series(d, ctx))
        out[o.name] = res
    return out


def scan_history(observables, hist, ctx: ObsContext):
    """One on-device ``lax.scan`` of the updates over a day-major history.

    ``hist`` maps STAT_KEYS to ``(days, B)`` arrays (device or host — host
    arrays are placed once). Returns ``(carries, dailies)`` mid-stream, so
    a resumed run can replay its pre-checkpoint reductions exactly and
    keep streaming from there."""
    hist_dev = {k: jnp.asarray(v) for k, v in hist.items()}
    carries = init_carries(observables, ctx)

    def body(c, stats):
        return update_all(observables, c, stats)

    return jax.lax.scan(body, carries, hist_dev)


def observe_history(observables, hist, ctx: ObsContext) -> dict:
    """Run the observables over an existing day-major history, on device.

    This is the post-run driver for engines whose scan bodies never see
    the whole batch axis (shard_map shards it); bit-identical to the
    in-scan path by purity of ``update``."""
    carries, dailies = scan_history(observables, hist, ctx)
    return finalize_all(observables, carries, dailies, ctx)


def observables_to_numpy(obs: dict) -> dict:
    """device pytrees -> host numpy (for RunResult / serialization)."""
    host = jax.tree.map(lambda x: np.asarray(x), jax.device_get(obs))
    return {name: OBSERVABLES[name]().to_host(res) if name in OBSERVABLES
            else res for name, res in host.items()}
