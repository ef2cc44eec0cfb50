"""Plain reference of the epidemic day loop, written apart from the program.

It follows the model as the configuration file states it and imports
nothing of ``repro``. Its inputs are the twin's raw data (people, their
attributes, locations, and each weekday's visits as person, location,
start and end) and the cell's configuration. From those it works out
itself what the program derives: each location's peak occupancy and
contact probability, the intervention selectors, the disease tables.

One simulated day, per scenario:

1. Interventions. Classic slots fold into a visit mask, a location mask
   and a susceptibility factor (vaccination). Test-trace-isolate slots
   test the eligible people with the smallest ``(draw, person)`` keys, up
   to the day's capacity, symptomatic people first. Order statistics are
   found by counting (bisection), not by sorting.
2. Exposure. For every infectious visit ``j`` and every visit ``i`` to the
   same location with overlapping time, ``i`` and ``j`` active and of
   different people, the pair makes contact when its draw is below the
   location's contact probability. A contact adds
   ``overlap * sus_i * inf_j`` to person ``i``'s exposure and, where
   ``j``'s person tested positive, one to ``i``'s traced count.
3. Infection where the draw exceeds ``exp(-tau * exposure)``; outbreak
   seeding on the first days; the state machine's timed transitions.
4. Daily counts, isolation and tracing, then the triggers.

How the pairs are enumerated (:func:`walk_pairs`, :func:`exposure`). A
day's real visits are sorted by location, so the visits to the location
of visit ``j`` are one contiguous window: ``wfirst[j]`` to ``wfirst[j] +
wsize[j] - 1``. The infectious visits are the rows. Each row is put in
the size class of its window, the least power of two ``W`` that holds it
(up to ``WIDTH_MAX``; a wider window is walked as several chunks of
``WIDTH_MAX``), and the rows of one class are taken a block at a time as
an ``[R, W]`` grid of (row, place in the window): its partner ``i`` is
``wfirst[j]`` plus the place, and places past ``wsize[j]`` are masked. So
no pair is searched for, and the work is the pairs, rounded up to a power
of two per row. A block is taken in two passes. The contact draw needs
only the two people and the location, so it is made for every pair;
the overlap and the susceptibility are fetched only for the pairs whose
draw fell below the contact probability, which a sort brings to the
front. Per-person disease-table reads are compare-selects over the S
states (:func:`lookup`), and each location's peak occupancy comes from
two sorts of the day's arrivals and departures (:func:`_peak_one_day`).

Every draw is the counter hash of ``(seed, stream, day, ids)``, so the
program and this reference draw the same numbers. What can differ is the
order in which floating-point exposure is summed; see ``compare``.

``dtype`` sets the precision of the floating-point work. ``float32`` is
what the configuration states; ``bfloat16`` is the control, which the
comparison has to fail.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

DAYS_PER_WEEK = 7
ABSORBING_DWELL = np.float32(1.0e9)
NEVER_OFF = np.float32(-3.0e38)

# Counter-hash streams of the model: one per kind of random decision.
CONTACT, INFECT, TRANSITION, DWELL, SEED_CHOICE, INIT_ATTR, TEST = (
    1, 2, 3, 4, 5, 7, 8)
_GOLDEN = 0x9E3779B9
_C1, _C2 = 0x85EBCA6B, 0xC2B2AE35

#: Pairs (row, place in the window) handled per step of the exposure loop.
PAIR_BLOCK = 1 << 17
#: The widest window walked in one piece; wider ones are walked in chunks.
WIDTH_MAX = 1 << 10
#: Drawn pairs given a closer look per step.
DRAWN_BLOCK = 1 << 13

#: One day's visits and their windows, as the device holds them.
DAY_KEYS = ("person", "loc", "start", "end", "wfirst", "wsize")
STAT_KEYS = ("day", "new_infections", "cumulative", "infectious",
             "susceptible", "contacts", "edges", "tests_used", "isolated",
             "traced")
PERSON_LEAVES = ("health", "dwell", "vaccinated", "tested", "traced",
                 "isolated_until")
SCALAR_LEAVES = ("day", "cumulative", "iv_active")


# ---------------------------------------------------------------------------
# the counter hash
# ---------------------------------------------------------------------------


def _mix(h, xp):
    u = xp.uint32
    h = h ^ (h >> u(16))
    h = h * u(_C1)
    h = h ^ (h >> u(13))
    h = h * u(_C2)
    return h ^ (h >> u(16))


def _hash(seed, words, xp):
    u = xp.uint32
    with np.errstate(over="ignore"):
        seed = u(seed & 0xFFFFFFFF) if isinstance(seed, int) \
            else xp.asarray(seed).astype(u)
        h = _mix(seed ^ u(_GOLDEN), xp)
        for i, w in enumerate(words):
            w = xp.asarray(w).astype(u) if not isinstance(w, int) \
                else u(w & 0xFFFFFFFF)
            h = _mix(h ^ _mix(w + u((_GOLDEN * (i + 1)) & 0xFFFFFFFF), xp),
                     xp)
    return h


def arm_seed(seed: int, arm: int) -> int:
    """The Monte Carlo seed of arm ``arm`` of a run under ``seed``: every
    arm draws an epidemic of its own."""
    return (seed + arm * _GOLDEN) & 0xFFFFFFFF


def draw(seed, *words):
    """U(0, 1) on the grid of 2**-24, offset by 2**-25, as float32."""
    h = _hash(seed, words, jnp)
    return ((h >> jnp.uint32(8)).astype(jnp.int32).astype(jnp.float32)
            * jnp.float32(2.0 ** -24) + jnp.float32(2.0 ** -25))


def draw_host(seed, *words):
    """The same draw on the host, in float64, for the selectors that are
    fixed before a run (compliance samples)."""
    h = _hash(seed, words, np)
    return (h >> np.uint32(8)).astype(np.float64) * 2.0 ** -24 + 2.0 ** -25


# ---------------------------------------------------------------------------
# inputs
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Twin:
    """The raw data of a twin: what a census-derived dataset holds."""

    num_people: int
    num_locations: int
    age_group: np.ndarray  # (P,) int
    beta_sus: np.ndarray  # (P,) f32
    beta_inf: np.ndarray  # (P,) f32
    loc_type: np.ndarray  # (L,) int
    # per weekday: (person, loc, start, end), real visits first, sorted by
    # location, then padding with person -1
    week: list
    num_real: list


def contact_probability(peak, a=5.0, b=40.0, alpha=1000.0):
    """The min/max/alpha contact model (paper Eq. 1) on peak occupancy."""
    n = np.asarray(peak, np.float32)
    expected = a + (b - a) * (1.0 - np.exp(-n / alpha))
    p = expected / np.maximum(n - 1.0, 1.0)
    return np.where(n <= 2.0, 1.0, np.minimum(p, 1.0)).astype(np.float32)


@jax.jit
def _peak_one_day(loc, start, end, real, last):
    """Peak number of simultaneous visitors at each location on one day:
    +1 at each arrival, -1 at each departure, departures first at equal
    times. ``last[l]`` is the index of location ``l``'s last event once
    the day's events are sorted by location, -1 where it has none.

    Each location's arrivals and departures balance, so the running count
    is 0 before its first event and its peak is the largest running count
    among its events, which a second sort, by (location, count), puts at
    its last event."""
    nowhere = jnp.iinfo(jnp.int32).max  # padding sorts last
    locs = jnp.where(jnp.concatenate([real, real]),
                     jnp.concatenate([loc, loc]), nowhere)
    times = jnp.concatenate([start, end])
    arrive = jnp.concatenate([jnp.ones_like(loc), jnp.zeros_like(loc)])
    locs_s, _, arrive_s = jax.lax.sort((locs, times, arrive), num_keys=3)
    _, run = jax.lax.sort((locs_s, jnp.cumsum(2 * arrive_s - 1)), num_keys=2)
    return jnp.where(last >= 0, run[jnp.maximum(last, 0)], 0)


def locations_of_day(loc: np.ndarray, n: int, num_locations: int):
    """For one day's visits (real first, sorted by location): each visit's
    window, the index of the first real visit to its location and their
    number (0 and 0 for padding), and each location's ``last`` event for
    ``_peak_one_day``."""
    count = np.bincount(loc[:n], minlength=num_locations)
    end = np.cumsum(count)
    wfirst = np.zeros(len(loc), np.int32)
    wsize = np.zeros(len(loc), np.int32)
    wfirst[:n] = (end - count)[loc[:n]]
    wsize[:n] = count[loc[:n]]
    last = np.where(count > 0, 2 * end - 1, -1).astype(np.int32)
    return wfirst, wsize, last


def check_twin(twin: Twin):
    """The layout the enumeration relies on: real visits first, sorted by
    location, then padding."""
    for (person, loc, _, _), n in zip(twin.week, twin.num_real):
        if (person[:n] < 0).any() or (person[n:] >= 0).any():
            raise ValueError("visits must be real first, then padding")
        if n > 1 and (np.diff(loc[:n]) < 0).any():
            raise ValueError("real visits must be sorted by location")


def size_classes(max_window: int, width_max: int = WIDTH_MAX) -> tuple:
    """``(width, chunks)`` of each size class of windows: widths 1, 2, 4,
    ... up to the first that holds ``max_window`` visits or is
    ``width_max``, one chunk each; then, if some window is wider than
    ``width_max``, a class that walks each window as that many chunks of
    ``width_max``."""
    if width_max & (width_max - 1):
        raise ValueError("width_max must be a power of two")
    classes, w = [], 1
    while w < min(max_window, width_max):
        classes.append((w, 1))
        w *= 2
    classes.append((w, 1))
    if max_window > width_max:
        classes.append((width_max, -(-max_window // width_max)))
    return tuple(classes)


def walk_pairs(classes: tuple, wfirst, wsize, is_row, visit, carry):
    """Hand every pair ``(j, i)`` of a row ``j`` (``is_row``) and a visit
    ``i`` in its window, ``i == j`` included, to ``visit(carry, j, i,
    ok)``, a block at a time: ``j`` is an ``[R]`` array of rows, ``i`` and
    ``ok`` are ``[R, W]``, and only the entries where ``ok`` holds are
    pairs. Returns the last carry.

    The rows are sorted by size class (``size_classes``) with one sort of
    ``class * V + visit``. A class of width ``W`` and ``T`` chunks gives
    each of its rows ``T`` pieces of ``W`` places, and is walked in blocks
    of ``R = PAIR_BLOCK // W`` pieces."""
    V = is_row.shape[0]
    C = len(classes)
    cls = sum((wsize > w).astype(jnp.int32) for w, _ in classes[:-1])
    key = jnp.sort(jnp.where(is_row, cls, C) * V
                   + jnp.arange(V, dtype=jnp.int32))
    offset = jnp.searchsorted(key, jnp.arange(C + 1, dtype=jnp.int32) * V)
    rows = key % V
    for c, (W, T) in enumerate(classes):
        R = max(PAIR_BLOCK // W, 1)
        pieces = (offset[c + 1] - offset[c]) * T
        place = jnp.arange(W, dtype=jnp.int32)[None, :]

        def block(b, carry, c=c, W=W, T=T, R=R, pieces=pieces, place=place):
            k = b * R + jnp.arange(R, dtype=jnp.int32)
            j = rows[jnp.minimum(offset[c] + k // T, V - 1)]
            at = (k % T)[:, None] * W + place
            ok = (k < pieces)[:, None] & (at < wsize[j][:, None])
            return visit(carry, j, jnp.where(ok, wfirst[j][:, None] + at, 0),
                         ok)

        carry = jax.lax.fori_loop(0, (pieces + R - 1) // R, block, carry)
    return carry


def take(x, idx):
    """``x[idx]`` through a one-dimensional index, which a TPU gathers
    faster than the same indices in two."""
    return x[idx.reshape(-1)].reshape(idx.shape)


def lookup(table, h):
    """``table[h]`` for state codes ``h`` in ``0..S-1``, as compare-selects
    over the S states: a TPU gathers from a small table at the cost of
    many element-wise passes."""
    out = jnp.broadcast_to(table[0], h.shape)
    for s in range(1, table.shape[0]):
        out = jnp.where(h == s, table[s], out)
    return out


def disease_tables(d: dict) -> dict:
    """Dense tables of the configuration's state machine."""
    states = list(d["states"])
    idx = {s: i for i, s in enumerate(states)}
    S = len(states)
    tp = np.zeros((S, S), np.float32)
    for s, outs in d["transitions"].items():
        for t, p in outs.items():
            tp[idx[s], idx[t]] = p
    for i in range(S):
        if tp[i].sum() == 0.0:
            tp[i, i] = 1.0
    dwell = np.full((S,), ABSORBING_DWELL, np.float32)
    for s, m in d["dwell_mean_days"].items():
        dwell[idx[s]] = m
    sym = np.zeros((S,), np.float32)
    for s in d["symptomatic"]:
        sym[idx[s]] = 1.0
    return {
        "sus": np.asarray(d["susceptibility"], np.float32),
        "inf": np.asarray(d["infectivity"], np.float32),
        "sym": sym,
        "cum": np.cumsum(tp, axis=-1).astype(np.float32),
        "dwell": dwell,
        "entry": idx[d["entry_state"]],
        "initial": idx[d["initial_state"]],
    }


def kth_smallest(x, k):
    """The ``k``-th smallest (from 1) of the non-negative floats ``x``, by
    bisection on their bit patterns, which non-negative floats order as
    their values: the least ``b`` with at least ``k`` entries at or below
    it. +inf where ``x`` has fewer than ``k`` entries."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)

    def step(_, lohi):
        lo, hi = lohi
        mid = lo + (hi - lo) // 2
        enough = jnp.sum(bits <= mid, dtype=jnp.int32) >= k
        return jnp.where(enough, lo, mid + 1), jnp.where(enough, mid, hi)

    inf = jnp.int32(0x7F800000)
    _, hi = jax.lax.fori_loop(0, 31, step, (jnp.int32(0), inf))
    return jax.lax.bitcast_convert_type(hi, jnp.float32)


def smallest_k(score, k):
    """Mask of the ``k`` smallest entries of ``score`` (non-negative), ties
    broken by the smaller index: everything below the ``k``-th smallest
    value, then as many at that value as fill ``k``, lowest index first."""
    n = score.shape[0]
    s = score.astype(jnp.float32)
    t = kth_smallest(s, k)
    below = s < t
    at = s == t
    need = k - jnp.sum(below, dtype=jnp.int32)
    idx = jnp.arange(n, dtype=jnp.int32)

    def step(_, lohi):  # least q with at least `need` ties at index <= q
        lo, hi = lohi
        mid = lo + (hi - lo) // 2
        enough = jnp.sum(at & (idx <= mid), dtype=jnp.int32) >= need
        return jnp.where(enough, lo, mid + 1), jnp.where(enough, mid, hi)

    _, q = jax.lax.fori_loop(0, 32, step, (jnp.int32(0), jnp.int32(n)))
    return below | (at & (idx <= q) & (need > 0))


def people_selector(sel: dict, twin: Twin, seed: int) -> np.ndarray:
    kind = sel["kind"]
    if kind == "everyone":
        return np.ones((twin.num_people,), bool)
    if kind == "age_group":
        return np.asarray(twin.age_group) == sel["group"]
    if kind == "random_fraction":
        u = draw_host(seed, INIT_ATTR, sel["salt"],
                      np.arange(twin.num_people, dtype=np.uint32))
        return u < sel["fraction"]
    if kind == "loc_type":
        return np.zeros((twin.num_people,), bool)
    raise ValueError(f"unknown selector {kind!r}")


def location_selector(sel: dict, twin: Twin) -> np.ndarray:
    if sel["kind"] == "loc_type":
        return np.asarray(twin.loc_type) == sel["loc_type"]
    return np.zeros((twin.num_locations,), bool)


@dataclasses.dataclass(frozen=True)
class Structure:
    """What the day step branches on: slot kinds, sizes, precision."""

    num_people: int
    num_locations: int
    classic: tuple  # ((action, trigger, metric), ...)
    tti: tuple  # (trace?, ...)
    seed_per_day: int
    dtype: str
    classes: tuple = ()  # size_classes of the twin's windows


def union_slots(arms: list) -> tuple:
    """Every arm's interventions in arm order: the batch's slot layout.
    Returns (classic [(arm index, iv)], tti [(arm index, iv)])."""
    classic, tti = [], []
    for a, arm in enumerate(arms):
        for iv in arm["interventions"]:
            (tti if iv["action"] == "test_trace_isolate" else classic).append(
                (a, iv))
    return classic, tti


def scenario_inputs(cfg: dict, traffic: dict, twin: Twin, seed: int):
    """Per-arm numbers, stacked over arms, and the static structure."""
    arms = cfg["arms"]
    classic, tti = union_slots(arms)
    tables = disease_tables(cfg["disease"])
    K, K2 = len(classic), len(tti)
    scen = []
    for a, _ in enumerate(arms):
        on = np.array([a == ai for ai, _ in classic], bool)
        on2 = np.array([a == ai for ai, _ in tti], bool)
        sa = arm_seed(seed, a)
        c = {
            "seed": np.uint32(sa),
            "enabled": on,
            "start": np.array([iv["trigger"].get("start", 0)
                               for _, iv in classic], np.int32),
            "end": np.array([min(iv["trigger"].get("end", 2 ** 31 - 1),
                                 2 ** 31 - 1) for _, iv in classic], np.int32),
            "on": np.array([iv["trigger"].get("on", 0.0) for _, iv in classic],
                           np.float32),
            "off": np.array([NEVER_OFF if iv["trigger"].get("off") is None
                             else iv["trigger"]["off"] for _, iv in classic],
                            np.float32),
            "factor": np.array([1.0 - iv.get("efficacy", 0.0)
                                for _, iv in classic], np.float32),
            "people": np.stack([people_selector(iv["selector"], twin, sa)
                                for _, iv in classic]) if K else
            np.zeros((0, twin.num_people), bool),
            "locs": np.stack([location_selector(iv["selector"], twin)
                              for _, iv in classic]) if K else
            np.zeros((0, twin.num_locations), bool),
            "pa_enabled": on2,
            "pa_start": np.array([iv["start_day"] for _, iv in tti], np.int32),
            "pa_tests": np.array([iv["tests_per_day"] for _, iv in tti],
                                 np.int32),
            "pa_iso": np.array([iv["isolation_days"] for _, iv in tti],
                               np.int32),
            "pa_trace_iso": np.array([iv.get("trace_isolation_days", 0)
                                      for _, iv in tti], np.int32),
            "pa_people": np.stack([people_selector(iv["selector"], twin, sa)
                                   for _, iv in tti]) if K2 else
            np.zeros((0, twin.num_people), bool),
        }
        scen.append(c)
    stacked = {k: np.stack([s[k] for s in scen]) for k in scen[0]}
    structure = Structure(
        num_people=twin.num_people,
        num_locations=twin.num_locations,
        classic=tuple((iv["action"], iv["trigger"]["kind"],
                       iv["trigger"].get("metric", "infectious"))
                      for _, iv in classic),
        tti=tuple(bool(iv["trace"]) for _, iv in tti),
        seed_per_day=int(traffic["seed_per_day"]),
        dtype="float32",
    )
    common = {
        "tau": np.float32(cfg["tau"] * cfg.get("time_unit", 1.0)),
        "seed_days": int(traffic["seed_days"]),
        "tables": tables,
    }
    return structure, stacked, common


# ---------------------------------------------------------------------------
# one day
# ---------------------------------------------------------------------------


def exposure(st: Structure, day: dict, prob, sus_p, inf_p, loc_open,
             positive, seed, d):
    """Step 2 of a day: each person's exposure, the day's contacts, and
    each person's contacts with people who tested positive (``trc``, kept
    where the structure has test-trace-isolate slots).

    ``day`` holds one day's visits (``person``, ``loc``, ``start``,
    ``end``) and their windows (``wfirst``, ``wsize``); ``sus_p`` and
    ``inf_p`` are each person's susceptibility and infectivity where they
    may go out, else 0. A visit is active where its person may go out and
    its location is open; the two visits of a pair share the location.

    A pair's contact draw depends on its two people and the location
    only, so each block of pairs is taken in two passes: the draw for
    every pair, then a closer look (overlap, susceptibility) at the pairs
    whose draw fell below the contact probability, sorted to the front
    and taken ``DRAWN_BLOCK`` at a time."""
    f = jnp.dtype(st.dtype)
    P = st.num_people
    vp, vl = day["person"], day["loc"]
    start_f, end_f = day["start"].astype(f), day["end"].astype(f)
    real = vp >= 0
    is_row = real & (inf_p[jnp.maximum(vp, 0)] > 0)

    def meet(carry, j, i, ok):
        lj, pj = vl[j], vp[j]
        pi = take(vp, i)
        lo = jnp.minimum(pi, pj[:, None]).astype(jnp.uint32)
        hi = jnp.maximum(pi, pj[:, None]).astype(jnp.uint32)
        u = draw(seed, CONTACT, d, lo, hi, lj[:, None].astype(jnp.uint32))
        drawn = (ok & (pi != pj[:, None]) & loc_open[lj][:, None]
                 & (u.astype(f) < prob[lj][:, None].astype(f)))
        n = ok.size
        slots = jnp.sort(jnp.where(drawn, jnp.arange(n, dtype=jnp.int32)
                                   .reshape(ok.shape), n).reshape(-1))
        i, pi = i.reshape(-1), pi.reshape(-1)
        start_j, end_j, inf_j = start_f[j], end_f[j], inf_p[pj]
        pos_j = positive[pj]
        W = ok.shape[1]

        def closer(k, carry):
            acc, cnt, trc = carry
            s = jax.lax.dynamic_slice(slots, (k * DRAWN_BLOCK,),
                                      (DRAWN_BLOCK,))
            live = s < n
            s = jnp.where(live, s, 0)
            r, ii, p = s // W, i[s], pi[s]
            overlap = jnp.maximum(jnp.minimum(end_f[ii], end_j[r])
                                  - jnp.maximum(start_f[ii], start_j[r]), 0)
            sus_i = sus_p[jnp.maximum(p, 0)]
            contact = live & (overlap > 0) & (sus_i > 0)
            rho = jnp.where(contact, overlap * sus_i * inf_j[r], 0)
            acc = acc.at[jnp.where(contact, p, P)].add(rho, mode="drop")
            cnt = cnt + contact.sum().astype(jnp.uint32)
            if st.tti:
                trc = trc.at[jnp.where(contact & pos_j[r], p, P)].add(
                    1, mode="drop")
            return acc, cnt, trc

        m = drawn.sum(dtype=jnp.int32)
        return jax.lax.fori_loop(0, (m + DRAWN_BLOCK - 1) // DRAWN_BLOCK,
                                 closer, carry)

    return walk_pairs(st.classes, day["wfirst"], day["wsize"], is_row, meet,
                      (jnp.zeros((P,), f), jnp.zeros((), jnp.uint32),
                       jnp.zeros((P,), jnp.int32)))


def init_state(structure: Structure, tables: dict, B: int) -> dict:
    P, K = structure.num_people, len(structure.classic)
    return {
        "day": jnp.zeros((B,), jnp.int32),
        "health": jnp.full((B, P), tables["initial"], jnp.int32),
        "dwell": jnp.full((B, P), ABSORBING_DWELL, jnp.float32),
        "cumulative": jnp.zeros((B,), jnp.int32),
        "iv_active": jnp.zeros((B, K), bool),
        "vaccinated": jnp.zeros((B, P), bool),
        "tested": jnp.zeros((B, P), bool),
        "traced": jnp.zeros((B, P), bool),
        "isolated_until": jnp.zeros((B, P), jnp.int32),
    }


@partial(jax.jit, static_argnums=(0,))
def day_step(st: Structure, data, common, sc, s, d):
    """One day of one scenario. ``data`` holds the week on the device,
    ``sc`` the scenario's numbers, ``s`` its state, ``d`` the day."""
    f = jnp.dtype(st.dtype)
    P, L = st.num_people, st.num_locations
    seed, tab = sc["seed"], common["tables"]
    pid = jnp.arange(P, dtype=jnp.uint32)
    h = s["health"]
    w = d % DAYS_PER_WEEK

    # 1. interventions, classic slots
    visit_ok = jnp.ones((P,), bool)
    loc_open = jnp.ones((L,), bool)
    vacc = s["vaccinated"]
    for k, (action, _, _) in enumerate(st.classic):
        on = s["iv_active"][k]
        if action == "isolate":
            visit_ok = visit_ok & ~(on & sc["people"][k])
        elif action == "close":
            loc_open = loc_open & ~(on & sc["locs"][k])
        elif action == "vaccinate":
            vacc = vacc | (on & sc["people"][k])
        else:
            raise ValueError(action)
    sus_mult = jnp.ones((P,), jnp.float32)
    vax = [k for k, c in enumerate(st.classic) if c[0] == "vaccinate"][:1]
    for k in vax:
        sus_mult = jnp.where(vacc & sc["people"][k], sc["factor"][k], 1.0)

    # 1b. test-trace-isolate: capacity-limited testing
    sym = lookup(tab["sym"], h) > 0
    detectable = lookup(tab["inf"], h) > 0
    in_iso = d < s["isolated_until"]
    visit_ok = visit_ok & ~in_iso if st.tti else visit_ok
    takes, tests_used = [], jnp.zeros((), jnp.int32)
    take_any = jnp.zeros((P,), bool)
    for k2 in range(len(st.tti)):
        act = sc["pa_enabled"][k2] & (d >= sc["pa_start"][k2])
        elig = (act & sc["pa_people"][k2] & ~s["tested"] & ~in_iso
                & (sym | s["traced"]))
        u = draw(seed, TEST, d, k2, pid).astype(f)
        score = jnp.where(elig & sym, u, jnp.where(elig, u + 2, 4))
        tests = sc["pa_tests"][k2]
        take = elig & smallest_k(score, jnp.minimum(tests, P)) & (tests > 0)
        takes.append(take)
        take_any = take_any | take
        tests_used = tests_used + take.sum().astype(jnp.int32)
    positive = take_any & detectable

    # 2. exposure over co-located, overlapping visits
    sus_p = jnp.where(visit_ok, lookup(tab["sus"], h).astype(f)
                      * data["beta_sus"].astype(f) * sus_mult.astype(f), 0)
    inf_p = jnp.where(visit_ok, lookup(tab["inf"], h).astype(f)
                      * data["beta_inf"].astype(f), 0)
    today = {k: data[k][w] for k in DAY_KEYS}
    acc, contacts, trc = exposure(st, today, data["prob"], sus_p, inf_p,
                                  loc_open, positive, seed, d)

    # 3. infection, seeding, state machine
    A = acc * common["tau"].astype(f)
    u_inf = draw(seed, INFECT, d, pid).astype(f)
    infected = (A > 0) & (u_inf > jnp.exp(-A))
    sus_ok = lookup(tab["sus"], h) > 0
    us = jnp.where(sus_ok, draw(seed, SEED_CHOICE, d, pid).astype(f), 2)
    thresh = kth_smallest(us, max(min(st.seed_per_day, P), 1))
    seeded = ((d < common["seed_days"]) & (us <= thresh) & sus_ok
              & (st.seed_per_day > 0))
    new = (infected | seeded) & sus_ok

    u_t = draw(seed, TRANSITION, d, pid)
    nxt = sum((lookup(tab["cum"][:, t], h) < u_t).astype(jnp.int32)
              for t in range(tab["cum"].shape[1]))
    # The countdown is bookkeeping of the float32 state, not arithmetic
    # whose precision the control lowers.
    dwell_after = s["dwell"] - 1
    timed = dwell_after <= 0
    h_t = jnp.where(timed, nxt, h)
    h_new = jnp.where(new, tab["entry"], h_t)
    changed = new | (timed & (h_new != h))
    mean = lookup(tab["dwell"], h_new)
    fresh = -mean.astype(f) * jnp.log(draw(seed, DWELL, d, pid).astype(f))
    fresh = jnp.maximum(fresh, 1).astype(jnp.float32)
    fresh = jnp.where(mean >= ABSORBING_DWELL, ABSORBING_DWELL, fresh)
    dwell = jnp.where(changed, fresh, dwell_after)

    n_new = new.sum().astype(jnp.int32)
    cumulative = s["cumulative"] + n_new
    stats = {
        "day": d,
        "new_infections": n_new,
        "cumulative": cumulative,
        "infectious": (lookup(tab["inf"], h_new) > 0).sum().astype(jnp.int32),
        "susceptible": (lookup(tab["sus"], h_new) > 0).sum().astype(
            jnp.int32),
        "contacts": contacts,
        "edges": contacts,
        "tests_used": tests_used,
        "isolated": (in_iso.sum().astype(jnp.int32) if st.tti
                     else jnp.zeros((), jnp.int32)),
        "traced": jnp.zeros((), jnp.int32),
    }

    # 4. isolation and tracing, then the triggers
    tested, traced, iso = s["tested"], s["traced"], s["isolated_until"]
    if st.tti:
        tested = tested | take_any
        newly = jnp.zeros((P,), bool)
        for k2, trace in enumerate(st.tti):
            iso = jnp.maximum(iso, jnp.where(
                takes[k2] & detectable, d + 1 + sc["pa_iso"][k2], 0))
            if trace:
                act = sc["pa_enabled"][k2] & (d >= sc["pa_start"][k2])
                nt = (trc > 0) & sc["pa_people"][k2] & act
                newly = newly | nt
                iso = jnp.maximum(iso, jnp.where(
                    nt, d + 1 + sc["pa_trace_iso"][k2], 0))
        traced = traced | newly
        stats["traced"] = newly.sum().astype(jnp.int32)
    trig = []
    for k, (_, kind, metric) in enumerate(st.classic):
        if kind == "day_range":
            t = (d >= sc["start"][k]) & (d < sc["end"][k])
        else:
            x = stats[metric].astype(jnp.float32)
            t = jnp.where(s["iv_active"][k], x >= sc["off"][k],
                          x >= sc["on"][k])
        trig.append(t & sc["enabled"][k])
    iv_active = jnp.stack(trig) if trig else s["iv_active"]
    new_state = {
        "day": d + 1, "health": h_new, "dwell": dwell,
        "cumulative": cumulative, "iv_active": iv_active, "vaccinated": vacc,
        "tested": tested, "traced": traced, "isolated_until": iso,
    }
    return new_state, stats


# ---------------------------------------------------------------------------
# a run
# ---------------------------------------------------------------------------


class Reference:
    """The reference over one twin and one cell's arms, on the device."""

    def __init__(self, cfg: dict, traffic: dict, twin: Twin, seed: int,
                 dtype: str = "float32"):
        if cfg.get("static_network", False):
            raise ValueError("the reference draws contacts by day only")
        check_twin(twin)
        structure, stacked, common = scenario_inputs(cfg, traffic, twin, seed)
        L = twin.num_locations
        week, peak, widest = [], jnp.zeros((L,), jnp.int32), 1
        for (person, loc, start, end), n in zip(twin.week, twin.num_real):
            wfirst, wsize, last = locations_of_day(loc, n, L)
            day = {k: jnp.asarray(x) for k, x in zip(
                DAY_KEYS, (person, loc, start, end, wfirst, wsize))}
            peak = jnp.maximum(peak, _peak_one_day(
                day["loc"], day["start"], day["end"], day["person"] >= 0,
                jnp.asarray(last)))
            widest = max(widest, int(wsize.max()))
            week.append(day)
        classes = size_classes(widest)
        V = len(twin.week[0][0])
        if (len(classes) + 1) * V >= 2 ** 31:
            raise ValueError("too many visits a day for the row sort's key")
        self.structure = dataclasses.replace(structure, dtype=dtype,
                                             classes=classes)
        self.B = len(cfg["arms"])
        self.scen = [jax.tree.map(lambda x: jnp.asarray(x[b]), stacked)
                     for b in range(self.B)]
        self.common = jax.tree.map(jnp.asarray, common)
        self.tables = common["tables"]
        self.data = {k: jnp.stack([d[k] for d in week]) for k in DAY_KEYS}
        self.data.update(
            prob=jnp.asarray(contact_probability(np.asarray(peak))),
            beta_sus=jnp.asarray(twin.beta_sus, jnp.float32),
            beta_inf=jnp.asarray(twin.beta_inf, jnp.float32))

    def init_state(self) -> dict:
        return init_state(self.structure, self.tables, self.B)

    def run(self, state: dict, days: int):
        """``days`` days from ``state`` (leaves with a leading scenario
        axis). Returns (state, hist) as host arrays, hist (days, B)."""
        finals, hists = [], []
        for b in range(self.B):
            s = jax.tree.map(lambda x: x[b], state)
            rows = []
            for _ in range(days):
                s, stats = day_step(self.structure, self.data, self.common,
                                    self.scen[b], s, s["day"])
                rows.append(stats)
            finals.append(jax.device_get(s))
            hists.append(jax.device_get(rows))
        final = {k: np.stack([f[k] for f in finals]) for k in finals[0]}
        hist = {k: np.array([[np.asarray(hists[b][t][k])
                              for b in range(self.B)] for t in range(days)])
                for k in STAT_KEYS}
        return final, hist


def stretches(cfg: dict, traffic: dict, twin: Twin, seed: int,
              dtype: str = "float32") -> list:
    """The reference over what a run compares: the advance to the
    window's start day, chunk by chunk, then one window chunk. A list of
    ``(state, hist)`` host pairs, one per chunk."""
    ref = Reference(cfg, traffic, twin, seed, dtype=dtype)
    K = int(traffic["chunk_days"])
    state, out = ref.init_state(), []
    for _ in range(int(traffic["start_day"]) // K + 1):
        state, hist = ref.run(state, K)
        out.append((state, hist))
        state = jax.tree.map(jnp.asarray, state)
    return out


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------


def compare(prog: list, ref: list) -> dict:
    """Mismatch shares between the program's and the reference's outputs.

    ``prog`` and ``ref`` are lists of ``(state, hist)`` pairs, one per
    stretch compared (the advance to the window's start, a window chunk).
    ``state_mismatch`` is the share of (scenario, person) entries whose
    state differs in any leaf, the scalar leaves counted as entries too;
    ``stat_mismatch`` the share of (day, scenario, key) daily counts that
    differ, and ``stat_gap`` the largest gap between two such counts as a
    share of the reference's count (of 1 where that is 0). All are 0 when
    the two agree bit for bit. The exposure sums
    of the two are added in different orders, so a draw that lies within
    rounding of its threshold can fall the other way; each such draw
    changes a handful of entries."""
    bad_state = n_state = bad_stat = n_stat = 0
    gap = 0.0
    for (ps, ph), (rs, rh) in zip(prog, ref):
        person_bad = None
        for k in PERSON_LEAVES:
            a, b = np.asarray(ps[k]), np.asarray(rs[k])
            if a.shape != b.shape:
                raise ValueError(f"state '{k}': {a.shape} vs {b.shape}")
            diff = a != b
            person_bad = diff if person_bad is None else person_bad | diff
        bad_state += int(person_bad.sum())
        n_state += person_bad.size
        for k in SCALAR_LEAVES:
            a, b = np.asarray(ps[k]), np.asarray(rs[k])
            bad_state += int((a.reshape(b.shape) != b).sum())
            n_state += b.size
        for k in STAT_KEYS:
            a = np.asarray(ph[k]).astype(np.int64)
            b = np.asarray(rh[k]).astype(np.int64)
            if a.shape != b.shape:
                raise ValueError(f"stat '{k}': {a.shape} vs {b.shape}")
            bad_stat += int((a != b).sum())
            n_stat += b.size
            if b.size:
                gap = max(gap, float((np.abs(a - b)
                                      / np.maximum(np.abs(b), 1)).max()))
    return {"state_mismatch": bad_state / n_state,
            "stat_mismatch": bad_stat / n_stat, "stat_gap": gap}
