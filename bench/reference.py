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
   ``overlap * sus_i * inf_j`` to person ``i``'s exposure. The pairs are
   enumerated from the infectious side, block by block, so the work is
   the number of such pairs and not the square of a location's size.
3. Infection where the draw exceeds ``exp(-tau * exposure)``; outbreak
   seeding on the first days; the state machine's timed transitions.
4. Daily counts, isolation and tracing, then the triggers.

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

#: Pairs handled per step of the exposure loop.
PAIR_BLOCK = 1 << 22

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


@partial(jax.jit, static_argnums=(0,))
def _peak_one_day(num_locations, loc, start, end, real):
    """Peak number of simultaneous visitors per location: +1 at each
    arrival, -1 at each departure, departures first at equal times."""
    n = loc.shape[0]
    locs = jnp.concatenate([loc, loc])
    times = jnp.concatenate([start, end])
    arrive = jnp.concatenate([jnp.ones((n,), jnp.int32),
                              jnp.zeros((n,), jnp.int32)])
    ok = jnp.concatenate([real, real])
    locs = jnp.where(ok, locs, num_locations)  # padding sorts last
    locs_s, _, arr_s = jax.lax.sort((locs, times, arrive), num_keys=3)
    step = 2 * arr_s - 1
    run = jnp.cumsum(step)
    first = jnp.concatenate([jnp.ones((1,), bool), locs_s[1:] != locs_s[:-1]])
    seg = jnp.cumsum(first) - 1
    base = jax.ops.segment_max(jnp.where(first, run - step, -2 ** 30), seg,
                               num_segments=2 * n)
    rel = run - base[seg]
    peak_seg = jax.ops.segment_max(rel, seg, num_segments=2 * n)
    loc_seg = jax.ops.segment_max(locs_s, seg, num_segments=2 * n)
    out = jnp.zeros((num_locations + 1,), jnp.int32)
    return out.at[loc_seg].max(peak_seg, mode="drop")[:num_locations]


def peak_occupancy(twin: Twin) -> np.ndarray:
    peak = np.zeros((twin.num_locations,), np.int32)
    for (person, loc, start, end), n in zip(twin.week, twin.num_real):
        real = np.arange(len(person)) < n
        peak = np.maximum(peak, np.asarray(_peak_one_day(
            twin.num_locations, jnp.asarray(loc), jnp.asarray(start),
            jnp.asarray(end), jnp.asarray(real))))
    return peak


def check_twin(twin: Twin):
    """The layout the enumeration relies on: real visits first, sorted by
    location, then padding."""
    for (person, loc, _, _), n in zip(twin.week, twin.num_real):
        if (person[:n] < 0).any() or (person[n:] >= 0).any():
            raise ValueError("visits must be real first, then padding")
        if n > 1 and (np.diff(loc[:n]) < 0).any():
            raise ValueError("real visits must be sorted by location")


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
    vp, vl = data["person"][w], data["loc"][w]
    vs, ve = data["start"][w], data["end"][w]
    first, size = data["first"][w], data["size"][w]
    prob = data["prob"]

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
    sym = tab["sym"][h] > 0
    detectable = tab["inf"][h] > 0
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
    p_sus = (tab["sus"][h].astype(f) * data["beta_sus"].astype(f)
             * sus_mult.astype(f))
    p_inf = tab["inf"][h].astype(f) * data["beta_inf"].astype(f)
    real = vp >= 0
    q = jnp.maximum(vp, 0)
    active = real & visit_ok[q] & loc_open[vl]
    sus_v = jnp.where(active, p_sus[q], 0)
    inf_v = jnp.where(active, p_inf[q], 0)
    src_v = active & positive[q]
    n_j = jnp.where(inf_v > 0, size[vl], 0)
    cum = jnp.cumsum(n_j)  # pairs are counted from the infectious side
    total = cum[-1]
    start_f, end_f = vs.astype(f), ve.astype(f)

    def block(b, carry):
        acc, cnt, trc = carry
        k = b * PAIR_BLOCK + jnp.arange(PAIR_BLOCK, dtype=jnp.int32)
        ok = k < total
        j = jnp.minimum(jnp.searchsorted(cum, k, side="right"),
                        vp.shape[0] - 1)
        i = first[vl[j]] + (k - (cum[j] - n_j[j]))
        i = jnp.where(ok, i, 0)
        overlap = jnp.maximum(jnp.minimum(end_f[i], end_f[j])
                              - jnp.maximum(start_f[i], start_f[j]), 0)
        pi, pj = vp[i], vp[j]
        lo = jnp.minimum(pi, pj).astype(jnp.uint32)
        hi = jnp.maximum(pi, pj).astype(jnp.uint32)
        u = draw(seed, CONTACT, d, lo, hi, vl[j].astype(jnp.uint32))
        contact = (ok & (pi != pj) & (overlap > 0) & (sus_v[i] > 0)
                   & (u.astype(f) < prob[vl[j]].astype(f)))
        rho = jnp.where(contact, overlap * sus_v[i] * inf_v[j], 0)
        who = jnp.where(contact, pi, P)
        acc = acc.at[who].add(rho, mode="drop")
        cnt = cnt + contact.sum().astype(jnp.uint32)
        trc = trc.at[jnp.where(contact & src_v[j], pi, P)].add(
            1, mode="drop")
        return acc, cnt, trc

    nblocks = (total + PAIR_BLOCK - 1) // PAIR_BLOCK
    acc, contacts, trc = jax.lax.fori_loop(
        0, nblocks, block,
        (jnp.zeros((P,), f), jnp.zeros((), jnp.uint32),
         jnp.zeros((P,), jnp.int32)))

    # 3. infection, seeding, state machine
    A = acc * common["tau"].astype(f)
    u_inf = draw(seed, INFECT, d, pid).astype(f)
    infected = (A > 0) & (u_inf > jnp.exp(-A))
    sus_ok = tab["sus"][h] > 0
    us = jnp.where(sus_ok, draw(seed, SEED_CHOICE, d, pid).astype(f), 2)
    thresh = kth_smallest(us, max(min(st.seed_per_day, P), 1))
    seeded = ((d < common["seed_days"]) & (us <= thresh) & sus_ok
              & (st.seed_per_day > 0))
    new = (infected | seeded) & sus_ok

    u_t = draw(seed, TRANSITION, d, pid)
    nxt = jnp.sum(tab["cum"][h] < u_t[:, None], axis=-1).astype(jnp.int32)
    # The countdown is bookkeeping of the float32 state, not arithmetic
    # whose precision the control lowers.
    dwell_after = s["dwell"] - 1
    timed = dwell_after <= 0
    h_t = jnp.where(timed, nxt, h)
    h_new = jnp.where(new, tab["entry"], h_t)
    changed = new | (timed & (h_new != h))
    mean = tab["dwell"][h_new]
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
        "infectious": (tab["inf"][h_new] > 0).sum().astype(jnp.int32),
        "susceptible": (tab["sus"][h_new] > 0).sum().astype(jnp.int32),
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
        self.structure = dataclasses.replace(structure, dtype=dtype)
        self.B = len(cfg["arms"])
        self.scen = [jax.tree.map(lambda x: jnp.asarray(x[b]), stacked)
                     for b in range(self.B)]
        self.common = jax.tree.map(jnp.asarray, common)
        self.tables = common["tables"]
        prob = contact_probability(peak_occupancy(twin))
        L = twin.num_locations
        firsts, sizes = [], []
        for (_, loc, _, _), n in zip(twin.week, twin.num_real):
            lo = np.searchsorted(loc[:n], np.arange(L), side="left")
            hi = np.searchsorted(loc[:n], np.arange(L), side="right")
            firsts.append(lo.astype(np.int32))
            sizes.append((hi - lo).astype(np.int32))
        cols = list(zip(*twin.week))
        self.data = {
            "person": jnp.asarray(np.stack(cols[0])),
            "loc": jnp.asarray(np.stack(cols[1])),
            "start": jnp.asarray(np.stack(cols[2])),
            "end": jnp.asarray(np.stack(cols[3])),
            "first": jnp.asarray(np.stack(firsts)),
            "size": jnp.asarray(np.stack(sizes)),
            "prob": jnp.asarray(prob),
            "beta_sus": jnp.asarray(twin.beta_sus, jnp.float32),
            "beta_inf": jnp.asarray(twin.beta_inf, jnp.float32),
        }

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
