"""Contact models (paper §III-A3).

The min/max/alpha model computes, per location, the probability p that any
given pair of simultaneously-present people actually come into contact, as a
function of the location's maximum occupancy N (a proxy for its size):

    p = min(1, [A + (B - A) * (1 - exp(-N / alpha))] / (N - 1))     (Eq. 1)

so that a person visiting at peak occupancy expects between A and B contacts.
The paper uses A=5, B=40, alpha=1000 (calibrated against POLYMOD).

As in the implementation described in §IV-C3, max occupancy is a
*pre-processing* product of the visit schedule (computed here with a
vectorized sweep instead of the paper's script), and the per-location p is
computed once at initialization and stored as a location attribute.

The second model (fixed probability everywhere) is used for purely synthetic
populations where max occupancy is not known in advance.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class MinMaxAlpha:
    min_contacts: float = 5.0  # A
    max_contacts: float = 40.0  # B
    alpha: float = 1000.0

    def probability(self, max_occupancy):
        """Vectorized Eq. 1. Works on numpy or jnp arrays."""
        xp = jnp if isinstance(max_occupancy, jnp.ndarray) else np
        N = xp.asarray(max_occupancy, dtype=xp.float32)
        A, B, a = self.min_contacts, self.max_contacts, self.alpha
        expected = A + (B - A) * (1.0 - xp.exp(-N / a))
        p = expected / xp.maximum(N - 1.0, 1.0)
        # N <= 2: everyone present makes contact (Eq. 1 is defined for N > 2).
        p = xp.where(N <= 2.0, 1.0, xp.minimum(p, 1.0))
        return p.astype(xp.float32)


@dataclasses.dataclass(frozen=True)
class FixedProbability:
    p: float = 0.5

    def probability(self, max_occupancy):
        xp = jnp if isinstance(max_occupancy, jnp.ndarray) else np
        N = xp.asarray(max_occupancy, dtype=xp.float32)
        return xp.full_like(N, xp.float32(self.p))


def max_occupancy_from_visits(
    num_locations: int,
    visit_loc: np.ndarray,
    visit_start: np.ndarray,
    visit_end: np.ndarray,
) -> np.ndarray:
    """Peak simultaneous occupancy per location from one day's visits.

    **Test oracle only** — the literal O(E) event loop (+1 at each arrival,
    -1 at each departure, running max per location), kept as the readable
    specification of the tie-breaking semantics (departures before arrivals
    at equal times, so touching visits never overlap). Production code uses
    the vectorized :func:`max_occupancy_fast`; the two are property-tested
    equal on tied-time schedules in tests/test_property.py.
    """
    occ = np.zeros((num_locations,), np.int32)
    if len(visit_loc) == 0:
        return occ
    # Event stream: (time, +1/-1, loc); departures before arrivals at ties
    # (a visit ending exactly when another starts does not overlap).
    times = np.concatenate([visit_start, visit_end])
    deltas = np.concatenate(
        [np.ones_like(visit_start, np.int32), -np.ones_like(visit_end, np.int32)]
    )
    locs = np.concatenate([visit_loc, visit_loc])
    order = np.lexsort((deltas, times))  # deltas=-1 (departure) sorts first
    cur = np.zeros((num_locations,), np.int32)
    for t, d, l in zip(times[order], deltas[order], locs[order]):
        cur[l] += d
        if cur[l] > occ[l]:
            occ[l] = cur[l]
    return occ


def max_occupancy_fast(
    num_locations: int,
    visit_loc: np.ndarray,
    visit_start: np.ndarray,
    visit_end: np.ndarray,
) -> np.ndarray:
    """Vectorized variant of :func:`max_occupancy_from_visits` (numpy only,
    O(E log E)): per-location running max via sorted cumulative deltas."""
    E = len(visit_loc)
    occ = np.zeros((num_locations,), np.int32)
    if E == 0:
        return occ
    assert num_locations < 2**30, num_locations
    times = np.concatenate([visit_start, visit_end]).astype(np.float32)
    arrive = np.concatenate([np.ones(E, np.int64), np.zeros(E, np.int64)])
    locs = np.concatenate([visit_loc, visit_loc]).astype(np.int64)
    # Sort by (loc, time, delta) with departures first at equal times: one
    # stable argsort of a packed key orders exactly as that lexsort.
    key = (locs << 33) | (float32_order_key(times) << 1) | arrive
    order = np.argsort(key, kind="stable")
    locs_s = locs[order]
    run = np.cumsum(2 * arrive[order] - 1)
    # Subtract the cumulative total up to the start of each location
    # segment, then take the running max over the segment.
    seg = np.flatnonzero(np.concatenate([[True], locs_s[1:] != locs_s[:-1]]))
    base = np.concatenate([[0], run])[seg]
    rel = run - np.repeat(base, np.diff(np.concatenate([seg, [2 * E]])))
    occ[locs_s[seg]] = np.maximum.reduceat(rel, seg)
    return occ


def float32_order_key(x: np.ndarray) -> np.ndarray:
    """Non-negative int64 keys that order as the float32 values do (-0.0
    ties with 0.0), for packing a float into one integer sort key."""
    bits = (np.asarray(x, np.float32) + np.float32(0.0)).view(np.uint32)
    flip = np.where(bits >> 31, np.uint32(0xFFFFFFFF), np.uint32(0x80000000))
    return (bits ^ flip).astype(np.int64)
