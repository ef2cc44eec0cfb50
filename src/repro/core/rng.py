"""Counter-based hash RNG for partition-invariant stochastic draws.

The paper (§VI) fixes one global seed so that all scaling runs have identical
epidemiological results — but in the Charm++ implementation that only holds
per partitioning, because draws are consumed from per-chare streams. Here
every random draw is a *pure function* of ``(seed, day, entity ids, stream)``
via a 32-bit mixing hash, so results are bitwise identical across any mesh
shape, worker count, or replay after restart. This is strictly stronger
reproducibility than the paper's and is what makes elastic restart exact.

The same integer arithmetic is used inside Pallas kernels (it is plain
uint32 ops, so it lowers to TPU VPU instructions and runs unchanged in
interpret mode) and in the pure-jnp reference oracles, so kernel-vs-ref
comparisons are exact.

Streams (documented constants, one per random decision in the simulator):
  CONTACT      per (pid_i, pid_j, day): did a co-occupant pair make contact?
  INFECT       per (pid, day): infection draw against total propensity
  TRANSITION   per (pid, day): FSA next-state categorical draw
  DWELL        per (pid, day): dwell-time draw for the state entered
  SEED_CHOICE  per (pid, day): outbreak seeding
  TEST         per (slot, pid, day): testing-priority draw for the
               capacity-limited daily test budget
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

# Stream ids — keep stable; they are part of the reproducibility contract.
CONTACT = np.uint32(0x01)
INFECT = np.uint32(0x02)
TRANSITION = np.uint32(0x03)
DWELL = np.uint32(0x04)
SEED_CHOICE = np.uint32(0x05)
VISIT_SAMPLE = np.uint32(0x06)
INIT_ATTR = np.uint32(0x07)
TEST = np.uint32(0x08)

_C1 = np.uint32(0x85EBCA6B)
_C2 = np.uint32(0xC2B2AE35)
_GOLDEN = np.uint32(0x9E3779B9)


def _u32(x):
    """Cast to uint32 with wrapping semantics (jnp arrays or python ints)."""
    if isinstance(x, (int, np.integer)):
        return np.uint32(x & 0xFFFFFFFF)
    return x.astype(jnp.uint32)


def fmix32(h):
    """Murmur3 finalizer: full-avalanche 32-bit mix. Works on jnp uint32."""
    with np.errstate(over="ignore"):  # uint32 wrap is the point
        h = _u32(h)
        h = h ^ (h >> 16)
        h = h * _C1
        h = h ^ (h >> 13)
        h = h * _C2
        h = h ^ (h >> 16)
    return h


def hash_u32(seed, *words):
    """Combine an arbitrary number of uint32 words into one mixed uint32.

    Broadcasting: any of the words may be arrays; standard jnp broadcasting
    applies. Order-sensitive (h is folded left-to-right), so (i, j) and
    (j, i) produce independent draws.
    """
    with np.errstate(over="ignore"):  # uint32 wrap is the point
        h = fmix32(_u32(seed) ^ _GOLDEN)
        for i, w in enumerate(words):
            h = fmix32(h ^ fmix32(_u32(w) + _GOLDEN * np.uint32(i + 1)))
    return h


def uniform(seed, *words):
    """U(0,1) float32 from the hash; never exactly 0 (safe for log)."""
    h = hash_u32(seed, *words)
    # Top 24 bits -> [0, 1) with 2^-24 resolution, then offset by 2^-25.
    # The value is below 2^24, so going through int32 is exact, and Mosaic
    # has no u32 -> f32 conversion.
    u = (h >> np.uint32(8)).astype(jnp.int32).astype(jnp.float32) \
        * jnp.float32(2.0**-24)
    return u + jnp.float32(2.0**-25)


def exponential(mean, seed, *words):
    """Exponential(mean) draw."""
    return -mean * jnp.log(uniform(seed, *words))


def categorical(cum_probs, seed, *words):
    """Inverse-CDF categorical draw.

    cum_probs: (..., K) cumulative probabilities along the last axis (rows
    end at ~1.0). Returns int32 index with the same batch shape as the
    broadcast of the hash words.
    """
    u = uniform(seed, *words)
    # count of cum < u  ==  sampled index
    return jnp.sum(cum_probs < u[..., None], axis=-1).astype(jnp.int32)


def np_uniform(seed, *words):
    """NumPy mirror of :func:`uniform` for host-side generators/tests."""

    def mix(h):
        h = np.uint32(h)
        with np.errstate(over="ignore"):
            h ^= h >> np.uint32(16)
            h *= _C1
            h ^= h >> np.uint32(13)
            h *= _C2
            h ^= h >> np.uint32(16)
        return h

    with np.errstate(over="ignore"):
        h = mix(np.uint32(seed & 0xFFFFFFFF) ^ _GOLDEN)
        for i, w in enumerate(words):
            w = np.asarray(w, dtype=np.uint64) & np.uint64(0xFFFFFFFF)
            h = mix(h ^ mix(w.astype(np.uint32) + _GOLDEN * np.uint32(i + 1)))
    u = (h >> np.uint32(8)).astype(np.float64) * 2.0**-24
    return u + 2.0**-25
