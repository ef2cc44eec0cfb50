"""Pure-jnp dense oracle for the interaction pass (Algorithm 1, reformulated).

For every ordered pair of visits (i, j) to the same location whose time
windows overlap for T_ij > 0 seconds:

  * the (unordered) pair makes *contact* with probability p_loc — one
    symmetric Bernoulli draw per (day, person-pair, location), counter-based;
  * a contact contributes propensity  T_ij * sus_val_i * inf_val_j  to row
    visit i (the global tau factor is applied by the caller — it is linear).

``sus_val`` is sigma(X_i)*beta_sigma(i) gathered per visit (zero unless the
person is susceptible); ``inf_val`` is iota(X_j)*beta_iota(j) (zero unless
infectious). The product being zero for non- susceptible×infectious pairs is
exactly the paper's optimization (1) in §IV-C2 — here it falls out of the
algebra instead of list bookkeeping.

This O(V^2) dense version is the correctness oracle for the Pallas kernel
and the blocked jnp paths; equivalence to the serial event-queue DES is
argued in DESIGN.md §2 and tested in tests/test_interactions.py against a
literal Python event-queue implementation.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core import rng


def contact_uniform(seed, day, pid_i, pid_j, loc):
    """Symmetric contact draw: same u for (i, j) and (j, i) at a location."""
    pmin = jnp.minimum(pid_i, pid_j).astype(jnp.uint32)
    pmax = jnp.maximum(pid_i, pid_j).astype(jnp.uint32)
    return rng.uniform(
        seed, rng.CONTACT, day, pmin, pmax, loc.astype(jnp.uint32)
    )


def fold_cols(x):
    """Sum a (C, R) tile over its column axis in one fixed order: halve the
    axis and add the two halves until one row is left. The order is spelled
    out as elementwise adds, so XLA (any backend) and Mosaic produce the
    same f32 bits, which a backend-chosen reduction tree does not promise.
    Returns (1, R)."""
    n = x.shape[0]
    while n > 1 and n % 2 == 0:
        n //= 2
        x = x[:n] + x[n:]
    if n > 1:  # only the dense oracle's odd widths reach this
        x = jnp.sum(x, axis=0, keepdims=True)
    return x


def _pair_terms(seed, day, pid_r, loc_r, start_r, end_r, p_r, sus_r,
                pid_c, loc_c, start_c, end_c, inf_c):
    overlap = jnp.maximum(
        jnp.minimum(end_r, end_c) - jnp.maximum(start_r, start_c), 0.0
    )
    valid = (
        (pid_r >= 0)
        & (pid_c >= 0)
        & (loc_r == loc_c)
        & (pid_r != pid_c)
        & (overlap > 0.0)
    )
    u = contact_uniform(seed, day, pid_r, pid_c, loc_r)
    contact = valid & (u < p_r)
    rho = overlap * sus_r * inf_c * contact.astype(jnp.float32)
    pair = contact & (sus_r > 0.0) & (inf_c > 0.0)
    return rho, pair


def pair_tile(
    seed,
    day,
    pid_r, loc_r, start_r, end_r, p_r, sus_r,  # row side (susceptible)
    pid_c, loc_c, start_c, end_c, inf_c,  # col side (infectious)
):
    """Compute one tile of propensities and contact counts.

    Shared verbatim by the dense oracle, the blocked jnp paths, and the
    Pallas kernel body — a single source of truth for the pair math. The
    tile is laid out (C, R): row-side operands broadcast along axis 0
    (shape (1, R), or (C, R)), col-side operands along axis 1 ((C, 1), or
    (C, R)), so the per-row sums come out lane-major as (1, R).
    Returns (rho_rowsum (1, R) f32, contact_count_rowsum (1, R) int32).
    """
    rho, pair = _pair_terms(seed, day, pid_r, loc_r, start_r, end_r, p_r,
                            sus_r, pid_c, loc_c, start_c, end_c, inf_c)
    # A tile rowsum cannot overflow int32; the day step widens before the
    # cross-worker contacts psum.
    return fold_cols(rho), fold_cols(pair.astype(jnp.int32))


def pair_tile_traced(
    seed,
    day,
    pid_r, loc_r, start_r, end_r, p_r, sus_r,  # row side (susceptible)
    pid_c, loc_c, start_c, end_c, inf_c,  # col side (infectious)
    src_c,  # col side: tracing-source weight (>0 for today's positives)
):
    """`pair_tile` plus the second accumulator: per-row traced-contact
    counts against tracing-*source* columns (contact tracing).

    The tracing condition is a strict subset of the contact-count condition
    (``src_c > 0`` requires ``inf_c > 0`` in practice, and the ``&`` makes
    it so regardless), so tiles that are dead for the exposure accumulator
    are dead for tracing *by algebra* — no extra masking, and the same
    skip/mask bitwise-equality argument the backends rely on carries over.
    Returns (rho_rowsum, cnt_rowsum i32, trc_rowsum i32), each (1, R).
    """
    rho, pair = _pair_terms(seed, day, pid_r, loc_r, start_r, end_r, p_r,
                            sus_r, pid_c, loc_c, start_c, end_c, inf_c)
    trc = pair & (src_c > 0.0)
    return (
        fold_cols(rho),
        fold_cols(pair.astype(jnp.int32)),
        fold_cols(trc.astype(jnp.int32)),
    )


def blocked_tile(seed, day, rows, cols, src=None):
    """`pair_tile` (or, with ``src``, `pair_tile_traced`) on 1-D (b,)
    blocks, as the jnp backends hold them: rows become (1, b), columns
    (b, 1), and the (1, b) sums come back as (b,)."""
    r = [a[None, :] for a in rows]
    c = [a[:, None] for a in cols]
    if src is None:
        out = pair_tile(seed, day, *r, *c)
    else:
        out = pair_tile_traced(seed, day, *r, *c, src[:, None])
    return tuple(o[0] for o in out)


def interactions_dense(
    pid, loc, start, end, p_loc, sus_val, inf_val, seed, day
):
    """Dense all-pairs oracle. Returns (acc (V,), contacts (V,))."""
    return blocked_tile(
        seed, day,
        (pid, loc, start, end, p_loc, sus_val),
        (pid, loc, start, end, inf_val),
    )


def interactions_dense_traced(
    pid, loc, start, end, p_loc, sus_val, inf_val, src_val, seed, day
):
    """Dense oracle with the tracing accumulator.
    Returns (acc (V,), contacts (V,), traced (V,))."""
    return blocked_tile(
        seed, day,
        (pid, loc, start, end, p_loc, sus_val),
        (pid, loc, start, end, inf_val), src_val,
    )
