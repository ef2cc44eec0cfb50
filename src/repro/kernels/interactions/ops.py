"""Jitted wrappers for the interaction pass.

Six interchangeable implementations, all bitwise-identical in output
(tested against each other and the dense oracle):

  interactions_dense          O(V^2) oracle (ref.py) — tests only.
  interactions_blocked_jnp    vmap over the block-pair schedule; vectorized,
                              no runtime skip — the throughput CPU path at
                              high prevalence.
  interactions_blocked_scan   scan + cond over the schedule; implements the
                              paper's short-circuit (§V-D) with a *runtime*
                              skip — pays one cond per tile, dead or live.
  interactions_compact        the active-set engine: compacts the schedule
                              to the live tiles inside jit (static-shape
                              stable sort) and runs a fori_loop bounded by
                              the *traced* live count, so a day with 0.1%
                              live tiles costs ~0.1% of the tile work.
  interactions_pallas         the TPU kernel (kernel.py) over the padded
                              schedule; compiled on TPU, interpret mode on
                              CPU, refused elsewhere.
  interactions_pallas_compact the fused kernel: the compact backend's
                              schedule compaction feeding the Pallas kernel
                              directly via scalar prefetch, with an
                              in-kernel traversed-edge counter (the
                              measured-TEPS numerator). The TPU analog of
                              `compact` — live-tile-bounded DMA + compute
                              in one launch.

All take the same (V,)-shaped visit arrays (location-sorted, padded with
pid == -1) plus the static BlockSchedule arrays and the two per-block
short-circuit flags (col_has_inf / row_has_sus), and return per-visit
propensity sums (before the global tau factor) and contact counts.

Bitwise equality across backends is structural, not accidental: every
backend accumulates live tiles in the same row-major schedule order, and
dead tiles contribute exact +0.0 (jnp) or are skipped (scan/compact/
pallas) — adding +0.0 to a non-negative f32 is a bitwise no-op, so
skipping and masking produce identical bits.

Contact tracing (PR 7) is a *second accumulator* in the same pass: every
backend takes a keyword-only ``src_val`` — the per-visit tracing-source
weight, >0 for visits by people who tested positive today. When given, the
backend returns a third per-visit output ``trc``: the number of traced
contacts (contact pairs whose column side is a tracing source), sharing the
tiles, schedule compaction and accumulation order of the exposure pass. The
tracing condition is a subset of the contact-count condition, so it is
exactly zero on dead tiles by algebra and inherits the bitwise-equality
contract for free. With ``src_val=None`` (the default) the extra output is
statically compiled out — the traced program is never built, so the
tracing-off path is the pre-PR program, bit for bit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.interactions.kernel import (
    interactions_pallas_call,
    interactions_pallas_compact_call,
    live_tiles,
)
from repro.kernels.interactions.ref import blocked_tile


def _block_any_positive(val, pid, num_blocks, block_size):
    flags = ((val > 0.0) & (pid >= 0)).reshape(num_blocks, block_size)
    return jnp.any(flags, axis=1).astype(jnp.int32)


def col_has_infectious(inf_val, pid, num_blocks, block_size):
    """Per column block: does any active visit carry infectivity today?
    This is the runtime input of the short-circuit optimization."""
    return _block_any_positive(inf_val, pid, num_blocks, block_size)


def row_has_susceptible(sus_val, pid, num_blocks, block_size):
    """Per row block: does any active visit carry susceptibility today?
    The symmetric short-circuit flag — early-outbreak days are
    susceptible-heavy (col_has_inf kills most tiles), late days are the
    mirror case (row_has_sus kills them)."""
    return _block_any_positive(sus_val, pid, num_blocks, block_size)


def _gather_block(arr, blk, b):
    return jax.lax.dynamic_slice_in_dim(arr, blk * b, b)


@functools.partial(jax.jit, static_argnames=("block_size",))
def interactions_blocked_jnp(
    pid, loc, start, end, p_loc, sus_val, inf_val,
    row_idx, col_idx, row_start, pair_active, col_has_inf, row_has_sus,
    meta,
    *,
    block_size: int,
    src_val=None,
):
    b = block_size
    V = pid.shape[0]
    nb = V // b
    seed, day = meta[0], meta[1]

    def one_pair(rb, cb, live):
        rows = [_gather_block(a, rb, b) for a in (pid, loc, start, end, p_loc, sus_val)]
        cols = [_gather_block(a, cb, b) for a in (pid, loc, start, end, inf_val)]
        # Masked (padding or short-circuited) pairs contribute zero; the
        # flops still run — this is the no-skip vectorized variant.
        src = None if src_val is None else _gather_block(src_val, cb, b)
        tile = blocked_tile(seed, day, rows, cols, src)
        return tuple(jnp.where(live, t, jnp.zeros((), t.dtype)) for t in tile)

    live = live_tiles(row_idx, col_idx, pair_active, col_has_inf, row_has_sus)
    outs = jax.vmap(one_pair)(row_idx, col_idx, live)
    folded = tuple(
        jax.ops.segment_sum(o, row_idx, num_segments=nb).reshape(V)
        for o in outs
    )
    return folded


@functools.partial(jax.jit, static_argnames=("block_size",))
def interactions_blocked_scan(
    pid, loc, start, end, p_loc, sus_val, inf_val,
    row_idx, col_idx, row_start, pair_active, col_has_inf, row_has_sus,
    meta,
    *,
    block_size: int,
    src_val=None,
):
    b = block_size
    V = pid.shape[0]
    seed, day = meta[0], meta[1]

    def _upd(arr, rb, delta):
        return jax.lax.dynamic_update_slice_in_dim(
            arr, jax.lax.dynamic_slice_in_dim(arr, rb * b, b) + delta, rb * b, 0
        )

    def step(carry, sched):
        rb, cb, live = sched

        def body(_):
            rows = [_gather_block(a, rb, b) for a in (pid, loc, start, end, p_loc, sus_val)]
            cols = [_gather_block(a, cb, b) for a in (pid, loc, start, end, inf_val)]
            src = None if src_val is None else _gather_block(src_val, cb, b)
            tile = blocked_tile(seed, day, rows, cols, src)
            return tuple(_upd(a, rb, t) for a, t in zip(carry, tile))

        def skip(_):
            return carry

        # Runtime short circuit: no flops at all for dead tiles — but the
        # scan still visits every tile to evaluate the cond.
        carry = jax.lax.cond(live, body, skip, None)
        return carry, None

    live = live_tiles(row_idx, col_idx, pair_active, col_has_inf, row_has_sus)
    acc0 = jnp.zeros((V,), jnp.float32)
    cnt0 = jnp.zeros((V,), jnp.int32)
    carry0 = (acc0, cnt0) if src_val is None else (
        acc0, cnt0, jnp.zeros((V,), jnp.int32)
    )
    out, _ = jax.lax.scan(step, carry0, (row_idx, col_idx, live))
    return out


@functools.partial(jax.jit, static_argnames=("block_size",))
def interactions_compact(
    pid, loc, start, end, p_loc, sus_val, inf_val,
    row_idx, col_idx, row_start, pair_active, col_has_inf, row_has_sus,
    meta,
    *,
    block_size: int,
    src_val=None,
):
    """Active-set backend: per-day work proportional to *live* tiles.

    Inside jit (static shapes throughout), the schedule is compacted with a
    stable argsort on the dead flag — live tiles move to the front keeping
    their row-major order, so accumulation order (and therefore every f32
    bit) matches the jnp/scan backends. A ``fori_loop`` bounded by the
    traced live count then touches only the live prefix: a zero-infectious
    day costs one sort of the (NP,) schedule and no tile math at all. This
    is the paper's §V-D short-circuit realized as wall clock instead of
    masking.
    """
    b = block_size
    V = pid.shape[0]
    seed, day = meta[0], meta[1]

    live = live_tiles(row_idx, col_idx, pair_active, col_has_inf, row_has_sus)
    # Stable partition: live tiles first, original (row-major) order kept.
    order = jnp.argsort(jnp.where(live, 0, 1), stable=True)
    rows_c = row_idx[order]
    cols_c = col_idx[order]
    n_live = live.sum()

    def _upd(arr, rb, delta):
        return jax.lax.dynamic_update_slice_in_dim(
            arr, jax.lax.dynamic_slice_in_dim(arr, rb * b, b) + delta, rb * b, 0
        )

    def body(k, carry):
        rb, cb = rows_c[k], cols_c[k]
        rows = [_gather_block(a, rb, b) for a in (pid, loc, start, end, p_loc, sus_val)]
        cols = [_gather_block(a, cb, b) for a in (pid, loc, start, end, inf_val)]
        src = None if src_val is None else _gather_block(src_val, cb, b)
        tile = blocked_tile(seed, day, rows, cols, src)
        return tuple(_upd(a, rb, t) for a, t in zip(carry, tile))

    acc0 = jnp.zeros((V,), jnp.float32)
    cnt0 = jnp.zeros((V,), jnp.int32)
    carry0 = (acc0, cnt0) if src_val is None else (
        acc0, cnt0, jnp.zeros((V,), jnp.int32)
    )
    return jax.lax.fori_loop(0, n_live, body, carry0)


def pallas_interpret() -> bool:
    """Whether the Pallas kernels run in interpret mode: compiled on TPU,
    interpreted on CPU (the test path), and refused anywhere else — a
    device that is neither must not be measured through the interpreter."""
    platform = jax.default_backend()
    if platform not in ("tpu", "cpu"):
        raise RuntimeError(
            f"the Pallas interaction kernels run on 'tpu' (compiled) or "
            f"'cpu' (interpreted), not on '{platform}'")
    return platform == "cpu"


_PALLAS_CALLS = {
    "pallas": interactions_pallas_call,
    "pallas-compact": interactions_pallas_compact_call,
}


def _pallas_full(backend, *args, block_size: int, src_val=None):
    """The kernel's outputs: (acc, cnt, edges), or (acc, cnt, trc, edges)
    with ``src_val``. ``edges`` is the in-kernel traversed-edge count, the
    sum of contact counts over live tiles, i.e. exactly ``cnt.sum()``."""
    return _PALLAS_CALLS[backend](
        *args, block_size=block_size, interpret=pallas_interpret(),
        src_val=src_val,
    )


def interactions_pallas(*args, **kwargs):
    """The kernel over the padded schedule (kernel.py): one grid step per
    scheduled tile, dead tiles skipped in the kernel."""
    return tuple(_pallas_full("pallas", *args, **kwargs)[:-1])


def interactions_pallas_compact(*args, **kwargs):
    """The fused active-set kernel: the compact backend's schedule
    compaction (the *same* stable sort, so accumulation order — and every
    f32 bit — matches `compact` and `jnp`) feeding the kernel, whose grid
    steps past the live prefix issue no DMA and no flops."""
    return tuple(_pallas_full("pallas-compact", *args, **kwargs)[:-1])


BACKENDS = {
    "jnp": interactions_blocked_jnp,
    "scan": interactions_blocked_scan,
    "compact": interactions_compact,
    "pallas": interactions_pallas,
    "pallas-compact": interactions_pallas_compact,
}


def interactions_auto(*args, backend: str = "jnp", **kwargs):
    """Dispatch by backend name.

    'jnp' is the dense-throughput CPU default, 'compact' the active-set
    engine (work ∝ live epidemic activity), 'pallas' the TPU kernel over
    the padded schedule and 'pallas-compact' the fused active-set kernel
    (compaction + tile math + edge telemetry). The Pallas kernels compile
    on TPU and run in interpret mode on CPU (:func:`pallas_interpret`).
    """
    return BACKENDS[backend](*args, **kwargs)


def interactions_auto_edges(*args, backend: str = "jnp", **kwargs):
    """Like ``interactions_auto`` but also returns the traversed-edge count
    (i32 scalar) — the TEPS numerator.

    For the Pallas backends the count comes from the in-kernel
    accumulator; every other backend derives it as ``cnt.sum()``. Both are
    sums of the same live-tile contact counts, so the two routes agree
    exactly (asserted in tests/test_interactions.py).
    """
    if backend in _PALLAS_CALLS:
        return _pallas_full(backend, *args, **kwargs)
    acc, cnt = BACKENDS[backend](*args, **kwargs)
    return acc, cnt, cnt.sum().astype(jnp.int32)


def interactions_auto_traced(*args, backend: str = "jnp", src_val=None,
                             **kwargs):
    """Traced twin of ``interactions_auto_edges``: runs the interaction
    pass with the second (contact-tracing) accumulator enabled and returns
    ``(acc, cnt, edges, trc)``.

    ``src_val`` is the per-visit tracing-source weight (>0 where the
    visitor tested positive today); ``trc`` is the per-visit count of
    traced contacts, accumulated tile-for-tile alongside ``acc``/``cnt``
    so it is bitwise identical across all five backends. ``edges`` keeps
    its meaning (and, on the Pallas backends, its in-kernel route).
    """
    assert src_val is not None
    if backend in _PALLAS_CALLS:
        acc, cnt, trc, edges = _pallas_full(
            backend, *args, src_val=src_val, **kwargs
        )
        return acc, cnt, edges, trc
    acc, cnt, trc = BACKENDS[backend](*args, src_val=src_val, **kwargs)
    return acc, cnt, cnt.sum().astype(jnp.int32), trc
