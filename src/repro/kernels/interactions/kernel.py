"""Pallas TPU kernel for the interaction pass.

Design (DESIGN.md §2): visits are presorted by location, so same-location
pairs live in a block-diagonal band. The host builds a static *block-pair
schedule* — the (row_block, col_block) tiles containing at least one
same-location pair — and the kernel runs a 1-D grid over that schedule,
streaming column tiles against each row tile and accumulating per-row-visit
propensity sums in VMEM (FlashAttention-style: O(block) memory, no (V, V)
materialization).

TPU mapping:
  * the (b, b) pair tile is pure VPU element-wise math on f32/u32 — at
    b=256 each tile is 256 KiB of operand loads for ~20*b^2 flops, i.e.
    arithmetic intensity ~b/5 flops/byte, comfortably compute-bound;
  * the counter-based hash RNG (core/rng.py) is 10 u32 VPU ops per pair and
    keeps draws identical to the jnp oracle bit-for-bit;
  * scalar-prefetch feeds the schedule (row/col indices) to the BlockSpec
    index_maps, the standard Pallas block-sparse pattern;
  * the paper's short-circuit optimization (§V-D) becomes a `pl.when` guard
    on a per-tile liveness flag (the column block has an infectious visitor
    today and the row block a susceptible one) — the runtime analog of
    skipping the DES at locations with no infectious visitors, at tile
    granularity.

Accumulation correctness: the schedule is row-major, so all column tiles of
one row block are consecutive grid steps; the output BlockSpec index is
constant over that run (Pallas keeps the block in VMEM) and the first step
of the run (the row index changes) zeroes the accumulators. Padding pairs
repeat the last real pair, dead, so the output index never regresses.

One kernel serves two launchers:

  interactions_pallas_call          the padded schedule: one grid step per
                                    scheduled tile, dead tiles skipped by
                                    `pl.when` (no flops, but a grid step and
                                    its DMAs per tile).
  interactions_pallas_compact_call  the fused active-set path
                                    ("pallas-compact"): the schedule is
                                    compacted inside jit (the same stable
                                    sort as ops.interactions_compact), live
                                    tiles first. Grid steps past the live
                                    count clamp their index maps to the last
                                    live tile, so the pipeline issues **zero
                                    new DMAs** for the dead tail and the body
                                    is `pl.when`-skipped: bounded by live
                                    work though the grid length is static.

Both accumulate a per-day traversed-edge counter in the kernel (a resident
(1, b) lane accumulator) — the measured-TEPS numerator — at zero extra
memory traffic.

Launches: the schedule arrays ride in SMEM (1 MiB on v5e), which holds
about 80k tiles, and a full-scale twin schedules more. So a schedule is cut
into launches of at most ``MAX_ROW_TILES`` tiles, each ending on a row-run
boundary: every row block is accumulated within one launch, in schedule
order. Every launch is a ``pallas_call`` named
``interactions_kernel``: the name the compiled program and its traces
carry. The per-visit outputs are aliased from launch to launch, so rows a
launch does not visit keep their values (zeros, if no launch visits them).

Layout: every visit array enters as a lane-major (1, V) view and moves in
(1, b) blocks; Mosaic cannot reshape a 1-D lane vector into a column. The
tile is computed as (C, R) (see ref.pair_tile): row-side blocks broadcast
down the sublanes as they are, column-side blocks are transposed once per
tile into (b, b) with the column value along each row, and the per-row sums
come out as (1, b) lane vectors, written to (1, b) output blocks.

Double-buffering: Pallas's pipeline machinery overlaps the (1, b) visit-block
copies for grid step k+1 with compute for step k automatically; because the
compacted schedule puts all live tiles in a contiguous prefix, every
prefetched block is useful work (the padded schedule wastes prefetch slots
on dead tiles).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.population import MAX_ROW_TILES
from repro.kernels.interactions.ref import pair_tile, pair_tile_traced


def live_tiles(row_idx, col_idx, pair_active, col_has_inf, row_has_sus):
    """The per-tile liveness predicate shared by every backend: scheduled,
    not padding, and with both an infectious column and susceptible row."""
    return (
        (pair_active == 1)
        & (col_has_inf[col_idx] > 0)
        & (row_has_sus[row_idx] > 0)
    )


def _tile(meta, row_refs, col_refs, src_ref):
    """One tile of pair math on (1, b) block refs. Column-side blocks are
    turned into (b, b) with c[i, j] = x[i] (a sublane broadcast and one
    transpose each); row-side blocks broadcast down the sublanes as they
    are. Returns the (1, b) per-row sums of ref.pair_tile(_traced)."""
    b = row_refs[0].shape[1]
    col = lambda ref: jnp.broadcast_to(ref[...], (b, b)).T
    rows = [ref[...] for ref in row_refs]
    cols = [col(ref) for ref in col_refs]
    if src_ref is None:
        return pair_tile(meta[0], meta[1], *rows, *cols)
    return pair_tile_traced(meta[0], meta[1], *rows, *cols, col(src_ref))


def _kernel(
    # scalar prefetch: this launch's slice of the schedule
    rows,    # (K,) i32 row block per grid step
    cols,    # (K,) i32 col block per grid step
    live,    # (K,) i32 (bool) — tile has work today
    n,       # (1,) i32 — steps of this launch that are in the schedule
    meta,    # (2,) u32: [seed, day]
    # row-side blocks (1, b)
    pid_r, loc_r, start_r, end_r, p_r, sus_r,
    # col-side blocks (1, b)
    pid_c, loc_c, start_c, end_c, inf_c,
    # Plain: (acc_in, cnt_in, acc, cnt, edges). Traced: (src_c, acc_in,
    # cnt_in, trc_in, acc, cnt, trc, edges) — one more col-side input and
    # one more accumulator for contact tracing. The *_in refs are the
    # aliased previous outputs, never read. The arity is fixed at trace
    # time, so the untraced program contains no tracing code at all.
    *rest,
):
    traced = len(rest) == 8
    src_c = rest[0] if traced else None
    accs = rest[4:7] if traced else rest[2:4]
    edges = rest[-1]
    k = pl.program_id(0)
    in_sched = k < n[0]

    @pl.when(k == 0)
    def _zero_edges():
        edges[...] = jnp.zeros_like(edges)

    @pl.when(in_sched & ((k == 0) | (rows[k] != rows[jnp.maximum(k - 1, 0)])))
    def _zero():
        for ref in accs:
            ref[...] = jnp.zeros_like(ref)

    @pl.when(in_sched & (live[k] == 1))
    def _body():
        sums = _tile(meta, (pid_r, loc_r, start_r, end_r, p_r, sus_r),
                     (pid_c, loc_c, start_c, end_c, inf_c), src_c)
        for ref, tile_sum in zip(accs, sums):
            ref[...] += tile_sum
        # sus x inf contact pairs traversed in this tile — the TEPS
        # numerator, measured where the work happens.
        edges[...] += sums[1]


def _launch_one(rows, cols, live, n, meta, visits, src, outs, *,
                block_size, interpret):
    """One pallas_call over a schedule slice of K = len(rows) tiles."""
    b = block_size
    V = visits[0].shape[1]
    K = rows.shape[0]

    def _clamp(k, n):
        # Steps past the slice's schedule pin every index map to its last
        # tile: the pipeline sees an unchanged block index, issues no DMA,
        # and the final output flush writes the last row's block once.
        return jnp.minimum(k, jnp.maximum(n[0] - 1, 0))

    def row_map(k, rows, cols, live, n, meta):
        return (0, rows[_clamp(k, n)])

    def col_map(k, rows, cols, live, n, meta):
        return (0, cols[_clamp(k, n)])

    def edge_map(k, rows, cols, live, n, meta):
        return (0, 0)

    row_spec = pl.BlockSpec((1, b), row_map)
    col_spec = pl.BlockSpec((1, b), col_map)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    traced = src is not None
    in_specs = (
        [row_spec] * 6 + [col_spec] * 5 + ([col_spec] if traced else [])
        + [any_spec] * len(outs)
    )
    out_specs = [row_spec] * len(outs) + [pl.BlockSpec((1, b), edge_map)]
    out_shape = [jax.ShapeDtypeStruct(o.shape, o.dtype) for o in outs] + [
        jax.ShapeDtypeStruct((1, b), jnp.int32)
    ]
    scalars = (rows, cols, live, n, meta)
    row_vals = visits[:4] + visits[4:6]
    col_vals = visits[:4] + visits[6:7]
    operands = scalars + row_vals + col_vals + ((src,) if traced else ()) \
        + tuple(outs)
    first_out = len(operands) - len(outs)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(K,),
        in_specs=in_specs,
        out_specs=out_specs,
    )
    assert all(v.shape == (1, V) for v in operands[len(scalars):])
    *outs, edges = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases={first_out + i: i for i in range(len(outs))},
        interpret=interpret,
        name="interactions_kernel",
    )(*operands)
    return tuple(outs), edges


def _launch(rows, cols, live, n, meta, visits, src, *, block_size,
            interpret):
    """Run the kernel over the first ``n`` tiles of a schedule, in launches
    of at most MAX_ROW_TILES tiles that each end on a row-run boundary.
    Returns the per-visit outputs as (V,) arrays plus the i32 edge count."""
    V = visits[0].shape[0]
    NP = rows.shape[0]
    K = min(MAX_ROW_TILES, NP)
    i32 = lambda a: a.astype(jnp.int32)
    rows, cols, live = i32(rows), i32(cols), i32(live)
    steps = jnp.arange(NP, dtype=jnp.int32)
    first = jnp.concatenate([jnp.ones((1,), bool), rows[1:] != rows[:-1]])
    run_start = jax.lax.cummax(jnp.where(first, steps, 0))
    # K dead tiles at the end keep every K-wide slice in bounds.
    pad = lambda a: jnp.concatenate([a, jnp.full((K,), a[-1], jnp.int32)])
    rows_p, cols_p = pad(rows), pad(cols)
    live_p = jnp.concatenate([live, jnp.zeros((K,), jnp.int32)])
    visits = tuple(v.reshape(1, V) for v in visits)
    src = None if src is None else src.reshape(1, V)
    outs0 = (jnp.zeros((1, V), jnp.float32), jnp.zeros((1, V), jnp.int32)) \
        + ((jnp.zeros((1, V), jnp.int32),) if src is not None else ())

    def body(carry):
        s, outs, total = carry
        t = s + K
        e = jnp.where(t >= n, n, run_start[jnp.minimum(t, NP - 1)])
        # The schedule builder bounds every row run by K tiles, so e > s;
        # the guard only keeps the loop finite if that contract is broken.
        e = jnp.where(e > s, e, jnp.minimum(t, n))
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, s, K)
        outs, edges = _launch_one(
            cut(rows_p), cut(cols_p), cut(live_p), (e - s).reshape(1), meta,
            visits, src, outs, block_size=block_size, interpret=interpret,
        )
        return e, outs, total + jnp.sum(edges, dtype=jnp.int32)

    _, outs, total = jax.lax.while_loop(
        lambda c: c[0] < n, body,
        (jnp.zeros((), jnp.int32), outs0, jnp.zeros((), jnp.int32)),
    )
    return tuple(o.reshape(V) for o in outs) + (total,)


def _visits(pid, loc, start, end, p_loc, sus_val, inf_val):
    return (
        pid.astype(jnp.int32), loc.astype(jnp.int32),
        start.astype(jnp.float32), end.astype(jnp.float32),
        p_loc.astype(jnp.float32), sus_val.astype(jnp.float32),
        inf_val.astype(jnp.float32),
    )


@functools.partial(jax.jit, static_argnames=("block_size", "interpret"))
def interactions_pallas_call(
    pid, loc, start, end, p_loc, sus_val, inf_val,
    row_idx, col_idx, row_start, pair_active, col_has_inf, row_has_sus,
    meta,
    *,
    block_size: int,
    interpret: bool,
    src_val=None,
):
    """The kernel over the padded schedule. All visit arrays are (V,) with
    V % block_size == 0; schedule arrays are (NP,) / (NB,). Returns
    (acc (V,), cnt (V,), edges () i32, n_live () i32); with ``src_val``
    (tracing-source weights), (acc, cnt, trc, edges, n_live). ``n_live`` is
    the number of tiles with work today. Row blocks the schedule never
    visits read zero."""
    del row_start  # the kernel finds row runs from the row indices
    assert pid.shape[0] % block_size == 0
    live = live_tiles(row_idx, col_idx, pair_active, col_has_inf, row_has_sus)
    return _launch(
        row_idx, col_idx, live, jnp.asarray(row_idx.shape[0], jnp.int32),
        meta.astype(jnp.uint32),
        _visits(pid, loc, start, end, p_loc, sus_val, inf_val),
        None if src_val is None else src_val.astype(jnp.float32),
        block_size=block_size, interpret=interpret,
    ) + (live.sum().astype(jnp.int32),)


@functools.partial(jax.jit, static_argnames=("block_size", "interpret"))
def interactions_pallas_compact_call(
    pid, loc, start, end, p_loc, sus_val, inf_val,
    row_idx, col_idx, row_start, pair_active, col_has_inf, row_has_sus,
    meta,
    *,
    block_size: int,
    interpret: bool,
    src_val=None,
):
    """The fused active-set path: compact the schedule to its live tiles
    (stable, so row-major order is kept) and run the kernel over that
    prefix only. Same arguments and returns as
    :func:`interactions_pallas_call`; rows with no live tile read zero."""
    del row_start
    assert pid.shape[0] % block_size == 0
    live = live_tiles(row_idx, col_idx, pair_active, col_has_inf, row_has_sus)
    # Stable partition: live tiles first, original (row-major) order kept.
    order = jnp.argsort(jnp.where(live, 0, 1), stable=True)
    n_live = live.sum().astype(jnp.int32)
    return _launch(
        row_idx[order], col_idx[order], live[order], n_live,
        meta.astype(jnp.uint32),
        _visits(pid, loc, start, end, p_loc, sus_val, inf_val),
        None if src_val is None else src_val.astype(jnp.float32),
        block_size=block_size, interpret=interpret,
    ) + (n_live,)
