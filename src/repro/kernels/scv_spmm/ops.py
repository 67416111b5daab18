"""Jit-ready public wrapper around the SCV SpMM Pallas kernel.

Handles:
* padding Z to (tile, feature_block) multiples,
* inserting zero-nnz dummy tiles so every PS block-row is visited (the
  kernel zero-initializes a strip on first visit; unvisited strips would
  be undefined),
* segmented (nnz-bucketed) plans: one kernel launch per capacity bucket,
  partial outputs summed (DESIGN.md §2),
* custom VJP: d/dZ = Â^T g (played through the reference segment-sum path,
  which XLA fuses well) and d/dvals = <g[row], z[col]> — making SCV
  aggregation trainable end-to-end (GNN training, §VII future work (i)).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.scv_spmm import ref as _ref
from repro.kernels.scv_spmm.scv_spmm import resolve_body, scv_spmm_pallas


#: Most tiles one kernel launch takes.  The three prefetched i32 tile
#: arrays (tile_row, tile_col, nnz_in_tile) live in SMEM, 1 MiB on v5e,
#: and 65,536 tiles fill 768 KiB of it.  A longer tile sequence runs as a
#: chain of accumulate-mode launches over consecutive spans.
MAX_LAUNCH_TILES = 1 << 16


def n_launches(n_tiles: int) -> int:
    """Kernel launches a tile sequence of ``n_tiles`` takes."""
    return -(-int(n_tiles) // MAX_LAUNCH_TILES)


def _chain(arrays, z, out, statics):
    """Launch the kernel over ``arrays`` (tile_row, tile_col, nnz_in_tile,
    rows, cols, vals) in spans of at most ``MAX_LAUNCH_TILES`` tiles.
    Each launch after the first, and every launch when ``out`` is given,
    runs in accumulate mode on the running output."""
    for lo in range(0, arrays[0].shape[0], MAX_LAUNCH_TILES):
        span = tuple(a[lo:lo + MAX_LAUNCH_TILES] for a in arrays)
        if out is None:
            out = _spmm(*span, z, *statics)
        else:
            out = _spmm_acc(*span, z, out, *statics)
    return out


def ensure_row_coverage(
    tile_row: np.ndarray,
    tile_col: np.ndarray,
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    nnz_in_tile: np.ndarray,
    n_row_blocks: int,
):
    """Append one zero-nnz dummy tile per unvisited block-row (host-side)."""
    if rows.ndim != 2 or cols.ndim != 2 or vals.ndim != 2:
        raise ValueError(
            "entry arrays must be 2-D [n_tiles, cap]; got rows.ndim="
            f"{rows.ndim}, cols.ndim={cols.ndim}, vals.ndim={vals.ndim} "
            "(reshape 1-D per-entry arrays to (n_tiles, cap) first)"
        )
    missing = np.setdiff1d(
        np.arange(n_row_blocks, dtype=np.int32), np.unique(tile_row)
    )
    if len(missing) == 0:
        return tile_row, tile_col, rows, cols, vals, nnz_in_tile
    k, cap = len(missing), rows.shape[1]
    return (
        np.concatenate([tile_row, missing]),
        np.concatenate([tile_col, np.zeros(k, tile_col.dtype)]),
        np.concatenate([rows, np.zeros((k, cap), rows.dtype)]),
        np.concatenate([cols, np.zeros((k, cap), cols.dtype)]),
        np.concatenate([vals, np.zeros((k, cap), vals.dtype)]),
        np.concatenate([nnz_in_tile, np.zeros(k, nnz_in_tile.dtype)]),
    )


def _feature_block_for(f: int, feature_block: int) -> int:
    """Clamp the feature block to the lane-padded (128-multiple) feature
    width — the one clamp rule shared by ``scv_spmm`` and
    ``scv_spmm_plan`` so a pre-padded Z always matches the inner kernel."""
    return min(feature_block, -(-f // 128) * 128)


def _pad_z(z: jnp.ndarray, tile: int, feature_block: int) -> jnp.ndarray:
    n, f = z.shape
    np_ = -(-n // tile) * tile
    fp = -(-f // feature_block) * feature_block
    if (np_, fp) == (n, f):
        return z
    return jnp.zeros((np_, fp), z.dtype).at[:n, :f].set(z)


def _infer_nnz(rows, cols, vals) -> jnp.ndarray:
    """Per-tile nnz from structural padding (legacy no-nnz callers).

    Padding slots are a suffix of each tile row with val == 0 AND
    row == col == 0; the inferred count is one past the last slot that
    breaks that pattern.  (A *real* trailing entry at local (0, 0) with
    value exactly 0 is indistinguishable from padding — it contributes
    nothing to the forward either way, and its d/dvals is dropped; pass
    ``nnz_in_tile`` explicitly where that distinction matters.)
    """
    if vals.shape[1] == 0:
        return jnp.zeros(vals.shape[0], jnp.int32)
    slot = jnp.arange(vals.shape[1], dtype=jnp.int32)[None, :]
    is_real = (vals != 0) | (rows != 0) | (cols != 0)
    return jnp.max(jnp.where(is_real, slot + 1, 0), axis=1).astype(jnp.int32)


# custom_vjp over (vals, z).  The integer index arrays are regular
# (residual-carried) arguments rather than nondiff_argnums: nondiff_argnums
# rejects tracers, and under an end-to-end jitted GNN forward (plans are
# pytree *arguments*, not closure constants) every plan array arrives as a
# tracer.  Their cotangents are symbolic float0 zeros.
@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11, 12, 13))
def _spmm(tile_row, tile_col, nnz_in_tile, rows, cols, vals, z,
          tile, n_rows, feature_block, interpret, body, chunk, dense_threshold):
    return scv_spmm_pallas(
        tile_row, tile_col, nnz_in_tile, rows, cols, vals, z,
        tile=tile, n_rows=n_rows, feature_block=feature_block,
        interpret=interpret, body=body, chunk=chunk,
        dense_threshold=dense_threshold,
    )


def _spmm_fwd(tile_row, tile_col, nnz_in_tile, rows, cols, vals, z,
              tile, n_rows, feature_block, interpret, body, chunk, dense_threshold):
    out = _spmm(tile_row, tile_col, nnz_in_tile, rows, cols, vals, z,
                tile, n_rows, feature_block, interpret, body, chunk,
                dense_threshold)
    return out, (tile_row, tile_col, nnz_in_tile, rows, cols, vals, z)


def _entry_grads(tile, tile_row, tile_col, nnz_in_tile, rows, cols, vals, z, g):
    """(dvals, dz) for one launch — shared by the plain and the
    accumulate-mode VJPs (the acc contribution is identity: d/dacc = g)."""
    grows = (tile_row[:, None] * tile + rows).reshape(-1)
    gcols = (tile_col[:, None] * tile + cols).reshape(-1)
    gf = g.astype(jnp.float32)
    zf = z.astype(jnp.float32)
    # d/dvals_e = <g[row_e], z[col_e]>
    dvals = jnp.sum(gf[grows] * zf[gcols], axis=-1).reshape(vals.shape)
    # mask padding slots (their val is structurally zero)
    slot = jnp.arange(vals.shape[1], dtype=jnp.int32)[None, :]
    dvals = jnp.where(slot < nnz_in_tile[:, None], dvals, 0.0).astype(vals.dtype)
    # d/dZ = A^T g : scatter-add g rows into z rows, weighted
    dz = jnp.zeros(z.shape, jnp.float32)
    dz = dz.at[gcols].add(gf[grows] * vals.reshape(-1)[:, None].astype(jnp.float32))
    return dvals, dz.astype(z.dtype)


def _f0(a):  # integer-typed primals take float0 cotangents
    # jax requires float0 cotangents as *numpy* arrays (jnp.zeros
    # cannot hold dtype float0) — deliberate host-side constant.
    return np.zeros(a.shape, jax.dtypes.float0)  # scvlint: ignore[SCV001]


def _spmm_bwd(tile, n_rows, feature_block, interpret, body, chunk,
              dense_threshold, res, g):
    tile_row, tile_col, nnz_in_tile, rows, cols, vals, z = res
    dvals, dz = _entry_grads(
        tile, tile_row, tile_col, nnz_in_tile, rows, cols, vals, z, g
    )
    return (
        _f0(tile_row), _f0(tile_col), _f0(nnz_in_tile), _f0(rows), _f0(cols),
        dvals, dz,
    )


_spmm.defvjp(_spmm_fwd, _spmm_bwd)


# Accumulate-mode launch: out = acc + Â Z with the accumulator aliased onto
# the output buffer.  ``acc`` is a *differentiable* operand — the chain
# out_k = out_{k-1} + contrib_k backpropagates by plain composition, each
# link passing the cotangent through to its predecessor unchanged.
@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10, 11, 12, 13, 14))
def _spmm_acc(tile_row, tile_col, nnz_in_tile, rows, cols, vals, z, acc,
              tile, n_rows, feature_block, interpret, body, chunk,
              dense_threshold):
    return scv_spmm_pallas(
        tile_row, tile_col, nnz_in_tile, rows, cols, vals, z, acc,
        tile=tile, n_rows=n_rows, feature_block=feature_block,
        interpret=interpret, body=body, chunk=chunk,
        dense_threshold=dense_threshold,
    )


def _spmm_acc_fwd(tile_row, tile_col, nnz_in_tile, rows, cols, vals, z, acc,
                  tile, n_rows, feature_block, interpret, body, chunk,
                  dense_threshold):
    out = _spmm_acc(tile_row, tile_col, nnz_in_tile, rows, cols, vals, z, acc,
                    tile, n_rows, feature_block, interpret, body, chunk,
                    dense_threshold)
    return out, (tile_row, tile_col, nnz_in_tile, rows, cols, vals, z)


def _spmm_acc_bwd(tile, n_rows, feature_block, interpret, body, chunk,
                  dense_threshold, res, g):
    tile_row, tile_col, nnz_in_tile, rows, cols, vals, z = res
    dvals, dz = _entry_grads(
        tile, tile_row, tile_col, nnz_in_tile, rows, cols, vals, z, g
    )
    # out = acc + contribution, identically in every row: d/dacc = g
    return (
        _f0(tile_row), _f0(tile_col), _f0(nnz_in_tile), _f0(rows), _f0(cols),
        dvals, dz, g,
    )


_spmm_acc.defvjp(_spmm_acc_fwd, _spmm_acc_bwd)


def scv_spmm(
    tile_row: jnp.ndarray,
    tile_col: jnp.ndarray,
    rows: jnp.ndarray,
    cols: jnp.ndarray,
    vals: jnp.ndarray,
    z: jnp.ndarray,
    *,
    tile: int,
    n_rows: int,
    nnz_in_tile: jnp.ndarray | None = None,
    feature_block: int = 256,
    interpret: bool = False,
    body: str = "auto",
    chunk: int | None = None,
    dense_threshold: int | None = None,
) -> jnp.ndarray:
    """out = Â Z over the SCV tile layout.  Returns f32[n_rows, F]."""
    from repro.core.scv import DEFAULT_CHUNK

    nt = tile_row.shape[0]
    if nt == 0:
        return jnp.zeros((n_rows, z.shape[1]), jnp.float32)
    f_orig = z.shape[1]
    feature_block = _feature_block_for(f_orig, feature_block)
    zp = _pad_z(z, tile, feature_block)
    if nnz_in_tile is None:
        # infer the structural padding suffix: without a mask, d/dvals
        # would be nonzero on padding slots (they share local (0, 0) with a
        # real corner entry, and <g[0], z[0]> is generally nonzero)
        nnz_in_tile = _infer_nnz(rows, cols, vals)
    arrays = (
        tile_row.astype(jnp.int32),
        tile_col.astype(jnp.int32),
        nnz_in_tile.astype(jnp.int32),
        rows.astype(jnp.int32),
        cols.astype(jnp.int32),
        vals,
    )
    statics = (
        tile, n_rows, feature_block, interpret,
        resolve_body(body, vals.shape[1], tile, nt),
        int(DEFAULT_CHUNK if chunk is None else chunk), dense_threshold,
    )
    # one launch zero-initializes only the strips its tiles visit: a tile
    # sequence split over several launches chains from explicit zeros
    out = None
    if nt > MAX_LAUNCH_TILES:
        out = jnp.zeros((n_rows, zp.shape[1]), jnp.float32)
    return _chain(arrays, zp, out, statics)[:, :f_orig]


def scv_spmm_plan(
    plan,
    z: jnp.ndarray,
    *,
    feature_block: int = 256,
    interpret: bool = False,
    body: str = "auto",
    chunk: int | None = None,
    dense_threshold: int | None = None,
    init: str = "coverage",
) -> jnp.ndarray:
    """``scv_spmm`` over a ``core.scv`` plan pytree (``SCVPlan`` or the
    nnz-bucketed ``SCVBucketedPlan``).

    All static kernel configuration (tile size, padded row count, entry
    capacity via the leaf shapes, the bucket ladder via the segment tuple)
    comes from the plan's aux data — nothing needs to be threaded alongside
    the arrays, so callers stay jit-able.  A bucketed plan runs one kernel
    launch per capacity segment, **chained through one accumulator**: the
    first launch zero-initializes its strips (its coverage dummies define
    the whole output — ``plan_from_tiles_bucketed`` emits them in the
    first segment only), and every later launch runs in accumulate mode
    (``input_output_aliases``) — visited strips are seeded from the
    previous launch's output, unvisited strips pass through.  Coverage
    dummies therefore exist once per *plan*, not once per segment at that
    segment's cap, and there is no partial-output sum tree.  Z is padded
    **once** for all segments (same tile, same feature_block — per-launch
    re-padding would be redundant work in eager mode).

    A segment longer than ``MAX_LAUNCH_TILES`` tiles (its prefetched tile
    arrays would overflow SMEM) runs as consecutive spans, one accumulate-
    mode launch each; the chain then starts from zeros, since no single
    launch visits every strip.

    ``init="zeros"`` starts the chain from an explicit zero accumulator
    instead: every row is then defined even when *no* segment covers it —
    the executor's sharded spans (which carry no per-span coverage) use
    this mode.

    ``body="auto"`` (the default) lets each segment pick its kernel body
    from its static ``(cap, tile)`` and tile count: the row-gather body
    for large ultra-sparse segments, the vector body otherwise
    (``scv_spmm.resolve_body``).  Every span of a segment runs the body
    the whole segment resolved to.

    Under the executor's feature-axis sharding (``core.exec``), ``z`` is a
    device-local ``Z[:, f0:f1]`` slab: the kernel's feature-block grid
    axis then simply runs over fewer blocks — the mesh mapping happens at
    the ``shard_map`` layer, the kernel is unchanged.
    """
    from repro.core.scv import DEFAULT_CHUNK

    if init not in ("coverage", "zeros"):
        raise ValueError(f"init must be 'coverage' or 'zeros', got {init!r}")
    # a bare SCVPlan is a 1-tuple; SCVBucketedPlan guarantees >= 1 segment
    segments = getattr(plan, "segments", (plan,))
    f_orig = z.shape[1]
    fb = _feature_block_for(f_orig, feature_block)
    zp = _pad_z(z, segments[0].tile, fb)
    n_rows = segments[0].padded_shape[0]
    chunk = int(DEFAULT_CHUNK if chunk is None else chunk)
    out = None
    # the coverage launch is the first segment's, as one launch: an empty
    # first segment, or one split over several launches (MAX_LAUNCH_TILES),
    # no longer defines every strip, so the chain starts from zeros
    nt0 = segments[0].tile_row.shape[0]
    if init == "zeros" or not 0 < nt0 <= MAX_LAUNCH_TILES:
        out = jnp.zeros((n_rows, zp.shape[1]), jnp.float32)
    for seg in segments:  # an empty segment launches nothing
        arrays = (
            seg.tile_row.astype(jnp.int32),
            seg.tile_col.astype(jnp.int32),
            seg.nnz_in_tile.astype(jnp.int32),
            seg.rows.astype(jnp.int32),
            seg.cols.astype(jnp.int32),
            seg.vals,
        )
        seg_body = resolve_body(body, seg.cap, seg.tile, seg.tile_row.shape[0])
        statics = (seg.tile, n_rows, fb, interpret, seg_body, chunk, dense_threshold)
        out = _chain(arrays, zp, out, statics)
    return out[:, :f_orig]


def scv_spmm_reference(*args, **kw):
    return _ref.scv_spmm_reference(*args, **kw)
