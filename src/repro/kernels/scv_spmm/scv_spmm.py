"""Pallas TPU kernel for SCV aggregation (DESIGN.md §2).

Mapping of the paper's mechanisms onto Pallas/TPU:

* One grid step processes one SCV tile (a Z-Morton vector group: T column
  vectors of height T).  ``PrefetchScalarGridSpec`` prefetches the tile
  coordinate arrays so the BlockSpec index maps are data-dependent — the
  "implicitly stores non-zero column locations → efficient prefetching"
  property of §III-B: Pallas double-buffers the *next* tile's Z block while
  the current tile computes, and skips the copy entirely when consecutive
  tiles share a column block (SCV's Z-reuse).

* The output BlockSpec revisits the same PS strip for every tile of a
  block-row; because the tile schedule keeps block-rows contiguous
  (``SCVTiles`` invariant), the strip lives in VMEM across all its tiles
  and is written back to HBM exactly once — §III-B's "fetched PS rows are
  reused multiple times before being evicted".

* Two kernel bodies (DESIGN.md §2):

  - ``body="vector"`` (default) — per chunk of C entries, a ``(T, C)``
    scatter matrix S (``S[t, j] = vals[j] * (rows[j] == t)``, built from a
    ``broadcasted_iota`` one-hot compare) and a ``(T, C)`` gather one-hot
    G (``G[u, j] = cols[j] == u``) turn the chunk into two MXU matmuls:
    ``out += S @ (Gᵀ Z)``.  Entries within a chunk land in *different* PS
    sublanes (the SCV column-vector order), and the matmul formulation
    removes the per-entry serialization entirely.  Tiles whose prefetched
    nnz exceeds ``dense_tile_threshold(T)`` are instead densified
    in-kernel (``D += S Gᵀ``, a ``(T, T)`` block) and hit the MXU as one
    plain ``out += D @ Z`` matmul — the hybrid selection rule
    ``benchmarks/kernel_roofline.py`` models, implemented.  Coverage-dummy
    tiles (nnz == 0) skip all compute via ``pl.when``.

  - ``body="scalar"`` — the pre-vectorization per-entry FMA loop, kept as
    the measured baseline for ``benchmarks/kernel_bench.py``.

* Padding entries carry val == 0 and are additionally skipped by bounding
  the chunk/entry loop with the prefetched per-tile nnz.

VMEM budget per step (defaults T=256, Fb=256, cap<=2048):
  Z block 256x256 f32 = 256 KiB, PS block 256 KiB, entries ~24 KiB,
  dense scratch 256 KiB -> ~0.8 MiB double-buffered, comfortably inside
  the ~16 MiB/core VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.scv import DEFAULT_CHUNK, dense_tile_threshold

#: Precision of the kernel's one-hot gather/scatter matmuls.  The
#: "one-hot matmul == exact gather" contract (DESIGN.md §2) needs Z rows
#: and entry values kept at f32, not rounded to bf16 as XLA's default f32
#: matmul does on the TPU.  Mosaic's default contracts f32 at f32 on v5e
#: (same error and time as HIGHEST there); HIGHEST states the contract
#: instead of leaning on that default.
ONEHOT_PRECISION = jax.lax.Precision.HIGHEST


def _kernel_scalar(
    # scalar-prefetch operands
    tile_row_ref,  # i32[nt]
    tile_col_ref,  # i32[nt]  (steers z BlockSpec; unused in body)
    nnz_ref,  # i32[nt]
    # array operands
    rows_ref,  # i32[1, cap]   (SMEM) local row of each entry
    cols_ref,  # i32[1, cap]   (SMEM) local col of each entry
    vals_ref,  # f32[1, cap]   (SMEM) value of each entry
    z_ref,  # [T, Fb]       (VMEM) combined-feature block
    *refs,  # (out_ref,) or (acc_ref, out_ref) in accumulate mode
):
    acc_ref, out_ref = refs if len(refs) == 2 else (None, refs[0])
    t = pl.program_id(1)

    # Fresh PS strip?  (first tile overall, or block-row changed.)
    prev = jnp.maximum(t - 1, 0)
    new_strip = jnp.logical_or(t == 0, tile_row_ref[t] != tile_row_ref[prev])

    @pl.when(new_strip)
    def _init():
        # accumulate mode: seed the strip from the chained accumulator
        # (the prior launch's output, aliased into this launch's buffer)
        # instead of zero — unvisited strips pass through untouched.
        if acc_ref is None:
            out_ref[...] = jnp.zeros_like(out_ref)
        else:
            out_ref[...] = acc_ref[...]

    nnz = nnz_ref[t]

    def body(i, _):
        r = rows_ref[0, i]
        c = cols_ref[0, i]
        v = vals_ref[0, i].astype(jnp.float32)
        zrow = z_ref[pl.ds(c, 1), :].astype(jnp.float32)
        out_ref[pl.ds(r, 1), :] += v * zrow
        return 0

    # No `unroll=`: jax raises ValueError for an unrolled fori_loop with
    # traced bounds, and nnz is prefetched data.
    jax.lax.fori_loop(0, nnz, body, 0)


def _kernel_vector(
    tile_row_ref,  # i32[nt]
    tile_col_ref,  # i32[nt]  (steers z BlockSpec; unused in body)
    nnz_ref,  # i32[nt]
    rows_ref,  # i32[cap // C, C]   (VMEM) local row of each entry
    cols_ref,  # i32[cap // C, C]   (VMEM) local col of each entry
    vals_ref,  # f32[cap // C, C]   (VMEM) value of each entry
    z_ref,  # [T, Fb]       (VMEM) combined-feature block
    *refs,  # (out_ref,) or (acc_ref, out_ref) in accumulate mode
    tile: int,
    chunk: int,
    dense_threshold: int,
):
    acc_ref, out_ref = refs if len(refs) == 2 else (None, refs[0])
    T, C = tile, chunk
    t = pl.program_id(1)

    prev = jnp.maximum(t - 1, 0)
    new_strip = jnp.logical_or(t == 0, tile_row_ref[t] != tile_row_ref[prev])

    @pl.when(new_strip)
    def _init():
        if acc_ref is None:
            out_ref[...] = jnp.zeros_like(out_ref)
        else:
            out_ref[...] = acc_ref[...]

    nnz = nnz_ref[t]
    n_chunks = (nnz + C - 1) // C
    iota_tc = jax.lax.broadcasted_iota(jnp.int32, (T, C), 0)

    def chunk_mats(k):
        """Scatter matrix S[t, j] = vals[j]*(rows[j]==t) and gather one-hot
        G[u, j] = (cols[j]==u) for chunk k.  Padding entries have val == 0,
        so their S column is zero and they contribute nothing."""
        # chunk k is row k of the entry block: a dynamic *sublane* index,
        # which Mosaic lowers at any C (a dynamic lane offset k*C is
        # refused unless it is provably a multiple of 128)
        sl = pl.ds(k, 1)
        r = rows_ref[sl, :]  # (1, C) broadcasts against the (T, C) iota
        c = cols_ref[sl, :]
        v = vals_ref[sl, :].astype(jnp.float32)
        scatter = jnp.where(iota_tc == r, v, 0.0)
        onehot = (iota_tc == c).astype(jnp.float32)
        return scatter, onehot

    # Hybrid rule: a tile dense enough that T^2 MXU MACs beat nnz VPU FMAs
    # is densified in-kernel and runs as one plain matmul.  The branch is
    # compiled out when no tile of this capacity can reach the threshold.
    use_dense = 0 <= dense_threshold < rows_ref.shape[0] * C
    is_dense = nnz > dense_threshold if use_dense else False

    @pl.when(jnp.logical_and(nnz > 0, jnp.logical_not(is_dense)))
    def _sparse():
        z = z_ref[...].astype(jnp.float32)

        def body(k, _):
            scatter, onehot = chunk_mats(k)
            # gathered[j, :] = Z[cols[j], :]  (one-hot matmul == exact gather)
            gathered = jax.lax.dot_general(
                onehot, z, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=ONEHOT_PRECISION,
            )
            out_ref[...] += jax.lax.dot_general(
                scatter, gathered, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=ONEHOT_PRECISION,
            )
            return 0

        jax.lax.fori_loop(0, n_chunks, body, 0)

    if use_dense:

        @pl.when(is_dense)
        def _dense():
            def body(k, d):
                scatter, onehot = chunk_mats(k)
                # D[t, u] += sum_j vals[j] * (rows[j]==t) * (cols[j]==u)
                return d + jax.lax.dot_general(
                    scatter, onehot, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                    precision=ONEHOT_PRECISION,
                )

            d = jax.lax.fori_loop(
                0, n_chunks, body, jnp.zeros((T, T), jnp.float32)
            )
            out_ref[...] += jax.lax.dot_general(
                d, z_ref[...].astype(jnp.float32), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=ONEHOT_PRECISION,
            )


@functools.partial(
    jax.jit,
    static_argnames=(
        "tile", "n_rows", "feature_block", "interpret", "body", "chunk",
        "dense_threshold",
    ),
)
def scv_spmm_pallas(
    tile_row: jnp.ndarray,  # i32[nt]
    tile_col: jnp.ndarray,  # i32[nt]
    nnz_in_tile: jnp.ndarray,  # i32[nt]
    rows: jnp.ndarray,  # i32[nt, cap]
    cols: jnp.ndarray,  # i32[nt, cap]
    vals: jnp.ndarray,  # f32[nt, cap]
    z: jnp.ndarray,  # [n_cols_padded, F_padded] — multiples of (tile, feature_block)
    acc: jnp.ndarray | None = None,  # f32[n_rows, F_padded] chained accumulator
    *,
    tile: int,
    n_rows: int,  # padded to a multiple of tile
    feature_block: int = 256,
    interpret: bool = False,
    body: str = "vector",
    chunk: int = DEFAULT_CHUNK,
    dense_threshold: int | None = None,
) -> jnp.ndarray:
    """One SCV SpMM launch.

    With ``acc`` (accumulate mode) the launch computes ``acc + Â Z``
    instead of ``Â Z``: the accumulator is aliased onto the output buffer
    (``input_output_aliases``), visited PS strips are *seeded* from it on
    first visit, and unvisited strips pass through untouched — so a chain
    of launches (one per capacity bucket) needs coverage dummies only in
    its first link (DESIGN.md §2).
    """
    nt, cap = vals.shape
    n_cols_p, f_p = z.shape
    T, Fb = tile, feature_block
    assert n_rows % T == 0 and n_cols_p % T == 0 and f_p % Fb == 0, (
        n_rows,
        z.shape,
        T,
        Fb,
    )

    if body == "vector":
        # chunk the entry arrays evenly: pad cap up to a multiple of the
        # chunk size (static shapes; the pad slots are structural zeros)
        C = min(int(chunk), max(cap, 1))
        if cap % C:
            pad = C - cap % C
            rows = jnp.pad(rows, ((0, 0), (0, pad)))
            cols = jnp.pad(cols, ((0, 0), (0, pad)))
            vals = jnp.pad(vals, ((0, 0), (0, pad)))
            cap += pad
        thr = dense_tile_threshold(T) if dense_threshold is None else int(dense_threshold)
        kernel = functools.partial(
            _kernel_vector, tile=T, chunk=C, dense_threshold=thr
        )
        # entry arrays feed vector compute (iota compares + matmuls), so
        # they live in VMEM as [nt, cap // C, C]: one tile's (cap // C, C)
        # block spans the array's last two dims (the TPU tiling accepts it
        # at any C), and chunk k is a sublane row of it
        rows, cols, vals = (a.reshape(nt, cap // C, C) for a in (rows, cols, vals))
        entry_spec = pl.BlockSpec(
            (None, cap // C, C), lambda f, t, tr, tc, nz: (t, 0, 0)
        )
    elif body == "scalar":
        kernel = _kernel_scalar
        # interpret-mode bench baseline only: a (1, cap) block is below
        # the TPU tiling minimum, so this body does not lower for the chip
        entry_spec = pl.BlockSpec(
            (1, cap), lambda f, t, tr, tc, nz: (t, 0), memory_space=pltpu.SMEM
        )
    else:
        raise ValueError(f"unknown kernel body {body!r}")

    grid = (f_p // Fb, nt)  # feature blocks outer, tiles inner

    in_specs = [
        # entry coordinate/value arrays: one tile's slice per step
        entry_spec,
        entry_spec,
        entry_spec,
        # Z block steered by the prefetched tile column
        pl.BlockSpec((T, Fb), lambda f, t, tr, tc, nz: (tc[t], f)),
    ]
    operands = (tile_row, tile_col, nnz_in_tile, rows, cols, vals, z)
    aliases = {}
    if acc is not None:
        assert acc.shape == (n_rows, f_p), (acc.shape, n_rows, f_p)
        # the accumulator rides the same index map as the output: the
        # kernel seeds each strip from its acc block on first visit, and
        # the buffer alias (acc is input 7 counting the scalar-prefetch
        # operands) makes unvisited strips retain the accumulator bytes
        in_specs.append(pl.BlockSpec((T, Fb), lambda f, t, tr, tc, nz: (tr[t], f)))
        operands += (acc.astype(jnp.float32),)
        aliases = {7: 0}

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((T, Fb), lambda f, t, tr, tc, nz: (tr[t], f)),
    )

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_rows, f_p), jnp.float32),
        input_output_aliases=aliases,
        interpret=interpret,
        name="scv_spmm",
    )(*operands)
