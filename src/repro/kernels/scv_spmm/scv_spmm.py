"""Pallas TPU kernel for SCV aggregation (DESIGN.md §2).

Mapping of the paper's mechanisms onto Pallas/TPU:

* Three kernel bodies share the launch name ``scv_spmm``; ``body="auto"``
  (the default) picks one per segment from its static ``(cap, tile)``
  and tile count (``resolve_body``, ``core.scv.uses_row_gather``).

* ``body="rows"`` — large ultra-sparse segments (``cap <= T / 2``, at
  least ``ROW_GATHER_MIN_TILES`` tiles).  Z stays in
  HBM; one grid step covers a group of tiles (64 at cap 8) and DMAs only
  the Z rows its entries reference (single rows where Z is one lane tile
  wide, else the 8-row slab holding each), fetching the next group's
  rows while this one computes.  Entry p of a group becomes row p of a
  scatter matrix Sᵀ as its DMA starts, and each chunk of C rows lands in
  the PS strip as one ``(Sᵀ)ᵀ @ gathered`` MXU matmul.  The PS strip is held in VMEM and
  written back by DMA when the block-row changes.  Padding slots and
  inert tiles issue no DMA.

* ``body="vector"`` — the other segments.  One grid step processes one
  SCV tile (a Z-Morton vector group: T column vectors of height T).
  ``PrefetchScalarGridSpec`` prefetches the tile coordinate arrays so the
  BlockSpec index maps are data-dependent — the "implicitly stores
  non-zero column locations → efficient prefetching" property of
  §III-B: Pallas double-buffers the *next* tile's Z block while the
  current tile computes, and skips the copy entirely when consecutive
  tiles share a column block (SCV's Z-reuse).  Per chunk of C entries, a
  ``(T, C)`` scatter matrix S (``S[t, j] = vals[j] * (rows[j] == t)``,
  built from a ``broadcasted_iota`` one-hot compare) and a ``(T, C)``
  gather one-hot G (``G[u, j] = cols[j] == u``) turn the chunk into two
  MXU matmuls: ``out += S @ (Gᵀ Z)``.  Tiles whose prefetched nnz
  exceeds ``dense_tile_threshold(T)`` are instead densified in-kernel
  (``D += S Gᵀ``, a ``(T, T)`` block) and hit the MXU as one plain
  ``out += D @ Z`` matmul — the hybrid selection rule
  ``benchmarks/kernel_roofline.py`` models, implemented.  Coverage-dummy
  tiles (nnz == 0) skip all compute via ``pl.when``.

* ``body="scalar"`` — the pre-vectorization per-entry FMA loop, kept as
  the measured baseline for ``benchmarks/kernel_bench.py``.

* Every body keeps one PS strip per block-row in VMEM across all the
  block-row's tiles and writes it to HBM once: the tile schedule keeps
  block-rows contiguous (``SCVTiles`` invariant) — §III-B's "fetched PS
  rows are reused multiple times before being evicted".  The vector and
  scalar bodies get this from an output BlockSpec that revisits the
  strip; the row-gather body manages the strip itself.

* Padding entries carry val == 0 and are additionally skipped by bounding
  the chunk/entry loop with the prefetched per-tile nnz.

VMEM budget per step: vector body (T=256, Fb=256, cap<=2048) Z block
256 KiB, PS block 256 KiB, entries ~24 KiB, dense scratch 256 KiB ->
~0.8 MiB double-buffered; row-gather body (T=64, Fb=128, 512 slots) row
buffers 512 KiB (8-row slabs: 4 MiB), Sᵀ 512 KiB, strips 64 KiB, all for
two groups — inside the ~16 MiB/core VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.scv import (
    DEFAULT_CHUNK,
    ROW_GATHER_SLOTS,
    dense_tile_threshold,
    uses_row_gather,
)

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


def _kernel_rows(
    # scalar-prefetch operands
    tile_row_ref,  # i32[nt]
    tile_col_ref,  # i32[nt]
    nnz_ref,  # i32[nt]
    # array operands: the SMEM block of B tiles holding group s
    cols_ref,  # i32[B, cap]   (SMEM) local col of each entry
    rows_ref,  # i32[B, cap]   (SMEM) local row of each entry
    vals_ref,  # f32[B, cap]   (SMEM) value of each entry
    z_hbm,  # [n_cols_p, F_p] (HBM) combined features
    *refs,  # ([acc_hbm,] out_hbm, gathered, [picked, sub_row,] scatter_t,
    #          strip, state, zsem, osem)
    tile: int,
    chunk: int,
    block: int,
    group: int,
    n_tiles: int,
    feature_block: int,
    slab: bool,
    accumulate: bool,
):
    """Row-gather body: one grid step covers a group of G tiles.

    Step s walks group s's real entries: entry p starts a DMA of Z row
    ``tile_col * T + col`` into row p of a gather buffer (or, with
    ``slab``, of the aligned 8-row slab holding it) and writes row p of a
    scatter matrix Sᵀ (``vals`` at lane ``rows``).  The step then waits
    for group s - 1's rows, fetched one step earlier into the other half
    of the double buffer, and adds each chunk of C of them to the PS
    strip as one ``(Sᵀ)ᵀ @ gathered`` matmul.  The PS strip lives in VMEM
    and is written back by DMA when the block-row changes.  A group whose
    first and last tiles share a block-row lies in it whole, since the
    tiles of a block-row are consecutive (``SCVTiles`` invariant); only a
    group spanning block-rows walks its tiles to split at each change.
    Entry arrays arrive in SMEM blocks of B tiles, B // G groups each."""
    refs = list(refs)
    acc_hbm = refs.pop(0) if accumulate else None
    out_hbm, gathered = refs[:2]
    picked, sub_row = refs[2:4] if slab else (None, None)
    scatter_t, strip, state, zsem, osem = refs[-5:]
    T, C, B, G, Fb = tile, chunk, block, group, feature_block
    per_block = -(-B // G)
    f = pl.program_id(0)
    s = pl.program_id(1)
    n_groups = pl.num_programs(1) - 1
    fcols = pl.ds(pl.multiple_of(f * Fb, Fb), Fb)
    lane_t = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
    sublane_c = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)
    n_slots = scatter_t.shape[1]

    # state: [block-row held in the strip (-1: none), strip slot,
    #         write of slot 0 in flight, write of slot 1 in flight,
    #         entries fetched into gather buffer 0, into buffer 1]
    @pl.when(s == 0)
    def _reset():
        state[0] = -1
        state[1] = 0
        state[2] = 0
        state[3] = 0

    def group_span(g):
        """Group g's first tile and one past its last, as indices into its
        SMEM block, and the block's first tile."""
        base = (g // per_block) * B
        lo = (g % per_block) * G
        return lo, jnp.minimum(lo + G, jnp.minimum(B, n_tiles - base)), base

    def z_copy(r, buf, q):
        if slab:
            src = z_hbm.at[pl.ds(pl.multiple_of(r - r % 8, 8), 8), fcols]
            dst = gathered.at[buf, q]
        else:
            src = z_hbm.at[pl.ds(r, 1), fcols]
            dst = gathered.at[buf, pl.ds(q, 1)]
        return pltpu.make_async_copy(src, dst, zsem.at[buf])

    def strip_copy(slot, row):
        return pltpu.make_async_copy(
            strip.at[slot], out_hbm.at[pl.ds(row * T, T), fcols], osem.at[slot]
        )

    @pl.when(s < n_groups)
    def _fetch():
        buf = s % 2
        lo, hi, base = group_span(s)

        def tile_body(i, q):
            z0 = tile_col_ref[base + i] * T

            def entry(e, q):
                r = z0 + cols_ref[i, e]
                z_copy(r, buf, q).start()
                scatter_t[buf, pl.ds(q, 1), :] = jnp.where(
                    lane_t == rows_ref[i, e], vals_ref[i, e], 0.0
                )
                if slab:
                    sub_row[buf * n_slots + q] = r % 8
                return q + 1

            return jax.lax.fori_loop(0, nnz_ref[base + i], entry, q)

        state[4 + buf] = jax.lax.fori_loop(lo, hi, tile_body, 0)

    def scatter(buf, lo, hi):
        """strip += rows lo..hi-1 of Sᵀ, applied to the gathered rows."""
        slot = state[1]
        rows_of = picked if slab else gathered.at[buf]

        def body(k, _):
            start = pl.multiple_of(k * C, C)
            keep = jnp.logical_and(sublane_c + start >= lo, sublane_c + start < hi)
            s_t = jnp.where(keep, scatter_t[buf, pl.ds(start, C), :], 0.0)
            zc = jnp.where(keep, rows_of[pl.ds(start, C), :], 0.0)
            strip[slot] += jax.lax.dot_general(
                s_t, zc, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=ONEHOT_PRECISION,
            )
            return 0

        jax.lax.fori_loop(lo // C, jnp.where(hi > lo, (hi + C - 1) // C, 0), body, 0)

    def write_back():
        """Start writing the held strip; free the other slot for reuse."""
        slot = state[1]
        strip_copy(slot, state[0]).start()
        state[2 + slot] = 1
        other = 1 - slot

        @pl.when(state[2 + other] == 1)
        def _():
            strip_copy(other, 0).wait()
            state[2 + other] = 0

        state[1] = other

    def switch_to(row):
        """Write back the held strip, if any, and open ``row``'s: zeros, or
        in accumulate mode the accumulator's rows."""
        @pl.when(state[0] >= 0)
        def _():
            write_back()

        slot = state[1]
        if acc_hbm is None:
            strip[slot] = jnp.zeros((T, Fb), jnp.float32)
        else:
            seed = pltpu.make_async_copy(
                acc_hbm.at[pl.ds(row * T, T), fcols], strip.at[slot], osem.at[2]
            )
            seed.start()
            seed.wait()
        state[0] = row

    @pl.when(s > 0)
    def _accumulate():
        buf = (s - 1) % 2
        lo, hi, base = group_span(s - 1)
        fetched = state[4 + buf]

        def wait(p, c):
            z_copy(0, buf, 0).wait()
            return c

        jax.lax.fori_loop(0, fetched, wait, 0)
        if slab:

            def pick(p, c):
                sub = sub_row[buf * n_slots + p]
                picked[pl.ds(p, 1), :] = gathered[buf, p, pl.ds(sub, 1), :]
                return c

            jax.lax.fori_loop(0, fetched, pick, 0)

        # the group's entries, block-row by block-row.  A group whose first
        # and last tiles share a block-row lies in it whole (block-rows are
        # consecutive), so it is one run and its tiles are not walked.
        last = n_tiles - 1  # an empty group's span may lie past the end
        one_row = (
            tile_row_ref[jnp.minimum(base + lo, last)]
            == tile_row_ref[jnp.minimum(base + hi - 1, last)]
        )

        def run(carry):
            i, first = carry
            row = tile_row_ref[base + i]

            @pl.when(row != state[0])
            def _():
                switch_to(row)

            def same_row(c):
                return jnp.logical_and(c[0] < hi, tile_row_ref[base + c[0]] == row)

            def step(c):
                return c[0] + 1, c[1] + nnz_ref[base + c[0]]

            end, p = jax.lax.while_loop(
                lambda c: jnp.logical_and(jnp.logical_not(one_row), same_row(c)),
                step, (i, first),
            )
            end, p = jnp.where(one_row, hi, end), jnp.where(one_row, fetched, p)
            scatter(buf, first, p)
            return end, p

        jax.lax.while_loop(lambda c: c[0] < hi, run, (lo, 0))

    @pl.when(s == n_groups)
    def _finish():
        @pl.when(state[0] >= 0)
        def _():
            write_back()

        for slot in (0, 1):

            @pl.when(state[2 + slot] == 1)
            def _():
                strip_copy(slot, 0).wait()


#: Tiles of one SMEM entry block of the row-gather body.  Three (B, cap)
#: blocks, double-buffered and lane-padded to 128 words, share the 1 MiB
#: of SMEM with the 768 KiB of prefetched tile arrays.
ROW_BLOCK_TILES = 64


def _rows_call(tile_row, tile_col, nnz_in_tile, rows, cols, vals, z, acc, *,
               tile, n_rows, feature_block, interpret, chunk):
    """pallas_call of the row-gather body (``_kernel_rows``)."""
    nt, cap = vals.shape
    f_p = z.shape[1]
    T, Fb, C = tile, feature_block, int(chunk)
    B = min(nt, ROW_BLOCK_TILES)
    per_block = 1  # groups per block: a power of two, G * cap <= slots
    while per_block < B and B * cap > ROW_GATHER_SLOTS * per_block:
        per_block *= 2
    G = -(-B // per_block)
    n_groups = -(-nt // B) * per_block
    slots = -(-(G * cap) // C) * C
    # a single-row DMA lowers only where a row is one (8, 128) tile's
    # sublane: Z one lane-tile wide.  Wider Z fetches the 8-row slab.
    slab = f_p > 128
    kernel = functools.partial(
        _kernel_rows, tile=T, chunk=C, block=B, group=G, n_tiles=nt,
        feature_block=Fb, slab=slab, accumulate=acc is not None,
    )

    # the block holding group s; the last step fetches nothing
    entries = pl.BlockSpec(
        (B, cap), lambda f, s, *_: (jnp.minimum(s, n_groups - 1) // per_block, 0),
        memory_space=pltpu.SMEM,
    )

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [entries, entries, entries, hbm]
    # rows are gathered and scattered in f32 (a no-op for f32 Z)
    z = z.astype(jnp.float32)
    operands = [tile_row, tile_col, nnz_in_tile, cols, rows,
                vals.astype(jnp.float32), z]
    aliases = {}
    if acc is not None:
        in_specs.append(hbm)
        operands.append(acc.astype(jnp.float32))
        aliases = {len(operands) - 1: 0}
    if slab:
        gather_scratch = [
            pltpu.VMEM((2, slots, 8, Fb), jnp.float32),  # 8-row slabs, 2 groups
            pltpu.VMEM((slots, Fb), jnp.float32),  # the entry's row of each slab
            pltpu.SMEM((2 * slots,), jnp.int32),  # its row within the slab
        ]
    else:
        gather_scratch = [pltpu.VMEM((2, slots, Fb), jnp.float32)]  # 2 groups
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(f_p // Fb, n_groups + 1),
        in_specs=in_specs,
        out_specs=hbm,
        scratch_shapes=gather_scratch + [
            pltpu.VMEM((2, slots, T), jnp.float32),  # Sᵀ, 2 groups
            pltpu.VMEM((2, T, Fb), jnp.float32),  # PS strips
            pltpu.SMEM((6,), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),  # Z rows, per gather buffer
            pltpu.SemaphoreType.DMA((3,)),  # strip write-backs, acc seed
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_rows, f_p), jnp.float32),
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        interpret=interpret,
        name="scv_spmm",
    )(*operands)


def resolve_body(body: str, cap: int, tile: int, n_tiles: int) -> str:
    """The kernel body a segment of ``n_tiles`` tiles runs: ``"auto"`` picks
    the row-gather body for large segments whose capacity is small against
    the tile (``core.scv.uses_row_gather``) and the vector body otherwise;
    any other name is taken as given."""
    if body != "auto":
        return body
    return "rows" if uses_row_gather(cap, tile, n_tiles) else "vector"


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
    body: str = "auto",
    chunk: int = DEFAULT_CHUNK,
    dense_threshold: int | None = None,
) -> jnp.ndarray:
    """One SCV SpMM launch.

    ``body`` names the kernel body (``resolve_body``): ``"auto"`` (the
    default) picks it from the static ``(cap, tile)``; ``"rows"``,
    ``"vector"`` and ``"scalar"`` force one.

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

    body = resolve_body(body, cap, T, nt)
    if body == "rows":
        return _rows_call(
            tile_row, tile_col, nnz_in_tile, rows, cols, vals, z, acc,
            tile=T, n_rows=n_rows, feature_block=Fb, interpret=interpret,
            chunk=chunk,
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
