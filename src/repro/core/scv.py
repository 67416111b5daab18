"""Sparse Compressed Vectors — the paper's contribution (§III).

Two representations are provided:

* :class:`SCVMatrix` — the *logical* format of Fig. 1(d): fixed-height
  column vectors, per-entry within-vector row offsets (``blk_id``), vector
  pointer array (``blk_ptr``), vectors enumerated row-major over the block
  grid (SCV) or along a Z-Morton curve over B x B vector groups (SCV-Z).
  This is what the cycle/traffic simulator replays and what matches the
  paper bit-for-bit.

* :class:`SCVTiles` — the *TPU device* layout consumed by the Pallas kernel
  (see DESIGN.md §2): the same entries regrouped into T x T tiles (a tile =
  one Z-Morton vector-group = T column vectors), each tile padded to a fixed
  entry capacity so shapes are static.  Within a tile, entries keep the SCV
  column-vector order (sorted by local column, then local row).  Tiles are
  scheduled so that all tiles of one PS block-row are consecutive — the
  Pallas analogue of "partial sums reused before eviction".

* :class:`SCVPlan` — the *executable* plan: the SCVTiles arrays on device
  (coverage dummies appended, perm padded), registered as a jax pytree so
  a whole GNN forward over it can sit under one ``jax.jit``.  Array fields
  are pytree **leaves**; ``tile`` / ``cap`` / ``shape`` / ``order`` are
  **static aux data**, so jit specializes on them (and on leaf shapes)
  exactly once per padding bucket.

* :class:`SCVBucketedPlan` — the nnz-bucketed variant (DESIGN.md §2): one
  ``SCVPlan`` segment per entry-capacity bucket so a single hub tile no
  longer sets the padded capacity of every tile; the kernel runs one
  launch per segment and sums the partials.

Construction is host-side preprocessing ("statically generated from the COO
format ... nearly equivalent to creating a CSR or CSC matrix" — §III-C);
``coo_to_scv_tiles`` emits tiles with vectorized numpy scatter, so the cost
really is a couple of sorts plus O(nnz) array ops even at million-edge
scale (``benchmarks/preprocess_bench.py`` gates this).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import numpy as np

from repro.core import morton
from repro.core.formats import COOMatrix
from repro.spans import span

ROW_MAJOR = "row_major"
ZMORTON = "zmorton"

# ---------------------------------------------------------------------------
# Kernel-model constants (DESIGN.md §2) — the single source of truth shared
# by the Pallas kernel (`kernels/scv_spmm`), the hybrid split below, and the
# roofline model (`benchmarks/kernel_roofline.py` imports these so the model
# and the implementation cannot drift).
# ---------------------------------------------------------------------------
#: VPU FMA-lane rate over MXU MAC rate (v5e: 8x128 lanes vs 128x128 MACs).
MXU_VPU_RATIO = 1.0 / 16.0
#: Entries per vectorized kernel chunk (one scatter/gather matmul pair).
DEFAULT_CHUNK = 128
#: Geometric ratio between adjacent capacity buckets.
BUCKET_RATIO = 4
#: Maximum number of capacity buckets a plan is split into.
MAX_BUCKETS = 4
#: Smallest per-tile entry capacity (TPU sublane count).
MIN_BUCKET_CAP = 8
#: Default tile size T (block row/column extent of an SCV tile).
DEFAULT_TILE = 64
#: Default single-bucket per-tile capacity when bucketing is disabled.
DEFAULT_CAP = 64
#: Default serving capacity ladder — the measured ladder A/B winner on the
#: sparse 131k-node pool (serve_bench ``ladder_ab``; 3-deep won the PR 10
#: re-run and serve_bench now *fails* if a recorded winner beats the
#: default past the ladder slack band, so this constant tracks the
#: measurement instead of drifting stale).  Per-regime overrides come
#: from ``repro.tune.TunedConfig``; scvlint SCV002 rejects re-declared
#: tile/cap/ladder literals outside this module and ``tune/config.py``.
DEFAULT_LADDER = (8, 32, 128)


def dense_tile_threshold(tile: int) -> int:
    """nnz above which a T x T tile is cheaper as a dense MXU matmul than
    as per-entry gather-FMA work on the VPU:

        T*T*F / MXU_rate < nnz * F / VPU_rate  =>  nnz > T^2 * VPU/MXU
    """
    return int(tile * tile * MXU_VPU_RATIO)


#: Largest capacity, as a share of the tile size T, of a segment the
#: kernel serves with its row-gather body: fetching at most ``cap`` single
#: Z rows costs less than the T-row block the vector body fetches, and a
#: single-row DMA is taken to cost about two rows of a block transfer.
ROW_GATHER_CAP_RATIO = 0.5
#: Fewest tiles a segment needs for the row-gather body.  Below it the
#: vector body's one grid step a tile (~0.4 µs on a v5e) costs under a
#: millisecond a launch, less than the row-gather body's longer compile,
#: which a stream of small-graph composites pays once per padding bucket
#: (the molecule cell's cold set-up was 8.5 s longer with it).
ROW_GATHER_MIN_TILES = 2048
#: Entry slots one row-gather grid step covers: ``ROW_GATHER_SLOTS // cap``
#: tiles per step, and the row buffer's height.
ROW_GATHER_SLOTS = 512


def uses_row_gather(cap: int, tile: int, n_tiles: int) -> bool:
    """Whether a segment of ``n_tiles`` tiles of capacity ``cap`` at tile
    size ``tile`` runs the kernel's row-gather body (DESIGN.md §2) rather
    than the vector body."""
    return cap <= tile * ROW_GATHER_CAP_RATIO and n_tiles >= ROW_GATHER_MIN_TILES


def bucket_caps_for(
    counts: np.ndarray,
    tile: int,
    max_buckets: int = MAX_BUCKETS,
    ratio: int = BUCKET_RATIO,
) -> tuple[int, ...]:
    """Ascending power-of-two capacity ladder covering ``counts``.

    The largest cap is the smallest power of two holding the heaviest tile
    (clamped to T^2 — a tile cannot exceed its dense size); smaller caps
    descend geometrically by ``ratio`` down to ``MIN_BUCKET_CAP``.  The
    ladder is a pure function of (max count, tile), so two graphs with
    similar hub sizes share plan aux — and therefore jit traces.
    """
    hi = int(counts.max()) if len(counts) else 1
    hi = max(MIN_BUCKET_CAP, min(hi, tile * tile))
    cap = MIN_BUCKET_CAP
    while cap < hi:
        cap *= 2
    caps = [cap]
    while len(caps) < max_buckets and caps[-1] // ratio >= MIN_BUCKET_CAP:
        caps.append(caps[-1] // ratio)
    return tuple(sorted(caps))


def launched_slots(
    counts: np.ndarray,
    tile: int,
    caps: tuple[int, ...],
    n_row_blocks: int = 0,
) -> int:
    """Capacity slots a bucketed plan *launches* for a tile-nnz histogram.

    Mirrors the ``coo_to_scv_tiles(cap=caps[-1])`` +
    :func:`plan_from_tiles_bucketed` layout arithmetic without building the
    plan: a logical tile with ``k`` entries chain-splits at the top cap —
    ``k // caps[-1]`` full chunks occupy top-cap slot rows and the
    remainder lands in the smallest cap holding it.  ``n_row_blocks``
    (when given) adds one ``caps[0]`` slot row per output block row as the
    first-segment coverage-dummy bound — an upper bound, since block rows
    already covered by a first-segment tile need no dummy.

    This is the number the byte model must price (``3 * slots * B`` for
    the rows/cols/vals triple), not logical nnz: BENCH_dist measured the
    nnz-priced model 1.11-3.79x optimistic against placed plans.
    """
    caps_arr = np.asarray(sorted(int(c) for c in caps), dtype=np.int64)
    if caps_arr.size == 0:
        raise ValueError("caps must be non-empty")
    counts_arr = np.asarray(counts, dtype=np.int64)
    counts_arr = counts_arr[counts_arr > 0]
    top = int(caps_arr[-1])
    slots = int(n_row_blocks) * int(caps_arr[0])
    if counts_arr.size == 0:
        return slots
    slots += int((counts_arr // top).sum()) * top
    rem = counts_arr % top
    rem = rem[rem > 0]
    if rem.size:
        slots += int(caps_arr[np.searchsorted(caps_arr, rem)].sum())
    return slots


def tile_nnz_histogram(a: COOMatrix, tile: int) -> np.ndarray:
    """Per-logical-tile entry counts — the input to ``bucket_caps_for``
    when deriving a ladder *before* tiles are built (chain-splitting at
    the ladder's largest cap needs the ladder first)."""
    T = int(tile)
    nbc = -(-a.shape[1] // T)
    key = (a.rows // T).astype(np.int64) * nbc + (a.cols // T)
    _, counts = np.unique(key, return_counts=True)
    return counts


# ---------------------------------------------------------------------------
# Logical SCV (paper Fig. 1(d))
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class SCVMatrix:
    blk_ptr: np.ndarray  # int32[n_vectors+1] — start of each vector in vals
    vec_row_blk: np.ndarray  # int32[n_vectors] — block-row of each vector
    vec_col: np.ndarray  # int32[n_vectors] — matrix column of each vector
    blk_id: np.ndarray  # int32[nnz] — row offset within vector (< B)
    vals: np.ndarray  # f32[nnz]
    vector_height: int  # B
    order: str  # ROW_MAJOR (SCV) or ZMORTON (SCV-Z)
    shape: tuple[int, int]

    @property
    def n_vectors(self) -> int:
        return int(self.vec_col.shape[0])

    @property
    def nnz(self) -> int:
        return int(self.vals.shape[0])

    @property
    def index_bits_per_entry(self) -> int:
        """log2(B) bits per entry — the storage advantage over COO's
        log2(N) (§III-A)."""
        return max(1, int(np.ceil(np.log2(self.vector_height))))

    def to_coo(self) -> COOMatrix:
        counts = np.diff(self.blk_ptr)
        vrow = np.repeat(self.vec_row_blk, counts).astype(np.int64)
        vcol = np.repeat(self.vec_col, counts).astype(np.int32)
        rows = (vrow * self.vector_height + self.blk_id).astype(np.int32)
        return COOMatrix(rows, vcol, self.vals.copy(), self.shape)


def coo_to_scv(
    a: COOMatrix,
    vector_height: int,
    order: str = ZMORTON,
) -> SCVMatrix:
    """Build SCV/SCV-Z from COO.

    Vectors (non-empty column strips of height B) are enumerated either
    row-major over the (block_row, column) grid — plain SCV, Fig. 2(d) —
    or along a Z-Morton curve over B x B vector *groups* with column order
    inside a group — SCV-Z, Fig. 2(e).
    """
    if order not in (ROW_MAJOR, ZMORTON):
        raise ValueError(f"unknown order {order!r}")
    B = int(vector_height)
    if B <= 0:
        raise ValueError("vector_height must be positive")
    m, n = a.shape

    row_blk = (a.rows // B).astype(np.int64)
    blk_id = (a.rows % B).astype(np.int64)
    col = a.cols.astype(np.int64)

    if order == ROW_MAJOR:
        # vectors ordered (block_row, col); entries within vector by row
        vkey = row_blk * n + col
        entry_key = vkey * B + blk_id
    else:
        # Z-curve over (block_row, col // B) groups, columns in order
        # inside a group, rows in order inside a vector.
        grp = morton.morton_encode(row_blk, col // B).astype(np.uint64)
        # combined key: (zcurve group, local col, local row)
        local_col = (col % B).astype(np.uint64)
        entry_key = (grp * np.uint64(B) + local_col) * np.uint64(B) + blk_id.astype(
            np.uint64
        )
        vkey = grp * np.uint64(B) + local_col  # unique per vector, curve order

    eorder = np.argsort(entry_key, kind="stable")
    vkey_s = np.asarray(vkey)[eorder]
    uniq, start = np.unique(vkey_s, return_index=True)
    counts = np.diff(np.append(start, len(vkey_s)))
    blk_ptr = np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)

    first = eorder[start]  # one representative entry per vector
    return SCVMatrix(
        blk_ptr=blk_ptr,
        vec_row_blk=row_blk[first].astype(np.int32),
        vec_col=col[first].astype(np.int32),
        blk_id=blk_id[eorder].astype(np.int32),
        vals=a.vals[eorder],
        vector_height=B,
        order=order,
        shape=a.shape,
    )


# ---------------------------------------------------------------------------
# Device tile layout for the Pallas kernel
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class SCVTiles:
    """Static-shape tiled SCV for `kernels/scv_spmm`.

    ``tile_row/tile_col`` give each tile's block coordinates (scalar-
    prefetched on TPU to steer the Z and PS BlockSpec index maps).  Entry
    arrays are padded to ``cap`` per tile; padding entries have val == 0 and
    row == col == 0 (they add zero — no masking needed in the kernel).
    Heavy tiles are split into chains of logical tiles sharing coordinates.

    Schedule invariant: tiles with equal ``tile_row`` are consecutive, and
    ``tile_row`` is non-decreasing **within each partition span** — the
    Pallas output window then moves monotonically and each PS strip is
    written back exactly once per span (paper's PS-reuse property).
    """

    tile_row: np.ndarray  # int32[nt]
    tile_col: np.ndarray  # int32[nt]
    rows: np.ndarray  # int32[nt, cap] — local row within tile
    cols: np.ndarray  # int32[nt, cap] — local col within tile
    vals: np.ndarray  # f32[nt, cap]
    nnz_in_tile: np.ndarray  # int32[nt]
    tile: int  # T (== SCV vector height == vector-group side)
    cap: int
    shape: tuple[int, int]  # original (unpadded) matrix shape
    order: str
    perm: Optional[np.ndarray] = None  # int64[nt, cap]: source COO entry of each slot (-1 pad)

    @property
    def n_tiles(self) -> int:
        return int(self.tile_row.shape[0])

    @property
    def nnz(self) -> int:
        return int(self.nnz_in_tile.sum())

    @property
    def padded_shape(self) -> tuple[int, int]:
        T = self.tile
        m, n = self.shape
        return (-(-m // T) * T, -(-n // T) * T)

    @property
    def padding_fraction(self) -> float:
        tot = self.n_tiles * self.cap
        return 1.0 - self.nnz / tot if tot else 0.0

    def to_coo(self) -> COOMatrix:
        T = self.tile
        rows = (
            self.tile_row[:, None].astype(np.int64) * T + self.rows
        ).ravel()
        cols = (
            self.tile_col[:, None].astype(np.int64) * T + self.cols
        ).ravel()
        vals = self.vals.ravel()
        keep = np.arange(self.cap)[None, :] < self.nnz_in_tile[:, None]
        keep = keep.ravel()
        return COOMatrix(
            rows[keep].astype(np.int32),
            cols[keep].astype(np.int32),
            vals[keep],
            self.shape,
        )


def _auto_cap(counts: np.ndarray, tile: int) -> int:
    """Pick the per-tile entry capacity minimizing padded slots.

    Splitting a tile with k entries under cap c costs ceil(k/c)*c slots; we
    scan caps (multiples of 8 — TPU sublane count) and take the argmin.
    """
    if len(counts) == 0:
        return 8
    cands = []
    hi = int(min(counts.max(), tile * tile))
    c = 8
    while c < hi * 2:
        cands.append(c)
        c *= 2
    cands.append(max(8, hi))
    best, best_slots = cands[0], None
    for c in cands:
        slots = int((-(-counts // c) * c).sum())
        if best_slots is None or slots < best_slots:
            best, best_slots = c, slots
    return int(best)


def _tile_sort(a: COOMatrix, tile: int, order: str):
    """Shared prologue of the tile builders: sort entries into SCV
    column-vector order within tiles and schedule the tiles.

    Returns ``(utrow, utcol, start, counts, sched, eorder, lrow_s, lcol_s,
    vals_s)`` — per-unique-tile coordinates / entry spans plus the sorted
    entry arrays.
    """
    T = int(tile)
    m, n = a.shape
    nbc = -(-n // T)
    trow = (a.rows // T).astype(np.int64)
    tcol = (a.cols // T).astype(np.int64)
    lrow = (a.rows % T).astype(np.int64)
    lcol = (a.cols % T).astype(np.int64)
    tkey = trow * nbc + tcol
    # SCV discipline within a tile: column-vector order (local col, row)
    eorder = np.argsort(tkey * (T * T) + lcol * T + lrow, kind="stable")
    tkey_s = tkey[eorder]
    # run-starts on the sorted keys (np.unique would sort a second time)
    if len(tkey_s):
        start = np.flatnonzero(np.r_[True, tkey_s[1:] != tkey_s[:-1]])
    else:
        start = np.zeros(0, np.int64)
    uniq = tkey_s[start]
    counts = np.diff(np.append(start, len(tkey_s))).astype(np.int64)
    utrow = (uniq // nbc).astype(np.int64)
    utcol = (uniq % nbc).astype(np.int64)

    # Tile schedule: group by block-row (consecutive PS windows); within a
    # block-row, Z order degenerates to ascending column — the cross-row
    # locality of the full 2-D curve is exploited at the *partition* level
    # (core/partition.py splits the true Z curve across devices).
    if order == ZMORTON:
        zkey = morton.morton_encode(utrow, utcol)
        sched = np.lexsort((zkey, utrow))
    elif order == ROW_MAJOR:
        sched = np.lexsort((utcol, utrow))
    else:
        raise ValueError(f"unknown order {order!r}")
    return utrow, utcol, start, counts, sched, eorder, lrow[eorder], lcol[eorder], a.vals[eorder]


def coo_to_scv_tiles(
    a: COOMatrix,
    tile: int,
    cap: Optional[int] = None,
    order: str = ZMORTON,
) -> SCVTiles:
    """COO -> device tile layout (see class docstring).

    Heavy tiles (more than ``cap`` entries) split into chains of logical
    tiles sharing coordinates.  Emission is vectorized numpy scatter: each
    output slot ``(chunk, s)`` with ``s < nnz_in_tile[chunk]`` pulls sorted
    entry ``start[tile(chunk)] + chunk_local * cap + s`` — no Python loop
    over tiles, so plan construction stays a few sorts + O(nnz) array ops
    at million-edge scale (``_coo_to_scv_tiles_loop`` keeps the scalar
    emitter as the equivalence/benchmark reference).
    """
    T = int(tile)
    utrow, utcol, start, counts, sched, eorder, lrow_s, lcol_s, vals_s = _tile_sort(
        a, T, order
    )
    if cap is None:
        cap = _auto_cap(counts, T)
    cap = int(cap)

    # chunks (logical output tiles) in schedule order
    nu = len(counts)
    n_chunks = (-(-counts // cap)).astype(np.int64)
    cc = n_chunks[sched]  # chunks per scheduled tile
    nt = int(cc.sum()) if len(cc) else 0
    chunk_tile = np.repeat(sched, cc)  # unique-tile index of each chunk
    first = np.cumsum(cc) - cc  # first chunk slot of each scheduled tile
    chunk_local = np.arange(nt, dtype=np.int64) - np.repeat(first, cc)

    tile_row = utrow[chunk_tile].astype(np.int32)
    tile_col = utcol[chunk_tile].astype(np.int32)
    nnz_out = np.minimum(
        cap, counts[chunk_tile] - chunk_local * cap
    ).astype(np.int32) if nt else np.zeros(0, np.int32)

    # per-entry destination slot: sorted entry j of tile t lands in chunk
    # ``chunk_first[t] + j // cap``, slot ``j % cap`` — an O(nnz) flat
    # scatter with no [nt, cap] index intermediates
    nnz = eorder.shape[0]
    rank = np.empty(nu, np.int64)
    rank[sched] = np.arange(nu, dtype=np.int64)
    chunk_first = first[rank]  # first output chunk of each unique tile
    inv = np.repeat(np.arange(nu, dtype=np.int64), counts)  # tile of entry
    pos = np.arange(nnz, dtype=np.int64) - np.repeat(start, counts)
    dst = (chunk_first[inv] + pos // cap) * cap + pos % cap
    rows_out = np.zeros(nt * cap, np.int32)
    cols_out = np.zeros(nt * cap, np.int32)
    vals_out = np.zeros(nt * cap, a.vals.dtype)
    perm_out = np.full(nt * cap, -1, np.int64)
    rows_out[dst] = lrow_s
    cols_out[dst] = lcol_s
    vals_out[dst] = vals_s
    perm_out[dst] = eorder
    rows_out = rows_out.reshape(nt, cap)
    cols_out = cols_out.reshape(nt, cap)
    vals_out = vals_out.reshape(nt, cap)
    perm_out = perm_out.reshape(nt, cap)
    return SCVTiles(
        tile_row=tile_row,
        tile_col=tile_col,
        rows=rows_out,
        cols=cols_out,
        vals=vals_out,
        nnz_in_tile=nnz_out,
        tile=T,
        cap=cap,
        shape=a.shape,
        order=order,
        perm=perm_out,
    )


def _coo_to_scv_tiles_loop(
    a: COOMatrix,
    tile: int,
    cap: Optional[int] = None,
    order: str = ZMORTON,
) -> SCVTiles:
    """Scalar per-tile emission loop — the pre-vectorization construction,
    kept as the byte-identical reference for tests and
    ``benchmarks/preprocess_bench.py``."""
    T = int(tile)
    utrow, utcol, start, counts, sched, eorder, lrow_s, lcol_s, vals_s = _tile_sort(
        a, T, order
    )
    if cap is None:
        cap = _auto_cap(counts, T)
    cap = int(cap)

    n_chunks = (-(-counts // cap)).astype(np.int64)
    nt = int(n_chunks.sum()) if len(n_chunks) else 0
    tile_row = np.zeros(nt, np.int32)
    tile_col = np.zeros(nt, np.int32)
    rows_out = np.zeros((nt, cap), np.int32)
    cols_out = np.zeros((nt, cap), np.int32)
    vals_out = np.zeros((nt, cap), a.vals.dtype)
    nnz_out = np.zeros(nt, np.int32)
    perm_out = np.full((nt, cap), -1, np.int64)

    out = 0
    for b in sched:
        s, k = int(start[b]), int(counts[b])
        for off in range(0, k, cap):
            take = min(cap, k - off)
            sl = slice(s + off, s + off + take)
            tile_row[out] = utrow[b]
            tile_col[out] = utcol[b]
            rows_out[out, :take] = lrow_s[sl]
            cols_out[out, :take] = lcol_s[sl]
            vals_out[out, :take] = vals_s[sl]
            perm_out[out, :take] = eorder[sl]
            nnz_out[out] = take
            out += 1
    assert out == nt
    return SCVTiles(
        tile_row=tile_row,
        tile_col=tile_col,
        rows=rows_out,
        cols=cols_out,
        vals=vals_out,
        nnz_in_tile=nnz_out,
        tile=T,
        cap=cap,
        shape=a.shape,
        order=order,
        perm=perm_out,
    )


def scv_to_tiles(a: SCVMatrix, cap: Optional[int] = None) -> SCVTiles:
    return coo_to_scv_tiles(a.to_coo(), a.vector_height, cap=cap, order=a.order)


# ---------------------------------------------------------------------------
# Executable plan pytree (device arrays + static aux; jit end-to-end)
# ---------------------------------------------------------------------------
@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class SCVPlan:
    """First-class jit-able SCV aggregation plan.

    Pytree contract (the whole point of this class):

    * **Leaves** — the device arrays ``tile_row``, ``tile_col``, ``rows``,
      ``cols``, ``vals``, ``nnz_in_tile``, ``perm``.  They trace through
      ``jax.jit`` / ``shard_map`` / ``jax.grad`` like any other argument.
      ``perm`` may be ``None`` (plans that never re-weight edges).
    * **Static aux data** — ``tile``, ``cap``, ``shape``, ``order``.  jit
      specializes on them (plus leaf shapes); two plans with equal aux and
      equal array shapes share one trace, which is what bounds recompiles
      to one per padding bucket.

    Unlike :class:`SCVTiles` (the host-side construction output), a plan
    always carries its coverage dummy tiles — one zero-nnz tile per
    otherwise-unvisited PS block-row, so the Pallas kernel defines the
    whole output — and its ``perm`` is padded to the covered tile count
    with ``-1`` ("no source entry"; consumers append a zero to the edge
    array so ``-1`` gathers it).
    """

    tile_row: Any  # i32[nt] (coverage dummies included)
    tile_col: Any  # i32[nt]
    rows: Any  # i32[nt, cap] local row within tile
    cols: Any  # i32[nt, cap] local col within tile
    vals: Any  # f32[nt, cap] (0 in padding slots)
    nnz_in_tile: Any  # i32[nt]
    perm: Any  # i32[nt, cap] source COO entry per slot (-1 pad), or None
    tile: int  # T — static
    cap: int  # static
    shape: tuple[int, int]  # original (unpadded) matrix shape — static
    order: str  # static

    def tree_flatten(self):
        return (
            (self.tile_row, self.tile_col, self.rows, self.cols, self.vals,
             self.nnz_in_tile, self.perm),
            (self.tile, self.cap, self.shape, self.order),
        )

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves, *aux)

    @property
    def n_tiles(self) -> int:
        return int(self.tile_row.shape[0])

    @property
    def padded_shape(self) -> tuple[int, int]:
        T = self.tile
        m, n = self.shape
        return (-(-m // T) * T, -(-n // T) * T)

    @property
    def n_row_blocks(self) -> int:
        return self.padded_shape[0] // self.tile

    def with_vals(self, vals) -> "SCVPlan":
        """Same plan, re-weighted entry values (GAT's per-edge attention)."""
        return dataclasses.replace(self, vals=vals)

    def reweighted(self, edge_vals) -> "SCVPlan":
        """Same plan, tile values re-gathered from a per-edge array through
        the ``perm`` leaf (GAT's attention weights).  Padding slots carry
        ``perm == -1`` and gather the appended zero."""
        if self.perm is None:
            raise ValueError(
                "per-edge re-weighting needs the plan's perm leaf; this plan "
                "was built without it (with_edges/with_perm disabled)"
            )
        import jax.numpy as jnp

        ev = jnp.concatenate([edge_vals, jnp.zeros((1,), edge_vals.dtype)])
        return self.with_vals(ev[self.perm].astype(self.vals.dtype))


def plan_from_tiles(
    t: SCVTiles, ensure_coverage: bool = True, with_perm: bool = True
) -> SCVPlan:
    """SCVTiles (host) -> SCVPlan (device pytree).

    The single code path for coverage-dummy insertion and perm padding:
    every consumer (single-graph ``build_graph``, the serving engine's
    composite assembly, ``scv_device_arrays``) builds plans here, so the
    "dummy rows carry perm == -1" invariant lives in exactly one place.
    """
    import jax.numpy as jnp

    tr, tc, rs, cs, vs, nz = (
        t.tile_row, t.tile_col, t.rows, t.cols, t.vals, t.nnz_in_tile,
    )
    if ensure_coverage:
        from repro.kernels.scv_spmm.ops import ensure_row_coverage

        tr, tc, rs, cs, vs, nz = ensure_row_coverage(
            tr, tc, rs, cs, vs, nz, t.padded_shape[0] // t.tile
        )
    pp = None
    if with_perm and t.perm is not None:
        if t.nnz >= 2**31:  # device perm is i32; refuse to wrap silently
            raise ValueError(
                f"entry count {t.nnz} overflows the int32 perm leaf"
            )
        pp = np.full((len(tr), t.cap), -1, np.int32)
        pp[: t.perm.shape[0]] = t.perm.astype(np.int32)
    with span("serve.plan.to_device"):
        return SCVPlan(
            tile_row=jnp.asarray(tr),
            tile_col=jnp.asarray(tc),
            rows=jnp.asarray(rs),
            cols=jnp.asarray(cs),
            vals=jnp.asarray(vs),
            nnz_in_tile=jnp.asarray(nz),
            perm=None if pp is None else jnp.asarray(pp),
            tile=t.tile,
            cap=t.cap,
            shape=t.shape,
            order=t.order,
        )


# ---------------------------------------------------------------------------
# nnz-bucketed capacity (DESIGN.md §2): per-bucket segments, per-segment cap
# ---------------------------------------------------------------------------
def bucket_tiles(t: SCVTiles, caps) -> tuple[SCVTiles, ...]:
    """Split tiles into capacity buckets: each tile goes to the smallest
    ``cap`` holding its nnz, and the entry arrays are truncated to that cap
    (entries are front-packed, so the truncation drops only structural
    padding).  One ``SCVTiles`` per cap, tiles in original schedule order —
    a subsequence of a block-row-grouped schedule keeps equal block-rows
    consecutive, so the kernel's PS-reuse invariant holds per bucket.
    """
    caps = tuple(sorted(int(c) for c in caps))
    if len(set(caps)) != len(caps) or not caps:
        raise ValueError(f"caps must be non-empty and distinct, got {caps}")
    nnz = t.nnz_in_tile.astype(np.int64)
    if len(nnz) and int(nnz.max()) > caps[-1]:
        raise ValueError(
            f"heaviest tile has {int(nnz.max())} entries > largest bucket "
            f"cap {caps[-1]}; build tiles with cap <= caps[-1] first"
        )
    which = np.searchsorted(caps, nnz)  # nnz == cap lands in that bucket

    def fit(a: np.ndarray, cap: int, fill) -> np.ndarray:
        """Truncate (or, for ladder caps above the build cap, pad) the
        entry axis to ``cap`` — truncation drops only structural padding
        because entries are front-packed."""
        if a.shape[1] >= cap:
            return a[:, :cap]
        out = np.full((a.shape[0], cap), fill, a.dtype)
        out[:, : a.shape[1]] = a
        return out

    def subset(mask: np.ndarray, cap: int) -> SCVTiles:
        return SCVTiles(
            tile_row=t.tile_row[mask],
            tile_col=t.tile_col[mask],
            rows=fit(t.rows[mask], cap, 0),
            cols=fit(t.cols[mask], cap, 0),
            vals=fit(t.vals[mask], cap, 0),
            nnz_in_tile=t.nnz_in_tile[mask],
            tile=t.tile,
            cap=cap,
            shape=t.shape,
            order=t.order,
            perm=fit(t.perm[mask], cap, -1) if t.perm is not None else None,
        )

    return tuple(subset(which == b, cap) for b, cap in enumerate(caps))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class SCVBucketedPlan:
    """Executable SCV plan split into capacity-bucket segments.

    Each segment is an :class:`SCVPlan` holding the tiles whose nnz fits
    its (static) cap — so one hub tile no longer inflates the padded entry
    arrays of every other tile the way a single global cap does.  The
    kernel runs one ``pallas_call`` per segment, chained through a single
    aliased accumulator (``ops.scv_spmm_plan``): the first launch
    zero-defines the whole output (coverage dummies live in the first
    segment only), later launches seed visited strips from the running
    accumulator and pass unvisited strips through.

    Pytree contract: the segment tuple is the only child (each segment is
    itself a pytree whose aux carries its cap), so jit specializes on the
    ladder ``caps`` + per-segment leaf shapes — the bucket layout is part
    of the trace signature exactly like a single plan's ``cap``.
    """

    segments: tuple[SCVPlan, ...]

    def __post_init__(self):
        if not self.segments:
            raise ValueError("SCVBucketedPlan needs at least one segment")
        caps = [s.cap for s in self.segments]
        if sorted(set(caps)) != caps:
            raise ValueError(f"segment caps must be ascending and distinct: {caps}")
        s0 = self.segments[0]
        for s in self.segments[1:]:
            if (s.tile, s.shape, s.order) != (s0.tile, s0.shape, s0.order):
                raise ValueError("segments disagree on tile/shape/order")

    def tree_flatten(self):
        return (tuple(self.segments), ())

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(tuple(children))

    # -- aux delegated to the segments (validated equal across them) -------
    @property
    def tile(self) -> int:
        return self.segments[0].tile

    @property
    def shape(self) -> tuple[int, int]:
        return self.segments[0].shape

    @property
    def order(self) -> str:
        return self.segments[0].order

    @property
    def caps(self) -> tuple[int, ...]:
        return tuple(s.cap for s in self.segments)

    @property
    def n_tiles(self) -> int:
        return sum(s.n_tiles for s in self.segments)

    @property
    def padded_shape(self) -> tuple[int, int]:
        return self.segments[0].padded_shape

    @property
    def n_row_blocks(self) -> int:
        return self.segments[0].n_row_blocks

    @property
    def perm(self):
        """Whether the plan supports per-edge re-weighting (all segments
        carry perm); exposed for feature tests, not for direct indexing."""
        perms = [s.perm for s in self.segments]
        return None if any(p is None for p in perms) else perms

    def reweighted(self, edge_vals) -> "SCVBucketedPlan":
        """Per-edge re-weighting, delegated to each segment (the segment
        perms all index the same global edge array)."""
        return SCVBucketedPlan(
            tuple(s.reweighted(edge_vals) for s in self.segments)
        )


def plan_from_tiles_bucketed(
    t: SCVTiles,
    caps=None,
    ensure_coverage: bool = True,
    with_perm: bool = True,
    config=None,
) -> SCVBucketedPlan:
    """SCVTiles (host) -> nnz-bucketed device plan.

    ``caps`` defaults to :func:`bucket_caps_for` over the tile nnz
    histogram; a ``repro.tune.TunedConfig`` may be passed as ``config``
    instead, in which case its ladder (or its single ``cap`` when the
    ladder is empty) supplies the caps.  Coverage dummies are emitted
    **once per plan**, in the first segment only (where zero nnz buckets
    them anyway — the smallest cap): the first kernel launch zero-defines
    the whole output and every later launch chains through it in
    accumulate mode (``ops.scv_spmm_plan``), so higher-cap segments never
    pay ``n_row_blocks * cap`` dummy slots again.
    """
    if config is not None:
        if caps is not None:
            raise ValueError("pass caps or config, not both")
        caps = tuple(config.bucket_caps) or (int(config.cap),)
    if caps is None:
        caps = bucket_caps_for(t.nnz_in_tile, t.tile)
    segs = bucket_tiles(t, caps)
    return SCVBucketedPlan(
        tuple(
            plan_from_tiles(
                s,
                ensure_coverage=(ensure_coverage and j == 0),
                with_perm=with_perm,
            )
            for j, s in enumerate(segs)
        )
    )


# ---------------------------------------------------------------------------
# Hybrid dense-tile split (beyond-paper; DESIGN.md §2)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class DenseTiles:
    """Logical tiles dense enough for the MXU (nnz > T^2 * VPU/MXU)."""

    tile_row: np.ndarray  # int32[nd]
    tile_col: np.ndarray  # int32[nd]
    blocks: np.ndarray  # f32[nd, T, T] densified
    tile: int
    shape: tuple[int, int]

    @property
    def n_tiles(self) -> int:
        return int(self.tile_row.shape[0])


def split_hybrid(
    tiles: SCVTiles, vpu_mxu_ratio: float = MXU_VPU_RATIO
) -> tuple[SCVTiles, DenseTiles]:
    """Partition logical tiles by density: tiles with
    nnz > T^2 * vpu_mxu_ratio run as dense T x T matmuls on the MXU
    (cheaper there than per-entry gather-FMA on the VPU); the ultra-sparse
    rest keeps the SCV gather path (``dense_tile_threshold`` is the same
    rule the Pallas kernel applies per tile in-kernel).  v5e: MXU 16384
    MAC/cyc vs VPU 1024 lane/cyc -> ratio 1/16."""
    T = tiles.tile
    key = tiles.tile_row.astype(np.int64) * (2**32) + tiles.tile_col
    uniq, inv = np.unique(key, return_inverse=True)
    tot = np.zeros(len(uniq), np.int64)
    np.add.at(tot, inv, tiles.nnz_in_tile.astype(np.int64))
    dense_logical = tot > (T * T) * vpu_mxu_ratio
    is_dense = dense_logical[inv]

    def subset(mask):
        return SCVTiles(
            tile_row=tiles.tile_row[mask],
            tile_col=tiles.tile_col[mask],
            rows=tiles.rows[mask],
            cols=tiles.cols[mask],
            vals=tiles.vals[mask],
            nnz_in_tile=tiles.nnz_in_tile[mask],
            tile=T,
            cap=tiles.cap,
            shape=tiles.shape,
            order=tiles.order,
            perm=tiles.perm[mask] if tiles.perm is not None else None,
        )

    sparse = subset(~is_dense)
    dpart = subset(is_dense)
    # densify the dense part (grouped by logical tile)
    dkey = dpart.tile_row.astype(np.int64) * (2**32) + dpart.tile_col
    duniq, dinv = np.unique(dkey, return_inverse=True)
    blocks = np.zeros((len(duniq), T, T), np.float32)
    slot = np.arange(dpart.cap)[None, :]
    keep = slot < dpart.nnz_in_tile[:, None]
    ti = np.repeat(dinv, dpart.cap)[keep.ravel()]
    np.add.at(
        blocks,
        (ti, dpart.rows[keep], dpart.cols[keep]),
        dpart.vals[keep],
    )
    dtiles = DenseTiles(
        tile_row=(duniq >> 32).astype(np.int32),
        tile_col=(duniq & 0xFFFFFFFF).astype(np.int32),
        blocks=blocks,
        tile=T,
        shape=tiles.shape,
    )
    return sparse, dtiles
