"""Delta plan maintenance: incremental edge mutation for SCV plans.

SCV-GNN's advantage is a *preprocessed* plan — and its liability is that
any edge change used to throw the plan away (`coo_to_scv_tiles` is
O(nnz)).  This module makes mutation first-class: a :class:`DeltaBatch`
of edge inserts/removes and :func:`apply_delta`, which patches
`SCVTiles` / `SCVPlan` / `SCVBucketedPlan` / `Graph` by splicing only
the Z-Morton tiles the delta touches.

The contract that anchors correctness (tested in
``tests/test_plan_roundtrip.py`` / ``tests/test_stream.py``): the patched
object is **byte-identical** to a from-scratch rebuild on the final COO,

    apply_delta(build(adj), d)  ==  build(apply_coo(adj, d))

for every layer's builder, and passes the full ``core.validate``
invariant chain.  That works because the canonical final COO ordering is
chosen to minimize churn (**hole-filling**, see :class:`_IdPlan`):

* inserts take the removal holes in ascending hole order (a same-batch
  remove+insert of one coordinate — the value-update idiom — keeps its
  id exactly);
* leftover inserts append at the tail with fresh ids;
* leftover holes are back-filled by the *moved tail survivors* (the
  surviving entries past the new length, ascending) and the COO truncates.

So the only entries whose source id changes are the ≤ ``n_remove`` moved
tail survivors — a patch rewrites the tiles holding delta coordinates or
moved survivors and **no** O(nnz) pass over the perm arrays ever happens.
When removals outnumber inserts the moved survivors must be
located: ``apply_delta(..., source=<pre-delta COO>)`` finds their tiles by
coordinate arithmetic (the ``Graph`` layer uses its own edge arrays);
without a source the perm leaves are scanned once, blockwise.

Only tiles whose (block_row, block_col) key matches a delta coordinate
(or holds a moved survivor) are re-spliced.  When no
tile's chain length changes (splices absorbed by capacity slack) the
chunk layout — array shapes, tile coordinates, schedule — is preserved
exactly, so downstream jit traces keyed on leaf shapes survive.  For
bucketed plans, a tile is re-bucketed **only** when its new chunk nnz
crosses a `caps` ladder boundary; segments the delta never touches keep
their arrays by identity.  A patched plan or graph keeps its leaves where
the input's were: device arrays stay device arrays, and a host-stage plan
(numpy leaves, the serving engine's member plans) stays on the host.

Requirements on the input (raising ``ValueError`` otherwise):

* plans must carry the ``perm`` leaf (it *is* the source-id bookkeeping
  the splice maintains);
* bucketed plans must have been chain-split at ``caps[-1]`` (the
  ``build_graph(bucket_caps=...)`` path) — chunk chains are reassembled
  across segments under the rule "all chunks but the last are full";
* zero-nnz tiles must form a trailing coverage tail (true of every
  built plan; serving *composites* interleave padding tiles and are not
  patchable — patch the members, reassemble the composite).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import numpy as np

from repro.core.formats import COOMatrix
from repro.core.scv import SCVBucketedPlan, SCVPlan, SCVTiles


# ---------------------------------------------------------------------------
# the delta
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class DeltaBatch:
    """One batch of edge mutations: removes apply first, then inserts.

    "Remove then insert the same coordinate" is therefore the value-update
    idiom; a removal removes **every** stored entry at its coordinate
    (COO inputs with duplicate coordinates have them all matched).
    """

    ins_rows: np.ndarray  # int32[ki]
    ins_cols: np.ndarray  # int32[ki]
    ins_vals: np.ndarray  # f32[ki]
    rem_rows: np.ndarray  # int32[kr]
    rem_cols: np.ndarray  # int32[kr]

    @classmethod
    def of(cls, inserts=(), removes=()) -> "DeltaBatch":
        """Build from ``[(row, col, val), ...]`` / ``[(row, col), ...]``."""
        ins = list(inserts)
        rem = list(removes)
        return cls(
            ins_rows=np.array([e[0] for e in ins], np.int32),
            ins_cols=np.array([e[1] for e in ins], np.int32),
            ins_vals=np.array([e[2] for e in ins], np.float32),
            rem_rows=np.array([e[0] for e in rem], np.int32),
            rem_cols=np.array([e[1] for e in rem], np.int32),
        )

    @property
    def n_insert(self) -> int:
        return int(self.ins_rows.shape[0])

    @property
    def n_remove(self) -> int:
        return int(self.rem_rows.shape[0])

    def __len__(self) -> int:
        return self.n_insert + self.n_remove

    def signature(self) -> bytes:
        """Framed byte digest input for delta-chained cache keys
        (``serve.plan_cache.delta_key``): dtype + length framing per
        array, so byte-aliased deltas of different shapes never collide."""
        parts = [b"delta;"]
        for a in (self.ins_rows, self.ins_cols, self.ins_vals,
                  self.rem_rows, self.rem_cols):
            arr = np.ascontiguousarray(a)
            parts.append(f"{arr.dtype.str}:{arr.shape[0]};".encode())
            parts.append(arr.tobytes())
        return b"".join(parts)


# ---------------------------------------------------------------------------
# admission (mirrors core.validate.check_coo)
# ---------------------------------------------------------------------------
def check_delta(
    delta: DeltaBatch,
    shape: Optional[tuple[int, int]] = None,
    coo: Optional[COOMatrix] = None,
) -> None:
    """Reject malformed deltas with a clear ``ValueError``.

    Structural checks (always): 1-D arrays agreeing on length, in-range
    non-negative node ids when ``shape`` (or ``coo``) is given, finite
    insert values, no duplicate insert coordinates, no duplicate remove
    coordinates.  With ``coo`` given, presence is checked too: every
    remove must match a stored edge, and an insert of an already-present
    edge is rejected unless the same coordinate is also removed in this
    batch (the value-update idiom).  ``apply_delta`` re-checks presence
    locally either way, so plan-level callers may skip ``coo``.
    """
    for name in ("ins_rows", "ins_cols", "ins_vals", "rem_rows", "rem_cols"):
        a = getattr(delta, name)
        if np.ndim(a) != 1:
            raise ValueError(f"delta.{name} must be 1-D, got ndim={np.ndim(a)}")
    if not (delta.ins_rows.shape == delta.ins_cols.shape == delta.ins_vals.shape):
        raise ValueError(
            "delta insert arrays disagree on length: "
            f"rows={delta.ins_rows.shape[0]} cols={delta.ins_cols.shape[0]} "
            f"vals={delta.ins_vals.shape[0]}"
        )
    if delta.rem_rows.shape != delta.rem_cols.shape:
        raise ValueError(
            "delta remove arrays disagree on length: "
            f"rows={delta.rem_rows.shape[0]} cols={delta.rem_cols.shape[0]}"
        )
    if shape is None and coo is not None:
        shape = coo.shape
    if shape is not None:
        m, n = shape
        for what, rr, cc in (
            ("insert", delta.ins_rows, delta.ins_cols),
            ("remove", delta.rem_rows, delta.rem_cols),
        ):
            if len(rr) == 0:
                continue
            if int(rr.min()) < 0 or int(cc.min()) < 0:
                raise ValueError(f"delta {what} node ids must be non-negative")
            if int(rr.max()) >= m or int(cc.max()) >= n:
                raise ValueError(
                    f"delta {what} node ids out of range for shape {shape}: "
                    f"max row {int(rr.max())}, max col {int(cc.max())}"
                )
    if delta.n_insert and not np.all(np.isfinite(delta.ins_vals)):
        bad = np.flatnonzero(~np.isfinite(np.asarray(delta.ins_vals)))
        raise ValueError(
            f"delta insert values must be finite; {len(bad)} non-finite "
            f"(first at {int(bad[0])})"
        )
    # duplicate coordinates within each op list are always ambiguous
    span = max(int(shape[1]) if shape is not None else 0,
               _coord_span(delta))
    ikey = _keys(delta.ins_rows, delta.ins_cols, span)
    rkey = _keys(delta.rem_rows, delta.rem_cols, span)
    for what, k in (("insert", ikey), ("remove", rkey)):
        if len(k) != len(np.unique(k)):
            raise ValueError(
                f"duplicate {what} coordinates in delta (each edge may be "
                f"{what}d at most once per batch)"
            )
    if coo is not None:
        ckey = np.sort(_keys(coo.rows, coo.cols, span))
        missing = ~_present(ckey, rkey)
        if missing.any():
            i = int(np.flatnonzero(missing)[0])
            raise ValueError(
                f"delta removes absent edge ({int(delta.rem_rows[i])}, "
                f"{int(delta.rem_cols[i])}); removes must match stored edges"
            )
        clash = _present(ckey, ikey) & ~_present(np.sort(rkey), ikey)
        if clash.any():
            i = int(np.flatnonzero(clash)[0])
            raise ValueError(
                f"delta inserts already-present edge ({int(delta.ins_rows[i])},"
                f" {int(delta.ins_cols[i])}); remove it in the same batch to "
                "update its value"
            )


def _coord_span(delta: DeltaBatch) -> int:
    hi = 0
    for a in (delta.ins_cols, delta.rem_cols):
        if len(a):
            hi = max(hi, int(np.asarray(a).max()) + 1)
    return hi


def _keys(rows, cols, span: int) -> np.ndarray:
    return np.asarray(rows, np.int64) * max(span, 1) + np.asarray(cols, np.int64)


def _present(sorted_keys: np.ndarray, query: np.ndarray) -> np.ndarray:
    """Membership of ``query`` in ``sorted_keys`` (boolean per query)."""
    if len(sorted_keys) == 0 or len(query) == 0:
        return np.zeros(len(query), bool)
    idx = np.searchsorted(sorted_keys, query)
    idx = np.minimum(idx, len(sorted_keys) - 1)
    return sorted_keys[idx] == query


# ---------------------------------------------------------------------------
# the id plan: old entry position -> new entry position
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class _IdPlan:
    """Bookkeeping for one delta's canonical final-COO ordering.

    The ordering is **hole-filling**: inserts take the removal holes in
    ascending hole order, leftover inserts append at the tail, leftover
    holes are back-filled by the *moved tail survivors* (the surviving
    entries past the new length ``L``, ascending), and the array truncates
    to ``L``.  The payoff over naive compaction: every survivor below
    ``L`` — in a small delta, essentially all of them — keeps its id, so a
    plan patch rewrites only the tiles holding delta coordinates or moved
    survivors and never takes an O(nnz) pass over the perm arrays.
    """

    nnz_old: int
    ki: int  # insert count
    removed: np.ndarray  # sorted old positions removed
    L: int  # final entry count
    targets: np.ndarray  # ascending new positions receiving the fill queue
    tail_surv: np.ndarray  # ascending old positions of moved survivors

    # fill queue = [insert 0..ki-1] + [tail survivors ascending]; queue[j]
    # lands at targets[j], so insert j's id is targets[j] and moved
    # survivor i's new id is targets[ki + i].


def _id_plan(removed: np.ndarray, nnz_old: int, ki: int) -> _IdPlan:
    kr = len(removed)
    L = nnz_old - kr + ki
    holes_below = removed[removed < L]
    extra = np.arange(nnz_old, L, dtype=np.int64)  # empty unless ki > kr
    targets = np.concatenate([holes_below, extra])
    q = np.arange(max(L, 0), nnz_old, dtype=np.int64)
    tail_surv = q[~_present(removed, q)]
    return _IdPlan(nnz_old, ki, removed, L, targets, tail_surv)


def _map_ids(ids: np.ndarray, p: _IdPlan) -> np.ndarray:
    """New ids for surviving old ids (identity except moved survivors)."""
    if p.tail_surv.size == 0 or ids.size == 0:
        return ids
    idx = np.searchsorted(p.tail_surv, ids)
    idxc = np.minimum(idx, len(p.tail_surv) - 1)
    moved = p.tail_surv[idxc] == ids
    out = ids.copy()
    out[moved] = p.targets[p.ki + idxc[moved]]
    return out


def _fill_array(a: np.ndarray, ins, p: _IdPlan) -> np.ndarray:
    """Apply the id plan to a per-entry array: keep the sub-``L`` prefix,
    scatter the fill queue (inserts then moved survivors) into targets."""
    out = np.empty(p.L, a.dtype)
    c = min(p.L, p.nnz_old)
    out[:c] = a[:c]
    out[p.targets] = np.concatenate(
        [np.asarray(ins, a.dtype), a[p.tail_surv]]
    )
    return out


# ---------------------------------------------------------------------------
# COO reference semantics (the parity anchor)
# ---------------------------------------------------------------------------
def apply_coo(coo: COOMatrix, delta: DeltaBatch, check: bool = True) -> COOMatrix:
    """Canonical final COO under the hole-filling ordering (see
    :class:`_IdPlan`).  Every ``apply_delta`` overload byte-matches its
    layer's builder applied to this result."""
    if check:
        check_delta(delta, coo=coo)
    span = coo.shape[1]
    ekey = _keys(coo.rows, coo.cols, span)
    rkey = np.sort(_keys(delta.rem_rows, delta.rem_cols, span))
    removed = np.flatnonzero(_present(rkey, ekey)).astype(np.int64)
    p = _id_plan(removed, coo.nnz, delta.n_insert)
    return COOMatrix(
        rows=_fill_array(coo.rows, delta.ins_rows, p),
        cols=_fill_array(coo.cols, delta.ins_cols, p),
        vals=_fill_array(coo.vals, delta.ins_vals, p),
        shape=coo.shape,
    )


# ---------------------------------------------------------------------------
# splice core (shared by every plan layer)
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class _Entries:
    """Flat per-entry view of the affected tiles, in build order
    (ascending tile key; within a tile by (local col, local row, id))."""

    tk: np.ndarray  # int64 — tile key trow * nbc + tcol
    lrow: np.ndarray
    lcol: np.ndarray
    vals: np.ndarray
    ids: np.ndarray  # int64 source COO positions


def _delta_tile_keys(delta: DeltaBatch, T: int, nbc: int):
    itk = (delta.ins_rows.astype(np.int64) // T) * nbc + (
        delta.ins_cols.astype(np.int64) // T
    )
    rtk = (delta.rem_rows.astype(np.int64) // T) * nbc + (
        delta.rem_cols.astype(np.int64) // T
    )
    return itk, rtk, np.unique(np.concatenate([itk, rtk]))


def _find_removed(e: _Entries, delta: DeltaBatch, rtk, T: int) -> np.ndarray:
    """Sorted source ids of the entries the delta removes, from the
    gathered delta-tile entries; raises on removes that match nothing."""
    TT = T * T
    ekey = e.tk * TT + e.lcol * T + e.lrow  # globally non-decreasing
    rkey = rtk * TT + (delta.rem_cols.astype(np.int64) % T) * T + (
        delta.rem_rows.astype(np.int64) % T
    )
    hit = np.searchsorted(ekey, rkey, side="right") > np.searchsorted(
        ekey, rkey, side="left"
    )
    if not hit.all():
        i = int(np.flatnonzero(~hit)[0])
        raise ValueError(
            f"delta removes absent edge ({int(delta.rem_rows[i])}, "
            f"{int(delta.rem_cols[i])})"
        )
    return np.sort(e.ids[_present(np.sort(rkey), ekey)])


def _moved_tile_keys(
    p: _IdPlan, source, perm_views, T: int, nbc: int
) -> np.ndarray:
    """Tile keys holding the moved tail survivors.

    With ``source`` (the pre-delta COO edge arrays) this is pure
    coordinate arithmetic on ≤ ``n_remove`` positions.  Without it, the
    perm arrays are scanned blockwise (bounded scratch) for slots whose
    id is a moved survivor — ``perm_views`` is ``[(ck, perm_2d), ...]``.
    """
    if source is not None:
        rr = np.asarray(source.rows)[p.tail_surv].astype(np.int64)
        cc = np.asarray(source.cols)[p.tail_surv].astype(np.int64)
        return np.unique((rr // T) * nbc + (cc // T))
    vals = p.tail_surv  # sorted ascending
    keys = []
    block = 1 << 22
    for ck, perm in perm_views:
        flat = perm.reshape(-1)
        chunks = []
        for st in range(0, flat.size, block):
            blk = flat[st : st + block]
            idx = np.minimum(np.searchsorted(vals, blk), len(vals) - 1)
            hits = np.flatnonzero(vals[idx] == blk)
            if hits.size:
                chunks.append((hits + st) // perm.shape[1])
        if chunks:
            keys.append(ck[np.unique(np.concatenate(chunks))])
    return np.unique(np.concatenate(keys)) if keys else np.zeros(0, np.int64)


def _splice_entries(
    e: _Entries, delta: DeltaBatch, itk, p: _IdPlan, T: int
) -> _Entries:
    """Remove + insert + re-id + re-sort the affected tiles' entries.

    ``e`` must contain every entry the delta removes AND every moved tail
    survivor (their ids change).  Raises on inserts of a coordinate still
    present after the removes."""
    TT = T * T
    ekey = e.tk * TT + e.lcol * T + e.lrow  # globally non-decreasing
    removed = _present(p.removed, e.ids)

    skey = ekey[~removed]
    ikey = itk * TT + (delta.ins_cols.astype(np.int64) % T) * T + (
        delta.ins_rows.astype(np.int64) % T
    )
    clash = np.searchsorted(skey, ikey, side="right") > np.searchsorted(
        skey, ikey, side="left"
    )
    if clash.any():
        i = int(np.flatnonzero(clash)[0])
        raise ValueError(
            f"delta inserts already-present edge ({int(delta.ins_rows[i])}, "
            f"{int(delta.ins_cols[i])}); remove it in the same batch to "
            "update its value"
        )
    if len(ikey) != len(np.unique(ikey)):
        raise ValueError("duplicate insert coordinates in delta")

    # survivors keep their ids (moved tail survivors re-mapped), insert j
    # takes targets[j]; one lexsort restores the builder's (tile key,
    # local col, local row, source id) entry order
    tk = np.concatenate([e.tk[~removed], itk])
    lrow = np.concatenate(
        [e.lrow[~removed], (delta.ins_rows.astype(np.int64) % T).astype(e.lrow.dtype)]
    )
    lcol = np.concatenate(
        [e.lcol[~removed], (delta.ins_cols.astype(np.int64) % T).astype(e.lcol.dtype)]
    )
    vals = np.concatenate(
        [e.vals[~removed], delta.ins_vals.astype(e.vals.dtype)]
    )
    ids = np.concatenate(
        [_map_ids(e.ids[~removed], p), p.targets[: p.ki]]
    )
    o = np.lexsort((ids, lrow, lcol, tk))
    return _Entries(tk[o], lrow[o], lcol[o], vals[o], ids[o])


@dataclasses.dataclass
class _Chunks:
    """Chunked (padded [k, cap]) form of a set of entries — the builder's
    emission arithmetic applied to just the affected tiles."""

    ck: np.ndarray  # int64[k] tile key per chunk
    local: np.ndarray  # int64[k] chain index within tile
    nnz: np.ndarray  # int32[k]
    rows: np.ndarray  # [k, cap]
    cols: np.ndarray
    vals: np.ndarray
    perm: np.ndarray  # [k, cap] source ids, -1 pad

    def __len__(self) -> int:
        return len(self.ck)


def _chunk_entries(e: _Entries, cap: int, dtypes) -> _Chunks:
    """Re-emit entries as capacity-``cap`` chunks — identical arithmetic
    to ``coo_to_scv_tiles``: entry j of a tile lands in chain chunk
    ``j // cap``, slot ``j % cap``; zero / -1 padding."""
    rdt, cdt, vdt, ndt, pdt = dtypes
    ne = len(e.tk)
    if ne:
        tstart = np.flatnonzero(np.r_[True, e.tk[1:] != e.tk[:-1]])
    else:
        tstart = np.zeros(0, np.int64)
    utk = e.tk[tstart]
    tcounts = np.diff(np.append(tstart, ne)).astype(np.int64)
    n_ch = -(-tcounts // cap)
    k = int(n_ch.sum()) if len(n_ch) else 0
    first = np.cumsum(n_ch) - n_ch
    ck = np.repeat(utk, n_ch)
    local = np.arange(k, dtype=np.int64) - np.repeat(first, n_ch)
    nnz = (
        np.minimum(cap, np.repeat(tcounts, n_ch) - local * cap).astype(ndt)
        if k
        else np.zeros(0, ndt)
    )
    pos = np.arange(ne, dtype=np.int64) - np.repeat(tstart, tcounts)
    inv = np.repeat(np.arange(len(utk), dtype=np.int64), tcounts)
    dst = (first[inv] + pos // cap) * cap + pos % cap
    rows = np.zeros(k * cap, rdt)
    cols = np.zeros(k * cap, cdt)
    vals = np.zeros(k * cap, vdt)
    perm = np.full(k * cap, -1, pdt)
    rows[dst] = e.lrow
    cols[dst] = e.lcol
    vals[dst] = e.vals
    perm[dst] = e.ids
    return _Chunks(
        ck, local, nnz,
        rows.reshape(k, cap), cols.reshape(k, cap),
        vals.reshape(k, cap), perm.reshape(k, cap),
    )


def _chunk_locals(ck: np.ndarray) -> np.ndarray:
    """Chain index of each chunk within its (consecutive-equal-key) tile."""
    k = len(ck)
    if not k:
        return np.zeros(0, np.int64)
    run = np.flatnonzero(np.r_[True, ck[1:] != ck[:-1]])
    return np.arange(k, dtype=np.int64) - np.repeat(
        run, np.diff(np.append(run, k))
    )


def _affected_chunk_idx(ck: np.ndarray, aff_keys: np.ndarray) -> np.ndarray:
    """Indices of chunks whose tile key is in ``aff_keys`` (ck sorted)."""
    lo = np.searchsorted(ck, aff_keys, side="left")
    hi = np.searchsorted(ck, aff_keys, side="right")
    spans = [np.arange(a, b) for a, b in zip(lo, hi) if b > a]
    return np.concatenate(spans) if spans else np.zeros(0, np.int64)


def _gather_entries(ch_nnz, ch_ck, rows, cols, vals, perm, idx) -> _Entries:
    """Flatten the real slots of chunks ``idx`` in stored order."""
    a_nnz = ch_nnz[idx].astype(np.int64)
    keep = np.arange(rows.shape[1])[None, :] < a_nnz[:, None]
    return _Entries(
        tk=np.repeat(ch_ck[idx], a_nnz),
        lrow=rows[idx][keep].astype(np.int64),
        lcol=cols[idx][keep].astype(np.int64),
        vals=vals[idx][keep],
        ids=perm[idx][keep].astype(np.int64),
    )


def _merge_chunks(ck_u, local_u, ck_n, local_n) -> tuple[np.ndarray, np.ndarray]:
    """Output positions for the unaffected (u) and new (n) chunk lists
    under the global (tile key, chain index) schedule order.  Both inputs
    are sorted and share no tile key, so a two-way searchsorted merge is
    exact."""
    span = int(
        max(local_u.max() if len(local_u) else 0,
            local_n.max() if len(local_n) else 0)
    ) + 1
    ku = ck_u * span + local_u
    kn = ck_n * span + local_n
    pos_u = np.arange(len(ku), dtype=np.int64) + np.searchsorted(kn, ku)
    pos_n = np.arange(len(kn), dtype=np.int64) + np.searchsorted(ku, kn)
    return pos_u, pos_n


# ---------------------------------------------------------------------------
# SCVTiles patch
# ---------------------------------------------------------------------------
def _tiles_geometry(t) -> tuple[int, int, int]:
    T = int(t.tile)
    m, n = t.shape
    return T, -(-m // T), -(-n // T)  # T, n_block_rows, n_block_cols


def _apply_tiles(
    t: SCVTiles, delta: DeltaBatch, inplace: bool, source=None
) -> tuple[SCVTiles, _IdPlan]:
    if t.perm is None:
        raise ValueError(
            "apply_delta needs the perm bookkeeping; build tiles with "
            "coo_to_scv_tiles (perm enabled) first"
        )
    nnz = np.asarray(t.nnz_in_tile)
    if len(nnz) and int(nnz.min()) <= 0:
        raise ValueError(
            "apply_delta on SCVTiles requires build-form tiles (no zero-nnz "
            "tiles); patch plans, not composites, for coverage-dummy handling"
        )
    T, _, nbc = _tiles_geometry(t)
    cap = int(t.cap)
    ck = t.tile_row.astype(np.int64) * nbc + t.tile_col.astype(np.int64)
    if len(ck) > 1 and not np.all(ck[1:] >= ck[:-1]):
        raise ValueError("tiles are not in schedule (ascending tile key) order")
    itk, rtk, aff = _delta_tile_keys(delta, T, nbc)
    aff_idx = _affected_chunk_idx(ck, aff)
    n_entries = int(nnz.sum())

    e = _gather_entries(nnz, ck, t.rows, t.cols, t.vals, t.perm, aff_idx)
    p = _id_plan(_find_removed(e, delta, rtk, T), n_entries, delta.n_insert)
    if p.L >= 2**31:
        raise ValueError("patched entry count overflows int32 source ids")
    if p.tail_surv.size:
        # moved tail survivors change id: their tiles join the affected set
        moved = _moved_tile_keys(p, source, [(ck, t.perm)], T, nbc)
        aff = np.union1d(aff, moved)
        aff_idx = _affected_chunk_idx(ck, aff)
        e = _gather_entries(nnz, ck, t.rows, t.cols, t.vals, t.perm, aff_idx)
    merged = _splice_entries(e, delta, itk, p, T)
    new = _chunk_entries(
        merged, cap,
        (t.rows.dtype, t.cols.dtype, t.vals.dtype, nnz.dtype, t.perm.dtype),
    )

    local = _chunk_locals(ck)
    layout_equal = len(new) == len(aff_idx) and np.array_equal(
        new.ck, ck[aff_idx]
    ) and np.array_equal(new.local, local[aff_idx])

    if layout_equal:
        if inplace:
            tr, tc = t.tile_row, t.tile_col
            rows, cols, vals = t.rows, t.cols, t.vals
            nz, perm = t.nnz_in_tile, t.perm
        else:
            # functional: copy only the leaves the patch writes;
            # tile_row / tile_col are layout — unchanged here — and stay
            # shared by identity (same contract as untouched bucketed
            # segments).  Callers that hold aliases still see immutable
            # history; callers that own their tiles should pass
            # ``inplace=True`` — the zero-copy hot path.
            tr, tc = t.tile_row, t.tile_col
            rows, cols, vals = t.rows.copy(), t.cols.copy(), t.vals.copy()
            nz, perm = t.nnz_in_tile.copy(), t.perm.copy()
        rows[aff_idx] = new.rows
        cols[aff_idx] = new.cols
        vals[aff_idx] = new.vals
        nz[aff_idx] = new.nnz
        perm[aff_idx] = new.perm
        if inplace:
            return t, p
        return dataclasses.replace(
            t, tile_row=tr, tile_col=tc, rows=rows, cols=cols, vals=vals,
            nnz_in_tile=nz, perm=perm,
        ), p

    # chain lengths changed (tile birth/death or a crossed chunk boundary):
    # interleave the surviving chunks with the re-emitted ones
    un = np.ones(len(ck), bool)
    un[aff_idx] = False
    un_idx = np.flatnonzero(un)
    pos_u, pos_n = _merge_chunks(ck[un_idx], local[un_idx], new.ck, new.local)
    k2 = len(un_idx) + len(new)

    def out(shape, dtype, fill=0):
        return np.full(shape, fill, dtype) if fill else np.zeros(shape, dtype)

    tile_row = out(k2, t.tile_row.dtype)
    tile_col = out(k2, t.tile_col.dtype)
    rows = out((k2, cap), t.rows.dtype)
    cols = out((k2, cap), t.cols.dtype)
    vals = out((k2, cap), t.vals.dtype)
    nz = out(k2, nnz.dtype)
    perm = out((k2, cap), t.perm.dtype, fill=-1)
    tile_row[pos_u] = t.tile_row[un_idx]
    tile_col[pos_u] = t.tile_col[un_idx]
    rows[pos_u] = t.rows[un_idx]
    cols[pos_u] = t.cols[un_idx]
    vals[pos_u] = t.vals[un_idx]
    nz[pos_u] = nnz[un_idx]
    perm[pos_u] = t.perm[un_idx]  # survivors outside affected tiles keep ids
    tile_row[pos_n] = (new.ck // nbc).astype(t.tile_row.dtype)
    tile_col[pos_n] = (new.ck % nbc).astype(t.tile_col.dtype)
    rows[pos_n] = new.rows
    cols[pos_n] = new.cols
    vals[pos_n] = new.vals
    nz[pos_n] = new.nnz
    perm[pos_n] = new.perm
    return dataclasses.replace(
        t, tile_row=tile_row, tile_col=tile_col, rows=rows, cols=cols,
        vals=vals, nnz_in_tile=nz, perm=perm,
    ), p


# ---------------------------------------------------------------------------
# SCVPlan patch (coverage-dummy tail maintained)
# ---------------------------------------------------------------------------
def _real_prefix(nnz: np.ndarray) -> int:
    """Length of the real-tile prefix; built plans keep every zero-nnz
    coverage dummy in one trailing tail."""
    nt_real = int(np.count_nonzero(nnz))
    if nt_real and int(nnz[:nt_real].min()) <= 0:
        raise ValueError(
            "apply_delta needs a built plan (zero-nnz tiles must form a "
            "trailing coverage tail); serving composites are not patchable "
            "— patch the member plans and reassemble"
        )
    return nt_real

def _coverage_tail(tile_row_real: np.ndarray, nbr: int) -> np.ndarray:
    """Block-rows needing a coverage dummy, ascending — matching
    ``ensure_row_coverage``'s append order."""
    counts = np.bincount(tile_row_real.astype(np.int64), minlength=nbr)
    return np.flatnonzero(counts[:nbr] == 0)


def _apply_plan_arrays(
    tile_row, tile_col, rows, cols, vals, nnz, perm,
    T: int, cap: int, shape, order: str, delta: DeltaBatch, source=None,
):
    """Patch one plan's host arrays (dummy tail maintained).  Returns the
    new arrays plus the delta's :class:`_IdPlan`."""
    m, n = shape
    nbr = -(-m // T)
    nt_real = _real_prefix(nnz)
    view = SCVTiles(
        tile_row=tile_row[:nt_real], tile_col=tile_col[:nt_real],
        rows=rows[:nt_real], cols=cols[:nt_real], vals=vals[:nt_real],
        nnz_in_tile=nnz[:nt_real], tile=T, cap=cap, shape=tuple(shape),
        order=order, perm=perm[:nt_real],
    )
    patched, idp = _apply_tiles(view, delta, inplace=False, source=source)

    missing = _coverage_tail(patched.tile_row, nbr)
    kd = len(missing)
    return (
        np.concatenate([patched.tile_row, missing.astype(tile_row.dtype)]),
        np.concatenate([patched.tile_col, np.zeros(kd, tile_col.dtype)]),
        np.concatenate([patched.rows, np.zeros((kd, cap), rows.dtype)]),
        np.concatenate([patched.cols, np.zeros((kd, cap), cols.dtype)]),
        np.concatenate([patched.vals, np.zeros((kd, cap), vals.dtype)]),
        np.concatenate([patched.nnz_in_tile, np.zeros(kd, nnz.dtype)]),
        np.concatenate([patched.perm, np.full((kd, cap), -1, perm.dtype)]),
        idp,
    )


def _place_like(ref, arrays) -> list:
    """``arrays`` where ``ref`` lives: as they are when ``ref`` is a numpy
    (host-stage) leaf, else put to the device."""
    if isinstance(ref, np.ndarray):
        return list(arrays)
    import jax.numpy as jnp

    return [jnp.asarray(a) for a in arrays]


def _with_leaves(p: SCVPlan, leaves) -> SCVPlan:
    """``p`` with new array leaves (``tree_flatten`` order), placed like
    ``p``'s own."""
    return SCVPlan.tree_unflatten(
        p.tree_flatten()[1], _place_like(p.tile_row, leaves)
    )


def _apply_plan(
    p: SCVPlan, delta: DeltaBatch, source=None
) -> tuple[SCVPlan, _IdPlan]:
    if p.perm is None:
        raise ValueError(
            "apply_delta needs the plan's perm leaf; this plan was built "
            "without it (with_perm disabled)"
        )
    tr, tc, rs, cs, vs, nz, pm = (
        np.asarray(p.tile_row), np.asarray(p.tile_col), np.asarray(p.rows),
        np.asarray(p.cols), np.asarray(p.vals), np.asarray(p.nnz_in_tile),
        np.asarray(p.perm),
    )
    tr2, tc2, rs2, cs2, vs2, nz2, pm2, idp = _apply_plan_arrays(
        tr, tc, rs, cs, vs, nz, pm, p.tile, p.cap, p.shape, p.order, delta,
        source=source,
    )
    return (
        _with_leaves(p, (tr2, tc2, rs2, cs2, vs2, nz2, pm2)),
        idp,
    )


# ---------------------------------------------------------------------------
# SCVBucketedPlan patch (ladder-crossing re-bucket only)
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class _SegView:
    """Host snapshot of one segment's real (non-dummy) chunks."""

    tile_row: np.ndarray
    tile_col: np.ndarray
    rows: np.ndarray
    cols: np.ndarray
    vals: np.ndarray
    nnz: np.ndarray
    perm: np.ndarray
    ck: np.ndarray  # int64 tile keys, ascending
    aff_idx: np.ndarray  # chunk indices belonging to affected tiles


def _seg_view(s: SCVPlan, nbc: int, aff_keys: np.ndarray) -> _SegView:
    if s.perm is None:
        raise ValueError(
            "apply_delta needs the plan's perm leaf; this plan was built "
            "without it (with_perm disabled)"
        )
    nz = np.asarray(s.nnz_in_tile)
    k = _real_prefix(nz)
    tr = np.asarray(s.tile_row)[:k]
    tc = np.asarray(s.tile_col)[:k]
    ck = tr.astype(np.int64) * nbc + tc.astype(np.int64)
    if len(ck) > 1 and not np.all(ck[1:] >= ck[:-1]):
        raise ValueError(
            "segment tiles are not in schedule (ascending tile key) order"
        )
    return _SegView(
        tile_row=tr, tile_col=tc,
        rows=np.asarray(s.rows)[:k], cols=np.asarray(s.cols)[:k],
        vals=np.asarray(s.vals)[:k], nnz=nz[:k],
        perm=np.asarray(s.perm)[:k], ck=ck,
        aff_idx=_affected_chunk_idx(ck, aff_keys),
    )


def _check_chain_split(views: list[_SegView], cap_build: int) -> None:
    """Affected tiles must obey the chain contract reconstruction relies
    on: at most one chunk per tile below the build capacity, and it is
    the chain's last.  (Plans built via ``build_graph(bucket_caps=...)``
    — chain-split at ``caps[-1]`` — always satisfy this.)

    Chunks are ordered descending-cap exactly as ``_gather_bucketed``
    reconstructs chains: full chunks live in the top segment, the partial
    tail wherever its nnz bucketed it — ascending order would misread a
    low-bucketed tail as a mid-chain partial chunk."""
    ckc = np.concatenate([v.ck[v.aff_idx] for v in reversed(views)])
    nzc = np.concatenate([v.nnz[v.aff_idx] for v in reversed(views)])
    if not len(ckc):
        return
    o = np.argsort(ckc, kind="stable")
    ckc, nzc = ckc[o], nzc[o]
    last = np.r_[ckc[1:] != ckc[:-1], True]
    bad = nzc[~last] != cap_build
    if bad.any():
        raise ValueError(
            "bucketed plan was not chain-split at caps[-1] "
            f"({cap_build}): an affected tile has a non-final chunk with "
            f"nnz={int(nzc[~last][bad][0])}; rebuild via "
            "build_graph(bucket_caps=...) before applying deltas"
        )


def _gather_bucketed(views: list[_SegView]) -> _Entries:
    """Reconstruct the affected tiles' entry chains: all full chunks live
    in the top segment in chain order, the (unique) partial chunk is the
    chain's last wherever its nnz bucketed it — so a descending-cap
    concatenation followed by a stable sort on tile key restores every
    chain exactly."""
    parts = [
        _gather_entries(v.nnz, v.ck, v.rows, v.cols, v.vals, v.perm, v.aff_idx)
        for v in reversed(views)
    ]
    e = _Entries(
        tk=np.concatenate([p.tk for p in parts]),
        lrow=np.concatenate([p.lrow for p in parts]),
        lcol=np.concatenate([p.lcol for p in parts]),
        vals=np.concatenate([p.vals for p in parts]),
        ids=np.concatenate([p.ids for p in parts]),
    )
    o = np.argsort(e.tk, kind="stable")
    return _Entries(e.tk[o], e.lrow[o], e.lcol[o], e.vals[o], e.ids[o])


def _apply_bucketed(
    bp: SCVBucketedPlan, delta: DeltaBatch, source=None
) -> tuple[SCVBucketedPlan, _IdPlan]:
    caps = bp.caps
    cap_build = caps[-1]
    T = bp.tile
    m, n = bp.shape
    nbr, nbc = -(-m // T), -(-n // T)
    itk, rtk, aff = _delta_tile_keys(delta, T, nbc)
    views = [_seg_view(s, nbc, aff) for s in bp.segments]
    _check_chain_split(views, cap_build)
    e = _gather_bucketed(views)

    n_entries = int(sum(int(v.nnz.sum()) for v in views))
    p = _id_plan(_find_removed(e, delta, rtk, T), n_entries, delta.n_insert)
    if p.L >= 2**31:
        raise ValueError("patched entry count overflows the int32 perm leaf")
    if p.tail_surv.size:
        # moved tail survivors change id: their tiles join the affected set
        moved = _moved_tile_keys(
            p, source, [(v.ck, v.perm) for v in views], T, nbc
        )
        aff = np.union1d(aff, moved)
        for v in views:
            v.aff_idx = _affected_chunk_idx(v.ck, aff)
        _check_chain_split(views, cap_build)
        e = _gather_bucketed(views)
    merged = _splice_entries(e, delta, itk, p, T)
    v0 = views[-1]
    newc = _chunk_entries(
        merged, cap_build,
        (v0.rows.dtype, v0.cols.dtype, v0.vals.dtype, v0.nnz.dtype,
         v0.perm.dtype),
    )
    bucket_of = np.searchsorted(caps, newc.nnz)  # nnz == cap -> that bucket

    out_segments: list[SCVPlan] = []
    for b, (s, v) in enumerate(zip(bp.segments, views)):
        cap_b = caps[b]
        sel = bucket_of == b
        if not sel.any() and not len(v.aff_idx):
            # the delta never touches this segment's chunk set: its device
            # arrays survive by identity (jit traces, sharded spans, cache
            # bytes all untouched) — the hole-filling ordering guarantees
            # every id outside the affected tiles is unchanged
            out_segments.append(s)
            continue
        un = np.ones(len(v.ck), bool)
        un[v.aff_idx] = False
        un_idx = np.flatnonzero(un)
        # affected tiles lose *all* their chunks in every segment, so the
        # surviving chunks keep complete chains and their within-segment
        # chain indices stay valid merge keys
        local_u = _chunk_locals(v.ck)[un_idx]
        ck_n = newc.ck[sel]
        local_n = _chunk_locals(ck_n)
        pos_u, pos_n = _merge_chunks(v.ck[un_idx], local_u, ck_n, local_n)
        k2 = len(un_idx) + int(sel.sum())

        tile_row = np.zeros(k2, v.tile_row.dtype)
        tile_col = np.zeros(k2, v.tile_col.dtype)
        rows = np.zeros((k2, cap_b), v.rows.dtype)
        cols = np.zeros((k2, cap_b), v.cols.dtype)
        vals = np.zeros((k2, cap_b), v.vals.dtype)
        nz = np.zeros(k2, v.nnz.dtype)
        perm = np.full((k2, cap_b), -1, v.perm.dtype)
        tile_row[pos_u] = v.tile_row[un_idx]
        tile_col[pos_u] = v.tile_col[un_idx]
        rows[pos_u] = v.rows[un_idx]
        cols[pos_u] = v.cols[un_idx]
        vals[pos_u] = v.vals[un_idx]
        nz[pos_u] = v.nnz[un_idx]
        perm[pos_u] = v.perm[un_idx]  # ids outside affected tiles unchanged
        # new chunks were emitted at cap_build; the segment stores the
        # front-packed prefix at its own cap (bucket_tiles' fit rule)
        tile_row[pos_n] = (ck_n // nbc).astype(v.tile_row.dtype)
        tile_col[pos_n] = (ck_n % nbc).astype(v.tile_col.dtype)
        rows[pos_n] = newc.rows[sel][:, :cap_b]
        cols[pos_n] = newc.cols[sel][:, :cap_b]
        vals[pos_n] = newc.vals[sel][:, :cap_b]
        nz[pos_n] = newc.nnz[sel]
        perm[pos_n] = newc.perm[sel][:, :cap_b]

        # coverage-free chaining: only segment 0 owes a coverage-dummy
        # tail (the builder emits coverage once per plan; later segments
        # chain through the aliased accumulator)
        missing = _coverage_tail(tile_row, nbr) if b == 0 \
            else np.zeros(0, np.int64)
        kd = len(missing)
        out_segments.append(_with_leaves(s, (
            np.concatenate([tile_row, missing.astype(tile_row.dtype)]),
            np.concatenate([tile_col, np.zeros(kd, tile_col.dtype)]),
            np.concatenate([rows, np.zeros((kd, cap_b), rows.dtype)]),
            np.concatenate([cols, np.zeros((kd, cap_b), cols.dtype)]),
            np.concatenate([vals, np.zeros((kd, cap_b), vals.dtype)]),
            np.concatenate([nz, np.zeros(kd, nz.dtype)]),
            np.concatenate([perm, np.full((kd, cap_b), -1, perm.dtype)]),
        )))
    return SCVBucketedPlan(tuple(out_segments)), p


# ---------------------------------------------------------------------------
# Graph patch (plan + COO edge arrays)
# ---------------------------------------------------------------------------
def _apply_graph(g, delta: DeltaBatch):
    # the Graph carries its own pre-delta edge arrays — use them as the
    # moved-survivor source so the plan patch never falls back to the
    # perm-scan
    source = g if g.rows is not None else None
    if isinstance(g.plan, SCVBucketedPlan):
        plan2, idp = _apply_bucketed(g.plan, delta, source=source)
    elif isinstance(g.plan, SCVPlan):
        plan2, idp = _apply_plan(g.plan, delta, source=source)
    else:
        raise TypeError(
            f"cannot patch a Graph holding {type(g.plan).__name__}; patch "
            "before device placement (re-shard the patched plan instead)"
        )
    rows = cols = vals = None
    if g.rows is not None:
        r = np.asarray(g.rows)
        c = np.asarray(g.cols)
        w = np.asarray(g.vals)
        rows, cols, vals = _place_like(g.rows, (
            _fill_array(r, delta.ins_rows, idp),
            _fill_array(c, delta.ins_cols, idp),
            _fill_array(w, delta.ins_vals, idp),
        ))
    return dataclasses.replace(g, plan=plan2, rows=rows, cols=cols, vals=vals)


# ---------------------------------------------------------------------------
# public entry point
# ---------------------------------------------------------------------------
def apply_delta(
    obj: Any,
    delta: DeltaBatch,
    *,
    inplace: bool = False,
    check: bool = True,
    source: Any = None,
):
    """Patch ``obj`` (SCVTiles / SCVPlan / SCVBucketedPlan / models.gnn
    Graph) with ``delta``; byte-identical to rebuilding the layer from
    ``apply_coo(coo, delta)``.

    ``inplace=True`` (SCVTiles only) mutates the arrays when the chunk
    layout is unchanged — the zero-allocation hot path for streams of
    slack-absorbed updates; a layout change (tile birth/death, chain
    growth) still returns a fresh object.  The functional default copies
    only the leaves the patch writes (layout leaves are shared by
    identity) — use it when other references to the tiles must keep
    seeing pre-delta bytes.  Plan layers always return new pytrees but
    reuse untouched device leaves (bucketed segments the delta never
    touches keep their arrays by identity).

    ``source`` (optional, anything with ``.rows`` / ``.cols`` — e.g. the
    pre-delta ``COOMatrix``) lets a net-shrinking delta locate the moved
    tail survivors by coordinate arithmetic instead of scanning the perm
    arrays.  Graphs use their own edge arrays and ignore it.
    """
    plan_shape = getattr(obj, "shape", None)
    if plan_shape is None and hasattr(obj, "plan"):  # models.gnn.Graph
        plan_shape = obj.plan.shape
    if check:
        check_delta(delta, shape=plan_shape)
    if len(delta) == 0:
        return obj
    if isinstance(obj, SCVTiles):
        return _apply_tiles(obj, delta, inplace=inplace, source=source)[0]
    if inplace:
        raise ValueError(
            "inplace patching is only supported for SCVTiles (device plan "
            "leaves are immutable)"
        )
    if isinstance(obj, SCVBucketedPlan):
        return _apply_bucketed(obj, delta, source=source)[0]
    if isinstance(obj, SCVPlan):
        return _apply_plan(obj, delta, source=source)[0]
    if hasattr(obj, "plan") and hasattr(obj, "n_nodes"):
        return _apply_graph(obj, delta)
    raise TypeError(f"apply_delta cannot patch {type(obj).__name__}")
