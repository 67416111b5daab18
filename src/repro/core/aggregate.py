"""Aggregation (Eq. (3): H' = Â · Z) with pluggable sparse backends.

This is the framework's first-class entry point for the paper's technique.
``aggregate(A, Z)`` dispatches on the format object:

* ``np.ndarray`` / ``jnp.ndarray``  — dense matmul (oracle / tiny graphs)
* ``CSRMatrix``                     — gather + segment_sum (row-major)
* ``CSCMatrix``                     — gather + scatter-add (column-major)
* ``BCSRMatrix``                    — dense-block einsum
* ``SCVMatrix``                     — logical SCV, executed via tiles
* ``SCVTiles``                      — TPU path: Pallas kernel (or the jnp
                                      reference on CPU / under tests)
* ``SCVPlan``                       — same TPU path, but the plan is a
                                      registered pytree: array leaves +
                                      static aux, so the call (and any
                                      caller up to the whole GNN forward)
                                      sits under a single ``jax.jit``
* ``SCVBucketedPlan``               — nnz-bucketed plan: one kernel launch
                                      per capacity segment, partial outputs
                                      summed (no global-max cap padding)

All backends are numerically equivalent (validated by property tests).
``aggregate_scv_plan`` is the jit-native entry point; the legacy
``aggregate_scv_tiles`` (host object + loose arrays dict) remains for
benchmarks and one-shot experiments and routes through the same kernels.
"""
from __future__ import annotations

import functools
from typing import Any, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.formats import BCSRMatrix, COOMatrix, CSCMatrix, CSRMatrix
from repro.core.scv import (
    SCVBucketedPlan,
    SCVMatrix,
    SCVPlan,
    SCVTiles,
    plan_from_tiles,
    scv_to_tiles,
)


# ---------------------------------------------------------------------------
# device-array bundles (jit-friendly)
# ---------------------------------------------------------------------------
def csr_device_arrays(a: CSRMatrix) -> dict[str, jnp.ndarray]:
    rows = np.repeat(np.arange(a.shape[0], dtype=np.int32), np.diff(a.row_ptr))
    return {
        "rows": jnp.asarray(rows),
        "cols": jnp.asarray(a.col_id),
        "vals": jnp.asarray(a.vals),
    }


def scv_device_arrays(t: SCVTiles, ensure_coverage: bool = True) -> dict[str, jnp.ndarray]:
    """Device bundle; with ``ensure_coverage`` a zero-nnz dummy tile is
    appended for every empty PS block-row so the Pallas kernel defines the
    whole output.  Thin dict view over :func:`plan_from_tiles` — the one
    code path for coverage insertion and perm padding."""
    p = plan_from_tiles(t, ensure_coverage=ensure_coverage, with_perm=False)
    return {
        "tile_row": p.tile_row,
        "tile_col": p.tile_col,
        "rows": p.rows,
        "cols": p.cols,
        "vals": p.vals,
        "nnz_in_tile": p.nnz_in_tile,
    }


# ---------------------------------------------------------------------------
# backends
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("n_rows",))
def aggregate_coo_segsum(
    rows: jnp.ndarray, cols: jnp.ndarray, vals: jnp.ndarray, z: jnp.ndarray, n_rows: int
) -> jnp.ndarray:
    """Row-major (CSR-style) aggregation: gather Z rows, weighted
    segment-sum into output rows.  XLA's bread-and-butter SpMM."""
    gathered = z[cols] * vals[:, None].astype(z.dtype)
    return jax.ops.segment_sum(gathered, rows, num_segments=n_rows)


@functools.partial(jax.jit, static_argnames=("n_rows",))
def aggregate_coo_scatter(
    rows: jnp.ndarray, cols: jnp.ndarray, vals: jnp.ndarray, z: jnp.ndarray, n_rows: int
) -> jnp.ndarray:
    """Column-major (CSC-style) aggregation: scatter-add partial sums."""
    out = jnp.zeros((n_rows, z.shape[1]), z.dtype)
    return out.at[rows].add(z[cols] * vals[:, None].astype(z.dtype))


def aggregate_dense(a: jnp.ndarray, z: jnp.ndarray) -> jnp.ndarray:
    return jnp.asarray(a, z.dtype) @ z


def aggregate_bcsr(a: BCSRMatrix, z: jnp.ndarray) -> jnp.ndarray:
    """Dense-block path: every stored block does a full B x B @ B x F —
    BCSR's storage liability becomes a compute liability (paper §II-B.3)."""
    B = a.block_size
    m, n = a.shape
    mp = -(-m // B) * B
    np_ = -(-n // B) * B
    zp = jnp.zeros((np_, z.shape[1]), z.dtype).at[: z.shape[0]].set(z)
    ztiles = zp.reshape(np_ // B, B, z.shape[1])
    blk_rows = np.repeat(
        np.arange(len(a.row_ptr) - 1, dtype=np.int32), np.diff(a.row_ptr)
    )
    prod = jnp.einsum(
        "kij,kjf->kif", jnp.asarray(a.blocks, z.dtype), ztiles[jnp.asarray(a.col_id)]
    )
    out = jax.ops.segment_sum(prod, jnp.asarray(blk_rows), num_segments=mp // B)
    return out.reshape(mp, z.shape[1])[:m]


def aggregate_scv_tiles(
    t: SCVTiles,
    z: jnp.ndarray,
    *,
    backend: str = "auto",
    feature_block: int = 128,
    arrays: dict[str, jnp.ndarray] | None = None,
) -> jnp.ndarray:
    """SCV aggregation over the device tile layout.

    backend:
      * "jnp"              — vectorized jnp reference (runs anywhere, oracle)
      * "pallas"           — the compiled TPU kernel; never interpreted, so
                             lowering fails loudly off a TPU
      * "pallas_interpret" — the same kernel in Pallas interpret mode (CPU
                             tests)
      * "auto"             — pallas on TPU, jnp elsewhere
    """
    from repro.kernels.scv_spmm import ops as scv_ops  # local import: keep core light
    from repro.kernels.scv_spmm import ref as scv_ref

    arr = arrays if arrays is not None else scv_device_arrays(t)
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "jnp"
    if backend == "jnp":
        out = scv_ref.scv_spmm_reference(
            arr["tile_row"], arr["tile_col"], arr["rows"], arr["cols"], arr["vals"],
            z, tile=t.tile, n_rows=t.padded_shape[0],
            nnz_in_tile=arr.get("nnz_in_tile"),
        )
    elif backend in ("pallas", "pallas_interpret"):
        out = scv_ops.scv_spmm(
            arr["tile_row"], arr["tile_col"], arr["rows"], arr["cols"], arr["vals"],
            z, tile=t.tile, n_rows=t.padded_shape[0],
            nnz_in_tile=arr.get("nnz_in_tile"),
            feature_block=feature_block,
            interpret=(backend == "pallas_interpret"),
        )
    else:
        raise ValueError(f"unknown backend {backend!r}")
    return out[: t.shape[0]]


def aggregate_scv_plan(
    p: "Union[SCVPlan, SCVBucketedPlan, ShardedPlan]",
    z: jnp.ndarray,
    *,
    backend: str = "auto",
    feature_block: int = 128,
) -> jnp.ndarray:
    """SCV aggregation over a plan pytree — the jit-native path.

    Accepts the single-cap :class:`SCVPlan`, the nnz-bucketed
    :class:`SCVBucketedPlan` (one kernel launch per capacity segment,
    partial outputs summed), and the mesh-placed
    :class:`repro.core.exec.ShardedPlan` (the executor's shard_map
    launch — one boundary ``psum``, feature slabs collective-free).
    Every array the computation reads is a pytree leaf of ``p`` and every
    piece of static configuration (tile, padded row count, bucket ladder,
    placement mesh + decision, backend selection) comes from the plan's
    aux data, so this function — and any caller threading plans around,
    up to ``models.gnn.gnn_forward`` — can sit under one outer
    ``jax.jit`` with zero host round-trips per layer.
    """
    from repro.kernels.scv_spmm import ops as scv_ops  # local import: keep core light
    from repro.kernels.scv_spmm import ref as scv_ref

    from repro.core.exec import ShardedPlan, aggregate_sharded

    if isinstance(p, ShardedPlan):
        return aggregate_sharded(
            p, z, backend=backend, feature_block=feature_block
        )
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "jnp"
    if backend == "jnp":
        out = scv_ref.scv_spmm_reference_plan(p, z)
    elif backend in ("pallas", "pallas_interpret"):
        out = scv_ops.scv_spmm_plan(
            p, z, feature_block=feature_block,
            interpret=(backend == "pallas_interpret"),
        )
    else:
        raise ValueError(f"unknown backend {backend!r}")
    return out[: p.shape[0]]


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------
Format = Union[np.ndarray, jnp.ndarray, COOMatrix, CSRMatrix, CSCMatrix, BCSRMatrix, SCVMatrix, SCVTiles, SCVPlan, SCVBucketedPlan, "ShardedPlan"]


def aggregate(a: Format, z: jnp.ndarray, **kw: Any) -> jnp.ndarray:
    """H' = Â Z for any supported adjacency format."""
    n_rows = a.shape[0]
    if isinstance(a, (np.ndarray, jnp.ndarray)):
        return aggregate_dense(a, z)
    if isinstance(a, COOMatrix):
        return aggregate_coo_segsum(
            jnp.asarray(a.rows), jnp.asarray(a.cols), jnp.asarray(a.vals), z, n_rows
        )
    if isinstance(a, CSRMatrix):
        d = csr_device_arrays(a)
        return aggregate_coo_segsum(d["rows"], d["cols"], d["vals"], z, n_rows)
    if isinstance(a, CSCMatrix):
        cols = np.repeat(np.arange(a.shape[1], dtype=np.int32), np.diff(a.col_ptr))
        return aggregate_coo_scatter(
            jnp.asarray(a.row_id), jnp.asarray(cols), jnp.asarray(a.vals), z, n_rows
        )
    if isinstance(a, BCSRMatrix):
        return aggregate_bcsr(a, z)
    if isinstance(a, SCVMatrix):
        return aggregate_scv_tiles(scv_to_tiles(a), z, **kw)
    if isinstance(a, SCVTiles):
        return aggregate_scv_tiles(a, z, **kw)
    from repro.core.exec import ShardedPlan

    if isinstance(a, (SCVPlan, SCVBucketedPlan, ShardedPlan)):
        return aggregate_scv_plan(a, z, **kw)
    raise TypeError(f"unsupported adjacency format: {type(a)}")


def aggregate_hybrid(
    t: SCVTiles, z: jnp.ndarray, *, backend: str = "jnp", **kw
) -> jnp.ndarray:
    """Beyond-paper hybrid: MXU-densified tiles + SCV gather tiles
    (DESIGN.md §2)."""
    from repro.core.scv import split_hybrid

    sparse, dense = split_hybrid(t)
    out = aggregate_scv_tiles(sparse, z, backend=backend, **kw)
    if dense.n_tiles:
        T = dense.tile
        np_cols = -(-t.shape[1] // T) * T
        zp = jnp.zeros((np_cols, z.shape[1]), z.dtype).at[: z.shape[0]].set(z)
        ztiles = zp.reshape(np_cols // T, T, z.shape[1])
        prod = jnp.einsum(
            "kij,kjf->kif",
            jnp.asarray(dense.blocks, z.dtype),
            ztiles[jnp.asarray(dense.tile_col)],
        ).astype(jnp.float32)
        upd = jax.ops.segment_sum(
            prod, jnp.asarray(dense.tile_row), num_segments=t.padded_shape[0] // T
        )
        out = out + upd.reshape(-1, z.shape[1])[: out.shape[0]]
    return out
