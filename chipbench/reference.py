"""The plain reference: a GCN forward in float64 numpy.

``h_{l+1} = A_hat (h_l W_l)``, ReLU between layers, the last layer linear:
the equations of Kipf and Welling (2017) that ``models.gnn`` serves.  The
aggregation is a scipy CSR product over the COO entries.  Nothing of the
program is imported, and nothing it made is read.

Each configuration states the precision its arithmetic runs at, one
entry per stage (``combination`` for ``h @ W``, ``aggregation`` for
``A_hat Z``).  The reference rounds the operands of each stage to the
stated type and sums in float64, so a program that keeps to the stated
precision differs from it by float32 summation order and by rare
rounding-boundary flips of its own float32 intermediates.  ``controls``
gives the controls: the same forward with one stage a step below its
stated type.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np
import scipy.sparse as sp

#: the nearest precision below each stated one: float32 for float64,
#: bfloat16 for float32, an 8-bit float for bfloat16 or float16
LOWER = {
    "float64": "float32",
    "float32": "bfloat16",
    "bfloat16": "float8_e4m3fn",
    "float16": "float8_e4m3fn",
}


def rounded(a: np.ndarray, dtype: str) -> np.ndarray:
    """``a`` rounded to ``dtype`` (round to nearest even), as float64."""
    if dtype == "float64":
        return np.asarray(a, np.float64)
    t = np.dtype(getattr(ml_dtypes, dtype)) if hasattr(ml_dtypes, dtype) else np.dtype(dtype)
    return np.asarray(a).astype(t).astype(np.float64)


def controls(precision: dict) -> dict:
    """The controls: for each stage, the stated precision with that stage
    one step below.  Each has to fail the configuration's limit."""
    return {f"{stage}_{LOWER[dtype]}": {**precision, stage: LOWER[dtype]}
            for stage, dtype in precision.items()}


def adjacency(rows, cols, vals, n: int) -> sp.csr_matrix:
    return sp.csr_matrix(
        (np.asarray(vals, np.float64), (np.asarray(rows), np.asarray(cols))), shape=(n, n)
    )


def forward(weights: list, adj: sp.csr_matrix, x: np.ndarray, precision: dict) -> np.ndarray:
    """The GCN forward over ``adj`` at the stated ``precision``; float64."""
    comb, agg = precision["combination"], precision["aggregation"]
    a = adj if agg in ("float64", "float32") else _rounded_csr(adj, agg)
    h = np.asarray(x, np.float64)
    for i, w in enumerate(weights):
        z = rounded(h, comb) @ rounded(w, comb)
        h = rounded(a @ rounded(z, agg), agg)
        if i + 1 < len(weights):
            h = np.maximum(h, 0.0)
    return h


def _rounded_csr(adj: sp.csr_matrix, dtype: str) -> sp.csr_matrix:
    out = adj.copy()
    out.data = rounded(out.data, dtype)
    return out


def gap(out: np.ndarray, ref: np.ndarray) -> float:
    """Widest gap between an output and its reference, as a share of the
    reference's largest magnitude.  A shape mismatch or a non-finite
    output is an infinite gap."""
    out = np.asarray(out)
    if out.shape != ref.shape or not np.isfinite(out).all():
        return float("inf")
    scale = float(np.abs(ref).max()) or 1.0
    return float(np.abs(out.astype(np.float64) - ref).max()) / scale
