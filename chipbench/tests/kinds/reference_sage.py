"""The plain reference of the tests' ``sage`` kind: a GraphSAGE forward in
float64 numpy, ``h_{l+1} = h_l W_self + A_hat (h_l W_neigh)``, ReLU
between layers, the last layer linear.  The combinations' operands and
the aggregation's input and output are rounded to the stated precision,
as in ``reference.py`` (GCN), whose adjacency, gap and controls it
shares."""
from __future__ import annotations

import numpy as np

from reference import adjacency, controls, gap, rounded  # noqa: F401  (read by the harness)


def forward(weights: list, adj, x: np.ndarray, precision: dict) -> np.ndarray:
    comb, agg = precision["combination"], precision["aggregation"]
    h = np.asarray(x, np.float64)
    for i, (w_self, w_neigh) in enumerate(weights):
        hc = rounded(h, comb)
        z = hc @ rounded(w_neigh, comb)
        h = hc @ rounded(w_self, comb) + rounded(adj @ rounded(z, agg), agg)
        if i + 1 < len(weights):
            h = np.maximum(h, 0.0)
    return h
