"""The paper's CSR-style baseline: XLA's COO segment-sum over the same graph.

Timed in the traced runs of full-graph cells, after the window, and
printed beside the kernel's time per request; not a metric.
"""
from __future__ import annotations

import time

import numpy as np

from harness import kind_module, log

#: host-clock timings span at least this long, many calls together
MIN_TIMED_S = 0.5


def segment_sum_seconds(coo, width: int, seed: int) -> float:
    """Seconds of one ``out[r] += v * z[c]`` aggregation by
    ``jax.ops.segment_sum`` at ``width``, warm, on the default device."""
    import jax
    import jax.numpy as jnp

    rows, cols, vals = (jnp.asarray(a) for a in (coo.rows, coo.cols, coo.vals))
    z = jnp.asarray(np.random.default_rng(seed).standard_normal((coo.n, width), dtype=np.float32))

    @jax.jit
    def agg(z):
        return jax.ops.segment_sum(vals[:, None] * z[cols], rows, num_segments=coo.n)

    agg(z).block_until_ready()
    reps, t0 = 0, time.perf_counter()
    while time.perf_counter() - t0 < MIN_TIMED_S:
        out = agg(z)
        reps += 1
    out.block_until_ready()
    return (time.perf_counter() - t0) / reps


def report(work, reduced, n_requests: int) -> None:
    """Print the baseline's time per request (one segment-sum for each
    aggregation of the model's kind, at its width) beside the kernel's,
    and their ratio."""
    coo = work.source.coo
    model = work.config["model"]
    widths = [f for f, _, _ in kind_module(model).aggregations(model, coo.n, coo.nnz)]
    base = sum(segment_sum_seconds(coo, f, work.seed) for f in widths)
    kernel = reduced.kernel_s.get("scv_spmm", 0.0) / max(n_requests, 1)
    log(f"baseline: XLA COO segment-sum per request (F={'+'.join(map(str, widths))}) "
        f"{base * 1e3:.6f} ms; scv_spmm per request {kernel * 1e3:.6f} ms; "
        f"kernel / baseline {kernel / base if base else float('nan'):.3f}")
