"""The ``scv_spmm`` kernel's share of its roofline: the least time the
chip needs for the aggregations of the requests completed in the traced
window (``cost.aggregation_least_time``, whatever implements them), over
the kernel's device time.  None where the trace holds no kernel time."""
import cost


def read(run):
    t = run.trace
    done = run.window.completed()
    if t is None or not done or not t.kernel_s.get("scv_spmm"):
        return None
    model = run.config["model"]
    least = sum(cost.aggregation_least_time(model, r.n_nodes, r.nnz, run.peak)[0] for r in done)
    return least / t.kernel_s["scv_spmm"] * 100.0
