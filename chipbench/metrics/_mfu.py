"""Model FLOP/s utilisation: the operations the forward needs for the
graphs completed in the window (``cost.model_flops`` of each), over the
window's seconds and the chip's bf16 peak."""
import cost


def read(run):
    w = run.window
    done = w.completed()
    if not done or run.trace is None:
        return None
    model = run.config["model"]
    flops = sum(cost.model_flops(model, r.n_nodes, r.nnz) for r in done)
    return flops / w.seconds / run.peak["bf16_flops_per_s"] * 100.0
