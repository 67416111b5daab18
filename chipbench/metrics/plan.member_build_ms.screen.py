"""Mean host milliseconds of one member plan build (``serve.plan.build``:
``build_graph`` on a plan-cache miss) in the window."""
from metrics._spans import mean_ms


def read(run):
    return mean_ms(run, "serve.plan.build")
