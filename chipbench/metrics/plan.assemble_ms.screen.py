"""Mean host milliseconds of one composite assembly
(``serve.plan.assemble``, on a composite plan-cache miss) in the window."""
from metrics._spans import mean_ms


def read(run):
    return mean_ms(run, "serve.plan.assemble")
