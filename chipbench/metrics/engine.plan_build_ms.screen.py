"""Host milliseconds of plan building per plan-cache miss in the window,
from the engine's counters (its host clock around each build)."""


def read(run):
    d = run.engine_delta
    misses = d["plan_cache_misses"]
    return d["plan_build_seconds"] / misses * 1e3 if misses else None
