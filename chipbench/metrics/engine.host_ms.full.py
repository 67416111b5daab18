"""Host milliseconds per full-graph request: the summed durations of the
program's top-level ``serve.*`` spans in the window (submit, wave
formation, plan resolution, features, dispatch, output fetch and split),
all but the wait on the device, over the requests completed in it."""
from metrics._spans import in_window


def read(run):
    recs, done = in_window(run), len(run.window.completed())
    if not recs or not done:
        return None
    ns = sum(r.end_ns - r.start_ns for r in recs
             if r.parent is None and r.name.startswith("serve.") and r.name != "serve.device_wait")
    return ns / done / 1e6 if ns else None
