"""Host milliseconds per member plan build spent putting its arrays on the
device: the ``serve.plan.to_device`` spans whose parent is a
``serve.plan.build``, summed, over the number of member builds in the
window."""
from metrics._spans import in_window


def read(run):
    recs = in_window(run)
    if not recs:
        return None
    builds = {r.sid for r in recs if r.name == "serve.plan.build"}
    if not builds:
        return None
    ns = sum(r.end_ns - r.start_ns for r in recs
             if r.name == "serve.plan.to_device" and r.parent in builds)
    return ns / len(builds) / 1e6
