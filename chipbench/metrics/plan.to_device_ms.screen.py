"""Host milliseconds of plan puts per member plan build: every
``serve.plan.to_device`` span in the window, whether under a member build
(``serve.plan.build``) or a composite assembly (``serve.plan.assemble``),
summed, over the number of member builds in the window.  So a program
that puts a composite once, and its members not at all, still reads its
puts, spread over the members."""
from metrics._spans import in_window


def read(run):
    recs = in_window(run)
    if not recs:
        return None
    builds = sum(r.name == "serve.plan.build" for r in recs)
    if not builds:
        return None
    ns = sum(r.end_ns - r.start_ns for r in recs if r.name == "serve.plan.to_device")
    return ns / builds / 1e6
