"""The program's own spans (``repro.spans``) that start inside the run's
window, on the host clock the window was measured on.  ``None`` where the
program records no spans, or where its ring dropped some that reach into
the window."""


def in_window(run):
    try:
        from repro import spans
    except ImportError:  # a program without the span log
        return None
    w = run.window
    return spans.window(round(w.t_open * 1e9), round(w.t_end * 1e9)) or None


def mean_ms(run, name: str):
    """Mean duration of the spans called ``name`` in the window."""
    recs = in_window(run) or []
    ns = [r.end_ns - r.start_ns for r in recs if r.name == name]
    return sum(ns) / len(ns) / 1e6 if ns else None
