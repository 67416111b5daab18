"""Device milliseconds of the ``scv_spmm`` kernel per request in the
traced window."""


def read(run):
    t, done = run.trace, len(run.window.completed())
    if t is None or not done or not t.kernel_s.get("scv_spmm"):
        return None
    return t.kernel_s["scv_spmm"] / done * 1e3
