"""Milliseconds per full-graph request, closed loop with one outstanding:
the window (to the completion that ends it) over the requests completed
in it."""


def read(run):
    done = len(run.window.completed())
    return run.window.seconds / done * 1e3 if done else None
