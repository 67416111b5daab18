"""Graphs completed in the window over the window's seconds."""


def read(run):
    done = len(run.window.completed())
    return done / run.window.seconds if done else None
