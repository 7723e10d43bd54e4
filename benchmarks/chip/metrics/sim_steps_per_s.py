"""Simulated steps whose records reached the host, over the wall time of
the whole window."""


def read(ctx):
    window = ctx["window"]
    if not window["steps"] or window["seconds"] <= 0:
        return None
    return window["steps"] / window["seconds"]
