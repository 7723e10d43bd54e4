"""Set-up: process start to the first timed call (device init, weights,
compile or cache load, warm-up)."""


def read(ctx):
    return ctx["setup_s"]
