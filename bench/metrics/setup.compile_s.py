"""Seconds of set-up spent lowering and compiling the cell's one step
shape, timed apart from its first execution: the benchmark's own host
span ``setup.compile``.  A warm compile cache shortens it."""


def read(ctx):
    return ctx["spans"].get("setup.compile")
