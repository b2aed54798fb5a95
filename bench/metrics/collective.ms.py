"""Milliseconds a step of collective operations on the slowest chip of
the traced window (``bench/trace.py``: ``Summary.collective_s``, the
summed durations of the ops it counts as collectives), over the
window's steps."""


def read(ctx):
    if not ctx["steps"]:
        return None
    return 1e3 * ctx["summary"].collective_s / ctx["steps"]
