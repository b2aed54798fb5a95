"""Milliseconds a step in which a collective operation runs and no
other operation does, on the chip with the most collective time
(``bench/trace.py``: ``Summary.exposed_s``): the part of
``collective.ms`` that nothing hides."""


def read(ctx):
    if not ctx["steps"]:
        return None
    return 1e3 * ctx["summary"].exposed_s / ctx["steps"]
