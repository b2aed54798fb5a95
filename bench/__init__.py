"""On-chip benchmark of the system: cells, metrics and checks as data.

``bench/run.py`` is the command.  Everything that belongs to one model
configuration, one traffic mix or one per-layer metric lives in a file
of its own that the harness finds by the name ``BENCHMARK.json`` gives
it (see :mod:`bench.spec`).
"""
