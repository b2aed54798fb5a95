"""The four-chip cell's readers of the certified collectives:
``collective.ms`` and ``collective.exposed_ms`` turn the traced window's
collective and exposed time into milliseconds a step, and
``collective.buckets`` reads the reducer's gauge, or nothing from a
program that has none."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spec, trace  # noqa: E402
from bench.trace import Op, Span  # noqa: E402


def _summary():
    """Two chips, window [0, 5]: chip 1 has the most collective time
    (3.5 s summed, 2.0 s of its union with no other op running)."""
    ops = [Op(0, "fusion.1", 0.0, 4.0),
           Op(0, "collective-permute-done.1", 1.0, 2.0),
           Op(1, "fusion.7", 0.0, 2.0),
           Op(1, "collective-permute-done.3", 1.0, 3.0),
           Op(1, "all-reduce.1", 2.5, 4.0)]
    return trace.summarize(ops, [Span("window", 0.0, 5.0)])


@pytest.mark.parametrize("metric, seconds", [("collective.ms", 3.5),
                                             ("collective.exposed_ms", 2.0)])
def test_collective_time_per_step(metric, seconds):
    read = spec.metric_reader(metric).read
    assert read({"summary": _summary(), "steps": 4}) == \
        pytest.approx(1e3 * seconds / 4)
    assert read({"summary": _summary(), "steps": 0}) is None


def test_buckets_read_the_reducers_gauge():
    from repro import obs

    read = spec.metric_reader("collective.buckets").read
    prev = obs.set_metrics(obs.MetricsRegistry())
    try:
        assert read({}) is None
        obs.metrics().gauge("train.overlap.buckets").set(12)
        assert read({}) == 12
    finally:
        obs.set_metrics(prev)
