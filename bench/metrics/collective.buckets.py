"""Buckets the certified gradient reducer split the gradient into: the
program's gauge ``train.overlap.buckets``, which the reducer sets as the
step is traced.  A program without that gauge reads nothing."""


def read(ctx):
    from repro import obs

    return obs.metrics().snapshot()["gauges"].get("train.overlap.buckets")
