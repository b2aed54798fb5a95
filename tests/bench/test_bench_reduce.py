"""The trace reduction of ``bench/trace.py``: busy and idle time, device
time by operation, collective time and its exposed part, and idle gaps
named by host spans -- on event lists worked out by hand and on a trace
recorded on the CPU from a small jitted function."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402
from bench.trace import Op, Span  # noqa: E402


def _ops(rows):
    return [Op(dev, name, a, b) for dev, name, a, b in rows]


# Each case: device ops, host spans, and what the reduction must give,
# worked out by hand for the window [0, 5].
CASES = {
    "compute-only": dict(
        ops=[(0, "fusion.1", 0.0, 1.0), (0, "fusion.2", 0.5, 2.0),
             (0, "fusion.1", 3.0, 4.0)],
        spans=[("window", 0.0, 5.0), ("batch", 2.2, 2.9),
               ("dispatch", 4.5, 6.0)],
        busy=3.0, collective=0.0, exposed=0.0,
        totals={"fusion.1": 2.0, "fusion.2": 1.5},
        gaps=[("batch", 1.0), ("dispatch", 1.0)]),
    "collective-half-hidden": dict(
        ops=[(0, "fusion.7", 0.0, 2.0),
             (0, "collective-permute-done.3", 1.0, 3.0),
             (0, "all-reduce.1", 2.5, 4.0)],
        spans=[("window", 0.0, 5.0)],
        # union of collectives [1, 4] = 3 s; compute covers [1, 2]
        busy=4.0, collective=3.5, exposed=2.0,
        totals={"fusion.7": 2.0, "collective-permute-done.3": 2.0,
                "all-reduce.1": 1.5},
        gaps=[("host.other", 1.0)]),
    "slowest-chip": dict(
        ops=[(0, "fusion.1", 0.0, 4.0), (0, "all-gather.2", 1.0, 2.0),
             (1, "fusion.1", 0.0, 1.0), (1, "reduce-scatter.4", 0.5, 2.5),
             (1, "fusion.1", 3.0, 5.0)],
        spans=[("window", 0.0, 5.0), ("dispatch", 2.4, 3.1)],
        # chip 1: 2 s of collective, [1, 2.5] not under compute
        busy=(4.0 + 4.5) / 2, collective=2.0, exposed=1.5,
        totals={"fusion.1": (4.0 + 3.0) / 2, "all-gather.2": 0.5,
                "reduce-scatter.4": 1.0},
        gaps=[("host.other", 1.0), ("dispatch", 0.5)]),
    "loop-hides-nothing": dict(
        # a while loop's event spans its body's ops: busy, but neither
        # compute that hides a collective nor an op of its own
        ops=[(0, "while.3", 0.0, 4.0), (0, "fusion.1", 0.0, 1.0),
             (0, "collective-permute-done.2", 1.0, 3.0),
             (0, "fusion.2", 3.0, 4.0)],
        spans=[("window", 0.0, 5.0)],
        busy=4.0, collective=2.0, exposed=2.0,
        totals={"fusion.1": 1.0, "collective-permute-done.2": 2.0,
                "fusion.2": 1.0},
        gaps=[("host.other", 1.0)]),
    "clipped-to-window": dict(
        ops=[(0, "fusion.1", -1.0, 1.0), (0, "fusion.2", 4.0, 7.0)],
        spans=[("window", 0.0, 5.0), ("batch", -3.0, 0.5)],
        busy=2.0, collective=0.0, exposed=0.0,
        totals={"fusion.1": 1.0, "fusion.2": 1.0},
        gaps=[("host.other", 3.0)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_reduction_by_hand(name):
    c = CASES[name]
    s = trace.summarize(_ops(c["ops"]), [Span(*x) for x in c["spans"]])
    assert s.window_s == pytest.approx(5.0)
    assert s.busy_s == pytest.approx(c["busy"])
    assert s.collective_s == pytest.approx(c["collective"])
    assert s.exposed_s == pytest.approx(c["exposed"])
    assert dict(s.device_ops) == pytest.approx(c["totals"])
    assert [g[0] for g in s.idle_gaps] == [g[0] for g in c["gaps"]]
    assert [g[1] for g in s.idle_gaps] == pytest.approx(
        [g[1] for g in c["gaps"]])


def test_summary_needs_a_window_and_device_ops():
    with pytest.raises(ValueError, match="window"):
        trace.summarize(_ops([(0, "f", 0, 1)]), [])
    with pytest.raises(ValueError, match="device operations"):
        trace.summarize([], [Span("window", 0, 1)])


def test_recorded_cpu_trace(tmp_path):
    """A trace recorded on the CPU: the host spans come back by name, the
    device operations are the jitted function's HLO ops, and busy time
    and per-op totals equal the union and sums of those very events."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda a, b: jnp.tanh(a @ b) + 1.0)
    a = jnp.ones((256, 256))
    hlo = f.lower(a, a).compile().as_text()
    f(a, a).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for i in range(3):
            with jax.profiler.TraceAnnotation("bench.batch"):
                x = a + i
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                y = f(x, a)
            y.block_until_ready()
    jax.profiler.stop_trace()

    ops, spans = trace.load(str(tmp_path))
    names = [s.name for s in spans]
    assert names.count("window") == 1
    assert names.count("batch") == 3 and names.count("dispatch") == 3
    assert ops and all(op.end >= op.start for op in ops)
    assert any(op.name in hlo for op in ops)
    labels = trace.op_labels(hlo)
    assert any("dot_general" in v or "tanh" in v for v in labels.values())

    s = trace.summarize(ops, spans)
    w = [x for x in spans if x.name == "window"][0]
    inside = sorted((max(o.start, w.start), min(o.end, w.end)) for o in ops
                    if min(o.end, w.end) > max(o.start, w.start))
    merged = []
    for lo, hi in inside:
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    n = len({o.device for o in ops})
    assert s.window_s == pytest.approx(w.end - w.start)
    assert s.busy_s == pytest.approx(sum(hi - lo for lo, hi in merged) / n)
    assert 0 < s.busy_s <= s.window_s
    totals = {}
    for lo, hi, o in ((max(o.start, w.start), min(o.end, w.end), o)
                      for o in ops):
        if hi > lo:
            totals[o.name] = totals.get(o.name, 0.0) + (hi - lo) / n
    for name, secs in s.device_ops:
        assert secs == pytest.approx(totals[name])
    idle = s.window_s - s.busy_s
    assert sum(g for _, g in s.idle_gaps) <= idle + 1e-9


def test_op_labels_drop_frames():
    hlo = ('  %fusion.7 = bf16[2,8]{1,0} fusion(%p), kind=kLoop, metadata='
           '{op_name="jit(step)/jit(main)/transpose(jvp(loss))/while/body/'
           'closed_call/checkpoint/bsd,dv->bsv/dot_general" '
           'stack_frame_id=3}\n'
           '  ROOT %add.1 = f32[] add(%a, %b), metadata={op_name='
           '"jit(step)/add"}')
    assert trace.op_labels(hlo) == {"fusion.7": "bwd bsd,dv->bsv/dot_general",
                                    "add.1": "add"}
