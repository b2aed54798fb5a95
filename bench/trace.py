"""From a profiler trace to the per-layer numbers.

:func:`load` reads an ``.xplane.pb`` into device operations (one list
per chip) and the benchmark's host spans (``bench.*`` annotations).  On
a TPU the operations are the events of each ``/device:TPU:<n>`` plane's
``XLA Ops`` line; on the CPU backend they are the host events that carry
an ``hlo_op`` stat.  Everything after that works on plain lists, so the
arithmetic is tested on a trace recorded on the CPU and on lists made by
hand:

* :func:`busy` - the union of the intervals in which an operation runs;
* :func:`collectives` - collective time and the part of it during which
  no other operation runs on that chip (exposed);
* :func:`op_totals` - device time by operation name;
* :func:`idle_gaps` - gaps in the union, each named by the host span
  that overlaps it most (``host.other`` where none does).
"""

from __future__ import annotations

import dataclasses
import glob
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]

COLLECTIVE_RE = re.compile(
    r"collective-permute|all-reduce|all-gather|reduce-scatter|all-to-all"
    r"|collective-broadcast|ppermute|psum")
#: ops whose event spans the ops of their body, which have events too
CONTAINER_RE = re.compile(r"^(while|conditional|call)(\.|$)")
_INST_RE = re.compile(r"^%?([\w.\-]+)\s*=")


@dataclasses.dataclass(frozen=True)
class Op:
    device: int
    name: str
    start: float          # seconds on the trace's clock
    end: float


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float
    end: float


def _stats(event) -> Dict[str, object]:
    try:
        return dict(event.stats)
    except (TypeError, ValueError):
        return {}


def load(trace_dir: str) -> Tuple[List[Op], List[Span]]:
    """Device operations and ``bench.*`` host spans of the trace in
    ``trace_dir`` (the directory given to ``jax.profiler.start_trace``)."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    ops: List[Op] = []
    spans: List[Span] = []
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    inst = _INST_RE.match(e.name)  # the HLO instruction
                    ops.append(Op(dev, inst.group(1) if inst else e.name,
                                  e.start_ns * 1e-9,
                                  (e.start_ns + e.duration_ns) * 1e-9))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append(Span(e.name[len("bench."):],
                                          e.start_ns * 1e-9,
                                          (e.start_ns + e.duration_ns) * 1e-9))
                        continue
                    st = _stats(e)
                    if "hlo_op" in st and e.duration_ns > 0:
                        ops.append(Op(int(st.get("device_ordinal", 0)),
                                      str(st["hlo_op"]), e.start_ns * 1e-9,
                                      (e.start_ns + e.duration_ns) * 1e-9))
    return ops, spans


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Interval], w0: float, w1: float) -> List[Interval]:
    return [(max(a, w0), min(b, w1)) for a, b in intervals
            if min(b, w1) > max(a, w0)]


def length(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def overlap(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Length of the intersection of two unions (each sorted, disjoint)."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def by_device(ops: Iterable[Op]) -> Dict[int, List[Op]]:
    out: Dict[int, List[Op]] = defaultdict(list)
    for op in ops:
        out[op.device].append(op)
    return dict(out)


def is_collective(op: Op) -> bool:
    return bool(COLLECTIVE_RE.search(op.name))


def is_container(op: Op) -> bool:
    return bool(CONTAINER_RE.match(op.name))


# ---------------------------------------------------------------------------
# the reductions
# ---------------------------------------------------------------------------

def busy(ops: Iterable[Op], w0: float, w1: float) -> float:
    """Seconds of ``[w0, w1]`` in which some operation runs."""
    return length(union(clip([(o.start, o.end) for o in ops], w0, w1)))


def collectives(ops: Iterable[Op], w0: float, w1: float) -> Tuple[float, float]:
    """``(summed durations of collective ops, the part of their union
    during which no other op runs)`` in ``[w0, w1]``, for one chip."""
    ops = list(ops)
    coll = clip([(o.start, o.end) for o in ops if is_collective(o)], w0, w1)
    other = union(clip([(o.start, o.end) for o in ops
                        if not is_collective(o) and not is_container(o)],
                       w0, w1))
    cu = union(coll)
    return length(coll), length(cu) - overlap(cu, other)


def op_totals(ops: Iterable[Op], w0: float, w1: float) -> Dict[str, float]:
    """Device seconds by op name, loops and calls left out (their bodies'
    ops are counted)."""
    out: Dict[str, float] = defaultdict(float)
    for o in ops:
        if is_container(o):
            continue
        (a, b), = clip([(o.start, o.end)], w0, w1) or [(0.0, 0.0)]
        out[o.name] += b - a
    return dict(out)


def idle_gaps(ops: Iterable[Op], spans: Sequence[Span], w0: float,
              w1: float) -> List[Tuple[str, float]]:
    """Each gap in the busy union inside ``[w0, w1]``, named by the host
    span that overlaps it most, longest first."""
    bu = union(clip([(o.start, o.end) for o in ops], w0, w1))
    edges = [w0] + [x for iv in bu for x in iv] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    named = []
    for a, b in gaps:
        best, name = 0.0, "host.other"
        for s in spans:
            ov = min(b, s.end) - max(a, s.start)
            if ov > best:
                best, name = ov, s.name
        named.append((name, b - a))
    return sorted(named, key=lambda x: -x[1])


@dataclasses.dataclass
class Summary:
    """What the per-layer readers take from one traced window."""

    window_s: float
    busy_s: float                       # mean over chips
    chips: int
    collective_s: float                 # slowest chip
    exposed_s: float                    # slowest chip
    ops: List[Op]
    device_ops: List[Tuple[str, float]]  # top 10, mean seconds per chip
    idle_gaps: List[Tuple[str, float]]   # top 10 single gaps


def summarize(ops: List[Op], spans: List[Span],
              window: str = "window") -> Summary:
    win = [s for s in spans if s.name == window]
    if not win:
        raise ValueError(f"the trace has no bench.{window} span")
    w0, w1 = win[0].start, win[0].end
    inner = [s for s in spans if s.name != window and s.end > w0
             and s.start < w1]
    dev = by_device(ops)
    if not dev:
        raise ValueError("the trace has no device operations")
    n = len(dev)
    totals: Dict[str, float] = defaultdict(float)
    gaps: List[Tuple[str, float]] = []
    coll, exposed, busy_sum = 0.0, 0.0, 0.0
    for d, dops in dev.items():
        busy_sum += busy(dops, w0, w1)
        c, e = collectives(dops, w0, w1)
        if c > coll:
            coll, exposed = c, e
        for k, v in op_totals(dops, w0, w1).items():
            totals[k] += v / n
        gaps.extend(idle_gaps(dops, inner, w0, w1))
    top = sorted(totals.items(), key=lambda x: -x[1])[:10]
    return Summary(window_s=w1 - w0, busy_s=busy_sum / n, chips=n,
                   collective_s=coll, exposed_s=exposed, ops=ops,
                   device_ops=top,
                   idle_gaps=sorted(gaps, key=lambda x: -x[1])[:10])


_META_RE = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?'
                      r'metadata=\{op_name="([^"]*)"')


#: name-stack frames that say nothing about which op it is
_FRAMES = {"closed_call", "checkpoint", "while", "body", "cond",
           "rematted_computation", "shard_map"}


def op_labels(hlo_text: str) -> Dict[str, str]:
    """``{instruction: where it came from}``: the JAX op name the
    compiled program's metadata gives each instruction, without its
    ``jit(...)`` frames and the frames of loops, calls and remat;
    ``transpose(jvp())`` marks the backward pass."""
    out = {}
    for line in hlo_text.splitlines():
        m = _META_RE.match(line)
        if m:
            parts = [p for p in m.group(2).split("/")
                     if not p.startswith("jit(") and p not in _FRAMES]
            bwd = "bwd " if any(p.startswith("transpose(") for p in parts) \
                else ""
            parts = [p for p in parts if not p.startswith(("transpose(",
                                                            "jvp("))]
            out[m.group(1)] = bwd + "/".join(parts[-2:])
    return out
