"""The program's own scopes and spans in a profiler trace.

``bench/trace.py`` reads device operations and the benchmark's own host
spans.  The program puts two more things into the same trace:

* device scopes (``jax.named_scope``) in the compiled program's op-name
  metadata: ``attention``, ``mlp``, ``loss`` and ``optimizer`` in the
  train step, and ``certified.permute``, ``certified.table``,
  ``certified.add``, ``certified.pack`` and ``certified.finish`` in the
  certified reducer;
* host spans of ``repro.obs`` (``repro.train.step`` and its children
  ``repro.train.batch``, ``.dispatch``, ``.wait`` and ``.observe``),
  while its tracer is enabled.

This module reduces them, on plain lists as ``bench/trace.py`` does:

* :func:`load_spans` - the host spans of both the benchmark and the
  program, each under its whole name (``bench.batch``,
  ``repro.train.wait``);
* :func:`op_scopes` - each instruction's op-name path;
* :func:`scope_totals` - device seconds per listed scope, per chip.  A
  fusion carries its root instruction's op name, so work fused across a
  scope boundary is counted on one side of it;
* :func:`op_codes` and :func:`permute_count` - collective-permute
  events per chip, found by opcode (their names differ by backend);
* :func:`idle_by_span` - each idle instant of a window given to the
  innermost host span covering it.
"""

from __future__ import annotations

import glob
import re
from collections import defaultdict
from typing import Collection, Dict, Iterable, List, Sequence, Tuple

from bench.trace import (_FRAMES, _META_RE, Op, Span, clip, is_container,
                         union)

#: host-span prefixes kept by :func:`load_spans`
SPAN_PREFIXES = ("bench.", "repro.")
#: a transform around a scope's name: ``jvp(loss)``, ``transpose(jvp())``
_WRAPPED_RE = re.compile(r"^[A-Za-z_]\w*\((.*)\)$")
#: an instruction's name and opcode: the first word before "(" that
#: follows a space (layouts such as ``T(8,128)`` follow a colon)
_OPCODE_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*.*?\s"
                        r"([a-z][\w\-]*)\(")
#: one event per collective-permute: a synchronous op, or an
#: asynchronous pair's ``-done``
PERMUTE_CODES = ("collective-permute", "collective-permute-done")


def load_spans(trace_dir: str) -> List[Span]:
    """Host spans of the trace in ``trace_dir`` whose names start with
    one of :data:`SPAN_PREFIXES`, names kept whole."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    spans: List[Span] = []
    for plane in ProfileData.from_file(files[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIXES):
                    spans.append(Span(e.name, e.start_ns * 1e-9,
                                      (e.start_ns + e.duration_ns) * 1e-9))
    return spans


def _scope_parts(op_name: str) -> Tuple[str, ...]:
    parts = []
    for p in op_name.split("/"):
        if p.startswith("jit("):
            continue
        while True:                      # jvp(loss) -> loss
            m = _WRAPPED_RE.match(p)
            if not m:
                break
            p = m.group(1)
        if p and p not in _FRAMES:
            parts.append(p)
    return tuple(parts)


def op_scopes(hlo_text: str) -> Dict[str, Tuple[str, ...]]:
    """``{instruction: op-name path}`` of the compiled program's text,
    without the frames ``bench.trace.op_labels`` drops; a transform
    around a scope (``jvp(loss)``, what ``value_and_grad`` makes of a
    scope at the top of the loss) gives the scope back."""
    out = {}
    for line in hlo_text.splitlines():
        m = _META_RE.match(line)
        if m:
            out[m.group(1)] = _scope_parts(m.group(2))
    return out


def scope_totals(ops: Iterable[Op], paths: Dict[str, Tuple[str, ...]],
                 scopes: Collection[str], w0: float,
                 w1: float) -> Dict[int, Dict[str, float]]:
    """``{chip: {scope: seconds}}`` in ``[w0, w1]``: each op counted
    under the innermost of ``scopes`` on its path, containers left out
    (their bodies' ops are counted); an op under none is not counted."""
    out: Dict[int, Dict[str, float]] = defaultdict(lambda: defaultdict(float))
    for o in ops:
        if is_container(o):
            continue
        scope = next((p for p in reversed(paths.get(o.name, ()))
                      if p in scopes), None)
        iv = clip([(o.start, o.end)], w0, w1)
        if scope is not None and iv:
            out[o.device][scope] += iv[0][1] - iv[0][0]
    return {d: dict(v) for d, v in out.items()}


def op_codes(hlo_text: str) -> Dict[str, str]:
    """``{instruction: opcode}`` of the compiled program's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _OPCODE_RE.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def permute_count(ops: Iterable[Op], codes: Dict[str, str], w0: float,
                  w1: float) -> Dict[int, int]:
    """``{chip: collective-permutes that end in [w0, w1]}``, one event
    per permute (``codes`` from :func:`op_codes`)."""
    out: Dict[int, int] = defaultdict(int)
    for o in ops:
        if codes.get(o.name) in PERMUTE_CODES and w0 <= o.end <= w1:
            out[o.device] += 1
    return dict(out)


def idle_by_span(ops: Iterable[Op], spans: Sequence[Span], w0: float,
                 w1: float) -> Dict[str, float]:
    """Idle seconds of one chip in ``[w0, w1]`` by host span: each idle
    instant goes to the shortest span of ``spans`` that covers it (the
    innermost, where spans nest), else to ``host.other``.  The values
    sum to the window's idle time."""
    busy = union(clip([(o.start, o.end) for o in ops], w0, w1))
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    cuts = sorted({w0, w1} | {x for s in spans for x in (s.start, s.end)
                              if w0 < x < w1})
    by_start = sorted(spans, key=lambda s: s.start)
    out: Dict[str, float] = defaultdict(float)
    active: List[Span] = []
    i = g = 0
    # between two cuts the covering spans do not change
    for lo, hi in zip(cuts, cuts[1:]):
        while i < len(by_start) and by_start[i].start <= lo:
            active.append(by_start[i])
            i += 1
        active = [s for s in active if s.end >= hi]
        while g < len(gaps) and gaps[g][1] <= lo:
            g += 1
        idle, j = 0.0, g
        while j < len(gaps) and gaps[j][0] < hi:
            idle += min(hi, gaps[j][1]) - max(lo, gaps[j][0])
            j += 1
        if idle > 0:
            name = (min(active, key=lambda s: s.end - s.start).name
                    if active else "host.other")
            out[name] += idle
    return dict(out)
