"""Tests for ``repro.obs``: tracer, metrics, capture→fold→replay, and
the instrumented pipeline (PR-7 observability tentpole).

Covers the contracts the instrumentation relies on:

* disabled-mode zero-overhead — ``span()`` on a disabled tracer is the
  shared :data:`NULL_SPAN` singleton (identity, not just equality) and
  nothing is buffered; ``timer()`` still measures;
* thread-safety — spans/counters recorded concurrently from a live
  ``Session.monitor()`` thread and the main thread never corrupt the
  ring buffer;
* round-trips — Chrome trace-event export parses back with matched
  span names, and ``WorkloadTrace`` JSON round-trips exactly;
* fold equivalence — a captured stationary workload folds to a JobMix
  whose ``key()`` equals the declared mix it was issued from;
* replay — per-phase-window plans never lose to the stationary
  declared-mix plan on the synthetic bursty trace.
"""

import json
import threading

import numpy as np
import pytest

from repro import obs
from repro.obs import (
    NULL_SPAN,
    MetricsRegistry,
    OpRecord,
    Tracer,
    WorkloadRecorder,
    WorkloadTrace,
    declared_mix,
    fold,
    replay,
    synthetic_bursty_trace,
)


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_span_nesting_records_and_clock_injection():
    clk = FakeClock()
    tr = Tracer(enabled=True, clock=clk)
    with tr.span("outer", label="a"):
        clk.advance(0.5)
        with tr.span("inner") as sp:
            clk.advance(0.25)
            sp.set(result=7)
    tr.event("mark", x=1)
    recs = tr.records()
    assert [r[1] for r in recs] == ["inner", "outer", "mark"]
    phases = {r[1]: r[0] for r in recs}
    assert phases == {"inner": "X", "outer": "X", "mark": "i"}
    by_name = {r[1]: r for r in recs}
    # durations come from the injected clock, exactly
    assert by_name["inner"][3] == pytest.approx(0.25)
    assert by_name["outer"][3] == pytest.approx(0.75)
    # depth: outer recorded at depth 0, inner at depth 1
    assert by_name["outer"][5] == 0
    assert by_name["inner"][5] == 1
    assert by_name["inner"][6] == {"result": 7}
    assert by_name["mark"][6] == {"x": 1}


def test_disabled_tracer_is_zero_alloc_and_records_nothing():
    clk = FakeClock()
    tr = Tracer(enabled=False, clock=clk)
    s1 = tr.span("a", big="attr")
    s2 = tr.span("b")
    assert s1 is NULL_SPAN and s2 is NULL_SPAN   # shared singleton
    with s1:
        pass
    tr.event("never", x=1)
    assert len(tr) == 0 and tr.emitted == 0
    # the null span carries no state at all
    assert not hasattr(NULL_SPAN, "__dict__")
    assert NULL_SPAN.elapsed == 0.0


def test_spans_annotate_the_profiler_only_when_recording(monkeypatch):
    """A recording span opens one ``repro.<name>`` profiler annotation
    around its interval; a disabled tracer's spans and timers open none
    and allocate nothing."""
    import tracemalloc

    import jax.profiler

    opened = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            opened.append(("enter", self.name))

        def __exit__(self, *exc):
            opened.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    off = Tracer(enabled=False)
    tracemalloc.start()
    try:
        before = tracemalloc.take_snapshot()
        for _ in range(1000):
            with off.span("train.wait"):
                pass
        after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    grown = sum(d.size_diff for d in after.compare_to(before, "filename")
                if d.traceback[0].filename.endswith("obs/trace.py"))
    assert grown == 0
    with off.timer("train.step"):
        pass
    assert opened == []

    on = Tracer(enabled=True)
    with on.span("train.step"):
        with on.timer("train.wait"):
            assert opened == [("enter", "repro.train.step"),
                              ("enter", "repro.train.wait")]
    assert opened[2:] == [("exit", "repro.train.wait"),
                          ("exit", "repro.train.step")]
    assert [r[1] for r in on.records()] == ["train.wait", "train.step"]


def test_timer_measures_even_when_disabled():
    clk = FakeClock()
    tr = Tracer(enabled=False, clock=clk)
    t = tr.timer("work")
    with t:
        clk.advance(1.5)
    assert t.elapsed == pytest.approx(1.5)       # the number is real
    assert len(tr) == 0                          # but nothing was recorded
    tr.set_enabled(True)
    t2 = tr.timer("work")
    with t2:
        clk.advance(0.5)
    assert t2.elapsed == pytest.approx(0.5)
    assert len(tr) == 1                          # enabled: recorded too


def test_span_records_error_attr_and_restores_depth():
    tr = Tracer(enabled=True)
    with pytest.raises(ValueError, match="boom"):
        with tr.span("failing"):
            raise ValueError("boom")
    (rec,) = tr.records()
    assert rec[6] == {"error": "ValueError: boom"}
    with tr.span("after"):
        pass
    assert tr.records()[-1][5] == 0, "depth must not leak after a raise"


def test_ring_buffer_bounded_and_resizable():
    tr = Tracer(enabled=True, buffer=4)
    for i in range(10):
        tr.event(f"e{i}")
    assert len(tr) == 4
    assert tr.emitted == 10                      # monotone, survives wrap
    assert [r[1] for r in tr.records()] == ["e6", "e7", "e8", "e9"]
    tr.set_buffer(2)
    assert [r[1] for r in tr.records()] == ["e8", "e9"]


def test_chrome_export_round_trip(tmp_path):
    clk = FakeClock()
    tr = Tracer(enabled=True, clock=clk)
    with tr.span("compile", mix="train"):
        clk.advance(0.125)
    tr.event("cache.hit", digest="abc")
    path = tmp_path / "trace.json"
    n = tr.export(str(path))
    assert n == 2
    doc = json.loads(path.read_text())
    assert doc["displayTimeUnit"] == "ms"
    evs = {e["name"]: e for e in doc["traceEvents"] if e["ph"] != "M"}
    assert evs["compile"]["ph"] == "X"
    assert evs["compile"]["dur"] == pytest.approx(0.125e6)
    assert evs["compile"]["args"] == {"mix": "train"}
    assert evs["cache.hit"]["ph"] == "i"
    assert evs["cache.hit"]["s"] == "t"
    # thread metadata names the lane
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert metas and metas[0]["name"] == "thread_name"


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_metrics_snapshot_and_reuse():
    m = MetricsRegistry()
    m.counter("plan.cache.hits").inc()
    m.counter("plan.cache.hits").inc(2)
    m.gauge("drift.score").set(0.25)
    m.histogram("probe.seconds", scale=1e-3).observe(0.004)
    m.histogram("probe.seconds").observe(0.016)
    snap = m.snapshot()
    assert snap["counters"]["plan.cache.hits"] == 3.0
    assert snap["gauges"]["drift.score"] == 0.25
    h = snap["histograms"]["probe.seconds"]
    assert h["count"] == 2 and h["sum"] == pytest.approx(0.02)
    # log2 buckets on the milli scale: 4ms -> 2^2, 16ms -> 2^4
    assert h["buckets"] == {"2": 1, "4": 1}


def test_metrics_prometheus_text():
    m = MetricsRegistry()
    m.counter("plan.cache.hits").inc(5)
    m.gauge("faults.health.state").set(2)
    m.histogram("plan.compile.seconds", scale=1e-3).observe(0.2)
    text = m.to_prometheus()
    assert "# TYPE plan_cache_hits counter\nplan_cache_hits 5" in text
    assert "# TYPE faults_health_state gauge\nfaults_health_state 2" in text
    assert "# TYPE plan_compile_seconds histogram" in text
    assert 'plan_compile_seconds_bucket{le="+Inf"} 1' in text
    assert "plan_compile_seconds_count 1" in text


def test_disabled_registry_hands_out_null_instruments():
    m = MetricsRegistry(enabled=False)
    c = m.counter("x")
    c.inc()
    m.gauge("y").set(3)
    m.histogram("z").observe(1.0)
    assert c is m.counter("x2"), "disabled registry shares one null"
    assert m.snapshot() == {"counters": {}, "gauges": {}, "histograms": {}}


# ---------------------------------------------------------------------------
# capture -> fold -> replay
# ---------------------------------------------------------------------------

def test_recorder_disabled_is_noop_and_enabled_captures():
    clk = FakeClock()
    rec = WorkloadRecorder(enabled=False, clock=clk)
    rec.record("all-reduce", 1e6)
    assert len(rec) == 0 and rec.captured == 0
    rec.enabled = True
    clk.advance(1.0)
    rec.record("all-reduce", 1e6, group=(0, 1, 2))
    (r,) = rec.trace().records
    assert r.op == "all-reduce" and r.size_bytes == 1e6
    assert r.group == (0, 1, 2)
    assert r.t == pytest.approx(1.0)             # epoch-relative


def test_workload_trace_json_round_trip(tmp_path):
    trace = synthetic_bursty_trace(8, steps=4, seed=3)
    path = tmp_path / "capture.json"
    trace.save(str(path))
    back = WorkloadTrace.load(str(path))
    assert back.name == trace.name
    assert back.meta == trace.meta
    assert back.records == trace.records         # exact dataclass equality


def test_fold_of_stationary_capture_matches_declared_mix():
    from repro.plan import CollectiveRequest, JobMix

    declared = JobMix(requests=(
        CollectiveRequest(op="all-reduce", size_bytes=4e6, count=2),
        CollectiveRequest(op="all-gather", size_bytes=1e6, count=1),
    ), name="declared")
    # a stationary workload issuing exactly the declared mix each step
    clk = FakeClock()
    rec = WorkloadRecorder(enabled=True, clock=clk)
    for _ in range(5):
        rec.record("all-reduce", 4e6)
        rec.record("all-reduce", 4e6)
        rec.record("all-gather", 1e6)
        clk.advance(1.0)
    windows = fold(rec.trace(), steps_per_window=5.0)
    assert len(windows) == 1
    assert windows[0].mix.key() == declared.key()
    counts = {r.op: r.count for r in windows[0].mix.requests}
    assert counts == {"all-reduce": 2.0, "all-gather": 1.0}


def test_fold_windows_split_phases():
    trace = synthetic_bursty_trace(8, steps=4, seed=0)
    windows = fold(trace, window_s=1.0)
    assert len(windows) == 4
    ops = [sorted({r.op for r in w.mix.requests}) for w in windows]
    assert ops == [["all-gather"], ["all-reduce"],
                   ["all-gather"], ["all-reduce"]]
    assert sum(w.n_records for w in windows) == len(trace)


def test_replay_phased_beats_declared_on_bursty_trace():
    from repro.fabric import make_datacenter, probe_fabric, scramble
    from repro.plan import PlanCompiler, SolveBudget

    n = 8
    fab, _ = scramble(make_datacenter(n, seed=0), seed=1)
    probe = probe_fabric(fab, seed=0)
    compiler = PlanCompiler(budget=SolveBudget(iters=60, chains=2))
    trace = synthetic_bursty_trace(n, steps=4, seed=0)
    stationary = compiler.compile(probe, declared_mix(trace))
    windows = fold(trace, window_s=1.0)
    phased = [(w, compiler.compile(probe, w.mix)) for w in windows]
    base = replay(trace, stationary, probe.lat, probe.bw)
    ph = replay(trace, stationary, probe.lat, probe.bw, windows=phased)
    assert base["unplanned"] == 0 and ph["unplanned"] == 0
    assert base["records"] == ph["records"] == len(trace)
    assert ph["total_seconds"] <= base["total_seconds"], \
        "phase-windowed plans lost to the single declared-mix plan"


def test_replay_counts_unplanned_ops():
    from repro.fabric import make_datacenter, probe_fabric
    from repro.plan import CollectiveRequest, JobMix, PlanCompiler, \
        SolveBudget

    probe = probe_fabric(make_datacenter(8, seed=0), seed=0)
    plan = PlanCompiler(budget=SolveBudget(iters=40, chains=1)).compile(
        probe, JobMix(requests=(
            CollectiveRequest(op="all-reduce", size_bytes=1e6, count=1),)))
    trace = WorkloadTrace(records=[
        OpRecord("all-reduce", 1e6, None, 0.0),
        OpRecord("all-to-all", 1e6, None, 0.5),   # no entry for this op
    ])
    out = replay(trace, plan, probe.lat, probe.bw)
    assert out["unplanned"] == 1
    assert out["per_op_seconds"].keys() == {"all-reduce"}


# ---------------------------------------------------------------------------
# pipeline integration
# ---------------------------------------------------------------------------

@pytest.fixture
def fresh_obs():
    """Swap in an enabled tracer + fresh registry/recorder; restore after."""
    prev_t = obs.set_tracer(Tracer(enabled=True))
    prev_m = obs.set_metrics(MetricsRegistry())
    prev_r = obs.set_recorder(WorkloadRecorder(enabled=True))
    try:
        yield obs.tracer(), obs.metrics(), obs.recorder()
    finally:
        obs.set_tracer(prev_t)
        obs.set_metrics(prev_m)
        obs.set_recorder(prev_r)


def test_compile_emits_spans_and_metrics(fresh_obs):
    tr, m, _ = fresh_obs
    from repro.fabric import make_datacenter, probe_fabric
    from repro.plan import PlanCompiler, SolveBudget
    from repro.session import train_mix

    probe = probe_fabric(make_datacenter(8, seed=0), seed=0)
    plan = PlanCompiler(budget=SolveBudget(iters=40, chains=1)).compile(
        probe, train_mix(1e6))
    names = {r[1] for r in tr.records()}
    assert "plan.compile" in names
    assert "plan.compile_entry" in names
    snap = m.snapshot()
    assert snap["counters"]["plan.compiles"] == 1.0
    assert snap["histograms"]["plan.compile.seconds"]["count"] == 1
    # the product number still comes from the obs timer
    assert plan.compile_seconds > 0.0


def test_session_monitor_thread_traces_safely(fresh_obs):
    """Tracer + metrics under a live monitor thread and main thread."""
    tr, m, _ = fresh_obs
    from repro.session import Session, SessionConfig

    cfg = SessionConfig.from_dict({
        "fabric": {"kind": "datacenter", "nodes": 8, "scramble_seed": 1},
        "solver": {"budget": {"iters": 40, "chains": 1}},
        "drift": {"threshold": 1e9},     # observe, never go stale
    })
    ticked = threading.Event()
    with Session(cfg) as s:
        s.plan()
        ref = s.reference_matrix()

        def poll():
            ticked.set()
            return ref

        s.monitor(poll=poll, interval_s=0.01)
        assert ticked.wait(timeout=10.0)
        # hammer the tracer from the main thread while the monitor runs
        for i in range(200):
            with tr.span("main.work", i=i):
                pass
    recs = tr.records()
    names = {r[1] for r in recs}
    assert "session.monitor.tick" in names
    assert "main.work" in names
    threads = {r[4] for r in recs}
    assert len(threads) >= 2, "expected records from at least two threads"
    for rec in recs:               # well-formed tuples, no corruption
        assert isinstance(rec[0], str) and isinstance(rec[1], str)
        assert isinstance(rec[2], float) and isinstance(rec[3], float)
    assert m.snapshot()["counters"]["session.monitor.ticks"] >= 1


def test_session_obs_config_exports_on_close(tmp_path, fresh_obs):
    from repro.session import Session, SessionConfig

    export = tmp_path / "trace.json"
    capture = tmp_path / "capture.json"
    cfg = SessionConfig.from_dict({
        "fabric": {"kind": "datacenter", "nodes": 8, "scramble_seed": 1},
        "solver": {"budget": {"iters": 40, "chains": 1}},
        "obs": {"enabled": True, "capture": True,
                "export_path": str(export),
                "capture_path": str(capture)},
    })
    with Session(cfg) as s:
        s.plan()
        obs.recorder().record("all-reduce", 1e6)
    doc = json.loads(export.read_text())
    assert any(e["name"] == "session.plan"
               for e in doc["traceEvents"] if e["ph"] != "M")
    back = WorkloadTrace.load(str(capture))
    assert back.records and back.records[-1].op == "all-reduce"


def test_obs_config_env_round_trip(monkeypatch):
    from repro.session import ObsConfig, SessionConfig

    monkeypatch.setenv("REPRO_OBS_ENABLED", "1")
    monkeypatch.setenv("REPRO_OBS_CAPTURE", "1")
    monkeypatch.setenv("REPRO_OBS_EXPORT_PATH", "/tmp/t.json")
    cfg = SessionConfig.from_env()
    assert cfg.obs.enabled is True
    assert cfg.obs.capture is True
    assert cfg.obs.export_path == "/tmp/t.json"
    back = SessionConfig.from_dict(json.loads(cfg.to_json()))
    assert back.obs == cfg.obs
    assert ObsConfig() != cfg.obs


def test_quarantine_warning_points_at_caller(tmp_path):
    """stacklevel satellite: the cache-quarantine warning names the
    caller's file, not repro internals, and mirrors an obs event."""
    from repro.plan import PlanCache, fabric_fingerprint
    from repro.plan.cache import _request_tag

    prev_t = obs.set_tracer(Tracer(enabled=True))
    try:
        cache = PlanCache(store_dir=str(tmp_path))
        bad = tmp_path / f"deadbeef__{_request_tag('')}.json"
        bad.write_text("{not json")
        fp = fabric_fingerprint(np.ones((4, 4)))
        with pytest.warns(RuntimeWarning, match="quarantined") as rec:
            assert cache.get(fp) is None
        assert rec[0].filename == __file__, \
            "warning must point at the caller via stacklevel"
        assert any(r[1] == "plan.cache.quarantine"
                   for r in obs.tracer().records())
        assert bad.with_suffix(".json.corrupt").exists()
    finally:
        obs.set_tracer(prev_t)
