"""The reductions of ``bench/scopes.py``: idle time split by the innermost
host span, op-name paths with transforms unwrapped, device time by
scope and collective-permute counts -- on lists worked out by hand and
on a trace recorded on the CPU from a smoke-size train step driven by
``Trainer`` with the ``repro.obs`` tracer on."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import scopes  # noqa: E402
from bench import trace  # noqa: E402
from bench.trace import Op, Span  # noqa: E402

MODEL_SCOPES = ("attention", "mlp", "loss", "optimizer")

# Each case: one chip's device ops, host spans, and the idle split of the
# window [0, 10], worked out by hand.
IDLE_CASES = {
    "nested-innermost": dict(
        ops=[(0.0, 2.0), (6.0, 10.0)],
        spans=[("repro.train.step", 1.0, 9.0),
               ("repro.train.wait", 3.0, 5.0),
               ("bench.batch", 1.5, 3.25)],
        # idle [2, 6]: [2, 3.25] under batch (the shortest of the three
        # there), [3.25, 5] under wait, [5, 6] under step
        want={"bench.batch": 1.25, "repro.train.wait": 1.75,
              "repro.train.step": 1.0}),
    "uncovered": dict(
        ops=[(2.0, 3.0)],
        spans=[("repro.train.batch", 4.0, 5.0)],
        want={"host.other": 8.0, "repro.train.batch": 1.0}),
    "busy-window": dict(
        ops=[(0.0, 4.0), (3.0, 10.0)],
        spans=[("repro.train.step", 0.0, 10.0)],
        want={}),
    "spans-past-the-window": dict(
        ops=[(1.0, 9.0)],
        spans=[("repro.train.step", -5.0, 0.5),
               ("repro.train.observe", 9.5, 12.0)],
        want={"repro.train.step": 0.5, "host.other": 1.0,
              "repro.train.observe": 0.5}),
    "many-gaps-one-span": dict(
        ops=[(i + 0.0, i + 0.75) for i in range(10)],
        spans=[("repro.train.wait", 0.0, 10.0)],
        want={"repro.train.wait": 2.5}),
}


@pytest.mark.parametrize("name", sorted(IDLE_CASES))
def test_idle_by_span_by_hand(name):
    c = IDLE_CASES[name]
    ops = [Op(0, "fusion.1", a, b) for a, b in c["ops"]]
    got = scopes.idle_by_span(ops, [Span(*s) for s in c["spans"]], 0.0, 10.0)
    assert got == pytest.approx(c["want"])
    idle = 10.0 - trace.busy(ops, 0.0, 10.0)
    assert sum(got.values()) == pytest.approx(idle)


def test_op_scopes_unwrap_transforms():
    hlo = ('  %fusion.7 = bf16[2,8]{1,0} fusion(%p), kind=kLoop, metadata='
           '{op_name="jit(step)/transpose(jvp())/while/body/closed_call/'
           'checkpoint/attention/bsd,dv->bsv/dot_general"}\n'
           '  %log.2 = f32[] log(%a), metadata={op_name='
           '"jit(step)/jvp(loss)/jit(take_along_axis)/log"}\n'
           '  ROOT %cp.1 = f32[4] collective-permute(%b), metadata={op_name='
           '"jit(step)/shard_map/certified.permute/ppermute"}')
    assert scopes.op_scopes(hlo) == {
        "fusion.7": ("attention", "bsd,dv->bsv", "dot_general"),
        "log.2": ("loss", "log"),
        "cp.1": ("certified.permute", "ppermute")}


def test_scope_totals_and_permutes_by_hand():
    paths = {"fusion.1": ("attention", "dot_general"),
             "fusion.2": ("optimizer", "certified.table", "gather"),
             "collective-permute-done.3": ("certified.permute", "ppermute"),
             "collective-permute-start.3": ("certified.permute", "ppermute"),
             "while.1": ("attention",)}
    ops = [Op(0, "while.1", 0.0, 9.0), Op(0, "fusion.1", 0.0, 2.0),
           Op(0, "fusion.2", 2.0, 3.0),
           Op(0, "collective-permute-start.3", 3.0, 3.5),
           Op(0, "collective-permute-done.3", 3.5, 5.0),
           Op(1, "fusion.1", -1.0, 1.0), Op(1, "fusion.9", 1.0, 2.0),
           Op(1, "collective-permute.4", 2.0, 2.5)]
    listed = {"attention", "optimizer", "certified.table",
              "certified.permute"}
    got = scopes.scope_totals(ops, paths, listed, 0.0, 10.0)
    assert got == {0: pytest.approx({"attention": 2.0,
                                     "certified.table": 1.0,
                                     "certified.permute": 2.0}),
                   1: pytest.approx({"attention": 1.0})}
    # the innermost listed scope wins; an unlisted one is passed over
    got = scopes.scope_totals(ops, paths, {"optimizer"}, 0.0, 10.0)
    assert got == {0: pytest.approx({"optimizer": 1.0})}
    codes = {"collective-permute-start.3": "collective-permute-start",
             "collective-permute-done.3": "collective-permute-done",
             "collective-permute.4": "collective-permute",
             "fusion.1": "fusion"}
    assert scopes.permute_count(ops, codes, 0.0, 10.0) == {0: 1, 1: 1}
    assert scopes.permute_count(ops, codes, 0.0, 4.0) == {1: 1}


def test_op_codes():
    hlo = ('  %collective-permute-start.8 = (f32[5,3104]{1,0:T(8,128)S(1)}, '
           'u32[]{:S(2)}) collective-permute-start(%concatenate.422), '
           'channel_id=1\n'
           '  ROOT %ppermute.3 = f32[4]{0} collective-permute(%p), '
           'source_target_pairs={{0,1},{1,0}}\n'
           '  %fusion.2 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(%a), '
           'kind=kLoop, calls=%fused_computation.2')
    assert scopes.op_codes(hlo) == {
        "collective-permute-start.8": "collective-permute-start",
        "ppermute.3": "collective-permute", "fusion.2": "fusion"}


def test_recorded_train_step(tmp_path):
    """A smoke-size train step run three times by ``Trainer`` under the
    profiler with the ``repro.obs`` tracer on: the program's spans come
    back nested under ``repro.train.step``, each model scope has device
    time, the scopes together stay within busy time, and the idle split
    sums to the window's idle time."""
    import jax

    from repro import obs
    from repro.configs import get_config
    from repro.models import get_model
    from repro.optim import AdamWConfig
    from repro.train import Trainer, TrainerConfig, init_state, make_train_step

    cfg = get_config("qwen2-0.5b").smoke()
    model = get_model(cfg)
    state = jax.jit(lambda k: init_state(model, k))(jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (4, 65), 0,
                             cfg.vocab_size)
    batch = {"tokens": ids[:, :-1], "labels": ids[:, 1:]}
    compiled = jax.jit(make_train_step(model, AdamWConfig())).lower(
        state, batch).compile()
    compiled(state, batch)[1]["loss"].block_until_ready()
    trainer = Trainer(step_fn=compiled, state=state,
                      batches=iter([batch] * 3),
                      cfg=TrainerConfig(total_steps=3, ckpt_dir=None))

    prev = obs.set_tracer(obs.Tracer(enabled=True))
    try:
        jax.profiler.start_trace(str(tmp_path))
        with jax.profiler.TraceAnnotation("bench.window"):
            trainer.run()
        jax.profiler.stop_trace()
    finally:
        obs.set_tracer(prev)

    spans = scopes.load_spans(str(tmp_path))
    (win,) = [s for s in spans if s.name == "bench.window"]
    steps = [s for s in spans if s.name == "repro.train.step"]
    assert len(steps) == 3
    for child in ("batch", "dispatch", "wait", "observe"):
        inner = [s for s in spans if s.name == f"repro.train.{child}"]
        assert len(inner) == 3
        for s in inner:
            assert any(p.start <= s.start and s.end <= p.end for p in steps)

    ops, bench_spans = trace.load(str(tmp_path))
    assert [s.name for s in bench_spans] == ["window"]
    w0, w1 = win.start, win.end
    paths = scopes.op_scopes(compiled.as_text())
    totals = scopes.scope_totals(ops, paths, MODEL_SCOPES, w0, w1)
    assert len(totals) == 1
    (per_scope,) = totals.values()
    for name in MODEL_SCOPES:
        assert per_scope.get(name, 0.0) > 0, (name, per_scope)
    busy = trace.busy(ops, w0, w1)
    assert sum(per_scope.values()) <= busy + 1e-9

    inner = [s for s in spans if s is not win]
    idle = scopes.idle_by_span(ops, inner, w0, w1)
    assert set(idle) <= {s.name for s in inner} | {"host.other"}
    assert sum(idle.values()) == pytest.approx((w1 - w0) - busy, rel=0.01)
