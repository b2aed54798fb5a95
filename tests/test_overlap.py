"""Tests for the overlap layer (ISSUE-10 tentpole).

Covers: the memoised schedule-table cache (no rebuild across calls),
``LoweredSchedule.slice_rounds`` windows, grad-tree bucketing, the
planned ``PlanEntry.bucket_bytes`` dimension, ``OverlapConfig``
round-trips, the ``direct-schedule-run`` lint rule, and — on an
8-device host mesh in subprocesses — bitwise equality of the
double-buffered overlap runner against ``run_schedule``, numeric
equivalence of the overlapped train step against the baseline,
per-bucket postconditions, ``Session.overlap_step``, and the serve
engine's armed decode/prefill overlap.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.analysis.lint import lint_file
from repro.collective import registered_builders
from repro.kernels import schedule_runner
from repro.plan.compiler import PlanEntry
from repro.session.config import OverlapConfig, SessionConfig
from repro.train.overlap_grads import certified_allreduce, partition_tree

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_sub(prog: str, sentinel: str, timeout: int = 900) -> None:
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, env=env, timeout=timeout, cwd=REPO)
    assert r.returncode == 0, r.stdout + r.stderr
    assert sentinel in r.stdout, r.stdout + r.stderr


# ---------------------------------------------------------------------------
# schedule-table cache
# ---------------------------------------------------------------------------

def test_schedule_tables_no_rebuild(monkeypatch):
    """Tables are built once per schedule value, never per call."""
    sched = certified_allreduce(4, 1 << 12, algo="ring")
    calls = {"n": 0}
    real = schedule_runner._step_tables

    def counting(step, n, n_chunks):
        calls["n"] += 1
        return real(step, n, n_chunks)

    monkeypatch.setattr(schedule_runner, "_step_tables", counting)
    schedule_runner.schedule_tables.cache_clear()
    t1 = schedule_runner.schedule_tables(sched)
    n_steps = sum(len(r) for r in sched.rounds)
    assert calls["n"] == n_steps
    t2 = schedule_runner.schedule_tables(sched)
    assert calls["n"] == n_steps          # second call: pure cache hit
    assert t1 is t2
    # frozen dataclasses hash by content: an equal re-lowering of the
    # same program shares the entry instead of rebuilding
    again = certified_allreduce(4, 1 << 12, algo="ring")
    schedule_runner.schedule_tables(again)
    assert calls["n"] == n_steps
    schedule_runner.schedule_tables.cache_clear()


# ---------------------------------------------------------------------------
# round slicing
# ---------------------------------------------------------------------------

def test_slice_rounds_windows():
    sched = certified_allreduce(4, 1 << 12, algo="ring")
    nr = len(sched.rounds)
    assert sched.slice_rounds(0, nr) is sched   # full window keeps the proof
    head = sched.slice_rounds(0, 2)
    tail = sched.slice_rounds(2, nr)
    assert len(head.rounds) == 2
    assert len(tail.rounds) == nr - 2
    # a partial window makes no end-state claim
    assert head.postcondition == "none"
    assert tail.postcondition == "none"
    parts = sched.split_rounds()
    assert len(parts) == nr
    assert all(len(p.rounds) == 1 for p in parts)
    with pytest.raises(ValueError):
        sched.slice_rounds(3, 2)
    with pytest.raises(ValueError):
        sched.slice_rounds(0, nr + 1)


# ---------------------------------------------------------------------------
# bucketing
# ---------------------------------------------------------------------------

def test_partition_tree_buckets():
    tree = {"a": np.zeros((100,), np.float32),
            "b": np.zeros((300,), np.float32),
            "c": np.zeros((50,), np.float32),
            "d": np.zeros((500,), np.float32)}
    # <= 0 bytes: everything in one bucket
    whole = partition_tree(tree, 0)
    assert len(whole) == 1
    assert whole[0].n_elems == 950
    buckets = partition_tree(tree, 1200)        # 300 float32 elements
    ids = [i for b in buckets for i in b.leaf_ids]
    assert ids == sorted(set(ids))              # every leaf exactly once
    assert sum(b.n_elems for b in buckets) == 950
    assert len(buckets) > 1
    # an oversized leaf still lands alone rather than being dropped
    assert any(b.leaf_ids == (3,) for b in buckets)


def test_partition_tree_leading_axis():
    tree = {"a": np.zeros((8, 100), np.float32)}
    b = partition_tree(tree, 0, leading_axis=True)[0]
    assert b.n_elems == 100                     # stacked axis not counted
    assert b.n_bytes == 400


# ---------------------------------------------------------------------------
# planned bucket_bytes dimension
# ---------------------------------------------------------------------------

def _entry(**over) -> PlanEntry:
    base = dict(op="all-reduce", bucket=22, size_bytes=4e6,
                group=(0, 1, 2, 3), algo="ring", algo_kwargs={},
                chunks=2, perm=(2, 0, 3, 1), expected_time=1e-3,
                identity_times={"ring": 2e-3}, solver_cost=1.0,
                oracle="simulator", bucket_bytes=1 << 20)
    base.update(over)
    return PlanEntry(**base)


def test_plan_entry_bucket_bytes_roundtrip():
    e = _entry()
    assert PlanEntry.from_dict(e.to_dict()) == e
    # plans serialized before the field existed default to "not planned"
    d = e.to_dict()
    del d["bucket_bytes"]
    assert PlanEntry.from_dict(d).bucket_bytes == 0.0


# ---------------------------------------------------------------------------
# OverlapConfig
# ---------------------------------------------------------------------------

def test_overlap_config_roundtrip():
    cfg = SessionConfig.from_dict(
        {"overlap": {"mode": "bucketed", "bucket_bytes": 1e6}})
    assert cfg.overlap.mode == "bucketed"
    assert cfg.overlap.bucket_bytes == 1e6
    assert SessionConfig.from_dict(cfg.to_dict()) == cfg
    # defaults: overlap off, bucket size delegated to the plan
    assert SessionConfig().overlap == OverlapConfig()


def test_overlap_config_validation_and_env():
    with pytest.raises(ValueError):
        OverlapConfig(mode="nope")
    cfg = SessionConfig.from_env(environ={
        "REPRO_OVERLAP_MODE": "fused",
        "REPRO_OVERLAP_BUCKET_BYTES": "2e6",
    })
    assert cfg.overlap.mode == "fused"
    assert cfg.overlap.bucket_bytes == 2e6


# ---------------------------------------------------------------------------
# lint rule: no raw run_schedule in workload layers
# ---------------------------------------------------------------------------

def test_lint_direct_schedule_run(tmp_path):
    body = ("def f(x, mesh, axis, sched):\n"
            "    return run_schedule(x, mesh, axis, sched)\n")
    train = tmp_path / "src" / "repro" / "train"
    train.mkdir(parents=True)
    (train / "bad.py").write_text(body)
    rules = [f.rule for f in lint_file(str(train / "bad.py"), str(tmp_path))]
    assert rules == ["direct-schedule-run"]
    # waiver comment is honored
    (train / "ok.py").write_text(
        "def f(x, mesh, axis, sched):\n"
        "    return run_schedule(x, mesh, axis, sched)"
        "  # lint: allow(direct-schedule-run)\n")
    assert lint_file(str(train / "ok.py"), str(tmp_path)) == []
    # the kernels layer itself is allowed to call the runner
    kern = tmp_path / "src" / "repro" / "kernels"
    kern.mkdir()
    (kern / "fine.py").write_text(body)
    assert lint_file(str(kern / "fine.py"), str(tmp_path)) == []


# ---------------------------------------------------------------------------
# 8-device host mesh: overlap runner == run_schedule, bitwise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algo", sorted(registered_builders()))
def test_overlapped_matches_run_schedule_8dev(algo):
    """Every algorithm, chunk_factor 1 and 2, a lane-aligned chunk (dense
    slab rows) and one that is not (``[1, chunk_len]`` rows): the
    overlap runner equals run_schedule bitwise alone, with compute
    shards, and resumed across the public state between two windows."""
    prog = """
import numpy as np
import jax
from jax.sharding import Mesh
from repro.collective import CollectiveOp, compile_op, JaxExecutor, get_builder
from repro.collective.builders import candidates
from repro.collective.passes import apply_permutation, chunk
from repro.analysis import require_certified
from repro.kernels.schedule_runner import (
    run_schedule, check_postcondition, schedule_tables, slab_shape)
from repro.kernels.overlap import (
    build_overlap_plan, run_overlapped, seed_state, finish_state)

algo = ALGO
n = 8
mesh = Mesh(np.array(jax.devices()[:n]), ("x",))
ex = JaxExecutor()
perm = [3, 1, 4, 7, 5, 0, 2, 6]
kind = get_builder(algo).kinds[0]
akw = next(a for b, a in candidates(kind, n) if b == algo)
# ring_sequential's second lap re-reduces the circulating partial sums:
# its postcondition holds over contributor sets, not over the numbers
numeric = algo != "ring_sequential"
for k in (1, 2):
    op = CollectiveOp(kind=kind, size_bytes=1 << 12, group=tuple(range(n)))
    prog = apply_permutation(compile_op(op, algo, **akw), perm)
    if k > 1:
        prog = chunk(prog, k)
    sched = ex.lower_schedule(prog)
    require_certified(prog, sched)
    for chunk_len, dense in ((256 * k, True), (6 * k, False)):
        assert (slab_shape(chunk_len, k)[1] == 128) == dense
        d = chunk_len * {"replicated": sched.n_chunks, "sharded": 1,
                         "addressed": n}[sched.init]
        x = np.arange(n * d, dtype=np.float32).reshape(n, d) / (n * d)
        ref = np.asarray(run_schedule(x, mesh, "x", sched,
                                      use_pallas_add=False))
        assert ref.shape == (n, sched.n_chunks, chunk_len)
        if numeric:
            assert not check_postcondition(sched, x, ref), (k, chunk_len)
        # no-compute overlap: bitwise identical to the plain runner
        out = jax.jit(lambda x: run_overlapped(
            x, mesh, "x", sched, use_pallas_add=False)[0])(x)
        assert np.array_equal(ref, np.asarray(out)), (k, chunk_len)
        # with compute shards interleaved: same result, shards all ran
        comp = [lambda i=i: jax.numpy.sum(jax.numpy.ones((16, 16)) * i)
                for i in range(5)]
        plan = build_overlap_plan(sched, 5)
        out2, res = jax.jit(lambda x: run_overlapped(
            x, mesh, "x", plan, compute=comp, use_pallas_add=False))(x)
        assert np.array_equal(ref, np.asarray(out2)), (k, chunk_len)
        assert [float(r) for r in res] == [256.0 * i for i in range(5)]
        # sliced composition through the public [n, n_chunks + 1,
        # chunk_len] state: window [0, m) then [m, end) == one shot
        m = max(1, len(sched.rounds) // 2)
        st = seed_state(sched, x)
        for window in ((0, m), (m, None)):
            assert st.shape == (n, sched.n_chunks + 1, chunk_len)
            st = jax.jit(lambda st: run_overlapped(
                None, mesh, "x", sched, state=st, rounds=window,
                return_state=True, use_pallas_add=False)[0])(st)
        assert np.array_equal(ref, np.asarray(finish_state(sched, st))), \
            (k, chunk_len)
    print(algo, k, "OK")
assert schedule_tables.cache_info().hits > 0
print("OVERLAP RUNNER OK")
""".replace("ALGO", repr(algo))
    _run_sub(prog, "OVERLAP RUNNER OK")


# ---------------------------------------------------------------------------
# the table path's lowering: row reads and writes on a major chunk axis
# ---------------------------------------------------------------------------

_TABLE_TARGETS = ("reducer", "ring", "halving_doubling")


@pytest.fixture(scope="module")
def table_lowerings(tmp_path_factory):
    """HLO facts of the certified table path, lowered on a 4-device mesh:
    the bucketed reducer (ring, buckets padded by its lane quantum) and
    ``schedule_body`` for a ring and for halving-doubling (steps of
    several chunks).  Per target: whether any gather or scatter is left,
    the chunk count, and the result shapes of the dynamic slices and
    updates in the ``certified.table`` scope."""
    out = tmp_path_factory.mktemp("tables") / "tables.json"
    prog = r"""
import json, re, sys
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.kernels.schedule_runner import schedule_body, slab_shape
from repro.train.overlap_grads import OverlapGradReducer, certified_allreduce

mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
rows = NamedSharding(mesh, P("data"))
sds = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=rows)
facts = {}

def record(name, lowered, n_chunks):
    text = lowered.as_text(dialect="hlo", debug_info=True)
    shapes = []
    for line in text.splitlines():
        op = re.search(r"= f32\[([0-9,]*)\]\S* (dynamic-slice|"
                       r"dynamic-update-slice)\(", line)
        if op and "certified.table" in line:
            shapes.append([int(d) for d in op.group(1).split(",")])
    facts[name] = {"gather": "gather(" in text,
                   "scatter": "scatter(" in text,
                   "n_chunks": n_chunks, "table_shapes": shapes}

sched = certified_allreduce(4, 1 << 14, algo="ring")
red = OverlapGradReducer(mesh, "data", sched, bucket_bytes=1 << 14)
tree = {"w": sds((4, 96, 64)), "b": sds((4, 1000))}
record("reducer", jax.jit(lambda g: red(g)[0]).lower(tree), sched.n_chunks)
for algo in ("ring", "halving_doubling"):
    sched = certified_allreduce(4, 1 << 14, algo=algo)
    buf = sds((4, sched.n_chunks) + slab_shape(1024))
    record(algo, jax.jit(schedule_body(mesh, "data", sched, False))
           .lower(buf), sched.n_chunks)
json.dump(facts, open(sys.argv[1], "w"))
print("TABLES LOWERED")
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", prog, str(out)],
                       capture_output=True, text=True, env=env,
                       timeout=600, cwd=REPO)
    assert r.returncode == 0 and "TABLES LOWERED" in r.stdout, \
        r.stdout + r.stderr
    return json.loads(out.read_text())


@pytest.mark.parametrize("target", _TABLE_TARGETS)
def test_table_path_has_no_gather_or_scatter(table_lowerings, target):
    facts = table_lowerings[target]
    assert not facts["gather"], target
    assert not facts["scatter"], target


@pytest.mark.parametrize("target", _TABLE_TARGETS)
def test_table_rows_keep_the_chunk_on_a_major_axis(table_lowerings,
                                                   target):
    """Every lane-aligned buffer the table scope slices or updates is
    ``[rows, tiles, 128]``: the chunk (or the one row read) on axis 0,
    each row whole lane rows."""
    facts = table_lowerings[target]
    shapes = facts["table_shapes"]
    assert shapes, target
    updates = [s for s in shapes if s[0] == facts["n_chunks"]]
    assert updates, shapes
    for shape in shapes:
        assert len(shape) == 3 and shape[2] == 128, (target, shape)
        assert shape[0] in (1, facts["n_chunks"]), (target, shape)


# ---------------------------------------------------------------------------
# 8-device host mesh: overlapped train step == baseline
# ---------------------------------------------------------------------------

def test_overlap_train_step_equivalence_8dev():
    prog = """
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.configs import get_config
from repro.data import SyntheticLM, host_batch
from repro.models import get_model
from repro.optim import AdamWConfig
from repro.train import init_state, make_train_step, jit_train_step
from repro.train.overlap_grads import (
    OverlapGradReducer, certified_allreduce, partition_tree)
from repro.kernels.overlap import run_overlapped
from repro.kernels.schedule_runner import check_postcondition

n = 8
mesh = Mesh(np.array(jax.devices()[:n]), ("data",))
cfg = get_config("qwen2-0.5b").smoke()
model = get_model(cfg)
opt = AdamWConfig(lr=1e-3)
state = init_state(model, jax.random.PRNGKey(0))
ds = SyntheticLM(cfg.vocab_size, 16, n, seed=0)
batch = host_batch(ds, 0)

base_step = jax.jit(make_train_step(model, opt))
base_state, base_metrics = base_step(state, batch)
base_grads = jax.jit(jax.grad(model.loss))(state.params, batch)

# per-shard grads, stacked [n, ...] — what the shard_map hands the reducer
shard = lambda l, i: l[i * (l.shape[0] // n):(i + 1) * (l.shape[0] // n)]
gstack = jax.tree.map(
    lambda *ls: jnp.stack(ls),
    *[jax.jit(jax.grad(model.loss))(
        state.params, jax.tree.map(lambda l, i=i: shard(l, i), batch))
      for i in range(n)])

pb = sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(state.params))
bb = pb / 3.5
sched = certified_allreduce(n, bb, algo="ring",
                            perm=[3, 1, 4, 7, 5, 0, 2, 6], chunk_factor=2)

for mode in ("bucketed", "fused"):
    red = OverlapGradReducer(mesh, "data", sched, bucket_bytes=bb, mode=mode)
    # reducer alone: mean of per-shard grads == baseline grads (fp tol)
    mean_tree = jax.jit(lambda g: red(g)[0])(gstack)
    for a, b in zip(jax.tree.leaves(mean_tree), jax.tree.leaves(base_grads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-6)
    # full jitted step: loss / grad-norm metrics match the baseline
    step = jit_train_step(model, opt, cfg, mesh, None, None, donate=False,
                          overlap=mode, reducer=red, axis="data")
    new_state, metrics = step(state, batch)
    np.testing.assert_allclose(float(metrics["loss"]),
                               float(base_metrics["loss"]),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(float(metrics["grad_norm"]),
                               float(base_metrics["grad_norm"]),
                               rtol=2e-4, atol=1e-5)
    # params: absolute bound only (Adam's 1st step is sign-like where
    # grads ~ 0, so relative comparison there is ill-conditioned)
    for a, b in zip(jax.tree.leaves(new_state.params),
                    jax.tree.leaves(base_state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0, atol=1e-4)
    print(mode, "OK", float(metrics["loss"]))

# per-bucket payloads satisfy the schedule's declared postcondition
leaves = [np.asarray(l, np.float32).reshape(n, -1)
          for l in jax.tree.leaves(gstack)]
q = sched.n_chunks * max(1, sched.chunk_factor)
for b in partition_tree(state.params, bb)[:2]:
    flat = np.concatenate([leaves[i] for i in b.leaf_ids], axis=1)
    payload = np.pad(flat, ((0, 0), (0, (-flat.shape[1]) % q)))
    out, _ = run_overlapped(payload, mesh, "data", sched,
                            use_pallas_add=False)
    bad = check_postcondition(sched, payload, np.asarray(out), atol=1e-4)
    assert not bad, bad

# Session facade: a planned, certified reducer end to end
from repro.session import Session, SessionConfig
scfg = SessionConfig.from_dict({
    "fabric": {"kind": "datacenter", "nodes": n, "scramble_seed": 1},
    "solver": {"budget": {"iters": 60, "chains": 2}},
    "payload_bytes": float(pb),
    "workload": "train",
    "overlap": {"mode": "bucketed"},
})
with Session(scfg) as s:
    red2 = s.overlap_step(mesh, "data")
mean2 = jax.jit(lambda g: red2(g)[0])(gstack)
for a, b in zip(jax.tree.leaves(mean2), jax.tree.leaves(base_grads)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-4, atol=1e-6)
print("TRAIN EQUIV DONE")
"""
    _run_sub(prog, "TRAIN EQUIV DONE")


# ---------------------------------------------------------------------------
# 8-device host mesh: serve engine armed overlap
# ---------------------------------------------------------------------------

def test_serve_overlap_8dev():
    prog = """
import jax
import numpy as np
from jax.sharding import Mesh

from repro.configs import get_config
from repro.core import make_datacenter, probe_fabric, scramble
from repro.models import get_model
from repro.plan import CollectiveRequest, JobMix, PlanCompiler, SolveBudget
from repro.serve import GenerationConfig, GenerationEngine
from repro import obs

fab, _ = scramble(make_datacenter(8, seed=0), seed=1)
probe = probe_fabric(fab, seed=0)
mix = JobMix((CollectiveRequest("all-gather", 1e6),
              CollectiveRequest("all-reduce", 4e6)), name="serve")
plan = PlanCompiler(fabric=fab,
                    budget=SolveBudget(iters=60, chains=2)).compile(probe, mix)
assert plan.lookup("all-reduce", 4e6).bucket_bytes > 0  # planned dimension

cfg = get_config("qwen2-0.5b").smoke()
model = get_model(cfg)
params = model.init(jax.random.PRNGKey(0))
mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
prompts = [[1, 2, 3, 4], [4, 3, 2, 1]]

base = GenerationEngine(
    model, params, GenerationConfig(max_new_tokens=5, eos_token=-1),
    plan=plan).generate(prompts)

eng = GenerationEngine(
    model, params, GenerationConfig(max_new_tokens=5, eos_token=-1),
    plan=plan)
sched = eng.arm_overlap(mesh, "data", payload_bytes=1e6)
assert sched.postcondition == "all_gather"
outs = eng.generate(prompts)
assert outs == base, (outs, base)
assert obs.metrics().counter("serve.overlap.postcondition_ok").value >= 1
print("SERVE OVERLAP DONE")
"""
    _run_sub(prog, "SERVE OVERLAP DONE")
