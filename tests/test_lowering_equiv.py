"""Translation validation: every lowering is bisimilar to its IR.

The matrix half parametrizes all registered builders across group
sizes and rewrite variants and demands a zero-mismatch bisimulation;
the adversarial half hand-corrupts schedules (and runs the seeded
mutant batch) to prove the validator actually rejects broken
lowerings.  The e2e half runs a certified schedule through real
``ppermute`` on a host-local mesh in a subprocess.
"""

import dataclasses
import json
import os
import random
import subprocess
import sys
import textwrap

import pytest

from repro.analysis import (
    VerificationError,
    bisimulate,
    certify_stages,
    lowering_kill_rate,
    lowering_mutants,
    require_certified,
)
from repro.analysis.lint import lint_file
from repro.collective import (
    CollectiveOp,
    JaxExecutor,
    compile_op,
    get_builder,
    registered_builders,
)
from repro.collective.builders import candidates
from repro.collective.passes import apply_permutation, chunk, fuse_rounds

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _build(algo, kind, akw, n):
    op = CollectiveOp(kind=kind, size_bytes=1 << 16, group=tuple(range(n)))
    return compile_op(op, algo, **dict(akw))


def _matrix(n_list=(4, 8, 16)):
    cases = []
    for algo in sorted(registered_builders()):
        b = get_builder(algo)
        for kind in b.kinds:
            for n in n_list:
                for a, akw in candidates(kind, n):
                    if a == algo:
                        cases.append((algo, kind, n,
                                      tuple(sorted(akw.items()))))
    return cases


MATRIX = _matrix()


def test_matrix_covers_every_registered_algorithm():
    assert {algo for algo, *_ in MATRIX} == set(registered_builders())
    assert {n for _, _, n, _ in MATRIX} == {4, 8, 16}


@pytest.mark.parametrize("variant", ["identity", "permuted", "chunked"])
@pytest.mark.parametrize("algo,kind,n,akw", MATRIX,
                         ids=[f"{a}-{k}-n{n}" for a, k, n, _ in MATRIX])
def test_lower_and_bisimulate_zero_mismatches(algo, kind, n, akw, variant):
    prog = _build(algo, kind, akw, n)
    if variant == "permuted":
        perm = list(range(n))
        random.Random(n).shuffle(perm)
        prog = apply_permutation(prog, perm)
    elif variant == "chunked":
        prog = chunk(prog, 2)
    findings, stats = bisimulate(prog)
    assert [f for f in findings if f.severity == "error"] == []
    assert stats["bisimilar"]
    assert stats["n_mismatched_entries"] == 0


@pytest.mark.parametrize("algo", sorted(registered_builders()))
def test_certify_stages_all_ok(algo):
    kind = get_builder(algo).kinds[0]
    akw = next(a for b, a in candidates(kind, 8) if b == algo)
    prog = _build(algo, kind, akw, 8)
    perm = list(range(8))
    random.Random(7).shuffle(perm)
    stages = certify_stages(prog, perm=perm, chunk_k=2)
    assert [s["stage"] for s in stages] == \
        ["base", "apply_permutation", "chunk", "fuse_rounds"]
    assert all(s["ok"] for s in stages), stages


# ---------------------------------------------------------------------------
# adversarial: the validator must reject hand-broken schedules
# ---------------------------------------------------------------------------

def _lowered():
    prog = _build("halving_doubling", "allreduce", (), 8)
    return prog, JaxExecutor().lower_schedule(prog)


def _codes(prog, sched):
    findings, stats = bisimulate(prog, sched)
    assert not stats["bisimilar"]
    return {f.code for f in findings if f.severity == "error"}


def test_dropped_step_is_lost_reduction():
    prog, sched = _lowered()
    rnds = list(sched.rounds)
    rnds[0] = rnds[0][:-1]
    codes = _codes(prog, dataclasses.replace(sched, rounds=tuple(rnds)))
    assert "LOST_REDUCTION" in codes


def test_swapped_tag_is_extra_transfer_and_lost_reduction():
    prog, sched = _lowered()
    rnds = list(sched.rounds)
    step = rnds[0][0]
    assert step.op == "reduce"
    rnds[0] = (dataclasses.replace(step, op="copy"),) + rnds[0][1:]
    codes = _codes(prog, dataclasses.replace(sched, rounds=tuple(rnds)))
    assert {"EXTRA_TRANSFER", "LOST_REDUCTION"} <= codes


def test_missing_round_is_schedule_shape():
    prog, sched = _lowered()
    broken = dataclasses.replace(sched, rounds=sched.rounds[:-1])
    codes = _codes(prog, broken)
    assert codes == {"SCHEDULE_SHAPE"}


def test_flipped_recv_mask_drops_the_transfer():
    prog, sched = _lowered()
    rnds = list(sched.rounds)
    step = rnds[0][0]
    dst = step.links[0][1]
    recv = list(step.recv_mask)
    recv[dst] = False
    rnds[0] = (dataclasses.replace(step, recv_mask=tuple(recv)),) \
        + rnds[0][1:]
    codes = _codes(prog, dataclasses.replace(sched, rounds=tuple(rnds)))
    assert "LOST_REDUCTION" in codes


def test_duplicated_step_is_extra_transfer():
    prog, sched = _lowered()
    rnds = list(sched.rounds)
    rnds[0] = rnds[0] + (rnds[0][0],)
    codes = _codes(prog, dataclasses.replace(sched, rounds=tuple(rnds)))
    assert codes == {"EXTRA_TRANSFER"}


def test_require_certified_raises_on_broken_schedule():
    prog, sched = _lowered()
    require_certified(prog, sched)  # the genuine artifact passes
    broken = dataclasses.replace(sched, rounds=sched.rounds[:-1])
    with pytest.raises(VerificationError):
        require_certified(prog, broken)


def test_lowering_mutants_are_distinct_and_broken():
    prog, _ = _lowered()
    muts = lowering_mutants(prog, seed=3)
    assert len(muts) >= 6
    fps = [s.fingerprint() for _, s in muts]
    assert len(set(fps)) == len(fps)
    assert {k for k, _ in muts} == {"drop_step", "flip_mask", "swap_tag"}


def test_lowering_mutant_kill_rate_at_least_95_percent():
    progs = [_build(a, k, akw, n) for a, k, n, akw in _matrix(n_list=(8,))]
    rate, survivors = lowering_kill_rate(progs, seed=0)
    assert rate >= 0.95, survivors


# ---------------------------------------------------------------------------
# plan-compiler integration: cache key + candidate filtering
# ---------------------------------------------------------------------------

def test_verify_cache_key_distinguishes_rewrites():
    # PR-8 regression: the old key (algo, kwargs, kind, n) replayed a
    # base program's verdict for its chunked/fused rewrites.
    from repro.plan.compiler import PlanCompiler

    base = _build("ring_sequential", "allreduce", (), 8)
    chunked = chunk(base, 4)
    fused, n_fused = fuse_rounds(base)
    assert n_fused > 0  # fusion actually changed the round structure
    keys = {PlanCompiler._verify_key(p) for p in (base, chunked, fused)}
    assert len(keys) == 3


def test_candidate_algorithms_lowerable_filter():
    from repro.plan.compiler import candidate_algorithms

    allc = candidate_algorithms("all-reduce", 8)
    low = candidate_algorithms("all-reduce", 8, lowerable_only=True)
    assert low  # generalized lowering: nothing is filtered out today
    assert set(a for a, _ in low) <= set(a for a, _ in allc)
    assert set(a for a, _ in low) <= set(JaxExecutor().lowerable_algorithms())


def test_session_lower_certifies_every_algorithm():
    ex = JaxExecutor()
    assert set(ex.lowerable_algorithms()) == set(registered_builders())
    prog = _build("bcube", "allreduce", (("base", 2),), 8)
    low = ex.lower(prog)
    assert low.schedule is not None
    require_certified(prog, low.schedule)


# ---------------------------------------------------------------------------
# lint rules
# ---------------------------------------------------------------------------

def _lint_src(tmp_path, rel, source):
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))
    return lint_file(str(path), str(tmp_path))


def test_lint_lowered_construction(tmp_path):
    bad = _lint_src(tmp_path, "src/repro/plan/mod.py", """\
        from repro.collective import LoweredSchedule
        s = LoweredSchedule(algorithm="x", kind="allreduce", n=2,
                            order=(0, 1), n_chunks=2, chunk_bytes=8,
                            init="replicated", postcondition="allreduce",
                            rounds=())
        """)
    assert [f.rule for f in bad] == ["lowered-construction"]
    # the lowering layer itself is exempt
    ok = _lint_src(tmp_path, "src/repro/collective/executors.py", """\
        from repro.collective import PermuteStep
        s = PermuteStep(links=(), op="copy", chunks=(),
                        send_mask=(), recv_mask=(), round_index=0)
        """)
    assert ok == []
    ok = _lint_src(tmp_path, "src/repro/analysis/mod.py", """\
        from repro.collective import PermuteStep
        s = PermuteStep(links=(), op="copy", chunks=(),
                        send_mask=(), recv_mask=(), round_index=0)
        """)
    assert ok == []


def test_lint_module_level_np_random(tmp_path):
    bad = _lint_src(tmp_path, "src/repro/mod.py", """\
        import numpy as np
        NOISE = np.random.rand(8)
        """)
    assert [f.rule for f in bad] == ["module-level-np-random"]
    ok = _lint_src(tmp_path, "src/repro/mod2.py", """\
        import numpy as np

        RNG = np.random.default_rng(0)

        def noise():
            return np.random.rand(8)
        """)
    assert ok == []


# ---------------------------------------------------------------------------
# e2e: a certified general schedule runs through real ppermute
# ---------------------------------------------------------------------------

_E2E_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import random
import sys

import jax
import numpy as np
from jax.sharding import Mesh

from repro.analysis import require_certified
from repro.collective import CollectiveOp, JaxExecutor, compile_op, get_builder
from repro.collective.builders import candidates
from repro.collective.passes import apply_permutation, chunk
from repro.kernels.overlap import finish_state, run_overlapped, seed_state
from repro.kernels.schedule_runner import check_postcondition, run_schedule

algo = sys.argv[1]
n = 8
mesh = Mesh(np.array(jax.devices()[:n]), ("x",))
ex = JaxExecutor()
kind = get_builder(algo).kinds[0]
akw = next(a for b, a in candidates(kind, n) if b == algo)
perm = list(range(n))
random.Random(5).shuffle(perm)
for k in (1, 2):
    op = CollectiveOp(kind=kind, size_bytes=n * 8 * 4,
                      group=tuple(range(n)))
    prog = apply_permutation(compile_op(op, algo, **akw), perm)
    if k > 1:
        prog = chunk(prog, k)
    sched = ex.lower_schedule(prog)
    require_certified(prog, sched)
    # a lane-aligned chunk (slab rows of 128 lanes) and one that is not
    for chunk_len in (128 * k, 3 * k):
        d = chunk_len * {"replicated": sched.n_chunks, "sharded": 1,
                         "addressed": n}[sched.init]
        x = np.arange(n * d, dtype=np.float32).reshape(n, d)
        out = np.asarray(run_schedule(x, mesh, "x", sched,
                                      use_pallas_add=False))
        # ring_sequential's second lap re-reduces the partial sums: its
        # postcondition holds over contributor sets, not numerically
        if algo != "ring_sequential":
            bad = check_postcondition(sched, x, out)
            assert not bad, (algo, k, chunk_len, bad)
        # resumed across the public state: two windows of rounds
        m = max(1, len(sched.rounds) // 2)
        st = seed_state(sched, x)
        for window in ((0, m), (m, None)):
            st, _ = jax.jit(lambda st: run_overlapped(
                None, mesh, "x", sched, state=st, rounds=window,
                return_state=True, use_pallas_add=False))(st)
        resumed = np.asarray(finish_state(sched, st))
        assert np.array_equal(out, resumed), (algo, k, chunk_len)
print("E2E LOWERING OK")
"""


@pytest.mark.parametrize("algo", sorted(registered_builders()))
def test_e2e_certified_schedule_runs_on_host_mesh(tmp_path, algo):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    script = tmp_path / "e2e_lowering.py"
    script.write_text(_E2E_SCRIPT)
    proc = subprocess.run([sys.executable, str(script), algo], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "E2E LOWERING OK" in proc.stdout
