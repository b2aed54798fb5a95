"""Generalized lowering benchmark: certify everything, execute for real.

Three gates, one artifact (``BENCH_lowering.json``):

* **bisimulation matrix** — every registered builder x kind x
  n in {4, 8, 16, 64} lowers to a ppermute schedule and bisimulates
  against its IR with zero mismatches; per-program lower+certify cost
  is tracked in µs so the translation-validation gate stays cheap
  relative to a plan compile;
* **mutant kill floor** — the seeded lowering-mutant batch
  (:func:`repro.analysis.lowering_kill_rate`) must be killed at
  >= ``KILL_FLOOR`` — the validator's teeth, pinned so a future
  refactor can't quietly blunt them;
* **end-to-end execution** — ring (control) plus the newly-lowerable
  halving_doubling and double_binary_tree run planned-vs-identity rank
  orders through real ``ppermute`` on a host-local 8-device mesh in a
  subprocess (``XLA_FLAGS`` device-count pinning must precede jax
  init), numeric postconditions checked, orders priced with
  ``SimExecutor`` for the simulated speedup.

``ring_sequential`` is certified in the matrix but excluded from
numeric execution: its second lap re-reduces circulating partials —
sound in the idempotent contributor-set domain and as a pricing regime
model, but numerically double-counting (see its builder docstring).

Usage::

    PYTHONPATH=src python benchmarks/lowering_e2e.py [--smoke] [--out PATH]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

if __package__ in (None, ""):  # runnable as a plain script without PYTHONPATH
    _repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(_repo_root, "src"))

import numpy as np

try:
    from .common import std_fabric, write_json
except ImportError:   # plain-script mode: benchmarks/ is sys.path[0]
    from common import std_fabric, write_json

from repro.analysis import bisimulate, lowering_kill_rate
from repro.collective import (
    CollectiveOp,
    JaxExecutor,
    SimExecutor,
    compile_op,
    get_builder,
    registered_builders,
)
from repro.collective.builders import candidates
from repro.collective.passes import apply_permutation

KILL_FLOOR = 0.95
SIZE = 1 << 20

#: numerically executed algorithms: ring is the legacy control, the
#: other two only became executable with the generalized lowering
E2E_ALGOS = ("ring", "halving_doubling", "double_binary_tree")
E2E_N = 8

_E2E_SCRIPT = r"""
import json, os, sys, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import numpy as np
from jax.sharding import Mesh

from repro.analysis import require_certified
from repro.collective import CollectiveOp, JaxExecutor, compile_op
from repro.collective.passes import apply_permutation
from repro.kernels.schedule_runner import check_postcondition, run_schedule

cfg = json.load(open(sys.argv[1]))
n = cfg["n"]
mesh = Mesh(np.array(jax.devices()[:n]), ("x",))
ex = JaxExecutor()
out = {}
for algo, perms in cfg["cases"].items():
    out[algo] = {}
    for label, perm in perms.items():
        op = CollectiveOp(kind="allreduce", size_bytes=cfg["size_bytes"],
                          group=tuple(range(n)))
        prog = apply_permutation(compile_op(op, algo), perm)
        sched = ex.lower_schedule(prog)
        require_certified(prog, sched)
        d = cfg["size_bytes"] // 4
        x = np.arange(n * d, dtype=np.float32).reshape(n, d) / (n * d)
        t0 = time.time()
        res = np.asarray(run_schedule(x, mesh, "x", sched,
                                      use_pallas_add=False))
        t_first = time.time() - t0
        t0 = time.time()
        res = np.asarray(run_schedule(x, mesh, "x", sched,
                                      use_pallas_add=False))
        t_steady = time.time() - t0
        bad = check_postcondition(sched, x, res)
        out[algo][label] = {"postcondition_ok": not bad,
                            "mismatches": bad[:4],
                            "first_call_ms": t_first * 1e3,
                            "steady_ms": t_steady * 1e3}
json.dump(out, open(cfg["out"], "w"))
print("E2E DONE")
"""


def _bisim_matrix(n_list) -> tuple:
    rows, matrix, n_bad = [], [], 0
    for algo in sorted(registered_builders()):
        b = get_builder(algo)
        for kind in b.kinds:
            for n in n_list:
                for a, akw in candidates(kind, n):
                    if a != algo:
                        continue
                    op = CollectiveOp(kind=kind, size_bytes=SIZE,
                                      group=tuple(range(n)))
                    prog = compile_op(op, algo, **akw)
                    t0 = time.time()
                    findings, stats = bisimulate(prog)
                    dt = time.time() - t0
                    errs = [f for f in findings if f.severity == "error"]
                    ok = stats["bisimilar"] and not errs
                    n_bad += 0 if ok else 1
                    matrix.append({"algorithm": algo, "kind": kind,
                                   "n": n, "ok": ok,
                                   "n_steps": stats["n_steps"],
                                   "n_transfers": stats["n_transfers"],
                                   "certify_us": round(dt * 1e6, 1)})
        n_max = max(n_list)
        per = [m for m in matrix if m["algorithm"] == algo]
        rows.append({"name": f"lowering_bisim_{algo}",
                     "us": max(m["certify_us"] for m in per),
                     "derived": f"programs={len(per)};"
                                f"ok={sum(m['ok'] for m in per)};"
                                f"n_max={n_max}"})
    return rows, matrix, n_bad


def _kill_rate(n: int = 8, seed: int = 0) -> tuple:
    progs = []
    for algo in sorted(registered_builders()):
        b = get_builder(algo)
        for kind in b.kinds:
            for a, akw in candidates(kind, n):
                if a == algo:
                    op = CollectiveOp(kind=kind, size_bytes=SIZE,
                                      group=tuple(range(n)))
                    progs.append(compile_op(op, algo, **akw))
    t0 = time.time()
    rate, survivors = lowering_kill_rate(progs, seed=seed)
    return rate, survivors, len(progs), time.time() - t0


def _plan_orders(seed: int = 0) -> dict:
    """Planned (solver) vs identity rank order per e2e algorithm."""
    from repro.core import make_cost_model, solve

    try:
        from .common import probed_cost
    except ImportError:
        from common import probed_cost

    fab = std_fabric(E2E_N, seed=seed)
    c = probed_cost(fab, SIZE, seed=seed)
    sim = SimExecutor(fab)
    orders = {}
    for algo in E2E_ALGOS:
        m = make_cost_model(get_builder(algo).cost_model, c, SIZE)
        planned = [int(x) for x in solve(m, iters=300, seed=seed).perm]
        identity = list(range(E2E_N))
        op = CollectiveOp(kind="allreduce", size_bytes=SIZE,
                          group=tuple(range(E2E_N)))
        t_id = sim.estimate(apply_permutation(compile_op(op, algo), identity))
        t_pl = sim.estimate(apply_permutation(compile_op(op, algo), planned))
        orders[algo] = {"identity": identity, "planned": planned,
                        "sim_identity_s": float(t_id),
                        "sim_planned_s": float(t_pl),
                        "sim_speedup": float(t_id / max(t_pl, 1e-30))}
    return orders


def _run_e2e(orders: dict, workdir: str) -> dict:
    cfg_path = os.path.join(workdir, "lowering_e2e_cfg.json")
    out_path = os.path.join(workdir, "lowering_e2e_out.json")
    script = os.path.join(workdir, "lowering_e2e_run.py")
    with open(script, "w") as f:
        f.write(_E2E_SCRIPT)
    with open(cfg_path, "w") as f:
        json.dump({"n": E2E_N, "size_bytes": 1 << 12, "out": out_path,
                   "cases": {a: {"identity": o["identity"],
                                 "planned": o["planned"]}
                             for a, o in orders.items()}}, f)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    # a host mesh: the child never reaches for a chip this process may hold
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, script, cfg_path], cwd=repo,
                          env=env, capture_output=True, text=True,
                          timeout=900)
    if proc.returncode != 0 or "E2E DONE" not in proc.stdout:
        raise RuntimeError(f"e2e subprocess failed: {proc.stderr[-2000:]}")
    with open(out_path) as f:
        return json.load(f)


def run(smoke: bool = False, out_path: str = "BENCH_lowering.json",
        seed: int = 0):
    n_list = (4, 8, 16) if smoke else (4, 8, 16, 64)
    rows, matrix, n_bad = _bisim_matrix(n_list)

    rate, survivors, n_progs, kill_dt = _kill_rate(seed=seed)
    rows.append({"name": "lowering_mutant_kill", "us": kill_dt * 1e6,
                 "derived": f"rate={rate:.3f};programs={n_progs};"
                            f"floor={KILL_FLOOR}"})

    orders = _plan_orders(seed=seed)
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        e2e = _run_e2e(orders, td)
    e2e_ok = all(v["postcondition_ok"]
                 for per in e2e.values() for v in per.values())
    for algo, per in e2e.items():
        rows.append({
            "name": f"lowering_e2e_{algo}",
            "us": per["planned"]["steady_ms"] * 1e3,
            "derived": f"post_ok={all(v['postcondition_ok'] for v in per.values())};"
                       f"sim_speedup={orders[algo]['sim_speedup']:.2f}"})

    ok = n_bad == 0 and rate >= KILL_FLOOR and e2e_ok
    rows.append({"name": "lowering_gate", "us": 0.0,
                 "derived": f"bisim_bad={n_bad};kill={rate:.3f};"
                            f"e2e_ok={e2e_ok};{'OK' if ok else 'FAIL'}"})

    results = {
        "benchmark": "lowering_e2e",
        "smoke": smoke,
        "n_list": list(n_list),
        "bisim": {"n_programs": len(matrix), "n_bad": n_bad,
                  "matrix": matrix},
        "mutants": {"kill_rate": rate, "floor": KILL_FLOOR,
                    "n_programs": n_progs,
                    "survivors": [list(s) for s in survivors]},
        "e2e": {"n": E2E_N,
                "excluded": {"ring_sequential":
                             "regime model; numerically double-counts"},
                "orders": {a: {k: v for k, v in o.items()
                               if k != "identity"}
                           for a, o in orders.items()},
                "runs": e2e},
        "gate_ok": bool(ok),
    }
    for r in rows:
        print(f"{r['name']},{r['us']:.3f},{r['derived']}")
    write_json(out_path, results, seed)
    if not ok:
        raise RuntimeError(
            f"lowering gate failed: bisim_bad={n_bad} kill={rate:.3f} "
            f"e2e_ok={e2e_ok}")
    return results


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: drop the n=64 bisim column")
    ap.add_argument("--out", default="BENCH_lowering.json")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    run(smoke=args.smoke, out_path=args.out, seed=args.seed)


if __name__ == "__main__":
    main()
