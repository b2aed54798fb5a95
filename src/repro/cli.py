"""The single ``python -m repro`` command-line interface.

One argparse tree, five subcommands, all round-tripping
:class:`repro.session.SessionConfig`::

    python -m repro probe --fabric datacenter --nodes 64
    python -m repro plan  --mesh 8x8 --dry-run
    python -m repro train --arch qwen2-0.5b --mesh 1x1 --steps 20
    python -m repro serve --arch qwen2-0.5b --max-new 16
    python -m repro bench --smoke

Every subcommand accepts ``--config session.json`` plus ``REPRO_*``
environment overrides (see :meth:`SessionConfig.from_env`) plus explicit
flags, in that precedence order; ``--dump-config`` prints the resolved
config as JSON and exits, so a flag-built config can be saved and
re-fed via ``--config`` unchanged.

The old ``python -m repro.launch.train`` / ``repro.launch.serve`` entry
points remain as deprecation shims that delegate here.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List, Optional, Sequence

from repro import obs

__all__ = ["main", "build_parser", "session_config_from_args",
           "model_config", "run_obs_scenario"]


# ---------------------------------------------------------------------------
# shared session arguments
# ---------------------------------------------------------------------------

def _add_session_args(ap: argparse.ArgumentParser) -> None:
    g = ap.add_argument_group("session config")
    g.add_argument("--config", default=None, metavar="JSON",
                   help="SessionConfig JSON file to start from")
    g.add_argument("--fabric", default=None,
                   choices=["datacenter", "tpu-fleet", "live"])
    g.add_argument("--nodes", type=int, default=None,
                   help="datacenter fabric size")
    g.add_argument("--pods", type=int, default=None,
                   help="tpu-fleet pod count")
    g.add_argument("--pod-shape", default=None, metavar="AxB")
    g.add_argument("--scramble-seed", type=int, default=None,
                   help="relabel nodes (the cloud's random IP list)")
    g.add_argument("--fabric-seed", type=int, default=None)
    g.add_argument("--probe-seed", type=int, default=None)
    g.add_argument("--probe-mode", default=None, choices=["dense", "sparse"],
                   help="dense n^2 probing or budgeted sparse probing")
    g.add_argument("--sparse", action="store_true", default=None,
                   help="shorthand for --probe-mode sparse")
    g.add_argument("--probe-budget", type=float, default=None,
                   help="sparse probe budget as a fraction of n(n-1)")
    g.add_argument("--mesh", default=None, metavar="AxB[xC]",
                   help="N-D mesh shape, e.g. 8x8 or 2x16x16")
    g.add_argument("--axes", default=None, metavar="a,b",
                   help="mesh axis names, e.g. data,model")
    g.add_argument("--payload-bytes", type=float, default=None)
    g.add_argument("--moe", action="store_true", default=None,
                   help="add the EP all-to-all to the default mix")
    g.add_argument("--plan-cache-dir", default=None,
                   help="persist compiled plans across launches")
    g.add_argument("--iters", type=int, default=None,
                   help="solver SA iterations per entry")
    g.add_argument("--chains", type=int, default=None)
    g.add_argument("--solver-engine", default=None,
                   choices=["vectorized", "reference"])
    g.add_argument("--solver-backend", default=None,
                   choices=["numpy", "jax"])
    g.add_argument("--solver-seed", type=int, default=None)
    g.add_argument("--drift-threshold", type=float, default=None)
    g.add_argument("--dump-config", action="store_true",
                   help="print the resolved SessionConfig JSON and exit")


def session_config_from_args(args: argparse.Namespace,
                             workload: Optional[str] = None):
    """Resolve file -> environment -> explicit flags into a SessionConfig."""
    from repro.session import SessionConfig

    base = SessionConfig.load(args.config) if args.config else SessionConfig()
    cfg = SessionConfig.from_env(base=base)

    updates: Dict[str, Any] = {}
    fabric: Dict[str, Any] = {}
    if args.fabric is not None:
        fabric["kind"] = args.fabric
    if args.nodes is not None:
        fabric["nodes"] = args.nodes
    if args.pods is not None:
        fabric["n_pods"] = args.pods
    if getattr(args, "pod_shape", None) is not None:
        fabric["pod_shape"] = args.pod_shape
    if args.scramble_seed is not None:
        fabric["scramble_seed"] = args.scramble_seed
    if args.fabric_seed is not None:
        fabric["seed"] = args.fabric_seed
    if fabric:
        updates["fabric"] = fabric
    probe: Dict[str, Any] = {}
    if args.probe_seed is not None:
        probe["seed"] = args.probe_seed
    if getattr(args, "probe_mode", None) is not None:
        probe["mode"] = args.probe_mode
    if getattr(args, "sparse", None):
        probe["mode"] = "sparse"
    if getattr(args, "probe_budget", None) is not None:
        probe["budget"] = args.probe_budget
    if probe:
        updates["probe"] = probe
    mesh: Dict[str, Any] = {}
    if args.mesh is not None:
        mesh["shape"] = args.mesh
    if args.axes is not None:
        mesh["axis_names"] = args.axes
    if mesh:
        updates["mesh"] = mesh
    solver: Dict[str, Any] = {}
    budget: Dict[str, Any] = {}
    if args.iters is not None:
        budget["iters"] = args.iters
    if args.chains is not None:
        budget["chains"] = args.chains
    if args.solver_engine is not None:
        budget["engine"] = args.solver_engine
    if args.solver_backend is not None:
        budget["backend"] = args.solver_backend
    if budget:
        solver["budget"] = budget
    if args.solver_seed is not None:
        solver["seed"] = args.solver_seed
    if solver:
        updates["solver"] = solver
    if args.plan_cache_dir is not None:
        updates["cache"] = {"dir": args.plan_cache_dir}
    if args.drift_threshold is not None:
        updates["drift"] = {"threshold": args.drift_threshold}
    if args.payload_bytes is not None:
        updates["payload_bytes"] = args.payload_bytes
    if args.moe:
        updates["moe"] = True
    if workload is not None:
        updates["workload"] = workload
    return cfg.replace(**updates) if updates else cfg


def _maybe_dump(args: argparse.Namespace, cfg) -> bool:
    if getattr(args, "dump_config", False):
        print(cfg.to_json())
        return True
    return False


# ---------------------------------------------------------------------------
# probe
# ---------------------------------------------------------------------------

def cmd_probe(args: argparse.Namespace) -> int:
    import numpy as np

    from repro.session import Session

    cfg = session_config_from_args(args)
    if _maybe_dump(args, cfg):
        return 0
    with Session(cfg) as s:
        s.attach()
        probe = s.probe
        lat = probe.lat
        off = lat[~np.eye(lat.shape[0], dtype=bool)] if lat.shape[0] > 1 \
            else np.zeros(1)
        print(f"[probe] fabric={cfg.fabric.kind} n={probe.n} "
              f"lat p10={np.percentile(off, 10) * 1e6:.1f}us "
              f"p50={np.percentile(off, 50) * 1e6:.1f}us "
              f"p90={np.percentile(off, 90) * 1e6:.1f}us "
              f"bw={'probed' if probe.bw is not None else 'n/a'}")
        if getattr(probe, "probes_used", 0):
            print(f"[probe] sparse: {probe.probes_used} directed probes "
                  f"({probe.probe_fraction * 100:.1f}% of dense n(n-1), "
                  f"budget {probe.probe_budget * 100:.0f}%)")
        if s.hierarchy is not None:
            print(s.hierarchy.describe())
        if args.out:
            payload = {
                "n": probe.n,
                "lat": probe.lat.tolist(),
                "bw": None if probe.bw is None else
                      np.where(np.isfinite(probe.bw), probe.bw, -1.0).tolist(),
                "n_probes": probe.n_probes,
                "percentile": probe.percentile,
            }
            if s.hierarchy is not None:
                payload["hierarchy"] = s.hierarchy.to_dict()
                payload["probes_used"] = int(getattr(probe, "probes_used", 0))
            with open(args.out, "w") as f:
                json.dump(payload, f)
            print(f"[probe] wrote {args.out}")
    return 0


# ---------------------------------------------------------------------------
# plan
# ---------------------------------------------------------------------------

def cmd_plan(args: argparse.Namespace) -> int:
    from repro.session import Session

    cfg = session_config_from_args(args)
    if args.dry_run:
        # a dry run must leave no trace: no persistent cache writes
        cfg = cfg.replace(cache={"dir": None})
    if _maybe_dump(args, cfg):
        return 0
    with Session(cfg) as s:
        plan = s.plan()
        hit = "cache hit" if s.service.stats["cache_hits"] else \
            f"compiled in {plan.compile_seconds:.2f}s"
        mode = "dry-run: " if args.dry_run else ""
        print(f"[plan] {mode}{plan.fingerprint.digest} ({hit}) "
              f"mix={cfg.workload} n={plan.n}")
        for (op, bucket, group), e in sorted(plan.entries.items()):
            fp = f" prog={e.program_fingerprint}" if e.program_fingerprint \
                else ""
            print(f"  {op:<15} bucket=2^{bucket:<3} group={len(group):>4} "
                  f"-> {e.algo:<20} chunks={e.chunks} "
                  f"t={e.expected_time * 1e3:.3f}ms "
                  f"({e.best_identity_time / max(e.expected_time, 1e-30):.2f}x "
                  f"vs identity){fp}")
        if plan.mesh_plan is not None:
            mp = plan.mesh_plan
            print(f"  mesh {'x'.join(map(str, mp.assignment.shape))} "
                  f"cost {mp.baseline_cost:.5f} -> {mp.cost:.5f} "
                  f"({mp.baseline_cost / max(mp.cost, 1e-30):.2f}x)")
        if plan.meta.get("hierarchy"):
            from repro.fabric import HierarchyModel

            tree = HierarchyModel.from_dict(plan.meta["hierarchy"])
            for line in tree.describe().splitlines():
                print(f"  {line}")
        if args.out:
            # an explicit --out is a user-requested artifact, written
            # even under --dry-run (which only skips the plan *store*)
            with open(args.out, "w") as f:
                f.write(plan.to_json())
            print(f"[plan] wrote {args.out}")
    return 0


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def model_config(args: argparse.Namespace):
    """The train/serve model: the arch's published widths, or its reduced
    same-family form under ``--smoke``."""
    from repro.configs import get_config

    arch = get_config(args.arch)
    return arch.smoke() if args.smoke else arch


def cmd_train(args: argparse.Namespace) -> int:
    import dataclasses as _dc
    import statistics

    import jax

    from repro.launch.specs import configure_sp
    from repro.launch.train import build_mesh, train_on_mesh
    from repro.models import get_model

    cfg = session_config_from_args(args, workload="train")
    if _maybe_dump(args, cfg):
        return 0

    arch = model_config(args)
    if args.smoke:
        arch = _dc.replace(arch, vocab_size=2048)
    shapes = jax.eval_shape(get_model(arch).init, jax.random.PRNGKey(0))
    grad_bytes = float(sum(s.size * s.dtype.itemsize
                           for s in jax.tree.leaves(shapes)))
    mesh, plan, reducer = build_mesh(
        args, len(jax.devices()), moe=bool(arch.n_experts),
        session_config=cfg, grad_bytes=grad_bytes)
    configure_sp(arch, mesh, plan=plan)   # SP/EP contexts + planned a2a ring

    report, state, batch_sharding = train_on_mesh(
        arch, mesh, steps=args.steps, batch=args.batch, seq=args.seq,
        lr=args.lr, reducer=reducer, ckpt_dir=args.ckpt_dir,
        log_every=args.log_every)
    h = report["history"]
    for row in h:
        print(f"[train] step {row['step']} loss {row['loss']:.4f} "
              f"({row['sec'] * 1e3:.1f} ms)")
    print(f"[train] arch={arch.name} d_model={arch.d_model} "
          f"steps={report['final_step']} "
          f"loss {h[0]['loss']:.3f} -> {h[-1]['loss']:.3f}")
    warm = [row["sec"] for row in h if row["step"] > 1]
    if warm:
        print(f"[train] step time after warm-up: "
              f"{statistics.median(warm) * 1e3:.1f} ms (median of "
              f"{len(warm)} logged steps after the first)")
    param = jax.tree.leaves(state.params)[0]
    batch_devs = jax.tree.leaves(batch_sharding)[0].device_set
    print(f"[train] mesh {dict(mesh.shape)}"
          f"{' overlap=' + reducer.mode if reducer is not None else ''}: "
          f"params on {len(param.sharding.device_set)} device(s), "
          f"batch on {len(batch_devs)}")
    stats = jax.devices()[0].memory_stats()
    if stats and "peak_bytes_in_use" in stats:
        print(f"[train] peak device memory "
              f"{stats['peak_bytes_in_use'] / 2**30:.2f} GiB "
              f"({jax.devices()[0].device_kind})")
    return 0


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def cmd_serve(args: argparse.Namespace) -> int:
    import jax
    import jax.numpy as jnp

    from repro.launch.specs import configure_sp
    from repro.launch.train import build_mesh
    from repro.models import get_model
    from repro.serve import GenerationConfig, GenerationEngine
    from repro.session import serve_mix

    cfg = session_config_from_args(args, workload="serve")
    # decode payloads are smaller than gradient payloads: keep the old
    # serve launcher's 1e6 default unless the payload was set explicitly
    # (flag, config file, or environment)
    if args.payload_bytes is None and args.config is None \
            and "REPRO_PAYLOAD_BYTES" not in os.environ:
        cfg = cfg.replace(payload_bytes=1e6)
    if _maybe_dump(args, cfg):
        return 0

    arch = model_config(args)
    model = get_model(arch)
    mix = serve_mix(cfg.payload_bytes, moe=bool(arch.n_experts))
    mesh, plan, _ = build_mesh(args, len(jax.devices()), mix=mix,
                               session_config=cfg)
    configure_sp(arch, mesh, plan=plan)

    params = model.init(jax.random.PRNGKey(0))
    fe = None
    if arch.family == "vlm":
        fe = jnp.ones((args.batch, arch.n_img_tokens, arch.d_model),
                      jnp.float32)
    if arch.family == "encdec":
        fe = jnp.ones((args.batch, arch.n_audio_ctx, arch.d_model),
                      jnp.float32)

    prompts = [
        [(11 * i + j) % arch.vocab_size for j in range(args.prompt_len)]
        for i in range(args.batch)
    ]
    with jax.set_mesh(mesh):
        eng = GenerationEngine(
            model, params,
            GenerationConfig(max_new_tokens=args.max_new, eos_token=-1),
            plan=plan)
        if plan is not None:
            print(f"[serve] plan {plan.fingerprint.digest} hints: "
                  f"{eng.collective_hints(cfg.payload_bytes)}")
        timer = obs.tracer().timer("cli.serve.generate", batch=args.batch)
        with timer:
            outs = eng.generate(prompts, frontend_embeds=fe)
        dt = max(timer.elapsed, 1e-9)
    total = sum(len(o) for o in outs)
    print(f"[serve] arch={arch.name} d_model={arch.d_model} "
          f"{total} tokens in {dt:.2f}s ({total / dt:.1f} tok/s); "
          f"row 0: {outs[0][:8]}")
    # no eos is set, so every row must carry max_new in-vocabulary ids
    bad = [t for o in outs for t in o if not 0 <= t < arch.vocab_size]
    if bad or any(len(o) != args.max_new for o in outs):
        print(f"[serve] FAIL: want {args.batch}x{args.max_new} ids in "
              f"[0, {arch.vocab_size}), got lengths "
              f"{[len(o) for o in outs]} and {len(bad)} out-of-range ids",
              file=sys.stderr)
        return 1
    return 0


# ---------------------------------------------------------------------------
# bench
# ---------------------------------------------------------------------------

def cmd_bench_faults(args: argparse.Namespace) -> int:
    """Seeded churn scenario: preempt 25% of the nodes mid-session, let
    the degradation ladder recover, referee the recovered order against
    identity, and rejoin the preempted nodes.  Fails (exit 1) if any
    recovery raises, loses the plan, or serves an order the cost model
    scores worse than identity."""
    from repro.faults import FaultSchedule, FaultyFabric
    from repro.fabric import make_datacenter, scramble
    from repro.session import Session

    n = 16 if args.smoke else 32
    iters = 200 if args.smoke else 400
    fab, _ = scramble(make_datacenter(n, seed=0), seed=1)
    schedule = FaultSchedule.generate(
        n, ticks=8, seed=args.seed, preempt_frac=0.25,
        timeout_rate=0.0, drop_rate=0.0, nan_rate=0.0)
    faulty = FaultyFabric(fab, schedule)
    cfg = session_config_from_args(args).replace(
        mesh={"shape": ()}, cache={"dir": None},
        probe={"n_probes": 4},
        solver={"budget": {"iters": iters, "chains": 4}})
    events: List[Dict[str, Any]] = []
    with Session(cfg) as s:
        s.attach(fab)
        s.plan()
        for _ in range(8):
            for ev in faulty.advance():
                timer = obs.tracer().timer("bench.recovery", kind=ev.kind)
                with timer:
                    if ev.kind == "node_preempt":
                        alive = s.alive
                        plan = s.on_node_leave(
                            [alive.index(b) for b in ev.nodes if b in alive])
                    else:
                        plan = s.on_node_join(
                            [b for b in ev.nodes if b not in s.alive])
                ms = timer.elapsed * 1e3
                ok = plan is not None and all(
                    e.expected_time <= e.best_identity_time * (1 + 1e-9)
                    and sorted(e.perm) == list(e.group)
                    for e in plan.entries.values())
                events.append({
                    "kind": ev.kind, "survivors": len(s.alive),
                    "recovery_ms": round(ms, 2),
                    "rungs": sorted(set(
                        (plan.meta.get("rungs") or {}).values()))
                    if plan is not None else [],
                    "ok": ok,
                })
                print(f"bench_faults,{ev.kind},{ms * 1e3:.0f},"
                      f"survivors={len(s.alive)}")
        health = s.health
    payload = {"bench": "session_faults", "smoke": bool(args.smoke),
               "n": n, "seed": args.seed, "health": health,
               "events": events}
    print(json.dumps(payload, indent=1))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"[bench] wrote {args.out}")
    if not events or not all(e["ok"] for e in events):
        print("[bench] FAIL: a churn recovery lost the plan or served "
              "an order worse than identity")
        return 1
    return 0


def run_obs_scenario(smoke: bool = True, seed: int = 0,
                     window_s: float = 1.0) -> Dict[str, Any]:
    """The obs benchmark scenario (CLI ``bench --scenario obs`` and
    ``benchmarks/obs_trace.py`` share this).

    Two measurements:

    * **tracing overhead** — median wall time of the same
      ``PlanCompiler.compile`` with the tracer disabled vs enabled
      (the disabled path must be a no-op: ``span()`` returns the
      shared null span);
    * **capture → replay** — price a synthetic bursty trace under the
      single declared-mix plan (one operator-declared payload size, see
      :func:`repro.obs.declared_mix`) vs per-phase-window plans
      compiled from :func:`repro.obs.fold` output.  Phase-aware
      planning must not lose to the stationary plan.
    """
    import statistics

    from repro.fabric import make_datacenter, probe_fabric, scramble
    from repro.obs import declared_mix, fold, replay, synthetic_bursty_trace
    from repro.plan import PlanCompiler, SolveBudget

    n = 16 if smoke else 32
    iters = 60 if smoke else 200
    reps = 5 if smoke else 9
    fab, _ = scramble(make_datacenter(n, seed=seed), seed=seed + 1)
    probe = probe_fabric(fab, seed=seed)
    compiler = PlanCompiler(budget=SolveBudget(iters=iters, chains=2))

    trace = synthetic_bursty_trace(n, seed=seed)
    stationary_mix = declared_mix(trace)

    tr = obs.tracer()
    was_enabled = tr.enabled
    timings: Dict[str, float] = {}
    try:
        for mode, enable in (("disabled", False), ("enabled", True)):
            tr.set_enabled(enable)
            samples = []
            for _ in range(reps):
                t = tr.timer("bench.obs.compile")   # measures even when off
                with t:
                    compiler.compile(probe, stationary_mix)
                samples.append(t.elapsed)
            timings[mode] = statistics.median(samples)
    finally:
        tr.set_enabled(was_enabled)
    overhead_pct = (timings["enabled"] / max(timings["disabled"], 1e-12)
                    - 1.0) * 100.0

    declared_plan = compiler.compile(probe, stationary_mix)
    windows = fold(trace, window_s=window_s)
    phased = [(w, compiler.compile(probe, w.mix)) for w in windows]
    base = replay(trace, declared_plan, probe.lat, probe.bw)
    ph = replay(trace, declared_plan, probe.lat, probe.bw, windows=phased)
    return {
        "bench": "obs",
        "smoke": bool(smoke),
        "n": n,
        "seed": seed,
        "compile": {
            "disabled_s": round(timings["disabled"], 6),
            "enabled_s": round(timings["enabled"], 6),
            "overhead_pct": round(overhead_pct, 3),
            "reps": reps,
        },
        "replay": {
            "trace": trace.name,
            "records": len(trace),
            "windows": len(windows),
            "declared_s": base["total_seconds"],
            "phased_s": ph["total_seconds"],
            "phased_beats_declared":
                ph["total_seconds"] <= base["total_seconds"],
            "unplanned": base["unplanned"] + ph["unplanned"],
        },
    }


def cmd_bench_obs(args: argparse.Namespace) -> int:
    """Observability scenario: tracing-overhead gate + capture→replay.

    Fails (exit 1) if enabled-tracer overhead exceeds 10% (CI noise
    headroom over the 2% budget recorded in BENCH_obs.json) or if the
    phase-windowed plans lose to the single declared-mix plan."""
    payload = run_obs_scenario(smoke=bool(args.smoke), seed=args.seed)
    print(json.dumps(payload, indent=1))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"[bench] wrote {args.out}")
    if payload["compile"]["overhead_pct"] >= 10.0:
        print("[bench] FAIL: enabled-tracer overhead "
              f"{payload['compile']['overhead_pct']:.1f}% >= 10%")
        return 1
    if not payload["replay"]["phased_beats_declared"]:
        print("[bench] FAIL: phase-windowed plans lost to the single "
              "declared-mix plan on the bursty trace")
        return 1
    return 0


def cmd_bench_overlap(args: argparse.Namespace) -> int:
    """Overlap scenario: planned+bucketed vs planned-sequential step.

    Thin CLI front for :mod:`benchmarks.overlap_step` (modeled-fabric
    pipeline gate + 8-device host-mesh numeric equivalence); fails
    (exit 1) when the bucketed step models under the 1.15x floor, the
    overlapped loss diverges from the baseline, or the certified
    schedule's postcondition breaks."""
    import importlib

    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path.insert(0, repo)
    try:
        mod = importlib.import_module("benchmarks.overlap_step")
    except ImportError as e:
        print(f"[bench] benchmarks/ not importable from {repo}: {e}")
        return 1
    try:
        mod.run(smoke=bool(args.smoke),
                out_path=args.out or "BENCH_overlap.json", seed=args.seed)
    except RuntimeError as e:
        print(f"[bench] FAIL: {e}")
        return 1
    return 0


def cmd_bench(args: argparse.Namespace) -> int:
    """Self-contained plan-pipeline benchmark (CI smoke + local sanity).

    Times, per fabric size: cold compile, warm cache hit, and the plan's
    expected speedup over the identity order — through the same Session
    facade applications use.

    ``--scenario faults`` switches to the churn/recovery scenario
    (:func:`cmd_bench_faults`); ``--scenario obs`` to the observability
    overhead + capture→replay scenario (:func:`cmd_bench_obs`);
    ``--scenario overlap`` to the overlapped-train-step gate
    (:func:`cmd_bench_overlap`).
    """
    from repro.session import Session

    if getattr(args, "scenario", "plan") == "faults":
        return cmd_bench_faults(args)
    if getattr(args, "scenario", "plan") == "obs":
        return cmd_bench_obs(args)
    if getattr(args, "scenario", "plan") == "overlap":
        return cmd_bench_overlap(args)
    sizes = [16] if args.smoke else [32, 64]
    iters = 200 if args.smoke else 800
    results: List[Dict[str, Any]] = []
    for n in sizes:
        cfg = session_config_from_args(args)
        cfg = cfg.replace(
            fabric={"kind": "datacenter", "nodes": n, "scramble_seed": 1},
            mesh={"shape": ()},
            cache={"dir": None},
            solver={"budget": {"iters": iters, "chains": 4}})
        with Session(cfg) as s:
            cold = obs.tracer().timer("bench.cold_compile", n=n)
            with cold:
                plan = s.plan()
            cold_s = cold.elapsed
            warm = obs.tracer().timer("bench.warm_hit", n=n)
            with warm:
                s.service.request(s.probe, s.mix)    # warm: LRU probe
            warm_s = warm.elapsed
            speedups = [
                e.best_identity_time / max(e.expected_time, 1e-30)
                for e in plan.entries.values()
            ]
            row = {
                "n": n,
                "entries": len(plan.entries),
                "cold_compile_s": round(cold_s, 4),
                "warm_hit_s": round(warm_s, 6),
                "warm_speedup_x": round(cold_s / max(warm_s, 1e-9), 1),
                "mean_speedup_vs_identity":
                    round(sum(speedups) / len(speedups), 3),
                "cache_hits": s.service.stats["cache_hits"],
            }
        results.append(row)
        print(f"bench,n={n},{row['cold_compile_s'] * 1e6:.0f},"
              f"warm_x={row['warm_speedup_x']}")
    payload = {"bench": "session_plan", "smoke": bool(args.smoke),
               "results": results}
    print(json.dumps(payload, indent=1))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"[bench] wrote {args.out}")
    for row in results:
        if row["cache_hits"] < 1:
            print("[bench] FAIL: warm request missed the plan cache")
            return 1
    return 0


# ---------------------------------------------------------------------------
# status / trace
# ---------------------------------------------------------------------------

def cmd_status(args: argparse.Namespace) -> int:
    """Print the process obs-metrics snapshot (JSON or Prometheus text).

    By default a small dry-run session (attach + plan, no cache writes)
    is driven first so the snapshot reflects a live pipeline; pass
    ``--no-run`` to dump whatever the process has already recorded.
    """
    cfg = session_config_from_args(args)
    if _maybe_dump(args, cfg):
        return 0
    if not args.no_run:
        from repro.session import Session

        run_cfg = cfg.replace(
            mesh={"shape": ()}, cache={"dir": None},
            **({} if args.iters is not None
               else {"solver": {"budget": {"iters": 60, "chains": 2}}}))
        with Session(run_cfg) as s:
            s.attach()
            s.plan()
    m = obs.metrics()
    if args.format == "prom":
        sys.stdout.write(m.to_prometheus())
    else:
        print(json.dumps(m.snapshot(), indent=1))
    return 0


def cmd_trace_export(args: argparse.Namespace) -> int:
    """Run the planning pipeline under the tracer, export Chrome JSON.

    The artifact loads in ``chrome://tracing`` and https://ui.perfetto.dev.
    """
    cfg = session_config_from_args(args)
    if _maybe_dump(args, cfg):
        return 0
    from repro.session import Session

    tr = obs.tracer()
    tr.set_enabled(True)
    run_cfg = cfg.replace(
        mesh={"shape": ()}, cache={"dir": None},
        **({} if args.iters is not None
           else {"solver": {"budget": {"iters": 60, "chains": 2}}}))
    with Session(run_cfg) as s:
        s.attach()
        s.plan()
    n_events = tr.export(args.out)
    print(f"[trace] wrote {n_events} events to {args.out} "
          f"(open in chrome://tracing or ui.perfetto.dev)")
    return 0


def cmd_trace_replay(args: argparse.Namespace) -> int:
    """Replay a captured (or synthetic bursty) workload trace.

    Compares the single declared-mix plan against per-phase-window
    plans compiled from the folded trace; prints both totals.
    """
    from repro.fabric import make_datacenter, probe_fabric, scramble
    from repro.obs import (WorkloadTrace, declared_mix, fold, replay,
                           synthetic_bursty_trace)
    from repro.plan import PlanCompiler, SolveBudget

    if args.trace:
        trace = WorkloadTrace.load(args.trace)
        n = int(trace.meta.get("n", args.nodes or 16))
    else:
        n = args.nodes or 16          # session-args --nodes, default 16
        trace = synthetic_bursty_trace(n, seed=args.seed)
    if not len(trace):
        print("[trace] empty trace: nothing to replay")
        return 1
    fab, _ = scramble(make_datacenter(n, seed=args.seed),
                      seed=args.seed + 1)
    probe = probe_fabric(fab, seed=args.seed)
    compiler = PlanCompiler(
        budget=SolveBudget(iters=args.iters or 200, chains=2))
    declared_plan = compiler.compile(probe, declared_mix(trace))
    windows = fold(trace, window_s=args.window)
    phased = [(w, compiler.compile(probe, w.mix)) for w in windows]
    base = replay(trace, declared_plan, probe.lat, probe.bw)
    ph = replay(trace, declared_plan, probe.lat, probe.bw, windows=phased)
    print(f"[trace] replay {trace.name}: {len(trace)} records, "
          f"{len(windows)} phase windows (window={args.window}s), n={n}")
    print(f"  declared-mix plan : {base['total_seconds'] * 1e3:.3f}ms "
          f"({base['unplanned']} unplanned)")
    print(f"  phase-window plans: {ph['total_seconds'] * 1e3:.3f}ms "
          f"({ph['unplanned']} unplanned)")
    win = base["total_seconds"] / max(ph["total_seconds"], 1e-30)
    print(f"  phased vs declared: {win:.4f}x")
    if args.out:
        payload = {"trace": trace.name, "n": n, "windows": len(windows),
                   "declared": base, "phased": ph}
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"[trace] wrote {args.out}")
    return 0


# ---------------------------------------------------------------------------
# analyze
# ---------------------------------------------------------------------------

def _analyze_sweep(n_list, fabric_nodes, seed):
    """Verify the full builder catalogue; returns (reports, n_bad)."""
    import random

    from repro.collective import (
        CollectiveOp, apply_permutation, chunk, compile_op, get_builder,
        registered_builders)
    from repro.collective.builders import candidates
    from repro.analysis import verify_program

    fab = None
    if fabric_nodes:
        from repro.fabric import make_datacenter
        fab = make_datacenter(fabric_nodes, seed=seed)
    reports = []
    n_bad = 0
    for algo in sorted(registered_builders()):
        b = get_builder(algo)
        for kind in b.kinds:
            for n in n_list:
                # candidates() supplies the feasible kwarg sets (e.g.
                # every valid bcube base at this n)
                akws = [akw for a, akw in candidates(kind, n) if a == algo]
                op = CollectiveOp(kind=kind, size_bytes=1 << 20,
                                  group=tuple(range(n)))
                for akw in akws:
                    base = compile_op(op, algo, **dict(akw))
                    rng = random.Random(seed + n)
                    perm = list(range(n))
                    rng.shuffle(perm)
                    variants = (("identity", base),
                                ("permuted", apply_permutation(base, perm)),
                                ("chunked", chunk(base, 4)))
                    for label, prog in variants:
                        use_fab = fab if fab is not None and \
                            fab.n == prog.n else None
                        rep = verify_program(prog, fabric=use_fab)
                        reports.append((label, rep))
                        if not rep.clean:
                            n_bad += 1
    return reports, n_bad


def _equiv_sweep(n_list, seed):
    """Differential translation validation over the builder catalogue.

    Every registered builder × kind × n is lowered and bisimulated at
    each rewrite stage (base → apply_permutation → chunk →
    fuse_rounds).  Returns (rows, n_bad) where each row is one
    program's stage-by-stage verdict list.
    """
    import random

    from repro.collective import CollectiveOp, compile_op, get_builder, \
        registered_builders
    from repro.collective.builders import candidates
    from repro.analysis import certify_stages

    rows = []
    n_bad = 0
    for algo in sorted(registered_builders()):
        b = get_builder(algo)
        for kind in b.kinds:
            for n in n_list:
                akws = [akw for a, akw in candidates(kind, n) if a == algo]
                op = CollectiveOp(kind=kind, size_bytes=1 << 20,
                                  group=tuple(range(n)))
                for akw in akws:
                    prog = compile_op(op, algo, **dict(akw))
                    rng = random.Random(seed + n)
                    perm = list(range(n))
                    rng.shuffle(perm)
                    stages = certify_stages(prog, perm=perm, chunk_k=4)
                    ok = all(s["ok"] for s in stages)
                    if not ok:
                        n_bad += 1
                    rows.append({
                        "algorithm": algo, "kind": kind, "n": n,
                        "algo_kwargs": dict(akw), "ok": ok,
                        "stages": stages,
                    })
    return rows, n_bad


def cmd_analyze(args: argparse.Namespace) -> int:
    """Static analysis: lint the repo, or verify collective Programs."""
    if args.lint:
        import os as _os

        from repro.analysis.lint import RULES, lint_repo

        root = args.root or _os.getcwd()
        findings, n_files = lint_repo(root)
        for f in findings:
            print(f)
        verdict = "clean" if not findings else f"{len(findings)} finding(s)"
        print(f"[lint] {n_files} files, {len(RULES)} rules: {verdict}")
        return 1 if findings else 0

    if args.equiv:
        n_list = [int(x) for x in args.n_list.split(",")]
        rows, n_bad = _equiv_sweep(n_list, args.seed)
        for row in rows:
            if row["ok"]:
                continue
            for st in row["stages"]:
                if st["ok"]:
                    continue
                print(f"  FAIL {row['algorithm']}/{row['kind']} "
                      f"n={row['n']} stage={st['stage']} "
                      f"codes={sorted(st['codes'])}")
        by_algo: Dict[str, int] = {}
        for row in rows:
            by_algo.setdefault(row["algorithm"], 0)
            if not row["ok"]:
                by_algo[row["algorithm"]] += 1
        for algo in sorted(by_algo):
            total = sum(1 for r in rows if r["algorithm"] == algo)
            state = "CERTIFIED" if not by_algo[algo] \
                else f"{by_algo[algo]} FAILING"
            print(f"  {algo:<22} {total:>3} programs  {state}")
        print(f"[analyze] equiv: {len(rows)} programs x "
              f"{len(rows[0]['stages']) if rows else 0} stages, "
              f"{n_bad} failing")
        if args.out:
            payload = {"n_programs": len(rows), "n_bad": n_bad,
                       "n_list": n_list, "rows": rows}
            with open(args.out, "w") as f:
                json.dump(payload, f, indent=1)
            print(f"[analyze] wrote {args.out}")
        return 1 if n_bad else 0

    if args.program:
        from repro.collective import CollectiveOp, compile_op, get_builder
        from repro.analysis import verify_program

        algo = args.program
        b = get_builder(algo)
        n = args.nodes or 16
        if not b.feasible(n):
            print(f"[analyze] {algo} is infeasible at n={n}")
            return 1
        fab = None
        if args.fabric_nodes:
            from repro.fabric import make_datacenter
            fab = make_datacenter(n, seed=args.seed)
        bad = 0
        for kind in b.kinds:
            op = CollectiveOp(kind=kind, size_bytes=args.payload_bytes
                              or (1 << 20), group=tuple(range(n)))
            rep = verify_program(compile_op(op, algo), fabric=fab)
            print(rep.describe())
            bad += 0 if rep.ok else 1
        return 1 if bad else 0

    if args.plan:
        from repro.session import Session
        from repro.analysis import verify_program

        cfg = session_config_from_args(args)
        if _maybe_dump(args, cfg):
            return 0
        bad = 0
        with Session(cfg) as s:
            plan = s.plan()
            fab = s._oracle_fabric
            for (op, bucket, group), e in sorted(plan.entries.items()):
                prog = e.program()
                use_fab = fab if fab is not None and fab.n >= max(group) + 1 \
                    else None
                rep = verify_program(prog, fabric=use_fab)
                print(f"  {op:<15} bucket=2^{bucket:<3} "
                      f"group={len(group):>4} {rep.summary()}")
                bad += 0 if rep.ok else 1
        print(f"[analyze] plan: {bad} failing entr{'y' if bad == 1 else 'ies'}"
              if bad else "[analyze] plan: all entries verified")
        return 1 if bad else 0

    # default: full-catalogue sweep
    n_list = [int(x) for x in args.n_list.split(",")]
    reports, n_bad = _analyze_sweep(n_list, args.fabric_nodes, args.seed)
    by_algo: Dict[str, int] = {}
    for label, rep in reports:
        by_algo[rep.algorithm] = by_algo.get(rep.algorithm, 0)
        if not rep.clean:
            by_algo[rep.algorithm] += 1
            print(rep.describe())
    for algo in sorted(by_algo):
        n_variants = sum(1 for _, r in reports if r.algorithm == algo)
        state = "CLEAN" if not by_algo[algo] else f"{by_algo[algo]} DIRTY"
        print(f"  {algo:<22} {n_variants:>3} variants  {state}")
    print(f"[analyze] {len(reports)} programs verified, "
          f"{n_bad} with errors/warnings")
    if args.out:
        payload = {
            "n_programs": len(reports),
            "n_bad": n_bad,
            "n_list": n_list,
            "reports": [dict(variant=label, **rep.to_dict())
                        for label, rep in reports],
        }
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"[analyze] wrote {args.out}")
    return 1 if n_bad else 0


# ---------------------------------------------------------------------------
# parser
# ---------------------------------------------------------------------------

def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="repro",
        description="Cloud Collectives: probe, plan, train, serve, bench")
    from repro import __version__

    ap.add_argument("--version", action="version",
                    version=f"repro {__version__}")
    sub = ap.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("probe", help="probe a fabric, print/export the result")
    _add_session_args(p)
    p.add_argument("--out", default=None, help="write probe JSON here")
    p.set_defaults(fn=cmd_probe)

    p = sub.add_parser("plan", help="compile (or fetch) a collective plan")
    _add_session_args(p)
    p.add_argument("--dry-run", action="store_true",
                   help="compile + report without touching the plan store")
    p.add_argument("--out", default=None, help="write the plan JSON here")
    p.set_defaults(fn=cmd_plan)

    p = sub.add_parser("train", help="train on a planned (reordered) mesh")
    _add_session_args(p)
    p.add_argument("--arch", default="qwen2-0.5b")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--reorder", choices=["none", "simulate", "probe"],
                   default="simulate")
    p.add_argument("--smoke", action="store_true",
                   help="reduced same-family config (d_model 64) for CPU "
                        "runs; default: the arch's published widths")
    p.add_argument("--ckpt-dir", default=None,
                   help="write checkpoints here (default: none)")
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--log-every", type=int, default=20,
                   help="log loss and step time every N steps (and the "
                        "first two)")
    p.set_defaults(fn=cmd_train, mesh_default="1x1")

    p = sub.add_parser("serve", help="batched generation on a planned mesh")
    _add_session_args(p)
    p.add_argument("--arch", default="qwen2-0.5b")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=16)
    p.add_argument("--max-new", type=int, default=32)
    p.add_argument("--reorder", choices=["none", "simulate", "probe"],
                   default="simulate")
    p.add_argument("--smoke", action="store_true",
                   help="reduced same-family config (d_model 64) for CPU "
                        "runs; default: the arch's published widths")
    p.set_defaults(fn=cmd_serve, mesh_default="1x1")

    p = sub.add_parser("bench", help="session/plan pipeline benchmark")
    _add_session_args(p)
    p.add_argument("--smoke", action="store_true",
                   help="one small fabric (CI)")
    p.add_argument("--scenario", default="plan",
                   choices=["plan", "faults", "obs", "overlap"],
                   help="plan: compile/cache pipeline; faults: seeded "
                        "churn with ladder recovery; obs: tracing "
                        "overhead + capture/replay; overlap: bucketed "
                        "overlapped train step vs sequential")
    p.add_argument("--seed", type=int, default=0,
                   help="scenario seed (faults schedule / obs trace)")
    p.add_argument("--out", default=None, help="write bench JSON here")
    p.set_defaults(fn=cmd_bench)

    p = sub.add_parser("analyze",
                       help="static analysis: verify Programs / lint repo")
    _add_session_args(p)
    p.add_argument("--lint", action="store_true",
                   help="run the repo's AST lint gate instead of the "
                        "program verifier")
    p.add_argument("--root", default=None,
                   help="repo root for --lint (default: cwd)")
    p.add_argument("--program", default=None, metavar="ALGO",
                   help="verify one registered builder's program")
    p.add_argument("--plan", action="store_true",
                   help="verify every entry of the session's plan")
    p.add_argument("--equiv", action="store_true",
                   help="differential translation validation: lower + "
                        "bisimulate every builder at each rewrite stage")
    p.add_argument("--n-list", default="4,8,16,64",
                   help="sweep group sizes (default: 4,8,16,64)")
    p.add_argument("--fabric-nodes", type=int, default=None,
                   help="attach a synthetic datacenter fabric of this "
                        "size for the contention pass")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--out", default=None,
                   help="write the verification report JSON here")
    p.set_defaults(fn=cmd_analyze)

    p = sub.add_parser("status",
                       help="obs metrics snapshot (json or prometheus)")
    _add_session_args(p)
    p.add_argument("--format", default="json", choices=["json", "prom"])
    p.add_argument("--no-run", action="store_true",
                   help="skip the dry-run pipeline; dump current metrics")
    p.set_defaults(fn=cmd_status)

    p = sub.add_parser("trace", help="export or replay obs traces")
    tsub = p.add_subparsers(dest="trace_cmd", required=True)

    t = tsub.add_parser("export",
                        help="run the pipeline traced, write Chrome JSON")
    _add_session_args(t)
    t.add_argument("--out", default="trace.json",
                   help="Chrome trace-event JSON path")
    t.set_defaults(fn=cmd_trace_export)

    t = tsub.add_parser("replay",
                        help="replay a captured/synthetic workload trace")
    _add_session_args(t)
    t.add_argument("--trace", default=None,
                   help="WorkloadTrace JSON (default: synthetic bursty)")
    t.add_argument("--window", type=float, default=1.0,
                   help="fold window seconds")
    t.add_argument("--seed", type=int, default=0)
    t.add_argument("--out", default=None, help="write comparison JSON here")
    t.set_defaults(fn=cmd_trace_replay)

    return ap


def main(argv: Optional[Sequence[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    if args.cmd not in ("train", "serve"):
        return args.fn(args)
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.train import LaunchError

    enable_compile_cache()
    # train/serve build meshes: give --mesh a launcher default of 1x1
    if args.mesh is None:
        args.mesh = args.mesh_default
    try:
        return args.fn(args)
    except LaunchError as e:
        print(f"repro {args.cmd}: error: {e}", file=sys.stderr)
        return 2


if __name__ == "__main__":
    sys.exit(main())
