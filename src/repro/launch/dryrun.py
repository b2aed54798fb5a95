"""Multi-pod dry-run driver (see DESIGN.md §5 and EXPERIMENTS.md §Dry-run).

For every (architecture x input shape) cell this driver:

1. builds the production mesh — ``(16, 16)`` single-pod or
   ``(2, 16, 16)`` multi-pod — with 512 placeholder host devices;
2. lowers + compiles the cell's step function (train_step / prefill /
   serve_step) with full-size ShapeDtypeStruct inputs and the sharding
   rules of :mod:`repro.parallel.sharding` — success proves the
   distribution config is coherent;
3. records ``compiled.memory_analysis()`` (fits-in-HBM evidence),
   ``compiled.cost_analysis()`` (raw), loop-scaled collective bytes
   (:mod:`repro.launch.hlo_analysis`), and — because XLA:CPU counts scan
   bodies once — **depth-differenced** FLOPs/bytes: the model is lowered
   unrolled at two reduced depths at full width, and the marginal
   per-layer cost extrapolates to full depth (``--no-diff`` to skip);
4. derives the three roofline terms and writes one JSON per cell under
   ``--out``.

Usage::

    python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k
    python -m repro.launch.dryrun --all                  # 16x16 + 2x16x16
    python -m repro.launch.dryrun --all --multi-pod-only
"""

import argparse
import dataclasses
import json
import os
import traceback
from typing import Any, Dict, Optional

import numpy as np

from repro import obs


def _cfg_overrides(cfg, overrides: Dict[str, Any]):
    return dataclasses.replace(cfg, **overrides) if overrides else cfg


def run_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool = False,
    do_diff: bool = True,
    overrides: Optional[Dict[str, Any]] = None,
    verbose: bool = True,
) -> Dict[str, Any]:
    import jax

    from repro.configs import SHAPES, get_config, shape_applicable
    from repro.launch import hlo_analysis as ha
    from repro.launch.mesh import make_production_mesh
    from repro.launch.specs import input_specs, step_callable

    cfg = _cfg_overrides(get_config(arch), overrides or {})
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": shape.kind,
    }
    if not ok:
        rec.update(status="skip", reason=why)
        if verbose:
            print(f"[dryrun] {arch} x {shape_name}: SKIP ({why})")
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(mesh.devices.shape))
    from repro.launch.specs import configure_sp

    configure_sp(cfg, mesh)
    fn = step_callable(cfg, shape)
    specs = input_specs(cfg, shape, mesh)

    # donation mirrors production: train donates the state, decode the
    # cache — memory_analysis then reports realistic aliasing.
    donate = (0,) if shape.kind == "train" else (
        (2,) if shape.kind == "decode" else ())
    lower_t = obs.tracer().timer("dryrun.lower", arch=arch, shape=shape_name)
    compile_t = obs.tracer().timer("dryrun.compile", arch=arch,
                                   shape=shape_name)
    with jax.set_mesh(mesh):
        with lower_t:
            lowered = jax.jit(fn, donate_argnums=donate).lower(*specs)
        with compile_t:
            compiled = lowered.compile()
    t_lower, t_compile = lower_t.elapsed, compile_t.elapsed

    ma = compiled.memory_analysis()
    mem = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "code_bytes": int(ma.generated_code_size_in_bytes),
    }
    # per-device steady-state estimate: args are aliased/donated for train
    live = (mem["argument_bytes"] + mem["output_bytes"]
            - mem["alias_bytes"] + mem["temp_bytes"])
    mem["live_bytes_per_device"] = int(live)
    mem["fits_16GB"] = bool(live < ha.HW().hbm_per_chip)

    ca = compiled.cost_analysis() or {}
    raw_flops = float(ca.get("flops", 0.0))
    raw_bytes = float(ca.get("bytes accessed", 0.0))

    coll = ha.parse_collectives(compiled.as_text())
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} mesh={rec['mesh']}")
        print(f"  memory_analysis: {ma}")
        print(f"  cost_analysis(raw): flops={raw_flops:.3e} "
              f"bytes={raw_bytes:.3e}")
        print(f"  collectives (loop-scaled): "
              f"{ {k: f'{v:.3e}' for k, v in coll.bytes_by_type.items()} } "
              f"total={coll.total_bytes:.3e} B")
        print(f"  lower={t_lower:.1f}s compile={t_compile:.1f}s "
              f"live/device={live/1e9:.2f} GB fits16GB={mem['fits_16GB']}")

    rec.update(
        status="ok",
        n_chips=n_chips,
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        memory=mem,
        cost_analysis_raw={"flops": raw_flops, "bytes_accessed": raw_bytes},
        collectives={
            "bytes_by_type": coll.bytes_by_type,
            "count_by_type": coll.count_by_type,
            "total_bytes": coll.total_bytes,
        },
    )

    if do_diff:
        try:
            rec["per_device"] = _depth_diff(cfg, shape, mesh, verbose)
        except Exception as e:  # depth-diff is best-effort
            rec["per_device"] = {"error": f"{type(e).__name__}: {e}"}

    _finish_roofline(rec, cfg, shape, n_chips)
    return rec


def _depth_variant(cfg, n: int):
    """Reduced-depth, unrolled, full-width copy of the config.

    Unrolls every scan that hides FLOPs from ``cost_analysis`` (which
    counts loop bodies once): the layer scan, the blockwise-attention
    q-chunk map, and the chunked-CE scan.  These chunked paths are
    memory layouts, not extra math, so disabling them leaves FLOPs/bytes
    semantics intact while making them countable.
    """
    kw: Dict[str, Any] = {"use_scan": False, "attn_q_chunk": 0,
                          "loss_chunk_size": 0}
    if cfg.block_pattern:
        kw["n_layers"] = n * len(cfg.block_pattern)
    else:
        kw["n_layers"] = n + cfg.n_dense_layers
    if cfg.family == "encdec":
        kw["n_encoder_layers"] = n
    return dataclasses.replace(cfg, **kw)


def _diff_layers(cfg, n: int) -> int:
    """How many 'marginal units' a depth-n variant contains."""
    return n


def _full_units(cfg) -> int:
    if cfg.block_pattern:
        return cfg.n_layers // len(cfg.block_pattern)  # (R,R,A) groups
    return cfg.n_layers - cfg.n_dense_layers


def _depth_diff(cfg, shape, mesh, verbose: bool) -> Dict[str, float]:
    """HLO-grounded totals via per-layer marginal cost (module docstring)."""
    import jax

    from repro.launch import hlo_analysis as ha
    from repro.launch.specs import input_specs, step_callable

    from repro.launch.specs import configure_sp

    results = []
    for n in (1, 2):
        c = _depth_variant(cfg, n)
        configure_sp(c, mesh)
        fn = step_callable(c, shape)
        specs = input_specs(c, shape, mesh)
        with jax.set_mesh(mesh):
            lowered = jax.jit(fn).lower(*specs)
            compiled = lowered.compile()
        ca = compiled.cost_analysis() or {}
        coll = ha.parse_collectives(compiled.as_text(), scale_loops=True)
        results.append({
            "flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "coll": float(coll.total_bytes),
        })
    u_full = _full_units(cfg)
    out = {}
    for key in ("flops", "bytes", "coll"):
        c1, c2 = results[0][key], results[1][key]
        marginal = max(c2 - c1, 0.0)
        out[key + "_total"] = c1 + marginal * (u_full - 1)
        out[key + "_marginal"] = marginal
    if verbose:
        print(f"  depth-diff: flops={out['flops_total']:.3e}/dev "
              f"bytes={out['bytes_total']:.3e}/dev "
              f"coll={out['coll_total']:.3e}/dev "
              f"(marginal flops {out['flops_marginal']:.3e} x {u_full} units)")
    return out


def _model_flops(cfg, shape) -> float:
    """MODEL_FLOPS: 6*N*D train / 2*N*D inference (N = active params)."""
    n_active = cfg.active_param_count()
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    mult = 6.0 if shape.kind == "train" else 2.0
    return mult * n_active * tokens


def _finish_roofline(rec, cfg, shape, n_chips: int) -> None:
    from repro.launch import hlo_analysis as ha

    pd = rec.get("per_device") or {}
    if "flops_total" in pd:
        # depth-diff numbers are per-device (cost_analysis is per-partition
        # post-SPMD); totals = per-device x chips.  Collectives: take the
        # larger of the depth-diff estimate and the loop-scaled parse of
        # the *shipped* (scanned/chunked) binary — the chunked attention
        # path can emit more collective traffic than the unrolled depth
        # variant (per-chunk K/V re-gathers; see EXPERIMENTS.md §Perf).
        total_flops = pd["flops_total"] * n_chips
        total_bytes = pd["bytes_total"] * n_chips
        total_coll = max(pd["coll_total"],
                         rec["collectives"]["total_bytes"]) * n_chips
        src = "depth_diff"
    else:
        total_flops = rec["cost_analysis_raw"]["flops"] * n_chips
        total_bytes = rec["cost_analysis_raw"]["bytes_accessed"] * n_chips
        total_coll = rec["collectives"]["total_bytes"] * n_chips
        src = "scan_raw"
    mf = _model_flops(cfg, shape)
    terms = ha.roofline_terms(total_flops, total_bytes, total_coll, n_chips)
    rec["roofline"] = dict(
        terms,
        source=src,
        hlo_flops=total_flops,
        hlo_bytes=total_bytes,
        collective_bytes=total_coll,
        model_flops=mf,
        useful_flops_frac=(mf / total_flops) if total_flops else 0.0,
    )


def main() -> None:
    # 512 placeholder host devices for the production meshes; set before
    # JAX first initialises its backends, and only for this command
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    from repro.configs import ARCH_IDS, SHAPES

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--multi-pod-only", action="store_true")
    ap.add_argument("--no-diff", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--override", default=None,
                    help="JSON dict of ModelConfig overrides (perf tuning)")
    ap.add_argument("--suffix", default=None,
                    help="artifact filename suffix (default: '_opt' iff "
                         "--override is set)")
    args = ap.parse_args()

    overrides = json.loads(args.override) if args.override else None
    os.makedirs(args.out, exist_ok=True)

    cells = []
    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [True] if args.multi_pod_only else (
        [False, True] if args.all else [args.multi_pod])
    for mp in meshes:
        for a in archs:
            for s in shapes:
                cells.append((a, s, mp))

    failures = 0
    for a, s, mp in cells:
        try:
            rec = run_cell(a, s, multi_pod=mp, do_diff=not args.no_diff,
                           overrides=overrides)
        except Exception as e:
            failures += 1
            rec = {"arch": a, "shape": s,
                   "mesh": "2x16x16" if mp else "16x16",
                   "status": "error",
                   "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-2000:]}
            print(f"[dryrun] {a} x {s} mesh={'2x16x16' if mp else '16x16'} "
                  f"FAILED: {e}")
        tag = "mp" if mp else "sp"
        suffix = args.suffix if args.suffix is not None else (
            "_opt" if overrides else "")
        path = os.path.join(args.out, f"{a}_{s}_{tag}{suffix}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1, default=float)
    print(f"[dryrun] done; {failures} failures; artifacts in {args.out}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
