"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  The cell, its configuration, its traffic
and its metrics are found by name (``bench/spec.py``).  The run makes
its weights and batches from ``--seed``, builds and warms the cell's one
step shape (set-up), measures ``--seconds`` of training, checks what the
timed path computed against the plain reference, and prints one JSON
object as the last line of standard output.  With ``--trace 0`` it
reports the cell's end-to-end metrics; with ``--trace 1`` it traces the
window and reports the per-layer metrics.

It exits non-zero before printing any result when JAX finds no TPU or
another number of chips than the cell asks for.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def fail(msg: str, code: int = 3) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def device_info(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def end_to_end(cell, r) -> dict:
    values = {
        "setup_s": r["setup_s"],
        "tokens_per_s": r["tokens"] / r["window_s"],
        "peak_hbm_gib": r["peak_bytes"] / 2**30,
    }
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end if m["name"] in values}


def per_layer(cell, r, summary, peaks) -> dict:
    from bench import spec

    ctx = {"spans": r["spans"], "summary": summary,
           "tokens_per_s": r["tokens"] / r["window_s"],
           "flops_per_token": r["flops_per_token"], "chips": r["chips"],
           "steps": r["steps"], "peaks": peaks}
    out = {}
    for m in cell.per_layer:
        v = spec.metric_reader(m["name"]).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def run_cell(cell, seed: int, seconds: float, trace: bool, devices, peaks,
             **kw) -> dict:
    """Set-up, window and check of ``cell`` on ``devices``; the result
    object.  ``kw`` goes to the traffic kind's ``run``."""
    from bench import spec

    kind = spec.kind(cell.traffic["kind"])
    r = kind.run(cell, seed, seconds, trace, T0, log, **kw)
    device = device_info(devices)
    device["memory_peak_bytes"] = r["peak_bytes"]
    out = {"correct": all(c["value"] <= c["limit"]
                          for c in r["checked"].values()),
           "attempted": r["steps"], "failed": 0}
    if trace:
        from bench import trace as tr

        summary = tr.summarize(*tr.load(r["trace_dir"]))
        shutil.rmtree(r["trace_dir"], ignore_errors=True)
        out["metrics"] = per_layer(cell, r, summary, peaks)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["device"] = device
        labels = tr.op_labels(r["hlo"])
        out["breakdown"] = {
            "device_ops": [[f"{k} {labels.get(k, '')}".strip(), v]
                           for k, v in summary.device_ops],
            "idle_gaps": [[k, v] for k, v in summary.idle_gaps]}
    else:
        out["metrics"] = end_to_end(cell, r)
        out["device"] = device
    out["checked"] = r["checked"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import spec

    cell = spec.cell(args.workload)
    # the compile cache sits at a fixed path inside the checkout, whatever
    # the environment says, so that two checkouts never share one
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"needs a TPU; JAX found {devices[0].platform}")
    if len(devices) != cell.chips:
        fail(f"{cell.name} asks for {cell.chips} chips; JAX found "
             f"{len(devices)}")
    peaks = spec.peaks(devices[0].device_kind)
    log(f"{cell.name}: {len(devices)} x {devices[0].device_kind}, seed "
        f"{args.seed}, {args.seconds:g} s, trace {args.trace}")

    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices,
                   peaks)
    for name, c in out["checked"].items():
        print(f"[bench] check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
