"""Readings that set a training cell's check limits: the control and the
planted faults, at the cell's own size, on the chips of this machine.

    python3 bench/readings.py --workload <cell> --seeds 1 2 3 [--fault ...]

For each seed it runs the plain reference over the cell's first
``check_steps`` batches, then each variant put in the program's place,
and prints the numbers the output check compares (``loss_gap``,
``grad_gap``, ``change_gap``) for the variant against the reference:

* ``control`` - the reference with every matrix product's operands put
  through float8 (e4m3, per-tensor scale), the precision below the
  configuration's bfloat16;
* ``half_batch`` - half of each batch left out, the mean taken over the
  rest;
* ``no_exchange`` - (several chips) the gradient's sum over the chips
  left out: each keeps its own rows' part.

A state left unchanged reads ``change_gap`` 1 by definition and needs no
run.  The benchmark's own runs never run this; the sound program's
readings are the ``check`` lines every benchmark run prints.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
VARIANTS = ("control", "half_batch", "no_exchange")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--variants", nargs="+", default=list(VARIANTS),
                    choices=VARIANTS)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench import data, spec
    from bench.kinds.train import compare

    cell = spec.cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) != cell.chips:
        sys.exit(f"needs {cell.chips} TPU chips; JAX found {devices}")
    ref = spec.reference(cell.config["family"])
    model, t = cell.config["model"], cell.traffic
    mesh = jax.make_mesh((len(devices),), ("r",))
    rows = t["batch_per_chip"] * len(devices)
    limits = spec.limits(cell.name)
    for seed in args.seeds:
        batches = [(b["tokens"], b["labels"]) for b in (
            data.batch(seed, i, rows, t["seq"], model["vocab_size"])
            for i in range(t["check_steps"]))]
        t0 = time.perf_counter()
        want = ref.train(model, t["optimizer"], mesh, seed, batches)
        ref_s = time.perf_counter() - t0
        for v in args.variants:
            if v == "no_exchange" and len(devices) == 1:
                continue
            kw = {"control": {"matmul_dtype": "float8_e4m3fn"},
                  "no_exchange": {"exchange": False}}.get(v, {})
            bs = batches if v != "half_batch" else [
                (a[: rows // 2], b[: rows // 2]) for a, b in batches]
            got = ref.train(model, t["optimizer"], mesh, seed, bs, **kw)
            row = {"cell": cell.name, "seed": seed, "variant": v,
                   "reference_s": ref_s,
                   "numbers": {k: c["value"] for k, c in
                               compare(got, want, limits).items()}}
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
