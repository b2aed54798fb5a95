#!/usr/bin/env python3
"""Smoke run of the system on a TPU, through the entry points users call.

Run from the root of a checkout, in one process that owns the chips::

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the four-chip phase only

One chip: ``repro train`` trains qwen2-0.5b at its published widths
(d_model 896, 24 layers, random weights from a seed) for 5 steps of
8 x 2048 tokens and must see a finite loss on every step; ``repro
serve`` then generates 4 x 32 tokens from 128-token prompts and must
return in-vocabulary ids only.

Four chips: a live probe of the chips, a plan and the reordered mesh;
the plan's certified all-reduce run through ``run_schedule`` with the
compiled Pallas add, checked against ``jax.lax.psum`` on the same seeded
data; then a few data-parallel qwen2-0.5b train steps on the planned
mesh with the gradient all-reduce left to XLA (``overlap=off``) and run
by the certified bucketed reducer (``overlap=bucketed``), whose losses
must agree within ``LOSS_RTOL``.

The script exits non-zero, before printing any result, when JAX finds no
TPU or fewer or more chips than asked for.  Its last line of output is
one JSON object naming the device JAX reports.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.abspath(__file__))

#: relative bound on |loss(bucketed) - loss(off)| at every step.  Both
#: steps see the same seeded weights and batches; they differ only in
#: how the bf16 gradients are summed over the four chips (XLA's
#: all-reduce vs the certified ring), which moves a gradient by at most
#: a bf16 rounding (2**-8 relative).  Three warm-up-scaled AdamW steps
#: move the loss by far less than this bound through such differences.
LOSS_RTOL = 1e-3


def _run_cli(argv) -> None:
    from repro.cli import main as cli_main

    print(f"[smoke] repro {' '.join(argv)}", flush=True)
    rc = cli_main(argv)
    if rc != 0:
        sys.exit(f"[smoke] FAIL: repro {argv[0]} exited {rc}")


def train_and_serve(train_argv, serve_argv) -> None:
    """The one-chip phase: the CLI's train then serve, in this process."""
    import jax

    _run_cli(train_argv)
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[smoke] train peak_bytes_in_use="
          f"{stats.get('peak_bytes_in_use', 'not reported')}", flush=True)
    _run_cli(serve_argv)


def check_certified_allreduce(sched, mesh, axis: str, seed: int = 0,
                              elems_per_rank: int = 1 << 22) -> None:
    """A certified all-reduce schedule vs ``psum`` on seeded data."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.kernels.schedule_runner import (
        check_postcondition, run_schedule)

    n = sched.n
    x = np.random.default_rng(seed).standard_normal(
        (n, elems_per_rank)).astype(np.float32)
    out = np.asarray(run_schedule(x, mesh, axis, sched))  # compiled fused_add
    bad = check_postcondition(sched, x, out)
    if bad:
        sys.exit(f"[smoke] FAIL: certified {sched.algorithm} broke its "
                 f"postcondition: {bad[:3]}")
    psum = np.asarray(jax.shard_map(
        lambda r: jax.lax.psum(r, axis), mesh=mesh, in_specs=P(axis),
        out_specs=P(axis), check_vma=False)(
            jax.device_put(x, NamedSharding(mesh, P(axis)))))
    want = psum.reshape(n, sched.n_chunks, -1)
    if sched.postcondition == "allreduce":
        got, ref = out, want
    else:                       # reduce-scatter: rank r owns chunk r
        got = out[np.arange(n), np.arange(n)]
        ref = want[np.arange(n), np.arange(n)]
    err = float(np.max(np.abs(got - ref)))
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
    print(f"[smoke] certified {sched.algorithm} ({sched.postcondition}, "
          f"{len(sched.rounds)} rounds, rank order {list(sched.rank_of)}) "
          f"== psum over {n} chips on {x.nbytes / n / 2**20:.0f} MiB per "
          f"rank: max |diff| {err:.3g}", flush=True)


def four_chips(arch, steps: int, batch: int, seq: int) -> None:
    """The four-chip phase: live probe -> plan -> reordered mesh, the
    certified all-reduce vs psum, and DP training off vs bucketed."""
    import jax
    import numpy as np

    from repro.launch.specs import configure_sp
    from repro.launch.train import train_on_mesh
    from repro.models import get_model
    from repro.session import Session, SessionConfig

    n = len(jax.devices())
    shapes = jax.eval_shape(get_model(arch).init, jax.random.PRNGKey(0))
    grad_bytes = float(sum(s.size * s.dtype.itemsize
                           for s in jax.tree.leaves(shapes)))
    cfg = SessionConfig.from_dict({
        "fabric": {"kind": "live"},
        "mesh": {"shape": [n], "axis_names": ["data"]},
        "workload": "train",
        "payload_bytes": grad_bytes,
        "overlap": {"mode": "bucketed"},
    })
    with Session(cfg) as session:
        lat = session.attach().probe.lat
        plan = session.plan()
        mesh = session.apply().mesh
        if mesh is None:
            sys.exit("[smoke] FAIL: the planned mesh could not be built")
        print(f"[smoke] live probe of {n} chips: one-way latency "
              f"{np.round(lat[~np.eye(n, dtype=bool)] * 1e6, 1).tolist()} "
              f"us; planned order {plan.mesh_plan.flat.tolist()}; mesh "
              f"{[d.id for d in mesh.devices.flat]}", flush=True)
        reducer = session.overlap_step(mesh, total_bytes=grad_bytes)
        # the plan's entry at the gradient payload, and the schedule the
        # bucketed reducer runs for each bucket
        check_certified_allreduce(session.lower("all-reduce").schedule,
                                  mesh, "data")
        check_certified_allreduce(reducer.schedule, mesh, "data")
    configure_sp(arch, mesh, plan=plan)

    losses = {}
    for mode, red in (("off", None), ("bucketed", reducer)):
        report, state, batch_sharding = train_on_mesh(
            arch, mesh, steps=steps, batch=batch, seq=seq, lr=1e-3,
            reducer=red, log_every=1)
        losses[mode] = [row["loss"] for row in report["history"]]
        secs = [round(row["sec"], 4) for row in report["history"]]
        param = jax.tree.leaves(state.params)[0]
        batch_devs = jax.tree.leaves(batch_sharding)[0].device_set
        print(f"[smoke] train {arch.name} d_model={arch.d_model} "
              f"overlap={mode}: losses {losses[mode]} step s {secs}; "
              f"a parameter on devices "
              f"{sorted(d.id for d in param.sharding.device_set)}, the "
              f"batch on {sorted(d.id for d in batch_devs)}", flush=True)
        if len(param.sharding.device_set) != n or len(batch_devs) != n:
            sys.exit(f"[smoke] FAIL: overlap={mode} did not use all "
                     f"{n} devices")
        del state
    np.testing.assert_allclose(losses["bucketed"], losses["off"],
                               rtol=LOSS_RTOL)
    print(f"[smoke] bucketed losses == off losses within rtol "
          f"{LOSS_RTOL}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="1: train + serve on one chip; 4: the four-chip "
                         "planned all-reduce and DP training phase only")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found "
                 f"{devices[0].platform}")
    if len(devices) != args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX found "
                 f"{len(devices)} chips")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    print(f"[smoke] {len(devices)} x {devices[0].device_kind}; compile "
          f"cache {enable_compile_cache()}", flush=True)
    if args.chips == 4:
        four_chips(get_config("qwen2-0.5b"), steps=3, batch=8, seq=2048)
    else:
        train_and_serve(
            ["train", "--arch", "qwen2-0.5b", "--mesh", "1x1",
             "--reorder", "simulate", "--batch", "8", "--seq", "2048",
             "--steps", "5", "--log-every", "1"],
            ["serve", "--arch", "qwen2-0.5b", "--batch", "4",
             "--prompt-len", "128", "--max-new", "32"])
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
