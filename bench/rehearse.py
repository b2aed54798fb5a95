"""Compile each cell's train step for a described TPU v5e, and print what
the compiler counts of its memory.  Nothing runs: this says whether a
size fits the chip and how much of it the step takes, not how fast it
is.  Run it by hand, on a machine with no chip:

    JAX_PLATFORMS=cpu python3 bench/rehearse.py --workload <cell> \
        [--layers N ...] [--batch-per-chip B ...]

It describes ``v5e:2x2`` (inside :func:`main`, never on import), builds
the cell's step exactly as ``bench/kinds/train.py`` does on a mesh of
one described chip or of all four, and compiles it for every
combination of the layer counts and per-chip batches given (the cell's
own by default).  The certified reducer of a four-chip cell comes from
a plan over a simulated uniform fabric, with the Pallas add compiled as
it is on a chip.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def rehearse(cell, layers: int, per_chip: int, topo) -> dict:
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from bench import spec
    from bench.kinds import train as K

    cfg = dict(cell.config, model=dict(cell.config["model"], n_layers=layers))
    traffic = cell.traffic
    arch = K.model_config(cfg)
    ref_mod = spec.reference(cfg["family"])
    make_state = K.state_maker(cfg, ref_mod)
    n = cell.chips
    shapes = jax.eval_shape(make_state, jax.random.key(0))
    grad_bytes = float(sum(s.size * s.dtype.itemsize
                           for s in jax.tree.leaves(shapes.params)))
    reducer = None
    if traffic["session"]["overlap"]["mode"] != "off":
        from repro.session import Session
        from repro.train.overlap_grads import reducer_from_plan
        import repro.kernels.ring_collective as rc

        rc.on_tpu = lambda: True          # compile fused_add as on a chip
        mesh = Mesh(np.array(topo.devices[:n]), ("data",))
        scfg = K.session_config(traffic, grad_bytes).replace(
            fabric={"kind": "tpu-fleet", "n_pods": 1, "pod_shape": (n, 1),
                    "scramble_seed": 0},
            mesh={"shape": (n,), "axis_names": ("data",)})
        with Session(scfg) as s:
            plan = s.plan()
            ov = scfg.overlap
            reducer = reducer_from_plan(plan, mesh, "data", grad_bytes,
                                        mode=ov.mode,
                                        use_pallas_add=ov.use_pallas_add)
    else:
        mesh = Mesh(np.array(topo.devices[:n]).reshape(n, 1),
                    ("data", "model"))
    rows = per_chip * n
    step_fn, state_shapes, batch_shapes, state_ns, batch_ns = K.build_step(
        arch, mesh, K.optimizer(traffic["optimizer"]), rows, traffic["seq"],
        make_state, reducer)
    sds = lambda s, ns: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=ns)
    t = time.perf_counter()
    out = {"layers": layers, "batch_per_chip": per_chip,
           "params": sum(s.size for s in jax.tree.leaves(shapes.params))}
    try:
        with jax.set_mesh(mesh):
            c = step_fn.lower(jax.tree.map(sds, state_shapes, state_ns),
                              jax.tree.map(sds, batch_shapes, batch_ns)
                              ).compile()
    except Exception as e:  # the compiler's refusal is the answer
        out["refused"] = str(e).splitlines()[0][:300]
        return out
    ma = c.memory_analysis()
    out.update(
        compile_s=round(time.perf_counter() - t, 1),
        argument_gib=ma.argument_size_in_bytes / 2**30,
        temp_gib=ma.temp_size_in_bytes / 2**30,
        output_gib=ma.output_size_in_bytes / 2**30,
        alias_gib=ma.alias_size_in_bytes / 2**30,
        kernels=c.as_text().count('custom_call_target="tpu_custom_call"'))
    if reducer is not None:
        out["reducer"] = (f"{reducer.schedule.algorithm}, "
                          f"{len(reducer.schedule.rounds)} rounds, bucket "
                          f"{reducer.bucket_bytes:.0f} B")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--layers", type=int, nargs="*")
    ap.add_argument("--batch-per-chip", type=int, nargs="*")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    from bench import spec

    # a compile for described devices cannot be read back without a chip
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    cell = spec.cell(args.workload)
    for layers in args.layers or [cell.config["model"]["n_layers"]]:
        for b in args.batch_per_chip or [cell.traffic["batch_per_chip"]]:
            print(rehearse(cell, layers, b, topo), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
