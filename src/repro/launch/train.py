"""Production training launcher internals.

The user-facing entry point is::

    python -m repro train --arch glm4-9b --steps 1000 \
        --mesh 16x16 --reorder probe        # probe + solve + reordered mesh

(``python -m repro.launch.train`` remains as a deprecation shim that
delegates there.)  :func:`build_mesh` is the piece the CLI and tests
share: it drives a :class:`repro.session.Session` through
probe → plan → apply and returns the (reordered) mesh plus the compiled
plan.  The paper's technique enters exactly once: the device order used
to build the Mesh.  :func:`train_on_mesh` then runs the sharded train
step on that mesh.
"""

from __future__ import annotations

import warnings
from typing import Optional

import numpy as np


def parse_mesh(s: str):
    dims = tuple(int(x) for x in s.split("x"))
    axes = ("pod", "data", "model")[-len(dims):] if len(dims) == 3 else (
        ("data", "model") if len(dims) == 2 else ("data",))
    return dims, axes


def default_job_mix(payload_bytes: float, moe: bool = False):
    """Deprecated: use :func:`repro.session.train_mix`."""
    warnings.warn(
        "repro.launch.train.default_job_mix is deprecated; use "
        "repro.session.train_mix", DeprecationWarning, stacklevel=2)
    from repro.session import train_mix

    return train_mix(payload_bytes, moe=moe)


class LaunchError(RuntimeError):
    """The launcher cannot build the mesh or the step it was asked for."""


def build_mesh(args, n_devices: int, mix=None, moe: bool = False,
               session_config=None, grad_bytes: Optional[float] = None):
    """Mesh per --reorder policy: none | simulate | probe.

    ``simulate``/``probe`` run the full Session lifecycle: attach (a
    simulated scrambled TPU fleet, or live pairwise probes), plan (the
    per-collective algorithm + rank order + the N-D mesh assignment,
    compiled once and cached under the fabric fingerprint), apply (the
    reordered Mesh).  ``mix`` overrides the planned collective histogram
    (serving passes its decode-shaped mix); ``session_config`` supplies
    cache dir / budget / payload / overlap when the caller (the CLI)
    already resolved a :class:`~repro.session.SessionConfig`.

    Returns ``(mesh, plan, reducer)``: plan is a :class:`repro.plan.Plan`
    (None when reordering is off), and reducer the certified gradient
    reducer of ``Session.overlap_step`` for ``grad_bytes`` of gradients
    when the config's overlap mode is not ``off`` (else None).

    Raises :class:`LaunchError` when ``args.mesh`` does not cover exactly
    ``n_devices`` devices, or when the planned mesh cannot be built.
    """
    from repro.launch.mesh import make_mesh_for_tests
    from repro.session import Session, SessionConfig

    shape, axes = parse_mesh(args.mesh)
    if int(np.prod(shape)) != n_devices:
        raise LaunchError(
            f"--mesh {args.mesh} spans {int(np.prod(shape))} devices but "
            f"this process has {n_devices}")
    base = session_config or SessionConfig()
    overlap = grad_bytes is not None and base.overlap.mode != "off"
    if args.reorder == "none":
        if overlap:
            raise LaunchError(
                f"overlap mode {base.overlap.mode!r} runs the plan's "
                f"certified all-reduce; it needs --reorder simulate or probe")
        return make_mesh_for_tests(shape, axes), None, None

    from repro.session.config import FabricConfig

    pods = shape[0] if len(shape) == 3 else 1
    if args.reorder == "probe":
        fabric = {"kind": "live"}
    elif base.fabric != FabricConfig():
        fabric = {}          # the user declared a fabric: honor it
    else:                                           # simulate
        fabric = {"kind": "tpu-fleet", "n_pods": max(pods, 1),
                  "pod_shape": (shape[-2], shape[-1]) if len(shape) >= 2
                  else (shape[-1], 1),
                  "scramble_seed": 0}
    cache_dir = getattr(args, "plan_cache_dir", None)
    payload = getattr(args, "payload_bytes", None)
    cfg = base.replace(
        fabric=fabric,
        mesh={"shape": shape, "axis_names": axes},
        cache={"dir": cache_dir if cache_dir is not None
               else base.cache.dir},
        payload_bytes=payload if payload is not None else base.payload_bytes,
        moe=moe or base.moe,
    )
    reducer = None
    with Session(cfg) as session:
        plan = session.plan(mix=mix)
        applied = session.apply()
        if applied.mesh is None:
            raise LaunchError(
                f"the planned {args.mesh} mesh could not be built over "
                f"{n_devices} devices (see the session warning above)")
        if overlap:
            reducer = session.overlap_step(applied.mesh,
                                           total_bytes=grad_bytes)
        hit = "cache hit" if session.service.stats["cache_hits"] else \
            f"compiled in {plan.compile_seconds:.2f}s"
    mp = plan.mesh_plan
    print(f"[launch] plan {plan.fingerprint.digest} ({hit}): "
          f"mesh identity {mp.baseline_cost:.5f} -> optimized {mp.cost:.5f} "
          f"({mp.baseline_cost / max(mp.cost, 1e-30):.2f}x), "
          f"{len(plan.entries)} collective entries")
    return applied.mesh, plan, reducer


def train_on_mesh(arch, mesh, *, steps: int, batch: int, seq: int,
                  lr: float, reducer=None, ckpt_dir: Optional[str] = None,
                  log_every: int = 20):
    """Train ``arch`` from a seeded init on ``mesh`` with the sharded step.

    The step is :func:`repro.train.train_step.jit_train_step`: TP specs
    plus ZeRO-1 over the mesh, or — given a ``reducer`` — the certified
    bucketed gradient all-reduce over ``reducer.axis`` with the state
    replicated.  The state is initialised straight into its shardings
    and every batch is placed on the mesh before its step.

    Returns ``(report, state, batch_sharding)``: the
    :meth:`repro.train.Trainer.run` report, the final train state and
    the sharding each batch was placed with.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.data import SyntheticLM, host_batch
    from repro.models import get_model
    from repro.optim import AdamWConfig, cosine_schedule
    from repro.train import Trainer, TrainerConfig, init_state
    from repro.train.train_step import (
        batch_pspecs, jit_train_step, state_pspecs)

    model = get_model(arch)
    opt = AdamWConfig(schedule=cosine_schedule(lr, 10, steps))
    ds = SyntheticLM(arch.vocab_size, seq, batch, seed=0)

    def init(rng):
        return init_state(model, rng)

    rng = jax.random.PRNGKey(0)
    state_shapes = jax.eval_shape(init, rng)
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    batch_shapes = {"tokens": tokens, "labels": tokens}
    if reducer is None:
        def named(tree):
            return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                                is_leaf=lambda x: isinstance(x, P))
        state_ns = named(state_pspecs(state_shapes, arch, mesh))
        batch_ns = named(batch_pspecs(batch_shapes, mesh))
        step_fn = jit_train_step(model, opt, arch, mesh, state_shapes,
                                 batch_shapes)
    else:
        # pure data parallelism: the whole state replicated, the batch
        # split over the reducer's axis (as jit_overlap_train_step wants)
        state_ns = NamedSharding(mesh, P())
        batch_ns = NamedSharding(mesh, P(reducer.axis))
        step_fn = jit_train_step(model, opt, arch, mesh, state_shapes,
                                 batch_shapes, overlap=reducer.mode,
                                 reducer=reducer, axis=reducer.axis)
    state = jax.jit(init, out_shardings=state_ns)(rng)

    def batches():
        i = 0
        while True:
            yield jax.device_put(host_batch(ds, i), batch_ns)
            i += 1

    trainer = Trainer(
        step_fn=step_fn, state=state, batches=batches(),
        cfg=TrainerConfig(total_steps=steps, ckpt_every=50,
                          ckpt_dir=ckpt_dir, log_every=log_every))
    with jax.set_mesh(mesh):
        report = trainer.run()
    return report, trainer.state, batch_ns


def main() -> None:
    """Deprecated entry point: delegates to ``python -m repro train``."""
    import sys

    warnings.warn(
        "python -m repro.launch.train is deprecated; use "
        "`python -m repro train`", DeprecationWarning, stacklevel=2)
    from repro.cli import main as cli_main

    raise SystemExit(cli_main(["train", *sys.argv[1:]]))


if __name__ == "__main__":
    main()
