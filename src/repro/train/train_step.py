"""Train-step factory: loss -> grads -> AdamW, with sharding plumbing.

``make_train_step`` returns a pure function suitable for ``jax.jit`` with
``in_shardings/out_shardings`` derived from :mod:`repro.parallel.sharding`
(params TP specs; optimizer moments additionally ZeRO-1 sharded over the
DP axes; batch over DP axes).  The same function is what the multi-pod
dry-run lowers for every (arch x train shape) cell.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.optim import AdamWConfig, OptState, apply_opt, init_opt
from repro.parallel import sharding as shd

__all__ = ["TrainState", "make_train_step", "state_pspecs", "batch_pspecs",
           "init_state"]


class TrainState(NamedTuple):
    params: Any
    opt: OptState
    step: jnp.ndarray


def init_state(model, rng) -> TrainState:
    params = model.init(rng)
    return TrainState(params=params, opt=init_opt(params),
                      step=jnp.zeros((), jnp.int32))


def make_train_step(
    model, opt_cfg: AdamWConfig
) -> Callable[[TrainState, Dict[str, jnp.ndarray]], Tuple[TrainState, Dict]]:
    def train_step(state: TrainState, batch: Dict[str, jnp.ndarray]):
        loss, grads = jax.value_and_grad(model.loss)(state.params, batch)
        new_params, new_opt, metrics = apply_opt(
            opt_cfg, state.params, grads, state.opt)
        metrics = dict(metrics, loss=loss)
        return TrainState(new_params, new_opt, state.step + 1), metrics

    return train_step


def state_pspecs(state_shapes: TrainState, cfg: ModelConfig, mesh: Mesh
                 ) -> TrainState:
    """PartitionSpecs for a TrainState: TP params + ZeRO-1 moments."""
    pspecs = shd.param_pspecs(state_shapes.params, cfg, mesh)

    def z1(spec, leaf):
        return shd.zero1_spec(spec, tuple(leaf.shape), mesh)

    m_specs = jax.tree.map(z1, pspecs, state_shapes.params)
    return TrainState(
        params=pspecs,
        opt=OptState(m=m_specs, v=m_specs, count=P()),
        step=P(),
    )


def batch_pspecs(batch: Dict[str, Any], mesh: Mesh) -> Dict[str, P]:
    bs = shd.batch_spec(mesh)

    def spec(leaf):
        return P(*bs, *([None] * (leaf.ndim - 1)))

    return jax.tree.map(spec, batch)


def jit_train_step(model, opt_cfg, cfg: ModelConfig, mesh: Mesh,
                   state_shapes: TrainState, batch_shapes: Dict[str, Any],
                   donate: bool = True, overlap: str = "off",
                   reducer: Any = None, axis: str = "data"):
    """jit with explicit shardings (ready to .lower() for the dry-run).

    ``overlap`` selects the gradient all-reduce path:

    * ``"off"`` (default) — the baseline below, preserved bit-for-bit:
      grads reduce through the compiler-inserted psum of the sharded
      ``value_and_grad``;
    * ``"bucketed"`` / ``"fused"`` — the certified bucketed overlap
      path (:mod:`repro.train.overlap_grads`): pass a ``reducer``
      (see :func:`~repro.train.overlap_grads.reducer_from_plan` or
      ``Session.overlap_step``) whose mode decides the interleave
      granularity; ``axis`` names the 1-D data-parallel mesh axis.
    """
    if overlap != "off":
        from .overlap_grads import OVERLAP_MODES, jit_overlap_train_step
        if overlap not in OVERLAP_MODES:
            raise ValueError(
                f"overlap must be 'off' or one of {OVERLAP_MODES}, "
                f"got {overlap!r}")
        if reducer is None:
            raise ValueError(
                "overlap != 'off' needs a reducer (Session.overlap_step "
                "or overlap_grads.reducer_from_plan)")
        if reducer.mode != overlap:
            reducer = type(reducer)(
                reducer.mesh, reducer.axis, reducer.schedule,
                bucket_bytes=reducer.bucket_bytes, mode=overlap,
                use_pallas_add=reducer.use_pallas_add)
        return jit_overlap_train_step(model, opt_cfg, mesh, axis, reducer,
                                      donate=donate)
    step_fn = make_train_step(model, opt_cfg)
    s_specs = state_pspecs(state_shapes, cfg, mesh)
    b_specs = batch_pspecs(batch_shapes, mesh)
    to_ns = lambda tree: jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, P))
    metrics_ns = None  # replicated
    return jax.jit(
        step_fn,
        in_shardings=(to_ns(s_specs), to_ns(b_specs)),
        out_shardings=(to_ns(s_specs), metrics_ns),
        donate_argnums=(0,) if donate else (),
    )
