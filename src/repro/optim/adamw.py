"""AdamW with global-norm clipping and ZeRO-1-shardable state.

Self-contained (no optax in the container).  State is a pytree mirroring
the parameters (``m``, ``v`` + a scalar count), so the sharding layer can
assign each moment tensor its own (ZeRO-1) spec independently of the
parameter spec.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["AdamWConfig", "OptState", "init_opt", "apply_opt", "global_norm",
           "cosine_schedule", "OPTIMIZER_SCOPE"]

#: device scope of one optimizer step, clipping included
OPTIMIZER_SCOPE = "optimizer"


class OptState(NamedTuple):
    m: Any
    v: Any
    count: jnp.ndarray


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    schedule: Optional[Callable[[jnp.ndarray], jnp.ndarray]] = None


def cosine_schedule(peak: float, warmup: int, total: int, floor: float = 0.1):
    def fn(step):
        step = step.astype(jnp.float32)
        warm = peak * step / max(warmup, 1)
        frac = jnp.clip((step - warmup) / max(total - warmup, 1), 0.0, 1.0)
        cos = floor * peak + (1 - floor) * peak * 0.5 * (1 + jnp.cos(jnp.pi * frac))
        return jnp.where(step < warmup, warm, cos)
    return fn


def init_opt(params: Any) -> OptState:
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    return OptState(
        m=jax.tree.map(zeros, params),
        v=jax.tree.map(zeros, params),
        count=jnp.zeros((), jnp.int32),
    )


def global_norm(tree: Any) -> jnp.ndarray:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves))


def apply_opt(
    cfg: AdamWConfig, params: Any, grads: Any, state: OptState
) -> Tuple[Any, OptState, dict]:
    """One AdamW step.  Returns (params', state', metrics)."""
    with jax.named_scope(OPTIMIZER_SCOPE):
        gnorm = global_norm(grads)
        scale = jnp.minimum(1.0, cfg.clip_norm / (gnorm + 1e-9))
        count = state.count + 1
        lr = cfg.schedule(count) if cfg.schedule is not None else cfg.lr
        b1c = 1.0 - cfg.b1 ** count.astype(jnp.float32)
        b2c = 1.0 - cfg.b2 ** count.astype(jnp.float32)

        def upd(p, g, m, v):
            g = g.astype(jnp.float32) * scale
            m = cfg.b1 * m + (1 - cfg.b1) * g
            v = cfg.b2 * v + (1 - cfg.b2) * jnp.square(g)
            mh = m / b1c
            vh = v / b2c
            step = (mh / (jnp.sqrt(vh) + cfg.eps)
                    + cfg.weight_decay * p.astype(jnp.float32))
            return (p.astype(jnp.float32) - lr * step).astype(p.dtype), m, v

        flat_p, tdef = jax.tree.flatten(params)
        flat_g = tdef.flatten_up_to(grads)
        flat_m = tdef.flatten_up_to(state.m)
        flat_v = tdef.flatten_up_to(state.v)
        out = [upd(p, g, m, v)
               for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
        new_p = tdef.unflatten([o[0] for o in out])
        new_m = tdef.unflatten([o[1] for o in out])
        new_v = tdef.unflatten([o[2] for o in out])
        metrics = {"grad_norm": gnorm, "lr": jnp.asarray(lr, jnp.float32)}
        return new_p, OptState(new_m, new_v, count), metrics
