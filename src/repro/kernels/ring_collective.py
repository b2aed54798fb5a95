"""Rank-reordered ring reduce-scatter / all-reduce — the paper's object.

:func:`ring_reduce_scatter` is a ``shard_map`` ring: N-1 steps of
``ppermute`` (neighbor order = **the solved rank permutation**) with the
local accumulation fused by a small Pallas add kernel (:func:`fused_add`,
through :func:`accumulate`).  The kernel compiles on a TPU and runs in
interpret mode on every other backend.  The ``perm`` argument is where
Cloud-Collectives plugs in: the neighbor list is the ring order produced
by :mod:`repro.core.solver`.

Note the equivalence: XLA's own reduce-scatter follows mesh-axis order,
so on the *reordered mesh* the plain ``jax.lax.psum_scatter`` already
benefits from the paper's technique; these kernels exist to (a) prove the
schedule explicitly and (b) fuse the accumulation.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["on_tpu", "fused_add", "accumulate", "ring_reduce_scatter",
           "ring_all_reduce"]


# ---------------------------------------------------------------------------
# local fused accumulate (Pallas)
# ---------------------------------------------------------------------------

def _add_kernel(a_ref, b_ref, o_ref):
    o_ref[...] = (a_ref[...].astype(jnp.float32)
                  + b_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def fused_add(a: jnp.ndarray, b: jnp.ndarray, block: int = 1024,
              interpret: bool = False) -> jnp.ndarray:
    """Tiled elementwise accumulate — the ring step's reduction op."""
    assert a.shape == b.shape
    flat = a.reshape(-1)
    n = flat.shape[0]
    block = min(block, n)
    pad = (-n) % block
    af = jnp.pad(flat, (0, pad))
    bf = jnp.pad(b.reshape(-1), (0, pad))
    out = pl.pallas_call(
        _add_kernel,
        grid=(af.shape[0] // block,),
        in_specs=[pl.BlockSpec((block,), lambda i: (i,)),
                  pl.BlockSpec((block,), lambda i: (i,))],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct(af.shape, a.dtype),
        interpret=interpret,
    )(af, bf)
    return out[:n].reshape(a.shape)


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU."""
    return jax.default_backend() == "tpu"


def accumulate(a: jnp.ndarray, b: jnp.ndarray,
               use_pallas_add: bool) -> jnp.ndarray:
    """The collectives' reduce step: ``a + b``.

    With ``use_pallas_add`` the sum goes through :func:`fused_add`,
    compiled on a TPU and interpreted on every other backend: the one
    place the certified collective paths decide interpret mode.
    """
    if use_pallas_add:
        return fused_add(a, b, interpret=not on_tpu())
    return a + b


# ---------------------------------------------------------------------------
# portable ring (shard_map + ppermute), neighbor order = solved perm
# ---------------------------------------------------------------------------

def _ring_links(perm: Sequence[int]) -> list:
    """ppermute links following the solved ring order: perm[i] -> perm[i+1].

    This closed form equals ``JaxExecutor().lower(ring_program).links``
    for a ring Program permuted by ``perm`` (pinned by
    ``tests/test_collective_ir.py``); the direct computation is kept
    because kernels re-derive links per trace and compiling a full
    O(n^2) Program for n neighbor pairs would dominate trace time at
    large n.
    """
    n = len(perm)
    return [(int(perm[i]), int(perm[(i + 1) % n])) for i in range(n)]


def ring_reduce_scatter(
    x: jnp.ndarray,
    mesh: Mesh,
    axis: str,
    perm: Optional[Sequence[int]] = None,
    use_pallas_add: bool = True,
) -> jnp.ndarray:
    """Reduce-scatter over ``axis`` with an explicit reordered ring.

    ``x``: [n, L] (L % n == 0), dim 0 sharded over ``axis`` — row d is
    device d's full local contribution.  Returns [n, L//n] sharded the
    same way: row d is the fully-reduced chunk d.

    Schedule (ring-position space; position i = pos_of[device]):
    at step s, position i forwards the partial sum of chunk
    ``perm[(i - s - 1) mod n]`` to position i+1, receives the partial of
    ``perm[(i - s - 2) mod n]`` and adds its own contribution.  After
    n-1 steps position i holds exactly chunk ``perm[i]`` = its own device
    id — i.e. reduce-scatter output lands in device-id order regardless
    of the ring order used for transport.
    """
    n = mesh.shape[axis]
    L = x.shape[1]
    assert x.shape[0] == n and L % n == 0, (x.shape, n)
    if perm is None:
        perm = list(range(n))
    links = _ring_links(perm)
    pos_of = np.zeros(n, dtype=np.int64)
    for i, d in enumerate(perm):
        pos_of[d] = i
    pos_arr = jnp.asarray(pos_of)
    perm_arr = jnp.asarray(np.asarray(perm, dtype=np.int64))

    def per_device(xs):
        chunks = xs[0].reshape(n, L // n)            # my n chunk contributions
        me = jax.lax.axis_index(axis)
        i = pos_arr[me]
        buf = jnp.take(chunks, perm_arr[(i - 1) % n], axis=0)

        def body(s, buf):
            received = jax.lax.ppermute(buf, axis, links)
            idx = perm_arr[(i - s - 2) % n]
            mine = jnp.take(chunks, idx, axis=0)
            return accumulate(received, mine, use_pallas_add)

        buf = jax.lax.fori_loop(0, n - 1, body, buf)
        return buf[None]

    f = jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(P(axis),), out_specs=P(axis), check_vma=False)
    return f(x)


def ring_all_reduce(x, mesh, axis, perm=None, **kw):
    """reduce-scatter + all-gather (bandwidth-optimal ring all-reduce).

    Returns [n, L]: every row holds the full reduced vector.
    """
    n = mesh.shape[axis]
    rs = ring_reduce_scatter(x, mesh, axis, perm=perm, **kw)

    def ag(c):
        # chunks arrive in device-id order (see ring_reduce_scatter)
        return jax.lax.all_gather(c[0], axis).reshape(1, -1)

    return jax.shard_map(ag, mesh=mesh, in_specs=(P(axis),),
                         out_specs=P(axis), check_vma=False)(rs)
