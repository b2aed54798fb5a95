"""RWKV6 WKV chunk-scan Pallas TPU kernel.

The WKV recurrence is sequential in time; running it token-by-token from
HBM is memory-bound (state [K, V] re-read per token).  TPU adaptation:
process the sequence in VMEM-resident **chunks** — the grid iterates
(batch*head, n_chunks); the chunk dimension is TPU-sequential so the
running state [K, V] persists in VMEM scratch across chunk iterations,
touching HBM once per chunk instead of once per token.  Within a chunk a
``fori_loop`` applies the exact per-token update (data-dependent decay
prevents a pure matmul form without approximation; the intra-chunk
matmul variant used by production RWKV kernels is noted as follow-up in
EXPERIMENTS.md §Perf).

Validated in interpret mode against :func:`repro.kernels.ref.wkv_chunk_ref`.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["wkv_scan"]


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, state_scr, y_scr,
                *, chunk: int, n_chunks: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    # channel-major [K, T] copies: token t is the static column t:t+1, so
    # every slice below has a static offset, as the TPU tiling requires
    r = r_ref[0].astype(jnp.float32).T                # [K, T]
    k = k_ref[0].astype(jnp.float32).T
    w = w_ref[0].astype(jnp.float32).T
    v = v_ref[0].astype(jnp.float32)                  # [T, V]
    u = u_ref[0].astype(jnp.float32).T                # [K, 1]
    state = state_scr[...]                            # [K, V]
    for t in range(chunk):
        kv = k[:, t:t + 1] * v[t:t + 1, :]            # [K, V]
        y_scr[t:t + 1, :] = jnp.sum((state + u * kv) * r[:, t:t + 1],
                                    axis=0, keepdims=True)
        state = w[:, t:t + 1] * state + kv
    state_scr[...] = state
    o_ref[0] = y_scr[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv_scan(
    r: jnp.ndarray,   # [B, S, H, K]
    k: jnp.ndarray,
    v: jnp.ndarray,   # [B, S, H, V]
    w: jnp.ndarray,
    u: jnp.ndarray,   # [H, K]
    chunk: int = 64,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns y [B, S, H, V] (fresh zero initial state)."""
    B, S, H, K = r.shape
    V = v.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    n_chunks = S // chunk

    # layout: fold (B, H) into one grid dim; time-major inside
    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, S, x.shape[-1])

    rf, kf, vf, wf = fold(r), fold(k), fold(v), fold(w)
    # a unit axis keeps u's block (1, 1, K) equal to the array's last two
    # dims, as the TPU tiling requires
    uf = jnp.broadcast_to(u[None], (B, H, K)).reshape(B * H, 1, K)

    grid = (B * H, n_chunks)
    kernel = functools.partial(_wkv_kernel, chunk=chunk, n_chunks=n_chunks)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, K), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, chunk, K), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, chunk, V), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, chunk, K), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, 1, K), lambda bh, c: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, V), lambda bh, c: (bh, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, V), v.dtype),
        scratch_shapes=[pltpu.VMEM((K, V), jnp.float32),
                        pltpu.VMEM((chunk, V), jnp.float32)],
        interpret=interpret,
    )(rf, kf, vf, wf, uf)
    return out.reshape(B, H, S, V).transpose(0, 2, 1, 3)
