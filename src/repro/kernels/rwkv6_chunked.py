"""RWKV6 WKV kernel, chunked MATMUL form (the MXU fast path).

The token-loop kernel (:mod:`rwkv6_scan`) is VPU-bound: per token it does
rank-1 state updates.  This kernel restates the recurrence per chunk of T
tokens as three matmuls (the standard chunked linear-attention identity,
extended with RWKV6's data-dependent per-channel decay):

with A_t = prod_{s<=t} w_s (cumulative decay within the chunk),
r~_t = r_t * A_{t-1}, k~_s = k_s / A_s:

    y_t   = r~_t @ S_0  +  sum_{s<t} (r~_t . k~_s) v_s  +  (r_t.(u*k_t)) v_t
    S_T   = diag(A_T) @ (S_0 + k~^T V)      # next chunk's initial state

i.e. Y = R~ S_0 + ((R~ K~^T) * M_strict) V + rowscale(R.(u*K)) V — all
MXU-shaped [T,K]x[K,V] / [T,K]x[K,T] contractions instead of T rank-1
updates.

Numerics: k~ = k / A_s grows like w_min^-T within a chunk; the products
consumed downstream are bounded (A_{t-1}/A_s <= 1 for s <= t-1), so only
the intermediate k~ must stay in f32 range: with the default T=16 this is
safe for per-channel decays w >= 1e-2 (k~ <= 1e32 < f32 max); the wrapper
asserts the chunk bound.  Validated against :func:`ref.wkv_chunk_ref`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["wkv_chunked_matmul"]


def _wkv_chunk_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, state_scr,
                      *, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    r = r_ref[0].astype(jnp.float32)         # [T, K]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)         # [T, V]
    w = w_ref[0].astype(jnp.float32)         # [T, K] decays in (0, 1)
    u = u_ref[0].astype(jnp.float32)         # [1, K]
    S0 = state_scr[...]                      # [K, V]

    T = r.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)

    log_w = jnp.log(w)
    # log A_t = sum_{s<=t} log w_s, as a lower-triangular matmul: the TPU
    # kernel compiler has no cumsum
    la = jax.lax.dot_general(
        jnp.where(row >= col, 1.0, 0.0), log_w, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)                  # [T, K]
    A = jnp.exp(la)                          # [T, K]
    A_prev = jnp.exp(la - log_w)             # A_{t-1} (A_0 = 1)
    r_t = r * A_prev                         # r~
    k_t = k * jnp.exp(-la)                   # k~

    inter = jax.lax.dot_general(
        r_t, S0, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                  # [T, V]
    qk = jax.lax.dot_general(
        r_t, k_t, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                  # [T, T]
    qk = jnp.where(row > col, qk, 0.0)                       # strict lower
    intra = jax.lax.dot_general(
        qk, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                  # [T, V]
    bonus = jnp.sum(r * u * k, axis=1, keepdims=True) * v
    o_ref[0] = (inter + intra + bonus).astype(o_ref.dtype)

    A_T = A[T - 1:T]                                         # [1, K]
    kv = jax.lax.dot_general(
        k_t * A_T, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                  # [K, V]
    state_scr[...] = A_T.T * S0 + kv


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv_chunked_matmul(
    r: jnp.ndarray,   # [B, S, H, K]
    k: jnp.ndarray,
    v: jnp.ndarray,   # [B, S, H, V]
    w: jnp.ndarray,   # [B, S, H, K], decays in (0, 1)
    u: jnp.ndarray,   # [H, K]
    chunk: int = 16,
    interpret: bool = False,
) -> jnp.ndarray:
    B, S, H, K = r.shape
    V = v.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    assert chunk <= 32, "k~ range bound: keep chunks short (see docstring)"
    n_chunks = S // chunk

    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, S, x.shape[-1])

    rf, kf, vf, wf = fold(r), fold(k), fold(v), fold(w)
    # a unit axis keeps u's block (1, 1, K) equal to the array's last two
    # dims, as the TPU tiling requires
    uf = jnp.broadcast_to(u[None], (B, H, K)).reshape(B * H, 1, K)

    out = pl.pallas_call(
        functools.partial(_wkv_chunk_kernel, chunk=chunk),
        grid=(B * H, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, K), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, chunk, K), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, chunk, V), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, chunk, K), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, 1, K), lambda bh, c: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, V), lambda bh, c: (bh, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, V), v.dtype),
        scratch_shapes=[pltpu.VMEM((K, V), jnp.float32)],
        interpret=interpret,
    )(rf, kf, vf, wf, uf)
    return out.reshape(B, H, S, V).transpose(0, 2, 1, 3)
