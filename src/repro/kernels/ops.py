"""Jit'd public wrappers for the Pallas kernels.

``attention_op`` / ``wkv_op`` auto-select interpret mode off-TPU so the
same call sites work in tests (CPU, interpret=True) and production
(TPU, compiled Mosaic).  The model configs choose the implementation via
``attention_impl`` ('xla' | 'flash').
"""

from __future__ import annotations

from .flash_attention import flash_attention
from .ring_collective import (fused_add, on_tpu, ring_all_reduce,
                              ring_reduce_scatter)
from .rwkv6_chunked import wkv_chunked_matmul
from .rwkv6_scan import wkv_scan

__all__ = ["attention_op", "wkv_op", "wkv_chunked_op", "fused_add",
           "ring_reduce_scatter", "ring_all_reduce", "on_tpu"]


def wkv_chunked_op(r, k, v, w, u, chunk=16):
    """MXU matmul-form WKV (auto interpret fallback off-TPU)."""
    return wkv_chunked_matmul(r, k, v, w, u, chunk=chunk,
                              interpret=not on_tpu())


def attention_op(q, k, v, causal=True, window=0, block_q=128, block_k=128):
    """Flash attention with automatic interpret fallback off-TPU."""
    return flash_attention(
        q, k, v, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=not on_tpu())


def wkv_op(r, k, v, w, u, chunk=64):
    return wkv_scan(r, k, v, w, u, chunk=chunk, interpret=not on_tpu())
