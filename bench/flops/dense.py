"""Model FLOPs of a dense decoder LM's training step, from its shapes.

Counted: every matrix product of the forward pass (QKV and output
projections, the SwiGLU feed-forward, the output head, and causal
attention's scores and weighted values over the positions each query may
see), times three for forward plus backward.  Not counted: the
recomputation of block remat, elementwise work (norms, rotary, softmax,
biases), the embedding lookup and the optimizer.  So ``mfu`` reads the
work the model needs, not the work the program does.
"""

from __future__ import annotations

from typing import Any, Dict


def matmul_params(m: Dict[str, Any]) -> int:
    """Weights that enter a matrix product once per token."""
    d, hd = m["d_model"], m["head_dim"]
    q, kv = m["n_heads"] * hd, m["n_kv_heads"] * hd
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * m["d_ff"]
    return m["n_layers"] * per_layer + d * m["vocab_size"]


def attention_flops(m: Dict[str, Any], seq: int) -> float:
    """Forward attention FLOPs per token, averaged over a causal row."""
    per_pos = 4 * m["n_heads"] * m["head_dim"]      # q.k and p.v, 2 each
    return m["n_layers"] * per_pos * (seq + 1) / 2


def train_flops_per_token(m: Dict[str, Any], seq: int) -> float:
    return 3 * (2 * matmul_params(m) + attention_flops(m, seq))
