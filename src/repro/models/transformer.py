"""Decoder-only transformer LM: dense (GQA+RoPE), MoE, MLA, VLM backbone.

Covers glm4-9b, qwen2-0.5b, granite-8b, minitron-8b, dbrx-132b,
deepseek-v2-236b and llava-next-mistral-7b (vision stub).

Layer-stacked parameters + ``jax.lax.scan`` keep the HLO size independent
of depth (compiling 60-layer deepseek on the CPU dry-run).  Leading
non-uniform layers (deepseek's first dense layer) are unrolled separately.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

from . import layers as L

Params = Dict[str, Any]

#: device scopes of the training step's layers: a block's attention
#: (projections, RoPE, scores, softmax, ``@ v``, output projection), its
#: feed-forward (dense or MoE), and the output head with the cross
#: entropy (:func:`lm_loss`)
ATTENTION_SCOPE = "attention"
MLP_SCOPE = "mlp"
LOSS_SCOPE = "loss"


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


class DecoderLM:
    """Functional decoder-only LM; all methods are jit/pjit friendly."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ------------------------------------------------------------------
    # init
    # ------------------------------------------------------------------
    def _init_block(self, rng, moe: bool) -> Params:
        cfg = self.cfg
        dt = _dtype(cfg)
        r = jax.random.split(rng, 4)
        p: Params = {
            "attn_norm": jnp.ones((cfg.d_model,), dt),
            "mlp_norm": jnp.ones((cfg.d_model,), dt),
        }
        if cfg.use_mla:
            p["attn"] = L.init_mla(r[0], cfg, dt)
        else:
            p["attn"] = L.init_attention(r[0], cfg, dt)
        if moe:
            p["moe"] = L.init_moe(r[1], cfg, dt)
        else:
            p["mlp"] = L.init_mlp(r[1], cfg.d_model, cfg.d_ff, dt)
        return p

    def init(self, rng) -> Params:
        cfg = self.cfg
        dt = _dtype(cfg)
        r = jax.random.split(rng, 4 + cfg.n_layers)
        n_head_layers = cfg.n_dense_layers if cfg.n_experts else 0
        n_scan = cfg.n_layers - n_head_layers
        moe = cfg.n_experts > 0

        # Stacked uniform blocks: init each layer then stack leaves.
        blocks = [self._init_block(r[4 + i], moe) for i in range(n_scan)]
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)

        params: Params = {
            "embed": L.dense_init(r[0], (cfg.vocab_size, cfg.d_model),
                                  scale=0.02, dtype=dt),
            "blocks": stacked,
            "final_norm": jnp.ones((cfg.d_model,), dt),
        }
        if n_head_layers:
            params["head_blocks"] = [
                self._init_block(r[1], False) for _ in range(n_head_layers)
            ]
        if not cfg.tie_embeddings:
            params["lm_head"] = L.dense_init(
                r[2], (cfg.d_model, cfg.vocab_size), scale=0.02, dtype=dt)
        return params

    # ------------------------------------------------------------------
    # blocks
    # ------------------------------------------------------------------
    def _block_fwd(self, p: Params, x, positions) -> Tuple[jnp.ndarray, jnp.ndarray]:
        cfg = self.cfg
        if cfg.sequence_parallel:
            x = L.sp_constrain(x)
        h = L.rms_norm(x, p["attn_norm"], cfg.norm_eps)
        with jax.named_scope(ATTENTION_SCOPE):
            if cfg.use_mla:
                attn_out, _ = L.mla_attention(p["attn"], h, cfg, positions)
            else:
                attn_out, _ = L.attention(
                    p["attn"], h, cfg, causal=True, positions=positions,
                    window=cfg.attn_window)
        x = x + attn_out
        h = L.rms_norm(x, p["mlp_norm"], cfg.norm_eps)
        with jax.named_scope(MLP_SCOPE):
            if "moe" in p:
                y, aux = L.moe_layer(p["moe"], h, cfg)
            else:
                y, aux = L.mlp(p["mlp"], h), jnp.zeros((), jnp.float32)
        return x + y, aux

    def _embed(self, params: Params, tokens,
               frontend_embeds: Optional[jnp.ndarray]) -> jnp.ndarray:
        cfg = self.cfg
        x = params["embed"][tokens]
        if cfg.family == "vlm" and frontend_embeds is not None:
            # anyres stub: patch embeddings replace the first n_img slots
            n_img = frontend_embeds.shape[1]
            x = jnp.concatenate(
                [frontend_embeds.astype(x.dtype), x[:, n_img:]], axis=1)
        return x

    def forward(
        self,
        params: Params,
        tokens: jnp.ndarray,
        frontend_embeds: Optional[jnp.ndarray] = None,
        return_features: bool = False,
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """tokens [B, S] -> (logits [B, S, V], aux_loss)."""
        cfg = self.cfg
        x = self._embed(params, tokens, frontend_embeds)
        positions = jnp.arange(tokens.shape[1])
        aux_total = jnp.zeros((), jnp.float32)

        for hp in params.get("head_blocks", []):
            x, aux = self._block_fwd(hp, x, positions)
            aux_total = aux_total + aux

        def body(carry, bp):
            x, aux_acc = carry
            fn = self._block_fwd
            if cfg.remat == "block":
                fn = jax.checkpoint(fn)
            x, aux = fn(bp, x, positions)
            return (x, aux_acc + aux), None

        if cfg.use_scan:
            (x, aux_total), _ = jax.lax.scan(body, (x, aux_total), params["blocks"])
        else:
            n = jax.tree.leaves(params["blocks"])[0].shape[0]
            for i in range(n):
                bp = jax.tree.map(lambda a: a[i], params["blocks"])
                (x, aux_total), _ = body((x, aux_total), bp)

        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        if return_features:
            return x, aux_total
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = x @ head
        return logits, aux_total

    # ------------------------------------------------------------------
    # losses
    # ------------------------------------------------------------------
    def loss(self, params: Params, batch: Dict[str, jnp.ndarray]) -> jnp.ndarray:
        cfg = self.cfg
        feats, aux = self.forward(
            params, batch["tokens"], batch.get("frontend_embeds"),
            return_features=True)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        ce = lm_loss(feats, head, batch["labels"], cfg.loss_chunk_size)
        return ce + 0.01 * aux

    # ------------------------------------------------------------------
    # serving
    # ------------------------------------------------------------------
    def init_cache(self, batch: int, s_max: int, dtype=None) -> Params:
        cfg = self.cfg
        dt = dtype or _dtype(cfg)
        n_head_layers = cfg.n_dense_layers if cfg.n_experts else 0
        n_scan = cfg.n_layers - n_head_layers

        def one(n):
            if cfg.use_mla:
                return {
                    "ckv": jnp.zeros((n, batch, s_max, cfg.kv_lora_rank), dt),
                    "k_rope": jnp.zeros((n, batch, s_max, cfg.qk_rope_head_dim), dt),
                }
            return {
                "k": jnp.zeros((n, batch, cfg.n_kv_heads, s_max, cfg.head_dim), dt),
                "v": jnp.zeros((n, batch, cfg.n_kv_heads, s_max, cfg.head_dim), dt),
            }

        cache: Params = {"scan": one(n_scan), "pos": jnp.zeros((), jnp.int32)}
        if n_head_layers:
            cache["head"] = one(n_head_layers)
        return cache

    def _block_decode(self, p: Params, x, layer_cache, pos):
        cfg = self.cfg
        h = L.rms_norm(x, p["attn_norm"], cfg.norm_eps)
        if cfg.use_mla:
            attn_out, new_cache = L.mla_attention_decode(
                p["attn"], h, layer_cache, pos, cfg)
        else:
            attn_out, new_cache = L.attention_decode(
                p["attn"], h, layer_cache, pos, cfg)
        x = x + attn_out
        h = L.rms_norm(x, p["mlp_norm"], cfg.norm_eps)
        if "moe" in p:
            y, _ = L.moe_layer(p["moe"], h, cfg)
        else:
            y = L.mlp(p["mlp"], h)
        return x + y, new_cache

    def decode_step(
        self, params: Params, tokens: jnp.ndarray, cache: Params
    ) -> Tuple[jnp.ndarray, Params]:
        """tokens [B] -> (logits [B, V], cache').  Window caches use
        pos % window as the write slot (ring buffer)."""
        cfg = self.cfg
        assert not cfg.attn_window, "windowed decode lives in the hybrid model"
        pos = cache["pos"]
        x = params["embed"][tokens][:, None, :]
        new_cache: Params = {"pos": pos + 1}
        write_pos = pos

        if "head_blocks" in params:
            hc = []
            for i, hp in enumerate(params["head_blocks"]):
                lc = jax.tree.map(lambda a: a[i], cache["head"])
                x, nc = self._block_decode(hp, x, lc, write_pos)
                hc.append(nc)
            new_cache["head"] = jax.tree.map(lambda *xs: jnp.stack(xs), *hc)

        def body(x, inp):
            bp, lc = inp
            x, nc = self._block_decode(bp, x, lc, write_pos)
            return x, nc

        if cfg.use_scan:
            x, scan_cache = jax.lax.scan(
                body, x, (params["blocks"], cache["scan"]))
        else:
            n = jax.tree.leaves(params["blocks"])[0].shape[0]
            ncs = []
            for i in range(n):
                bp = jax.tree.map(lambda a: a[i], params["blocks"])
                lc = jax.tree.map(lambda a: a[i], cache["scan"])
                x, nc = self._block_decode(bp, x, lc, write_pos)
                ncs.append(nc)
            scan_cache = jax.tree.map(lambda *xs: jnp.stack(xs), *ncs)
        new_cache["scan"] = scan_cache

        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        return (x @ head)[:, 0], new_cache

    def prefill(
        self,
        params: Params,
        tokens: jnp.ndarray,
        frontend_embeds: Optional[jnp.ndarray] = None,
    ) -> Tuple[jnp.ndarray, Params]:
        """Full forward; returns (last-position logits [B, V], cache).

        The cache is sized to the prompt (serving engines re-allocate for
        generation headroom via ``init_cache``).
        """
        cfg = self.cfg
        B, S = tokens.shape
        x = self._embed(params, tokens, frontend_embeds)
        positions = jnp.arange(S)
        caches = []

        def run_block(bp, x):
            h = L.rms_norm(x, bp["attn_norm"], cfg.norm_eps)
            if cfg.use_mla:
                attn_out, kv = L.mla_attention(bp["attn"], h, cfg, positions)
            else:
                attn_out, kv = L.attention(
                    bp["attn"], h, cfg, causal=True, positions=positions,
                    window=cfg.attn_window)
            x = x + attn_out
            h = L.rms_norm(x, bp["mlp_norm"], cfg.norm_eps)
            if "moe" in bp:
                y, _ = L.moe_layer(bp["moe"], h, cfg)
            else:
                y = L.mlp(bp["mlp"], h)
            return x + y, kv

        for hp in params.get("head_blocks", []):
            x, kv = run_block(hp, x)
            caches.append(("head", kv))

        def body(x, bp):
            x, kv = run_block(bp, x)
            return x, kv

        if cfg.use_scan:
            x, scan_kv = jax.lax.scan(body, x, params["blocks"])
        else:
            n = jax.tree.leaves(params["blocks"])[0].shape[0]
            kvs = []
            for i in range(n):
                bp = jax.tree.map(lambda a: a[i], params["blocks"])
                x, kv = run_block(bp, x)
                kvs.append(kv)
            scan_kv = jax.tree.map(lambda *xs: jnp.stack(xs), *kvs)

        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = x[:, -1] @ head

        cache: Params = {"pos": jnp.asarray(S, jnp.int32), "scan": scan_kv}
        head_kvs = [kv for tag, kv in caches if tag == "head"]
        if head_kvs:
            cache["head"] = jax.tree.map(lambda *xs: jnp.stack(xs), *head_kvs)
        return logits, cache


def _xent(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def lm_loss(features: jnp.ndarray, head: jnp.ndarray, labels: jnp.ndarray,
            chunk: int = 0) -> jnp.ndarray:
    """Cross entropy from final hidden states, never materializing the
    full [B, S, V] logits: sequence chunks are projected + reduced inside
    a rematerialized scan, so peak memory is [B, chunk, V] (forward AND
    backward).  Essential for the 150k-256k-vocab archs at 1M tokens."""
    with jax.named_scope(LOSS_SCOPE):
        B, S, D = features.shape
        # pin the vocab sharding of the head so the chunk-scan's gradient
        # accumulator stays vocab-sharded (an unsharded f32 [D, 256k] grad
        # accumulator costs 4.2 GB/device on the 256k-vocab archs).
        if head.ndim == 2:
            head = L.sp_head_constrain(head)
        if chunk <= 0 or S <= chunk or S % chunk != 0:
            return _xent(features @ head, labels)
        n = S // chunk
        xc = features.reshape(B, n, chunk, D).transpose(1, 0, 2, 3)
        lc = labels.reshape(B, n, chunk).transpose(1, 0, 2)

        def chunk_loss(xi, li):
            # bf16 operands, f32 accumulation (a post-matmul astype would be
            # hoisted into an f32 copy of the whole head)
            logits = jnp.einsum("bsd,dv->bsv", xi, head,
                                preferred_element_type=jnp.float32)
            logz = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, li[..., None], axis=-1)[..., 0]
            return jnp.sum(logz - gold)

        def body(acc, inp):
            xi, li = inp
            return acc + jax.checkpoint(chunk_loss)(xi, li), None

        total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xc, lc))
        return total / (B * S)
