"""Plain float32 reference of a dense decoder LM's training step.

It follows the published description of the llama/qwen2 decoder
(RMSNorm, rotary positions with the half-split rotation, grouped-query
attention with an optional QKV bias, a SwiGLU feed-forward, an untied or
tied output head, mean token cross entropy) and AdamW with decoupled
weight decay and global-norm clipping.  It imports nothing of the
program.  Every matrix product runs in float32 at
``Precision.HIGHEST``; ``matmul_dtype`` puts each operand through a
per-tensor scaled cast to a lower precision first, which is the control
of the output check.

The weights are made here from the seed (:func:`make_params`), in the
program's tree layout (``embed``, ``blocks`` stacked over layers,
``final_norm``, ``lm_head``), so the program under test and this
reference start from the same numbers and neither takes them from the
other.  Parameters are stored in the configuration's ``dtype`` after
each update, as the configuration states; all arithmetic is float32.

The step runs layer by layer (one layer's forward, then one layer's
backward with its forward recomputed) and attention and the loss in
blocks of query rows, so that a step of the benchmark's real sizes fits
one chip beside the reference's own AdamW state.  Rows are split over
the given mesh's one axis and the gradient summed with ``psum``.
"""

from __future__ import annotations

import math
import zlib
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
#: query rows per attention block and token rows per loss block
Q_BLOCK = 512
LOSS_ROWS = 1024


# ---------------------------------------------------------------------------
# weights from the seed, in the program's layout
# ---------------------------------------------------------------------------

def leaf_table(cfg: Dict[str, Any]) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """``path -> (shape, init)`` of every parameter leaf.

    ``path`` joins the tree keys with ``/``; leaves under ``blocks``
    carry a leading layer axis.  ``init`` is ``normal`` (std
    1/sqrt(fan_in)), ``embed`` (std 0.02), ``bias`` (std 0.02) or
    ``ones``."""
    d, h, kv = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"]
    hd, f, v, n = cfg["head_dim"], cfg["d_ff"], cfg["vocab_size"], cfg["n_layers"]
    t = {
        "embed": ((v, d), "embed"),
        "final_norm": ((d,), "ones"),
        "blocks/attn_norm": ((n, d), "ones"),
        "blocks/mlp_norm": ((n, d), "ones"),
        "blocks/attn/wq": ((n, d, h * hd), "normal"),
        "blocks/attn/wk": ((n, d, kv * hd), "normal"),
        "blocks/attn/wv": ((n, d, kv * hd), "normal"),
        "blocks/attn/wo": ((n, h * hd, d), "normal"),
        "blocks/mlp/w1": ((n, d, f), "normal"),
        "blocks/mlp/w3": ((n, d, f), "normal"),
        "blocks/mlp/w2": ((n, f, d), "normal"),
    }
    if cfg["qkv_bias"]:
        t["blocks/attn/bq"] = ((n, h * hd), "bias")
        t["blocks/attn/bk"] = ((n, kv * hd), "bias")
        t["blocks/attn/bv"] = ((n, kv * hd), "bias")
    if not cfg["tie_embeddings"]:
        t["lm_head"] = ((d, v), "embed")
    return t


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number up to 2**63."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def make_leaf(key: jax.Array, path: str, shape, init: str, dtype):
    if init == "ones":
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    scale = 1.0 / math.sqrt(shape[-2]) if init == "normal" else 0.02
    return (jax.random.normal(k, shape, F32) * scale).astype(dtype)


def nest(flat: Dict[str, Any]) -> Dict[str, Any]:
    tree: Dict[str, Any] = {}
    for path, x in flat.items():
        node = tree
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = x
    return tree


def flatten(tree: Dict[str, Any], prefix: str = "") -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for k, x in tree.items():
        path = f"{prefix}{k}"
        if isinstance(x, dict):
            out.update(flatten(x, path + "/"))
        else:
            out[path] = x
    return out


def make_params(cfg: Dict[str, Any], key: jax.Array) -> Dict[str, Any]:
    """Every parameter from ``key``, in ``cfg['dtype']`` (jit this)."""
    dt = jnp.dtype(cfg["dtype"])
    return nest({p: make_leaf(key, p, s, i, dt)
                 for p, (s, i) in leaf_table(cfg).items()})


# ---------------------------------------------------------------------------
# per-leaf norms, one number per layer of a stacked leaf
# ---------------------------------------------------------------------------

def leaf_norms(tree: Dict[str, Any], scale: float = 1.0) -> Dict[str, Any]:
    """``{path[.layer]: ||leaf|| * scale}`` in float32 (jit this)."""
    out = {}
    for path, x in flatten(tree).items():
        sq = jnp.square(x.astype(F32))
        if path.startswith("blocks/"):
            per = jnp.sqrt(jnp.sum(sq.reshape(sq.shape[0], -1), axis=1))
            for i in range(x.shape[0]):
                out[f"{path}.{i}"] = per[i] * scale
        else:
            out[path] = jnp.sqrt(jnp.sum(sq)) * scale
    return out


# ---------------------------------------------------------------------------
# the model, plainly
# ---------------------------------------------------------------------------

def _q(x, qdtype):
    """Per-tensor scaled round trip through ``qdtype`` (the control):
    the forward pass sees the rounded operand, the gradient passes
    through unchanged."""
    return x if qdtype is None else _qdq(x, qdtype)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _qdq(x, qdtype):
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / float(jnp.finfo(qdtype).max), 1.0)
    return (x / s).astype(qdtype).astype(F32) * s


def _qdq_fwd(x, qdtype):
    return _qdq(x, qdtype), None


def _qdq_bwd(qdtype, _, g):
    return (g,)


_qdq.defvjp(_qdq_fwd, _qdq_bwd)


def _mm(eq: str, a, b, qdtype):
    return jnp.einsum(eq, _q(a, qdtype), _q(b, qdtype), precision=HIGHEST,
                      preferred_element_type=F32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [B, S, H, hd]: rotate (first half, second half) pairs."""
    S, hd = x.shape[1], x.shape[3]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, qdtype):
    """Causal attention; q [B, S, H, hd], k/v [B, S, KV, hd]."""
    B, S, H, hd = q.shape
    G = H // k.shape[2]
    k = jnp.repeat(k, G, axis=2)          # head h reads kv head h // G
    v = jnp.repeat(v, G, axis=2)
    blk = min(Q_BLOCK, S)
    n = S // blk

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, axis=1)
        s = _mm("bqhd,bkhd->bhqk", qi, k, qdtype) / math.sqrt(hd)
        pos_q = i * blk + jnp.arange(blk)
        mask = pos_q[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _mm("bhqk,bkhd->bqhd", p, v, qdtype)

    out = jax.lax.map(jax.checkpoint(block), jnp.arange(n))
    return out.transpose(1, 0, 2, 3, 4).reshape(B, S, H, hd)


def layer(p: Dict[str, Any], x, cfg: Dict[str, Any], qdtype=None):
    """One decoder layer on x [B, S, D] (float32 weights)."""
    B, S, D = x.shape
    H, KV, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    a = p["attn"]
    h = _rms(x, p["attn_norm"], cfg["norm_eps"])
    q = _mm("bsd,de->bse", h, a["wq"], qdtype)
    k = _mm("bsd,de->bse", h, a["wk"], qdtype)
    v = _mm("bsd,de->bse", h, a["wv"], qdtype)
    if cfg["qkv_bias"]:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = _rope(q.reshape(B, S, H, hd), cfg["rope_theta"])
    k = _rope(k.reshape(B, S, KV, hd), cfg["rope_theta"])
    v = v.reshape(B, S, KV, hd)
    o = _attention(q, k, v, qdtype).reshape(B, S, H * hd)
    x = x + _mm("bse,ed->bsd", o, a["wo"], qdtype)
    m = p["mlp"]
    h = _rms(x, p["mlp_norm"], cfg["norm_eps"])
    g = _mm("bsd,df->bsf", h, m["w1"], qdtype)
    u = _mm("bsd,df->bsf", h, m["w3"], qdtype)
    return x + _mm("bsf,fd->bsd", jax.nn.silu(g) * u, m["w2"], qdtype)


def head_loss_sum(x, norm, head, labels, cfg, qdtype=None):
    """Summed token cross entropy of x [B, S, D] against labels [B, S]."""
    D = x.shape[-1]
    rows = x.reshape(-1, D)
    lab = labels.reshape(-1)
    blk = min(LOSS_ROWS, rows.shape[0])
    n = rows.shape[0] // blk

    def block(i):
        xi = jax.lax.dynamic_slice_in_dim(rows, i * blk, blk)
        li = jax.lax.dynamic_slice_in_dim(lab, i * blk, blk)
        z = _mm("nd,dv->nv", _rms(xi, norm, cfg["norm_eps"]), head, qdtype)
        gold = jnp.take_along_axis(z, li[:, None], axis=1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(z, axis=-1) - gold)

    return jnp.sum(jax.lax.map(jax.checkpoint(block), jnp.arange(n)))


# ---------------------------------------------------------------------------
# one AdamW step, layer by layer
# ---------------------------------------------------------------------------

def lr_at(opt: Dict[str, Any], count: int) -> float:
    """Linear warm-up to ``lr`` over ``warmup_steps``, then cosine decay
    to ``lr * floor`` at ``total_steps``."""
    peak, warm = opt["lr"], opt["warmup_steps"]
    if count < warm:
        return peak * count / max(warm, 1)
    frac = min(max((count - warm) / max(opt["total_steps"] - warm, 1), 0.0),
               1.0)
    fl = opt["floor"]
    return fl * peak + (1 - fl) * peak * 0.5 * (1 + math.cos(math.pi * frac))


class Reference:
    """The reference's state and its step, on ``mesh`` (one axis)."""

    def __init__(self, cfg: Dict[str, Any], opt: Dict[str, Any], mesh: Mesh,
                 seed: int, matmul_dtype: Optional[str] = None,
                 exchange: bool = True):
        self.cfg, self.opt, self.mesh = cfg, opt, mesh
        self.exchange = exchange
        self.ax = mesh.axis_names[0]
        self.q = jnp.dtype(matmul_dtype) if matmul_dtype else None
        self.rep = NamedSharding(mesh, P())
        self.rows = NamedSharding(mesh, P(self.ax))
        self.dtype = jnp.dtype(cfg["dtype"])
        key = seed_key(seed)
        table = leaf_table(cfg)
        self.p = {k: jax.jit(partial(make_leaf, path=k, shape=s, init=i,
                                     dtype=self.dtype),
                             out_shardings=self.rep)(key)
                  for k, (s, i) in table.items()}
        zeros = jax.jit(lambda x: jnp.zeros(x.shape, F32),
                        out_shardings=self.rep)
        self.m = {k: zeros(x) for k, x in self.p.items()}
        self.v = {k: zeros(x) for k, x in self.p.items()}
        self.count = 0
        self._build()

    # -- compiled pieces -------------------------------------------------
    def _build(self):
        cfg, q, ax = self.cfg, self.q, self.ax
        rep, rows = P(), P(ax)
        tied = cfg["tie_embeddings"]
        blk_keys = [k for k in self.p if k.startswith("blocks/")]

        def layer_p(blocks, i):
            return nest({k[len("blocks/"):]: blocks[k][i].astype(F32)
                         for k in blk_keys})

        def gsum(g):
            """The gradient summed over the mesh; without the exchange
            each device keeps its own rows' part (a planted fault)."""
            return jax.lax.psum(g, ax) if self.exchange else g

        def smap(f, in_specs, out_specs):
            return jax.jit(jax.shard_map(f, mesh=self.mesh, in_specs=in_specs,
                                         out_specs=out_specs, check_vma=False))

        def embed(table, tokens):
            return table[tokens].astype(F32)

        def fwd(blocks, i, x):
            return layer(layer_p(blocks, i), x, cfg, q)

        def bwd(blocks, i, x, dy):
            _, vjp = jax.vjp(lambda p, x: layer(p, x, cfg, q),
                             layer_p(blocks, i), x)
            dp, dx = vjp(dy)
            dp = gsum(flatten(dp))
            return dp, dx

        def head(x, norm, w, labels, n_tokens):
            def f(x, norm, w):
                return head_loss_sum(x, norm, w, labels, cfg, q) / n_tokens
            loss, vjp = jax.vjp(f, x, norm.astype(F32), w.astype(F32))
            dx, dn, dw = vjp(jnp.ones((), F32))
            return jax.lax.psum(loss, ax), dx, gsum(dn), gsum(dw)

        def embed_grad(tokens, dx, shape):
            g = jnp.zeros(shape, F32).at[tokens.reshape(-1)].add(
                dx.reshape(-1, dx.shape[-1]))
            return gsum(g)

        self._embed = smap(embed, (rep, rows), rows)
        self._fwd = smap(fwd, (rep, rep, rows), rows)
        self._bwd = smap(bwd, (rep, rep, rows, rows), (rep, rows))
        self._head = jax.jit(jax.shard_map(
            head, mesh=self.mesh, in_specs=(rows, rep, rep, rows, rep),
            out_specs=(rep, rows, rep, rep), check_vma=False))
        vshape = (cfg["vocab_size"], cfg["d_model"])
        self._embed_grad = smap(partial(embed_grad, shape=vshape),
                                (rows, rows), rep)
        self._tied = tied

        o = self.opt
        dt = self.dtype

        def adam(p, g, m, v, scale, lr, b1c, b2c):
            g = g * scale
            m = o["b1"] * m + (1 - o["b1"]) * g
            v = o["b2"] * v + (1 - o["b2"]) * g * g
            pf = p.astype(F32)
            step = (m / b1c) / (jnp.sqrt(v / b2c) + o["eps"]) \
                + o["weight_decay"] * pf
            return (pf - lr * step).astype(dt), m, v

        def adam_layer(p, g, m, v, i, scale, lr, b1c, b2c):
            pn, mn, vn = adam(p[i], g, m[i], v[i], scale, lr, b1c, b2c)
            return p.at[i].set(pn), m.at[i].set(mn), v.at[i].set(vn)

        self._adam = jax.jit(adam, donate_argnums=(0, 2, 3))
        self._adam_layer = jax.jit(adam_layer, donate_argnums=(0, 2, 3))
        self._sq = jax.jit(lambda g: jnp.sum(g * g))

    # -- one step --------------------------------------------------------
    def _backward(self, x, xs, tok, lab, n_tokens, sink):
        """Back from the loss through every layer to the embedding,
        handing each gradient to ``sink(path, grad, layer)`` as it comes
        (``layer`` is None outside ``blocks``).  Returns the loss."""
        tied = self._tied
        w = self.p["embed"].T if tied else self.p["lm_head"]
        loss, dx, dnorm, dw = self._head(x, self.p["final_norm"], w, lab,
                                         n_tokens)
        del w
        sink("final_norm", dnorm, None)
        if not tied:
            sink("lm_head", dw, None)
            dw = None
        for i in reversed(range(self.cfg["n_layers"])):
            blocks = {k: v for k, v in self.p.items()
                      if k.startswith("blocks/")}
            g, dx = self._bwd(blocks, i, xs[i], dx)
            for k, gk in g.items():
                sink(f"blocks/{k}", gk, i)
            del g, blocks
        demb = self._embed_grad(tok, dx)
        sink("embed", demb + dw.T if tied else demb, None)
        return loss

    def step(self, tokens: np.ndarray, labels: np.ndarray) -> Dict[str, Any]:
        """One AdamW step on the batch; returns the loss and the
        gradient's per-leaf norms (before clipping).

        Two backward passes: the first measures the gradient's norm,
        which clipping needs before any leaf may move; the second applies
        each leaf's update as its gradient comes, so that no more than
        one layer's gradient is held beside the weights and moments."""
        tok = jax.device_put(tokens, self.rows)
        lab = jax.device_put(labels, self.rows)
        n_tokens = jnp.asarray(tokens.size, F32)
        blocks = {k: x for k, x in self.p.items() if k.startswith("blocks/")}
        x = self._embed(self.p["embed"], tok)
        xs = []
        for i in range(self.cfg["n_layers"]):
            xs.append(x)
            x = self._fwd(blocks, i, x)
        del blocks

        norms: Dict[str, float] = {}

        def measure(path, g, i):
            name = path if i is None else f"{path}.{i}"
            norms[name] = float(np.sqrt(self._sq(g)))

        loss = self._backward(x, xs, tok, lab, n_tokens, measure)
        gnorm = math.sqrt(sum(v * v for v in norms.values()))
        scale = min(1.0, self.opt["clip_norm"] / (gnorm + 1e-9))
        self.count += 1
        c = self.count
        args = (np.float32(scale), np.float32(lr_at(self.opt, c)),
                np.float32(1 - self.opt["b1"] ** c),
                np.float32(1 - self.opt["b2"] ** c))

        def apply(path, g, i):
            if i is None:
                self.p[path], self.m[path], self.v[path] = self._adam(
                    self.p[path], g, self.m[path], self.v[path], *args)
            else:
                self.p[path], self.m[path], self.v[path] = self._adam_layer(
                    self.p[path], g, self.m[path], self.v[path], i, *args)

        self._backward(x, xs, tok, lab, n_tokens, apply)
        return {"loss": float(loss), "grad_norms": norms, "scale": scale}

    def change_norms(self, key) -> Dict[str, float]:
        """Per-leaf norms of the parameters' change since the seed."""
        table = leaf_table(self.cfg)
        out = {}
        for k, x in self.p.items():
            s, i = table[k]
            f = jax.jit(lambda p, key, k=k, s=s, i=i: leaf_norms(
                {k: p - make_leaf(key, k, s, i, self.dtype)}))
            out.update({name: float(val) for name, val in
                        f(x, key).items()})
        return out

    def free(self):
        for d in (self.p, self.m, self.v):
            for x in d.values():
                x.delete()
            d.clear()


def train(cfg: Dict[str, Any], opt: Dict[str, Any], mesh: Mesh, seed: int,
          batches: List[Tuple[np.ndarray, np.ndarray]],
          matmul_dtype: Optional[str] = None,
          exchange: bool = True) -> Dict[str, Any]:
    """The reference's readings over ``batches`` from the seeded weights:
    each step's loss, the first gradient's per-leaf norms as the
    optimizer takes it (clipped) and before clipping, and the
    parameters' per-leaf change after the last step.

    ``matmul_dtype`` makes it the control; ``exchange=False`` leaves the
    gradient's sum over the mesh out (a fault, for the readings that set
    the check's limits)."""
    ref = Reference(cfg, opt, mesh, seed, matmul_dtype, exchange)
    losses, first = [], None
    for tokens, labels in batches:
        r = ref.step(tokens, labels)
        losses.append(r["loss"])
        if first is None:
            first = r
    change = ref.change_norms(seed_key(seed))
    ref.free()
    return {"losses": losses,
            "grad_raw": first["grad_norms"],
            "grad": {k: v * first["scale"]
                     for k, v in first["grad_norms"].items()},
            "change": change}
