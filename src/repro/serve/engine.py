"""Batched generation engine: prefill + decode with KV/state caches.

Wave-based continuous batching: requests with equal prompt length join a
prefill wave; decode then steps the whole wave until every slot finishes
(EOS or per-request max).  The decode step function is jitted once per
(batch, s_max) and reused across waves.

On a mesh, caches follow :func:`repro.parallel.sharding.cache_pspecs`
(batch over DP axes, heads over model); the engine code is identical on
1 chip and 512 — this is the ``serve_step`` that the decode-shape
dry-run cells lower.

Multi-length batching via left-pad masks is future work; waves require
equal prompt lengths (assert below).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

__all__ = ["GenerationConfig", "GenerationEngine", "make_serve_step"]


@dataclasses.dataclass
class GenerationConfig:
    max_new_tokens: int = 32
    eos_token: int = 0
    temperature: float = 0.0      # 0 = greedy
    seed: int = 0


def make_serve_step(model) -> Callable:
    """The single-token decode step used by the dry-run decode cells."""

    def serve_step(params, tokens, cache):
        return model.decode_step(params, tokens, cache)

    return serve_step


class GenerationEngine:
    def __init__(self, model, params, gen_cfg: Optional[GenerationConfig] = None,
                 plan=None, session=None):
        self.model = model
        self.params = params
        self.cfg = gen_cfg or GenerationConfig()
        self._prefill = jax.jit(model.prefill)
        self._decode = jax.jit(model.decode_step)
        #: armed by arm_overlap(): the planned, certified all-gather
        #: schedule fused with decode/prefill compute
        self._overlap: Optional[Dict[str, Any]] = None
        self._overlap_decode: Optional[Callable] = None
        self.stats: Dict[str, float] = {"prefill_tokens": 0, "decode_steps": 0}
        #: a repro.session.Session may own the plan lifecycle for the
        #: engine: its (lazily compiled) plan is adopted when no explicit
        #: plan is passed, and re-plans it performs (drift) are visible
        #: because collective_hints() re-reads session.planned
        self.session = session
        if plan is None and session is not None:
            plan = session.plan() if session.planned is None else session.planned
        #: compiled collective plan (repro.plan.Plan) for the serving mesh;
        #: the engine's TP collectives ride the mesh built from it, and
        #: per-op entries are surfaced for operators via collective_hints()
        self.plan = plan
        if plan is not None:
            self.stats["plan_fingerprint"] = plan.fingerprint.digest

    def collective_hints(self, payload_bytes: float = 1e6) -> Dict[str, Dict]:
        """Per-op plan entries the decode-path collectives map onto.

        TP decode issues all-gather / reduce-scatter per layer; MoE
        archs add the EP all-to-all.  Returns {op: entry summary} from
        the plan's nearest size buckets (empty without a plan).
        """
        if self.session is not None and self.session.planned is not None:
            self.plan = self.session.planned       # pick up drift re-plans
        if self.plan is None:
            return {}
        out: Dict[str, Dict] = {}
        for op in ("all-gather", "reduce-scatter", "all-to-all"):
            e = self.plan.lookup(op, payload_bytes)
            if e is not None:
                out[op] = {
                    "algo": e.algo, "chunks": e.chunks,
                    "expected_time": e.expected_time,
                    "speedup_vs_identity":
                        e.best_identity_time / max(e.expected_time, 1e-30),
                }
                if e.program_fingerprint:
                    out[op]["program"] = e.program_fingerprint
        return out

    def lowered_collective(self, op: str, payload_bytes: float = 1e6):
        """The plan's lowered schedule for ``op`` at ``payload_bytes``.

        Rebuilds the entry's typed :class:`~repro.collective.Program`
        and lowers it through :class:`repro.collective.JaxExecutor` —
        the engine pulls the ppermute ring/shift schedule from the plan
        instead of re-deriving it from ``(algo, perm)`` tuples.  Returns
        a :class:`repro.collective.Lowered` (ring links or a2a shift
        rounds in axis-index space), or ``None`` when the plan has no
        entry for ``op`` or the chosen algorithm has no static ppermute
        form (e.g. halving-doubling, which XLA runs natively).
        """
        if self.session is not None and self.session.planned is not None:
            self.plan = self.session.planned       # pick up drift re-plans
        if self.plan is None:
            return None
        entry = self.plan.lookup(op, payload_bytes)
        if entry is None:
            return None
        from repro.collective import JaxExecutor

        ex = JaxExecutor()
        prog = entry.program()
        return ex.lower(prog) if ex.can_lower(prog) else None

    def arm_overlap(self, mesh, axis: str, payload_bytes: float = 1e6):
        """Fuse the planned all-gather into decode/prefill compute.

        Looks up the plan's all-gather entry at ``payload_bytes``,
        lowers it, **certifies the exact schedule artifact**
        (:func:`repro.analysis.require_certified` — unlike
        :meth:`lowered_collective`, nothing uncertified escapes here),
        and rearms the wave loop: each decode step then issues the
        schedule's rounds via :func:`repro.kernels.overlap.run_overlapped`
        with the *next* token's decode as resident compute, and prefill
        overlaps the prompt-activation gather with cache growth.  The
        gathered payload is the step's activation block, so the
        schedule's allgather postcondition is checkable against it
        (``generate`` checks the first step of every wave).

        Returns the certified :class:`LoweredSchedule`.
        """
        from repro.analysis import require_certified
        from repro.collective import JaxExecutor

        if self.session is not None and self.session.planned is not None:
            self.plan = self.session.planned
        if self.plan is None:
            raise ValueError("arm_overlap() needs a plan (or session)")
        entry = self.plan.lookup("all-gather", payload_bytes)
        if entry is None:
            raise ValueError(
                f"plan has no all-gather entry near {payload_bytes:.0f} B")
        prog = entry.program()
        sched = JaxExecutor().lower_schedule(prog)
        require_certified(prog, sched)
        if mesh.shape[axis] != sched.n:
            raise ValueError(f"mesh axis {axis!r} has {mesh.shape[axis]} "
                             f"devices, schedule wants {sched.n}")
        self._overlap = {"mesh": mesh, "axis": axis, "schedule": sched}

        def step(params, cur, cache, payload):
            from repro.kernels.overlap import run_overlapped

            gathered, (dec,) = run_overlapped(
                payload, mesh, axis, sched,
                compute=[lambda: self.model.decode_step(params, cur, cache)],
                use_pallas_add=False)
            logits, new_cache = dec
            return logits, new_cache, gathered

        self._overlap_decode = jax.jit(step)
        self.stats["overlap_algo"] = sched.algorithm
        return sched

    def _ag_payload(self, logits: jnp.ndarray) -> jnp.ndarray:
        """Rank-major ``[n, D]`` all-gather input from an activation block.

        The step's logits block stands in for the TP activations the
        gather moves on a real mesh; padded so every rank's shard is a
        whole number of schedule pieces.
        """
        sched = self._overlap["schedule"]
        n, k = sched.n, max(1, sched.chunk_factor)
        flat = logits.reshape(-1)
        per = -(-flat.size // n)
        per = -(-per // k) * k
        return jnp.pad(flat, (0, n * per - flat.size)).reshape(n, per)

    def _check_gather(self, payload, gathered) -> None:
        """End-to-end postcondition of the wave's first overlapped gather."""
        from repro.kernels.schedule_runner import check_postcondition

        bad = check_postcondition(self._overlap["schedule"],
                                  np.asarray(payload), np.asarray(gathered))
        if bad:
            raise RuntimeError(
                "overlapped all-gather violated its postcondition: "
                + "; ".join(bad[:3]))
        obs.metrics().counter("serve.overlap.postcondition_ok").inc()

    def _sample(self, logits: jnp.ndarray, rng) -> jnp.ndarray:
        if self.cfg.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            rng, logits / self.cfg.temperature).astype(jnp.int32)

    def generate(
        self,
        prompts: List[List[int]],
        frontend_embeds: Optional[jnp.ndarray] = None,
        max_new_tokens: Optional[int] = None,
    ) -> List[List[int]]:
        """One wave: equal-length prompts -> generated continuations."""
        lens = {len(p) for p in prompts}
        assert len(lens) == 1, f"wave needs equal prompt lengths, got {lens}"
        max_new = max_new_tokens or self.cfg.max_new_tokens
        B = len(prompts)
        tokens = jnp.asarray(prompts, dtype=jnp.int32)
        P = tokens.shape[1]

        with obs.tracer().span("serve.prefill", batch=B, prompt_len=P):
            logits, cache = self._prefill(self.params, tokens, frontend_embeds)
        self.stats["prefill_tokens"] += B * P
        # grow the cache to P + max_new slots; when armed, the planned
        # all-gather of the prompt activations rides along, with the
        # cache growth as its resident compute
        if self._overlap is not None:
            ov = self._overlap
            from repro.kernels.overlap import run_overlapped

            payload = self._ag_payload(logits)
            with obs.tracer().span("serve.overlap.prefill",
                                   bytes=float(payload.nbytes)):
                _, (cache,) = run_overlapped(
                    payload, ov["mesh"], ov["axis"], ov["schedule"],
                    compute=[lambda: _grow_cache(cache, P, P + max_new)],
                    use_pallas_add=False)
        else:
            cache = _grow_cache(cache, P, P + max_new)

        # TP decode issues an all-gather + reduce-scatter of the step's
        # activations per layer; the per-step logits block is the
        # observable proxy for that payload on a single-host run
        act_bytes = float(logits.size * logits.dtype.itemsize)
        rec = obs.recorder()
        rng = jax.random.PRNGKey(self.cfg.seed)
        out = np.zeros((B, max_new), dtype=np.int32)
        finished = np.zeros(B, dtype=bool)
        cur = self._sample(logits, rng)
        timer = obs.tracer().timer("serve.decode", batch=B)
        with timer:
            for t in range(max_new):
                out[:, t] = np.where(
                    finished, self.cfg.eos_token, np.asarray(cur))
                finished |= np.asarray(cur) == self.cfg.eos_token
                if finished.all():
                    break
                rng, sub = jax.random.split(rng)
                if self._overlap is not None:
                    # step t's planned all-gather (of step t's activation
                    # block) is on the wire while step t+1's decode runs
                    payload = self._ag_payload(logits)
                    logits, cache, gathered = self._overlap_decode(
                        self.params, cur, cache, payload)
                    if t == 0:
                        self._check_gather(payload, gathered)
                else:
                    logits, cache = self._decode(self.params, cur, cache)
                self.stats["decode_steps"] += 1
                rec.record("all-gather", act_bytes)
                rec.record("reduce-scatter", act_bytes)
                cur = self._sample(logits, sub)
            timer.set(steps=t + 1)
        obs.metrics().counter("serve.waves").inc()
        return [row[: _trim(row, self.cfg.eos_token)].tolist() for row in out]


def _trim(row: np.ndarray, eos: int) -> int:
    hits = np.nonzero(row == eos)[0]
    return int(hits[0]) if len(hits) else len(row)


#: cache keys that carry a sequence dimension, and where it sits
#: (negative index).  State caches (wkv, h, conv, *_sx) never grow.
_SEQ_DIM = {"k": -2, "v": -2, "ckv": -2, "k_rope": -2}


def _grow_cache(cache: Any, cur_len: int, new_len: int) -> Any:
    """Pad the sequence dim of prefill caches to decode headroom.

    Key-aware: only KV/latent buffers grow; recurrent states and the
    ring-buffer window caches of the hybrid arch pass through untouched.
    (Whisper cross-attn xk/xv are fixed to the audio context — untouched.)
    """
    if new_len <= cur_len:
        return cache

    def grow(path, leaf):
        name = None
        for p in reversed(path):
            if hasattr(p, "key"):
                name = p.key
                break
        if name not in _SEQ_DIM or not hasattr(leaf, "ndim") or leaf.ndim == 0:
            return leaf
        d = leaf.ndim + _SEQ_DIM[name]
        if leaf.shape[d] != cur_len:   # ring-buffer (hybrid) or fixed ctx
            return leaf
        pad = [(0, 0)] * leaf.ndim
        pad[d] = (0, new_len - cur_len)
        return jnp.pad(leaf, pad)

    return jax.tree_util.tree_map_with_path(grow, cache)
