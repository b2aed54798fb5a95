"""Bucketed gradient all-reduce fused with compute via certified schedules.

The data-parallel gradient all-reduce is the train step's one fleet-wide
collective; the paper's reordering wins only move *step* time if the
reordered schedule overlaps the step's compute (exposed communication is
the real cost).  This module is the train-side consumer of
:mod:`repro.kernels.overlap`:

* the grad pytree is partitioned into size-targeted **buckets**
  (:func:`partition_tree`) — bucket size is a *planned* dimension: the
  plan compiler scores candidate bucket payloads per octave and stores
  the winner on :attr:`PlanEntry.bucket_bytes`, which
  :func:`reducer_from_plan` picks up through ordinary ``Plan.lookup``;
* each bucket's payload runs the **certified** all-reduce schedule —
  certification happens before fusion (``require_certified`` /
  ``Session.lower``), and fusion never edits rounds;
* buckets are **pipelined**: bucket ``b``'s transfer goes on the wire
  while bucket ``b - 1``'s finishing math (un-flatten, mean) and any
  caller-supplied resident compute run, at bucket granularity
  (``mode="bucketed"``) or spread shard-by-shard across the schedule's
  rounds (``mode="fused"``).

Every mode computes the same reduction element-for-element — the modes
differ only in *when* compute is traced relative to the certified
rounds — so the overlapped step's loss and grads match the sequential
baseline to float tolerance (exactly, between explicit modes).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.analysis import require_certified
from repro.collective import CollectiveOp, JaxExecutor, compile_op
from repro.collective.executors import LoweredSchedule
from repro.collective.passes import apply_permutation, chunk as chunk_pass
from repro.kernels.overlap import run_overlapped
from repro.kernels.schedule_runner import LANES
from repro.optim import apply_opt

from .train_step import TrainState

__all__ = [
    "GradBucket",
    "partition_tree",
    "certified_allreduce",
    "OverlapGradReducer",
    "reducer_from_plan",
    "make_overlap_train_step",
    "jit_overlap_train_step",
    "OVERLAP_MODES",
]

OVERLAP_MODES = ("sequential", "bucketed", "fused")

#: device scopes of the reducer's own math: the per-chip gradients
#: stacked and packed into bucket vectors; a bucket's finishing math,
#: the mean and the slicing of the reduced vector into gradient leaves
PACK_SCOPE = "certified.pack"
FINISH_SCOPE = "certified.finish"


@dataclasses.dataclass(frozen=True)
class GradBucket:
    """One size-targeted slice of the (flattened) grad pytree."""

    index: int
    leaf_ids: Tuple[int, ...]        # indices into jax.tree.flatten order
    sizes: Tuple[int, ...]           # per-leaf element counts
    n_elems: int
    n_bytes: int


def partition_tree(tree, bucket_bytes: float,
                   leading_axis: bool = False) -> List[GradBucket]:
    """Greedy size-targeted partition of a pytree, in flatten order.

    ``bucket_bytes <= 0`` yields a single bucket.  With
    ``leading_axis=True`` leaves carry a stacked per-rank axis 0 that
    does not count toward the payload.  Works on arrays and on shape
    structs (anything with ``.shape``/``.dtype``), so the partition can
    be computed once from a template and reused across steps.
    """
    leaves = jax.tree.leaves(tree)
    buckets: List[GradBucket] = []
    cur_ids: List[int] = []
    cur_sizes: List[int] = []
    cur_bytes = 0
    for i, leaf in enumerate(leaves):
        shape = tuple(leaf.shape)[1:] if leading_axis else tuple(leaf.shape)
        size = int(np.prod(shape, dtype=np.int64)) if shape else 1
        nbytes = size * np.dtype(leaf.dtype).itemsize
        if cur_ids and bucket_bytes > 0 and cur_bytes + nbytes > bucket_bytes:
            buckets.append(GradBucket(
                index=len(buckets), leaf_ids=tuple(cur_ids),
                sizes=tuple(cur_sizes), n_elems=sum(cur_sizes),
                n_bytes=cur_bytes))
            cur_ids, cur_sizes, cur_bytes = [], [], 0
        cur_ids.append(i)
        cur_sizes.append(size)
        cur_bytes += nbytes
    if cur_ids:
        buckets.append(GradBucket(
            index=len(buckets), leaf_ids=tuple(cur_ids),
            sizes=tuple(cur_sizes), n_elems=sum(cur_sizes),
            n_bytes=cur_bytes))
    return buckets


def certified_allreduce(n: int, size_bytes: float, algo: str = "ring",
                        perm: Optional[Sequence[int]] = None,
                        chunk_factor: int = 1,
                        **algo_kwargs) -> LoweredSchedule:
    """Compile, lower and certify an all-reduce schedule for ``n`` ranks.

    The session-less convenience path (tests, benchmarks): planned
    deployments go through ``Session.lower`` / :func:`reducer_from_plan`
    instead, where the plan supplies algorithm, permutation and bucket
    size.  The returned schedule is certified against its program by
    :func:`repro.analysis.require_certified` before anything runs it.
    """
    op = CollectiveOp(kind="allreduce", size_bytes=float(size_bytes),
                      group=tuple(range(n)))
    prog = compile_op(op, algo, **algo_kwargs)
    if perm is not None:
        prog = apply_permutation(prog, [int(p) for p in perm])
    if chunk_factor > 1:
        prog = chunk_pass(prog, chunk_factor)
    sched = JaxExecutor().lower_schedule(prog)
    require_certified(prog, sched)
    return sched


class OverlapGradReducer:
    """Bucketed, certified DP gradient mean over one mesh axis.

    Callable on a *stacked* grad pytree (leaves ``[n, ...]``, sharded
    over ``axis``): returns the mean tree plus any resident-compute
    results.  The same certified schedule runs every bucket — the
    lowering is payload-agnostic, so the runner's memoised SEND/RECV
    tables hit across buckets and steps.

    Each bucket vector is padded so that every piece of a chunk is a
    whole number of 128-lane rows: the runner then holds each chunk as
    a tile-dense slab on a major axis, and the finish slices the
    padding off.
    """

    def __init__(self, mesh: Mesh, axis: str, schedule: LoweredSchedule,
                 bucket_bytes: float = 0.0, mode: str = "bucketed",
                 use_pallas_add: bool = False):
        if mode not in OVERLAP_MODES:
            raise ValueError(f"mode must be one of {OVERLAP_MODES}, "
                             f"got {mode!r}")
        if schedule.postcondition != "allreduce":
            raise ValueError("OverlapGradReducer needs an all-reduce "
                             f"schedule, got {schedule.postcondition!r}")
        if mesh.shape[axis] != schedule.n:
            raise ValueError(f"mesh axis {axis!r} has {mesh.shape[axis]} "
                             f"devices, schedule wants {schedule.n}")
        self.mesh = mesh
        self.axis = axis
        self.schedule = schedule
        self.bucket_bytes = float(bucket_bytes)
        self.mode = mode
        self.use_pallas_add = use_pallas_add
        self.n = schedule.n

    # -- bucketing ---------------------------------------------------------
    def buckets_for(self, stacked_tree) -> List[GradBucket]:
        return partition_tree(stacked_tree, self.bucket_bytes,
                              leading_axis=True)

    # -- the reduction -----------------------------------------------------
    def __call__(self, stacked_tree,
                 compute: Sequence[Callable[[], Any]] = ()
                 ) -> Tuple[Any, List[Any]]:
        leaves, tdef = jax.tree.flatten(stacked_tree)
        buckets = self.buckets_for(stacked_tree)
        # set while tracing: the number of buckets the compiled step runs
        obs.metrics().gauge("train.overlap.buckets").set(len(buckets))
        n = self.n
        quantum = (self.schedule.n_chunks * max(1, self.schedule.chunk_factor)
                   * LANES)

        payloads = []
        with jax.named_scope(PACK_SCOPE):
            for bkt in buckets:
                flat = [leaves[i].reshape(n, -1) for i in bkt.leaf_ids]
                vec = (flat[0] if len(flat) == 1
                       else jnp.concatenate(flat, axis=1))
                pad = (-vec.shape[1]) % quantum
                if pad:
                    vec = jnp.pad(vec, ((0, 0), (0, pad)))
                payloads.append(vec)

        outs: List[Any] = [None] * len(buckets)
        finished: Dict[int, Any] = {}
        results: List[Any] = [None] * len(compute)
        shapes = [tuple(l.shape)[1:] for l in leaves]

        def finisher_shards(b: int):
            """Thunks turning bucket ``b``'s raw output into mean leaves.

            ``bucketed``: one shard per bucket; ``fused``: one per leaf,
            so the plan spreads them across the next bucket's rounds.
            """
            bkt = buckets[b]

            def vec():
                # flat until sliced into leaves (see kernels.overlap's
                # _rank_buffer: a fused reshape chain compiles slowly)
                return jax.lax.optimization_barrier(
                    outs[b].reshape(n, -1)[0, :bkt.n_elems] / n)

            if self.mode == "fused":
                shards = []
                off = 0
                for i, sz in zip(bkt.leaf_ids, bkt.sizes):
                    def one(i=i, off=off, sz=sz):
                        with jax.named_scope(FINISH_SCOPE):
                            return vec()[off:off + sz].reshape(shapes[i])
                    shards.append((i, one))
                    off += sz
                return shards

            def whole(bkt=bkt):
                with jax.named_scope(FINISH_SCOPE):
                    v, off, out = vec(), 0, []
                    for i, sz in zip(bkt.leaf_ids, bkt.sizes):
                        out.append(v[off:off + sz].reshape(shapes[i]))
                        off += sz
                    return out
            return [(("bucket", b), whole)]

        def land(tag, value):
            if isinstance(tag, tuple) and tag[0] == "bucket":
                bkt = buckets[tag[1]]
                for i, leaf in zip(bkt.leaf_ids, value):
                    finished[i] = leaf
            elif isinstance(tag, tuple) and tag[0] == "user":
                results[tag[1]] = value
            else:
                finished[tag] = value

        user_split = np.array_split(np.arange(len(compute)),
                                    max(1, len(buckets)))
        pipelined = self.mode != "sequential"
        for b, payload in enumerate(payloads):
            shards = []
            if pipelined and b > 0:
                shards.extend(finisher_shards(b - 1))
            shards.extend(
                (("user", int(u)), compute[int(u)]) for u in user_split[b])
            tags = [t for t, _ in shards]
            out_b, res = run_overlapped(
                payload, self.mesh, self.axis, self.schedule,
                compute=[fn for _, fn in shards],
                use_pallas_add=self.use_pallas_add)
            outs[b] = out_b
            for tag, value in zip(tags, res):
                land(tag, value)
        # drain: the last bucket (every bucket, in sequential mode)
        for b in range(len(buckets)):
            if buckets[b].leaf_ids[0] in finished:
                continue
            for tag, fn in finisher_shards(b):
                land(tag, fn())

        mean_tree = tdef.unflatten([finished[i] for i in range(len(leaves))])
        return mean_tree, results


def reducer_from_plan(plan, mesh: Mesh, axis: str, total_bytes: float,
                      group: Optional[Sequence[int]] = None,
                      mode: str = "bucketed",
                      bucket_bytes: Optional[float] = None,
                      use_pallas_add: bool = False) -> OverlapGradReducer:
    """Reducer from a compiled :class:`~repro.plan.Plan`.

    Two ``PlanEntry`` lookups: the octave of the *full* grad payload
    supplies the planned ``bucket_bytes``, then the octave of the bucket
    payload supplies the algorithm/permutation/chunking actually run —
    so both the bucket size and the schedule are planned dimensions.
    The schedule is lowered and certified here, before any fusion.
    """
    entry = plan.lookup("all-reduce", total_bytes, group)
    bb = float(bucket_bytes if bucket_bytes is not None
               else (entry.bucket_bytes or total_bytes))
    entry_b = plan.lookup("all-reduce", bb, group)
    prog = entry_b.program()
    sched = JaxExecutor().lower_schedule(prog)
    require_certified(prog, sched)
    if sched.postcondition != "allreduce":
        # some algorithms (e.g. bcube) lower their all-reduce to a
        # schedule that ends reduce-scattered; the reducer needs every
        # rank to finish with the full sum, so fall back to a ring at
        # the planned rank order (the reordering win is kept, the
        # algorithm choice is not)
        local = [entry_b.group.index(p) for p in entry_b.perm]
        sched = certified_allreduce(len(entry_b.group), bb, algo="ring",
                                    perm=local,
                                    chunk_factor=max(1, entry_b.chunks))
    return OverlapGradReducer(mesh, axis, sched, bucket_bytes=bb, mode=mode,
                              use_pallas_add=use_pallas_add)


def make_overlap_train_step(model, opt_cfg, mesh: Mesh, axis: str,
                            reducer: OverlapGradReducer):
    """Train step whose grad all-reduce is the reducer's certified path.

    Pure data parallelism over ``axis``: params replicated, batch
    sharded on its leading dim.  Per-device grads come out of a
    ``shard_map`` stacked ``[n, ...]``; the reducer pipelines the
    bucketed certified schedules (with the previous bucket's finishing
    math as resident compute) and AdamW applies to the mean — the same
    ``apply_opt`` as the baseline step, on grads that match it to float
    tolerance.
    """
    n = mesh.shape[axis]

    def local(params, b):
        loss, g = jax.value_and_grad(model.loss)(params, b)
        with jax.named_scope(PACK_SCOPE):
            return loss[None], jax.tree.map(lambda t: t[None], g)

    sm = jax.shard_map(local, mesh=mesh, in_specs=(P(), P(axis)),
                       out_specs=(P(axis), P(axis)), check_vma=False)

    def step(state: TrainState, batch):
        losses, gstack = sm(state.params, batch)
        loss = jnp.mean(losses)
        grads, _ = reducer(gstack)
        new_params, new_opt, metrics = apply_opt(
            opt_cfg, state.params, grads, state.opt)
        metrics = dict(metrics, loss=loss)
        return TrainState(new_params, new_opt, state.step + 1), metrics

    return step


def jit_overlap_train_step(model, opt_cfg, mesh: Mesh, axis: str,
                           reducer: OverlapGradReducer, donate: bool = True):
    """jit of :func:`make_overlap_train_step` with explicit shardings."""
    step_fn = make_overlap_train_step(model, opt_cfg, mesh, axis, reducer)
    rep = NamedSharding(mesh, P())            # pytree-prefix: whole state
    batch_ns = NamedSharding(mesh, P(axis))   # prefix: every batch leaf
    return jax.jit(
        step_fn,
        in_shardings=(rep, batch_ns),
        out_shardings=None,
        donate_argnums=(0,) if donate else (),
    )
