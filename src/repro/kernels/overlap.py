"""Compute–communication overlap for certified collective schedules.

:mod:`repro.kernels.schedule_runner` executes a certified
:class:`~repro.collective.executors.LoweredSchedule` standalone; this
module fuses one into a surrounding step.  The schedule becomes a
round-pipelined state machine:

* **issue** — gather each step's payload from round-entry state and put
  it on the wire (``jax.lax.ppermute``);
* **apply** — land the staged receives at the round barrier (``reduce``
  accumulates through the Pallas
  :func:`~repro.kernels.ring_collective.fused_add` kernel, ``copy``
  overwrites);
* **overlap** — between issue and apply, run resident compute shards
  and the *next* transfer.  ``chunk_factor`` pieces of one round are
  column-disjoint slices of the chunk buffers, so piece ``p + 1``'s
  transfer is issued while piece ``p``'s reduce and the resident
  compute run — the generalized form of the hand-overlapped ring in
  :mod:`repro.kernels.ring_collective`.

The interleaving is explicit: an :class:`OverlapPlan` lists, per
``(round, piece)`` slot, which caller-supplied compute shards (Pallas
matmul / flash-attention thunks, optimizer sub-steps...) run while that
slot's transfer is in flight.  In the traced program the shards have no
data dependency on the staged transfer, which is exactly the freedom
the XLA scheduler needs to hide the collective-permute.

Certification boundary: schedules are certified *before* fusion
(``Session.lower`` / ``require_certified``), and fusion never edits a
round — partial execution goes through
:meth:`LoweredSchedule.slice_rounds`, which only windows the certified
round sequence.  Interleaving therefore cannot change what the
collective computes: :func:`run_overlapped` is element-for-element the
same reduction order as :func:`~repro.kernels.schedule_runner.run_schedule`.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.collective.executors import LoweredSchedule

from .schedule_runner import (PERMUTE_SCOPE, TABLE_SCOPE, land_receives,
                              schedule_tables)

__all__ = [
    "OverlapSlot",
    "OverlapPlan",
    "build_overlap_plan",
    "run_overlapped",
    "seed_state",
    "finish_state",
]


@dataclasses.dataclass(frozen=True)
class OverlapSlot:
    """One pipeline slot: a ``(round, piece)`` transfer + resident compute.

    ``round_index`` indexes the (possibly sliced) schedule's rounds; a
    negative value marks a drain slot that only runs compute.
    ``compute`` holds indices into the caller's compute-shard list —
    those shards run while this slot's transfer is in flight.
    """

    round_index: int
    piece: int
    compute: Tuple[int, ...] = ()


@dataclasses.dataclass(frozen=True)
class OverlapPlan:
    """Explicit interleaving of schedule rounds with compute shards.

    Slots are executed in order; every ``(round, piece)`` of the
    schedule appears exactly once, rounds grouped and ascending (round
    barriers are data dependencies — pieces of one round commute, rounds
    do not).  The plan never rewrites the schedule: it only decides
    *when*, relative to the certified rounds, each compute shard runs.
    """

    schedule: LoweredSchedule
    n_compute: int
    slots: Tuple[OverlapSlot, ...]

    def validate(self) -> None:
        k = max(1, self.schedule.chunk_factor)
        want = [(r, p) for r in range(len(self.schedule.rounds))
                for p in range(k)]
        got = [(s.round_index, s.piece) for s in self.slots
               if s.round_index >= 0]
        if sorted(got) != want:
            raise ValueError(
                f"plan must cover every (round, piece) exactly once: "
                f"want {len(want)} slots, got {sorted(got)!r}")
        rounds_seen = [r for r, _ in got]
        if rounds_seen != sorted(rounds_seen):
            raise ValueError("slots must keep rounds in ascending order")
        cids = [c for s in self.slots for c in s.compute]
        if len(set(cids)) != len(cids) or any(
                not (0 <= c < self.n_compute) for c in cids):
            raise ValueError(
                f"compute ids must each appear once and lie in "
                f"[0, {self.n_compute}): got {cids!r}")


def build_overlap_plan(schedule: LoweredSchedule,
                       n_compute: int = 0) -> OverlapPlan:
    """Default plan: compute shards spread evenly over the slot grid.

    Slots run round-major (pieces of a round adjacent, so the
    double-buffered issue of piece ``p + 1`` overlaps piece ``p``'s
    apply).  Leftover compute — or all of it, for a round-less
    schedule — lands in a trailing drain slot.
    """
    k = max(1, schedule.chunk_factor)
    grid = [(r, p) for r in range(len(schedule.rounds)) for p in range(k)]
    if not grid:
        slots = ((OverlapSlot(-1, 0, tuple(range(n_compute))),)
                 if n_compute else ())
        return OverlapPlan(schedule, n_compute, slots)
    splits = np.array_split(np.arange(n_compute), len(grid))
    slots = tuple(
        OverlapSlot(r, p, tuple(int(c) for c in cids))
        for (r, p), cids in zip(grid, splits))
    return OverlapPlan(schedule, n_compute, slots)


def _check_inputs(schedule: LoweredSchedule, shape: Tuple[int, ...]) -> None:
    """Validate ``[n, D]`` rank-major inputs against the declared init."""
    n, n_chunks = schedule.n, schedule.n_chunks
    if len(shape) != 2 or shape[0] != n:
        raise ValueError(f"want [n={n}, D] rank-major inputs, got {shape}")
    if schedule.init == "replicated":
        if shape[1] % n_chunks:
            raise ValueError(f"D={shape[1]} not divisible by "
                             f"n_chunks={n_chunks}")
    elif schedule.init == "addressed":
        if n_chunks != n * n or shape[1] % n:
            raise ValueError(f"addressed init wants n_chunks=n^2 and "
                             f"D divisible by n, got {shape}")
    elif schedule.init != "sharded":
        raise ValueError(f"unknown init {schedule.init!r}")


def _rank_buffer(schedule: LoweredSchedule, xr, r) -> jnp.ndarray:
    """Rank ``r``'s ``[n_chunks + 1, chunk_len]`` buffer from its input row.

    ``xr`` is rank ``r``'s row of the declared init (``replicated``: the
    full local vector; ``sharded``: its own chunk; ``addressed``: its n
    outgoing pieces); the last row is the zero scratch row.
    """
    n, n_chunks = schedule.n, schedule.n_chunks
    if schedule.init == "replicated":
        chunk_len = xr.shape[0] // n_chunks
        # the barrier keeps the flat row as it is: fused with the reshape
        # of a [vocab, d_model] gradient that produced it, the split into
        # chunk rows becomes a relayout that takes the TPU compiler minutes
        body = jax.lax.optimization_barrier(xr).reshape(n_chunks, chunk_len)
    elif schedule.init == "sharded":
        chunk_len = xr.shape[0]
        body = jnp.zeros((n_chunks, chunk_len), xr.dtype).at[r].set(xr)
    else:                                                    # addressed
        chunk_len = xr.shape[0] // n
        body = jax.lax.dynamic_update_slice(
            jnp.zeros((n_chunks, chunk_len), xr.dtype),
            xr.reshape(n, chunk_len), (r * n, 0))
    return jnp.concatenate([body, jnp.zeros((1, chunk_len), xr.dtype)])


def seed_state(schedule: LoweredSchedule, x) -> jnp.ndarray:
    """Position-major ``[n, n_chunks + 1, chunk_len]`` state from inputs.

    The traceable (jnp) counterpart of the runner's initial-buffer
    construction: ``x`` is rank-major per the schedule's declared init,
    and row ``n_chunks`` is the zero scratch row that absorbs
    non-participating positions.
    """
    x = jnp.asarray(x)
    _check_inputs(schedule, x.shape)
    bufs = jax.vmap(lambda xr, r: _rank_buffer(schedule, xr, r))(
        x, jnp.arange(schedule.n))
    return bufs[np.asarray(schedule.rank_of, dtype=np.int64)]


def finish_state(schedule: LoweredSchedule, state) -> jnp.ndarray:
    """Back to rank space, scratch row dropped (run_schedule's output)."""
    order = np.asarray(schedule.order, dtype=np.int64)
    return jnp.asarray(state)[order][:, :schedule.n_chunks]


def _seed_on_mesh(mesh: Mesh, axis: str, schedule: LoweredSchedule,
                  x) -> jnp.ndarray:
    """:func:`seed_state` for ``x`` sharded over ``mesh[axis]``.

    Each device builds its own rank's buffer, and one ``ppermute`` moves
    rank ``rank_of[p]``'s buffer to position p: the state never leaves
    its shards (a global gather over the sharded axis is what the TPU
    compiler handles slowly at gradient sizes).
    """
    _check_inputs(schedule, tuple(np.shape(x)))
    links = [(int(r), p) for p, r in enumerate(schedule.rank_of)]

    def per_device(rows):
        with jax.named_scope(TABLE_SCOPE):
            buf = _rank_buffer(schedule, rows[0], jax.lax.axis_index(axis))
        with jax.named_scope(PERMUTE_SCOPE):
            return jax.lax.ppermute(buf, axis, links)[None]

    return jax.shard_map(per_device, mesh=mesh, in_specs=(P(axis),),
                         out_specs=P(axis), check_vma=False)(x)


def _finish_on_mesh(mesh: Mesh, axis: str, schedule: LoweredSchedule,
                    state) -> jnp.ndarray:
    """:func:`finish_state` for a state sharded over ``mesh[axis]``."""
    links = [(p, int(r)) for p, r in enumerate(schedule.rank_of)]

    def per_device(rows):
        with jax.named_scope(PERMUTE_SCOPE):
            moved = jax.lax.ppermute(rows[0], axis, links)
        with jax.named_scope(TABLE_SCOPE):
            return moved[None, :schedule.n_chunks]

    return jax.shard_map(per_device, mesh=mesh, in_specs=(P(axis),),
                         out_specs=P(axis), check_vma=False)(state)


def _make_issue(mesh: Mesh, axis: str, rnd_tables, cols: slice):
    """shard_map'd transfer of one (round, piece): gather + ppermute.

    Returns ``None`` when the round has no effective links.  Output is
    one staged ``[n, m, piece_len]`` array per effective step — a value
    with no dependency on anything but round-entry state, so resident
    compute traced between issue and apply is free to overlap it.
    """
    live = [(eff, send) for eff, send, _ in rnd_tables if eff]
    if not live:
        return None

    def per_device(rows):
        buf = rows[0]
        me = jax.lax.axis_index(axis)
        outs = []
        for eff_links, send in live:
            with jax.named_scope(TABLE_SCOPE):
                my_send = jnp.asarray(send)[me]           # [m]
                payload = buf[my_send, cols]
            with jax.named_scope(PERMUTE_SCOPE):
                outs.append(jax.lax.ppermute(payload, axis, eff_links)[None])
        return tuple(outs)

    return jax.shard_map(per_device, mesh=mesh, in_specs=(P(axis),),
                         out_specs=tuple(P(axis) for _ in live),
                         check_vma=False)


def _make_apply(mesh: Mesh, axis: str, rnd_tables, rnd_ops,
                cols: slice, n_chunks: int, use_pallas_add: bool):
    """shard_map'd round barrier: land staged receives, re-zero scratch."""
    live = [((eff, recv), op)
            for (eff, _, recv), op in zip(rnd_tables, rnd_ops) if eff]
    if not live:
        return None

    def per_device(rows, *staged):
        buf = rows[0]
        me = jax.lax.axis_index(axis)
        for ((eff_links, recv), op), rx in zip(live, staged):
            buf = land_receives(buf, me, recv, cols, op, rx[0], n_chunks,
                                use_pallas_add)
        return buf[None]

    in_specs = (P(axis),) + tuple(P(axis) for _ in live)
    return jax.shard_map(per_device, mesh=mesh, in_specs=in_specs,
                         out_specs=P(axis), check_vma=False)


def run_overlapped(
    x,
    mesh: Mesh,
    axis: str,
    plan: Union[OverlapPlan, LoweredSchedule],
    compute: Sequence[Callable[[], Any]] = (),
    *,
    use_pallas_add: bool = True,
    state: Optional[jnp.ndarray] = None,
    rounds: Optional[Tuple[int, Optional[int]]] = None,
    return_state: bool = False,
) -> Tuple[jnp.ndarray, List[Any]]:
    """Execute ``plan`` with compute shards fused into the round pipeline.

    ``plan`` is an :class:`OverlapPlan` or a bare certified
    :class:`LoweredSchedule` (a default plan is built over it).  With a
    bare schedule, ``rounds=(start, stop)`` executes only that window
    (via :meth:`LoweredSchedule.slice_rounds`); pass ``state`` to resume
    mid-stream and ``return_state=True`` to keep pipelining later.

    Returns ``(out, results)``: ``out`` matches
    :func:`~repro.kernels.schedule_runner.run_schedule` element for
    element (or the raw position-major state when ``return_state``),
    and ``results[i]`` is compute shard ``i``'s value.
    """
    if isinstance(plan, LoweredSchedule):
        schedule = plan if rounds is None else plan.slice_rounds(*rounds)
        plan = build_overlap_plan(schedule, len(compute))
    else:
        if rounds is not None:
            raise ValueError("pass rounds= only with a bare schedule; "
                             "an OverlapPlan already fixes its window")
        schedule = plan.schedule
        if plan.n_compute != len(compute):
            raise ValueError(f"plan expects {plan.n_compute} compute "
                             f"shards, got {len(compute)}")
    plan.validate()

    n, n_chunks = schedule.n, schedule.n_chunks
    if mesh.shape[axis] != n:
        raise ValueError(f"mesh axis {axis!r} has {mesh.shape[axis]} "
                         f"devices, schedule wants {n}")
    if state is None:
        state = _seed_on_mesh(mesh, axis, schedule, x)
    state = jnp.asarray(state)
    chunk_len = state.shape[-1]
    k = max(1, schedule.chunk_factor)
    if chunk_len % k:
        raise ValueError(
            f"chunk_len {chunk_len} not divisible by chunk_factor {k}")
    piece_len = chunk_len // k

    tables, ops = schedule_tables(schedule)
    # a piece is a static column window of every chunk row
    piece_cols = [slice(p * piece_len, (p + 1) * piece_len)
                  for p in range(k)]

    def stage_fns(slot):
        if slot.round_index < 0:
            return None, None
        cols = piece_cols[slot.piece]
        issue = _make_issue(mesh, axis, tables[slot.round_index], cols)
        apply_ = _make_apply(mesh, axis, tables[slot.round_index],
                             ops[slot.round_index], cols, n_chunks,
                             use_pallas_add)
        return issue, apply_

    results: List[Any] = [None] * len(compute)
    slots = plan.slots
    staged_next: Any = None
    fns = [stage_fns(s) for s in slots]
    if slots and fns[0][0] is not None:
        staged_next = fns[0][0](state)
    for i, slot in enumerate(slots):
        staged, staged_next = staged_next, None
        issue_next, same_round = None, False
        if i + 1 < len(slots):
            issue_next = fns[i + 1][0]
            same_round = slots[i + 1].round_index == slot.round_index
        # double buffer: the next piece of this round reads the same
        # round-entry columns, so its transfer goes on the wire before
        # this slot's reduce lands
        if issue_next is not None and same_round:
            staged_next = issue_next(state)
        # resident compute — traced with no dependency on the transfer
        for cid in slot.compute:
            results[cid] = compute[cid]()
        apply_ = fns[i][1]
        if apply_ is not None:
            state = apply_(state, *staged)
        if issue_next is not None and not same_round:
            staged_next = issue_next(state)

    if return_state:
        return state, results
    return _finish_on_mesh(mesh, axis, schedule, state), results
