"""Compute–communication overlap for certified collective schedules.

:mod:`repro.kernels.schedule_runner` executes a certified
:class:`~repro.collective.executors.LoweredSchedule` standalone; this
module fuses one into a surrounding step.  The schedule becomes a
round-pipelined state machine:

* **issue** — read each step's payload rows from round-entry state and
  put them on the wire (``jax.lax.ppermute``);
* **apply** — land the staged receives at the round barrier (``reduce``
  accumulates, with XLA's add or the Pallas
  :func:`~repro.kernels.ring_collective.fused_add` kernel, ``copy``
  overwrites);
* **overlap** — between issue and apply, run resident compute shards
  and the *next* transfer.  ``chunk_factor`` pieces of one round are
  disjoint windows of the chunk rows, so piece ``p + 1``'s
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

Inside, each device holds its chunk rows in the runner's layout,
``[n_chunks, *slab_shape(chunk_len, chunk_factor)]`` with the chunk on
the major axis.  The public state of :func:`seed_state`,
:func:`finish_state` and ``run_overlapped(state=..., return_state=True)``
is ``[n, n_chunks + 1, chunk_len]`` with a zero last row, converted only
at that boundary.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.collective.executors import LoweredSchedule

from .schedule_runner import (PERMUTE_SCOPE, TABLE_SCOPE, Window,
                              land_receives, piece_windows, schedule_tables,
                              send_rows, slab_shape)

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


def _chunk_len(schedule: LoweredSchedule, shape: Tuple[int, ...]) -> int:
    """Validate ``[n, D]`` rank-major inputs against the declared init;
    returns the chunk length they make."""
    n, n_chunks = schedule.n, schedule.n_chunks
    if len(shape) != 2 or shape[0] != n:
        raise ValueError(f"want [n={n}, D] rank-major inputs, got {shape}")
    if schedule.init == "replicated":
        if shape[1] % n_chunks:
            raise ValueError(f"D={shape[1]} not divisible by "
                             f"n_chunks={n_chunks}")
        return shape[1] // n_chunks
    if schedule.init == "addressed":
        if n_chunks != n * n or shape[1] % n:
            raise ValueError(f"addressed init wants n_chunks=n^2 and "
                             f"D divisible by n, got {shape}")
        return shape[1] // n
    if schedule.init != "sharded":
        raise ValueError(f"unknown init {schedule.init!r}")
    return shape[1]


def _rank_buffer(schedule: LoweredSchedule, xr, r,
                 row: Tuple[int, ...]) -> jnp.ndarray:
    """Rank ``r``'s ``[n_chunks, *row]`` chunk rows from its input row.

    ``xr`` is rank ``r``'s row of the declared init (``replicated``: the
    full local vector; ``sharded``: its own chunk; ``addressed``: its n
    outgoing pieces); ``row`` is the shape of one chunk row, the
    runner's slab or ``(chunk_len,)``.
    """
    n, n_chunks = schedule.n, schedule.n_chunks
    if schedule.init == "replicated":
        # the barrier keeps the flat row as it is: fused with the reshape
        # of a [vocab, d_model] gradient that produced it, the split into
        # chunk rows becomes a relayout that takes the TPU compiler minutes
        return jax.lax.optimization_barrier(xr).reshape(n_chunks, *row)
    zeros = jnp.zeros((n_chunks,) + row, xr.dtype)
    if schedule.init == "sharded":
        return jax.lax.dynamic_update_index_in_dim(
            zeros, xr.reshape(row), r, 0)
    return jax.lax.dynamic_update_slice_in_dim(              # addressed
        zeros, xr.reshape((n,) + row), r * n, 0)


def seed_state(schedule: LoweredSchedule, x) -> jnp.ndarray:
    """Position-major ``[n, n_chunks + 1, chunk_len]`` state from inputs.

    The traceable (jnp) counterpart of the runner's initial-buffer
    construction: ``x`` is rank-major per the schedule's declared init,
    and row ``n_chunks`` is a zero row the runner never reads.
    """
    x = jnp.asarray(x)
    chunk_len = _chunk_len(schedule, x.shape)
    bufs = jax.vmap(
        lambda xr, r: _rank_buffer(schedule, xr, r, (chunk_len,)))(
        x, jnp.arange(schedule.n))
    return _to_public(bufs[np.asarray(schedule.rank_of, dtype=np.int64)])


def finish_state(schedule: LoweredSchedule, state) -> jnp.ndarray:
    """Back to rank space, last row dropped (run_schedule's output)."""
    order = np.asarray(schedule.order, dtype=np.int64)
    return jnp.asarray(state)[order][:, :schedule.n_chunks]


def _to_public(rows) -> jnp.ndarray:
    """``[n, n_chunks, *slab]`` chunk rows as the public state."""
    n, n_chunks = rows.shape[:2]
    flat = rows.reshape(n, n_chunks, -1)
    return jnp.concatenate([flat, jnp.zeros_like(flat[:, :1])], axis=1)


def _from_public(schedule: LoweredSchedule, state) -> jnp.ndarray:
    """The public state as the runner's ``[n, n_chunks, *slab]`` rows."""
    n, n_chunks = schedule.n, schedule.n_chunks
    state = jnp.asarray(state)
    if state.ndim != 3 or state.shape[:2] != (n, n_chunks + 1):
        raise ValueError(f"want a [n={n}, n_chunks + 1={n_chunks + 1}, "
                         f"chunk_len] state, got {state.shape}")
    slab = slab_shape(state.shape[2], schedule.chunk_factor)
    return state[:, :n_chunks].reshape(n, n_chunks, *slab)


def _seed_on_mesh(mesh: Mesh, axis: str, schedule: LoweredSchedule,
                  x) -> jnp.ndarray:
    """The runner's chunk rows for ``x`` sharded over ``mesh[axis]``.

    Each device builds its own rank's rows, and one ``ppermute`` moves
    rank ``rank_of[p]``'s rows to position p: the state never leaves
    its shards (a global gather over the sharded axis is what the TPU
    compiler handles slowly at gradient sizes).
    """
    chunk_len = _chunk_len(schedule, tuple(np.shape(x)))
    slab = slab_shape(chunk_len, schedule.chunk_factor)
    links = [(int(r), p) for p, r in enumerate(schedule.rank_of)]

    def per_device(rows):
        with jax.named_scope(TABLE_SCOPE):
            buf = _rank_buffer(schedule, rows[0], jax.lax.axis_index(axis),
                               slab)
        with jax.named_scope(PERMUTE_SCOPE):
            return jax.lax.ppermute(buf, axis, links)[None]

    return jax.shard_map(per_device, mesh=mesh, in_specs=(P(axis),),
                         out_specs=P(axis), check_vma=False)(x)


def _finish_on_mesh(mesh: Mesh, axis: str, schedule: LoweredSchedule,
                    state) -> jnp.ndarray:
    """Rank-space ``[n, n_chunks, chunk_len]`` from the runner's rows
    sharded over ``mesh[axis]``."""
    links = [(p, int(r)) for p, r in enumerate(schedule.rank_of)]

    def per_device(rows):
        with jax.named_scope(PERMUTE_SCOPE):
            moved = jax.lax.ppermute(rows[0], axis, links)
        with jax.named_scope(TABLE_SCOPE):
            return moved.reshape(1, schedule.n_chunks, -1)

    return jax.shard_map(per_device, mesh=mesh, in_specs=(P(axis),),
                         out_specs=P(axis), check_vma=False)(state)


def _make_issue(mesh: Mesh, axis: str, rnd_tables, window: Window,
                n_chunks: int):
    """shard_map'd transfer of one (round, piece): row reads + ppermute.

    Returns ``None`` when the round has no effective links.  Output is
    one staged ``[n, m, *piece]`` array per effective step — a value
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
                payload = send_rows(buf, me, send, window, n_chunks)
            with jax.named_scope(PERMUTE_SCOPE):
                outs.append(jax.lax.ppermute(payload, axis, eff_links)[None])
        return tuple(outs)

    return jax.shard_map(per_device, mesh=mesh, in_specs=(P(axis),),
                         out_specs=tuple(P(axis) for _ in live),
                         check_vma=False)


def _make_apply(mesh: Mesh, axis: str, rnd_tables, rnd_ops,
                window: Window, n_chunks: int, use_pallas_add: bool):
    """shard_map'd round barrier: land the staged receives."""
    live = [((eff, recv), op)
            for (eff, _, recv), op in zip(rnd_tables, rnd_ops) if eff]
    if not live:
        return None

    def per_device(rows, *staged):
        buf = rows[0]
        me = jax.lax.axis_index(axis)
        for ((eff_links, recv), op), rx in zip(live, staged):
            buf = land_receives(buf, me, recv, window, op, rx[0], n_chunks,
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
    mid-stream and ``return_state=True`` to keep pipelining later; that
    state is :func:`seed_state`'s ``[n, n_chunks + 1, chunk_len]``.

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
    else:
        state = _from_public(schedule, state)
    k = max(1, schedule.chunk_factor)
    windows = piece_windows(state.shape[2] * state.shape[3], k)

    tables, ops = schedule_tables(schedule)

    def stage_fns(slot):
        if slot.round_index < 0:
            return None, None
        window = windows[slot.piece]
        issue = _make_issue(mesh, axis, tables[slot.round_index], window,
                            n_chunks)
        apply_ = _make_apply(mesh, axis, tables[slot.round_index],
                             ops[slot.round_index], window, n_chunks,
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
        # round-entry rows, so its transfer goes on the wire before
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
        return _to_public(state), results
    return _finish_on_mesh(mesh, axis, schedule, state), results
