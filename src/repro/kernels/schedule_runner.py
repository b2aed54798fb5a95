"""Execute a certified :class:`LoweredSchedule` on a jax mesh.

The generalized counterpart of :mod:`repro.kernels.ring_collective`:
instead of a hand-derived ring, this runner interprets the per-round
``PermuteStep``\\ s the :class:`~repro.collective.JaxExecutor` lowering
produced — so *any* registered algorithm (trees, halving-doubling,
bcube, recursive-doubling...) runs on real devices through
``jax.lax.ppermute``.  The reduce is XLA's add, which fuses into the
update that lands it, or with ``use_pallas_add`` the Pallas
:func:`~repro.kernels.ring_collective.fused_add` kernel.

Execution semantics mirror the translation validator exactly
(:mod:`repro.analysis.equiv`):

* the mesh-axis index IS the schedule's position space; device p holds
  logical rank ``schedule.rank_of[p]``'s buffer;
* rounds are barriers: every step's payload is read from the
  round-entry buffer, all receives are staged, and applied together at
  the round boundary;
* a link ``(s, d)`` fires iff ``send_mask[s] and recv_mask[d]``;
* ``reduce`` accumulates into the destination chunk row, ``copy``
  overwrites it;
* ``chunk_factor`` k pipelines the body serially over k payload
  slices.

The local buffer is ``[n_chunks, *slab]`` per device: the chunk index
is the major axis and each chunk row is a tile-dense slab
(:func:`slab_shape`), ``[chunk_len // 128, 128]`` when each of the k
pieces is a whole number of 128-lane rows, else ``[1, chunk_len]``.
Each step's ``m`` chunk slots are read and written one row at a time
with dynamic slices indexed by the static SEND/RECV tables, so a round
moves the rows it sends and lands, not the whole buffer.  A position
with nothing to send in a slot sends row 0, which no one lands; one
with nothing to receive keeps its row through a select on the landed
row.  Every step stays a static dense ``ppermute`` (no per-device
control flow, so the whole schedule jits to one XLA program).

This module never certifies anything itself: callers obtain schedules
through ``Session.lower`` / ``JaxExecutor.lower`` where
:func:`repro.analysis.equiv.require_certified` has already proven the
artifact, which is the whole point of translation validation — the
runner can be this trusting *because* the schedule carries a proof.
"""

from __future__ import annotations

import functools
from typing import Callable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.collective.executors import LoweredSchedule

from .ring_collective import accumulate

__all__ = ["run_schedule", "schedule_body", "check_postcondition",
           "schedule_tables", "land_receives", "send_rows", "slab_shape",
           "piece_windows", "PERMUTE_SCOPE", "TABLE_SCOPE", "ADD_SCOPE"]

#: device scopes of the certified paths (here and in
#: :mod:`repro.kernels.overlap`): every ``ppermute``; the row reads and
#: writes through the SEND/RECV tables, seeding and finishing; the reduce
PERMUTE_SCOPE = "certified.permute"
TABLE_SCOPE = "certified.table"
ADD_SCOPE = "certified.add"

#: lanes of a TPU vreg: a slab row of this width is tile-dense
LANES = 128

#: a piece's static place in a chunk row's slab: ``(starts, sizes)``
Window = Tuple[Tuple[int, int], Tuple[int, int]]


def slab_shape(chunk_len: int, chunk_factor: int = 1) -> Tuple[int, int]:
    """Shape of one chunk row in the runner's buffer.

    ``[chunk_len // 128, 128]`` when each of the ``chunk_factor`` pieces
    is a whole number of lane rows, so a row is whole tiles on every
    backend's layout; else ``[1, chunk_len]``.
    """
    k = max(1, chunk_factor)
    if chunk_len % k:
        raise ValueError(
            f"chunk_len {chunk_len} not divisible by chunk_factor {k}")
    if (chunk_len // k) % LANES == 0:
        return chunk_len // LANES, LANES
    return 1, chunk_len


def piece_windows(chunk_len: int, chunk_factor: int = 1) -> List[Window]:
    """Each piece's static window in a chunk row's :func:`slab_shape`:
    lane rows of a dense slab, else columns of the one row."""
    k = max(1, chunk_factor)
    tiles, lanes = slab_shape(chunk_len, k)
    if lanes == LANES:
        t = tiles // k
        return [((p * t, 0), (t, LANES)) for p in range(k)]
    w = chunk_len // k
    return [((0, p * w), (1, w)) for p in range(k)]


def _step_tables(step, n: int, n_chunks: int):
    """Static send/land tables of one PermuteStep.

    Returns ``(eff_links, SEND, RECV)``: the mask-filtered ppermute
    link list and ``[n, m]`` int32 chunk-row tables (``n_chunks`` marks
    a slot the position does not send or receive).
    """
    m = max((len(c) for c in step.chunks), default=0)
    m = max(m, 1)
    send = np.full((n, m), n_chunks, dtype=np.int32)
    recv = np.full((n, m), n_chunks, dtype=np.int32)
    eff_links: List[Tuple[int, int]] = []
    for (s, d), chunks in zip(step.links, step.chunks):
        if not (step.send_mask[s] and step.recv_mask[d]):
            continue
        eff_links.append((int(s), int(d)))
        send[s, :len(chunks)] = chunks
        recv[d, :len(chunks)] = chunks
    return eff_links, send, recv


@functools.lru_cache(maxsize=256)
def schedule_tables(schedule: LoweredSchedule):
    """Static per-round ``(eff_links, SEND, RECV)`` tables + op tags.

    Schedules are hot-path constants: a train step re-runs the same
    certified artifact every call, so the tables are memoised on the
    schedule *value* (frozen dataclasses hash by content — two lowerings
    of the same program share one entry).  Returns
    ``(tables, ops)`` where ``tables[r][s]`` is :func:`_step_tables` of
    round ``r``'s step ``s`` and ``ops[r][s]`` its reduce/copy tag.
    The cached arrays are read-only by convention — every consumer
    reads them without mutation.
    """
    tables = tuple(
        tuple(_step_tables(step, schedule.n, schedule.n_chunks)
              for step in rnd)
        for rnd in schedule.rounds)
    ops = tuple(tuple(step.op for step in rnd) for rnd in schedule.rounds)
    return tables, ops


def _initial_buffers(schedule: LoweredSchedule, x) -> np.ndarray:
    """Rank-space ``[n, n_chunks, chunk_len]`` buffers from inputs.

    ``x`` is rank-major: row r is logical rank r's contribution, shaped
    by the schedule's declared init (``replicated``: the full local
    vector; ``sharded``: rank r's own chunk; ``addressed``: the n
    outgoing pieces).
    """
    n, n_chunks = schedule.n, schedule.n_chunks
    x = np.asarray(x)
    assert x.ndim == 2 and x.shape[0] == n, x.shape
    if schedule.init == "replicated":
        assert x.shape[1] % n_chunks == 0, (x.shape, n_chunks)
        return x.reshape(n, n_chunks, x.shape[1] // n_chunks)
    if schedule.init == "sharded":
        buf = np.zeros((n, n_chunks, x.shape[1]), dtype=x.dtype)
        for r in range(n):
            buf[r, r] = x[r]
        return buf
    if schedule.init == "addressed":
        assert n_chunks == n * n and x.shape[1] % n == 0, (x.shape, n_chunks)
        buf = np.zeros((n, n_chunks, x.shape[1] // n), dtype=x.dtype)
        for s in range(n):
            buf[s, s * n:(s + 1) * n] = x[s].reshape(n, -1)
        return buf
    raise ValueError(f"unknown init {schedule.init!r}")


def _entry(column: Sequence, me) -> jnp.ndarray:
    """This device's entry of a static per-position table column."""
    return jax.lax.dynamic_index_in_dim(jnp.asarray(column), me,
                                        keepdims=False)


def send_rows(buf, me, send, window: Window, n_chunks: int) -> jnp.ndarray:
    """This device's ``[m, *piece]`` payload of one step: the SEND
    table's rows, one dynamic slice each.  An unused slot reads row 0,
    which its destination does not land."""
    start, size = window
    rows = []
    for j in range(send.shape[1]):
        col = np.where(send[:, j] < n_chunks, send[:, j], 0)
        rows.append(jax.lax.dynamic_slice(
            buf, (_entry(col.astype(np.int32), me),) + start, (1,) + size))
    return rows[0] if len(rows) == 1 else jnp.concatenate(rows)


def land_receives(buf, me, recv, window: Window, op: str, received,
                  n_chunks: int, use_pallas_add: bool) -> jnp.ndarray:
    """Land one step's receives in this device's ``buf``: ``reduce``
    accumulates into the RECV table's rows, ``copy`` overwrites them.

    Each slot is one row read and written in place.  Where some
    position receives nothing in a slot, a select keeps that
    position's row as it was."""
    start, size = window
    for j in range(recv.shape[1]):
        gets = recv[:, j] < n_chunks
        if not gets.any():
            continue
        keep = not gets.all()
        with jax.named_scope(TABLE_SCOPE):
            row = _entry(np.where(gets, recv[:, j], 0).astype(np.int32), me)
            at = (row,) + start
            if op == "reduce" or keep:
                current = jax.lax.dynamic_slice(buf, at, (1,) + size)
        new = received[j:j + 1]
        if op == "reduce":
            with jax.named_scope(ADD_SCOPE):
                new = accumulate(current, new, use_pallas_add)
        with jax.named_scope(TABLE_SCOPE):
            if keep:
                new = jnp.where(_entry(gets, me), new, current)
            buf = jax.lax.dynamic_update_slice(buf, new, at)
    return buf


def schedule_body(mesh: Mesh, axis: str, schedule: LoweredSchedule,
                  use_pallas_add: bool = True
                  ) -> Callable[[jnp.ndarray], jnp.ndarray]:
    """The traceable body of :func:`run_schedule`, over ``mesh[axis]``.

    Maps position-major ``[n, n_chunks, *slab_shape(chunk_len,
    chunk_factor)]`` buffers (the device at axis position p holds
    logical rank ``rank_of[p]``'s chunk rows) to the same layout after
    the last round.  It depends only on the buffers' shape, so it can
    be lowered from a ``ShapeDtypeStruct``.
    """
    n = schedule.n
    if mesh.shape[axis] != n:
        raise ValueError(f"mesh axis {axis!r} has {mesh.shape[axis]} "
                         f"devices, schedule wants {n}")
    k = schedule.chunk_factor
    # static per-step tables, resolved once per schedule (memoised —
    # repeated calls on the same certified artifact skip the rebuild)
    tables, ops = schedule_tables(schedule)

    def per_device(rows):
        buf = rows[0]                              # [n_chunks, *slab]
        chunk_len = buf.shape[1] * buf.shape[2]
        if buf.shape[1:] != slab_shape(chunk_len, k):
            raise ValueError(
                f"chunk rows {buf.shape[1:]} are not the slab "
                f"{slab_shape(chunk_len, k)} of chunk_len {chunk_len}")
        me = jax.lax.axis_index(axis)
        for window in piece_windows(chunk_len, k):
            for rnd_tables, rnd_ops in zip(tables, ops):
                entry = buf                        # round-entry snapshot
                staged = []
                for eff_links, send, recv in rnd_tables:
                    if not eff_links:
                        staged.append(None)
                        continue
                    with jax.named_scope(TABLE_SCOPE):
                        payload = send_rows(entry, me, send, window,
                                            schedule.n_chunks)
                    with jax.named_scope(PERMUTE_SCOPE):
                        staged.append(
                            jax.lax.ppermute(payload, axis, eff_links))
                for (eff_links, send, recv), op, received in zip(
                        rnd_tables, rnd_ops, staged):
                    if received is None:
                        continue
                    buf = land_receives(buf, me, recv, window, op, received,
                                        schedule.n_chunks, use_pallas_add)
        return buf[None]

    return jax.shard_map(per_device, mesh=mesh, in_specs=(P(axis),),
                         out_specs=P(axis), check_vma=False)


@functools.lru_cache(maxsize=64)
def _jitted_body(mesh: Mesh, axis: str, schedule: LoweredSchedule,
                 use_pallas_add: bool):
    """:func:`schedule_body` compiled once per schedule: run eagerly, a
    shard_map dispatches every row read and write on its own."""
    return jax.jit(schedule_body(mesh, axis, schedule, use_pallas_add))


def run_schedule(
    x,
    mesh: Mesh,
    axis: str,
    schedule: LoweredSchedule,
    use_pallas_add: bool = True,
) -> jnp.ndarray:
    """Run ``schedule`` over ``mesh[axis]``; returns final rank buffers.

    ``x``: ``[n, D]`` rank-major inputs (see :func:`_initial_buffers`
    for D per init).  Returns ``[n, n_chunks, chunk_len]`` rank-major —
    row r is logical rank r's final chunk buffer, against which the
    declared postcondition can be checked
    (:func:`check_postcondition`).
    """
    body = _jitted_body(mesh, axis, schedule, use_pallas_add)
    buf0 = _initial_buffers(schedule, x)
    n, n_chunks, chunk_len = buf0.shape
    slab = slab_shape(chunk_len, schedule.chunk_factor)
    # device at axis position p plays logical rank rank_of[p]
    rank_of = np.asarray(schedule.rank_of, dtype=np.int64)
    out_pos = body(jnp.asarray(buf0[rank_of].reshape(n, n_chunks, *slab)))
    # back to rank space
    order = np.asarray(schedule.order, dtype=np.int64)
    return jnp.asarray(out_pos)[order].reshape(n, n_chunks, chunk_len)


def check_postcondition(schedule: LoweredSchedule, x,
                        out, atol: float = 1e-5) -> List[str]:
    """Numerically verify ``out`` satisfies the declared postcondition.

    ``x``/``out`` as in :func:`run_schedule`.  Returns human-readable
    mismatch descriptions (empty list = postcondition holds) — the
    end-to-end complement of the symbolic bisimulation proof.
    """
    n, n_chunks = schedule.n, schedule.n_chunks
    x = np.asarray(x, dtype=np.float64)
    out = np.asarray(out, dtype=np.float64)
    post = schedule.postcondition
    bad: List[str] = []

    def close(a, b) -> bool:
        return bool(np.allclose(a, b, atol=atol, rtol=1e-5))

    if post in ("allreduce", "reduce"):
        want = x.sum(axis=0).reshape(n_chunks, -1)   # replicated init
        if post == "allreduce":
            for r in range(n):
                if not close(out[r], want):
                    bad.append(f"rank {r}: allreduce result diverges")
        else:
            if not any(close(out[r], want) for r in range(n)):
                bad.append("no rank holds the fully-reduced vector")
    elif post == "reduce_scatter":
        want = x.sum(axis=0).reshape(n_chunks, -1)
        for r in range(n):
            if not close(out[r, r], want[r]):
                bad.append(f"rank {r}: chunk {r} not fully reduced")
    elif post == "all_gather":
        for r in range(n):
            for c in range(n_chunks):
                if not close(out[r, c], x[c]):
                    bad.append(f"rank {r}: chunk {c} not gathered")
    elif post == "all_to_all":
        piece = x.reshape(n, n, -1)                  # [src, dst, len]
        for s in range(n):
            for d in range(n):
                if not close(out[d, s * n + d], piece[s, d]):
                    bad.append(f"piece {s}→{d} undelivered")
    elif post != "none":
        bad.append(f"unknown postcondition {post!r}")
    return bad
